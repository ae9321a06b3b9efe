"""Kernels (a chunk's grouped attention under the indexer's selection): share of device self time in the kernel ``fleetx_gqa_sparse_prefill``, which attends under an int8 mask over EVERY key block up to the chunk's last row, chosen from or not. Grows with the context: 33 blocks at 33k rows where the chosen rows would fill two."""
from perfbench.layer_metrics import _vl


def read(run):
    return _vl.share(run, "kernel")
