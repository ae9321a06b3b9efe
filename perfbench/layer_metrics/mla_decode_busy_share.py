"""Kernels: share of device self time in the absorbed decode kernel
``fleetx_mla_decode_paged`` (every layer of every traced tick)."""
from perfbench.layer_metrics import _mla


def read(run):
    return _mla.share(run, "kernel")
