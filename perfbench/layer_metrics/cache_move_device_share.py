"""Scheduler and cache: share of device self time spent moving the KV cache:
the write of new rows, the layer scan's slice and update of each layer's
pool, and copies of the whole pool. From the traced run's ``.xplane.pb`` by
``_parts.py``; None without a trace."""
from perfbench.layer_metrics import _parts


def read(run):
    return _parts.read_share(run, "cache_move")
