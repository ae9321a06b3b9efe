"""Trainer: share of device self time in gradient clipping, the optimizer
update and the sentry's select. From the traced run's ``.xplane.pb`` by
``_parts.py``; None without a trace."""
from perfbench.layer_metrics import _parts


def read(run):
    return _parts.read_share(run, "update")
