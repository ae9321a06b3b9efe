"""Device: share of device self time that no rule of ``_parts.RULES`` names:
what the tracing still cannot attribute. From the traced run's
``.xplane.pb`` by ``_parts.py``; None without a trace."""
from perfbench.layer_metrics import _parts


def read(run):
    return _parts.read_share(run, "unscoped")
