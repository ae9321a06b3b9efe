"""Model: share of device self time in attention outside the Pallas kernels:
projections, layer norm before them, mask, dense-path softmax. From the
traced run's ``.xplane.pb`` by ``_parts.py``; None without a trace."""
from perfbench.layer_metrics import _parts


def read(run):
    return _parts.read_share(run, "attn")
