"""Trainer: share of device self time in the layer scan's own traffic:
stacking the activations saved for the backward pass, slicing them back,
copies of the carry. From the traced run's ``.xplane.pb`` by ``_parts.py``;
None without a trace."""
from perfbench.layer_metrics import _parts


def read(run):
    return _parts.read_share(run, "carry")
