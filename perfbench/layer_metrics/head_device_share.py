"""Model: share of device self time in the embedding, the final norm, the
logits and the sampler or the loss. From the traced run's ``.xplane.pb`` by
``_parts.py``; None without a trace."""
from perfbench.layer_metrics import _parts


def read(run):
    return _parts.read_share(run, "head")
