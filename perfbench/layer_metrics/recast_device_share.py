"""Model: share of device self time spent recasting float32 weights to bf16
(the compiler's ``convert``s of whole weight stacks, once per program).
From the traced run's ``.xplane.pb`` by ``_parts.py``; None without a
trace."""
from perfbench.layer_metrics import _parts


def read(run):
    return _parts.read_share(run, "recast")
