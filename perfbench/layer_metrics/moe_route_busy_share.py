"""Model (expert routing): share of the device's busy (self) time under
the scope ``moe_route``: router, softmax, top-k, row layout, gather,
weighted sum. A sub-part of ``mlp``, like ``moe_experts_busy_share``."""
from perfbench.layer_metrics import _moe


def read(run):
    return _moe.read_share(run, "moe_route")
