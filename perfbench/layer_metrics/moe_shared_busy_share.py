"""Model: share of device self time in the shared expert (scope
``moe_shared``: a gated MLP of one expert's width that every token meets,
outside ``moe_experts``)."""
from perfbench.layer_metrics import _mla


def read(run):
    return _mla.share(run, "moe_shared")
