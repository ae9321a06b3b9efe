"""Kernels (grouped expert matmuls): share of the device's busy (self)
time under the scope ``moe_experts`` (``fleetx_moe_gate_up``,
``fleetx_moe_down``). A sub-part of ``mlp`` (``_parts.RULES`` books the
module path ``layer/moe_mlp`` there), so no member of ``_parts.PARTS``."""
from perfbench.layer_metrics import _moe


def read(run):
    return _moe.read_share(run, "moe_experts")
