"""Kernels (grouped expert matmuls): the least time the chip could take
for the expert layers of the traced programs (the larger of bytes over
peak bytes/s and operations over peak FLOP/s, by
``flops_moe.expert_layer_cost``) over the device time under the scope
``moe_experts``, in percent. What was really routed comes from the
program's counters: per layer and program, the pairs routed and the
distinct experts read, for one-token programs (ticks) and longer ones
(prefills) apart, as means over the run; how many of each program the
trace holds comes from its program line. Memory-bound at decode shapes: an
expert is read for some four rows."""
from perfbench import flops, flops_moe
from perfbench.layer_metrics import _moe

_PROGRAMS = (("tick", "jit__decode_fn"), ("prefill", "jit_prefill"))


def read(run):
    took = _moe.traced_seconds(run).get("moe_experts")
    if not took or run.peaks is None:
        return None
    model, count = run.cell.config["model"], run.counters
    layers = count.get("moe_layers")
    if not layers:
        return None
    least = 0.0
    for kind, program in _PROGRAMS:
        calls = count.get(f"moe_{kind}_layer_calls")
        programs = len(run.trace["module_s"].get(program, ()))
        if not calls or not programs:
            continue
        ops, bytes_ = flops_moe.expert_layer_cost(
            count[f"moe_{kind}_pairs"] / calls,
            count[f"moe_{kind}_experts_read"],
            model["hidden_size"], model["ffn_hidden_size"])
        least += (programs * layers
                  * flops.roofline_seconds(ops, bytes_, run.peaks)[0])
    return 100.0 * least / took if least else None
