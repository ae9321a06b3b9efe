"""Model (routing): of the (token, expert) pairs the ticks' routers chose,
the share whose expert is ZERO-COMPUTE (it returns its input, weighed: no
weights read, no row of the grouped matmuls): the program's counter
``moe_tick_zero_pairs``, counted on the device over the whole run, over all
the pairs the ticks routed (layer calls x lanes x ``top_k``). Were routing
even it would be zero / (routed + zero) outputs (256 / 768 = 0.33); 0 would
mean the mechanism never engages. None for a program without the counter (a
parent commit's, another configuration's)."""


def read(run):
    calls = run.counters.get("moe_tick_layer_calls")
    zero = run.counters.get("moe_tick_zero_pairs")
    if not calls or zero is None:
        return None
    model = run.cell.config["model"]
    return zero / (calls * run.cell.deploy["lanes"] * model["top_k"])
