"""Scheduler (routing): of the (token, expert) pairs a tick's routers
choose, the share whose expert this program HOLDS: the pairs laid out here
a layer call (the program's counters ``moe_tick_pairs`` over
``moe_tick_layer_calls``, counted on the device over the whole run) over
the pairs a layer call routes (``lanes x top_k``). Were routing even it
would be held / routed (12 / 192 = 0.0625); the group limit makes it lumpy
(a token's 8 experts lie in 4 of 8 groups, and this program holds half of
one group)."""


def read(run):
    calls = run.counters.get("moe_tick_layer_calls")
    model = run.cell.config["model"]
    if not calls or "num_routed_experts" not in model:
        return None
    return (run.counters["moe_tick_pairs"] / calls
            / (run.cell.deploy["lanes"] * model["top_k"]))
