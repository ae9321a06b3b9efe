"""Scheduler (routing): of a tick's rows, the share that chose at least one
expert of the ONE ROUTER GROUP this program holds, the tokens a deployment
would send this chip: the program's counter ``moe_tick_group_tokens``
(counted on the device over the whole run, free lanes among the rows, as in
``moe_tick_pairs``) over the rows its ticks' layer calls routed
(``moe_tick_layer_calls`` x lanes). A token's experts lie in 4 of 8 groups:
about 0.5 x the chance that one of its 8 falls in a group that stays, were
the scores uniform. None for a share that is no whole group (the program
then counts nothing of the kind)."""


def read(run):
    tokens = run.counters.get("moe_tick_group_tokens")
    calls = run.counters.get("moe_tick_layer_calls")
    if tokens is None or not calls:
        return None
    return tokens / (calls * run.cell.deploy["lanes"])
