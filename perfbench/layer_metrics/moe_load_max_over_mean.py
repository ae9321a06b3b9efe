"""Model (expert routing): the largest expert's load over the mean load,
averaged over the layers and decode ticks of the run, from the counter the
program keeps on the device (``moe_tick_load_max_over_mean`` of
``ServingMetrics.snapshot()``). 1 is a perfectly even router."""


def read(run):
    value = run.counters.get("moe_tick_load_max_over_mean")
    return float(value) if value else None
