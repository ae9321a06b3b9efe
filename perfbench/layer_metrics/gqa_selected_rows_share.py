"""Scheduler and cache (the indexer's pruning over a cache with heads): of
the cached rows behind the queries of the traced ticks, the share they
attended over: ``selected_rows`` over ``index_rows`` of the program's
``serving.decode`` spans (``fleetx_tpu/serving/engine.py`` counts both from
the lanes' lengths: a lane with ``n`` rows scores ``n`` index keys and
selects ``min(n, index_topk)``), as ``dsa_selected_rows_share`` reads a
latent pool's. 2,048 over the mean cached rows where every lane stands past
``index_topk``; 1 where the traffic never reaches it and the mechanism is
idle."""
from perfbench.layer_metrics import _dsa


def read(run):
    inside = run.traced or run.window
    selected = _dsa.span_field(run, ("serving.decode",), "selected_rows",
                               inside)
    behind = _dsa.span_field(run, ("serving.decode",), "index_rows", inside)
    if not selected or not sum(behind):
        return None
    return sum(selected) / sum(behind)
