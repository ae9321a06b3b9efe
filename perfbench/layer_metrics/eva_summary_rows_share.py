"""Scheduler and cache: the pooled rows over ALL the rows the decode calls of
one EVA layer attended over, over the ticks of the window
(``eva_summary_rows`` over ``eva_summary_rows + eva_window_rows`` of the
program's ``serving.decode`` spans). Near 0 the traffic stays inside one
window and the summary class does nothing."""
from perfbench.layer_metrics import _eva


def read(run):
    rows = _eva.decode_rows(run)
    both = sum(r[0] + r[1] for r in rows)
    return sum(r[1] for r in rows) / both if both else None
