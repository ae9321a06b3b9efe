"""Scheduler and cache: the rows the decode calls of one EVA layer attended
over, exact and pooled, over the positions their lanes stood at (what full
attention would have read), over the ticks of the window (``eva_window_rows +
eva_summary_rows`` over ``eva_positions`` of the program's
``serving.decode`` spans). Near 1 the traffic does not work the mechanism."""
from perfbench.layer_metrics import _eva


def read(run):
    rows = _eva.decode_rows(run)
    positions = sum(r[2] for r in rows)
    return sum(r[0] + r[1] for r in rows) / positions if positions else None
