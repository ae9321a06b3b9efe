"""Scheduler and cache: the cache rows the window layers' decode calls read
over the rows full attention would have read there, over the ticks of the
window (``window_rows`` over ``full_rows`` of the program's
``serving.decode`` spans). Near 1 the traffic does not work the window."""
from perfbench.layer_metrics import _swa


def read(run):
    rows = _swa.decode_rows(run)
    full = sum(r[0] for r in rows)
    return sum(r[1] for r in rows) / full if full else None
