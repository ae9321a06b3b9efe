"""Scheduler and cache (rows from a tower): of the prompt rows admitted in the
window, the share a vision tower's rows fill and not the word table's
(``image_rows`` over ``prompt_len`` of the program's ``serving.admit`` spans;
the counter ``image_rows`` sums the same field over the engine's life). Nine
in ten where the traffic is scanned pages; None for a cell without images."""
from perfbench.layer_metrics import _vl


def read(run):
    seen = _vl.admissions(run)
    asked = sum(a[0] for a in seen)
    return sum(a[1] for a in seen) / asked if asked else None
