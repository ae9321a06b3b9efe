"""Scheduler and cache: admissions of the window that resumed the
convolution state from a matched page's tail over all admissions of the
window (``state_resumed`` of the program's ``serving.admit`` spans)."""
from perfbench.layer_metrics import _lfm2


def read(run):
    seen = _lfm2.admissions(run)
    return sum(bool(a[2]) for a in seen) / len(seen) if seen else None
