"""Scheduler and cache: prompt tokens served from the prefix trie (keys and
values from shared pages, convolution state from their tails) over prompt
tokens admitted, over the admissions of the window (``matched`` over
``prompt_len`` of the program's ``serving.admit`` spans). Near 0 the traffic
does not work the mechanism."""
from perfbench.layer_metrics import _lfm2


def read(run):
    seen = _lfm2.admissions(run)
    asked = sum(a[0] for a in seen)
    return sum(a[1] for a in seen) / asked if asked else None
