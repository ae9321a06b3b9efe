"""Scheduler and cache (the trie's skip of the tower): of the images of the
prompts admitted in the window, the share that lay wholly inside the prefix
trie's match and were neither encoded nor prefilled (``images_skipped`` over
``images`` of the program's ``serving.admit`` spans; the counters
``images_skipped`` and ``images_encoded`` sum the two sides over the engine's
life). Two in three where every session is asked three questions; near 0 the
traffic does not work the mechanism."""
from perfbench.layer_metrics import _vl


def read(run):
    seen = _vl.admissions(run)
    asked = sum(a[2] for a in seen)
    return sum(a[3] for a in seen) / asked if asked else None
