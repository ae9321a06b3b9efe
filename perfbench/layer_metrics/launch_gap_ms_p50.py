"""Serving engine: median launch gap on the device: over consecutive
programs of the device's program line whose second was already dispatched
(its dispatch span had ended) when the first ended, the start of the
second less the end of the first. It is all the idle time a decode-only
tick has while one tick is kept in flight. From the joined timeline
(``_timeline.py``): device times, the dispatch spans only say which pairs
count."""
from perfbench.layer_metrics import _timeline


def read(run):
    return _timeline.median(run, "launch_gap_ms_p50",
                            lambda t: t.launch_gaps_ms())
