"""Serving engine: per admission, from the end of its last
``serving.prefill`` span (the program is dispatched) to the START of that
program on the device: the wait behind the decode tick in flight and, in
a chunked cell, behind that tick's chunk. Negative where the device was
idle and began the program before the dispatching call returned. With
``prefill_ms_p50`` and ``first_token_return_ms_p50`` it makes up the time
from the dispatch to the first token on the host. From the joined timeline
(``_timeline.py``); the median over the admissions in the trace."""
from perfbench.layer_metrics import _timeline


def read(run):
    return _timeline.median(
        run, "first_token_queue_ms_p50",
        lambda t: [a["queue_ms"] for a in t.admissions()])
