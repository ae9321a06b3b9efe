"""Scheduler and cache: per admission, the device time with NO program
running between the start of its ``serving.admit`` span (of its final
``serving.prefill_chunk`` in a chunked admission) and the start of the
first decode program dispatched after its lane install: what the
admission's host work, its token's way back and the install cost the
chip. Admissions of one ``step()`` share the decode that ends them, so
each also counts the idle time of those after it. From the joined timeline
(``_timeline.py``); the median over the admissions in the trace."""
from perfbench.layer_metrics import _timeline


def read(run):
    return _timeline.median(
        run, "admit_idle_ms_p50",
        lambda t: [a["idle_ms"] for a in t.admissions()])
