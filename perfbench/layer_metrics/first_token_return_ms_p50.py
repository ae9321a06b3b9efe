"""Serving engine: per admission, from the END of its prefill program on
the device to the end of the ``serving.first_token`` span whose ``reads``
names that program: the result's way back to the host (the copy, the
notification and the wake-up of the waiting thread, which the trace has
no events for). From the joined timeline (``_timeline.py``), whose
``slack_wait_us`` is the least such time it saw for any program; the
median over the admissions in the trace."""
from perfbench.layer_metrics import _timeline


def read(run):
    return _timeline.median(
        run, "first_token_return_ms_p50",
        lambda t: [a["return_ms"] for a in t.admissions()])
