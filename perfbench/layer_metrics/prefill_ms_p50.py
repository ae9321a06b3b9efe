"""Serving engine: median device time of one prefill program
(``jit_prefill`` on the trace's program line). The program's
``serving.prefill`` span is not used: it ends when the call is dispatched,
before the device has run it."""
from perfbench import harness


def read(run):
    if not run.trace:
        return None
    seconds = run.trace["module_s"].get("jit_prefill")
    return harness.percentile(seconds, 50) * 1e3 if seconds else None
