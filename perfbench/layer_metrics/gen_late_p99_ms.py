"""Load generator: 99th percentile of actual submit minus due time (over
the requests due before the profiler disturbed the run). One thread both
submits and steps the engine, so a request waits out the ``engine.step()``
it arrives in, which can hold several admissions. TTFT runs from the due
time, so lateness flatters nothing: it says how coarse the generator's
clock is."""
from perfbench import harness


def read(run):
    late = [r["late_ms"] for r in run.samples.get("requests", ())
            if run.before_trace(r["due_s"])]
    return harness.percentile(late, 99) if late else None
