"""Serving engine: median time the CALLER took between two steps: from the
end of one step's ``serving.observe`` (the last the engine does in
``step()``) to the start of the next step's first ``serving.snapshot`` (the
first it does), less the ``serving.submit`` spans in between, which are the
engine's. What is left is the benchmark's own loop a step (its clients'
polling, its clock), the closed loop's ``gen_late_p99_ms``: how far a
throughput is the harness's. Over pairs of consecutive steps inside the
window and outside the traced stretch. None from a program without
``serving.observe`` spans, and in a rehearsal."""
import bisect

from perfbench import harness


def read(run):
    if run.cell.tiny:
        return None
    observes = run.spans_named("serving.observe", untraced_only=True)
    begins = sorted(s.start_s for s in run.spans_named("serving.snapshot")
                    if s.parent is None)
    submits = sorted(run.spans_named("serving.submit"),
                     key=lambda s: s.start_s)
    starts = [s.start_s for s in submits]
    caller = []
    for observe in observes:
        i = bisect.bisect_left(begins, observe.end_s)
        if i == len(begins) or begins[i] > run.window[1]:
            continue
        begin = begins[i]
        if run.traced and observe.end_s < run.traced[0] <= begin:
            continue  # the profiler opened between the two steps
        ours = sum(s.duration_s for s in submits[
            bisect.bisect_left(starts, observe.end_s):
            bisect.bisect_left(starts, begin)])
        caller.append((begin - observe.end_s - ours) * 1e3)
    return harness.percentile(caller, 50) if caller else None
