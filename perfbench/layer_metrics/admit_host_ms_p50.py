"""Scheduler and cache: median host time of one admission: the
``serving.admit`` span less its ``serving.first_token`` child (the wait
for the prefill program), i.e. claiming pages, matching the prefix, the
uploads before the dispatch and the lane install after it. Admissions
outside the traced stretch; None from a program without
``serving.first_token`` spans."""
from perfbench import harness


def read(run):
    waits = {}
    for s in run.spans_named("serving.first_token"):
        waits.setdefault(s.attrs.get("request"), []).append(s)
    host = []
    for admit in run.spans_named("serving.admit", untraced_only=True):
        inside = [w for w in waits.get(admit.attrs.get("request"), ())
                  if admit.start_s <= w.start_s and w.end_s <= admit.end_s]
        if inside and admit.end_s <= run.window[1]:
            host.append((admit.duration_s
                         - sum(w.duration_s for w in inside)) * 1e3)
    return harness.percentile(host, 50) if host else None
