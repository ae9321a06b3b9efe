"""Scheduler and cache: median wait from the time a measured request was
due to the start of its ``serving.admit`` span (requests due before the
profiler disturbed the run)."""
from perfbench import harness


def read(run):
    due = {r["id"]: r["due_s"] for r in run.samples.get("requests", ())
           if r["id"] is not None and run.before_trace(r["due_s"])}
    waits = [(s.start_s - due[s.attrs["request"]]) * 1e3
             for s in run.spans_named("serving.admit")
             if s.attrs.get("request") in due]
    return harness.percentile(waits, 50) if waits else None
