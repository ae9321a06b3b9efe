"""Scheduler and cache, client side: 50th percentile, over the requests DUE
inside the window (and before the profiler disturbed the run), of first
``on_token`` minus the time the request was due (a failed or refused
request enters as 1e9 ms). With some 64 requests in a window and 16 lanes
held about 7.5 s each, whether the lanes ran out during the window decides
it: it swings too widely from seed to seed to carry a bound (PERF.md,
Findings, PR 22)."""
from perfbench import harness


def read(run):
    ttft = [r["ttft_ms"] for r in run.samples.get("requests", ())
            if run.before_trace(r["due_s"])]
    return harness.percentile(ttft, 50) if ttft else None
