"""Scheduler and cache, client side: 90th percentile, over the requests DUE
inside the window (and before the profiler disturbed the run), of first
``on_token`` minus the time the request was due (a failed or refused
request enters as 1e9 ms). A record beside ``ttft_p50_ms``, which is an
end-to-end metric of the chat cell since PR 37: with 384 requests a window
the 90th percentile still spreads by 4-13% over a set of six seeds
(PERF.md, Findings, PR 37), where the median spreads by 2-3%."""
from perfbench import harness


def read(run):
    ttft = [r["ttft_ms"] for r in run.samples.get("requests", ())
            if run.before_trace(r["due_s"])]
    return harness.percentile(ttft, 90) if ttft else None
