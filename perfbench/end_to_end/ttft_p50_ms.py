"""50th percentile, over ALL requests due inside the window, of first
``on_token`` minus the time the request was DUE (a failed or refused
request enters as 1e9 ms): what a chat user waits for the first token,
the wait for the step in progress included (the program's own server
takes a request in between two steps as well)."""
from perfbench import harness


def read(run):
    ttft = [r["ttft_ms"] for r in run.samples.get("requests", ())]
    return harness.percentile(ttft, 50) if ttft else None
