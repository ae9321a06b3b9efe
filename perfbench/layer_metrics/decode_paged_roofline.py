"""Kernels (paged decode attention): the least time the chip could take to
read the live cache rows of the traced ticks (memory-bound: bytes over peak
bytes/s, by ``flops.paged_decode_call_cost``) over the time the
``fleetx_decode`` calls took, in percent. Live rows per tick are the
driver's count of cached positions of the open requests, averaged over the
traced stretch; one call per layer per tick."""
from perfbench import flops


def read(run):
    if not run.trace or run.peaks is None or not run.traced:
        return None
    calls = run.trace["family_calls"].get("decode")
    a, b = run.traced
    live = [n for t, n in run.samples.get("live_tokens", ()) if a <= t <= b]
    if not calls or not live:
        return None
    model = run.cell.config["model"]
    heads = model["num_attention_heads"]
    ops, bytes_ = flops.paged_decode_call_cost(
        sum(live) / len(live), heads, model["hidden_size"] // heads,
        run.samples["lanes"])
    least = flops.roofline_seconds(ops, bytes_, run.peaks)[0] * calls
    return 100.0 * least / run.trace["family_s"]["decode"]
