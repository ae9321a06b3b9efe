"""Serving, closed loop: ``clients`` callers, each sending its next request
when its last one has returned, so a slower engine is offered less. Each
client's sequence of requests is a function of the seed and the client's
index. The first round, one request per client, is warm-up (set-up): the
window opens when the last of them has returned, with every client by then
somewhere in its later requests. Tokens count if delivered inside the
window; nothing is drained after it."""

from __future__ import annotations

import time

from perfbench import harness, serving, traffic as traffic_gen


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    device, clock, engine, reference, buckets, phases = serving.set_up(
        cell, seed, t_process)
    job = cell.traffic
    vocab = cell.config["model"]["vocab_size"]
    streams = [traffic_gen.client_stream(job, seed, c, vocab)
               for c in range(job["clients"])]
    harness.log(f"{len(streams)} clients; warmed {len(buckets)} prefill "
                f"buckets {buckets[0]}-{buckets[-1]}")
    clients = serving.Clients(engine)
    profiler = harness.ProfilerWindow(trace, job["trace_s"])
    holding = {}                      # client -> its open record
    first_round = set()
    start = end = None
    live = []
    while True:
        now = time.perf_counter()
        for c, stream in enumerate(streams):
            rec = holding.get(c)
            if rec is None or (rec["id"] not in clients.open):
                holding[c] = clients.submit(next(stream), now, client=c)
                if rec is None and holding[c]["id"] is not None:
                    first_round.add(holding[c]["id"])
        if start is None and not (first_round & clients.open):
            start, end = now, now + seconds
            profiler.arm(start, seconds)
        elif start is not None:
            if now >= end:
                profiler.close()
                break
            profiler.poll(now)
        engine.step()
        live.append((time.perf_counter(), clients.live_tokens))

    inside = [r for r in clients.records.values()
              if start <= r["submit_s"] <= end]
    checks = serving.serving_checks(engine, clients, clock, (start, end),
                                    reference, buckets, phases)
    done = [r for r in inside if r["id"] not in clients.open]
    ttft = [(r["stamps"][0] - r["submit_s"]) * 1e3 for r in inside
            if r["stamps"]]
    samples = {
        "token_s": clients.token_s,
        "gaps": clients.gaps(start, end),
        "closed_ttft_ms": ttft,
        "live_tokens": live,
        "lanes": cell.deploy["lanes"],
        "requests_done": len(done),
        "prompt_tokens_done": sum(len(r["request"].prompt) for r in done),
    }
    harness.log(f"requests submitted in the window {len(inside)}, returned "
                f"{len(done)} ({len(done) / seconds:.2f}/s); prompt tokens "
                f"prefilled/s {samples['prompt_tokens_done'] / seconds:.0f}; "
                f"closed-loop ttft ms p50 {harness.percentile(ttft, 50):.1f}")
    return harness.Run(
        cell=cell, device=device, setup_s=start - t_process,
        window=(start, end), attempted=len(inside),
        failed=len(clients.refused) + checks["wrong_results"],
        correct=checks["correct"], checks=checks, samples=samples,
        spans=harness.program_spans(start), counters=serving.counters(engine),
        traced=profiler.traced, trace=profiler.reduce() if trace else None,
        peaks=harness.device_peaks(device, cell.tiny))
