"""Serving, open loop: requests arrive on a schedule fixed by the traffic
file and the seed, whatever the engine does. One thread: before each
``engine.step()`` it submits every request whose due time has passed. A
ramp (``ramp_s``, set-up) fills the lanes before the window opens; the
requests DUE inside the window are the ones measured, each timed from when
it was due; tokens count only if delivered inside the window. After the
window closes the loop runs on, bounded by ``grace_s``, until every
measured request has its first token.

A request that falls due while a step runs is submitted when the step
returns, as the program's own server does it (``serving/api/server.py``
holds one lock around ``step()`` and ``submit()``): that wait is the
deployment's, and is inside a TTFT taken from the due time there as here.
What the GENERATOR adds is the rest of a request's lateness, from the later
of its due time and the last step's return to its submit (the loop's own
work and the submits queued before it): ``own_late_ms`` of a request.

In a ``--trace 1`` run the schedule's clock stands still while the profiler
starts and while it writes its trace out (2-3 s and more, with this thread
held): at 9.6 requests/s those seconds would queue some thirty requests,
the traced stretch would see full lanes where the cell runs them 0.6 busy,
and requests due near the window's end would outwait the grace."""

from __future__ import annotations

import time

from perfbench import harness, serving, traffic as traffic_gen


def lateness_ms(rec: dict) -> tuple:
    """``(late, own)`` of a submitted request's record: submit less due
    time, and the generator's own part of it (module docstring)."""
    return ((rec["submit_s"] - rec["due_s"]) * 1e3,
            (rec["submit_s"] - max(rec["due_s"], rec["free_s"])) * 1e3)


def replay(engine, pending, ramp: float, seconds: float, grace_s: float,
           profiler):
    """Offer ``pending`` (requests sorted by due time, from 0) to the
    engine in real time; the window is ``[ramp, ramp + seconds)`` after the
    start. Returns ``(clients, measured records, live-token samples,
    (window start, window end))``."""
    pending = list(reversed(pending))  # pop from the end
    clients = serving.Clients(engine)
    origin = time.perf_counter()
    start, end = origin + ramp, origin + ramp + seconds
    profiler.arm(start, seconds)
    measured, live = [], []
    free = origin  # when the last step returned: the thread could submit
    while True:
        now = time.perf_counter()
        while pending and origin + pending[-1].due_s <= now:
            request = pending.pop()
            rec = clients.submit(request, origin + request.due_s, free_s=free)
            if rec["due_s"] >= start:
                measured.append(rec)
        if now >= end:
            profiler.close()
            waiting = [r for r in measured if r["id"] is not None
                       and not r["stamps"]]
            if not waiting or now >= end + grace_s:
                break
        elif now >= start:
            if profiler.poll(now):
                # starting the profiler, and writing its trace out, holds
                # this thread for seconds: the schedule's clock stood still
                # meanwhile, so that what fell due then does not pile up
                # and the traced stretch sees the load the cell offers
                stalled = time.perf_counter() - now
                origin, end = origin + stalled, end + stalled
        if clients.open:
            engine.step()
            free = time.perf_counter()
            live.append((free, clients.live_tokens))
        elif pending:
            time.sleep(max(0.0, min(
                origin + pending[-1].due_s - time.perf_counter(), 0.001)))
        else:
            time.sleep(0.001)

    return clients, measured, live, (start, end)


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    device, clock, engine, reference, buckets, phases = serving.set_up(
        cell, seed, t_process)
    job = cell.traffic
    ramp = float(job["ramp_s"])
    pending = traffic_gen.open_loop_trace(
        job, seed, ramp + seconds, cell.config["model"]["vocab_size"], ramp)
    harness.log(f"trace {traffic_gen.trace_hash(pending)}: {len(pending)} "
                f"requests over {ramp + seconds:.0f} s; warmed {len(buckets)} "
                f"prefill buckets {buckets[0]}-{buckets[-1]}")
    profiler = harness.ProfilerWindow(trace, job["trace_s"])
    clients, measured, live, (start, end) = replay(
        engine, pending, ramp, seconds, job["grace_s"], profiler)
    failed = [r for r in measured if not r["stamps"]]
    checks = serving.serving_checks(engine, clients, clock, (start, end),
                                    reference, buckets, phases)
    checks["measured_without_token"] = len(failed)
    # a failed or refused request misses every limit: it enters the tail
    # as a very long wait
    requests = [{"id": r["id"], "due_s": r["due_s"],
                 "late_ms": lateness_ms(r)[0],
                 "own_late_ms": lateness_ms(r)[1],
                 "ttft_ms": ((r["stamps"][0] - r["due_s"]) * 1e3
                             if r["stamps"] else 1e9)} for r in measured]
    samples = {"token_s": clients.token_s, "requests": requests,
               "gaps": clients.gaps(start, end), "live_tokens": live,
               "lanes": cell.deploy["lanes"]}
    pct = harness.percentile
    ttft = [r["ttft_ms"] for r in requests]
    gap_ms = [ms for _, ms in samples["gaps"]]
    own = [r["own_late_ms"] for r in requests]
    harness.log(
        f"requests measured {len(measured)}; ttft ms "
        + " ".join(f"p{q} {pct(ttft, q):.1f}" for q in (25, 50, 75, 90))
        + "; gap ms "
        + " ".join(f"p{q} {pct(gap_ms, q):.2f}" for q in (50, 90, 95, 97, 99))
        + f" over {len(gap_ms)} gaps; tokens/s in the window "
        f"{sum(1 for t in clients.token_s if start <= t <= end) / seconds:.1f}"
        f"; generator late ms p50 "
        f"{pct([r['late_ms'] for r in requests], 50):.2f} (of it the "
        f"generator's own p50 {pct(own, 50):.3f} p99 {pct(own, 99):.3f}); "
        f"open at the end {len(clients.open)}")
    return harness.Run(
        cell=cell, device=device, setup_s=start - t_process,
        window=(start, end), attempted=len(measured),
        failed=len(failed) + checks["wrong_results"],
        correct=checks["correct"], checks=checks, samples=samples,
        spans=harness.program_spans(start), counters=serving.counters(engine),
        traced=profiler.traced, trace=profiler.reduce() if trace else None,
        peaks=harness.device_peaks(device, cell.tiny))
