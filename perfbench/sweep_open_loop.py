"""Find the knee of an open-loop cell once, on the chip: the highest rate
the engine sustains without a growing queue.

    python3 perfbench/sweep_open_loop.py --workload <cell> --rates 1.2,1.6,2.0 --seconds 30 --seed 1

One process and one engine, so the rates share one compilation; between
rates the engine drains. For each rate it prints the requests measured,
tokens/s, TTFT and gap percentiles, the share of requests whose first token
came within ``--ttft-limit-ms``, the backlog (requests submitted and
not finished) at the middle and at the end of the window: a backlog that
grows through the window is past the knee; and, by the cell's own readers,
the lanes' occupancy and the share of gaps that held another request's
admission, which says where the gaps' 95th percentile lies (PERF.md
section 2). Not part of a check: the rate it
finds is written into the traffic file as a number.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("FLEETX_OBS_SPANS", "1048576")

from perfbench import harness, serving, traffic as traffic_gen  # noqa: E402
from perfbench.drivers.serve_open_loop import lateness_ms, replay  # noqa: E402
from perfbench.layer_metrics import admit_gap_share, lane_occupancy  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--ttft-limit-ms", type=float, default=1000.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    cell = harness.load_cell(args.workload, tiny=args.tiny)
    harness.own_the_chip(cell.chips, cell.tiny)

    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    engine = serving.build_engine(
        cell, *serving.build_model(cell, args.seed))
    serving.warm_up(engine, cell, args.seed)
    vocab = cell.config["model"]["vocab_size"]
    ramp = float(cell.traffic["ramp_s"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        job = copy.deepcopy(cell.traffic)
        job["arrivals"]["rate_per_s"] = rate
        pending = traffic_gen.open_loop_trace(
            job, args.seed + i, ramp + args.seconds, vocab, ramp)
        profiler = harness.ProfilerWindow(False, 0.0)
        clients, measured, live, (start, end) = replay(
            engine, pending, ramp, args.seconds, 0.0, profiler)

        def backlog(at):
            return sum(1 for r in clients.records.values()
                       if r["submit_s"] <= at
                       and (not r["stamps"] or r["stamps"][-1] > at
                            or r["id"] in clients.open))

        ttft = [(r["stamps"][0] - r["due_s"]) * 1e3 if r["stamps"] else 1e9
                for r in measured]
        stamped = clients.gaps(start, end)
        gaps = [ms for _, ms in stamped]
        run = harness.Run(  # what the cell's own readers take
            cell=cell, device={}, setup_s=0.0, window=(start, end),
            attempted=len(measured), failed=0, correct=False, checks={},
            samples={"gaps": stamped, "lanes": cell.deploy["lanes"]},
            spans=harness.program_spans(start), counters={})
        tokens = sum(1 for t in clients.token_s if start <= t <= end)
        print("sweep " + json.dumps({
            "rate_per_s": rate, "measured": len(measured),
            "tokens_per_s": tokens / args.seconds,
            "ttft_ms_p50": harness.percentile(ttft, 50),
            "ttft_ms_p90": harness.percentile(ttft, 90),
            "ttft_within_limit": sum(t <= args.ttft_limit_ms for t in ttft)
            / max(len(ttft), 1),
            **{f"gap_ms_p{q}": harness.percentile(gaps, q)
               for q in (50, 90, 95, 97, 99)},
            "gen_late_ms_p50": harness.percentile(
                [lateness_ms(r)[0] for r in measured], 50),
            "gen_own_late_ms_p50": harness.percentile(
                [lateness_ms(r)[1] for r in measured], 50),
            "admit_gap_share": admit_gap_share.read(run),
            "lane_occupancy": lane_occupancy.read(run),
            "backlog_mid": backlog((start + end) / 2),
            "backlog_end": backlog(end),
            "lanes": cell.deploy["lanes"]}), flush=True)
        t0 = time.perf_counter()
        engine.drain()
        harness.log(f"drained in {time.perf_counter() - t0:.1f} s")
    harness.log(f"memory_peak_bytes {harness.memory_peak_bytes(cell.chips)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
