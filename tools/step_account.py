"""Run one cell of the benchmark and fold the step's own account.

    python3 tools/step_account.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ``perfbench/run.py`` unchanged (its result line is printed as ever)
and then prints one more line, ``step_account {...}``, folded from the
run's spans and counters (docs/OBSERVABILITY.md "What a step says of
itself"): the window's lane-steps by state, with the waiting ones by
cause, as shares of ``lanes x ticks``; the steps that took their second
prefill-shaped call; whether the five lane fields added up
to the lanes on EVERY ``serving.decode`` span and ``waiting_on`` stood
exactly where a lane waited; the engine's ``lane_steps_*`` counters against
the spans; what the window's steps carried (``serving.tick``); and the
median and total of ``serving.submit`` and ``serving.observe``, which is
what the account's own span costs a step. The line is also written to
``chiprun_out/step_account/<cell>.<seed>.<trace>.json``. PERF.md section 5's
table of the closed-loop cells was made with it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FIELDS = ("lanes_finishing", "lanes_prefilling", "lanes_waiting",
          "lanes_unasked")


def fold(run) -> dict:
    """The account of ``run`` (a ``perfbench.harness.Run``)."""
    from fleetx_tpu.obs.tracing import get_recorder
    from fleetx_tpu.serving.metrics import LANE_STATES, lane_steps

    lanes = run.samples["lanes"]
    inside = lambda s: s.end_s <= run.window[1]  # noqa: E731
    ticks = [s.attrs for s in run.spans_named("serving.decode")
             if inside(s) and "lanes_waiting" in s.attrs]
    steps = dict.fromkeys(LANE_STATES, 0)
    broken = 0
    for at in ticks:
        if ("waiting_on" in at) != (at["lanes_waiting"] > 0):
            broken += 1
            continue
        for state, n in lane_steps(at["batch"], at).items():
            steps[state] += n
        broken += at["batch"] + sum(at[f] for f in FIELDS) != lanes
    total = lanes * len(ticks)
    counted = {state: run.counters.get("lane_steps_" + state)
               for state in LANE_STATES}
    carried = [s for s in run.spans_named("serving.tick")
               if inside(s) and "admitted" in s.attrs]
    out = {
        "cell": run.cell.name, "lanes": lanes, "ticks_dispatched": len(ticks),
        "lane_steps": steps,
        "lane_shares": {k: v / total for k, v in steps.items()} if total
        else {},
        "spans_out_of_balance": broken,
        "counters": counted,
        # the counters run from the engine's first tick to the moment the
        # driver took them with the run's spans: against every dispatch
        # until then, the warm-up's (before the window) among them
        "counters_over_lanes": (sum(v or 0 for v in counted.values())
                                / lanes),
        "ticks_dispatched_in_all": sum(
            1 for s in ([s for s in get_recorder().spans()
                         if s.start_s < run.window[0]] + run.spans)
            if s.name == "serving.decode" and "lanes_waiting" in s.attrs),
        "steps": len(carried),
        "steps_with_prefill": sum(
            1 for s in carried if s.attrs["admitted"] + s.attrs["chunked"]
            + s.attrs["tower"]),
        # the steps of an engine with a chunk size that took their second
        # prefill-shaped call, and the engine's own count of them (from its
        # first tick on; None before PR 70)
        "steps_with_two_calls": sum(
            1 for s in carried if run.cell.deploy.get("prefill_chunk")
            and s.attrs["chunked"] + s.attrs["admitted"] > 1),
        "second_chunks": run.counters.get("second_chunks"),
        "carried": {key: sum(s.attrs[key] for s in carried)
                    for key in ("admitted", "chunked", "tower", "decoded",
                                "prefill_rows")},
        "window_s": run.window[1] - run.window[0],
    }
    for name in ("serving.submit", "serving.observe"):
        spans = [s.duration_s for s in run.spans_named(name) if inside(s)]
        out[name] = {"spans": len(spans),
                     "median_us": statistics.median(spans) * 1e6
                     if spans else None,
                     "total_s": sum(spans)}
    if run.trace:
        out["idle_gaps"] = run.trace["idle_gaps"]
        out["idle_share"] = run.trace["idle_share"]
        out["trace_window_s"] = run.trace["window_s"]
    return out


def main() -> int:
    from perfbench import harness, run as bench

    held = {}
    by_name = harness.by_name

    def keeping(kind, name):
        module = by_name(kind, name)
        if kind != "drivers":
            return module

        class Driver:
            @staticmethod
            def run(*args, **kwargs):
                held["run"] = module.run(*args, **kwargs)
                return held["run"]
        return Driver

    harness.by_name = keeping
    code = bench.main()
    run = held["run"]
    if "lanes" not in run.samples:
        return code
    account = fold(run)
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--seed")
    ap.add_argument("--trace", default="0")
    given, _ = ap.parse_known_args()
    out = os.path.join(ROOT, "chiprun_out", "step_account")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "{}.{}.{}.json".format(
            run.cell.name, given.seed, given.trace)), "w") as f:
        json.dump(account, f)
    print("step_account " + json.dumps(account), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
