"""Run one cell of BENCHMARK.json once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic mix and deployment by name, hands
them to the driver the traffic file names (``perfbench/drivers/<name>.py``)
and prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` when traced). ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics, each computed by the reader
``perfbench/end_to_end/<metric>.py`` or ``perfbench/layer_metrics/
<metric>.py``; a reader that finds nothing to read returns None and its
metric is left out. Without a TPU holding the chips the cell asks for, the
run ends non-zero and prints no result. ``--tiny`` rehearses the control
flow at toy sizes on any backend: it reports ``"correct": false`` and no
metric.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# the program's span ring holds 4,096 spans by default, which a serving
# window overflows: size it for this process before the program is imported
os.environ.setdefault("FLEETX_OBS_SPANS", "1048576")

from perfbench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes: no metric is reported")
    args = ap.parse_args()

    cell = harness.load_cell(args.workload, tiny=args.tiny)
    driver = harness.by_name("drivers", cell.traffic["driver"])
    run = driver.run(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_process=T_PROCESS)

    def read(kind, entries):
        out = {}
        for entry in entries:
            value = harness.by_name(kind, entry["name"]).read(run)
            if value is not None:
                out[entry["name"]] = {"value": value, "unit": entry["unit"]}
        return out

    # both kinds go on an earlier line either way (a traced run's
    # end-to-end numbers against an untraced run's are the tracing overhead)
    end_to_end = read("end_to_end", cell.end_to_end)
    per_layer = read("layer_metrics", cell.per_layer)
    if not args.tiny:  # a rehearsal's numbers are of the CPU: never shown
        harness.log(f"end_to_end (trace={args.trace}) " + json.dumps(end_to_end))
        harness.log(f"per_layer (trace={args.trace}) " + json.dumps(per_layer))
    metrics = per_layer if args.trace else end_to_end
    device = dict(run.device,
                  memory_peak_bytes=harness.memory_peak_bytes(cell.chips))
    result = {"correct": bool(run.correct) and not args.tiny,
              "attempted": run.attempted, "failed": run.failed,
              "metrics": {} if args.tiny else metrics, "device": device}
    if args.tiny:
        result["rehearsal"] = sorted(metrics)
    if args.trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    harness.log("checks " + json.dumps(run.checks, default=str))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
