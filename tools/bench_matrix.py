"""Benchmark topology matrix — the test_tipc harness, TPU-shaped.

Capability parity with the reference CI benchmark grid
(/root/reference/benchmarks/test_tipc/gpt/dygraph/hybrid_parallel/
benchmark_common/run_benchmark.sh:20-22 and the N1C1/N1C8/N4C32 entry
scripts): each case launches the REAL training CLI as a subprocess with
``-o`` overrides over a shrunk model (the reference shrinks 24->4 layers so
cases finish inside CI, run_benchmark.sh:84-87), parses the training log
for the ``ips:`` keyword (tokens/s) and the ``loss:`` convergence keyword,
emits one JSON record per case, and FAILS when any topology's loss diverges
from the single-configuration baseline — all topologies see the same data
and seed, so their losses must agree (the dp-vs-single math check).

    python tools/bench_matrix.py                    # 8-device virtual CPU grid
    python tools/bench_matrix.py --devices 1        # one real chip
    python tools/bench_matrix.py --out grid.json --steps 8

Serving-tuning mode (``--serving-tuning``, ROADMAP item 3c: the PR 10
residual tuning debts, auto-banked the first hardware window that runs
this): instead of the training grid, drive ``tools/bench_serving.py``
through (a) the paged-cache PAGE-SIZE sweep (``--page-sizes``, default
16,32,64 — the DMA-tile tradeoff the correctness-tuned 16 ignores) and
(b) an INT8 flash-decode ``FLEETX_DECODE_BLOCK_K`` retune
(``--block-k``, default 128,256,512 — the int8 native tile is (32,128),
so the bf16-tuned block may be wrong), one subprocess per case, each
case's byte/tolerance parity asserted by the bench itself. The summary
names the winning page size and block_k; ``--out`` banks the whole
grid.

    BENCH_MATRIX_PLATFORM=tpu python tools/bench_matrix.py --serving-tuning

Train-tuning mode (``--train-tuning``, ROADMAP item 3c's remaining fold:
the r05 staged remat/block-size sweep, promoted into the banked grid):
drives ``bench.py`` through (a) remat-policy cases (``--remat-cases`` —
granularities, ``none``, and ``granularity+save+save`` extra-save
points) and (b) a training flash-attention block sweep
(``--flash-blocks``, FLEETX_FLASH_BLOCK_QxK), one subprocess per case
with extras off. Every case sees the same data and seed, so final
losses must agree (remat and kernel tiling change scheduling, never
math) — divergence fails the grid. The summary names best_remat and
best_flash_block, so the first TPU window auto-banks a tuned training
config next to the serving one.

    BENCH_MATRIX_PLATFORM=tpu python tools/bench_matrix.py --train-tuning
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

IPS_RE = re.compile(r"ips_total: (\d+)")
LOSS_RE = re.compile(r"loss: ([0-9.]+), avg_batch_cost")

# the grid: name -> -o overrides (mirrors the reference's
# DP{n}-MP{n}-PP{n} / sharding / SP case axes)
CASES_8 = {
    "DP8-MP1-PP1": {"Distributed.dp_degree": 8},
    "DP4-MP2-PP1": {"Distributed.dp_degree": 4, "Distributed.mp_degree": 2},
    "DP4-MP2-PP1-SP": {"Distributed.dp_degree": 4, "Distributed.mp_degree": 2,
                       "Model.sequence_parallel": True},
    "DP2-MP2-PP2": {"Distributed.dp_degree": 2, "Distributed.mp_degree": 2,
                    "Distributed.pp_degree": 2},
    "DP2-MP1-PP1-Sharding4-Stage2": {
        "Distributed.dp_degree": 2,
        "Distributed.sharding.sharding_degree": 4,
        "Distributed.sharding.sharding_stage": 2,
    },
    # r5: attention dropout now runs under cp (inside the per-hop flash
    # kernels, position-keyed so the realized mask matches cp=1); hidden
    # dropout's mask assignment permutes with the zig-zag order — same
    # distribution, different stream, within this grid's 3% loss gate
    "DP4-CP2": {"Distributed.dp_degree": 4, "Distributed.cp_degree": 2},
    "DP8-Recompute": {"Distributed.dp_degree": 8,
                      "Model.use_recompute": True,
                      "Model.recompute_granularity": "core_attn"},
}
CASES_1 = {
    "DP1-MP1-PP1": {"Distributed.dp_degree": 1},
}

# N4C32-analogue grids (reference ships N1C1/N1C8/N4C32 test_tipc entries;
# here 16/32 virtual devices stand in for the 4-host topology — same mesh
# factors as __graft_entry__.dryrun_multichip's 16/32-device table)
CASES_16 = {
    "DP16-MP1-PP1": {"Distributed.dp_degree": 16},
    "DP4-MP2-PP2": {"Distributed.dp_degree": 4, "Distributed.mp_degree": 2,
                    "Distributed.pp_degree": 2},
    "DP2-MP2-PP2-Sharding2-Stage2": {
        "Distributed.dp_degree": 2, "Distributed.mp_degree": 2,
        "Distributed.pp_degree": 2,
        "Distributed.sharding.sharding_degree": 2,
        "Distributed.sharding.sharding_stage": 2,
    },
    "DP8-CP2": {"Distributed.dp_degree": 8, "Distributed.cp_degree": 2},
}
CASES_32 = {
    "DP32-MP1-PP1": {"Distributed.dp_degree": 32},
    "DP8-MP2-PP2": {"Distributed.dp_degree": 8, "Distributed.mp_degree": 2,
                    "Distributed.pp_degree": 2},
    "DP2-MP2-PP2-Sharding4-Stage2": {
        "Distributed.dp_degree": 2, "Distributed.mp_degree": 2,
        "Distributed.pp_degree": 2,
        "Distributed.sharding.sharding_degree": 4,
        "Distributed.sharding.sharding_stage": 2,
    },
}

def cases_by_devices():
    """Resolved at call time (not import) so tests can monkeypatch the
    per-count grids."""
    return {1: CASES_1, 8: CASES_8, 16: CASES_16, 32: CASES_32}


def make_dataset(tmp: str, vocab: int = 120) -> str:  # < tiny config vocab_size=128
    rng = np.random.RandomState(0)
    docs = [rng.randint(0, vocab, size=rng.randint(80, 200)).astype(np.int32)
            for _ in range(64)]
    prefix = os.path.join(tmp, "bench")
    np.save(prefix + "_ids.npy", np.concatenate(docs))
    np.savez(prefix + "_idx.npz",
             lens=np.asarray([len(d) for d in docs], np.int32))
    return prefix


def run_case(name, overrides, args, data_prefix, tmp):
    cmd = [
        sys.executable, os.path.join(REPO, "tools", "train.py"),
        "-c", os.path.join(REPO, "configs", "tiny", "pretrain_gpt_tiny_cpu.yaml"),
        "-o", f"Engine.max_steps={args.steps}",
        "-o", "Engine.logging_freq=1",
        "-o", f"Data.Train.dataset.input_dir={data_prefix}",
        "-o", f"Engine.save_load.output_dir={os.path.join(tmp, name)}",
        "-o", f"Engine.mix_precision.use_pure_fp16={args.amp}",
    ]
    for k, v in overrides.items():
        cmd += ["-o", f"{k}={v}"]
    env = dict(os.environ)
    # the parsed ips:/loss: lines log at INFO/TRAIN level; a quieter
    # inherited level (e.g. the test conftest) would blank the log
    env["FLEETX_LOG_LEVEL"] = "INFO"
    # default: virtual CPU mesh (topology/convergence gate, not a perf
    # number) — including the single-device N1C1 case.
    # BENCH_MATRIX_PLATFORM=tpu runs the cases on a real slice with
    # >= --devices chips (reference test_tipc measures real perf;
    # bench.py is the official single-chip number).
    if os.environ.get("BENCH_MATRIX_PLATFORM", "cpu") == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        )
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                              timeout=args.timeout)
        log = proc.stdout + proc.stderr
        returncode = proc.returncode
    except subprocess.TimeoutExpired as e:
        # fail this case only; the rest of the grid must still run
        log = ((e.stdout or b"").decode("utf-8", "replace")
               + (e.stderr or b"").decode("utf-8", "replace")
               + f"\n[bench_matrix] case timed out after {args.timeout}s")
        returncode = -1
    ips = [int(m) for m in IPS_RE.findall(log)]
    losses = [float(m) for m in LOSS_RE.findall(log)]
    record = {
        # a run whose loss never parses (e.g. NaN) is a failure even if the
        # process exits 0 — the convergence gate must not silently skip it
        "case": name,
        "ok": bool(returncode == 0 and ips and losses
                   and np.isfinite(losses[-1])),
        "ips_tokens_per_s": ips[-1] if ips else None,  # steady-state (last)
        "loss_first": losses[0] if losses else None,
        "loss_last": losses[-1] if losses else None,
        "overrides": overrides,
    }
    if not record["ok"]:
        record["log_tail"] = log[-2000:]
    return record


def _run_bench_serving(env_extra, timeout):
    """One ``tools/bench_serving.py`` subprocess; returns its JSON
    records keyed by metric name (None on failure, with the log tail)."""
    cmd = [sys.executable, os.path.join(REPO, "tools", "bench_serving.py")]
    env = dict(os.environ)
    env["FLEETX_LOG_LEVEL"] = "ERROR"  # keep stdout JSON-parseable
    if os.environ.get("BENCH_MATRIX_PLATFORM", "cpu") == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, f"[bench_matrix] bench_serving timed out after {timeout}s"
    if proc.returncode != 0:
        return None, (proc.stdout + proc.stderr)[-2000:]
    records = {}
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "metric" in rec:
                records[rec["metric"]] = rec
    return records, None


def run_serving_tuning(args):
    """The PR 10 residual tuning debts as grid cases (module docstring):
    page-size sweep + int8 flash-decode block_k retune, each a
    bench_serving subprocess whose parity gates must hold. Returns one
    record per case."""
    results = []
    sizes = args.page_sizes.strip()
    if sizes:
        records, err = _run_bench_serving(
            {"BENCH_SERVING_PAGE_SIZES": sizes}, args.timeout)
        rec = (records or {}).get("gpt_345m_serving_page_sweep")
        ok = err is None and rec is not None and rec["detail"]["parity"]
        out = {"case": f"PageSweep[{sizes}]", "ok": bool(ok)}
        if rec is not None:
            out.update({
                "best_page_size": rec["detail"]["best_page_size"],
                "tokens_per_s": rec["value"],
                "sweep": rec["detail"]["sweep"],
            })
        if err is not None:
            out["log_tail"] = err
        results.append(out)
    # each block_k case runs the full bench_serving suite and reads only
    # its int8 record — wasteful-looking, but the int8 record's
    # speedup/parity fields are computed AGAINST that same run's bf16
    # continuous baseline, so the suite is the unit of comparison; a
    # tuning window pays minutes, not hours
    for bk in (s.strip() for s in args.block_k.split(",") if s.strip()):
        records, err = _run_bench_serving(
            {"FLEETX_DECODE_BLOCK_K": bk}, args.timeout)
        rec = (records or {}).get("gpt_345m_serving_int8")
        # the int8 record's own tolerance-parity assertion is the gate:
        # a block_k that breaks decode correctness fails its subprocess
        ok = err is None and rec is not None and rec["detail"]["parity"]
        out = {"case": f"Int8BlockK{bk}", "ok": bool(ok),
               "block_k": int(bk)}
        if rec is not None:
            out.update({
                "tokens_per_s": rec["value"],
                "speedup_vs_bf16": rec["detail"].get("speedup_vs_bf16"),
                "decode_bytes_per_token_int8":
                    rec["detail"].get("decode_bytes_per_token_int8"),
            })
        if err is not None:
            out["log_tail"] = err
        results.append(out)
    return results


def _run_bench_train(env_extra, timeout):
    """One ``bench.py`` subprocess (extras off); returns the anchor
    training record (None on failure, with the log tail)."""
    cmd = [sys.executable, os.path.join(REPO, "bench.py")]
    env = dict(os.environ)
    env["FLEETX_LOG_LEVEL"] = "ERROR"
    env["BENCH_EXTRA"] = "0"  # one training record per case
    if os.environ.get("BENCH_MATRIX_PLATFORM", "cpu") == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        # host-feasible per-case work; a TPU run keeps bench.py defaults
        env.setdefault("BENCH_SEQ", "128")
        env.setdefault("BENCH_BATCH", "1")
        env.setdefault("BENCH_STEPS", "2")
        env.setdefault("BENCH_WARMUP", "1")
    env.update(env_extra)
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, f"[bench_matrix] bench.py timed out after {timeout}s"
    if proc.returncode != 0:
        return None, (proc.stdout + proc.stderr)[-2000:]
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("metric", "").startswith("gpt_345m_pretrain"):
                return rec, None
    return None, "[bench_matrix] no training record in bench.py stdout"


def _train_case(name, rec, err, extra=None):
    """Normalize one train-tuning case record. A non-finite loss is a
    FAILED case even when the subprocess exits 0 — NaN would otherwise
    sail through the convergence gate (NaN comparisons are all False)."""
    loss = (rec or {}).get("detail", {}).get("loss")
    out = {"case": name,
           "ok": bool(err is None and rec is not None
                      and loss is not None and np.isfinite(loss))}
    if extra:
        out.update(extra)
    if rec is not None:
        d = rec["detail"]
        out.update({
            "tokens_per_s": rec["value"],
            "mfu": d.get("mfu"),
            "xla_mfu": d.get("xla_mfu"),
            "loss": d.get("loss"),
            "step_time_s": d.get("step_time_s"),
            "peak_hbm_gb": d.get("peak_hbm_gb"),
            "overlap": d.get("overlap"),
        })
    if err is not None:
        out["log_tail"] = err
    return out


def run_train_tuning(args):
    """ROADMAP 3c's remaining fold (the r05 staged remat/block sweep,
    promoted): remat policy x flash-block cases as parity-gated bench.py
    subprocess runs. Remat sweeps at the default blocks, blocks sweep at
    the default (core_attn) remat — the cross terms are second-order and
    a tuning window pays per case. The winners summary is what the first
    TPU window banks as the tuned training config; the convergence gate
    (same data+seed, remat/tiling must not change the math) fails any
    case whose loss diverges."""
    results = []
    for g in (s.strip() for s in args.remat_cases.split(",") if s.strip()):
        env = {"BENCH_RECOMPUTE": "0"} if g == "none" else {
            "BENCH_RECOMPUTE": "1", "BENCH_GRANULARITY": g.split("+")[0]}
        if "+" in g:  # e.g. core_attn+qkv_out+ffn_gelu -> extra saves
            env["BENCH_EXTRA_SAVES"] = ",".join(g.split("+")[1:])
        rec, err = _run_bench_train(env, args.timeout)
        results.append(_train_case(f"Remat[{g}]", rec, err,
                                   {"remat": g}))
    for bk in (s.strip() for s in args.flash_blocks.split(",") if s.strip()):
        q, _, k = bk.partition("x")
        env = {"FLEETX_FLASH_BLOCK_Q": q, "FLEETX_FLASH_BLOCK_K": k or q}
        rec, err = _run_bench_train(env, args.timeout)
        results.append(_train_case(f"FlashBlock[{bk}]", rec, err,
                                   {"flash_block": bk}))
    return results


def _train_tuning_summary(results, loss_rtol):
    import statistics

    failures = [r["case"] for r in results if not r["ok"]]
    ok = [r for r in results if r["ok"]]
    losses = [r["loss"] for r in ok if r.get("loss") is not None]
    diverged = []
    if losses:
        # reference = the MEDIAN loss, not the arbitrary first case: if
        # the first case were the broken one, every correct case would be
        # flagged and the broken one crowned best_* below
        ref_loss = statistics.median(losses)
        for r in ok:
            if r.get("loss") is None:
                continue
            rel = abs(r["loss"] - ref_loss) / max(abs(ref_loss), 1e-9)
            if rel > loss_rtol:
                diverged.append((r["case"], round(rel, 4)))
    # a diverged case is mathematically wrong, not fast — it must never
    # be banked as the winner a TPU window would tune toward
    bad = {name for name, _ in diverged}
    clean = [r for r in ok if r["case"] not in bad]
    remat = [r for r in clean if "remat" in r]
    blocks = [r for r in clean if "flash_block" in r]
    return {
        "metric": "bench_matrix_train_tuning",
        "cases": len(results),
        "passed": len(ok),
        "failed_cases": failures,
        "loss_diverged": diverged,
        "best_remat": (max(remat, key=lambda r: r["tokens_per_s"])["remat"]
                       if remat else None),
        "best_flash_block": (
            max(blocks, key=lambda r: r["tokens_per_s"])["flash_block"]
            if blocks else None),
    }


def _serving_tuning_summary(results):
    failures = [r["case"] for r in results if not r["ok"]]
    block_cases = [r for r in results
                   if r["ok"] and r["case"].startswith("Int8BlockK")]
    best_bk = (max(block_cases, key=lambda r: r["tokens_per_s"])["block_k"]
               if block_cases else None)
    sweep = next((r for r in results
                  if r["ok"] and r["case"].startswith("PageSweep")), None)
    return {
        "metric": "bench_matrix_serving_tuning",
        "cases": len(results),
        "passed": sum(r["ok"] for r in results),
        "failed_cases": failures,
        "best_page_size": sweep["best_page_size"] if sweep else None,
        "best_int8_block_k": best_bk,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8,
                    help="8 = virtual CPU grid; 1 = current platform")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--amp", default="False")
    ap.add_argument("--timeout", type=int, default=900,
                    help="per-case timeout (reference: timeout 15m)")
    ap.add_argument("--loss-rtol", type=float, default=0.03,
                    help="max relative final-loss divergence vs the first "
                         "case (same data+seed => same math)")
    ap.add_argument("--out", default=None, help="write the grid json here")
    ap.add_argument("--serving-tuning", action="store_true",
                    help="run the serving tuning grid (page-size sweep + "
                         "int8 block_k retune) instead of the training grid")
    ap.add_argument("--page-sizes", default="16,32,64",
                    help="paged-cache page sizes to sweep (empty = skip)")
    ap.add_argument("--block-k", default="128,256,512",
                    help="FLEETX_DECODE_BLOCK_K values for the int8 "
                         "flash-decode retune (empty = skip)")
    ap.add_argument("--train-tuning", action="store_true",
                    help="run the training tuning grid (remat policy + "
                         "flash block sizes as parity-gated bench.py "
                         "runs) instead of the topology grid")
    ap.add_argument("--remat-cases", default="core_attn,full_attn,full,"
                                             "core_attn+qkv_out+ffn_gelu",
                    help="remat cases: granularity, 'none', or "
                         "granularity+save+save (empty = skip)")
    ap.add_argument("--flash-blocks", default="256x256,512x512,1024x512",
                    help="FLEETX_FLASH_BLOCK_QxK values to sweep "
                         "(empty = skip)")
    args = ap.parse_args(argv)

    if args.train_tuning:
        results = run_train_tuning(args)
        for rec in results:
            print(json.dumps(rec))
        summary = _train_tuning_summary(results, args.loss_rtol)
        print(json.dumps(summary))
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"summary": summary, "results": results}, f,
                          indent=2)
        if summary["failed_cases"] or summary["loss_diverged"]:
            raise SystemExit(
                f"train tuning failed: "
                f"{summary['failed_cases'] or summary['loss_diverged']}")
        return

    if args.serving_tuning:
        results = run_serving_tuning(args)
        for rec in results:
            print(json.dumps(rec))
        summary = _serving_tuning_summary(results)
        print(json.dumps(summary))
        if args.out:
            with open(args.out, "w") as f:
                json.dump({"summary": summary, "results": results}, f,
                          indent=2)
        if summary["failed_cases"]:
            raise SystemExit(
                f"serving tuning failed: {summary['failed_cases']}")
        return

    grids = cases_by_devices()
    try:
        cases = grids[args.devices]
    except KeyError:
        raise SystemExit(
            f"no case grid for --devices {args.devices} "
            f"(have {sorted(grids)})"
        )
    results = []
    with tempfile.TemporaryDirectory() as tmp:
        data_prefix = make_dataset(tmp)
        for name, overrides in cases.items():
            rec = run_case(name, overrides, args, data_prefix, tmp)
            results.append(rec)
            print(json.dumps(rec))

    failures = [r["case"] for r in results if not r["ok"]]
    # convergence check: every topology must see the same loss (the data
    # order and seed are fixed; the parallelism must not change the math)
    ref = next((r for r in results if r["ok"]), None)
    diverged = []
    if ref and ref["loss_last"]:
        for r in results:
            if not r["ok"] or r["loss_last"] is None:
                continue
            rel = abs(r["loss_last"] - ref["loss_last"]) / abs(ref["loss_last"])
            if rel > args.loss_rtol:
                diverged.append((r["case"], round(rel, 4)))
    summary = {
        "metric": "bench_matrix",
        "cases": len(results),
        "passed": sum(r["ok"] for r in results),
        "failed_cases": failures,
        "loss_diverged": diverged,
        "baseline_case": ref["case"] if ref else None,
    }
    print(json.dumps(summary))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"summary": summary, "results": results}, f, indent=2)
    if failures or diverged:
        raise SystemExit(f"bench matrix failed: {failures or diverged}")


if __name__ == "__main__":
    main()
