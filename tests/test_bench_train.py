"""bench.py smoke: the driver contract (one JSON line) and the perf-knob
surface (BENCH_* env) on the CPU platform with a tiny config."""

import json
import os
import subprocess
import sys
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow  # 244.3s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_bench_one_json_line_with_knobs():
    env = {
        **os.environ,
        # single-device: the inherited 8-virtual-device XLA_FLAGS would put
        # a dp8 all-reduce in the step, whose CPU rendezvous (8 threads,
        # 40s termination timeout) flakes on a loaded test host
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "JAX_PLATFORMS": "cpu",
        "BENCH_EXTRA": "0",
        "BENCH_BATCH": "1",
        "BENCH_SEQ": "128",
        "BENCH_STEPS": "1",
        "BENCH_WARMUP": "1",
        # exercise the perf knobs: remat save-set, bf16 moments, dropout
        # overrides (BENCH_SCAN=0 is skipped here: unrolling 24 layers
        # takes minutes of CPU compile; the knob only flips
        # GPTConfig.scan_layers, which test_gpt_model covers)
        "BENCH_EXTRA_SAVES": "qkv_out,ffn_gelu",
        "BENCH_MOMENT_DTYPE": "bfloat16",
        "BENCH_HIDDEN_DROPOUT": "0.0",
        "BENCH_ATTN_DROPOUT": "0.0",
    }
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
    assert len(lines) == 1, r.stdout  # the driver parses exactly one line
    rec = json.loads(lines[0])
    assert rec["metric"] == "gpt_345m_pretrain_throughput"
    assert rec["unit"] == "tokens/s" and rec["value"] > 0
    d = rec["detail"]
    assert d["recompute"] == "True:core_attn"
    assert "peak_hbm_gb" in d
    # the CPU has no row in utils/hw.py: a CPU record carries no MFU
    assert d["loss"] > 0 and d["platform"] == "cpu" and d["mfu"] is None
