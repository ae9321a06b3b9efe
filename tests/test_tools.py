"""CLI helper tools: the parallel shell executor (reference
ppfleetx/tools/multiprocess_tool.py), the Imagen text-embedding
precompute tool (replacing the reference's in-process T5/DeBERTa encode,
imagen/utils.py), the chaos driver's scenarios and the observability
dump."""

import json
import subprocess
import sys

import numpy as np
import pytest

REPO = __file__.rsplit("/tests/", 1)[0]


def test_multiprocess_tool_runs_and_reports(tmp_path):
    out = tmp_path / "made"
    out.mkdir()
    cmd_file = tmp_path / "cmds.txt"
    cmd_file.write_text(
        "\n".join(f"touch {out}/f{i}" for i in range(8)) + "\n# comment line\n"
    )
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/multiprocess_tool.py",
         "--num-proc", "4", "--cmd-file", str(cmd_file)],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    assert len(list(out.iterdir())) == 8
    assert "8 commands" in r.stdout


def test_multiprocess_tool_nonzero_exit_on_failure(tmp_path):
    cmd_file = tmp_path / "cmds.txt"
    cmd_file.write_text("true\nfalse\ntrue\n")
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/multiprocess_tool.py",
         "--num-proc", "2", "--cmd-file", str(cmd_file)],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 1
    assert "1 failed" in r.stdout


def test_precompute_text_embeddings_hash(tmp_path):
    caps = tmp_path / "caps.jsonl"
    caps.write_text(
        "\n".join(
            json.dumps({"text": t})
            for t in ["a red bird", "a red bird", "blue dog swimming"]
        )
    )
    prefix = str(tmp_path / "out" / "train")
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/precompute_text_embeddings.py",
         "--input", str(caps), "--output-prefix", prefix,
         "--max-text-len", "8", "--cond-dim", "16"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    embeds = np.load(prefix + "_embeds.npy")
    mask = np.load(prefix + "_mask.npy")
    assert embeds.shape == (3, 8, 16) and embeds.dtype == np.float16
    assert mask.shape == (3, 8)
    # deterministic: identical captions embed identically
    np.testing.assert_array_equal(embeds[0], embeds[1])
    assert mask[0].sum() == 3 and mask[2].sum() == 3
    assert not np.array_equal(embeds[0], embeds[2])
    # rows are masked beyond caption length
    assert np.all(embeds[0][3:] == 0)


@pytest.mark.slow  # 9.8s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_chaos_check_sentry_scenario(tmp_path):
    """The chaos smoke driver's sentry scenario passes in-process (the
    full sweep is tests/test_resilience.py; this proves the CLI works)."""
    sys.path.insert(0, REPO)
    import tools.chaos_check as cc

    rc = cc.main(["--only", "sentry", "--workdir", str(tmp_path)])
    assert rc == 0


@pytest.mark.slow  # ~18s; the contract itself is tier-1 via
def test_chaos_check_sentry_zero_scenario(tmp_path):
    # tests/test_zero_update.py (sentry-skip byte parity on the sharded
    # step); this proves the CLI scenario end-to-end
    """The ZeRO-sharded sentry chaos scenario (NaN skip leaves sharded
    params + opt state byte-identical, FLEETX_ZERO_UPDATE=1 on a dp
    mesh) passes through the CLI driver."""
    sys.path.insert(0, REPO)
    import tools.chaos_check as cc

    rc = cc.main(["--only", "sentry_zero", "--workdir", str(tmp_path)])
    assert rc == 0


def test_chaos_check_unknown_scenario_fails(tmp_path):
    """An unknown scenario name is a non-zero exit, not a silent pass."""
    sys.path.insert(0, REPO)
    import tools.chaos_check as cc

    assert cc.main(["--only", "nope", "--workdir", str(tmp_path)]) == 1


@pytest.mark.slow  # ~30s (3 tiny trainer compiles); the contracts are
def test_chaos_check_train_elastic_scenario(tmp_path, capsys):
    # tier-1 via tests/test_elastic.py (dp2->dp1 reshard byte parity,
    # async snapshot contracts, host-loss injector) and
    # tests/test_resilience.py; this proves the dp4->dp2 host-loss story
    # end-to-end through the CLI driver
    """The elastic-training chaos scenario (host loss at step 3 ->
    emergency snapshot -> dp4->dp2 shrink -> reshard-on-load resume with
    post-shrink loss parity vs an uninterrupted dp2 run) passes through
    the CLI driver."""
    sys.path.insert(0, REPO)
    import tools.chaos_check as cc

    rc = cc.main(["--only", "train_elastic", "--workdir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "PASS train_elastic" in out


@pytest.mark.slow  # 75.2s baseline (PR 12 tier-1 budget audit): every
def test_chaos_check_serving_recovery_scenarios(tmp_path, capsys):
    # contract here is tier-1 via tests/test_serving_recovery.py; this
    # proves the CLI driver end-to-end (same precedent as the spill smoke)
    """The serving crash-safety scenarios (recovery, poison quarantine,
    hung-tick watchdog, graceful drain, mid-verify speculative fault)
    pass through the CLI driver and print one PASS line each — the
    acceptance-gate demonstration outside pytest (the full suites are
    tests/test_serving_recovery.py and tests/test_spec_serving.py)."""
    sys.path.insert(0, REPO)
    import tools.chaos_check as cc

    names = ("serving_recovery,serving_poison,serving_hang,serving_drain,"
             "serving_spec")
    rc = cc.main(["--only", names, "--workdir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0, out
    for name in names.split(","):
        assert f"PASS {name}" in out


@pytest.mark.slow  # ~15s; tier-1 covers the same contracts via
def test_chaos_check_serving_mesh_scenario(tmp_path, capsys):
    # tests/test_mesh_serving.py (mp2 parity + sharded recover); this
    # proves the CLI scenario end-to-end
    """The mesh-sharded serving chaos scenario (tick fault + recover()
    on an mp2 engine, byte parity vs clean, per-device cache bytes stay
    halved, engine_recovery event) passes through the CLI driver."""
    sys.path.insert(0, REPO)
    import tools.chaos_check as cc

    rc = cc.main(["--only", "serving_mesh", "--workdir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "PASS serving_mesh" in out


@pytest.mark.slow  # ~10s; tier-1 covers the same contracts via
def test_chaos_check_serving_spill_scenario(tmp_path, capsys):
    # tests/test_chunked_serving.py (mid-chunk fault + host-tier
    # recovery survival); this proves the CLI scenario end-to-end
    """The two-level-page-cache chaos scenario (spill under pool
    pressure, mid-chunk fault, host tier survives recovery, revived
    pages reused, byte parity) passes through the CLI driver."""
    sys.path.insert(0, REPO)
    import tools.chaos_check as cc

    rc = cc.main(["--only", "serving_spill", "--workdir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "PASS serving_spill" in out


@pytest.mark.slow  # ~15s; tier-1 covers the same contracts via
def test_chaos_check_serving_disagg_scenario(tmp_path, capsys):
    # tests/test_serving_disagg.py (export/admit parity, fallback
    # ladder); this proves the CLI scenario end-to-end
    """The phase-disaggregated chaos scenario (1 prefill + 1 decode
    replica byte-identical to colocated, corrupt KV ship replayed to
    parity, prefill replica killed mid-run and its requests replayed)
    passes through the CLI driver."""
    sys.path.insert(0, REPO)
    import tools.chaos_check as cc

    rc = cc.main(["--only", "serving_disagg", "--workdir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "PASS serving_disagg" in out


@pytest.mark.slow  # ~35s; tier-1 covers the same contracts via
def test_chaos_check_router_scenarios(tmp_path, capsys):
    # tests/test_router.py (kill-failover byte parity, conservation
    # churn, saturation shedding); this proves the CLI driver end-to-end
    """The multi-replica router chaos scenarios — a replica killed
    mid-burst (zero-token-loss migration, byte parity, replica_dead +
    request_migrated events, goodput shows no lost requests) and
    past-saturation degradation (rejects + sheds, exactly one terminal
    result each, router alive after) — pass through the CLI driver."""
    sys.path.insert(0, REPO)
    import tools.chaos_check as cc

    rc = cc.main(["--only", "router_kill,router_saturation",
                  "--workdir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "PASS router_kill" in out
    assert "PASS router_saturation" in out


@pytest.mark.slow  # ~20s; tier-1 covers the same contracts via
def test_chaos_check_serving_qos_scenario(tmp_path, capsys):
    # tests/test_router_qos.py (preemption byte parity, churn
    # conservation under kill, lane-scoped shed); this proves the CLI
    # scenario end-to-end
    """The per-tenant QoS chaos scenario (flooding tenant saturates the
    fleet, priority tenant preempts in, replica SIGKILLed mid-preemption
    churn — priority AND preempted-flood streams byte-identical to a
    clean engine, shed confined to the flood lane) passes through the
    CLI driver."""
    sys.path.insert(0, REPO)
    import tools.chaos_check as cc

    rc = cc.main(["--only", "serving_qos", "--workdir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "PASS serving_qos" in out


def test_obs_dump_scrapes_live_server(tmp_path):
    """tools/obs_dump.py against a live exposition server writes the
    three payloads; the Chrome trace parses and carries the host spans
    (docs/OBSERVABILITY.md endpoint contract)."""
    sys.path.insert(0, REPO)
    from fleetx_tpu.obs import ObsServer, emit, span
    from tools import obs_dump

    emit("obs_dump_probe")
    with span("obs.dump.probe"):
        pass
    srv = ObsServer(port=0).start()
    try:
        out = tmp_path / "obs"
        rc = obs_dump.main(["--url", srv.url, "--out-dir", str(out)])
        assert rc == 0
        text = (out / "metrics.prom").read_text()
        assert "fleetx_events_total" in text
        snap = json.loads((out / "snapshot.json").read_text())
        assert any(e["kind"] == "obs_dump_probe" for e in snap["events"])
        trace = json.loads((out / "trace.json").read_text())
        assert any(e.get("name") == "obs.dump.probe"
                   for e in trace["traceEvents"])
    finally:
        srv.stop()
    # a dead endpoint is a loud non-zero exit, not a silent empty dump
    assert obs_dump.main(["--url", "http://127.0.0.1:9",
                          "--out-dir", str(tmp_path / "dead"),
                          "--timeout-s", "0.5"]) == 1


def test_precomputed_embeddings_feed_text_image_dataset(tmp_path):
    """The tool's output is directly mmap-consumable by TextImageDataset."""
    sys.path.insert(0, REPO)
    from fleetx_tpu.data.multimodal_dataset import TextImageDataset

    caps = tmp_path / "caps.txt"
    caps.write_text("one caption here\nsecond caption\n")
    prefix = str(tmp_path / "train")
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/precompute_text_embeddings.py",
         "--input", str(caps), "--output-prefix", prefix,
         "--max-text-len", "8", "--cond-dim", "16"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    np.save(prefix + "_images.npy",
            np.zeros((2, 16, 16, 3), np.uint8))
    ds = TextImageDataset(input_dir=prefix, image_size=16,
                          max_text_len=8, cond_dim=16)
    item = ds[0]
    assert item["text_embeds"].shape == (8, 16)
    assert item["text_mask"].shape == (8,)
