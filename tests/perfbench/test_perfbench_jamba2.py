"""The Jamba2 configuration against the published one, the state's and the
scan's byte counts on hand-worked cases, the readers of the new spans,
counters, scopes and kernels on hand-made runs, the traced ``--tiny``
rehearsal of the new cell, and the driver's checks at rehearsal size: the
reference check passes the engine as built, and a bfloat16 state, the inner
norms left out, ``D * u`` left out, a state zeroed between calls, a state a
position stale or padded rows updating it (``perfbench/probe_jamba2.py``,
which puts the same questions on the chip at the published widths) each
turn it false; and the check of the engine's own programs on the requests
in flight, which a bfloat16 state in the tick alone, a state zeroed at
every tick and stale block tables turn false."""

import json
import os
import subprocess
import sys
import types

import pytest

from perfbench import flops_ssm, harness, probe_jamba2
from perfbench.drivers import serve_closed_loop_ssm as driver
from perfbench.layer_metrics import (_ssm, ssm_mix_busy_share,
                                     ssm_scan_busy_share, ssm_scan_roofline,
                                     ssm_step_roofline, state_bytes_share)

CELL = "jamba2-3b-serve-chat-peak"
BENCH = harness.load_json("BENCHMARK.json")
# ai21labs/AI21-Jamba2-3B, config.json (catalog architectures.jsonl), written
# out: the source's key and its value
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 8192, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
# the model group's key for a source's key where the two differ
MINE = {"intermediate_size": "dense_ffn_hidden_size",
        "num_hidden_layers": "num_layers", "rms_norm_eps": "norm_eps"}
SAME = ("hidden_size", "mamba_d_conv", "mamba_d_state", "mamba_dt_rank",
        "mamba_expand", "max_position_embeddings", "num_attention_heads",
        "num_key_value_heads", "tie_word_embeddings", "vocab_size")


def test_every_key_is_the_published_one_and_nothing_is_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "jamba2-3b")
    data = harness.load_json(entry["file"])
    model = data["model"]
    for key, value in PUBLISHED.items():
        assert data[key] == value, key
        if key in MINE or key in SAME:
            assert model[MINE.get(key, key)] == value, key
    assert entry["reduced"] == data["reduced"] == []
    assert model["ffn_hidden_size"] == model["num_dense_layers"] * 0 + 8192
    assert model["num_dense_layers"] == model["num_layers"] == 28
    assert model["layer_types"] == [
        "full_attention" if i % 14 == 7 else "mamba" for i in range(28)]
    assert model["rope_layout"] == [0] * 28 and "head_size" not in model
    assert set(data["assumed"]) >= {"layer_order", "state_dtype", "sampling"}
    assert (data["compute_dtype"], data["weight_dtype"]) == ("bfloat16",) * 2
    assert data["state_dtype"].startswith("float32")
    assert data["source"] == entry["source"] and data["reference"] == "jamba2_f32"


def test_the_cell_and_its_traffic_are_the_issues():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "serve_closed_loop_ssm"
    assert cell.traffic["clients"] == cell.deploy["lanes"] == 256
    (tenant,) = cell.traffic["tenants"]
    assert tenant["prompt"] == {"dist": "lognormal", "median": 256,
                                "sigma": 0.6, "min": 64, "max": 768}
    assert tenant["output"] == {"dist": "lognormal", "median": 128,
                                "sigma": 0.6, "min": 32, "max": 256}
    assert (tenant["shared_prefix_len"], cell.traffic["block"],
            cell.traffic["trace_s"]) == (0, 4, 3.0)
    deploy = cell.deploy
    assert (deploy["cache_len"], deploy["page_size"], deploy["pool_tokens"],
            deploy["prefill_bucket"]) == (1024, 16, 262144, 128)
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    listed = {m["name"] for m in cell.per_layer}
    assert {"ssm_mix_busy_share", "ssm_scan_busy_share", "ssm_scan_roofline",
            "ssm_step_roofline", "state_bytes_share"} <= listed
    assert "batch.decode_paged_roofline" not in listed


def test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved():
    """``test_perfbench_lfm2.py``'s first test wants the LFM2 cell last in
    ``serve_tokens_per_s``'s list and alone in ``state_bytes_share``'s; it
    runs on the benchmark as it stood when it was written
    (``tests/conftest.py`` ``_WRITTEN_BEFORE``), and this holds the two
    lists as they are since this cell joined both."""
    lfm2 = "lfm2-l14-serve-agent-prefix"
    (serve,) = [m for m in BENCH["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    assert serve["workloads"][-2:] == [lfm2, CELL]
    (state,) = [m for m in BENCH["per_layer"]
                if m["name"] == "state_bytes_share"]
    assert state["workloads"] == [lfm2, CELL]
    for m in BENCH["per_layer"]:     # appended, and nothing else moved
        if CELL in m["workloads"]:
            assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1


def test_state_and_scan_bytes_on_hand_worked_cases():
    model = harness.load_cell(CELL).config["model"]
    assert flops_ssm.mamba_layers(model) == 26
    assert flops_ssm.sizes(model) == (5120, 16, 3)
    assert flops_ssm.lane_state_bytes(model) == 327_680 + 30_720 == 358_400
    ops, bytes_ = flops_ssm.step_cost(256, model)
    # what the step kernel moves, h alone: 4.36 GB of a tick's 4.77 GB
    assert bytes_ == 256 * 26 * 2 * 327_680
    assert ops == 256 * 26 * 7 * 16 * 5120
    ops, bytes_ = flops_ssm.scan_cost(384, model)
    assert bytes_ == 26 * (384 * (3 * 5120 + 32) * 4 + 2 * 327_680)
    assert ops == 26 * 384 * 7 * 16 * 5120
    tiny = {"layer_types": ["mamba", "full_attention"], "hidden_size": 64}
    assert flops_ssm.lane_state_bytes(tiny) == 16 * 128 * 4 + 3 * 128 * 2


def _span(name, start, **attrs):
    return types.SimpleNamespace(name=name, start_s=start, end_s=start + 0.01,
                                 attrs=attrs)


def _run(spans=(), counters=None, trace=None, traced=None):
    run = types.SimpleNamespace(
        spans=list(spans), counters=counters or {}, window=(0.0, 10.0),
        trace=trace, traced=traced, peaks={"bf16_flops": 197e12,
                                           "hbm_bytes_per_s": 819e9},
        cell=harness.load_cell(CELL))
    run.spans_named = lambda name: [s for s in run.spans if s.name == name]
    return run


def test_a_program_without_the_spans_scopes_or_kernels_reports_nothing():
    """What the parent commit's program gives the new readers: no field on
    its spans, no trace, no counter; none raises and each leaves its metric
    out."""
    bare = _run([_span("serving.decode", 1.0, batch=3),
                 _span("serving.admit", 2.0, prompt_len=9)])
    for reader in (ssm_mix_busy_share, ssm_scan_busy_share, ssm_scan_roofline,
                   ssm_step_roofline, state_bytes_share):
        assert reader.read(bare) is None
    traced = _run(bare.spans, trace={"busy_s": 1.0}, traced=(0.0, 3.0))
    assert ssm_step_roofline.read(traced) is None     # no trace file either
    assert _ssm.span_field(bare, ("serving.decode",), "state_lanes") == []


def test_span_fields_scopes_and_kernels_on_hand_made_rows(monkeypatch):
    spans = [_span("serving.decode", 1.0, state_lanes=256, attn_rows=9),
             _span("serving.decode", 2.0, state_lanes=128, attn_rows=9),
             _span("serving.decode", 11.0, state_lanes=1),
             _span("serving.admit", 1.5, scan_rows=384, prompt_len=300),
             _span("serving.prefill_chunk", 2.5, scan_rows=128)]
    run = _run(spans, trace={"busy_s": 1.0}, traced=(0.5, 2.2))
    assert _ssm.span_field(run, ("serving.decode",), "state_lanes") == [256, 128]
    assert _ssm.span_field(run, ("serving.admit", "serving.prefill_chunk"),
                           "scan_rows", run.traced) == [384]
    scope = "jit(f)/cached_forward/_decoder_stack/while/body/layer/attn/"
    rows = [  # [instruction text, op_name, program, start_ns, dur_ns]
        ["%fusion.1 = ...", scope + "ssm_mix/in_proj/dot", "jit_f", 0, 300],
        ["%fleetx_ssm_step.3 = custom-call", scope + "ssm_mix/ssm_step/x",
         "jit_f", 300, 200],
        ["%fleetx_ssm_scan.5 = custom-call", scope + "ssm_mix/ssm_scan/x",
         "jit_f", 500, 100],
        ["%fusion.9 = ...", "jit(f)/cached_forward/_decoder_stack/while/body/"
         "layer/mlp/dot", "jit_f", 600, 400]]
    read = _ssm.seconds_of({"/device:TPU:0": rows})
    assert read["total"] == pytest.approx(1e-6)
    assert read["mix"] == pytest.approx(0.6e-6)
    assert read["scan_step"] == pytest.approx(0.3e-6)
    assert (read["step"], read["step_calls"]) == (pytest.approx(0.2e-6), 1)
    assert (read["scan"], read["scan_calls"]) == (pytest.approx(0.1e-6), 1)
    # 28 step calls of 1 us each = one tick of 256 lanes: its bytes' time
    read.update(step=28e-6, step_calls=28, scan=28e-6, scan_calls=28,
                total=100e-6)
    monkeypatch.setattr(_ssm, "seconds", lambda run: read)
    least = 256 * 26 * 2 * 327_680 / 819e9          # h in and out
    assert ssm_step_roofline.read(run) == pytest.approx(
        100 * least / 28e-6 * (192 / 256))            # mean lanes 192
    model = run.cell.config["model"]
    assert ssm_scan_roofline.read(run) == pytest.approx(
        100 * flops_ssm.scan_cost(384, model)[1] / 819e9 / 28e-6)
    assert ssm_mix_busy_share.read(run) == pytest.approx(0.6e-6 / 100e-6)
    assert state_bytes_share.read(_run(counters={
        "state_bytes_lanes": 256 * 9_318_400,
        "kv_page_bytes_in_use": 100_000_000})) == pytest.approx(23.855104)


# --------------------------------------------------- the traced rehearsal

def _listed():
    return [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [CELL])]


# what a ``--tiny --trace 1`` run reports on the CPU, where no reader of
# the device's trace, of its memory or of a peak finds anything
TINY_REPORTS = {"batch.admit_host_ms_p50", "batch.lane_occupancy",
                "batch.tick_host_ms_p50", "batch.tick_ms_p50",
                "batch.tick_overlap_share", "state_bytes_share"}


@pytest.fixture(scope="module")
def traced_rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=harness.ROOT)
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "3", "--seconds", "2", "--trace", "1",
         "--tiny"], cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.mark.parametrize("name", _listed())
def test_traced_rehearsal_reports_each_entry_that_lists_the_cell(
        traced_rehearsal, name):
    result, out = traced_rehearsal
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is False and result["metrics"] == {}
    reported = set(result["rehearsal"])
    assert reported <= set(_listed())
    assert (name in reported) == (name in TINY_REPORTS), sorted(reported)
    assert '"compiles_in_window": 0' in out and '"engine_ok": true' in out
    assert '"reference_ok": true' in out


# ------------------------------------------ the checks at rehearsal size

@pytest.fixture(scope="module")
def probe_cell():
    cell = harness.load_cell(CELL, tiny=True)
    cell.deploy.update(lanes=4, pool_tokens=4 * cell.deploy["cache_len"])
    return cell


@pytest.fixture(scope="module")
def readings(probe_cell):
    """Every reading of the probe's first part (a bfloat16 state too: the
    first layer's state tells it at any width)."""
    return dict(probe_jamba2.readings(probe_cell, driver, 11))


def test_the_reference_check_passes_the_engine_as_built(readings):
    out = readings["as_built"]
    assert out["reference_ok"], out
    assert out["reference_positions_checked"] == 16 + 4
    assert out["reference_rms_err"] < 0.5 * driver.REFERENCE_RMS_TOL * out[
        "reference_logit_std"]


@pytest.mark.parametrize("fault", probe_jamba2.FAULTS)
def test_a_planted_fault_turns_the_reference_check_false(readings, fault):
    assert not readings[fault]["reference_ok"], readings[fault]
    if fault == "bf16_state":  # nothing else differs in the first layer
        assert readings[fault]["whole_chunked_first_state_rel_rms_diff"] > (
            10 * driver.FIRST_STATE_TOL)
        assert readings["as_built"][
            "whole_chunked_first_state_rel_rms_diff"] < (
            driver.FIRST_STATE_TOL / 10)


@pytest.fixture(scope="module")
def engine_readings(probe_cell, readings):
    return dict(probe_jamba2.engine_readings(
        probe_cell, driver, 11, readings["as_built"]["reference_logit_std"]))


@pytest.mark.parametrize("name", probe_jamba2.ENGINE_FAULTS)
def test_the_engines_own_programs_are_held_to_the_checked_ones(
        engine_readings, name):
    out = engine_readings[name]
    assert out["engine_lanes_checked"] == 4
    assert out["engine_ok"] == (name == "engine_as_built"), out
    if name in ("engine_as_built", "engine_stale_tables"):
        assert out["engine_first_state_rel_rms_err"] < (
            driver.FIRST_STATE_TOL / 10), out
    else:  # a bfloat16 tick, a state zeroed at every tick
        assert out["engine_first_state_rel_rms_err"] > (
            10 * driver.FIRST_STATE_TOL), out
