"""The Ling-3.0-flash configuration against the published one written out,
its parameter arithmetic term by term, the cell's bytes, the stream (lengths
of ``order_seed`` and the client alone, ids of the seed, the 15 : 1 mix), the
three new readers on hand-made runs, the traced ``--tiny`` rehearsal of the
new cell (whose checks, at rehearsal size, pass the engine as built), and the
driver's reference check at rehearsal size: it passes the engine as built,
and the lower precisions and the unnormed rotary key that
``perfbench/probe_ling3.py`` plants (which puts the same questions on the
chip at the published widths) turn it false."""

import itertools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import flops_kda, flops_mla, harness, probe_ling3
from perfbench.drivers import serve_closed_loop_ling as driver
from perfbench.layer_metrics import (_scope, latent_bytes_share,
                                     mla_qk_norm_busy_share,
                                     moe_group_tokens_share)

CELL = "ling3-l7-serve-reason-widebatch"
CONFIG = "ling3-flash-ep8-l7"
BENCH = harness.load_json("BENCHMARK.json")
NEW = ["mla_qk_norm_busy_share", "moe_group_tokens_share",
       "latent_bytes_share"]

# the catalog row's ``config`` (architectures.jsonl beside the model-configs
# guide, ``Ling-3.0-flash``), written out
_CLAMP = [0] * 35 + [4] * 7
_SHARED_CLAMP = [0] * 34 + [5] * 6 + [7] * 2
PUBLISHED = {
    "expert_swiglu_limit_list": _CLAMP, "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": _SHARED_CLAMP,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 128, "value_norm": False, "vocab_size": 157184,
    "model_type": "bailing_hybrid"}
CUT = {"num_hidden_layers": 7, "first_k_dense_replace": 1, "num_experts": 64,
       "vocab_size": 19648}
PARAMETERS = 2_866_268_352


def _config():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    return entry, harness.load_json(entry["file"])


def test_every_width_is_the_published_one_and_the_cuts_are_the_share():
    entry, data = _config()
    assert entry["source"] == data["source"] == (
        "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/"
        "config.json")
    assert entry["reduced"] == data["reduced"] == list(CUT)
    for key, value in PUBLISHED.items():
        assert data[key] == CUT.get(key, value), key
    assert data["published"] == {k: PUBLISHED[k] for k in CUT}
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    model = data["model"]
    for mine, theirs in (
            ("hidden_size", 2560), ("num_attention_heads", 32),
            ("head_size", PUBLISHED["head_dim"]),
            ("ffn_hidden_size", PUBLISHED["moe_intermediate_size"]),
            ("dense_ffn_hidden_size", PUBLISHED["intermediate_size"]),
            ("kda_num_heads", 32), ("kda_head_dim", 128),
            ("kda_conv_size", PUBLISHED["short_conv_kernel_size"]),
            ("kda_no_lora", True), ("kda_gate_rank", 0),
            ("kda_safe_gate", True), ("kda_lower_bound", -5.0),
            ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
            ("qk_rope_head_dim", 64), ("v_head_dim", 128),
            ("qk_norm", True), ("qk_norm_scope", "head"),
            ("attention_gate", "sigmoid_head"), ("rope_theta", 6000000.0),
            ("norm_eps", 1e-06), ("top_k", 8), ("n_group", 8),
            ("topk_group", 4), ("num_shared_experts", 1),
            ("num_routed_experts", 512), ("norm_topk_prob", True),
            ("routed_scaling_factor", 2.5), ("use_expert_bias", True),
            ("max_position_embeddings", 262144),
            ("tie_word_embeddings", False)):
        assert model[mine] == theirs, mine
    assert "q_lora_rank" not in model          # q_lora_rank null: no latent
    # the share, in the program's names: one whole period behind layer 0,
    # five KDA to one latent, ONE router group held
    assert model["num_layers"] == 7 and model["num_dense_layers"] == 1
    assert model["layer_types"] == ["kda"] * 4 + ["latent_attention"] + [
        "kda"] * 2
    kept = [0] + list(range(2, 8))             # the published layers kept
    assert [t == "latent_attention" for t in model["layer_types"]] == [
        (i + 1) % PUBLISHED["layer_group_size"] == 0 for i in kept]
    assert (model["num_experts"], model["first_expert_held"]) == (64, 0)
    assert model["num_experts"] == 512 // model["n_group"]
    # the clamp: 0 in every layer kept, and carried so
    assert {_CLAMP[i] for i in kept} == {_SHARED_CLAMP[i] for i in kept} == {0}
    assert model["expert_swiglu_limit"] == 0.0
    assert model["shared_expert_swiglu_limit"] == 0.0
    for said in ("bfloat16 S", "bfloat16 decay", "unnormed rotary key"):
        assert said in data["state_dtype"], said
    letters = [a[:3] for a in data["assumed"]]
    assert letters[:7] == [f"({c})" for c in "abcdefg"]
    assert all("OTHER READING" in a for a in data["assumed"][:4])
    assert any("multi-token-prediction" in d for d in data["departures"])
    assert any("REFUSED" in d for d in data["departures"])
    for key in ("deployment", "departures", "assumed", "sizing", "tiny"):
        assert data[key], key


def test_the_yaml_carries_the_same_model_section():
    from fleetx_tpu.utils.config import get_config

    _, data = _config()
    published = get_config(os.path.join(harness.ROOT, data["train_yaml"]),
                           nranks=1, overrides=["Distributed.dp_degree=1"]).Model
    for key, value in data["model"].items():
        assert published.get(key) == value, key


def test_the_parameter_arithmetic_is_the_issues_term_by_term():
    _, data = _config()
    assert data["parameters"] == PARAMETERS
    assert f"{PARAMETERS:,}" in data["sizing"]
    h, inner = 2560, 32 * 128
    kda = (h * 3 * inner + 3 * inner * 4 + h * inner + inner + 32 + h * 32
           + h * inner + 128 + inner * h)
    latent = (h * 32 * 192 + 192 + h * 576 + 512 + 64 + 512 * 32 * 256
              + h * 32 + inner * h)
    norms, dense = 2 * h, 3 * h * 6144
    router, expert = h * 512 + 512, 3 * h * 768
    assert (kda, latent) == (63_049_888, 31_965_952)
    assert (norms, dense, router, expert) == (5_120, 47_185_920, 1_311_232,
                                              5_898_240)
    outside = norms + router + expert + 64 * expert
    assert kda + norms + dense == 110_240_928
    assert kda + outside == 447_751_840 and latent + outside == 416_667_904
    head = 2 * 19648 * h + h
    assert head == 100_600_320
    assert (kda + norms + dense) + 5 * (kda + outside) + (
        latent + outside) + head == PARAMETERS
    # the whole model by the same count: ~125B-A5.5B
    whole = (35 * kda + 7 * latent + 42 * norms + 2 * dense
             + 40 * (router + expert + 512 * expert) + 2 * 157184 * h + h)
    active = whole - 40 * 504 * expert
    assert 124e9 < whole < 125e9 and 5.4e9 < active < 5.6e9
    # (the program's own tree at these widths: tests/test_ling3_serving.py)


def test_the_cell_and_its_traffic_are_the_issues():
    cell = harness.load_cell(CELL)
    deploy, job = cell.deploy, cell.traffic
    assert cell.chips == 1 and job["driver"] == "serve_closed_loop_ling"
    assert job["closed_loop"]["clients"] == deploy["lanes"] in (128, 96)
    assert str(deploy["lanes"]) in deploy["lanes_choice"]
    assert (deploy["cache_len"], deploy["page_size"]) == (18432, 16)
    assert deploy["cache_len"] == -(-(16384 + 1792) // 512) * 512
    assert deploy["pool_tokens"] == deploy["lanes"] * deploy["cache_len"]
    assert (deploy["prefill_chunk"], deploy["prefill_bucket"]) == (512, 256)
    assert job["prompt"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.6, "min": 256, "max": 4096}
    assert job["document"] == {"every": 16, "prompt": {
        "dist": "lognormal", "median": 12288, "sigma": 0.3, "min": 8192,
        "max": 16384}}
    assert job["output"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.35, "min": 512, "max": 1792}
    assert job["block"] == 4 and isinstance(job["order_seed"], int)
    assert job["trace_s"] == 3.0
    assert (job["document"]["prompt"]["max"] + job["output"]["max"]
            <= deploy["cache_len"])
    # the bytes: weights + the lanes' matrix state + the ONE latent pool
    model = cell.config["model"]
    assert flops_kda.lane_state_bytes(model) == 2_097_152 + 73_728
    assert 6 * flops_kda.lane_state_bytes(model) == 13_025_280
    assert flops_mla.row_bytes(model) == 1280        # as held; 1,152 of values
    state = deploy["lanes"] * 6 * flops_kda.lane_state_bytes(model)
    pool = (deploy["pool_tokens"] // 16 + 1) * 16 * flops_mla.row_bytes(model)
    if deploy["lanes"] == 128:
        assert round(state / 1e9, 2) == 1.67 and round(pool / 1e9, 2) == 3.02
        assert round((2 * PARAMETERS + state + pool) / 1e9, 1) == 10.4
    assert 2 * PARAMETERS + state + pool > 0.25 * 16e9
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert entry["config"] == CONFIG and entry["traffic"] == "reason-widebatch"
    assert len(entry["why"]) <= 200
    for said in ("closed loop", "latent", "KDA", "8x"):
        assert said in entry["why"], said


def test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved():
    (serve,) = [m for m in BENCH["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    assert serve["workloads"][-2:] == ["keyevl2-l6-serve-pagesqa-sparse",
                                       CELL]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == CONFIG
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    for m in mine:
        assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
        assert m["moves"] in ("serve_tokens_per_s", "setup_s")
    names = {m["name"] for m in mine}
    assert set(NEW) | {
        "state_bytes_share", "attn_gate_busy_share", "moe_experts_roofline",
        "moe_shared_busy_share", "moe_pairs_here_share", "batch.tick_ms_p50",
        "kda_mix_busy_share", "kda_chunk_busy_share", "kda_step_busy_share",
        "kda_chunk_roofline", "kda_step_roofline", "mla_decode_roofline",
        "mla_decode_busy_share", "mla_prefill_attn_busy_share",
        "mla_proj_busy_share", "setup_compile_s"} <= names
    # no layer of this stack runs the grouped decode or chunk kernels
    assert not names & {"batch.decode_kernel_device_share",
                        "batch.decode_paged_roofline", "prefill_gqa_roofline"}
    new = BENCH["per_layer"][-3:]
    assert [m["name"] for m in new] == NEW
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "serve_tokens_per_s" for m in new)
    assert [m["source"] for m in new] == ["device_trace", "program_counter",
                                          "program_counter"]
    assert [m["layer"] for m in new] == ["model", "model",
                                         "scheduler and cache"]
    assert len(BENCH["per_layer"]) == 114 and len(BENCH["workloads"]) == 15
    assert len(BENCH["configs"]) == 13
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(json.dumps(BENCH, indent=1)) < 65536


# ------------------------------------------------------------- the stream

def _head(job, seed, client, n, vocab=19648):
    return list(itertools.islice(
        driver.client_stream(job, seed, client, vocab), n))


@pytest.mark.parametrize("tiny", [False, True])
def test_lengths_are_of_order_seed_and_the_client_and_ids_of_the_seed(tiny):
    job = harness.load_cell(CELL, tiny=tiny).traffic
    one, other = _head(job, 1, 0, 8), _head(job, 2 ** 31 + 7, 0, 8)
    assert [(len(r.prompt), r.max_new_tokens, r.tenant) for r in one] == [
        (len(r.prompt), r.max_new_tokens, r.tenant) for r in other]
    assert not np.array_equal(one[0].prompt, other[0].prompt)
    again = _head(job, 1, 0, 8)
    assert all(np.array_equal(a.prompt, b.prompt) for a, b in zip(one, again))
    moved = _head({**job, "order_seed": job["order_seed"] + 1}, 1, 0, 8)
    assert [len(r.prompt) for r in moved] != [len(r.prompt) for r in one]
    assert [len(r.prompt) for r in _head(job, 1, 1, 8)] != [
        len(r.prompt) for r in one]                 # a client's own order
    for r in one:
        spec = (job["document"]["prompt"] if r.tenant == "document"
                else job["prompt"])
        assert spec["min"] <= len(r.prompt) <= spec["max"]
        assert r.prompt.min() >= 1 and r.prompt.max() < 19648
        assert job["output"]["min"] <= r.max_new_tokens <= job["output"]["max"]
    # a block of 4 holds the four quantiles once each, whatever the order
    outputs = [r.max_new_tokens for r in one]
    assert sorted(outputs[:4]) == sorted(outputs[4:])


@pytest.mark.parametrize("tiny", [False, True])
def test_one_request_in_sixteen_carries_a_document_in_every_round(tiny):
    job = harness.load_cell(CELL, tiny=tiny).traffic
    every, clients = job["document"]["every"], job["closed_loop"]["clients"]
    rounds = np.array([[r.tenant == "document" for r in _head(job, 3, c, every)]
                       for c in range(clients)])     # [client, round]
    # every client sends one document in ``every`` requests, and every round
    # of all the clients holds its 1 in ``every``
    assert (rounds.sum(1) == 1).all()
    assert (rounds.sum(0) == clients // every).all()
    assert rounds.mean() == 1 / every
    short = job["prompt"]["max"]
    assert all(len(r.prompt) > short for c in range(3)
               for r in _head(job, 3, c, every) if r.tenant == "document")


# -------------------------------------------------- the readers

def _run(trace=None, counters=None, lanes=None):
    cell = harness.load_cell(CELL)
    if lanes:
        cell.deploy["lanes"] = lanes
    return harness.Run(
        cell=cell, device={}, setup_s=1.0, window=(0.0, 40.0), attempted=1,
        failed=0, correct=True, checks={}, samples={}, spans=[],
        counters=counters or {}, traced=(30.0, 34.0) if trace else None,
        trace=trace, peaks=None)


def test_a_program_without_the_scope_or_the_counters_reports_nothing():
    # an untraced run, and a parent commit's program (no such scope, counter)
    for reader in (mla_qk_norm_busy_share, moe_group_tokens_share,
                   latent_bytes_share):
        assert reader.read(_run()) is None
    # a share that is no whole group counts no group tokens (A.X-K1's)
    assert moe_group_tokens_share.read(_run(counters={
        "moe_tick_layer_calls": 600, "moe_tick_pairs": 4000})) is None
    # a latent family without lane state, a lane-state family without latents
    assert latent_bytes_share.read(_run(counters={
        "latent_pages_in_use": 10, "latent_page_bytes": 20480,
        "state_bytes_lanes": 0})) is None
    assert latent_bytes_share.read(_run(counters={
        "state_bytes_lanes": 1 << 30, "kv_page_bytes_in_use": 1 << 20})) is None


def test_the_counter_readers_on_a_recorded_run():
    """The counters of my chip run, PR 64 (chiprun_out/pr64/
    l128_run_6400000011.log): 128 lanes."""
    counters = {"moe_tick_layer_calls": 22890, "moe_tick_pairs": 3005041,
                "moe_tick_group_tokens": 1478164,
                "state_bytes_lanes": 1667235840,
                "kv_page_bytes_in_use": 339496960,
                "latent_pages_in_use": 16577, "latent_page_bytes": 20480}
    run = _run(counters=counters, lanes=128)
    assert moe_group_tokens_share.read(run) == pytest.approx(
        1478164 / (22890 * 128)) == pytest.approx(0.5045, abs=1e-4)
    assert latent_bytes_share.read(run) == pytest.approx(
        339496960 / (339496960 + 1667235840)) == pytest.approx(0.1692,
                                                               abs=1e-4)


def test_the_scope_reader_on_hand_made_rows(monkeypatch):
    from perfbench.layer_metrics import _parts

    path = ("jit(_decode_fn)/cached_forward/GPTModel/layers/"
            "layers._decoder_stack/while/body/layer/attn/")
    rows = [["fusion.1", path + "mla_proj/mla_qk_norm/RMSNorm/mul", 0, 0, 300],
            ["fusion.2", path + "mla_proj/dot_general", 0, 1000, 1500],
            ["fusion.3", path + "mla_proj/mla_qk_norm", 0, 3000, 200],
            ["fusion.4", path + "kda_mix/mla_qk_normal/dot", 0, 4000, 2000]]
    monkeypatch.setattr(_parts, "_named", lambda rows: rows)
    assert _scope.share_of({0: rows}, "mla_qk_norm") == pytest.approx(
        500 / 4000)
    assert _scope.share_of({0: rows}, "no_such_scope") is None


# --------------------------------------------------- the traced rehearsal

def _listed():
    return [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [CELL])]


# what a ``--tiny --trace 1`` run reports on the CPU, where no reader of
# the device's trace, of its memory or of a peak finds anything
TINY_REPORTS = {"batch.lane_occupancy", "batch.tick_host_ms_p50",
                "batch.tick_ms_p50", "batch.tick_overlap_share",
                "moe_load_max_over_mean", "moe_pairs_here_share",
                "state_bytes_share", "moe_group_tokens_share",
                "latent_bytes_share"}


@pytest.fixture(scope="module")
def traced_rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=harness.ROOT)
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 5), "--seconds", "2",
         "--trace", "1", "--tiny"], cwd=harness.ROOT, env=env,
        capture_output=True, text=True, timeout=900)
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
    assert '"reference_ok": true' in out and '"rule_ok": true' in out
    assert "'moe_tick_group_tokens'" in out and "'latent_pages_in_use'" in out


def test_the_tiny_cell_runs_end_to_end_and_is_correct():
    """The driver itself on the rehearsal cell (``run.py --tiny`` never
    reports a rehearsal as correct: it is no measurement): the loop, both
    checks and the decision, with the kernels' presence waived off the
    chip."""
    import time

    cell = harness.load_cell(CELL, tiny=True)
    run = driver.run(cell, seed=2 ** 31 + 9, seconds=2.0, trace=False,
                     t_process=time.perf_counter())
    assert run.correct and run.checks["correct"], run.checks
    assert run.attempted > 0 and run.failed == 0
    assert run.checks["reference_ok"] and run.checks["engine_ok"]
    assert run.checks["engine_lanes_checked"] >= 1
    spans = [s.attrs for s in run.spans if s.name == "serving.decode"]
    assert spans and all("latent_rows" in a and "state_lanes" in a
                         for a in spans)
    assert run.counters["moe_tick_group_tokens"] > 0
    assert latent_bytes_share.read(run) is not None


# ------------------------------------------ the checks at rehearsal size

# the lower precisions and the unnormed key (each reading retraces the
# check's programs); ``python perfbench/probe_ling3.py --seeds 7 --tiny``
# plants all eight here, and the chip's readings are PERF.md's
PLANTED = ("bf16_state", "bf16_decay", "unnormed_rotary_key")


@pytest.fixture(scope="module")
def readings():
    cell = harness.load_cell(CELL, tiny=True)
    return dict(probe_ling3.readings(cell, driver, 11, only=PLANTED))


def test_the_reference_check_passes_the_engine_as_built(readings):
    out = readings["as_built"]
    assert out["reference_ok"] and out["layers_ok"] and out["rule_ok"], out
    assert out["reference_positions_checked"] == 12 + 4
    assert out["reference_rms_err"] < 1e-3 * driver.REFERENCE_RMS_TOL * out[
        "reference_logit_std"]
    assert out["rule_state_rel_rms_err"] < 0.1 * driver.RULE_TOL
    assert out["rule_beta_max"] < 1.0 and out["rule_log_decay_min"] >= -5.0


@pytest.mark.parametrize("fault", PLANTED)
def test_a_planted_fault_turns_the_reference_check_false(readings, fault):
    assert set(PLANTED) <= set(probe_ling3.FAULTS)
    out = readings[fault]
    assert not out["reference_ok"], out
    if fault.startswith("bf16"):        # the rule's to refuse, and alone
        assert max(out["rule_state_rel_rms_err"],
                   out["rule_output_rel_rms_err"]) > 5 * driver.RULE_TOL
        assert out["layers_ok"]
    else:                               # the latent rows' to refuse
        assert out["rule_ok"] and out["layers_ok"]
        assert out["reference_rotary_key_rel_rms_err"] > (
            10 * driver.REFERENCE_ROWS_TOL)
