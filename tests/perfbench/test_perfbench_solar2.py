"""The Solar-Open2 configuration against the published one written out, its
parameter arithmetic, the cell's bytes, the stream (lengths of ``order_seed``
and the client alone, ids of the seed), ``flops_kda``'s counts, the five new
readers on hand-made runs, and the driver's reference check at rehearsal size: it passes the engine as
built, and each fault of ``perfbench/probe_solar2.py`` (which puts the same
questions on the chip at the published widths) turns it false."""

import itertools
import os
import types

import numpy as np
import pytest

from perfbench import flops_kda, harness, probe_solar2
from perfbench.drivers import serve_closed_loop_kda as driver
from perfbench.layer_metrics import (_kda, kda_chunk_busy_share,
                                     kda_chunk_roofline, kda_mix_busy_share,
                                     kda_step_busy_share, kda_step_roofline)

CELL = "solar2-l8-serve-docreason-mixed"
CONFIG = "solar-open2-ep16-l8"
BENCH = harness.load_json("BENCHMARK.json")
NEW = ["kda_mix_busy_share", "kda_chunk_busy_share", "kda_step_busy_share",
       "kda_chunk_roofline", "kda_step_roofline"]

# the catalog row's ``config`` (architectures.jsonl beside the model-configs
# guide, ``Solar-Open2-250B``), written out
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
CUT = {"num_hidden_layers": 8, "n_routed_experts": 20, "vocab_size": 24576,
       "gqa_layers": [0, 4]}
PARAMETERS = 3_898_842_752


def _config():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    return entry, harness.load_json(entry["file"])


def test_every_width_is_the_published_one_and_the_cuts_are_the_share():
    entry, data = _config()
    assert entry["source"] == data["source"] == (
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
        "config.json")
    assert entry["reduced"] == data["reduced"] == list(CUT)
    for key, value in PUBLISHED.items():
        assert data[key] == CUT.get(key, value), key
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    model, attn = data["model"], PUBLISHED["linear_attn_config"]
    for mine, theirs in (
            ("hidden_size", 4096), ("num_attention_heads", 64),
            ("num_key_value_heads", 8), ("head_size", 128),
            ("ffn_hidden_size", PUBLISHED["moe_intermediate_size"]),
            ("kda_num_heads", attn["num_heads"]),
            ("kda_head_dim", attn["head_dim"]),
            ("kda_conv_size", attn["short_conv_kernel_size"]),
            ("kda_neg_eigval", True), ("attention_gate", "sigmoid"),
            ("norm_eps", 1e-05), ("top_k", 8), ("num_shared_experts", 1),
            ("num_routed_experts", 320), ("norm_topk_prob", True),
            ("max_position_embeddings", 1048576),
            ("tie_word_embeddings", False)):
        assert model[mine] == theirs, mine
    # the share, in the program's names; use_rope false: no layer rotates
    assert model["num_layers"] == 8 and model["num_experts"] == 20
    assert model["layer_types"] == ["full_attention", "kda", "kda",
                                    "kda"] * 2
    assert [i for i, t in enumerate(model["layer_types"])
            if t == "full_attention"] == CUT["gqa_layers"]
    assert model["rope_layout"] == [0] * 8 and model["num_dense_layers"] == 0
    assert "must fail a bfloat16 S" in data["state_dtype"]
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
    low_rank = 4096 * 128 + 128 * 8192 + 8192
    kda = (3 * 4096 * 8192 + 24576 * 4 + low_rank + 64 + 4096 * 64
           + low_rank + 128 + 8192 * 4096)
    outside = 2 * 4096 + 4096 * 320 + 320 + 3 * 4096 * 1280
    gqa = 3 * 4096 * 8192 + 2 * 4096 * 1024
    expert = 3 * 4096 * 1280
    assert (kda, outside, gqa) == (137_740_480, 17_047_872, 109_051_904)
    period = (gqa + outside + 20 * expert) + 3 * (kda + outside + 20 * expert)
    assert period == 1_848_756_032
    assert 2 * period + 2 * 24576 * 4096 + 4096 == PARAMETERS
    # the whole model by the same count: 250B-A15B
    whole = 12 * (gqa + outside) + 36 * (kda + outside) + 48 * 320 * expert \
        + 2 * 196608 * 4096 + 4096
    active = whole - 48 * 312 * expert
    assert 249e9 < whole < 251e9 and 14e9 < active < 15.5e9
    # (the program's own tree at these widths: tests/test_solar2_serving.py)


def test_the_cell_and_its_traffic_are_the_issues():
    cell = harness.load_cell(CELL)
    deploy, job = cell.deploy, cell.traffic
    assert cell.chips == 1 and job["driver"] == "serve_closed_loop_kda"
    assert job["closed_loop"]["clients"] == deploy["lanes"] in (48, 32)
    assert (deploy["cache_len"], deploy["page_size"]) == (17920, 16)
    assert deploy["cache_len"] == 16384 + 1536 == 35 * 512
    assert deploy["pool_tokens"] == 393216
    assert (deploy["prefill_chunk"], deploy["prefill_bucket"]) == (512, 256)
    assert job["prompt"] == {"dist": "lognormal", "median": 4096,
                             "sigma": 0.7, "min": 1024, "max": 16384}
    assert job["output"] == {"dist": "lognormal", "median": 768, "sigma": 0.4,
                             "min": 256, "max": 1536}
    assert job["block"] == 4 and isinstance(job["order_seed"], int)
    assert job["prompt"]["max"] + job["output"]["max"] <= deploy["cache_len"]
    # the bytes: weights + lane state + the pool over the 2 GQA layers
    model = cell.config["model"]
    state = deploy["lanes"] * 6 * flops_kda.lane_state_bytes(model)
    pool = (deploy["pool_tokens"] // 16 + 1) * 16 * 2 * 4096
    assert flops_kda.lane_state_bytes(model) == 4_194_304 + 147_456
    if deploy["lanes"] == 48:
        assert round(state / 1e9, 2) == 1.25 and round(pool / 1e9, 2) == 3.22
        assert round((2 * PARAMETERS + state + pool) / 1e9, 2) == 12.27
    assert 2 * PARAMETERS + state + pool > 11e9
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert entry["config"] == CONFIG and entry["traffic"] == "docreason-mixed"
    for said in ("tokens an expert and tick", "16x", "8 of 48"):
        assert said in entry["why"], said


def test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved():
    (serve,) = [m for m in BENCH["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    assert serve["workloads"][-2:] == ["longcat-l4-serve-rollout-skewed",
                                       CELL]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == CONFIG
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    for m in mine:
        assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
        assert m["moves"] in ("serve_tokens_per_s", "setup_s")
    names = {m["name"] for m in mine}
    assert set(NEW) | {"state_bytes_share", "attn_gate_busy_share",
                       "moe_experts_roofline", "moe_shared_busy_share",
                       "moe_pairs_here_share", "batch.tick_ms_p50",
                       "batch.decode_kernel_device_share",
                       "setup_compile_s"} <= names
    # left off, each for what a traced run read (PERF.md section 3): two
    # shares whose accepted counts read above 100% here, and four readers
    # that find too few admissions in a traced stretch of 3 s
    assert not names & {
        "prefill_gqa_roofline", "batch.decode_paged_roofline",
        "batch.admit_host_ms_p50", "batch.first_token_queue_ms_p50",
        "batch.first_token_return_ms_p50", "batch.admit_idle_ms_p50"}
    new = BENCH["per_layer"][-5:]
    assert [m["name"] for m in new] == NEW
    assert all(m["workloads"] == [CELL] and m["source"] == "device_trace"
               and m["moves"] == "serve_tokens_per_s" for m in new)
    assert [m["layer"] for m in new] == ["model"] + ["kernels"] * 4
    assert [m["unit"] for m in new] == ["share"] * 3 + ["%"] * 2
    assert len(BENCH["per_layer"]) <= 128 and len(BENCH["workloads"]) == 13
    assert len(BENCH["configs"]) == 11
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


# ------------------------------------------------------------- the stream

def _head(job, seed, client, n, vocab=24576):
    return list(itertools.islice(
        driver.client_stream(job, seed, client, vocab), n))


@pytest.mark.parametrize("tiny", [False, True])
def test_lengths_are_of_order_seed_and_the_client_and_ids_of_the_seed(tiny):
    job = harness.load_cell(CELL, tiny=tiny).traffic
    one, other = _head(job, 1, 0, 8), _head(job, 2, 0, 8)
    assert [(len(r.prompt), r.max_new_tokens) for r in one] == [
        (len(r.prompt), r.max_new_tokens) for r in other]
    assert not np.array_equal(one[0].prompt, other[0].prompt)
    again = _head(job, 1, 0, 8)
    assert all(np.array_equal(a.prompt, b.prompt) for a, b in zip(one, again))
    moved = _head({**job, "order_seed": job["order_seed"] + 1}, 1, 0, 8)
    assert [len(r.prompt) for r in moved] != [len(r.prompt) for r in one]
    lo, hi = job["prompt"]["min"], job["prompt"]["max"]
    assert all(lo <= len(r.prompt) <= hi and r.prompt.min() >= 1
               and r.prompt.max() < 24576 for r in one)
    # a block of 4 holds the four quantiles once each, whatever the order
    assert sorted(len(r.prompt) for r in one[:4]) == sorted(
        len(r.prompt) for r in one[4:])


def test_the_checks_calls_are_the_engines_programs_in_its_order():
    served = types.SimpleNamespace(engine=types.SimpleNamespace(
        prefill_chunk=512, prefill_bucket=256))
    calls = driver.Served.calls
    assert calls(served, driver.CHECK_PROMPT) == [
        (0, 512, 512), (512, 512, 512), (1024, 512, 512), (1536, 512, 512),
        (2048, 192, 256)]
    assert calls(served, 1024) == [(0, 512, 512), (512, 512, 512)]
    # a rest shorter than the tail wanted goes BEFORE the last whole chunk
    assert calls(served, 1030, tail=64) == [(0, 512, 512), (512, 6, 256),
                                            (518, 512, 512)]
    assert calls(served, 1030) == [(0, 512, 512), (512, 512, 512),
                                   (1024, 6, 256)]


# -------------------------------------------------- the counts and readers

def test_the_operations_and_bytes_are_the_recurrences():
    model = harness.load_cell(CELL).config["model"]
    ops, moved = flops_kda.chunk_cost(512, model)
    assert ops == 6 * 512 * 64 * 7 * 128 * 128
    assert moved == 6 * (512 * (5 * 8192 + 64) * 4 + 2 * 4_194_304)
    ops, moved = flops_kda.step_cost(48, model)
    assert ops == 48 * 6 * 64 * 7 * 128 * 128
    assert moved == 48 * 6 * (2 * 4_194_304 + (5 * 8192 + 64) * 4)
    assert round(moved / 1e9, 2) == 2.46          # what a tick moves of state
    tiny = harness.load_cell(CELL, tiny=True).config["model"]
    assert flops_kda.lane_state_bytes(tiny, 4) == 4 * 16 * 16 * 4 + 9 * 64 * 4


def _run(trace=None, spans=(), peaks=None):
    cell = harness.load_cell(CELL)
    return harness.Run(
        cell=cell, device={}, setup_s=1.0, window=(0.0, 40.0), attempted=1,
        failed=0, correct=True, checks={}, samples={}, spans=list(spans),
        counters={}, traced=(30.0, 34.0) if trace else None, trace=trace,
        peaks=peaks)


def test_a_program_without_the_scopes_or_kernels_reports_nothing():
    # an untraced run, and a parent commit's program (no such scope or span)
    for reader in (kda_mix_busy_share, kda_chunk_busy_share,
                   kda_step_busy_share, kda_chunk_roofline,
                   kda_step_roofline):
        assert reader.read(_run()) is None


def test_the_readers_read_scopes_kernels_and_span_fields_on_hand_made_rows(
        monkeypatch):
    from perfbench import peaks
    from perfbench.layer_metrics import _parts

    path = ("jit(_decode_fn)/cached_forward/GPTModel/layers/"
            "layers._decoder_stack/while/body/layer/attn/")
    layers = 8
    rows = ([["fleetx_kda_step.1", path + "kda_mix/kda_step/pallas_call", 0,
              1000 * i, 500] for i in range(layers)]
            + [["fusion.2", path + "kda_mix/KDAMixer/qkv_proj/dot_general", 0,
                20000, 1500],
               ["fleetx_kda_chunk.3", path + "kda_mix/kda_chunk/pallas_call",
                0, 30000, 4000],
               ["fusion.4", path + "HybridSelfAttention/dot_general", 0,
                40000, 2000]])
    monkeypatch.setattr(_parts, "load_xplane", lambda path: {0: rows})
    monkeypatch.setattr(_parts, "_named", lambda rows: rows)
    read = _kda.seconds_of(_parts.load_xplane("made"))
    total = layers * 500 + 1500 + 4000 + 2000
    assert read["total"] == pytest.approx(total / 1e9)
    assert read["mix"] == pytest.approx((total - 2000) / 1e9)
    assert read["step"] == pytest.approx(layers * 500 / 1e9)
    assert read["chunk"] == pytest.approx(4000 / 1e9)
    assert (read["step_kernel_calls"], read["chunk_kernel_calls"]) == (8, 1)
    # the step's roofline: one tick traced (8 calls over 8 layers), 48 lanes
    span = types.SimpleNamespace(name="serving.decode", start_s=31.0,
                                 end_s=31.1, attrs={"state_lanes": 48})
    run = _run({"busy_s": 1.0}, [span], peaks.peaks_for("TPU v5 lite"))
    monkeypatch.setattr(_kda, "seconds", lambda run: read)
    model = run.cell.config["model"]
    least = flops_kda.step_cost(48, model)[1] / 819e9
    assert kda_step_roofline.read(run) == pytest.approx(
        100 * least / (layers * 500 / 1e9), rel=1e-3)
    assert kda_chunk_roofline.read(run) is None       # no span says the rows


# (the cell's ``--tiny`` rehearsal is ``test_perfbench_rehearsal.py``'s
# ``test_tiny_rehearsal_runs_the_cells_control_flow``, which takes every cell)

# ------------------------------------------ the checks at rehearsal size

# the two lower precisions (each reading retraces the check's programs);
# ``python perfbench/probe_solar2.py --seeds 7 --tiny`` plants all six
# here, and the chip's readings are PERF.md's
PLANTED = ("bf16_state", "bf16_router")


@pytest.fixture(scope="module")
def readings():
    cell = harness.load_cell(CELL, tiny=True)
    return dict(probe_solar2.readings(cell, driver, 11, only=PLANTED))


def test_the_reference_check_passes_the_engine_as_built(readings):
    out = readings["as_built"]
    assert out["reference_ok"] and out["layers_ok"] and out["rule_ok"], out
    assert out["reference_positions_checked"] == 12 + 4
    assert out["reference_rms_err"] < 1e-3 * driver.REFERENCE_RMS_TOL * out[
        "reference_logit_std"]
    assert out["rule_state_rel_rms_err"] < 1e-2 * driver.RULE_TOL
    assert 1.0 < out["rule_beta_max"] < 2.0


@pytest.mark.parametrize("fault", PLANTED)
def test_a_planted_fault_turns_the_reference_check_false(readings, fault):
    assert set(PLANTED) <= set(probe_solar2.FAULTS)
    out = readings[fault]
    assert not out["reference_ok"], out
    if fault == "bf16_state":           # the rule's to refuse, and alone
        assert out["rule_state_rel_rms_err"] > 10 * driver.RULE_TOL
        assert out["layers_ok"]
    if fault == "bf16_router":          # the weights' to refuse
        assert out["layer_weight_max_rel_err"] > 10 * driver.LAYER_WEIGHT_TOL
        assert out["rule_ok"]


def test_the_orders_are_replayed_on_this_cells_stream():
    cell = harness.load_cell(CELL)
    rates = probe_solar2.order_rates(cell, [1, 2], 18.0, 51.0, seconds=10.0)
    assert [r["order"] for r in rates] == [1, 2]
    assert all(r["serve_tokens_per_s"] > 0 for r in rates)
    again = probe_solar2.order_rates(cell, [1], 18.0, 51.0, seconds=10.0)
    assert again[0] == rates[0]        # no device, no clock: a replay
