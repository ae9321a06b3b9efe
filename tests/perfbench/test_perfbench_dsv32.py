"""The DeepSeek-V3.2 configuration against the published one written out, its
parameter count against the program's own model, the cell and its traffic's
quantiles, ``flops_dsa`` on hand-worked cases, the readers of the new spans,
scopes and kernel on hand-made runs, the traced ``--tiny`` rehearsal of the
new cell, and the driver's checks at rehearsal size: the reference check
passes the engine as built, each fault of ``perfbench/probe_dsv32.py`` (which
puts the same questions on the chip at the published widths) turns it
false, and the engine's own programs are held to the checked ones."""

import itertools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import flops, flops_dsa, harness, probe_dsv32, traffic
from perfbench.drivers import docqa_stream
from perfbench.drivers import serve_closed_loop_dsa as driver
from perfbench.layer_metrics import (_dsa, _mla, dsa_attn_busy_share,
                                     dsa_decode_roofline, dsa_index_busy_share,
                                     dsa_prefill_roofline,
                                     dsa_select_busy_share,
                                     dsa_selected_rows_share)

CELL = "dsv32-l5-serve-longqa-sparse"
BENCH = harness.load_json("BENCHMARK.json")
# deepseek-ai/DeepSeek-V3.2, config.json (catalog architectures.jsonl),
# written out: the source's key and its value
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}
CUT = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
       "n_routed_experts": 16, "vocab_size": 16160}
# the model group's key for a source's key where the two differ
MINE = {"intermediate_size": "dense_ffn_hidden_size",
        "moe_intermediate_size": "ffn_hidden_size",
        "num_hidden_layers": "num_layers", "rms_norm_eps": "norm_eps",
        "n_routed_experts": "num_experts", "num_experts_per_tok": "top_k",
        "first_k_dense_replace": "num_dense_layers",
        "n_shared_experts": "num_shared_experts"}
SAME = ("hidden_size", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "max_position_embeddings",
        "num_attention_heads", "n_group", "topk_group", "norm_topk_prob",
        "routed_scaling_factor", "tie_word_embeddings", "vocab_size",
        "index_n_heads", "index_head_dim", "index_topk")
LANES = 6   # (8 unless the builder's run passed the limits: it did)


def _config():
    entry = next(c for c in BENCH["configs"] if c["name"] == "dsv32-ep16-l5")
    return entry, harness.load_json(entry["file"])


def test_every_width_is_the_published_one_and_four_keys_are_cut():
    entry, data = _config()
    model = data["model"]
    for key, value in PUBLISHED.items():
        want = CUT.get(key, value)
        assert data[key] == want, key
        if key in MINE or key in SAME:
            assert model[MINE.get(key, key)] == want, key
    assert sorted(entry["reduced"]) == sorted(data["reduced"]) == sorted(CUT)
    assert data["published"] == {k: PUBLISHED[k] for k in CUT}
    # no width is cut: reduced names depth, dense layers, held experts, vocab
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # the router keeps its published width; the chip holds experts 0-15
    assert model["num_routed_experts"] == 256
    assert (model["first_expert_held"], model["num_experts"]) == (0, 16)
    assert model["rope_scaling_factor"] == 40.0
    assert model["use_expert_bias"] is True        # topk_method noaux_tc
    assert model["layer_types"] == ["latent_attention"] * 5
    assert model["family"] == "dsv32" and data["reference"] == "dsv32_f32"
    for text in ("Hadamard", "FP8"):
        assert text in data["departures"][0]
    assert "multi-token-prediction" in data["departures"][1]
    assert len(data["assumed"]) == 4 and "16 chips" in data["deployment"]
    tiny = data["tiny"]["model"]
    assert (tiny["index_n_heads"], tiny["index_head_dim"],
            tiny["index_topk"]) == (4, 16, 24)


def test_the_parameter_count_is_the_programs_own_models():
    import jax

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    _, data = _config()
    model = GPTForPretraining(GPTConfig.from_model_config(data["model"]))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    counted = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    h, layers = 7168, 5
    mla = (h * 1536 + 1536 * 128 * 192 + h * 576 + 512 * 128 * 256
           + 128 * 128 * h)
    assert mla == 187_105_280
    indexer = 1536 * 64 * 128 + h * 128 + h * 64
    assert indexer == 13_959_168
    attention = mla + 1536 + 512 + indexer + 2 * 128   # norms, the LayerNorm
    expert = 3 * h * 2048
    assert 3 * h * 18432 == 396_361_728 and expert == 44_040_192
    by_hand = (layers * (attention + 2 * h)          # + the two layer norms
               + 3 * h * 18432                        # the leading dense layer
               + 4 * (h * 256 + 256 + 16 * expert + expert)
               + h + 2 * 16160 * h)                   # final norm, embed, head
    assert counted == by_hand == data["parameters"] == 4_635_518_208
    assert 9.27e9 < 2 * counted < 9.28e9
    # a cached row: 512 + 128 (k_r in its tile) + 128 (kI), bfloat16, 5 layers
    assert (512 + 128 + 128) * 2 * layers == 7680


def test_the_cell_and_its_traffic_are_the_issues():
    cell = harness.load_cell(CELL)
    job, deploy = cell.traffic, cell.deploy
    assert cell.chips == 1 and job["driver"] == "serve_closed_loop_dsa"
    assert "clients" not in job
    assert job["closed_loop"]["clients"] == deploy["lanes"] == LANES
    assert "6 clients and lanes and nothing else changed" in deploy["about"]
    assert job["document"] == {"dist": "lognormal", "median": 32768,
                               "sigma": 0.3, "min": 16384, "max": 49152}
    assert job["question"] == {"dist": "uniform", "min": 64, "max": 256}
    assert job["output"] == {"dist": "lognormal", "median": 192,
                             "sigma": 0.35, "min": 64, "max": 384}
    assert (job["questions"], job["block"], job["order_seed"], job["page"],
            job["trace_s"]) == (4, 4, 1, 16, 3.0)
    assert (deploy["cache_len"], deploy["page_size"], deploy["prefill_chunk"],
            deploy["prefill_bucket"]) == (50176, 16, 512, 256)
    assert deploy["cache_len"] >= 49152 + 256 + 384
    assert deploy["cache_len"] % 1024 == 0          # whole key blocks
    assert deploy["pool_tokens"] >= LANES * deploy["cache_len"]
    # every query has at least 8 times index_topk rows behind it
    assert job["document"]["min"] == 8 * cell.config["model"]["index_topk"]
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    listed = {m["name"] for m in cell.per_layer}
    assert {"dsa_index_busy_share", "dsa_select_busy_share",
            "dsa_attn_busy_share", "dsa_selected_rows_share",
            "dsa_prefill_roofline", "dsa_decode_roofline",
            "mla_proj_busy_share", "mla_decode_busy_share",
            "moe_shared_busy_share", "moe_pairs_here_share",
            "moe_experts_roofline", "prefix_tokens_saved_share",
            "batch.tick_ms_p50", "batch.hbm_peak_gb"} <= listed
    # (it counts every live row at the configuration's heads: not read here)
    assert not {"mla_decode_roofline", "mla_prefill_attn_busy_share",
                "state_bytes_share"} & listed


def test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved():
    (serve,) = [m for m in BENCH["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    assert serve["workloads"][-2:] == ["axk1-l6-serve-docqa-latent", CELL]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == "dsv32-ep16-l5"
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    for m in mine:
        assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
        assert m["moves"] == "serve_tokens_per_s"
    new = [m["name"] for m in BENCH["per_layer"][-6:]]
    assert new == ["dsa_index_busy_share", "dsa_select_busy_share",
                   "dsa_attn_busy_share", "dsa_selected_rows_share",
                   "dsa_prefill_roofline", "dsa_decode_roofline"]
    assert all(m["workloads"] == [CELL] for m in BENCH["per_layer"][-6:])
    assert all("workloads" in m for m in BENCH["per_layer"])
    assert len(BENCH["per_layer"]) <= 128 and len(BENCH["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


# ------------------------------------------------------------- the stream

def _head(job, seed, client, n, vocab=16160):
    return list(itertools.islice(
        docqa_stream.client_stream(job, seed, client, vocab), n))


@pytest.mark.parametrize("tiny", [False, True])
def test_the_stream_is_a_function_of_the_seed_for_tokens_alone(tiny):
    job = harness.load_cell(CELL, tiny=tiny).traffic
    a, b, c = (_head(job, seed, 2, 6) for seed in (7, 7, 2 ** 31 + 11))
    sizes = lambda rs: [(len(r.prompt), r.max_new_tokens, r.tenant)  # noqa: E731
                        for r in rs]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert sizes(a) == sizes(c)                    # the seed draws no length
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    assert all(1 <= r.prompt.min() and r.prompt.max() < 16160 for r in a)


def test_the_documents_hold_their_quantiles_and_fit_the_cache():
    cell = harness.load_cell(CELL)
    job = cell.traffic
    order = np.random.default_rng(1)
    docs = [docqa_stream.document_pages(job, n) for _ in range(64)
            for n in traffic.stratified_lengths(order, job["document"], 4)]
    assert all(16384 <= n <= 49152 and n % 16 == 0 for n in docs)
    assert 30000 < np.median(docs) < 35500          # median 32,768
    low, high = np.percentile(docs, [10, 90])
    assert 20000 < low < 24500 and 44000 < high <= 49152  # sigma 0.3, clipped
    for client in range(LANES):
        requests = _head(job, 5, client, 9)
        first = 4 - client % 4
        assert [r.tenant for r in requests[:first]] == [
            f"doc0.q{q}" for q in range(4 - first, 4)]
        for r in requests:
            assert len(r.prompt) + r.max_new_tokens <= cell.deploy["cache_len"]
            assert len(r.prompt) >= 16384 + 64


# ------------------------------------------------ operations and bytes

def test_flops_dsa_on_hand_worked_cases():
    model = harness.load_cell(CELL).config["model"]
    # a (query, key row) pair of the indexer: 64 heads x 128, 256 B a key
    ops, bytes_ = flops_dsa.index_cost(1, model)
    assert (ops, bytes_) == (2 * 64 * 128, 256.0) == (16384, 256.0)
    # a chunk at 32k rows: 512 x 32,768 pairs = 0.27 TFLOP a layer
    assert flops_dsa.index_cost(512 * 32768, model)[0] == pytest.approx(
        0.275e12, rel=0.01)
    # a chosen row of a tick: 128 heads x (576 + 512) x 2 on 1,280 B
    ops, bytes_ = flops_dsa.sparse_decode_cost(1, model)
    assert (ops, bytes_) == (128 * 1088 * 2, 1280.0)
    # 218 FLOP a byte: under the v5e's ridge of 240, the bytes bound a tick
    assert round(ops / bytes_) == 218
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ops, bytes_ = flops_dsa.sparse_decode_cost(6 * 2048, model)
    assert flops.roofline_seconds(ops, bytes_, peaks) == (
        pytest.approx(6 * 2048 * 1280 / 819e9), "memory")
    # a chunk's pair in the cheaper form: 128 heads x (128 + 64 + 128) x 2,
    # no bytes (how many rows the chunk's queries share is not in the spans)
    assert flops_dsa.sparse_chunk_cost(1, model) == (128 * 320 * 2, 0.0)
    assert flops_dsa.sparse_chunk_cost(512 * 2048, model)[0] == pytest.approx(
        0.0859e12, rel=0.01)


# ----------------------------------------------------------- the readers

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


READERS = (dsa_index_busy_share, dsa_select_busy_share, dsa_attn_busy_share,
           dsa_selected_rows_share, dsa_prefill_roofline, dsa_decode_roofline)


def test_a_program_without_the_spans_scopes_or_kernel_reports_nothing():
    """What the parent commit's program gives the new readers: no field on
    its spans, no trace; none raises and each leaves its metric out."""
    bare = _run([_span("serving.decode", 1.0, batch=3, latent_rows=900),
                 _span("serving.admit", 2.0, prompt_len=9)])
    for reader in READERS:
        assert reader.read(bare) is None
    traced = _run(bare.spans, trace={"busy_s": 1.0}, traced=(0.0, 3.0))
    for reader in READERS:   # no trace file either, no field
        assert reader.read(traced) is None
    # A.X-K1's program in this cell's place: its scopes, none of these
    rows = [["%fusion.1 = ...", "jit(f)/layer/attn/mla_proj/dot", "jit_f", 0,
             100]]
    read = _dsa.seconds_of({"/device:TPU:0": rows})
    assert read["total"] > 0 and not any(read[s] for s in _dsa.SCOPES)
    assert read["kernel_calls"] == 0


def test_span_fields_scopes_and_the_kernel_on_hand_made_rows(monkeypatch):
    spans = [_span("serving.decode", 1.0, latent_rows=180_000,
                   selected_rows=12_288, index_rows=180_000),
             _span("serving.decode", 2.0, latent_rows=204_000,
                   selected_rows=12_288, index_rows=204_000),
             _span("serving.decode", 11.0, latent_rows=1, selected_rows=1),
             _span("serving.prefill_chunk", 1.5, latent_rows=33_280,
                   selected_rows=512 * 2048, index_rows=512 * 33_024),
             _span("serving.admit", 1.7, selected_rows=256 * 2048)]
    run = _run(spans, trace={"busy_s": 1.0}, traced=(0.5, 2.2))
    # 2,048 of 32,000 rows a lane: the indexer prunes to 0.064
    assert dsa_selected_rows_share.read(run) == pytest.approx(
        24_576 / 384_000)
    scope = "jit(f)/cached_forward/_decoder_stack/while/body/layer/attn/"
    rows = [  # [instruction text, op_name, program, start_ns, dur_ns]
        ["%fusion.1 = ...", scope + "dsa_index/dot", "jit_f", 0, 100],
        ["%fusion.2 = ...", scope + "attn_full/dsa_index/while/body/dot",
         "jit_f", 100, 150],
        ["%sort.3 = ...", scope + "attn_full/dsa_select/sort", "jit_f", 250,
         50],
        ["%gather.4 = ...", scope + "attn_full/dsa_attn/gather", "jit_f", 300,
         40],
        ["%fleetx_dsa_prefill.5 = custom-call", scope + "attn_full/dsa_attn/x",
         "jit_f", 340, 360],
        ["%fleetx_mla_decode_paged.6 = custom-call", scope + "attn_full/x",
         "jit_f", 700, 100],
        ["%fusion.7 = ...", scope + "mla_proj/dot", "jit_f", 800, 200]]
    read = _dsa.seconds_of({"/device:TPU:0": rows})
    assert read["total"] == pytest.approx(1e-6)
    assert read["dsa_index"] == pytest.approx(0.25e-6)
    assert read["dsa_select"] == pytest.approx(0.05e-6)
    assert read["dsa_attn"] == pytest.approx(0.40e-6)   # gather + the kernel
    assert (read["kernel"], read["kernel_calls"]) == (pytest.approx(0.36e-6), 1)
    monkeypatch.setattr(_dsa, "seconds", lambda run: read)
    assert dsa_index_busy_share.read(run) == pytest.approx(0.25)
    assert dsa_select_busy_share.read(run) == pytest.approx(0.05)
    assert dsa_attn_busy_share.read(run) == pytest.approx(0.40)
    # 10 calls (5 layers x 2 prefill programs) of (512 + 256) / 2 x 2,048
    # pairs at the mean: 128 x 320 x 2 operations a pair at the chip's peak
    read.update(kernel=10 * 30e-3, kernel_calls=10)
    least = 384 * 2048 * 128 * 320 * 2 / 197e12
    assert dsa_prefill_roofline.read(run) == pytest.approx(
        100 * least / 30e-3)
    assert dsa_prefill_roofline.read(run) < 100
    # the tick's kernel over the compact pool: 12,288 chosen rows a call
    mla = _mla.seconds_of({"/device:TPU:0": rows})
    mla.update(kernel=10 * 40e-6, kernel_calls=10)
    monkeypatch.setattr(_mla, "seconds", lambda run: mla)
    assert dsa_decode_roofline.read(run) == pytest.approx(
        100 * (12_288 * 1280 / 819e9) / 40e-6)
    assert dsa_decode_roofline.read(run) < 100


# --------------------------------------------------- the traced rehearsal

def _listed():
    return [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [CELL])]


# what a ``--tiny --trace 1`` run reports on the CPU, where no reader of
# the device's trace, of its memory or of a peak finds anything
TINY_REPORTS = {"batch.admit_host_ms_p50", "batch.lane_occupancy",
                "batch.tick_host_ms_p50", "batch.tick_ms_p50",
                "batch.tick_overlap_share", "moe_load_max_over_mean",
                "moe_pairs_here_share", "prefix_tokens_saved_share",
                "dsa_selected_rows_share"}


@pytest.fixture(scope="module")
def traced_rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=harness.ROOT)
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 5), "--seconds", "2",
         "--trace", "1", "--tiny"], cwd=harness.ROOT, env=env,
        capture_output=True, text=True, timeout=600)
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
    assert '"reference_ok": true' in out and '"selection_ok": true' in out
    assert "'index_pool_bytes'" in out and "'rows_selected'" in out


# ------------------------------------------ the checks at rehearsal size

@pytest.fixture(scope="module")
def probe_cell():
    cell = harness.load_cell(CELL, tiny=True)
    cell.deploy.update(pool_tokens=3 * cell.deploy["cache_len"])
    return cell


@pytest.fixture(scope="module")
def readings(probe_cell):
    return dict(probe_dsv32.readings(
        probe_cell, driver, 11,
        faults=probe_dsv32.FAULTS + probe_dsv32.CPU_FAULTS
        + probe_dsv32.READINGS))


def test_the_reference_check_passes_the_engine_as_built(readings):
    out = readings["as_built"]
    assert out["reference_ok"] and out["layers_ok"] and out["selection_ok"]
    assert out["hit_matched_tokens"] == 128 and out["cold_matched_tokens"] == 0
    assert out["reference_positions_checked"] == 16 + 4
    assert out["hit_cold_logit_rms_diff"] == 0.0 and out[
        "hit_cold_same_choices"]
    # the document is several index_topk long: 24 of 128-148 rows attended
    assert out["sets_rows_attended_mean"] == 24.0 and out["sets_sizes_right"]
    assert out["sets_shared_min"] == 1.0
    assert out["index_rel_rms_err"] < 1e-3 * driver.INDEX_TOL
    assert out["reference_rms_err"] < 1e-3 * driver.REFERENCE_RMS_TOL * out[
        "reference_logit_std"]
    assert max(out["reference_ckv_rel_rms_err"],
               out["reference_kr_rel_rms_err"],
               out["reference_ki_rel_rms_err"]) < 1e-3 * driver.REFERENCE_ROWS_TOL


# (at the rehearsal's float32 and its 148 keys a chunk's scores rounded to
# bfloat16 move nothing past a limit: that reading is the chip's to take)
@pytest.mark.parametrize("fault", [
    f for f in probe_dsv32.FAULTS + probe_dsv32.CPU_FAULTS
    if not f.startswith("bf16")])
def test_a_planted_fault_turns_the_reference_check_false(readings, fault):
    out = readings[fault]
    assert not out["reference_ok"], out
    if fault in ("relu_left_out", "head_weights_left_out"):
        assert out["layers_ok"] and not out["selection_ok"]
        assert out["index_rel_rms_err"] > 10 * driver.INDEX_TOL
        assert out["sets_shared_min"] < driver.SET_SHARE_TOL
    if fault == "index_key_unrotated":   # the third leaf's rows, alone
        assert out["reference_ki_rel_rms_err"] > 2 * driver.REFERENCE_ROWS_TOL
        assert max(out["reference_ckv_rel_rms_err"],
                   out["reference_kr_rel_rms_err"]) < driver.REFERENCE_ROWS_TOL
    if fault == "tick_ignores_selection":  # the decode steps' logits
        assert out["layers_ok"]
        assert out["reference_decode_rms_err"] > (
            2 * driver.REFERENCE_RMS_TOL * out["reference_logit_std"])
    if fault == "bias_in_weights":
        assert out["selection_ok"] and not out["layers_ok"]
        assert out["layer_weight_max_rel_err"] > 10 * driver.LAYER_WEIGHT_TOL
    if fault == "unheld_pair_computed":
        assert out["layer_output_rel_rms_err"] > 10 * driver.LAYER_OUTPUT_TOL
    if fault == "selects_from_unseen":
        assert not out["selection_ok"]


@pytest.fixture(scope="module")
def engine_readings(probe_cell, readings):
    return dict(probe_dsv32.engine_readings(
        probe_cell, driver, 11, readings["as_built"]["reference_logit_std"]))


@pytest.mark.parametrize("name", probe_dsv32.ENGINE_FAULTS)
def test_the_engines_own_programs_are_held_to_the_checked_ones(
        engine_readings, name):
    out = engine_readings[name]
    assert out["engine_lanes_checked"] == 3
    assert out["engine_ok"] == (name == "engine_as_built"), out
    if name != "engine_as_built":  # (the dense tick: 0.33 at this size)
        assert out["engine_rows_max_rel_rms_err"] > 1.5 * driver.ENGINE_ROWS_TOL
