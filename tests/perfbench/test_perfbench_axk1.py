"""The A.X-K1 configuration against the published one written out, its
parameter count against the program's own model, the stream of documents
and questions, ``flops_mla`` on hand-worked cases, the readers of the new
spans, counters, scopes and kernel on hand-made runs, the traced ``--tiny``
rehearsal of the new cell, and the driver's checks at rehearsal size: the
reference check passes the engine as built, and each fault of
``perfbench/probe_axk1.py`` (which puts the same questions on the chip at
the published widths) turns it false; and the check of the engine's own
programs on the requests in flight."""

import itertools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import flops, flops_mla, harness, probe_axk1
from perfbench.drivers import docqa_stream
from perfbench.drivers import serve_closed_loop_mla as driver
from perfbench.layer_metrics import (_mla, mla_decode_busy_share,
                                     mla_decode_roofline,
                                     mla_prefill_attn_busy_share,
                                     mla_proj_busy_share, moe_pairs_here_share,
                                     moe_shared_busy_share)

CELL = "axk1-l6-serve-docqa-latent"
BENCH = harness.load_json("BENCHMARK.json")
# skt/A.X-K1, config.json (catalog architectures.jsonl), written out: the
# source's key and its value
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 8, "n_routed_experts": 192, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 64, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096, "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
    "v_head_dim": 128, "vocab_size": 163840}
CUT = {"num_hidden_layers": 6, "n_routed_experts": 12, "vocab_size": 20480}
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
        "routed_scaling_factor", "tie_word_embeddings", "vocab_size")


def _config():
    entry = next(c for c in BENCH["configs"] if c["name"] == "axk1-ep16-l6")
    return entry, harness.load_json(entry["file"])


def test_every_width_is_the_published_one_and_three_keys_are_the_share():
    entry, data = _config()
    model = data["model"]
    for key, value in PUBLISHED.items():
        want = CUT.get(key, value)
        assert data[key] == want, key
        if key in MINE or key in SAME:
            assert model[MINE.get(key, key)] == want, key
    assert sorted(entry["reduced"]) == sorted(data["reduced"]) == sorted(CUT)
    assert data["published"] == {k: PUBLISHED[k] for k in CUT}
    # the router keeps its published width; the chip holds experts 0-11
    assert model["num_routed_experts"] == 192
    assert model["first_expert_held"] == 0 and model["gate"] == "sigmoid_topk"
    assert model["layer_types"] == ["latent_attention"] * 6
    yarn = PUBLISHED["rope_scaling"]
    assert (model["rope_scaling_factor"], model["rope_scaling_beta_fast"],
            model["rope_scaling_beta_slow"], model["rope_scaling_mscale"],
            model["rope_scaling_mscale_all_dim"],
            model["rope_scaling_original_max_position"]) == (
        yarn["factor"], yarn["beta_fast"], yarn["beta_slow"], yarn["mscale"],
        yarn["mscale_all_dim"], yarn["original_max_position_embeddings"])
    assert "16 chips share each layer" in data["deployment"]
    assert len(data["assumed"]) == 2 and "topk_method" in data["assumed"][0]
    assert "rotary" in data["assumed"][1]
    assert (data["compute_dtype"], data["weight_dtype"]) == ("bfloat16",) * 2
    assert data["source"] == entry["source"] and data["reference"] == "axk1_f32"
    # the floors of a share: four layers after the dense one, eight experts,
    # an eighth of the vocabulary
    assert model["num_layers"] - model["num_dense_layers"] >= 4
    assert model["num_experts"] >= 8
    assert model["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_yaml_carries_the_same_model_section():
    import yaml

    _, data = _config()
    with open(os.path.join(harness.ROOT, data["train_yaml"])) as f:
        section = yaml.safe_load(f)["Model"]
    for key, value in data["model"].items():
        assert section[key] == value, key


def test_the_parameter_count_is_the_programs_own_models():
    """``flops.gpt_param_count`` counts the GPT-2 block only; here the count
    is written out from the widths and held to the program's model."""
    import jax

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    _, data = _config()
    model = GPTForPretraining(GPTConfig.from_model_config(data["model"]))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    counted = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    h, layers = 7168, 6
    attention = (h * 1536 + 1536 + 1536 * 64 * 192 + h * 576 + 512
                 + 512 * 64 * 256 + 64 * 128 * h)
    assert attention - 1536 - 512 == 101_122_048
    expert = 3 * h * 2048
    by_hand = (layers * (attention + 2 * h)          # + the two layer norms
               + 3 * h * 18432                        # the leading dense layer
               + 5 * (h * 192 + 12 * expert + expert)  # router, held, shared
               + h + 2 * 20480 * h)                   # final norm, embed, head
    assert counted == by_hand == 4_166_294_528
    assert 8.33e9 < 2 * counted < 8.34e9


def test_the_cell_and_its_traffic_are_the_issues():
    cell = harness.load_cell(CELL)
    job, deploy = cell.traffic, cell.deploy
    assert cell.chips == 1 and job["driver"] == "serve_closed_loop_mla"
    # 24 were asked for unless the cached set-up passed 95 s: it did
    assert "clients" not in job
    assert job["closed_loop"]["clients"] == deploy["lanes"] == 16
    assert job["document"] == {"dist": "lognormal", "median": 12288,
                               "sigma": 0.4, "min": 8192, "max": 24576}
    assert job["question"] == {"dist": "uniform", "min": 64, "max": 256}
    assert job["output"] == {"dist": "lognormal", "median": 192,
                             "sigma": 0.35, "min": 64, "max": 384}
    assert (job["questions"], job["block"], job["order_seed"], job["page"],
            job["trace_s"]) == (4, 4, 1, 16, 3.0)
    assert (deploy["cache_len"], deploy["page_size"], deploy["pool_tokens"],
            deploy["prefill_chunk"], deploy["prefill_bucket"]) == (
        25600, 16, 24 * 25600, 512, 256)
    assert deploy["cache_len"] >= 24576 + 256 + 384
    assert deploy["cache_len"] % deploy["prefill_chunk"] == 0
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    listed = {m["name"] for m in cell.per_layer}
    assert {"mla_decode_roofline", "mla_decode_busy_share",
            "mla_prefill_attn_busy_share", "mla_proj_busy_share",
            "moe_shared_busy_share", "moe_pairs_here_share",
            "moe_experts_roofline", "prefix_tokens_saved_share"} <= listed
    assert not {"state_bytes_share", "batch.decode_paged_roofline"} & listed


def test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved():
    """``test_perfbench_jamba2.py`` wants the Jamba2 cell last in the lists
    it joined; it runs on the benchmark as it stood when it was written
    (``tests/conftest.py`` ``_WRITTEN_BEFORE``), and this holds the lists as
    they are since this cell joined them."""
    (serve,) = [m for m in BENCH["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    assert serve["workloads"][-2:] == ["jamba2-3b-serve-chat-peak", CELL]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == "axk1-ep16-l6"
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    for m in mine:
        assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
        assert m["moves"] == "serve_tokens_per_s"
    new = [m["name"] for m in BENCH["per_layer"][-6:]]
    assert new == ["mla_decode_roofline", "mla_decode_busy_share",
                   "mla_prefill_attn_busy_share", "mla_proj_busy_share",
                   "moe_shared_busy_share", "moe_pairs_here_share"]
    assert all(m["workloads"] == [CELL] for m in BENCH["per_layer"][-6:])
    assert len(BENCH["per_layer"]) <= 128 and len(BENCH["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


# ------------------------------------------------------------- the stream

def _head(job, seed, client, n, vocab=20480):
    return list(itertools.islice(
        docqa_stream.client_stream(job, seed, client, vocab), n))


def _sizes(requests):
    return [(len(r.prompt), r.max_new_tokens, r.tenant) for r in requests]


@pytest.mark.parametrize("tiny", [False, True])
def test_the_stream_is_a_function_of_the_seed_for_tokens_and_of_order_seed_for_lengths(
        tiny):
    job = harness.load_cell(CELL, tiny=tiny).traffic
    a, b, c = (_head(job, seed, 2, 9) for seed in (7, 7, 2 ** 31 + 11))
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert _sizes(a) == _sizes(c)                  # the seed draws no length
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    assert _sizes(a) != _sizes(_head(dict(job, order_seed=2), 7, 2, 9))
    assert _sizes(a) != _sizes(_head(job, 7, 3, 9))    # a client's own order
    for r in a:
        assert r.prompt.dtype == np.int32
        assert 1 <= r.prompt.min() and r.prompt.max() < 20480


def test_a_document_is_whole_pages_asked_four_times_and_the_first_fewer():
    job = harness.load_cell(CELL).traffic
    for client in range(5):
        requests = _head(job, 5, client, 10)
        first = 4 - client % 4
        names = [r.tenant for r in requests]
        assert names[:first] == [f"doc0.q{q}" for q in range(4 - first, 4)]
        assert names[first:first + 4] == [f"doc1.q{q}" for q in range(4)]
        by_doc = {}
        for r in requests:
            by_doc.setdefault(r.tenant.split(".")[0], []).append(r)
        for asked in by_doc.values():
            shortest = min(len(r.prompt) for r in asked)
            document = max(n for n in range(8192, shortest, 16)
                           if all(np.array_equal(r.prompt[:n],
                                                 asked[0].prompt[:n])
                                  for r in asked)) if len(asked) > 1 else None
            for r in asked:
                own = len(r.prompt) - (document or 0)
                assert 64 <= r.max_new_tokens <= 384
                if document:
                    assert document % 16 == 0 and 8192 <= document <= 24576
                    assert 64 <= own <= 256
                assert len(r.prompt) + r.max_new_tokens <= 25600
    assert docqa_stream.document_pages(job, 8100) == 8192
    assert docqa_stream.document_pages(job, 12295) == 12288
    assert docqa_stream.document_pages(job, 99999) == 24576


# ------------------------------------------------ operations and bytes

def test_flops_mla_on_hand_worked_cases():
    model = harness.load_cell(CELL).config["model"]
    assert flops_mla.widths(model) == (64, 512, 64, 128, 128)
    # a row as the two leaves hold it: 512 + a 128-lane tile, bfloat16
    assert flops_mla.row_bytes(model) == 1280
    ops, bytes_ = flops_mla.decode_cost(1000, model)
    assert ops == 1000 * 64 * (576 + 512) * 2 == 139_264_000
    assert bytes_ == 1_280_000
    # 109 FLOP a byte as held (121 on the 1,152 bytes of values): under the
    # v5e's ridge of 240, so the bytes bound the kernel
    assert round(ops / bytes_) == 109
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.roofline_seconds(ops, bytes_, peaks)[:2]
    assert least == pytest.approx(bytes_ / 819e9)
    # re-expansion: 2 x 512 x 16,384 a cached row, chunk and layer
    assert flops_mla.reexpansion_cost(1, model) == 2 * 512 * 64 * 256
    assert flops_mla.reexpansion_cost(1, model) == 16_777_216
    # a chunk of 512 over one key: 512 x 64 x (192 + 128) x 2
    assert flops_mla.chunk_attention_cost(512, 1, model) == 20_971_520
    tiny = {"num_attention_heads": 4, "kv_lora_rank": 32,
            "qk_rope_head_dim": 8, "qk_nope_head_dim": 16, "v_head_dim": 16}
    assert flops_mla.row_bytes(tiny, itemsize=4) == (32 + 128) * 4
    assert flops_mla.decode_cost(10, tiny)[0] == 10 * 4 * (40 + 32) * 2


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


READERS = (mla_decode_roofline, mla_decode_busy_share,
           mla_prefill_attn_busy_share, mla_proj_busy_share,
           moe_shared_busy_share, moe_pairs_here_share)


def test_a_program_without_the_spans_scopes_or_kernel_reports_nothing():
    """What the parent commit's program gives the new readers: no field on
    its spans, no trace, no counter; none raises and each leaves its metric
    out."""
    bare = _run([_span("serving.decode", 1.0, batch=3),
                 _span("serving.admit", 2.0, prompt_len=9)])
    for reader in READERS:
        assert reader.read(bare) is None
    traced = _run(bare.spans, trace={"busy_s": 1.0}, traced=(0.0, 3.0))
    assert mla_decode_roofline.read(traced) is None   # no trace file either
    assert _mla.span_field(bare, ("serving.decode",), "latent_rows") == []
    # another configuration's counters: an expert layer that holds them all
    other = _run(counters={"moe_tick_layer_calls": 10, "moe_tick_pairs": 80})
    other.cell = harness.load_cell("olmoe-l8-serve-gen-batch")
    assert moe_pairs_here_share.read(other) is None


def test_span_fields_scopes_and_the_kernel_on_hand_made_rows(monkeypatch):
    spans = [_span("serving.decode", 1.0, latent_rows=200_000, pairs=640),
             _span("serving.decode", 2.0, latent_rows=100_000, pairs=640),
             _span("serving.decode", 11.0, latent_rows=1),
             _span("serving.prefill_chunk", 1.5, latent_rows=1024)]
    run = _run(spans, trace={"busy_s": 1.0}, traced=(0.5, 2.2))
    assert _mla.span_field(run, ("serving.decode",), "latent_rows") == [
        200_000, 100_000]
    assert _mla.span_field(run, ("serving.prefill_chunk",), "latent_rows",
                           run.traced) == [1024]
    scope = "jit(f)/cached_forward/_decoder_stack/while/body/layer/"
    rows = [  # [instruction text, op_name, program, start_ns, dur_ns]
        ["%fusion.1 = ...", scope + "attn/mla_proj/dot", "jit_f", 0, 100],
        ["%fusion.2 = ...", scope + "attn/attn_full/mla_absorb/dot", "jit_f",
         100, 50],
        ["%fleetx_mla_decode_paged.3 = custom-call", scope + "attn/attn_full/x",
         "jit_f", 150, 250],
        ["%fusion.4 = ...", scope + "attn/attn_full/while/body/mla_kv_up/dot",
         "jit_f", 400, 120],
        ["%fusion.5 = ...", scope + "attn/attn_full/while/body/"
         "mla_attn_prefill/dot", "jit_f", 520, 180],
        ["%fusion.6 = ...", scope + "mlp/moe_mlp/moe_shared/dot", "jit_f",
         700, 60],
        ["%fusion.7 = ...", scope + "mlp/moe_mlp/moe_experts/dot", "jit_f",
         760, 240]]
    read = _mla.seconds_of({"/device:TPU:0": rows})
    assert read["total"] == pytest.approx(1e-6)
    assert read["mla_proj"] == pytest.approx(0.1e-6)
    assert read["mla_absorb"] == pytest.approx(0.05e-6)
    assert (read["kernel"], read["kernel_calls"]) == (pytest.approx(0.25e-6), 1)
    assert read["mla_kv_up"] == pytest.approx(0.12e-6)
    assert read["mla_attn_prefill"] == pytest.approx(0.18e-6)
    assert read["moe_shared"] == pytest.approx(0.06e-6)
    monkeypatch.setattr(_mla, "seconds", lambda run: read)
    assert mla_decode_busy_share.read(run) == pytest.approx(0.25)
    assert mla_prefill_attn_busy_share.read(run) == pytest.approx(0.30)
    assert mla_proj_busy_share.read(run) == pytest.approx(0.15)
    assert moe_shared_busy_share.read(run) == pytest.approx(0.06)
    # 12 calls (6 layers x 2 ticks) of 150,000 live rows each at the mean,
    # 1,280 bytes a row: the bytes' time over what the calls took
    read.update(kernel=12 * 400e-6, kernel_calls=12)
    least = 150_000 * 1280 / 819e9
    assert mla_decode_roofline.read(run) == pytest.approx(
        100 * least / 400e-6)
    assert mla_decode_roofline.read(run) < 100
    # 16 lanes x 8 = 128 pairs a layer call are routed; 8 land here
    assert moe_pairs_here_share.read(_run(counters={
        "moe_tick_layer_calls": 1000, "moe_tick_pairs": 8000})) == (
        pytest.approx(8 / 128))


# --------------------------------------------------- the traced rehearsal

def _listed():
    return [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [CELL])]


# what a ``--tiny --trace 1`` run reports on the CPU, where no reader of
# the device's trace, of its memory or of a peak finds anything
TINY_REPORTS = {"batch.admit_host_ms_p50", "batch.lane_occupancy",
                "batch.tick_host_ms_p50", "batch.tick_ms_p50",
                "batch.tick_overlap_share", "moe_load_max_over_mean",
                "moe_pairs_here_share", "prefix_tokens_saved_share"}


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
    cell.deploy.update(pool_tokens=3 * cell.deploy["cache_len"])
    return cell


@pytest.fixture(scope="module")
def readings(probe_cell):
    return dict(probe_axk1.readings(probe_cell, driver, 11))


def test_the_reference_check_passes_the_engine_as_built(readings):
    out = readings["as_built"]
    assert out["reference_ok"] and out["layers_ok"], out
    assert out["hit_matched_tokens"] == 64 and out["cold_matched_tokens"] == 0
    assert out["reference_positions_checked"] == 16 + 4
    assert out["hit_cold_logit_rms_diff"] == 0.0
    assert out["reference_rms_err"] < 1e-3 * driver.REFERENCE_RMS_TOL * out[
        "reference_logit_std"]
    assert max(out["reference_ckv_rel_rms_err"],
               out["reference_kr_rel_rms_err"]) < 1e-3 * driver.REFERENCE_ROWS_TOL


# (at the rehearsal's float32 and its 64 keys a chunk's scores rounded to
# bfloat16 move the logits by 1e-5 of their deviation: that reading is the
# chip's to take, at the published widths)
@pytest.mark.parametrize("fault", [f for f in probe_axk1.FAULTS
                                   if f != "bf16_scores"])
def test_a_planted_fault_turns_the_reference_check_false(readings, fault):
    out = readings[fault]
    assert not out["reference_ok"], out
    if fault == "key_unrotated":   # the rows' to refuse: the key alone
        assert out["reference_kr_rel_rms_err"] > 2 * driver.REFERENCE_ROWS_TOL
        assert out["reference_ckv_rel_rms_err"] < driver.REFERENCE_ROWS_TOL
    if fault == "w_uv_left_out":   # the logits' to refuse: the layers agree
        assert out["layers_ok"]
        assert out["reference_decode_rms_err"] > (
            2 * driver.REFERENCE_RMS_TOL * out["reference_logit_std"])
    if fault in ("group_limit_off", "bf16_router"):
        assert out["layer_experts_beside_reference"] > 0
    if fault == "bf16_router":
        assert out["layer_weight_max_rel_err"] > 10 * driver.LAYER_WEIGHT_TOL
    if fault in ("shared_expert_dropped", "unheld_pair_computed"):
        assert out["layer_output_rel_rms_err"] > 10 * driver.LAYER_OUTPUT_TOL


@pytest.fixture(scope="module")
def engine_readings(probe_cell, readings):
    return dict(probe_axk1.engine_readings(
        probe_cell, driver, 11, readings["as_built"]["reference_logit_std"]))


@pytest.mark.parametrize("name", probe_axk1.ENGINE_FAULTS)
def test_the_engines_own_programs_are_held_to_the_checked_ones(
        engine_readings, name):
    out = engine_readings[name]
    assert out["engine_lanes_checked"] == 3
    assert out["engine_ok"] == (name == "engine_as_built"), out
    if name != "engine_as_built":
        assert out["engine_rows_max_rel_rms_err"] > 2 * driver.ENGINE_ROWS_TOL
