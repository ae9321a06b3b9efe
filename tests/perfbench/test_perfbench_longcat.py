"""The LongCat-Flash configuration against the published one written out,
its parameter count against the program's own model, the cell's bytes, the
stream of topic-skewed prompts, the two new readers on hand-made runs, the
traced ``--tiny`` rehearsal of the new cell, and the driver's reference
check at rehearsal size: it passes the engine as built, and each fault of
``perfbench/probe_longcat.py`` (which puts the same questions on the chip at
the published widths) turns it false."""

import itertools
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import harness, probe_longcat
from perfbench.drivers import rollout_stream
from perfbench.drivers import serve_closed_loop_longcat as driver
from perfbench.layer_metrics import moe_zero_busy_share, moe_zero_pairs_share

CELL = "longcat-l4-serve-rollout-skewed"
CONFIG = "longcat-flash-ep32-l4"
BENCH = harness.load_json("BENCHMARK.json")

# the catalog row's ``config`` (architectures.jsonl beside the model-configs
# guide, ``LongCat-Flash-Omni``), written out
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}
CUT = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384}
# the source's key -> the model group's (``GPTConfig``'s names)
MINE = {"ffn_hidden_size": "dense_ffn_hidden_size",
        "expert_ffn_hidden_size": "ffn_hidden_size",
        "rms_norm_eps": "norm_eps", "moe_topk": "top_k",
        "zero_expert_num": "num_zero_experts"}
SAME = ("hidden_size", "num_attention_heads", "kv_lora_rank", "q_lora_rank",
        "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim",
        "mla_scale_q_lora", "mla_scale_kv_lora", "routed_scaling_factor",
        "max_position_embeddings", "rope_theta")
PARAMETERS = 5_172_749_312


def _config():
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    return entry, harness.load_json(entry["file"])


def test_every_width_is_the_published_one_and_three_keys_are_the_share():
    entry, data = _config()
    assert entry["source"] == data["source"] == (
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/"
        "main/config.json")
    assert entry["reduced"] == data["reduced"] == list(CUT)
    assert data["published"] == {k: PUBLISHED[k] for k in CUT}
    for key, value in PUBLISHED.items():
        assert data[key] == CUT.get(key, value), key
    # no width among the cuts
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    model = data["model"]
    for key in SAME:
        assert model[key] == PUBLISHED[key], key
    for theirs, mine in MINE.items():
        assert model[mine] == PUBLISHED[theirs], (theirs, mine)
    # the share, in the program's names: 4 double layers are 8 halves
    assert model["num_layers"] == 2 * CUT["num_layers"] and model["moe_shortcut"]
    assert model["layer_types"] == ["latent_attention"] * 8
    assert model["num_experts"] == CUT["n_routed_experts"]
    assert model["num_routed_experts"] == PUBLISHED["n_routed_experts"]
    assert model["first_expert_held"] == 0 and model["num_dense_layers"] == 0
    assert model["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert model["gate"] == "softmax_bias_topk" and not model["norm_topk_prob"]
    assert model["expert_bias_init_std"] == pytest.approx(0.25 / 768)
    assert "32 chips" in data["deployment"] and data["reference"] == "longcat_f32"
    assert len(data["assumed"]) == 6 and len(data["departures"]) == 1
    assert "encoders" in data["departures"][0]
    # the floors: four periods, 8 routed experts, an eighth of the vocabulary
    assert CUT["num_layers"] >= 4 and CUT["n_routed_experts"] >= 8


def test_the_yaml_carries_the_same_model_section():
    from fleetx_tpu.utils.config import get_config

    _, data = _config()
    published = get_config(os.path.join(harness.ROOT, data["train_yaml"]),
                           nranks=1, overrides=["Distributed.dp_degree=1"]).Model
    for key, value in data["model"].items():
        assert published.get(key) == value, key


def test_the_parameter_count_is_the_programs_own_models():
    import flax
    import jax

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    _, data = _config()
    model = GPTForPretraining(GPTConfig.from_model_config(
        {**data["model"], "dtype": "bfloat16"}))
    shapes = jax.eval_shape(lambda: flax.core.meta.unbox(model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))))
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree.leaves(shapes["params"]))
    assert count == PARAMETERS
    assert f"{PARAMETERS:,}" in data["sizing"]
    # the issue's arithmetic, part by part
    attention = (6144 * 1536 + 1536 + 1536 * 12288 + 6144 * 576 + 512
                 + 512 * 16384 + 8192 * 6144)
    dense, expert = 3 * 6144 * 12288, 3 * 6144 * 2048
    outside = 2 * attention + 2 * dense + 4 * 6144 + 6144 * 768 + 768
    assert (attention, outside) == (90_572_800, 638_874_368)
    assert 4 * (outside + 16 * expert) + 2 * 16384 * 6144 + 6144 == PARAMETERS
    # the whole model by the same count: 560B-A27B
    whole = 28 * (outside + 512 * expert) + 2 * 131072 * 6144 + 6144
    active = 28 * (outside + 8 * expert) + 2 * 131072 * 6144 + 6144
    assert 555e9 < whole < 565e9 and 26e9 < active < 28.5e9


def test_the_cell_and_its_traffic_are_the_issues():
    cell = harness.load_cell(CELL)
    deploy, job = cell.deploy, cell.traffic
    assert cell.chips == 1 and job["driver"] == "serve_closed_loop_longcat"
    assert job["closed_loop"]["clients"] == deploy["lanes"] in (64, 48)
    assert (deploy["cache_len"], deploy["page_size"]) == (4096, 16)
    assert deploy["pool_tokens"] == deploy["lanes"] * 4096
    assert deploy["prefill_chunk"] in (2048, 1024)       # or the fall-back
    assert deploy["prefill_bucket"] == 256
    assert job["prompt"] == {"dist": "lognormal", "median": 512, "sigma": 0.6,
                             "min": 128, "max": 2048}
    assert job["output"] == {"dist": "lognormal", "median": 1024, "sigma": 0.4,
                             "min": 256, "max": 2048}
    assert job["topics"] == {"count": 8, "weight_exponent": 1.0,
                             "zipf_exponent": 1.1}
    assert job["block"] == 4 and isinstance(job["order_seed"], int)
    # the longest request fits a lane, and no request can meet a full pool
    assert job["prompt"]["max"] + job["output"]["max"] <= deploy["cache_len"]
    # the bytes: weights + (lanes x 256 + 1) pages x 16 rows x 8 halves x 1,280 B
    rows = (deploy["pool_tokens"] // 16 + 1) * 16
    cache = rows * 8 * (512 + 128) * 2
    total = 2 * PARAMETERS + cache
    if deploy["lanes"] == 64:
        assert rows == 262_160 and round(cache / 1e9, 2) == 2.68
        assert round(total / 1e9, 2) == 13.03
    assert total > 12e9            # the fullest device holds over 12 GB
    (entry,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert entry["config"] == CONFIG and entry["traffic"] == "rollout-skewed"
    for said in ("tokens an expert and tick", "32x", "rows an expert",
                 "4 of 28"):
        assert said in entry["why"], said


def test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved():
    (serve,) = [m for m in BENCH["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    assert serve["workloads"][-2:] == ["trinity-l5-serve-mixed-longshort", CELL]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == CONFIG
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    for m in mine:
        assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
        assert m["moves"] in ("serve_tokens_per_s", "setup_s")
    names = {m["name"] for m in mine}
    # what A.X-K1's cell lists, less the shared expert and the prefix cache
    axk1 = {m["name"] for m in BENCH["per_layer"]
            if "axk1-l6-serve-docqa-latent" in m.get("workloads", ())}
    assert names == (axk1 - {"moe_shared_busy_share",
                             "prefix_tokens_saved_share"}
                     ) | {"moe_zero_pairs_share", "moe_zero_busy_share"}
    new = BENCH["per_layer"][-2:]
    assert [m["name"] for m in new] == ["moe_zero_pairs_share",
                                        "moe_zero_busy_share"]
    assert all(m["workloads"] == [CELL] and m["layer"] == "model" for m in new)
    assert [m["source"] for m in new] == ["program_counter", "device_trace"]
    assert len(BENCH["per_layer"]) <= 128 and len(BENCH["workloads"]) == 12
    assert len(BENCH["configs"]) == 10
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


# ------------------------------------------------------------- the stream

def _head(job, seed, client, n, vocab=16384):
    return list(itertools.islice(
        rollout_stream.client_stream(job, seed, client, vocab), n))


@pytest.mark.parametrize("tiny", [False, True])
def test_topics_lengths_and_ranks_are_of_order_seed_and_the_client_alone(tiny):
    job = harness.load_cell(CELL, tiny=tiny).traffic
    vocab = 512 if tiny else 16384
    a, b = _head(job, 7, 2, 12, vocab), _head(job, 8, 2, 12, vocab)
    for x, y in zip(a, b):       # another --seed: the same stream
        assert (x.tenant, x.max_new_tokens) == (y.tenant, y.max_new_tokens)
        assert np.array_equal(x.prompt, y.prompt)
    other = _head(job, 7, 3, 12, vocab)       # another client: another stream
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in other] or (
        [r.tenant for r in a] != [r.tenant for r in other])
    moved = _head({**job, "order_seed": job["order_seed"] + 1}, 7, 2, 12, vocab)
    assert not all(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, moved))
    lo, hi = job["prompt"]["min"], job["prompt"]["max"]
    for r in a + other:
        assert lo <= len(r.prompt) <= hi and r.prompt.dtype == np.int32
        assert 1 <= r.prompt.min() and r.prompt.max() < vocab
        assert job["output"]["min"] <= r.max_new_tokens <= job["output"]["max"]
    # every block of 4 holds the lengths' quantiles once
    for spec, of in ((job["prompt"], lambda r: len(r.prompt)),
                     (job["output"], lambda r: r.max_new_tokens)):
        blocks = [sorted(of(r) for r in a[i:i + 4]) for i in (0, 4, 8)]
        assert blocks[0] == blocks[1] == blocks[2], spec


def test_topics_are_drawn_at_one_over_k_and_ids_zipf_over_the_topics_ranks():
    job = harness.load_cell(CELL).traffic
    weights = rollout_stream.topic_weights(job["topics"])
    assert np.allclose(weights * weights[0] ** -1, 1 / np.arange(1, 9))
    requests = [r for c in range(16) for r in _head(job, 1, c, 40)]
    topics = np.bincount([int(r.tenant[5:]) for r in requests], minlength=8)
    assert np.abs(topics / topics.sum() - weights).max() < 0.05
    ids, cdf = rollout_stream._tables(job["order_seed"], 8, 1.1, 16384)
    assert ids.shape == (8, 16383) and sorted(ids[3]) == list(range(1, 16384))
    assert not np.array_equal(ids[0], ids[1])      # a topic's own permutation
    # Zipf 1.1 over 16,383 ranks: rank 1 has 1 / H of the mass, and the ranks
    # drawn follow the cumulative weights
    mass = np.arange(1, 16384) ** -1.1
    assert cdf[0] == pytest.approx(mass[0] / mass.sum())
    for topic in (0, 1):
        mine = np.concatenate([r.prompt for r in requests
                               if r.tenant == f"topic{topic}"])
        rank_of = np.empty(16384, np.int64)
        rank_of[ids[topic]] = np.arange(16383)
        ranks = rank_of[mine]
        assert abs((ranks == 0).mean() - cdf[0]) < 0.01
        assert abs((ranks < 100).mean() - cdf[99]) < 0.02
    # the skew: a tenth of the ids carries most of a topic's tokens
    assert cdf[1637] > 0.6


# ------------------------------------------------------------- the readers

def _run(counters=None, trace=None):
    cell = harness.load_cell(CELL)
    return types.SimpleNamespace(cell=cell, counters=counters or {},
                                 trace=trace, spans=[], window=(0.0, 1.0))


def test_a_program_without_the_counters_or_scopes_reports_nothing():
    # a parent commit's program counts no zero pair and is not traced here
    assert moe_zero_pairs_share.read(_run()) is None
    assert moe_zero_pairs_share.read(_run(
        {"moe_tick_layer_calls": 40, "moe_tick_pairs": 9})) is None
    assert moe_zero_busy_share.read(_run()) is None


def test_the_zero_pairs_share_is_of_all_the_pairs_the_ticks_routed():
    lanes = harness.load_cell(CELL).deploy["lanes"]
    counters = {"moe_tick_layer_calls": 40,
                "moe_tick_zero_pairs": 40 * lanes * 4}
    assert moe_zero_pairs_share.read(_run(counters)) == pytest.approx(4 / 12)
    counters["moe_tick_zero_pairs"] = 0      # the mechanism never engaged
    assert moe_zero_pairs_share.read(_run(counters)) == 0.0


def test_the_busy_share_reads_the_two_scopes_on_hand_made_rows(monkeypatch):
    path = ("jit(_decode_fn)/cached_forward/GPTModel/layers/"
            "layers._decoder_stack/while/body/layer/mlp/")
    rows = [["fusion.1", path + "moe_mlp/SharedMoEMLP/moe_zero/mul", 0, 0, 300],
            ["fusion.2", path + "moe_shortcut/add", 0, 400, 100],
            ["fusion.3", path + "moe_mlp/SharedMoEMLP/moe_route/top_k", 0, 600,
             400],
            ["fusion.4", path + "dense/dot_general", 0, 1100, 1200]]
    from perfbench.layer_metrics import _parts

    monkeypatch.setattr(_parts, "load_xplane", lambda path: {0: rows})
    monkeypatch.setattr(_parts, "_named", lambda rows: rows)
    moe_zero_busy_share._share.cache_clear()
    assert moe_zero_busy_share._share("made", 0.0) == pytest.approx(
        (300 + 100) / 2000)
    monkeypatch.setattr(_parts, "load_xplane", lambda path: {0: rows[2:]})
    moe_zero_busy_share._share.cache_clear()
    assert moe_zero_busy_share._share("made", 1.0) is None


# --------------------------------------------------- the traced rehearsal

def _listed():
    return [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [CELL])]


# what a ``--tiny --trace 1`` run reports on the CPU, where no reader of
# the device's trace, of its memory or of a peak finds anything
TINY_REPORTS = {"batch.admit_host_ms_p50", "batch.lane_occupancy",
                "batch.tick_host_ms_p50", "batch.tick_ms_p50",
                "batch.tick_overlap_share", "moe_load_max_over_mean",
                "moe_pairs_here_share", "moe_zero_pairs_share"}


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
    assert '"reference_ok": true' in out and "moe_tick_zero_pairs" in out


# ------------------------------------------ the checks at rehearsal size

# one fault a limit (each reading retraces the check's three programs: the
# file keeps under a minute); ``python perfbench/probe_longcat.py --seeds 7
# --tiny`` plants all six here, and the chip's readings are PERF.md's
PLANTED = ("zero_experts_left_out", "shortcut_lands_a_half_early",
           "s_kv_left_out", "bias_in_the_weights")


@pytest.fixture(scope="module")
def readings():
    cell = harness.load_cell(CELL, tiny=True)
    cell.deploy.update(pool_tokens=3 * cell.deploy["cache_len"])
    return dict(probe_longcat.readings(cell, driver, 11, only=PLANTED))


def test_the_reference_check_passes_the_engine_as_built(readings):
    out = readings["as_built"]
    assert out["reference_ok"] and out["layers_ok"], out
    assert out["cold_matched_tokens"] == 0
    assert out["reference_positions_checked"] == 16 + 4
    assert out["reference_rms_err"] < 1e-3 * driver.REFERENCE_RMS_TOL * out[
        "reference_logit_std"]
    assert max(out["reference_ckv_rel_rms_err"],
               out["reference_kr_rel_rms_err"]) < 1e-3 * driver.REFERENCE_ROWS_TOL
    assert 0 < out["layer_zero_pairs_share"] < 1
    low, high = out["layer_routed_pairs_a_token_min_max"]
    assert 0 <= low < high <= 3        # what a token costs varies


@pytest.mark.parametrize("fault", PLANTED)
def test_a_planted_fault_turns_the_reference_check_false(readings, fault):
    assert set(PLANTED) <= set(probe_longcat.FAULTS)
    out = readings[fault]
    assert not out["reference_ok"], out
    if fault == "zero_experts_left_out":
        assert out["layer_output_rel_rms_err"] > 10 * driver.LAYER_OUTPUT_TOL
    if fault == "shortcut_lands_a_half_early":
        # every layer alone is right: the rows after it and the logits say it
        assert out["layers_ok"]
        assert out["reference_ckv_rel_rms_err"] > driver.REFERENCE_ROWS_TOL
    if fault == "s_kv_left_out":       # the rows' to refuse: the latent first
        assert out["reference_ckv_rel_rms_err"] > 2 * driver.REFERENCE_ROWS_TOL
        assert out["reference_ckv_rel_rms_err"] > 3 * out[
            "reference_kr_rel_rms_err"]
    if fault == "bias_in_the_weights":
        assert out["layer_weight_max_rel_err"] > 10 * driver.LAYER_WEIGHT_TOL


def test_the_orders_are_replayed_on_this_cells_stream():
    cell = harness.load_cell(CELL)
    rates = probe_longcat.order_rates(cell, [1, 2], 12.0, 40.0, seconds=10.0)
    assert [r["order"] for r in rates] == [1, 2]
    assert all(r["serve_tokens_per_s"] > 0 for r in rates)
    again = probe_longcat.order_rates(cell, [1], 12.0, 40.0, seconds=10.0)
    assert again[0] == rates[0]        # no device, no clock: a replay
