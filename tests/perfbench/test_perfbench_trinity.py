"""The Trinity configuration against the catalog row written out, its
parameter count against the program's own model, the cell and its traffic
against ISSUE 49's numbers, ``flops_gqa_prefill`` on hand-worked cases, the
readers of the new span fields, scopes and kernel on hand-made runs, the
traced ``--tiny`` rehearsal of the new cell, and the driver's checks at
rehearsal size: the reference check passes the engine as built, each fault of
``perfbench/probe_trinity.py`` (which puts the same questions on the chip at
the published widths) turns it false, and the engine's own programs are held
to the checked ones."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench import (flops, flops_gqa_prefill, harness, probe_trinity,
                       traffic)
from perfbench.drivers import serve_closed_loop_swa_share as driver
from perfbench.layer_metrics import (_gqa, attn_gate_busy_share,
                                     prefill_gqa_roofline)

CELL = "trinity-l5-serve-mixed-longshort"
BENCH = harness.load_json("BENCHMARK.json")
SLIDING, FULL = "sliding_attention", "full_attention"
# arcee-ai/Trinity-Large-Preview, config.json (catalog architectures.jsonl),
# written out: the source's key and its value
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": [FULL if l % 4 == 3 else SLIDING for l in range(60)],
    "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
    "model_type": "afmoe", "moe_intermediate_size": 3072, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 48, "num_dense_layers": 6,
    "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
    "num_hidden_layers": 60, "num_key_value_heads": 8,
    "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}
CUT = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 32,
       "vocab_size": 25024, "layer_types": [SLIDING] * 4 + [FULL]}
# the model group's key for a source's key where the two differ
MINE = {"intermediate_size": "dense_ffn_hidden_size",
        "moe_intermediate_size": "ffn_hidden_size", "head_dim": "head_size",
        "num_hidden_layers": "num_layers", "rms_norm_eps": "norm_eps",
        "num_experts_per_tok": "top_k", "route_norm": "norm_topk_prob",
        "route_scale": "routed_scaling_factor"}
SAME = ("hidden_size", "max_position_embeddings", "num_attention_heads",
        "num_key_value_heads", "num_dense_layers", "num_experts", "n_group",
        "topk_group", "num_shared_experts", "sliding_window", "layer_types",
        "tie_word_embeddings", "vocab_size", "rope_theta")
LANES = 8    # (12 unless the builder's run passed the limits: it did)


def _config():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "trinity-large-ep8-l5")
    return entry, harness.load_json(entry["file"])


def test_every_width_is_the_published_one_and_five_keys_are_cut():
    entry, data = _config()
    model = data["model"]
    for key, value in PUBLISHED.items():
        want = CUT.get(key, value)
        assert data[key] == want, key
        if key in MINE or key in SAME:
            assert model[MINE.get(key, key)] == want, key
    assert sorted(entry["reduced"]) == sorted(data["reduced"]) == sorted(CUT)
    assert entry["source"] == data["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/"
        "config.json")
    # no width is cut; the floors hold: a whole period (three window layers
    # and a full one) after one dense layer, 32 >= 8 experts, an eighth
    assert not [k for k in CUT if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert PUBLISHED["layer_types"][8:12] == CUT["layer_types"][1:]
    assert PUBLISHED["layer_types"][0] == CUT["layer_types"][0] == SLIDING
    assert CUT["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the router keeps its published width; the chip holds experts 0-31
    assert model["num_routed_experts"] == 256
    assert (model["first_expert_held"], model["num_experts"]) == (0, 32)
    assert model["sliding_window_layout"] == model["rope_layout"] == [
        1, 1, 1, 1, 0]
    assert model["embedding_multiplier"] == pytest.approx(3072 ** 0.5)
    assert (model["attention_gate"], model["sandwich_norm"], model["qk_norm"],
            model["qk_norm_scope"]) == ("sigmoid", True, True, "head")
    assert (model["gate"], model["use_expert_bias"],
            model["expert_bias_init_std"]) == ("sigmoid_topk", True, 0.05)
    assert model["family"] == "trinity" and data["reference"] == "trinity_f32"
    assert data["norm_weight_std"] == 0.1
    assert "8 chips share each layer" in data["deployment"]
    assert len(data["assumed"]) == 8 and len(data["departures"]) == 2
    tiny = data["tiny"]["model"]
    assert tiny["layer_types"].count(FULL) == 2 and tiny["sliding_window"] == 64


def test_the_yaml_carries_the_same_model_section():
    from fleetx_tpu.utils.config import get_config

    _, data = _config()
    yaml = get_config(os.path.join(harness.ROOT, data["train_yaml"]), nranks=1,
                      overrides=["Distributed.dp_degree=1"]).Model
    for key, value in data["model"].items():
        assert yaml[key] == value, key


def test_the_parameter_count_is_the_programs_own_models():
    import jax

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    _, data = _config()
    model = GPTForPretraining(GPTConfig.from_model_config(data["model"]))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    counted = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    h, layers = 3072, 5
    attention = 3 * h * 6144 + 2 * h * 1024     # q, gate, out; k, v
    assert attention == 62_914_560
    dense, expert = 3 * h * 12288, 3 * h * 3072
    assert (dense, expert) == (113_246_208, 28_311_552)
    by_hand = (layers * (attention + 2 * h + 2 * 128)   # + two norms, q/k norm
               + dense + 2 * h
               + 4 * (33 * expert + h * 256 + 256 + 2 * h)
               + h + 2 * 25024 * h)                     # final norm, embed, head
    assert counted == by_hand == data["parameters"] == 4_321_903_872
    assert 8.64e9 < 2 * counted < 8.65e9
    # the whole model by the same count: the published 400B, 13B a token
    whole = (60 * (attention + 4 * h + 256) + 6 * dense
             + 54 * (257 * expert + h * 256 + 256) + h + 2 * 200192 * h)
    active = whole - 54 * 252 * expert
    assert 398e9 < whole < 399e9 and 13.3e9 < active < 13.5e9
    # a token's key and value in a layer
    assert 2 * 8 * 128 * 2 == 4096


def test_the_cell_and_its_traffic_are_the_issues():
    cell = harness.load_cell(CELL)
    job, deploy = cell.traffic, cell.deploy
    assert cell.chips == 1 and job["driver"] == "serve_closed_loop_swa_share"
    assert job["clients"] == deploy["lanes"] == LANES
    assert (job["block"], job["trace_s"]) == (4, 3.0)
    assert isinstance(job["order_seed"], int)
    chat, longdoc = job["tenants"]
    assert (chat["name"], chat["weight"], longdoc["name"],
            longdoc["weight"]) == ("chat", 0.75, "longdoc", 0.25)
    assert chat["prompt"] == {"dist": "lognormal", "median": 1024,
                              "sigma": 0.7, "min": 128, "max": 4096}
    assert longdoc["prompt"] == {"dist": "lognormal", "median": 32768,
                                 "sigma": 0.35, "min": 16384, "max": 49152}
    for tenant in job["tenants"]:
        assert tenant["shared_prefix_len"] == 0
        assert tenant["output"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.35, "min": 128, "max": 512}
    assert (deploy["cache_len"], deploy["page_size"], deploy["prefill_chunk"],
            deploy["prefill_bucket"]) == (51200, 16, 2048, 256)
    assert deploy["cache_len"] == 49152 + 2048      # 49,152 + 512, to the chunk
    assert deploy["pool_tokens"] == LANES * deploy["cache_len"]
    window = cell.config["model"]["sliding_window"]
    # a long lane's context is 4-12 times the window; a chat lane stays under
    assert longdoc["prompt"]["min"] == 4 * window
    assert longdoc["prompt"]["max"] == 12 * window
    assert chat["prompt"]["max"] == window
    # the two classes' bytes at the 12 lanes asked for: 2.52 GB and 1.21 GB,
    # 12.37 GB with the weights; at the 8 taken 1.68 and 0.81 GB, 11.13 GB
    full, windowed = ((lanes * 3200 + 1) * 16 * 4096 for lanes in (12, LANES))
    assert 2.51e9 < full < 2.53e9 and 1.67e9 < windowed < 1.69e9
    full, windowed = ((lanes * 385 + 1) * 4 * 16 * 4096
                      for lanes in (12, LANES))
    assert 1.21e9 < full < 1.22e9 and 0.80e9 < windowed < 0.81e9
    assert 12.36e9 < 2.52e9 + 1.21e9 + 2 * 4_321_903_872 < 12.39e9
    assert "8 clients and lanes and nothing else changed" in deploy["about"]
    assert "REFUSED" in deploy["about"] and "Fall-backs" in deploy[
        "prefill_choice"]
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s",
                                                    "setup_s"}
    listed = {m["name"] for m in cell.per_layer}
    assert {"attn_gate_busy_share", "prefill_gqa_roofline",
            "swa_decode_roofline", "swa_window_rows_share",
            "swa_pool_bytes_share", "swa_window_attn_busy_share",
            "swa_full_attn_busy_share", "moe_experts_roofline",
            "moe_experts_busy_share", "moe_route_busy_share",
            "moe_load_max_over_mean", "moe_shared_busy_share",
            "moe_pairs_here_share", "batch.tick_ms_p50",
            "batch.hbm_peak_gb"} <= listed
    assert not {"batch.admit_host_ms_p50", "batch.admit_idle_ms_p50",
                "prefix_tokens_saved_share", "state_bytes_share"} & listed


def test_the_cell_is_appended_to_the_lists_it_joins_and_nothing_else_moved():
    (serve,) = [m for m in BENCH["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    assert serve["workloads"][-2:] == ["dsv32-l5-serve-longqa-sparse", CELL]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == "trinity-large-ep8-l5"
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    assert len(mine) == 31
    for m in mine:
        assert m["workloads"][-1] == CELL and m["workloads"].count(CELL) == 1
        assert m["moves"] == "serve_tokens_per_s"
    new = BENCH["per_layer"][-2:]
    assert [(m["name"], m["layer"], m["unit"]) for m in new] == [
        ("attn_gate_busy_share", "model", "share"),
        ("prefill_gqa_roofline", "kernels", "%")]
    assert all(m["workloads"] == [CELL] for m in new)
    assert all("workloads" in m for m in BENCH["per_layer"])
    assert len(BENCH["per_layer"]) <= 128
    assert (len(BENCH["workloads"]), len(BENCH["configs"])) == (11, 9)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


# ------------------------------------------------------------- the stream

def test_the_order_is_fixed_and_the_seed_draws_the_tokens():
    job = harness.load_cell(CELL).traffic

    def head(seed, client, n=12):
        stream = traffic.client_stream(job, seed, client, 25024)
        return [next(stream) for _ in range(n)]

    def sizes(rs):
        return [(r.tenant, len(r.prompt), r.max_new_tokens) for r in rs]

    a, b = head(7, 3), head(2 ** 31 + 11, 3)
    assert sizes(a) == sizes(b)
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert all(1 <= r.prompt.min() and r.prompt.max() < 25024 for r in a)
    every = [r for c in range(LANES) for r in head(7, c, 24)]
    long = [r for r in every if r.tenant == "longdoc"]
    assert 0.15 < len(long) / len(every) < 0.35
    assert all(16384 <= len(r.prompt) <= 49152 for r in long)
    assert all(128 <= len(r.prompt) <= 4096 for r in every
               if r.tenant == "chat")
    assert 29000 < np.median([len(r.prompt) for r in long]) < 37000
    assert all(len(r.prompt) + r.max_new_tokens <= 51200 - 1 for r in every)


# ------------------------------------------------ operations and bytes

def test_flops_gqa_prefill_on_hand_worked_cases():
    model = harness.load_cell(CELL).config["model"]
    # one key row, one query row: 48 heads x 4 x 128 operations in each of
    # the layers of its kind; the row's key and value copied for every head
    ops, bytes_ = flops_gqa_prefill.chunk_cost(1, 0, 1, model)
    assert ops == 48 * 4 * 128 == 24576
    assert bytes_ == 48 * 2 * 128 * 2 + 2 * 48 * 128 * 2 * 5
    ops, _ = flops_gqa_prefill.chunk_cost(0, 1, 1, model)
    assert ops == 4 * 24576                       # four window layers
    # a chunk of 2,048 behind 32k: 34 key blocks of 1,024 in the full layer,
    # 6 (the window's 4 + the chunk's 2) in each window layer
    ops, bytes_ = flops_gqa_prefill.chunk_cost(34 * 1024, 6 * 1024, 2048, model)
    assert ops == pytest.approx(48 * 4 * 128 * 2048 * (34 + 24) * 1024)
    assert ops == pytest.approx(2.99e12, rel=0.01)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(ops, bytes_, peaks) == (
        pytest.approx(ops / 197e12), "compute")
    # a configuration of one kind of layer counts them all full
    plain = {"num_layers": 3, "num_attention_heads": 4, "hidden_size": 512}
    assert flops_gqa_prefill.chunk_cost(10, 99, 2, plain)[0] == (
        3 * 4 * 4 * 128 * 2 * 10)


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


READERS = (attn_gate_busy_share, prefill_gqa_roofline)


def test_a_program_without_the_field_scope_or_kernel_reports_nothing():
    """What the parent commit's program gives the new readers: no
    ``attn_query_rows`` on its spans, no scope; none raises and each leaves
    its metric out."""
    bare = _run([_span("serving.prefill_chunk", 1.0, final=False,
                       attn_full_key_rows=1024, attn_window_key_rows=1024),
                 _span("serving.admit", 2.0, prompt_len=9)])
    assert _gqa.chunk_spans(bare) == []
    for reader in READERS:
        assert reader.read(bare) is None
    traced = _run(bare.spans, trace={"busy_s": 1.0}, traced=(0.0, 3.0))
    for reader in READERS:   # no trace file either
        assert reader.read(traced) is None
    # SmallThinker's program in this cell's place: the kernel, no gate
    rows = [["%fleetx_prefill_gqa.1 = custom-call", "jit(f)/layer/attn/"
             "attn_full/x", "jit_f", 0, 100]]
    read = _gqa.seconds_of({"/device:TPU:0": rows})
    assert read["kernel_calls"] == 1 and not read["attn_gate"]


def test_span_fields_scopes_and_the_kernel_on_hand_made_rows(monkeypatch):
    spans = [_span("serving.prefill_chunk", 1.0, attn_full_key_rows=34816,
                   attn_window_key_rows=6144, attn_query_rows=2048),
             _span("serving.admit", 1.5, attn_full_key_rows=1024,
                   attn_window_key_rows=1024, attn_query_rows=1024),
             _span("serving.prefill_chunk", 11.0, attn_full_key_rows=1,
                   attn_window_key_rows=1, attn_query_rows=1)]
    run = _run(spans, trace={"busy_s": 1.0}, traced=(0.5, 2.2))
    assert _gqa.chunk_spans(run, run.traced) == [(34816, 6144, 2048),
                                                 (1024, 1024, 1024)]
    scope = "jit(f)/cached_forward/_decoder_stack/while/body/layer/"
    rows = [  # [instruction text, op_name, program, start_ns, dur_ns]
        ["%fusion.1 = ...", scope + "attn/attn_gate/gate_proj/dot", "jit_f",
         0, 60],
        ["%fusion.2 = ...", scope + "attn/attn_gate/mul", "jit_f", 60, 20],
        ["%fusion.3 = ...", scope + "attn/post_norm/mul", "jit_f", 80, 30],
        ["%fusion.4 = ...", scope + "mlp/post_norm/mul", "jit_f", 110, 40],
        ["%fleetx_prefill_gqa.5 = custom-call", scope + "attn/attn_full/x",
         "jit_f", 150, 350],
        ["%fusion.6 = ...", scope + "mlp/moe_mlp/moe_experts/dot", "jit_f",
         500, 500]]
    read = _gqa.seconds_of({"/device:TPU:0": rows})
    assert read["total"] == pytest.approx(1e-6)
    assert read["attn_gate"] == pytest.approx(0.08e-6)
    assert read["post_norm"] == pytest.approx(0.07e-6)
    assert (read["kernel"], read["kernel_calls"]) == (pytest.approx(0.35e-6), 1)
    monkeypatch.setattr(_gqa, "seconds", lambda run: read)
    assert attn_gate_busy_share.read(run) == pytest.approx(0.08)
    # 10 calls (5 layers x 2 prefill programs) that took 25 ms: the mean of
    # the two programs' operations at the chip's peak, twice
    read.update(kernel=25e-3, kernel_calls=10)
    model = run.cell.config["model"]
    least = sum(flops_gqa_prefill.chunk_cost(*rows, model)[0] / 197e12
                for rows in ((34816, 6144, 2048), (1024, 1024, 1024)))
    assert prefill_gqa_roofline.read(run) == pytest.approx(
        100 * least / 25e-3)
    assert 50 < prefill_gqa_roofline.read(run) < 100


# --------------------------------------------------- the traced rehearsal

def _listed():
    return [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", [CELL])]


# what a ``--tiny --trace 1`` run reports on the CPU, where no reader of
# the device's trace, of its memory or of a peak finds anything
TINY_REPORTS = {"batch.lane_occupancy", "batch.tick_host_ms_p50",
                "batch.tick_ms_p50", "batch.tick_overlap_share",
                "moe_load_max_over_mean", "moe_pairs_here_share",
                "swa_pool_bytes_share", "swa_window_rows_share"}


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
    assert '"reference_ok": true' in out and '"edge_ok": true' in out


# ------------------------------------------ the checks at rehearsal size

@pytest.fixture(scope="module")
def probe_cell():
    cell = harness.load_cell(CELL, tiny=True)
    cell.deploy.update(pool_tokens=3 * cell.deploy["cache_len"])
    return cell


# (an expert rounded to 8 bits moves a 32-wide layer's output by a
# thousandth: that reading is the chip's to take, at the published widths)
@pytest.fixture(scope="module")
def readings(probe_cell):
    return dict(probe_trinity.readings(
        probe_cell, driver, 11, only=probe_trinity.FAULTS))


def test_the_reference_check_passes_the_engine_as_built(readings):
    out = readings["as_built"]
    assert out["reference_ok"] and out["layers_ok"] and out["edge_ok"], out
    assert out["reference_positions_checked"] == 16 + 4
    assert out["window_pages_recycled_in_check"] > 0
    assert out["layer_tol"] == [driver.LAYER_WEIGHT_TOL,
                                driver.LAYER_OUTPUT_TOL]
    assert out["reference_rms_err"] < 1e-3 * driver.REFERENCE_RMS_TOL * out[
        "reference_logit_std"]
    for program in ("chunk", "step"):
        assert out[f"edge_{program}_behind_max_abs_diff"] == 0.0
        assert out[f"edge_{program}_at_edge_max_abs_diff"] > 0.0


@pytest.mark.parametrize("fault", probe_trinity.FAULTS)
def test_a_planted_fault_turns_the_reference_check_false(readings, fault):
    out = readings[fault]
    assert not out["reference_ok"], out
    unit = out["reference_logit_std"]
    if fault in ("window_4095", "window_4097"):
        # the edge's to refuse, and the edge's alone at the published sizes
        assert not out["edge_ok"] and out["layers_ok"]
        behind = out["edge_step_behind_max_abs_diff"]
        assert (behind > 0) == (fault == "window_4097")
    elif fault in ("route_scale_left_out", "bias_in_the_weights",
                   "bf16_router"):
        assert out["layer_weight_max_rel_err"] > 10 * driver.LAYER_WEIGHT_TOL
        assert out["edge_ok"]
    elif fault in ("unheld_pair_computed", "shared_expert_twice"):
        assert out["layer_output_rel_rms_err"] > 10 * driver.LAYER_OUTPUT_TOL
    else:   # the logits' to refuse: the expert layers agree
        assert out["layers_ok"] and out["edge_ok"]
        assert out["reference_rms_err"] > 2 * driver.REFERENCE_RMS_TOL * unit


@pytest.fixture(scope="module")
def engine_readings(probe_cell, readings):
    return dict(probe_trinity.engine_readings(
        probe_cell, driver, 11, readings["as_built"]["reference_logit_std"]))


@pytest.mark.parametrize("name", probe_trinity.ENGINE_FAULTS)
def test_the_engines_own_programs_are_held_to_the_checked_ones(
        engine_readings, name):
    out = engine_readings[name]
    assert out["engine_lanes_checked"] == 3
    assert out["engine_lanes_short_skipped"] == 0
    assert out["engine_ok"] == (name == "engine_as_built"), out


def test_the_place_taken_in_the_swa_driver_is_given_back():
    from perfbench.drivers import serve_closed_loop_ref as ref_driver
    from perfbench.drivers import serve_closed_loop_swa as swa_driver

    before = {name: getattr(swa_driver, name) for name in driver._SIZES}
    with driver.in_the_swa_drivers_place():
        assert swa_driver.check_sizes is driver.check_sizes
        assert ref_driver.build_model is driver.build_model
        with driver.in_the_swa_drivers_place():   # (the checks open it again)
            assert swa_driver.ENGINE_LANES == 8
        assert swa_driver.layer_check is driver.layer_check
    assert {name: getattr(swa_driver, name)
            for name in driver._SIZES} == before
    assert ref_driver.build_model is driver._made_with_ones
    assert swa_driver.REFERENCE_MAX_TOL == 0.16    # SmallThinker's own
