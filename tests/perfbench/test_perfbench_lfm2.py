"""The LFM2 configuration against the published one, the decode attention's
and the state's byte counts on hand-worked cases, the readers of the new
spans, counters and scope on hand-made runs, and the driver's checks at
rehearsal size: the reference check passes the engine as built, and a state
zeroed where a prefill starts, a state read a position stale, a bias left
out of the choice, a router computed in bfloat16 or int8 experts
(``perfbench/probe_lfm2.py``, which puts the same questions on the chip at
the published widths) each turn it false; and the check of the engine's own
programs on the requests in flight, which stale block tables turn false."""

import types

import jax
import jax.numpy as jnp
import pytest

from perfbench import flops, flops_lfm2, flops_moe, harness, probe_lfm2
from perfbench.drivers import serve_closed_loop_lfm2 as driver
from perfbench.layer_metrics import (_lfm2, lfm2_decode_roofline,
                                     prefix_tokens_saved_share,
                                     state_bytes_share, state_resume_share)

CELL = "lfm2-l14-serve-agent-prefix"
# LiquidAI/LFM2-8B-A1B, config.json (catalog architectures.jsonl), written
# out: the source's key, its value, the model group's key and value
PUBLISHED = [
    ("conv_L_cache", 3, "conv_L_cache", 3),
    ("conv_bias", False, "use_bias", False),
    ("hidden_size", 2048, "hidden_size", 2048),
    ("intermediate_size", 7168, "dense_ffn_hidden_size", 7168),
    ("max_position_embeddings", 128000, "max_position_embeddings", 128000),
    ("model_type", "lfm2_moe", "family", "lfm2"),
    ("moe_intermediate_size", 1792, "ffn_hidden_size", 1792),
    ("norm_eps", 1e-05, "norm_eps", 1e-05),
    ("norm_topk_prob", True, "norm_topk_prob", True),
    ("num_attention_heads", 32, "num_attention_heads", 32),
    ("num_dense_layers", 2, "num_dense_layers", 2),
    ("num_experts", 32, "num_experts", 32),
    ("num_experts_per_tok", 4, "top_k", 4),
    ("num_key_value_heads", 8, "num_key_value_heads", 8),
    ("rope_theta", 1000000, "rope_theta", 1000000.0),
    ("routed_scaling_factor", 1, "routed_scaling_factor", 1.0),
    ("use_expert_bias", True, "use_expert_bias", True),
    ("vocab_size", 65536, "vocab_size", 65536),
]
FIRST_14 = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 3


def test_every_width_is_the_published_one_and_only_the_depth_is_cut():
    bench = harness.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b-l14")
    data = harness.load_json(entry["file"])
    for key, value, mine, mapped in PUBLISHED:
        assert data[key] == value, key
        assert data["model"][mine] == mapped, mine
    model = data["model"]
    assert data["num_hidden_layers"] == model["num_layers"] == 14   # of 24
    assert data["layer_types"] == model["layer_types"] == FIRST_14
    assert data["published_layer_types"][:14] == FIRST_14
    assert set(entry["reduced"]) == set(data["reduced"]) == {
        "num_hidden_layers", "num_layers", "layer_types"}
    assert (model["gate"], model["qk_norm"], model["qk_norm_scope"],
            model["tie_word_embeddings"], model["mlp_act"]) == (
        "sigmoid_topk", True, "head", True, "swiglu")
    assert 0 < model["expert_bias_init_std"] <= 0.05
    assert set(data["assumed"]) >= {"tied_head", "conv_thirds", "qk_norm",
                                    "dense_width", "bias", "bias_values"}
    cell = harness.load_json("perfbench", "cells", CELL + ".json")
    traffic = harness.load_json("perfbench", "traffic", "agent-prefix.json")
    assert (traffic["closed_loop"]["clients"], cell["lanes"],
            traffic["block"]) == (48, 48, 4)
    assert [(t["weight"], t["shared_prefix_len"]) for t in traffic["tenants"]
            ] == [(0.4, 4096), (0.3, 3072), (0.2, 2048), (0.1, 1024)]
    for tenant in traffic["tenants"]:
        pre = tenant["shared_prefix_len"]
        assert tenant["prompt"] == {"dist": "uniform", "min": pre + 64,
                                    "max": pre + 512}
        assert tenant["output"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.35, "min": 64, "max": 256}
    # the longest request fits a lane, and no request can meet a full pool
    # even if nothing were shared
    assert cell["cache_len"] >= 4096 + 512 + 256
    assert cell["pool_tokens"] == cell["lanes"] * cell["cache_len"]
    assert cell["prefill_chunk"] % cell["page_size"] == 0
    (serve,) = [m for m in bench["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    assert serve["workloads"][-1] == CELL
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])]
    alone = [m["name"] for m in bench["per_layer"]
             if m.get("workloads") == [CELL]]
    assert "decode_paged_roofline" not in " ".join(mine)
    assert {"lfm2_decode_roofline", "conv_mix_busy_share",
            "prefix_tokens_saved_share", "state_resume_share",
            "state_bytes_share"} <= set(alone)
    # the readers it shares with the other closed-loop cells: one entry
    # each, which lists this cell among them (PR 37)
    assert {"moe_experts_roofline", "batch.unscoped_device_share"} <= set(mine)


def test_the_costs_on_hand_worked_cases():
    model = dict(num_attention_heads=4, num_key_value_heads=2, hidden_size=32,
                 conv_L_cache=3,
                 layer_types=["conv", "full_attention", "conv", "conv"])
    assert flops_lfm2.layer_counts(model) == (1, 3)
    assert flops_lfm2.row_bytes(model) == 2 * 2 * 8 * 2          # K and V
    # 100 live rows in ONE attention layer: 100 x 64 bytes; 2 lanes x 4
    # heads x 8 x 2 bytes, in and out; two products of 8 a head and row
    ops, bytes_ = flops_lfm2.decode_tick_cost(100, 2, model)
    assert (ops, bytes_) == (2 * 2 * 100 * 4 * 8, 100 * 64 + 2 * 2 * 32 * 2)
    assert flops_lfm2.tail_page_bytes(model) == 3 * 2 * 32 * 2
    data = harness.load_json("perfbench", "configs",
                             "lfm2-8b-a1b-l14.json")["model"]
    assert flops_lfm2.row_bytes(data) == 2048
    assert flops_lfm2.layer_counts(data) == (3, 11)
    assert flops_lfm2.tail_page_bytes(data) == 11 * 8192
    ops, bytes_ = flops_lfm2.decode_tick_cost(48 * 3600, 48, data)
    assert flops.roofline_seconds(ops, bytes_, {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})[1] == "memory"
    # what flops.paged_decode_call_cost would have counted for the same
    # tick: 32 x 64 lanes a row and every live row in all 14 layers
    theirs = 14 * flops.paged_decode_call_cost(48 * 3600, 32, 64, 48)[1]
    assert 15 < theirs / bytes_ < 20
    # an expert layer's cost is counted at the EXPERTS' width, which the
    # model group keeps under ``ffn_hidden_size``
    assert flops_moe.expert_layer_cost(192, 31.9, 2048, data[
        "ffn_hidden_size"])[1] == pytest.approx(
        (31.9 * 3 * 2048 * 1792 + 192 * 2 * (2048 + 1792)) * 2)


def _span(name, start, **attrs):
    return types.SimpleNamespace(name=name, start_s=start, end_s=start + 0.01,
                                 duration_s=0.01, attrs=attrs)


def _run(spans, counters=None, trace=None, traced=None):
    cell = harness.load_cell(CELL)
    run = harness.Run(
        cell=cell, device={}, setup_s=1.0, window=(10.0, 50.0), attempted=1,
        failed=0, correct=True, checks={}, samples={"lanes": 48},
        spans=spans, counters=counters or {}, traced=traced, trace=trace,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    return run


def test_the_span_and_counter_readers_on_a_hand_made_run():
    spans = [_span("serving.admit", 5.0, prompt_len=4400, matched=0,
                   state_resumed=False),            # before the window
             _span("serving.admit", 11.0, prompt_len=4400, matched=4096,
                   state_resumed=True),
             _span("serving.admit", 12.0, prompt_len=1200, matched=1024,
                   state_resumed=True),
             _span("serving.admit", 13.0, prompt_len=400, matched=0,
                   state_resumed=False),
             _span("serving.decode", 20.0, batch=48, attn_rows=100_000),
             _span("serving.decode", 21.0, batch=48, attn_rows=140_000)]
    run = _run(spans, {"kv_page_bytes_in_use": 1000, "state_bytes_lanes": 30,
                       "state_bytes_snapshots": 870})
    assert prefix_tokens_saved_share.read(run) == pytest.approx(5120 / 6000)
    assert state_resume_share.read(run) == pytest.approx(2 / 3)
    assert state_bytes_share.read(run) == pytest.approx(0.9)
    assert _lfm2.decode_rows(run) == [100_000, 140_000]
    # 6 kernel calls over 3 attention layers are 2 ticks of 120,000 rows:
    # 3 x 120,000 x 2,048 bytes a tick at 819 GB/s, over the kernel's time
    run = _run(spans, traced=(19.0, 22.0), trace={
        "family_calls": {"decode": 6}, "family_s": {"decode": 0.004}})
    bytes_ = 3 * 120_000 * 2048 + 2 * 48 * 2048 * 2 * 3
    assert lfm2_decode_roofline.read(run) == pytest.approx(
        100 * 2 * bytes_ / 819e9 / 0.004)
    # a parent commit's program has none of these: nothing is read
    bare = _run([_span("serving.admit", 11.0, prompt_len=9),
                 _span("serving.decode", 20.0, batch=3)], trace={
        "family_calls": {"decode": 6}, "family_s": {"decode": 0.004}},
        traced=(19.0, 22.0))
    for reader in (prefix_tokens_saved_share, state_resume_share,
                   state_bytes_share, lfm2_decode_roofline):
        assert reader.read(bare) is None


def test_the_scope_reader_finds_the_conv_operator():
    stack = ("jit(_decode_fn)/cached_forward/gpt/layers/"
             "layers._decoder_stack/while/body/layer/attn")
    rows = [
        ["%while.1 = while(...)", "", "jit__decode_fn", 0.0, 100.0],
        ["%fusion.1 = fusion(...)",
         stack + "/cond/branch_0_fun/conv_mix/ShortConv/in_proj/dot_general",
         "jit__decode_fn", 10.0, 20.0],
        ["%fusion.2 = fusion(...)",
         stack + "/cache_write/conv_state/scatter", "jit__decode_fn", 40.0,
         5.0],
        ["%fleetx_decode_paged.1 = custom-call(...)",
         stack + "/cond/branch_1_fun/attn_full/fleetx_decode_paged/pallas_call",
         "jit__decode_fn", 50.0, 30.0],
    ]
    seconds = _lfm2.scope_seconds({"/device:TPU:0": rows})
    assert seconds["conv_mix"] == pytest.approx(20e-9)
    assert seconds["total"] == pytest.approx(100e-9)
    from perfbench.layer_metrics import _parts

    # the accepted rules book the new scopes: the operator to attn, the
    # state's reads and writes to cache_move
    assert _parts.part_of("fusion", rows[1][1]) == "attn"
    assert _parts.part_of("fusion", rows[2][1]) == "cache_move"
    assert _parts.part_of("fusion", stack.replace(
        "/attn", "/mlp") + "/cond/branch_1_fun/moe_mlp/moe_route/add") == "mlp"
    assert _parts.part_of("dynamic-slice", stack.rsplit(
        "/layer", 1)[0] + "/dynamic_slice") == "cache_move"


@pytest.fixture(scope="module")
def tiny():
    """The cell at rehearsal size, computed in float32 (the weights still
    held in bfloat16), the layers' matrices scaled until they, and not the
    head alone, decide the logits, as in
    tests/perfbench/test_perfbench_smallthinker.py."""
    cell = harness.load_cell(CELL, tiny=True)
    cell.config["compute_dtype"] = "float32"
    model, variables = driver.ref_driver.build_model(cell, 3)
    variables = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 8.0 if "['layers']" in jax.tree_util.keystr(path)
        and x.ndim >= 3 and "conv_kernel" not in jax.tree_util.keystr(path)
        else x, variables)
    engine = driver.build_engine(cell, model, variables)
    return cell, model, variables, engine


def test_the_engine_as_built_passes_the_reference_check(tiny):
    cell, model, variables, engine = tiny
    assert {x.dtype for x in jax.tree.leaves(variables)} == {
        jnp.dtype("bfloat16")}
    out = driver.reference_check(engine, variables, cell, 3)
    assert out["reference_ok"], out
    prefix, own, decode, _, _ = driver.check_sizes(cell)
    assert (out["hit_matched_tokens"], out["cold_matched_tokens"]) == (prefix, 0)
    assert out["reference_positions_checked"] == own + decode
    assert out["hit_cold_logit_rms_diff"] == 0.0     # resumed bit for bit
    assert out["reference_rms_err"] < 2e-3 * out["reference_logit_std"]
    assert out["layers_ok"] and out["layer_experts_beside_reference"] == 0
    assert out["layer_weight_max_rel_err"] <= driver.LAYER_WEIGHT_TOL


@pytest.mark.parametrize("fault", ["state_zeroed", "state_stale",
                                   "bias_left_out", "bf16_router"])
def test_a_planted_fault_turns_the_reference_check_false(tiny, fault):
    cell, model, variables, engine = tiny
    import contextlib
    import dataclasses

    from perfbench import probe_precision

    context = {"state_zeroed": lambda: probe_lfm2.state_read("zeroed"),
               "state_stale": lambda: probe_lfm2.state_read("stale"),
               "bf16_router": probe_precision.router_in_bfloat16}.get(
        fault, contextlib.nullcontext)()
    changed = probe_lfm2.GATE_FAULTS.get(fault, {})
    with context:
        faulty = engine.model.clone(cfg=dataclasses.replace(
            engine.model.cfg, **changed))
        served = driver.Served(engine, model=faulty, params=(
            probe_lfm2.leaves_of(faulty, engine.params) if changed else None))
        out = driver.reference_check(engine, variables, cell, 3, served)
    assert not out["reference_ok"], out
    if fault.startswith("state"):
        assert out["layers_ok"]        # every layer, on the input it saw, is right
        assert out["reference_first_rms_err"] > (
            driver.REFERENCE_FIRST_TOL * out["reference_logit_std"])
    else:
        assert not out["layers_ok"]


def test_int8_experts_turn_the_check_false(tiny):
    cell, model, variables, engine = tiny
    served = driver.Served(engine, params=probe_lfm2.int8_experts(
        engine.params))
    out = driver.reference_check(engine, variables, cell, 3, served)
    assert not out["reference_ok"] and not out["layers_ok"], out
    assert out["layer_output_rel_rms_err"] > driver.LAYER_OUTPUT_TOL


@pytest.mark.parametrize("fault", ["engine_as_built", "engine_stale_tables"])
def test_the_engines_own_programs_are_held_to_the_checked_ones(tiny, fault):
    cell, model, variables, _ = tiny
    import copy

    small = copy.copy(cell)
    small.deploy = dict(cell.deploy)
    ((name, out),) = probe_lfm2.engine_readings(small, driver, 3, 1.0,
                                                only=(fault,))
    assert out["engine_lanes_checked"] == cell.deploy["lanes"]
    assert out["engine_tokens_served_checked"] > 0
    if fault == "engine_as_built":
        assert out["engine_ok"], out
        assert out["engine_rows_max_rel_rms_err"] < 1e-2
        return
    assert not out["engine_ok"], out
    assert out["engine_rows_max_rel_rms_err"] > driver.ENGINE_ROWS_TOL, out
