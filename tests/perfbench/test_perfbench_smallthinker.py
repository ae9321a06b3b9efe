"""The SmallThinker configuration against the published one, the decode
attention's byte count over two classes of page on a hand-worked case, the
reader of the attention scopes on a hand-made trace, and the driver's
reference check at rehearsal size: it passes the engine as built, and a
window layer attending its whole row, a rotated full layer, int8 experts
or a router computed in bfloat16 (``perfbench/probe_smallthinker.py``,
which puts the same questions on the chip at the published widths) each
turn it false; and the driver's check of the engine's own programs on the
requests in flight, which a fault in those programs alone turns false."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import (flops, flops_swa, harness, probe_precision,
                       probe_smallthinker)
from perfbench.drivers import serve_closed_loop_swa as driver
from perfbench.layer_metrics import _swa

CELL = "smallthinker-l8-serve-longdoc-gen"
# PowerInfer/SmallThinker-21BA3B-Instruct, config.json (catalog
# architectures.jsonl), written out: the source's key, its value, the model
# group's key and value
PUBLISHED = [
    ("head_dim", 128, "head_size", 128),
    ("hidden_size", 2560, "hidden_size", 2560),
    ("max_position_embeddings", 16384, "max_position_embeddings", 16384),
    ("model_name", "smallthinker_21b_instruct", "family", "smallthinker"),
    ("moe_ffn_hidden_size", 768, "ffn_hidden_size", 768),
    ("moe_num_active_primary_experts", 6, "top_k", 6),
    ("moe_num_primary_experts", 64, "num_experts", 64),
    ("moe_primary_router_apply_softmax", True, "gate", "softmax_topk"),
    ("norm_topk_prob", True, "norm_topk_prob", True),
    ("num_attention_heads", 28, "num_attention_heads", 28),
    ("num_key_value_heads", 4, "num_key_value_heads", 4),
    ("rms_norm_eps", 1e-06, "norm_eps", 1e-06),
    ("rope_scaling", None, None, None),
    ("rope_theta", 1500000, "rope_theta", 1500000.0),
    ("sliding_window_size", 4096, "sliding_window", 4096),
    ("tie_word_embeddings", False, "tie_word_embeddings", False),
    ("vocab_size", 151936, "vocab_size", 151936),
]
PERIOD = [0, 1, 1, 1]   # the published lists are 13 of these


def test_every_width_is_the_published_one_and_only_the_depth_is_cut():
    bench = harness.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "smallthinker-21b-a3b-l8")
    data = harness.load_json(entry["file"])
    for key, value, mine, mapped in PUBLISHED:
        assert data[key] == value, key
        if mine is not None:
            assert data["model"][mine] == mapped, mine
    model = data["model"]
    assert data["num_hidden_layers"] == model["num_layers"] == 8   # of 52
    for key in ("rope_layout", "sliding_window_layout"):
        assert data[key] == model[key] == PERIOD * 2, key
    assert set(entry["reduced"]) == set(data["reduced"]) == {
        "num_hidden_layers", "num_layers", "rope_layout",
        "sliding_window_layout"}
    assert (model["mlp_act"], model["router_input"], model["norm"],
            model["position_embedding"]) == ("reglu", "block_input",
                                             "rmsnorm", "rope")
    assert set(data["assumed"]) >= {"router_input", "window", "attention"}
    cell = harness.load_json("perfbench", "cells", CELL + ".json")
    traffic = harness.load_json("perfbench", "traffic", "longdoc-gen.json")
    (tenant,) = traffic["tenants"]
    assert (traffic["clients"], cell["lanes"], traffic["block"]) == (24, 24, 4)
    assert tenant["prompt"] == {"dist": "lognormal", "median": 6144,
                                "sigma": 0.4, "min": 3072, "max": 12288}
    assert tenant["output"] == {"dist": "lognormal", "median": 256,
                                "sigma": 0.35, "min": 128, "max": 512}
    # no request can meet a full pool of either class (the engine sizes
    # the window class itself, from lanes, window and chunk)
    assert cell["cache_len"] >= 12288 + 512
    assert cell["pool_tokens"] == cell["lanes"] * cell["cache_len"]


def test_decode_tick_cost_on_a_hand_worked_case():
    model = dict(num_layers=4, num_attention_heads=4, num_key_value_heads=2,
                 head_size=8, hidden_size=64, sliding_window=16,
                 sliding_window_layout=PERIOD)
    assert flops_swa.layer_counts(model) == (1, 3)
    assert flops_swa.row_bytes(model) == 2 * 2 * 8 * 2      # K and V: 64
    # 100 live rows in the full layer, 30 in each of three window layers:
    # 190 rows x 64 bytes; 2 lanes x 4 heads x 8 x 2 bytes, in and out, in
    # 4 layers; two products of 8 a query head and row
    ops, bytes_ = flops_swa.decode_tick_cost(100, 30, 2, model)
    assert bytes_ == 190 * 64 + 2 * 2 * 32 * 2 * 4
    assert ops == 2 * 2 * 190 * 4 * 8
    assert flops_swa.pool_bytes_share(10, 4, model) == (10 + 12) / 40
    # the published widths: 2,048 bytes a row and layer, memory-bound
    data = harness.load_json("perfbench", "configs",
                             "smallthinker-21b-a3b-l8.json")["model"]
    assert flops_swa.row_bytes(data) == 2048
    assert flops_swa.layer_counts(data) == (2, 6)
    ops, bytes_ = flops_swa.decode_tick_cost(24 * 6800, 24 * 4096, 24, data)
    assert flops.roofline_seconds(ops, bytes_, {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})[1] == "memory"
    # what flops.paged_decode_call_cost would have counted for the same
    # tick: 28 x 91 lanes a row and every live row in every layer
    theirs = 8 * flops.paged_decode_call_cost(24 * 6800, 28, 2560 // 28, 24)[1]
    assert 4.5 < theirs / bytes_ < 7.5


def test_the_scope_reader_tells_a_window_layers_attention_from_a_full_ones():
    stack = "jit(_decode_fn)/cached_forward/gpt/layers/while/body/layer/attn"
    rows = [
        ["%while.1 = while(...)", "", "jit__decode_fn", 0.0, 100.0],
        ["%fleetx_decode_paged.1 = custom-call(...)",
         stack + "/cond/branch_0_fun/attn_full/fleetx_decode_paged/pallas_call",
         "jit__decode_fn", 10.0, 20.0],
        ["%fleetx_decode_paged.2 = custom-call(...)",
         stack + "/cond/branch_1_fun/attn_window/fleetx_decode_paged/pallas_call",
         "jit__decode_fn", 30.0, 30.0],
        ["%fusion.3 = fusion(...)", stack + "/qkv_proj/dot_general",
         "jit__decode_fn", 60.0, 30.0],
    ]
    seconds = _swa.scope_seconds({"/device:TPU:0": rows})
    assert seconds["attn_full"] == pytest.approx(20e-9)
    assert seconds["attn_window"] == pytest.approx(30e-9)
    assert seconds["total"] == pytest.approx(100e-9)
    assert _swa.scope_seconds({}) == {"attn_full": 0.0, "attn_window": 0.0,
                                      "total": 0.0}


@pytest.fixture(scope="module")
def tiny():
    """The cell at rehearsal size, computed in float32 (the weights still
    held in bfloat16), the layers' matrices scaled until they, and not the
    head alone, decide the logits, as in
    tests/perfbench/test_perfbench_olmoe.py: at 8 experts and top 2 an
    expert that changes hands carries a third of a token's expert output,
    so the limits set on the chip mean something here only in float32."""
    cell = harness.load_cell(CELL, tiny=True)
    cell.config["compute_dtype"] = "float32"
    model, variables = driver.ref_driver.build_model(cell, 3)
    variables = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 16.0 if "['layers']" in jax.tree_util.keystr(path)
        and x.ndim >= 3 else x, variables)
    return cell, model, variables


def check(cell, model, held, variables):
    engine = driver.build_engine(cell, model, held)
    assert engine.prefill_chunk == cell.deploy["prefill_chunk"]
    assert engine.cache_manager.window_pool is not None
    return driver.reference_check(engine, variables, cell, 3)


def test_the_engine_as_built_passes_the_reference_check(tiny):
    cell, model, variables = tiny
    assert {x.dtype for x in jax.tree.leaves(variables)} == {jnp.dtype("bfloat16")}
    out = check(cell, model, variables, variables)
    assert out["reference_ok"], out
    prompt, decode, tail, answers, answer_tokens = driver.check_sizes(cell)
    assert prompt > cell.config["model"]["sliding_window"]
    assert out["reference_positions_checked"] == tail + decode
    assert out["engine_tokens_checked"] == len(answers) * answer_tokens
    assert out["window_pages_recycled_in_check"] > 0
    assert out["experts_differ_positions"] == 0
    assert out["reference_rms_err"] < 1e-3 * out["reference_logit_std"]
    assert out["layers_ok"] and out["layer_experts_beside_reference"] == 0
    assert out["layer_weight_max_rel_err"] <= driver.LAYER_WEIGHT_TOL
    assert out["layer_output_rel_rms_err"] < 1e-3  # the weights are bfloat16


def test_an_ignored_window_turns_the_check_false(tiny):
    """Every window layer attending its whole row (a rotated full layer
    reads inside the limits at 52 positions and a head of 16: that one is
    the chip's to show, ``probe_smallthinker.py``, and
    tests/test_smallthinker_serving.py's on the full forward)."""
    cell, model, variables = tiny
    cfg = model.cfg
    changed = dict(sliding_window=cell.deploy["cache_len"])
    out = check(cell, model.clone(cfg=dataclasses.replace(cfg, **changed)),
                variables, variables)
    assert not out["reference_ok"], out
    assert out["layers_ok"]            # every layer, on the input it saw, is right
    assert out["reference_rms_err"] > (driver.REFERENCE_RMS_TOL
                                       * out["reference_logit_std"])


def test_int8_experts_turn_the_check_false(tiny):
    cell, model, variables = tiny
    out = check(cell, model, probe_precision.int8_experts(variables), variables)
    assert not out["reference_ok"] and not out["layers_ok"], out
    assert out["layer_weight_max_rel_err"] <= driver.LAYER_WEIGHT_TOL
    assert out["layer_output_rel_rms_err"] > driver.LAYER_OUTPUT_TOL


def test_a_router_in_bfloat16_turns_the_check_false(tiny):
    cell, model, variables = tiny
    with probe_precision.router_in_bfloat16():
        out = check(cell, model.clone(), variables, variables)
    assert not out["reference_ok"] and not out["layers_ok"], out
    assert out["layer_weight_max_rel_err"] > 10 * driver.LAYER_WEIGHT_TOL


@pytest.mark.parametrize("fault", probe_smallthinker.ENGINE_FAULTS)
def test_a_fault_in_the_engines_programs_alone_turns_the_engine_check_false(
        tiny, fault):
    """The engine's chunk prefill and tick built with the fault, the
    check's own programs without it: the rows the engine wrote for the
    requests in flight stand apart from theirs, from the first layer the
    fault reaches on. As built they are the same to the last bit here
    (float32, one lane or three)."""
    cell, model, variables = tiny
    ((name, out),) = probe_smallthinker.engine_readings(
        cell, driver, 3, 1.0, only=(fault,), built=(model, variables))
    assert out["engine_lanes_checked"] == cell.deploy["lanes"]
    assert out["engine_tokens_served_checked"] > 0
    by_layer = out["engine_rows_rel_rms_err_by_layer"]
    if fault == "engine_as_built":
        assert out["engine_ok"] and max(by_layer) < 1e-5, out
        return
    assert not out["engine_ok"], out
    assert out["engine_rows_max_rel_rms_err"] > 2 * driver.ENGINE_ROWS_TOL
    if fault == "engine_window_ignored":
        # layer 1 is the first with a window: what it wrote is layer 0's
        assert max(by_layer[:2]) < 1e-5 < min(by_layer[2:])


def test_the_loop_replayed_on_the_host_counts_tokens_as_the_cell_does():
    """``perfbench/simulate_closed_loop.py`` on the generator's own lengths:
    the number PERF.md section 7 gives for this seed and these two program
    times, and as many chunk-ticks as the prompts have chunks."""
    from perfbench import simulate_closed_loop

    pinned = harness.load_cell(CELL)
    cell = dataclasses.replace(pinned, traffic={
        k: v for k, v in pinned.traffic.items() if k != "order_seed"})
    out = simulate_closed_loop.simulate(cell, 3000000311, 40.0, 0.0205, 0.0275)
    assert out["serve_tokens_per_s"] == pytest.approx(448.5)
    assert out["requests_returned_in_window"] == 67
    # twice the window at the same times holds about twice the requests
    twice = simulate_closed_loop.simulate(cell, 3000000311, 80.0, 0.0205, 0.0275)
    assert 1.8 < twice["requests_returned_in_window"] / 67 < 2.2
    # the order the traffic file pins since PR 37 gives every seed one
    # number (the times of PR 34's traced run: PERF.md section 6)
    rates = [simulate_closed_loop.simulate(pinned, seed, 40.0, 0.015, 0.02693)[
        "serve_tokens_per_s"] for seed in (3000000311, 5)]
    assert rates == [pytest.approx(477.925)] * 2


def test_the_reference_sums_the_experts_it_is_given_at_the_positions_compared(tiny):
    """``given`` exchanges the router's own choice for the system's at the
    last positions and leaves the choice it REPORTS the router's."""
    cell, _, variables = tiny
    logits = driver.ref_driver.reference_module(cell).configured(
        cell.config["model"])
    tokens = np.random.default_rng(0).integers(1, 512, 40, dtype=np.int32)
    own, chosen, _ = logits(variables["params"], tokens, tail=4,
                            with_experts=True)
    same, again, _ = logits(variables["params"], tokens, tail=4,
                            with_experts=True, given=chosen[:, -4:])
    np.testing.assert_allclose(same, own, atol=1e-6)
    other = (np.asarray(chosen[:, -4:]) + 1) % cell.config["model"]["num_experts"]
    moved, reported, _ = logits(variables["params"], tokens, tail=4,
                                with_experts=True, given=other)
    assert np.abs(np.asarray(moved) - np.asarray(own)).max() > 1e-3
    # the first layer's router read the same stream either way (the later
    # ones read what the exchanged experts made of it)
    assert (np.asarray(reported)[0] == np.asarray(chosen)[0]).all()
    assert (np.asarray(again) == np.asarray(chosen)).all()
