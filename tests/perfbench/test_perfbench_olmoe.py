"""The OLMoE configuration against the published one, the expert layer's
operation and byte count on a hand-worked case, the reader of the expert
scopes on a hand-made trace, and the driver's reference check at rehearsal
size: it passes the engine as built, and int8 experts or a router computed
in bfloat16 (``perfbench/probe_precision.py``, which puts the same question
on the chip at the published widths) turn it false."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from perfbench import flops_moe, harness, probe_precision, serving
from perfbench.drivers import serve_closed_loop_ref as driver
from perfbench.layer_metrics import _moe

CELL = "olmoe-l8-serve-gen-batch"
# allenai/OLMoE-1B-7B-0125-Instruct, config.json (catalog architectures.jsonl),
# written out: the source's key, its value, the model group's key and value
PUBLISHED = [
    ("hidden_size", 2048, "hidden_size", 2048),
    ("intermediate_size", 1024, "ffn_hidden_size", 1024),
    ("num_attention_heads", 16, "num_attention_heads", 16),
    ("num_key_value_heads", 16, "num_attention_heads", 16),
    ("vocab_size", 50304, "vocab_size", 50304),
    ("max_position_embeddings", 4096, "max_position_embeddings", 4096),
    ("num_experts", 64, "num_experts", 64),
    ("num_experts_per_tok", 8, "top_k", 8),
    ("norm_topk_prob", False, "norm_topk_prob", False),
    ("rope_theta", 10000, "rope_theta", 10000.0),
    ("rms_norm_eps", 1e-05, "norm_eps", 1e-05),
    ("attention_bias", False, "use_bias", False),
    ("tie_word_embeddings", False, "tie_word_embeddings", False),
    ("hidden_act", "silu", "mlp_act", "swiglu"),
    ("model_type", "olmoe", "family", "olmoe"),
    ("clip_qkv", None, None, None),
    ("rope_scaling", None, None, None),
]


def test_every_width_is_the_published_one_and_only_the_depth_is_cut():
    bench = harness.load_json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "olmoe-1b-7b-l8")
    data = harness.load_json(entry["file"])
    for key, value, mine, mapped in PUBLISHED:
        assert data[key] == value, key
        if mine is not None:
            assert data["model"][mine] == mapped, mine
    assert data["num_hidden_layers"] == data["model"]["num_layers"] == 8  # of 16
    assert set(entry["reduced"]) == set(data["reduced"]) == {
        "num_layers", "num_hidden_layers"}
    model = data["model"]
    assert (model["position_embedding"], model["norm"], model["qk_norm"],
            model["gate"]) == ("rope", "rmsnorm", True, "softmax_topk")
    assert model["hidden_size"] // model["num_attention_heads"] == 128
    # the arithmetic the file repeats, against the program's own model
    # (flops.gpt_param_count knows the GPT-2 block only)
    import numpy as np

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    layer = (4 * 2048 ** 2 + 64 * 3 * 2048 * 1024 + 2048 * 64   # matrices
             + 2 * 2048 + 2 * 2048)                # two norms, q_norm, k_norm
    want = 8 * layer + 2 * 50304 * 2048 + 2048     # + embedding, head, final norm
    assert abs(want - 3.56e9) < 0.01e9
    shapes = jax.eval_shape(
        GPTForPretraining(GPTConfig.from_model_config(model)).init,
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == want
    cell = harness.load_json("perfbench", "cells", CELL + ".json")
    assert cell["pool_tokens"] == cell["lanes"] * cell["cache_len"] == 40960
    assert cell["cache_len"] >= 640 + 512 and cell["prefill_bucket"] == 64


def test_expert_layer_cost_on_a_hand_worked_case():
    # 2 pairs to 1 expert, hidden 4, width 3, 2 bytes an element:
    # weights 1 x 3 x 4 x 3 = 36 elements; rows 2 x (4 in + 3 out + 3 in +
    # 4 out) = 28 elements; 64 elements = 128 bytes;
    # 3 products x 2 pairs x 2 x 4 x 3 = 144 operations
    assert flops_moe.expert_layer_cost(2, 1, 4, 3) == (144.0, 128.0)
    # a decode tick of the cell: 256 pairs, 63 experts read, one layer
    ops, bytes_ = flops_moe.expert_layer_cost(256, 63, 2048, 1024)
    assert ops == 256 * 3 * 2 * 2048 * 1024
    assert bytes_ == (63 * 3 * 2048 * 1024 + 256 * 2 * 3072) * 2
    assert bytes_ / 819e9 > ops / 197e12      # memory-bound, by a factor 60


def test_the_scope_reader_books_innermost_scopes_and_self_time():
    stack = "jit(_decode_fn)/cached_forward/gpt/layers/while/body/layer/moe_mlp"
    rows = [
        ["%while.1 = while(...)", "", "jit__decode_fn", 0.0, 100.0],
        ["%fleetx_moe_gate_up.1 = custom-call(...)",
         stack + "/moe_experts/fleetx_moe_gate_up/pallas_call",
         "jit__decode_fn", 10.0, 40.0],
        ["%fusion.2 = fusion(...)", stack + "/moe_route/router/dot_general",
         "jit__decode_fn", 50.0, 10.0],
        ["%fusion.3 = fusion(...)", stack.replace("moe_mlp", "attn") + "/mul",
         "jit__decode_fn", 60.0, 30.0],
    ]
    seconds = _moe.scope_seconds({"/device:TPU:0": rows})
    assert seconds["moe_experts"] == pytest.approx(40e-9)
    assert seconds["moe_route"] == pytest.approx(10e-9)
    assert seconds["total"] == pytest.approx(100e-9)   # the while: 20 of its own
    assert _moe.scope_seconds({}) == {"moe_experts": 0.0, "moe_route": 0.0,
                                      "total": 0.0}


@pytest.fixture(scope="module")
def tiny():
    """The cell at rehearsal size, the layers' matrices scaled until they,
    and not the head alone, decide the logits (as at the published widths).

    Computed in FLOAT32 here (the weights still held in bfloat16). The
    driver's limits are set on the chip at the published widths, where the
    system's top 8 differ from the reference's at a third of the positions
    and the logits hardly notice: one of 8 experts with a weight of a
    fortieth changes hands. At 8 experts and top 2 the expert that changes
    hands carries a third of the token's expert output, so in bfloat16 the
    tiny engine AS BUILT stands at 0.9 of the logit spread, far outside any
    limit that means something. In float32 no expert changes hands in the
    engine as built (distance 1e-4), and the two faults below each move
    experts and logits far past the limits. What the faults read at the
    published widths is in PERF.md (Findings, PR 27: my chip runs)."""
    cell = harness.load_cell(CELL, tiny=True)
    cell.config["compute_dtype"] = "float32"
    model, variables = driver.build_model(cell, 3)
    variables = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 16.0 if "['layers']" in jax.tree_util.keystr(path)
        and x.ndim >= 3 else x, variables)
    return cell, model, variables


def test_the_engine_as_built_passes_the_reference_check(tiny):
    cell, model, variables = tiny
    assert {x.dtype for x in jax.tree.leaves(variables)} == {jnp.dtype("bfloat16")}
    engine = serving.build_engine(cell, model, variables)
    assert engine.prefill_bucket == cell.deploy["prefill_bucket"]
    out = driver.reference_check(engine, variables, cell, 3)
    assert out["reference_ok"], out
    assert out["engine_tokens_checked"] == 64
    assert out["experts_positions_checked"] == 84
    assert out["experts_differ_positions"] == 0
    assert out["reference_rms_err"] < 1e-3 * out["reference_logit_std"]
    assert out["layers_ok"] and out["layer_positions_checked"] == 2 * 84
    assert out["layer_experts_beside_reference"] == 0
    assert out["layer_weight_max_rel_err"] < 1e-5
    assert out["layer_output_rel_rms_err"] < 1e-3  # the weights are bfloat16


def test_int8_experts_turn_the_check_false(tiny):
    cell, model, variables = tiny
    engine = serving.build_engine(cell, model,
                                 probe_precision.int8_experts(variables))
    out = driver.reference_check(engine, variables, cell, 3)
    assert not out["reference_ok"] and not out["layers_ok"], out
    # the router is untouched and so are its weights; the experts' sum is not
    assert out["layer_weight_max_rel_err"] <= driver.LAYER_WEIGHT_TOL
    assert out["layer_output_rel_rms_err"] > driver.LAYER_OUTPUT_TOL
    assert out["reference_rms_err"] > (driver.REFERENCE_RMS_TOL
                                       * out["reference_logit_std"])


def test_a_router_in_bfloat16_turns_the_check_false(tiny):
    cell, model, variables = tiny
    with probe_precision.router_in_bfloat16():
        engine = serving.build_engine(cell, model.clone(), variables)
        out = driver.reference_check(engine, variables, cell, 3)
    assert not out["reference_ok"] and not out["layers_ok"], out
    assert out["layer_weight_max_rel_err"] > 10 * driver.LAYER_WEIGHT_TOL
    assert out["experts_differ_positions"] >= 1
    assert out["reference_max_abs_err"] > (driver.REFERENCE_MAX_TOL
                                           * out["reference_logit_std"])


def test_the_layer_check_judges_the_layer_on_the_input_it_saw(tiny):
    """In bfloat16 the tiny engine's logits stand far from the reference's
    (the fixture's docstring), because experts change hands as the layers'
    rounding moves their inputs; each layer, on the input it saw, still
    agrees with the reference's layer: the layer limits hold where the
    logit limits cannot."""
    cell, _, variables = tiny
    cell = dataclasses.replace(cell, config={**cell.config,
                                             "compute_dtype": "bfloat16"})
    model, _ = driver.build_model(cell, 3)
    engine = serving.build_engine(cell, model, variables)
    out = driver.reference_check(engine, variables, cell, 3)
    assert out["layers_ok"], out
    assert out["layer_experts_beside_reference"] == 0
    assert out["layer_positions_checked"] == 2 * 84
