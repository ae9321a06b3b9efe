"""The gate ``sigmoid_topk`` of ``parallel/moe.py`` ``DroplessMoEMLP`` alone,
against its definition written out in numpy: sigmoid scores in float32, a
bias that steers the CHOICE and never the weight, the chosen scores
normalised over their sum + 1e-6, the scaling factor; the Pallas expert
kernels under it at the new shapes' proportions (experts' width 7/8 of the
hidden size, 4 a token); and the counters, which dense layers beside the
expert layers leave alone."""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import traced_apply

from fleetx_tpu.models.gpt.model import GPTConfig
from fleetx_tpu.parallel.moe import MOE_STATS, DroplessMoEMLP

SIZES = dict(
    vocab_size=64, hidden_size=32, num_layers=3, num_attention_heads=4,
    ffn_hidden_size=28, dense_ffn_hidden_size=48, num_dense_layers=1,
    layer_types=("conv", "full_attention", "conv"), num_experts=8, top_k=4,
    expert_mode=True, gate="sigmoid_topk", use_expert_bias=True,
    expert_bias_init_std=0.1, norm_topk_prob=True, position_embedding="rope",
    norm="rmsnorm", mlp_act="swiglu", use_bias=False, dtype=jnp.float32,
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)


def layer(**changes):
    cfg = GPTConfig(**{**SIZES, **changes})
    module = DroplessMoEMLP(cfg)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 9, 32)),
                    jnp.float32)
    params = flax.core.meta.unbox(jax.jit(module.init)(
        jax.random.PRNGKey(1), x))
    params = jax.tree.map(lambda w: w * 8.0 if w.ndim == 2 else w, params)
    return module, params, x


def written_out(params, x, *, bias=True, normalise=True, scaling=1.0, k=4):
    p = jax.tree.map(np.asarray, params["params"])
    tokens = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    scores = 1.0 / (1.0 + np.exp(-(tokens @ p["router"]["kernel"])))
    ranked = scores + (p["expert_bias"] if bias else 0.0)
    chosen = np.argsort(-ranked, axis=-1)[:, :k]
    weights = np.take_along_axis(scores, chosen, -1)
    if normalise:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
    weights = weights * scaling
    out = np.zeros_like(tokens)
    for t in range(len(tokens)):
        for e, w in zip(chosen[t], weights[t]):
            gate = tokens[t] @ p["w_gate"][e]
            hidden = gate / (1.0 + np.exp(-gate)) * (tokens[t] @ p["w_up"][e])
            out[t] += w * (hidden @ p["w_down"][e])
    return out.reshape(x.shape), chosen, weights


@pytest.mark.parametrize("changes,kw", [
    ({}, {}),
    ({"norm_topk_prob": False}, {"normalise": False}),
    ({"routed_scaling_factor": 2.5}, {"scaling": 2.5}),
    ({"use_expert_bias": False, "expert_bias_init_std": 0.0},
     {"bias": False}),
])
def test_the_gate_is_its_definition(changes, kw):
    module, params, x = layer(**changes)
    y, sown = traced_apply(module, params, x, mutable=["routing"])
    want, chosen, weights = written_out(params, x, **kw)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    routing = sown["routing"]
    assert (np.sort(np.asarray(routing["experts"][0]).reshape(-1, 4), -1)
            == np.sort(chosen, -1)).all()
    np.testing.assert_allclose(
        np.sort(np.asarray(routing["weights"][0]).reshape(-1, 4), -1),
        np.sort(weights, -1), rtol=1e-5)


def test_the_bias_moves_the_choice_and_never_the_weight():
    module, params, x = layer()
    _, with_bias, _ = written_out(params, x)
    _, without, _ = written_out(params, x, bias=False)
    moved = (np.sort(with_bias, -1) != np.sort(without, -1)).any(-1).mean()
    assert 0.2 < moved <= 1.0
    # a bias the same for every expert changes nothing at all
    flat = jax.tree_util.tree_map_with_path(
        lambda path, w: jnp.full_like(w, 0.3)
        if "expert_bias" in jax.tree_util.keystr(path) else w, params)
    np.testing.assert_allclose(
        np.asarray(traced_apply(module, flat, x)),
        written_out(params, x, bias=False)[0], rtol=2e-4, atol=2e-5)


def test_the_kernels_run_the_gate_at_the_new_proportions(monkeypatch):
    """The Mosaic kernels (interpreted) under the sigmoid gate, the experts
    taken from the layer stack at an index that skips a dense layer: the
    same sum as ``ragged_dot``'s."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    module, params, x = layer()
    stack = tuple(jnp.stack([jnp.zeros_like(params["params"][k]),
                             params["params"][k]])
                  for k in ("w_gate", "w_up", "w_down"))
    cache = {"moe_stats": jnp.zeros((2, 2 * len(MOE_STATS) * 2), jnp.uint32)}
    y, mut = traced_apply(module, {**params, "cache": cache}, x, decode=True,
                          expert_stack=stack, layer_index=jnp.int32(1),
                          mutable=["cache"])
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(traced_apply(module, params, x)),
        rtol=2e-4, atol=2e-5)
    stats = np.asarray(mut["cache"]["moe_stats"])
    assert not stats[0].any() and stats[1].any()


def test_dense_layers_count_nothing_in_the_expert_counters():
    """``moe_stats`` has a row an EXPERT layer (the roofline reader
    multiplies one layer's cost by its rows): 2 of this stack's 3."""
    from fleetx_tpu.models.gpt.mixed_stack import layer_plan
    from fleetx_tpu.models.gpt.generation import init_decode_cache
    from fleetx_tpu.models.gpt.model import GPTForPretraining
    import dataclasses

    cfg = dataclasses.replace(GPTConfig(**SIZES), decode_cache_len=32,
                              decode_num_pages=9, decode_page_size=8)
    assert layer_plan(cfg)["counts"] == {"conv": 2, "mamba": 0, "kda": 0,
                                         "attention": 1, "dense": 1,
                                         "experts": 2}
    cache = init_decode_cache(GPTForPretraining(cfg), 2)["gpt"]["layers"]
    assert cache["moe_stats"].shape == (2, 24)
    assert cache["cached_key"].shape == (9, 8, 32)        # one attention layer
    assert cache["conv_state"].shape == (2 * 9, 2, 32)    # two conv layers
