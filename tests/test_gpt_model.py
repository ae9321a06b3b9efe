"""GPT model unit tests: init/forward/grad, recompute variants, scan vs
unrolled equivalence, loss masking."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt.model import (
    GPTConfig,
    GPTForPretraining,
    pretraining_loss,
)

TINY = GPTConfig(
    vocab_size=128,
    hidden_size=64,
    num_layers=2,
    num_attention_heads=4,
    ffn_hidden_size=128,
    max_position_embeddings=64,
    dtype=jnp.float32,
    use_flash_attention=False,
)


def _data(b=2, s=16, vocab=128, seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, vocab, (b, s)).astype(np.int32)
    labels = rng.randint(0, vocab, (b, s)).astype(np.int32)
    mask = np.ones((b, s), np.float32)
    return jnp.asarray(tokens), jnp.asarray(labels), jnp.asarray(mask)


def test_forward_shapes():
    tokens, _, _ = _data()
    model = GPTForPretraining(TINY)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, 128)
    assert logits.dtype == jnp.float32


def test_scan_param_stacking():
    tokens, _, _ = _data()
    model = GPTForPretraining(TINY)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    layer_params = variables["params"]["gpt"]["layers"]["layer"]
    qkv = layer_params["attn"]["qkv_proj"]["kernel"]
    value = qkv.value if hasattr(qkv, "value") else qkv
    assert value.shape[0] == TINY.num_layers  # stacked over the scan axis


def test_scan_vs_unrolled_same_loss():
    """Scanned and unrolled stacks must be numerically identical given the
    same params (re-keyed)."""
    tokens, labels, mask = _data()
    m_scan = GPTForPretraining(TINY)
    m_unroll = GPTForPretraining(
        GPTConfig(**{**TINY.__dict__, "scan_layers": False})
    )
    v_scan = jax.jit(m_scan.init)(jax.random.PRNGKey(0), tokens)
    # map scanned params [L, ...] -> unrolled layer_i params
    import flax

    flat = flax.traverse_util.flatten_dict(
        flax.core.unfreeze(v_scan["params"]), sep="/"
    )
    out = {}
    for k, v in flat.items():
        val = v.value if hasattr(v, "value") else v
        if k.startswith("gpt/layers/layer/"):
            for i in range(TINY.num_layers):
                out[k.replace("gpt/layers/layer/", f"gpt/layer_{i}/")] = val[i]
        else:
            out[k] = val
    v_unroll = {"params": flax.traverse_util.unflatten_dict(out, sep="/")}
    l1 = m_scan.apply(v_scan, tokens)
    l2 = m_unroll.apply(v_unroll, tokens)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("granularity", ["full", "full_attn", "core_attn"])
@pytest.mark.slow  # 18.8s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_recompute_matches_no_recompute(granularity):
    tokens, labels, mask = _data()
    base = GPTForPretraining(TINY)
    remat = GPTForPretraining(
        GPTConfig(
            **{
                **TINY.__dict__,
                "use_recompute": True,
                "recompute_granularity": granularity,
            }
        )
    )
    params = jax.jit(base.init)(jax.random.PRNGKey(0), tokens)

    def loss_fn(model):
        def f(p):
            return pretraining_loss(model.apply(p, tokens), labels, mask)

        return f

    l0, g0 = jax.value_and_grad(loss_fn(base))(params)
    l1, g1 = jax.value_and_grad(loss_fn(remat))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    flat0 = jax.tree.leaves(g0)
    flat1 = jax.tree.leaves(g1)
    for a, b in zip(flat0, flat1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


def test_no_recompute_layers_unrolled():
    tokens, _, _ = _data()
    cfg = GPTConfig(
        **{
            **TINY.__dict__,
            "use_recompute": True,
            "recompute_granularity": "full",
            "no_recompute_layers": (0,),
            "scan_layers": True,  # must auto-fall-back to unrolled
        }
    )
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    assert "layer_0" in params["params"]["gpt"]
    logits = model.apply(params, tokens)
    assert logits.shape == (2, 16, 128)


def test_causality():
    """Changing a future token must not change past logits."""
    tokens, _, _ = _data()
    model = GPTForPretraining(TINY)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    l1 = model.apply(params, tokens)
    tokens2 = tokens.at[:, -1].set((tokens[:, -1] + 1) % 128)
    l2 = model.apply(params, tokens2)
    np.testing.assert_allclose(
        np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]), rtol=1e-5, atol=1e-6
    )
    assert not np.allclose(np.asarray(l1[:, -1]), np.asarray(l2[:, -1]))


def test_loss_masking():
    tokens, labels, mask = _data()
    model = GPTForPretraining(TINY)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    logits = model.apply(params, tokens)
    full = pretraining_loss(logits, labels, mask)
    assert np.isfinite(float(full))
    half_mask = mask.at[:, : 16 // 2].set(0.0)
    half = pretraining_loss(logits, labels, half_mask)
    assert not np.isclose(float(full), float(half))
    zero = pretraining_loss(logits, labels, mask * 0)
    assert float(zero) == 0.0


def test_dropout_determinism_keys():
    """Same dropout key → same loss; different key → different loss."""
    tokens, labels, mask = _data()
    model = GPTForPretraining(TINY)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    k1, k2 = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
    a = model.apply(params, tokens, deterministic=False, rngs={"dropout": k1})
    b = model.apply(params, tokens, deterministic=False, rngs={"dropout": k1})
    c = model.apply(params, tokens, deterministic=False, rngs={"dropout": k2})
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(a), np.asarray(c))


def test_unfused_qkv():
    tokens, _, _ = _data()
    cfg = GPTConfig(**{**TINY.__dict__, "fuse_attn_qkv": False})
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    assert model.apply(params, tokens).shape == (2, 16, 128)
