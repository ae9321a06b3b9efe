"""HashDropout: contract parity with nn.Dropout (ops/dropout.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.ops.dropout import HashDropout

RATE = 0.25


def _apply(x, key, rate=RATE, deterministic=False):
    m = HashDropout(rate)
    return m.apply({}, x, deterministic, rngs={"dropout": key})


def test_deterministic_passthrough():
    x = jnp.ones((4, 8))
    out = HashDropout(RATE).apply({}, x, True)
    np.testing.assert_array_equal(out, x)


def test_zero_rate_passthrough():
    x = jnp.ones((4, 8))
    out = HashDropout(0.0).apply({}, x, False, rngs={"dropout": jax.random.PRNGKey(0)})
    np.testing.assert_array_equal(out, x)


def test_same_key_same_mask_diff_key_diff_mask():
    x = jnp.ones((16, 64))
    a = _apply(x, jax.random.PRNGKey(7))
    b = _apply(x, jax.random.PRNGKey(7))
    c = _apply(x, jax.random.PRNGKey(8))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_values_are_zero_or_scaled():
    x = jnp.full((32, 128), 2.0)
    out = np.asarray(_apply(x, jax.random.PRNGKey(3)))
    scaled = 2.0 / (1.0 - RATE)
    near_zero = np.abs(out) < 1e-6
    near_scaled = np.abs(out - scaled) < 1e-5
    assert np.all(near_zero | near_scaled)
    assert near_zero.any() and near_scaled.any()


def test_keep_fraction_close_to_rate():
    x = jnp.ones((256, 512))
    out = np.asarray(_apply(x, jax.random.PRNGKey(11)))
    keep_frac = (out != 0).mean()
    assert abs(keep_frac - (1.0 - RATE)) < 0.01
    # inverted-scale preserves the mean
    assert abs(out.mean() - 1.0) < 0.02


def test_gradient_is_the_mask_scale():
    x = jnp.ones((8, 32))
    key = jax.random.PRNGKey(5)

    def loss(x):
        return jnp.sum(_apply(x, key))

    g = np.asarray(jax.grad(loss)(x))
    out = np.asarray(_apply(x, key))
    np.testing.assert_allclose(g, out, rtol=1e-6)  # d(x*scale)/dx == scale


def test_bf16_dtype_preserved():
    x = jnp.ones((8, 32), jnp.bfloat16)
    out = _apply(x, jax.random.PRNGKey(1))
    assert out.dtype == jnp.bfloat16


def test_full_rate_zeros():
    x = jnp.ones((4, 8))
    out = _apply(x, jax.random.PRNGKey(0), rate=1.0)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("shape", [(3, 5), (2, 7, 11), (4, 8, 16, 2)])
def test_arbitrary_shapes_under_jit(shape):
    x = jnp.ones(shape)
    key = jax.random.PRNGKey(2)
    out = jax.jit(lambda x: _apply(x, key))(x)
    assert out.shape == shape


def test_model_level_determinism():
    """GPT with fast_dropout: same dropout key → same loss, diff key → diff
    (mirrors test_gpt_model.py::test_dropout_determinism_keys)."""
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_attention_heads=4, ffn_hidden_size=128,
                    max_position_embeddings=32, hidden_dropout_prob=0.2,
                    attention_probs_dropout_prob=0.0, dtype=jnp.float32,
                    fast_dropout=True)
    model = GPTForPretraining(cfg)
    tokens = jnp.arange(32)[None, :] % 128
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    k1, k2 = jax.random.PRNGKey(1), jax.random.PRNGKey(2)
    a = model.apply(params, tokens, deterministic=False, rngs={"dropout": k1})
    b = model.apply(params, tokens, deterministic=False, rngs={"dropout": k1})
    c = model.apply(params, tokens, deterministic=False, rngs={"dropout": k2})
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(a), np.asarray(c))


def test_fast_dropout_false_restores_nn_dropout():
    from flax import linen as nn

    from fleetx_tpu.ops.dropout import dropout_layer

    assert isinstance(dropout_layer(0.1, "d", False), nn.Dropout)
    assert isinstance(dropout_layer(0.1, "d", True), HashDropout)


@pytest.mark.slow  # 6.8s baseline (PR 12 tier-1 budget audit): the
def test_fast_dropout_false_end_to_end():
    # nn.Dropout-vs-hash equivalence units stay tier-1
    """The nn.Dropout rollback path still trains (GPT forward+backward)."""
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_attention_heads=4, ffn_hidden_size=128,
                    max_position_embeddings=32, hidden_dropout_prob=0.2,
                    attention_probs_dropout_prob=0.0, dtype=jnp.float32,
                    fast_dropout=False)
    model = GPTForPretraining(cfg)
    tokens = jnp.arange(32)[None, :] % 128
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)

    def loss(params):
        logits = model.apply(params, tokens, deterministic=False,
                             rngs={"dropout": jax.random.PRNGKey(1)})
        return jnp.mean(logits.astype(jnp.float32) ** 2)

    l, g = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(l))
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(g))
