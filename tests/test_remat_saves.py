"""recompute_extra_saves: graded remat save-sets (models/gpt/model.py).

The granularity's base save-set plus extra checkpoint_name'd tensors must
not change the math — only the memory/recompute tradeoff.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt.model import (
    GPTConfig,
    GPTForPretraining,
    _remat_policy,
)


def _loss_and_grads(cfg, dropout_key=None):
    model = GPTForPretraining(cfg)
    tokens = (jnp.arange(64).reshape(2, 32) * 7) % cfg.vocab_size
    labels = jnp.roll(tokens, -1, axis=1)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    train = {} if dropout_key is None else dict(
        deterministic=False, rngs={"dropout": dropout_key})

    def loss_fn(params):
        logits = model.apply(params, tokens, **train)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(
            jnp.take_along_axis(lp, labels[..., None], axis=-1)
        )

    loss, grads = jax.value_and_grad(loss_fn)(params)
    return loss, grads


def _cfg(**kw):
    base = dict(
        vocab_size=128, hidden_size=64, num_layers=2, num_attention_heads=4,
        ffn_hidden_size=128, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_recompute=True,
        recompute_granularity="core_attn",
    )
    base.update(kw)
    return GPTConfig(**base)


@pytest.mark.slow  # 24.5s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_extra_saves_do_not_change_math():
    l0, g0 = _loss_and_grads(_cfg())
    l1, g1 = _loss_and_grads(_cfg(
        recompute_extra_saves=("qkv_out", "ffn_gelu")))
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g0, g1,
    )


@pytest.mark.slow  # 8.4s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_full_granularity_with_saves_is_graded():
    pol = _remat_policy(_cfg(recompute_granularity="full",
                             recompute_extra_saves=("ffn_gelu",)))
    assert pol is not None
    l0, _ = _loss_and_grads(_cfg(recompute_granularity="full"))
    l1, _ = _loss_and_grads(_cfg(recompute_granularity="full",
                                 recompute_extra_saves=("ffn_gelu",)))
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)


def test_from_model_config_parses_csv_and_list():
    a = GPTConfig.from_model_config(
        {"vocab_size": 128, "recompute_extra_saves": "qkv_out,ffn_gelu"})
    assert a.recompute_extra_saves == ("qkv_out", "ffn_gelu")
    b = GPTConfig.from_model_config(
        {"vocab_size": 128, "recompute_extra_saves": ["mlp_out"]})
    assert b.recompute_extra_saves == ("mlp_out",)


def test_unknown_save_name_raises():
    import pytest

    with pytest.raises(ValueError, match="checkpoint_name"):
        _remat_policy(_cfg(recompute_extra_saves=("qkv",)))


def _saved_names(policy):
    """Which of the model's checkpoint names ``policy`` saves."""
    from jax._src.ad_checkpoint import name_p
    from fleetx_tpu.models.gpt.model import _CHECKPOINT_NAMES

    return {n for n in _CHECKPOINT_NAMES if policy(name_p, name=n)}


@pytest.mark.parametrize("granularity,extra,saved", [
    ("core_attn", None, {"core_attn_out", "core_attn_lse"}),
    ("full_attn", None, {"attn_out"}),
    ("full", None, set()),
    # the statistic is a name like the others: full_attn may add it
    ("full_attn", ("core_attn_lse",), {"attn_out", "core_attn_lse"}),
    ("full", ("core_attn_out", "core_attn_lse"),
     {"core_attn_out", "core_attn_lse"}),
])
def test_policy_saves_what_the_granularity_states(granularity, extra, saved):
    policy = _remat_policy(_cfg(recompute_granularity=granularity,
                                recompute_extra_saves=extra))
    assert _saved_names(policy) == saved


def _kernel_calls(jaxpr, kernel_name):
    """``pallas_call`` equations of the named kernel, sub-jaxprs included
    (a scan's body counts once, whatever its length)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == kernel_name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _kernel_calls(sub, kernel_name)
    return n


@pytest.mark.parametrize("sharded", [False, True])
@pytest.mark.parametrize("scan_layers", [True, False])
def test_core_attn_runs_the_flash_forward_once_a_layer(
        monkeypatch, eight_devices, scan_layers, sharded):
    """The backward kernels read the forward's output and row statistic;
    ``core_attn`` saves both by name, so the rematerialised layer stops
    before the kernel. ``full`` saves nothing and runs it again. Under a
    dp2 x mp2 mesh the kernel sits in a ``shard_map``: the same counts."""
    import contextlib

    from fleetx_tpu.ops.pallas.flash_attention import KERNEL_NAMES
    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh

    # the kernels, in interpret mode (one 32-row tile a sequence)
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    bodies = 1 if scan_layers else 2  # a scanned layer is one body
    mesh = build_mesh(MeshConfig(dp=2, mp=2), eight_devices[:4])

    def calls(granularity):
        cfg = _cfg(recompute_granularity=granularity,
                   scan_layers=scan_layers)
        model = GPTForPretraining(cfg)
        tokens = (jnp.arange(128).reshape(4, 32) * 7) % cfg.vocab_size
        params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
        with use_mesh(mesh) if sharded else contextlib.nullcontext():
            jaxpr = jax.make_jaxpr(jax.grad(
                lambda p: model.apply(p, tokens).astype(jnp.float32).sum()
            ))(params).jaxpr
        assert ("shard_map" in str(jaxpr)) == sharded
        return [_kernel_calls(jaxpr, k) // bodies for k in KERNEL_NAMES]

    assert calls("core_attn") == [1, 1, 1]  # forward, dq, dkv
    assert calls("full") == [2, 1, 1]


def test_core_attn_flash_is_bitwise_no_recompute(monkeypatch):
    """Saving the statistic changes which program runs, not one bit of
    what it computes: the saved values are the ones a second forward would
    have produced (the dropout bits are a hash of the seed)."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")

    def run(**kw):
        # unrolled: inside a scanned body XLA:CPU sums the LayerNorm scale's
        # gradient in another order under ANY recompute (1e-9; 'full' too)
        return _loss_and_grads(
            _cfg(attention_probs_dropout_prob=0.1, scan_layers=False, **kw),
            dropout_key=jax.random.PRNGKey(7))

    l0, g0 = run(use_recompute=False)
    l1, g1 = run()  # core_attn
    assert float(l0) == float(l1)
    jax.tree.map(np.testing.assert_array_equal, g0, g1)
