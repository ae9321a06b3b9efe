"""Fused LM-head + cross-entropy kernel (ops/pallas/ce_loss.py).

Parity against the plain logsumexp reference (models/gpt/model.py
pretraining_loss math) in forward and both gradients, bf16 path, block
fitting, TPU lowering, and the end-to-end model integration
(GPTForPretraining with fused_ce=True == the logits path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.ops.pallas.ce_loss import (
    fit_vocab_block,
    fused_linear_ce,
)

N, D, V = 64, 32, 384  # V = 3*128: one aligned vocab block


def _hwl(n=N, d=D, v=V, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(ks[0], (n, d), dtype)
    w = jax.random.normal(ks[1], (v, d), dtype)
    labels = jax.random.randint(ks[2], (n,), 0, v)
    return h, w, labels


def _ref_token_loss(h, w, labels):
    logits = (h.astype(jnp.float32) @ w.astype(jnp.float32).T)
    logz = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return logz - lab


def test_fit_vocab_block():
    assert fit_vocab_block(50304) == 384  # GPT vocab: 384 | 50304
    assert fit_vocab_block(512) == 512
    assert fit_vocab_block(1000) is None  # no lane-aligned block divides
    assert fit_vocab_block(130048, want=512) == 512
    assert fit_vocab_block(25152) == 64   # GPT vocab / mp2: 64-lane fallback
    assert fit_vocab_block(12576) is None  # below the 64-lane floor


def test_forward_matches_reference():
    h, w, labels = _hwl()
    out = fused_linear_ce(h, w, labels)
    ref = _ref_token_loss(h, w, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_forward_multi_token_and_vocab_blocks():
    # several token blocks AND several vocab blocks stream through scratch
    h, w, labels = _hwl(n=512, v=1152)  # 1152 = 3 x 384
    out = fused_linear_ce(h, w, labels)
    ref = _ref_token_loss(h, w, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_grads_match_reference():
    h, w, labels = _hwl()
    mask = jnp.asarray(np.random.default_rng(0).integers(0, 2, (N,)),
                       jnp.float32)

    def loss_fused(h, w):
        return (fused_linear_ce(h, w, labels) * mask).sum()

    def loss_ref(h, w):
        return (_ref_token_loss(h, w, labels) * mask).sum()

    gf = jax.grad(loss_fused, argnums=(0, 1))(h, w)
    gr = jax.grad(loss_ref, argnums=(0, 1))(h, w)
    # the chip's exp differs from the host's in the last bits: its run
    # read 1.3e-4 on one element of 2048 (CPU: within 1e-4)
    atol = 2e-4 if jax.default_backend() == "tpu" else 1e-4
    for a, b, name in zip(gf, gr, ("dh", "dw")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=atol,
                                   err_msg=f"{name} mismatch")


def test_bf16_inputs():
    h, w, labels = _hwl(dtype=jnp.bfloat16)
    out = fused_linear_ce(h, w, labels)
    assert out.dtype == jnp.float32
    ref = _ref_token_loss(h, w, labels)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    g = jax.grad(lambda a, b: fused_linear_ce(a, b, labels).sum(),
                 argnums=(0, 1))(h, w)
    assert g[0].dtype == jnp.bfloat16 and g[1].dtype == jnp.bfloat16
    assert all(np.isfinite(np.asarray(x, np.float32)).all() for x in g)


def test_unaligned_vocab_raises():
    h, w, labels = _hwl(v=1000)
    with pytest.raises(ValueError):
        fused_linear_ce(h, w, labels)


def test_kernels_lower_for_tpu():
    import fleetx_tpu.ops.pallas.ce_loss as ce

    orig = ce._interpret
    ce._interpret = lambda: False
    try:
        h, w, labels = _hwl(n=256, d=128, v=768, dtype=jnp.bfloat16)

        def fwd(h, w):
            return fused_linear_ce(h, w, labels).sum()

        def bwd(h, w):
            return jax.grad(fwd, argnums=(0, 1))(h, w)

        jax.jit(fwd).trace(h, w).lower(lowering_platforms=("tpu",))
        jax.jit(bwd).trace(h, w).lower(lowering_platforms=("tpu",))
    finally:
        ce._interpret = orig


@pytest.mark.slow  # 27.9s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_model_fused_ce_matches_logits_path():
    """GPTForPretraining(fused_ce) loss + grads == the logits path."""
    from fleetx_tpu.models.gpt.model import (
        GPTConfig, GPTForPretraining, masked_loss_mean, pretraining_loss,
    )

    base = dict(
        vocab_size=384, hidden_size=32, num_layers=2, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False,
    )
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 384, (2, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 384, (2, 32)), jnp.int32)
    mask = jnp.ones((2, 32), jnp.float32)

    plain = GPTForPretraining(GPTConfig(**base))
    fused = GPTForPretraining(GPTConfig(**base, fused_ce=True))
    params = jax.jit(plain.init)(jax.random.PRNGKey(0), tokens)

    def loss_plain(p):
        return pretraining_loss(plain.apply(p, tokens), labels, mask)

    def loss_fused(p):
        return masked_loss_mean(
            fused.apply(p, tokens, labels=labels), mask)

    lp, gp = jax.value_and_grad(loss_plain)(params)
    lf, gf = jax.value_and_grad(loss_fused)(params)
    np.testing.assert_allclose(float(lf), float(lp), rtol=1e-5)
    flat_p = jax.tree.leaves(gp)
    flat_f = jax.tree.leaves(gf)
    for a, b in zip(flat_f, flat_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_mesh_dp_matches_unsharded(eight_devices):
    """dp2 x fsdp2 mesh: the kernel shard_maps over the token dim and
    matches the unsharded call bitwise."""
    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh

    h, w, labels = _hwl(n=64)
    ref = fused_linear_ce(h, w, labels)
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2), eight_devices[:4])
    with use_mesh(mesh):
        out = fused_linear_ce(h, w, labels)
        g = jax.grad(lambda a, b: fused_linear_ce(a, b, labels).sum(),
                     argnums=(0, 1))(h, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    gr = jax.grad(lambda a, b: fused_linear_ce(a, b, labels).sum(),
                  argnums=(0, 1))(h, w)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_module_demotes_fused_ce_when_ineligible(eight_devices, tmp_path):
    """GPTModule silently falls back to the XLA logits path when fused_ce
    cannot apply (unaligned vocab like GPT-2's 50257, or mp/cp > 1)."""
    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import AttrDict, process_configs

    def cfg(vocab, mp=1):
        c = AttrDict(
            Global=AttrDict(seed=0, global_batch_size=8),
            Engine=AttrDict(max_steps=1, logging_freq=1,
                            mix_precision=AttrDict(use_pure_fp16=False),
                            save_load=AttrDict(save_steps=10**9,
                                               output_dir=str(tmp_path))),
            Model=AttrDict(module="GPTModule", vocab_size=vocab,
                           hidden_size=32, num_layers=2,
                           num_attention_heads=2, ffn_hidden_size=64,
                           max_position_embeddings=32,
                           hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           fused_ce=True, use_flash_attention=False),
            Optimizer=AttrDict(
                name="AdamW", weight_decay=0.0,
                lr=AttrDict(name="CosineAnnealingWithWarmupDecay",
                            decay_steps=10, max_lr=1e-3, min_lr=1e-4)),
            Distributed=AttrDict(dp_degree=8 // mp, mp_degree=mp),
        )
        process_configs(c, nranks=8)
        return c

    m = build_module(cfg(50257))  # GPT-2 vocab: no lane-aligned block
    assert not m.gpt_config.fused_ce
    m = build_module(cfg(50304))
    assert m.gpt_config.fused_ce
    # mp2 is now SUPPORTED via the vocab-parallel kernel (see
    # test_module_fused_ce_allows_mp)


@pytest.mark.slow  # 27.4s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_mesh_vocab_parallel_matches_unsharded(eight_devices):
    """mp2 (and dp2 x mp2): the embedding shards over the vocab dim and the
    global logsumexp/label-logit combine across shards — forward and both
    grads must match the unsharded kernel."""
    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh

    h, w, labels = _hwl(n=64, v=768)  # 768 = 2 x 384: aligned per shard

    def loss(a, b):
        return (fused_linear_ce(a, b, labels) ** 2).sum()

    ref = fused_linear_ce(h, w, labels)
    gr = jax.grad(loss, argnums=(0, 1))(h, w)
    for degrees in (dict(mp=2), dict(dp=2, mp=2)):
        mesh = build_mesh(MeshConfig(**degrees), eight_devices[:4])
        with use_mesh(mesh):
            out = fused_linear_ce(h, w, labels)
            gm = jax.grad(loss, argnums=(0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        for a, b, name in zip(gm, gr, ("dh", "dw")):
            # f32 accumulation order differs between the sharded and
            # unsharded walks; values reach O(100)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=5e-4,
                                       err_msg=f"{name} {degrees}")


def test_mesh_vocab_parallel_vs_logits_reference(eight_devices):
    """mp2 fused CE vs the dense logsumexp reference (not just the
    unsharded kernel): catches errors common to both kernel paths."""
    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh

    h, w, labels = _hwl(n=64, v=768, seed=3)
    mesh = build_mesh(MeshConfig(mp=2), eight_devices[:2])
    with use_mesh(mesh):
        out = fused_linear_ce(h, w, labels)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref_token_loss(h, w, labels)),
                               rtol=1e-5, atol=1e-5)


def test_module_fused_ce_allows_mp(eight_devices, tmp_path):
    """mp>1 no longer demotes (vocab-parallel path); unaligned shard does."""
    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import AttrDict, process_configs

    def cfg(vocab, mp):
        c = AttrDict(
            Global=AttrDict(seed=0, global_batch_size=8),
            Engine=AttrDict(max_steps=1, logging_freq=1,
                            mix_precision=AttrDict(use_pure_fp16=False),
                            save_load=AttrDict(save_steps=10**9,
                                               output_dir=str(tmp_path))),
            Model=AttrDict(module="GPTModule", vocab_size=vocab,
                           hidden_size=32, num_layers=2,
                           num_attention_heads=2, ffn_hidden_size=64,
                           max_position_embeddings=32,
                           hidden_dropout_prob=0.0,
                           attention_probs_dropout_prob=0.0,
                           fused_ce=True, use_flash_attention=False),
            Optimizer=AttrDict(
                name="AdamW", weight_decay=0.0,
                lr=AttrDict(name="CosineAnnealingWithWarmupDecay",
                            decay_steps=10, max_lr=1e-3, min_lr=1e-4)),
            Distributed=AttrDict(dp_degree=8 // mp, mp_degree=mp),
        )
        process_configs(c, nranks=8)
        return c

    # mp2: vocab shard 25152 = 64*393 -> 64-lane fallback block, allowed
    assert build_module(cfg(50304, 2)).gpt_config.fused_ce
    # mp4: shard 12576 = 32*393 -> below the 64-lane floor, demoted
    assert not build_module(cfg(50304, 4)).gpt_config.fused_ce


def test_kernels_lower_for_tpu_64_block():
    """The 64-lane fallback block (GPT vocab / mp2 = 25152 = 64*393) must
    survive Mosaic lowering, not just the interpreter — last block dims
    that DIVIDE 128 are legal but this is the only place we prove it."""
    import fleetx_tpu.ops.pallas.ce_loss as ce

    assert fit_vocab_block(25152) == 64
    orig = ce._interpret
    ce._interpret = lambda: False
    try:
        # v=448 = 64*7: forces block_v=64 (no 128-multiple divides)
        h, w, labels = _hwl(n=64, d=128, v=448, dtype=jnp.bfloat16)
        assert fit_vocab_block(448) == 64

        def fwd(h, w):
            return fused_linear_ce(h, w, labels).sum()

        def bwd(h, w):
            return jax.grad(fwd, argnums=(0, 1))(h, w)

        jax.jit(fwd).trace(h, w).lower(lowering_platforms=("tpu",))
        jax.jit(bwd).trace(h, w).lower(lowering_platforms=("tpu",))
    finally:
        ce._interpret = orig


@pytest.mark.slow  # 12.9s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_mesh_vocab_parallel_64_block_shard(eight_devices):
    """mp2 over v=384: each shard is 192 = 64*3, exercising the 64-lane
    fallback through the vocab-parallel path end to end."""
    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh

    h, w, labels = _hwl(n=64, v=384, seed=5)
    assert fit_vocab_block(192) == 64
    ref = _ref_token_loss(h, w, labels)
    mesh = build_mesh(MeshConfig(mp=2), eight_devices[:2])
    with use_mesh(mesh):
        out = fused_linear_ce(h, w, labels)
        g = jax.grad(lambda a, b: (fused_linear_ce(a, b, labels) ** 2).sum(),
                     argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    gr = jax.grad(lambda a, b: (_ref_token_loss(a, b, labels) ** 2).sum(),
                  argnums=(0, 1))(h, w)
    for a, b, name in zip(g, gr, ("dh", "dw")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=5e-4,
                                   err_msg=name)
