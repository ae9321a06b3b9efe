"""Flash-kernel ring attention (VERDICT r4 weak #4).

The default cp path now runs the Pallas kernel per (q-block, kv-block)
pair inside the ring — these tests pin:
- the kernel path actually engages (call counter, not just parity),
- forward/grad parity vs the plain XLA reference across cp degrees
  (multi-hop rings exercise both where-branches of the hop classifier),
- attention dropout under cp: identical realized mask to the single-device
  flash kernel (bits keyed on global ids — zig-zag block ids ARE original
  positions), gradients included — the restriction the GPT model used to
  raise NotImplementedError for.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import fleetx_tpu.ops.pallas.flash_attention as fa
from fleetx_tpu.ops.attention import causal_attention
from fleetx_tpu.parallel.context_parallel import (
    ring_self_attention,
    zigzag_merge,
    zigzag_split,
)
from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh


def _qkv(b=2, s=64, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.fixture
def flash_calls(monkeypatch):
    """Counts per-pair kernel invocations inside the ring."""
    calls = {"n": 0}
    orig = fa.block_fwd_lse

    def counting(*args, **kwargs):
        calls["n"] += 1
        return orig(*args, **kwargs)

    monkeypatch.setattr(fa, "block_fwd_lse", counting)
    return calls


def _ring(q, k, v, mesh, cp, causal=True, rate=0.0, rng=None):
    qz, kz, vz = (zigzag_split(x, cp) for x in (q, k, v))
    with use_mesh(mesh):
        out = jax.jit(
            lambda a, b, c: ring_self_attention(
                a, b, c, mesh=mesh, causal=causal, expected_cp=cp,
                dropout_rate=rate, dropout_rng=rng,
            )
        )(qz, kz, vz)
    return zigzag_merge(out, cp)


@pytest.mark.parametrize("cp", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.slow  # 47.1s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_flash_ring_forward_matches_reference(eight_devices, flash_calls,
                                              cp, causal):
    q, k, v = _qkv(s=128)  # s_blk = 32 or 16: kernel path for both cps
    mesh = build_mesh(MeshConfig(cp=cp), eight_devices[:cp])
    out = _ring(q, k, v, mesh, cp, causal=causal)
    ref = causal_attention(q, k, v, causal=causal, use_flash=False)
    assert flash_calls["n"] > 0, "flash ring did not engage"
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("cp", [2, 4])
@pytest.mark.slow  # 38.4s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_flash_ring_grads_match_reference(eight_devices, cp):
    """Custom-VJP ring backward (kv + dk/dv co-rotation) vs autodiff of the
    XLA reference. cp=4 exercises both hop-classifier branches."""
    q, k, v = _qkv(s=128)
    mesh = build_mesh(MeshConfig(cp=cp), eight_devices[:cp])

    def ring_loss(q, k, v):
        return (_ring(q, k, v, mesh, cp) ** 2).sum()

    def ref_loss(q, k, v):
        return (causal_attention(q, k, v, use_flash=False) ** 2).sum()

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_ring_dropout_matches_single_kernel(eight_devices):
    """Same rng => the cp2 ring realizes the SAME dropout mask as the
    unsharded flash kernel: bits are keyed on original global positions."""
    q, k, v = _qkv(s=128)
    rng = jax.random.PRNGKey(11)
    mesh = build_mesh(MeshConfig(cp=2), eight_devices[:2])
    out = _ring(q, k, v, mesh, 2, rate=0.2, rng=rng)
    ref = fa.flash_attention(q, k, v, dropout_rate=0.2, dropout_rng=rng,
                             mesh_shard=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow  # 31.5s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_flash_ring_dropout_grads_match_single_kernel(eight_devices):
    q, k, v = _qkv(s=64)
    rng = jax.random.PRNGKey(5)
    mesh = build_mesh(MeshConfig(cp=2), eight_devices[:2])

    def ring_loss(q, k, v):
        return (_ring(q, k, v, mesh, 2, rate=0.1, rng=rng) ** 2).sum()

    def ref_loss(q, k, v):
        return (fa.flash_attention(q, k, v, dropout_rate=0.1,
                                   dropout_rng=rng,
                                   mesh_shard=False) ** 2).sum()

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.slow  # 9.0s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_flash_ring_with_dp_mp_dropout(eight_devices):
    """cp2 x dp2 x mp2: batch/head axes sharded inside the same shard_map;
    the kernel's meta must globalize (batch, head) ids so the mask still
    matches the unsharded kernel."""
    q, k, v = _qkv(b=4, s=64)
    rng = jax.random.PRNGKey(3)
    mesh = build_mesh(MeshConfig(dp=2, cp=2, mp=2), eight_devices)
    out = _ring(q, k, v, mesh, 2, rate=0.2, rng=rng)
    ref = fa.flash_attention(q, k, v, dropout_rate=0.2, dropout_rng=rng,
                             mesh_shard=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_cp2_lowering_contains_kernel_custom_call(eight_devices):
    """TPU lowering of a cp2 ring step contains the Mosaic custom call at
    the per-shard block shape — the ring hops run the kernel, not einsum
    attention (VERDICT r4 item #3 done-criterion)."""
    b, s, h, d = 2, 256, 4, 64
    q = jnp.zeros((b, s, h, d), jnp.bfloat16)
    mesh = build_mesh(MeshConfig(cp=2), eight_devices[:2])
    rng = jax.random.PRNGKey(0)

    def step(q, k, v):
        return jax.grad(
            lambda a, b_, c: ring_self_attention(
                a, b_, c, mesh=mesh, expected_cp=2, dropout_rate=0.1,
                dropout_rng=rng,
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    orig = fa._interpret
    fa._interpret = lambda: False
    try:
        with use_mesh(mesh):
            text = (jax.jit(step).trace(q, q, q)
                    .lower(lowering_platforms=("tpu",)).as_text())
    finally:
        fa._interpret = orig
    call_lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert call_lines, "no Mosaic custom call in the cp2 lowering"
    # per-pair block operands: [b*h, s_blk, d] with s_blk = s/(2*cp) = 64
    local = f"tensor<{b * h}x{s // 4}x{d}xbf16>"
    assert any(local in ln for ln in call_lines), call_lines[0]


@pytest.mark.slow  # 32.5s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_model_cp_attention_dropout_runs(eight_devices):
    """GPT with cp_degree=2 and attention dropout trains a step (used to
    raise NotImplementedError at models/gpt/model.py)."""
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    cfg = GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_attention_heads=4,
        ffn_hidden_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.2,
        use_flash_attention=False, cp_degree=2, dtype=jnp.float32,
    )
    model = GPTForPretraining(cfg)
    mesh = build_mesh(MeshConfig(cp=2), eight_devices[:2])
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 32)), jnp.int32
    )
    with use_mesh(mesh):
        params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
        logits = jax.jit(
            lambda p, t: model.apply(
                p, t, deterministic=False,
                rngs={"dropout": jax.random.PRNGKey(1)},
            )
        )(params, tokens)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.slow  # 36.5s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_model_cp_flash_under_remat(eight_devices):
    """cp2 ring-flash inside nn.remat (selective recompute): the custom
    VJP must compose with jax.checkpoint over the scanned layer stack."""
    from fleetx_tpu.models.gpt.model import (
        GPTConfig, GPTForPretraining, pretraining_loss,
    )

    cfg = GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_attention_heads=4,
        ffn_hidden_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        use_flash_attention=False, cp_degree=2, dtype=jnp.float32,
        use_recompute=True, recompute_granularity="core_attn",
    )
    model = GPTForPretraining(cfg)
    mesh = build_mesh(MeshConfig(cp=2), eight_devices[:2])
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
    mask = jnp.ones((2, 32), jnp.float32)
    with use_mesh(mesh):
        params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)

        def loss(p):
            return pretraining_loss(model.apply(p, tokens), labels, mask)

        l, g = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(l))
    gn = float(jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(g))))
    assert np.isfinite(gn) and gn > 0


def test_flash_ring_long_sequence_2048(eight_devices):
    """Long-context smoke: 2048-seq cp4 ring (s_blk 256, multi-tile kernel
    calls per hop) against the XLA reference — the CPU-side stand-in for
    the TPU-gated 32k case (tests/test_flash_attention.py)."""
    q, k, v = _qkv(b=1, s=2048, h=2, d=32)
    mesh = build_mesh(MeshConfig(cp=4), eight_devices[:4])
    out = _ring(q, k, v, mesh, 4)
    ref = causal_attention(q, k, v, use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
