"""Flash-attention kernel vs XLA reference (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.ops.attention import _reference_attention
from fleetx_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(b=2, s=256, h=2, d=32, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, s, h, d), dtype)
    k = jax.random.normal(k2, (b, s, h, d), dtype)
    v = jax.random.normal(k3, (b, s, h, d), dtype)
    return q, k, v


def _ref(q, k, v):
    return _reference_attention(
        q, k, v, causal=True, attn_mask=None, dropout_rate=0.0,
        dropout_rng=None, deterministic=True,
    )


@pytest.mark.parametrize("s,block", [(256, 128), (128, 128), (256, 64)])
def test_forward_matches_reference(s, block):
    q, k, v = _qkv(s=s)
    out = flash_attention(q, k, v, block_q=block, block_k=block)
    ref = _ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_mixed_block_sizes():
    q, k, v = _qkv(s=256)
    out = flash_attention(q, k, v, block_q=128, block_k=64)
    ref = _ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_grads_match_reference():
    q, k, v = _qkv(s=256, d=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, 128, 128) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref(q, k, v) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_bf16_inputs():
    q, k, v = _qkv(s=256, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v)
    ref = _ref(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=5e-2, atol=5e-2
    )


def test_untileable_seq_raises():
    # no 8-row tile divides 100 (100 % 8 != 0): the only untileable case
    # left now that blocks shrink to the largest divisor of the sequence
    q, k, v = _qkv(s=100)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=128, block_k=128)


def test_formerly_untileable_seq_now_shrinks_blocks():
    # s=200 used to raise at 128-blocks; fit_blocks now picks 40x40
    q, k, v = _qkv(s=200)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = _reference_attention(q, k, v, causal=True, attn_mask=None,
                               dropout_rate=0.0, dropout_rng=None,
                               deterministic=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)

# ---------------------------------------------------------------- dropout

@pytest.fixture
def hash_rng():
    """Force the lowbias32 hash bit source so the dense reference can
    reproduce the kernel's mask bit-for-bit on ANY backend (real TPUs
    opt into the hardware PRNG, which has no host-side replica)."""
    import fleetx_tpu.ops.pallas.flash_attention as fa

    orig = fa.HW_RNG
    fa.HW_RNG = False
    yield
    fa.HW_RNG = orig


@pytest.fixture
def hw_rng_on():
    """Force the hardware-PRNG bit source: the TPU-gated test_hw_rng_*
    certification tests must exercise pltpu.prng_* regardless of the
    module default (ADVICE r4 medium: the default stays hash until these
    pass on a live chip — which requires them to actually run the HW
    path)."""
    import fleetx_tpu.ops.pallas.flash_attention as fa

    orig = fa.HW_RNG
    fa.HW_RNG = True
    yield
    fa.HW_RNG = orig


def _hash_dropout_ref(q, k, v, seed, rate):
    """Dense attention applying the kernel's exact hash mask (pure jnp, so it
    reproduces the in-kernel dropout bit-for-bit)."""
    from fleetx_tpu.ops.pallas.flash_attention import dropout_keep_scale

    b, s, h, d = q.shape
    scale = 1.0 / (d**0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qp = jnp.arange(s, dtype=jnp.int32)[:, None]
    kp = jnp.arange(s, dtype=jnp.int32)[None, :]
    scores = jnp.where(qp >= kp, scores, -1e30)
    p = jax.nn.softmax(scores, -1)
    bh = (jnp.arange(b)[:, None] * h + jnp.arange(h)[None, :]).astype(jnp.int32)
    mask = dropout_keep_scale(
        seed, bh[:, :, None, None], qp[None, None], kp[None, None], rate
    )
    return jnp.einsum("bhqk,bkhd->bqhd", p * mask, v.astype(jnp.float32)).astype(q.dtype)


def test_dropout_forward_matches_hash_reference(hash_rng):
    q, k, v = _qkv(s=256, d=32)
    rng = jax.random.PRNGKey(7)
    rate = 0.1
    seed = jax.random.bits(rng, (1,), "uint32").astype(jnp.int32)[0]
    out = flash_attention(q, k, v, dropout_rate=rate, dropout_rng=rng)
    ref = _hash_dropout_ref(q, k, v, seed, rate)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)
    # the mask actually drops ~rate of entries: outputs differ from no-dropout
    nodrop = flash_attention(q, k, v)
    assert float(jnp.abs(out - nodrop).max()) > 1e-3


def test_dropout_grads_match_hash_reference(hash_rng):
    q, k, v = _qkv(s=256, d=32)
    rng = jax.random.PRNGKey(3)
    rate = 0.15
    seed = jax.random.bits(rng, (1,), "uint32").astype(jnp.int32)[0]

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, dropout_rate=rate, dropout_rng=rng) ** 2).sum()

    def loss_ref(q, k, v):
        return (_hash_dropout_ref(q, k, v, seed, rate) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_dropout_rate_statistics():
    """Empirical drop fraction of the hash mask ≈ rate (hash quality check)."""
    from fleetx_tpu.ops.pallas.flash_attention import dropout_keep_scale

    rate = 0.1
    qp = jnp.arange(512, dtype=jnp.int32)[:, None]
    kp = jnp.arange(512, dtype=jnp.int32)[None, :]
    m = dropout_keep_scale(jnp.int32(12345), jnp.int32(3), qp, kp, rate)
    dropped = float((m == 0).mean())
    assert abs(dropped - rate) < 0.01, dropped


def test_dropout_requires_rng():
    q, k, v = _qkv(s=128)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, dropout_rate=0.1)


def test_kernels_lower_for_tpu():
    """Mosaic lowering runs in Python before backend compile, so block-spec
    layout violations (the bug that kept the kernel dark on hardware in
    rounds 1-2) are catchable from CPU: lower fwd+bwd for the tpu platform."""
    import fleetx_tpu.ops.pallas.flash_attention as fa

    orig = fa._interpret
    fa._interpret = lambda: False
    try:
        q = jnp.zeros((2, 256, 4, 64), jnp.bfloat16)
        rng = jax.random.PRNGKey(0)

        def fwd(q, k, v):
            return fa.flash_attention(q, k, v, dropout_rate=0.1, dropout_rng=rng)

        def bwd(q, k, v):
            return jax.grad(
                lambda a, b, c: fwd(a, b, c).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

        jax.jit(fwd).trace(q, q, q).lower(lowering_platforms=("tpu",))
        jax.jit(bwd).trace(q, q, q).lower(lowering_platforms=("tpu",))
    finally:
        fa._interpret = orig


# ------------------------------------------------- non-causal + kv_lens

def _ref_masked(q, k, v, kv_lens=None, causal=False):
    mask = None
    if kv_lens is not None:
        mask = (jnp.arange(k.shape[1])[None, :] < kv_lens[:, None])[
            :, None, None, :
        ]
    return _reference_attention(
        q, k, v, causal=causal, attn_mask=mask, dropout_rate=0.0,
        dropout_rng=None, deterministic=True,
    )


def test_noncausal_forward_matches_reference():
    q, k, v = _qkv(s=256)
    out = flash_attention(q, k, v, causal=False)
    ref = _ref_masked(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_kv_lens_forward_matches_reference(causal):
    q, k, v = _qkv(s=256)
    kv_lens = jnp.asarray([100, 256], jnp.int32)
    out = flash_attention(q, k, v, causal=causal, kv_lens=kv_lens)
    ref = _ref_masked(q, k, v, kv_lens=kv_lens, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_kv_lens_grads_match_reference():
    q, k, v = _qkv(s=256, d=32)
    kv_lens = jnp.asarray([77, 200], jnp.int32)
    # probe only valid q rows: padded rows carry no loss in real batches
    row_w = (jnp.arange(256)[None, :] < kv_lens[:, None]).astype(jnp.float32)
    w = row_w[:, :, None, None]

    def loss_flash(q, k, v):
        return ((flash_attention(q, k, v, causal=False, kv_lens=kv_lens) * w) ** 2).sum()

    def loss_ref(q, k, v):
        return ((_ref_masked(q, k, v, kv_lens=kv_lens) * w) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_fully_masked_rows_emit_zeros_not_nan():
    q, k, v = _qkv(s=256)
    kv_lens = jnp.asarray([0, 128], jnp.int32)  # batch 0 fully padded
    out = flash_attention(q, k, v, causal=False, kv_lens=kv_lens)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)


def test_long_sequence_2048():
    """Longer-seq smoke at 2048: 16 k-blocks stream through the grid."""
    q, k, v = _qkv(b=1, s=2048, h=1, d=32)
    out = flash_attention(q, k, v)
    ref = _ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


@pytest.mark.slow  # 2.8s (PR 15 tier-1 budget audit): long-seq grads
# stay tier-1 via test_kv_lens_grads_across_major_blocks_512 (the
# multi-major-block case) and the 2048 forward test; the grads-at-2048
# combination re-runs in the slow sweep
def test_long_sequence_grads_2048():
    """Streamed K/V backward: causal skip clamps both the k-stream (dq) and
    q-stream (dkv) index maps; grads must still match the XLA reference."""
    q, k, v = _qkv(b=1, s=2048, h=1, d=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref(q, k, v) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4,
            err_msg=f"d{name} mismatch",
        )


def test_kernels_lower_for_tpu_32k():
    """32k-seq fwd+bwd must lower for TPU: VMEM now holds only one resident
    block per operand + the scratch carry, independent of sequence length
    (VERDICT r3 weak #3: the old whole-row regime capped seq at ~8-16k)."""
    import fleetx_tpu.ops.pallas.flash_attention as fa

    orig = fa._interpret
    fa._interpret = lambda: False
    try:
        q = jnp.zeros((1, 32768, 1, 64), jnp.bfloat16)

        def fwd(q, k, v):
            return fa.flash_attention(q, k, v)

        def bwd(q, k, v):
            return jax.grad(
                lambda a, b, c: fwd(a, b, c).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

        jax.jit(fwd).trace(q, q, q).lower(lowering_platforms=("tpu",))
        jax.jit(bwd).trace(q, q, q).lower(lowering_platforms=("tpu",))
    finally:
        fa._interpret = orig


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="needs a real TPU (VMEM envelope is the thing under test)",
)
def test_long_sequence_32k_real_tpu():
    """32k tokens single chip, fwd + grads, no VMEM OOM (VERDICT r4 item 3
    done-criterion). Run explicitly on hardware:
    pytest tests/test_flash_attention.py -k 32k_real."""
    q, k, v = _qkv(b=1, s=32768, h=1, d=64, dtype=jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    out = flash_attention(q, k, v)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (dq, dk, dv):
        assert np.isfinite(np.asarray(g, np.float32)).all()


def test_block_env_override_validation():
    """FLEETX_FLASH_BLOCK_Q/K are validated at import: zero, negative, or
    sublane-misaligned (non-multiple-of-8) values, and a Q/K pair where
    block_k does not divide block_q, must raise a descriptive error instead
    of a ZeroDivisionError or a silent XLA fallback at dispatch
    (ADVICE r3 #4)."""
    import subprocess
    import sys

    for bad in ("0", "-128", "100", "abc", "64"):  # 100 % 8 != 0; 64 % 128 pair
        proc = subprocess.run(
            [sys.executable, "-c",
             "import fleetx_tpu.ops.pallas.flash_attention"],
            env={**__import__("os").environ, "FLEETX_FLASH_BLOCK_Q": bad,
                 "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode != 0, bad
        assert "FLEETX_FLASH_BLOCK_Q" in proc.stderr, proc.stderr[-500:]


def test_bf16_grads_match_reference():
    """bf16 operands now feed the MXU directly in all three kernels (f32
    accumulation); grads must still track the XLA reference at bf16-level
    tolerance."""
    q, k, v = _qkv(s=256, d=32, dtype=jnp.bfloat16)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v).astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref(q, k, v).astype(jnp.float32) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-1, atol=1e-1, err_msg=f"d{name} mismatch",
        )


def test_fit_blocks_shrinks_for_non_multiple_seqs():
    """Seqs that are multiples of 128 but not 512 stay on the flash path
    (blocks shrink to the largest divisor instead of demoting to XLA)."""
    from fleetx_tpu.ops.pallas.flash_attention import fit_blocks

    bq, bk = fit_blocks(768, 512, 512)
    assert bq % bk == 0 and 768 % bq == 0 and 768 % bk == 0 and bk >= 128
    bq, bk = fit_blocks(1920, 512, 512)
    assert bq % bk == 0 and 1920 % bq == 0
    assert fit_blocks(12, 512, 512) == (None, None)  # no 8-row tile divides
    # asymmetric request: block_k capped at block_q
    bq, bk = fit_blocks(1024, 128, 512)
    assert bk <= bq and 1024 % bq == 0


def test_flash_odd_seq_parity():
    """768-seq (not a multiple of the 512 default) runs the kernel and
    matches the XLA reference."""
    import numpy as np

    from fleetx_tpu.ops.attention import _reference_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 768, 2, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 768, 2, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 768, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    ref = _reference_attention(q, k, v, causal=True, attn_mask=None,
                               dropout_rate=0.0, dropout_rng=None,
                               deterministic=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


# ------------------------------------------------- hardware PRNG dropout
# Real-TPU-only: pltpu.prng_* has no CPU lowering. The math (masking, VJP
# chain) is identical to the hash path validated above; these check the
# bit-source swap — per-tile seeding consistency across fwd/dq/dkv — which
# is the only thing the hardware path changes.


def _on_tpu():
    return jax.default_backend() == "tpu"


@pytest.mark.skipif("not _on_tpu()")
def test_hw_rng_deterministic_by_seed(hw_rng_on):
    q, k, v = _qkv(s=256, d=32)
    rng = jax.random.PRNGKey(11)
    a = flash_attention(q, k, v, dropout_rate=0.2, dropout_rng=rng)
    b = flash_attention(q, k, v, dropout_rate=0.2, dropout_rng=rng)
    c = flash_attention(q, k, v, dropout_rate=0.2,
                        dropout_rng=jax.random.PRNGKey(12))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


@pytest.mark.skipif("not _on_tpu()")
def test_hw_rng_drop_fraction(hw_rng_on):
    """v = identity exposes the dropped softmax rows directly:
    out[b, q, h, :] == drop(softmax(scores))[q, :]."""
    s = d = 128
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, s, 1, d), jnp.float32)
    k = jnp.asarray(rng.randn(1, s, 1, d), jnp.float32)
    v = jnp.asarray(np.eye(s)[None, :, None, :], jnp.float32)
    rate = 0.25
    out = np.asarray(
        flash_attention(q, k, v, dropout_rate=rate,
                        dropout_rng=jax.random.PRNGKey(5))
    )[0, :, 0, :]  # [q, k] dropped probabilities
    qp, kp = np.mgrid[0:s, 0:s]
    valid = qp >= kp  # causal cells; softmax probs there are > 0
    dropped = (out[valid] == 0.0).mean()
    assert abs(dropped - rate) < 0.03, dropped


@pytest.mark.skipif("not _on_tpu()")
def test_hw_rng_grads_match_finite_differences(hw_rng_on):
    """fwd and both bwd kernels must regenerate the SAME bits per tile; a
    seeding mismatch shows up as a grad/finite-difference divergence."""
    q, k, v = (x.astype(jnp.float32) for x in _qkv(s=128, d=32))
    rng = jax.random.PRNGKey(9)
    rate = 0.2

    def loss(q, k, v):
        out = flash_attention(q, k, v, dropout_rate=rate, dropout_rng=rng)
        return (out.astype(jnp.float32) ** 2).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    rs = np.random.RandomState(1)
    eps = 1e-2
    for idx, name in ((0, "q"), (1, "k"), (2, "v")):
        t = jnp.asarray(rs.randn(*q.shape), jnp.float32)
        args_p = [q, k, v]
        args_m = [q, k, v]
        args_p[idx] = args_p[idx] + eps * t
        args_m[idx] = args_m[idx] - eps * t
        fd = (float(loss(*args_p)) - float(loss(*args_m))) / (2 * eps)
        an = float(jnp.sum(grads[idx] * t))
        np.testing.assert_allclose(an, fd, rtol=5e-2, atol=5e-1,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_dropout_with_kv_lens_matches_reference(hash_rng, causal):
    """Dropout and the kv_lens key mask compose: masked cells stay exactly
    zero, surviving cells carry the hash keep/scale."""
    from fleetx_tpu.ops.pallas.flash_attention import dropout_keep_scale

    q, k, v = _qkv(s=256, d=32)
    kv_lens = jnp.asarray([100, 256], jnp.int32)
    rng = jax.random.PRNGKey(21)
    rate = 0.2
    seed = jax.random.bits(rng, (1,), "uint32").astype(jnp.int32)[0]
    out = flash_attention(q, k, v, causal=causal, kv_lens=kv_lens,
                          dropout_rate=rate, dropout_rng=rng)

    b, s, h, d = q.shape
    scale = 1.0 / (d**0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qp = jnp.arange(s, dtype=jnp.int32)[:, None]
    kp = jnp.arange(s, dtype=jnp.int32)[None, :]
    mask = (kp < kv_lens[:, None, None, None])
    if causal:
        mask = mask & (qp >= kp)
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, -1)
    p = jnp.where(mask, p, 0.0)  # fully-masked rows: zeros, not uniform
    bh = (jnp.arange(b)[:, None] * h
          + jnp.arange(h)[None, :]).astype(jnp.int32)
    drop = dropout_keep_scale(
        seed, bh[:, :, None, None], qp[None, None], kp[None, None], rate
    )
    ref = jnp.einsum("bhqk,bkhd->bqhd", p * drop, v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref, q.dtype),
                               rtol=2e-4, atol=2e-5)


def test_fit_blocks_invariants_sweep():
    """For every 8-multiple sequence up to 4k: blocks divide s, block_k
    divides block_q, both within requested bounds; non-8-multiples give
    (None, None)."""
    from fleetx_tpu.ops.pallas.flash_attention import fit_blocks

    for s in range(8, 4097, 8):
        for want_q, want_k in ((512, 512), (128, 128), (256, 128), (128, 512)):
            bq, bk = fit_blocks(s, want_q, want_k)
            assert bq is not None, (s, want_q, want_k)
            assert s % bq == 0 and s % bk == 0 and bq % bk == 0
            assert bq <= min(want_q, s) and bk <= min(want_k, s, bq)
            assert bq % 8 == 0 and bk % 8 == 0
    for s in (4, 12, 20, 100, 1001):
        if s % 8:
            assert fit_blocks(s, 512, 512) == (None, None)


@pytest.mark.parametrize("causal", [False, True])
def test_kv_lens_across_major_blocks_512(causal):
    """kv cuts landing in different 512-blocks (and mid-block) at seq 1024:
    exercises the two-phase trip counts when n_kv_full differs per major."""
    q, k, v = _qkv(b=2, s=1024, h=1, d=32)
    kv_lens = jnp.asarray([100, 700], jnp.int32)
    out = flash_attention(q, k, v, causal=causal, kv_lens=kv_lens,
                          block_q=512, block_k=512)
    ref = _ref_masked(q, k, v, kv_lens=kv_lens, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-4)


def test_kv_lens_grads_across_major_blocks_512():
    q, k, v = _qkv(b=2, s=1024, h=1, d=32)
    kv_lens = jnp.asarray([100, 700], jnp.int32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, kv_lens=kv_lens,
                                block_q=512, block_k=512) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref_masked(q, k, v, kv_lens=kv_lens, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-2, atol=1e-3,
            err_msg=f"d{name} mismatch",
        )


# ------------------------------------------------- pad-to-tileable dispatch

def test_dispatch_pads_untileable_seq_to_kernel(monkeypatch):
    """seq 197 (ViT) routes to the kernel via padding instead of the XLA
    fallback: the dispatch pads to 200 (one tile), masks padded keys with
    kv_lens, and slices padded query rows off."""
    from fleetx_tpu.ops import attention as attn_mod

    calls = {"n": 0}
    orig = flash_attention

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    monkeypatch.setattr(
        "fleetx_tpu.ops.pallas.flash_attention.flash_attention", counting)
    q, k, v = _qkv(b=2, s=197, h=2, d=32)
    out = attn_mod.causal_attention(q, k, v, causal=False)
    assert calls["n"] == 1, "padded dispatch did not reach the kernel"
    assert out.shape == q.shape
    ref = _reference_attention(q, k, v, causal=False, attn_mask=None,
                               dropout_rate=0.0, dropout_rng=None,
                               deterministic=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_dispatch_pad_grads_exact(monkeypatch):
    """Padded-row cotangents are zero, so gradients through the padded
    dispatch equal the XLA reference's."""
    from fleetx_tpu.ops import attention as attn_mod

    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    q, k, v = _qkv(b=1, s=197, h=2, d=32)

    def loss_pad(q, k, v):
        return (attn_mod.causal_attention(q, k, v, causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_reference_attention(
            q, k, v, causal=True, attn_mask=None, dropout_rate=0.0,
            dropout_rng=None, deterministic=True) ** 2).sum()

    gp = jax.grad(loss_pad, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_dispatch_pad_composes_with_kv_lens(monkeypatch):
    """ERNIE-style: caller kv_lens AND the pad mask must both apply."""
    from fleetx_tpu.ops import attention as attn_mod

    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    q, k, v = _qkv(b=2, s=197, h=2, d=32)
    kv_lens = jnp.asarray([100, 197], jnp.int32)
    out = attn_mod.causal_attention(q, k, v, causal=False, kv_lens=kv_lens)
    ref = _ref_masked(q, k, v, kv_lens=kv_lens, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
