"""Fused LM-head + cross-entropy — Pallas TPU kernels with custom VJP.

The reference computes logits with a (vocab-parallel) matmul and feeds
them to a softmax-CE criterion (/root/reference/ppfleetx/models/
language_model/gpt/dygraph/single_model.py:660-736 ``GPTForPretraining``
+ ``GPTPretrainingCriterion``), materializing [tokens, vocab] twice
(logits + softmax grad). At GPT vocab 50304 and bench shapes
(8x1024 tokens) that is ~1.6 GB of f32 activations each way — the
largest tensor in the model. This kernel streams vocab blocks through
VMEM with an online logsumexp, so the full logits matrix never reaches
HBM:

- forward: grid (token-block i, vocab-block j), j innermost sequential;
  one [bt, H] hidden block stays resident while [bv, H] embedding blocks
  stream; scratch carries (running max, running sumexp, label logit);
  emits per-token loss and the logsumexp.
- backward: dlogits = softmax(s) - onehot(label) is REcomputed blockwise
  from the saved logsumexp (the flash-attention trick applied to CE):
  the dh kernel accumulates dlogits @ W over vocab blocks; the dW kernel
  accumulates dlogits^T @ h over token blocks. Two extra matmul passes
  (~9% step FLOPs at 345M) buy back the logits' HBM round-trips and the
  1.6 GB live-activation peak — the final staged lever in
  docs/PERFORMANCE.md.

Requires the (per-shard) vocab to admit a lane-aligned block — a
128-multiple <= 512 dividing it, or the 64-lane fallback (see
``fit_vocab_block``); callers fall back to the XLA path otherwise.
Tokens dim must be a multiple of 8. Under an mp>1 mesh the
vocab-parallel form shards the embedding and combines per-shard
(logsumexp, label-logit) stats outside the shard_map region.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu.ops.pallas.flash_attention import (
    _NN,
    _NT,
    _TN,
    _dot,
    _interpret,
)

__all__ = ["fused_linear_ce", "fit_vocab_block"]

NEG_INF = -1e30


def _params_2d():
    # j (vocab / token stream) is the innermost scratch-carrying axis
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def fit_vocab_block(v: int, want: int = 512):
    """Largest lane-aligned block dividing ``v`` and <= want, or None (the
    caller then uses the XLA path). Preference: a multiple of 128 (full
    lanes); fallback: 64 (Mosaic also accepts last block dims DIVIDING
    128, and 64 keeps half the lanes — e.g. the GPT vocab 50304 sharded
    mp2 is 25152 = 64*393, 128-unaligned). Below 64 the lane waste makes
    the kernel pointless, so smaller divisors demote instead."""
    for bv in range(want - want % 128, 127, -128):
        if v % bv == 0:
            return bv
    if v % 64 == 0:
        return 64
    return None


def _fit_token_block(n: int, want: int = 256):
    for bt in range(want - want % 8, 7, -8):
        if n % bt == 0:
            return bt
    return None


def _mm_dt(dtype):
    return jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32


def _fwd_kernel(labels_ref, h_ref, w_ref, loss_ref, lse_ref, m_scr, l_scr,
                lab_scr, *, block_v: int, n_v: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        lab_scr[:] = jnp.zeros(lab_scr.shape, jnp.float32)

    mm = _mm_dt(h_ref.dtype)
    h = h_ref[:].astype(mm)
    w = w_ref[:].astype(mm)
    s = _dot(h, w, _NT)  # [bt, bv]
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (1, block_v), 1)
    m = m_scr[:]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    l_scr[:] = l_scr[:] * jnp.exp(m - m_new) + jnp.sum(
        jnp.exp(s - m_new), axis=-1, keepdims=True)
    m_scr[:] = m_new
    hit = labels_ref[:] == col  # [bt, 1] == [1, bv] -> [bt, bv]
    lab_scr[:] = lab_scr[:] + jnp.sum(
        jnp.where(hit, s, 0.0), axis=-1, keepdims=True)

    @pl.when(j == n_v - 1)
    def _fin():
        lse = m_scr[:] + jnp.log(l_scr[:])
        lse_ref[:] = lse
        loss_ref[:] = lse - lab_scr[:]


def _dh_kernel(labels_ref, a_ref, b_ref, lse_ref, h_ref, w_ref, dh_ref,
               dh_scr, *, block_v: int, n_v: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dh_scr[:] = jnp.zeros(dh_scr.shape, jnp.float32)

    mm = _mm_dt(h_ref.dtype)
    h = h_ref[:].astype(mm)
    w = w_ref[:].astype(mm)
    s = _dot(h, w, _NT)
    p = jnp.exp(s - lse_ref[:])  # softmax via saved logsumexp
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (1, block_v), 1)
    # generalized cotangent dl = a*softmax + b*onehot: the plain CE
    # backward is (a, b) = (g, -g); the vocab-parallel stats primitive
    # feeds the cotangents of (lse_loc, lab_loc) directly
    dl = (a_ref[:] * p
          + b_ref[:] * jnp.where(labels_ref[:] == col, 1.0, 0.0))
    dh_scr[:] = dh_scr[:] + _dot(dl.astype(mm), w, _NN)

    @pl.when(j == n_v - 1)
    def _fin():
        dh_ref[:] = dh_scr[:].astype(dh_ref.dtype)


def _dw_kernel(labels_ref, a_ref, b_ref, lse_ref, h_ref, w_ref, dw_ref,
               dw_scr, *, block_t: int, n_t: int, block_v: int):
    j = pl.program_id(0)  # vocab block (parallel)
    i = pl.program_id(1)  # token stream (sequential)

    @pl.when(i == 0)
    def _init():
        dw_scr[:] = jnp.zeros(dw_scr.shape, jnp.float32)

    mm = _mm_dt(h_ref.dtype)
    h = h_ref[:].astype(mm)
    w = w_ref[:].astype(mm)
    s = _dot(h, w, _NT)  # [bt, bv]
    p = jnp.exp(s - lse_ref[:])
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, (1, block_v), 1)
    dl = (a_ref[:] * p
          + b_ref[:] * jnp.where(labels_ref[:] == col, 1.0, 0.0))
    dw_scr[:] = dw_scr[:] + _dot(dl.astype(mm), h, _TN)  # [bv, H]

    @pl.when(i == n_t - 1)
    def _fin():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ce(h, w, labels, block_t, block_v):
    out, _ = _fused_ce_fwd(h, w, labels, block_t, block_v)
    return out


def _fused_ce_fwd(h, w, labels, block_t, block_v):
    n, d = h.shape
    v = w.shape[0]
    n_t, n_v = n // block_t, v // block_v
    lab2 = labels.astype(jnp.int32)[:, None]  # [n, 1]
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_v=block_v, n_v=n_v),
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
        ],
        compiler_params=_params_2d(),
        interpret=_interpret(),
        name="fleetx_ce_fwd",
    )(lab2, h, w)
    return loss[:, 0], (h, w, lab2, lse)


def _fused_ce_bwd(block_t, block_v, res, g):
    h, w, lab2, lse = res
    n, d = h.shape
    v = w.shape[0]
    n_t, n_v = n // block_t, v // block_v
    g2 = g.astype(jnp.float32)[:, None]  # [n, 1]
    dh = _dh_call(lab2, g2, -g2, lse, h, w, block_t, block_v)
    dw = _dw_call(lab2, g2, -g2, lse, h, w, block_t, block_v)
    dlabels = np.zeros(lab2.shape[:1], dtype=jax.dtypes.float0)
    return dh, dw, dlabels


def _dh_call(lab2, a2, b2, lse, h, w, block_t, block_v):
    n, d = h.shape
    n_t, n_v = n // block_t, w.shape[0] // block_v
    return pl.pallas_call(
        functools.partial(_dh_kernel, block_v=block_v, n_v=n_v),
        grid=(n_t, n_v),
        in_specs=[
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), h.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],
        compiler_params=_params_2d(),
        interpret=_interpret(),
        name="fleetx_ce_dh",
    )(lab2, a2, b2, lse, h, w)


def _dw_call(lab2, a2, b2, lse, h, w, block_t, block_v):
    n, d = h.shape
    v = w.shape[0]
    n_t, n_v = n // block_t, v // block_v
    return pl.pallas_call(
        functools.partial(_dw_kernel, block_t=block_t, n_t=n_t,
                          block_v=block_v),
        grid=(n_v, n_t),
        in_specs=[
            pl.BlockSpec((block_t, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_t, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((block_t, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((v, d), w.dtype),
        scratch_shapes=[pltpu.VMEM((block_v, d), jnp.float32)],
        compiler_params=_params_2d(),
        interpret=_interpret(),
        name="fleetx_ce_dw",
    )(lab2, a2, b2, lse, h, w)


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


# ------------------------------------------------ vocab-parallel (TP) form
# The reference's vocab-parallel LM head + ParallelCrossEntropy
# (hybrid_model.py:49-71, 857-904) as a kernel: each mp shard runs the
# SAME Pallas kernels over its vocab shard and returns per-shard
# (logsumexp, label-logit) stats on a MENTIONED mp output axis; the
# cross-shard combine (exact logsumexp + sum) happens OUTSIDE the
# shard_map in plain jnp, where autodiff is trivially exact. (Replicated
# outputs under check_vma=False transpose with an ambiguous scale — the
# stats formulation sidesteps that entirely.) The stats primitive's VJP
# uses the generalized kernel cotangent dl = a*softmax_local + b*onehot.

def _local_labels(labels, v_loc: int, mp_axis: str):
    """Global label ids -> this shard's local ids; off-shard -> -1 (matches
    no column, so the local label-logit stays 0 and the cross-shard sum
    recovers exactly the owning shard's value)."""
    shard = jax.lax.axis_index(mp_axis)
    l_loc = labels.astype(jnp.int32) - shard * v_loc
    return jnp.where((l_loc >= 0) & (l_loc < v_loc), l_loc, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _vp_stats(h, w_shard, l_loc, block_t, block_v):
    out, _ = _vp_stats_fwd(h, w_shard, l_loc, block_t, block_v)
    return out


def _vp_stats_fwd(h, w_shard, l_loc, block_t, block_v):
    loss_loc, (_, _, lab2, lse) = _fused_ce_fwd(
        h, w_shard, l_loc, block_t, block_v)
    lse1 = lse[:, 0]
    lab1 = lse1 - loss_loc  # 0 when the label lives on another shard
    return (lse1, lab1), (h, w_shard, lab2, lse)


def _vp_stats_bwd(block_t, block_v, res, cts):
    h, w_shard, lab2, lse = res
    ca, cb = cts  # cotangents of (lse_loc, lab_loc)
    a2 = ca.astype(jnp.float32)[:, None]
    b2 = cb.astype(jnp.float32)[:, None]
    dh = _dh_call(lab2, a2, b2, lse, h, w_shard, block_t, block_v)
    dw = _dw_call(lab2, a2, b2, lse, h, w_shard, block_t, block_v)
    dlabels = np.zeros(lab2.shape[:1], dtype=jax.dtypes.float0)
    return dh, dw, dlabels


_vp_stats.defvjp(_vp_stats_fwd, _vp_stats_bwd)


def fused_linear_ce(hidden: jax.Array, emb: jax.Array,
                    labels: jax.Array) -> jax.Array:
    """Per-token CE loss of ``logits = hidden @ emb^T`` without ever
    materializing the logits. hidden [n, d] (model dtype), emb [v, d]
    (same dtype), labels [n] int — returns [n] f32 token losses
    (callers apply loss_mask / normalization).

    Under an ambient mesh the call shard_maps over the token dim
    (dp/fsdp) and, when mp > 1, over the VOCAB dim of the embedding too
    (vocab-parallel: per-shard stats combined outside the region).
    Raises ValueError when no lane-aligned blocks fit — callers gate
    with :func:`fit_vocab_block` on the PER-SHARD vocab (v // mp) and
    fall back to the XLA logits path."""
    n, d = hidden.shape
    v = emb.shape[0]
    block_v = fit_vocab_block(v)
    if block_v is None:
        raise ValueError(
            f"fused_linear_ce: vocab {v} admits no lane-aligned block "
            "(need a 128-multiple <= 512 dividing it, or 64 | v)"
        )

    from fleetx_tpu.parallel.mesh import ambient_mesh
    from fleetx_tpu.parallel.mesh import shard_map as _shard_map

    mesh = ambient_mesh()
    n_data, n_mp = 1, 1
    if mesh is not None:
        sizes = dict(mesh.shape)
        n_data = sizes.get("dp", 1) * sizes.get("fsdp", 1)
        n_mp = sizes.get("mp", 1)
        if n % n_data or (n_mp > 1 and v % n_mp):
            mesh = None  # indivisible: run unsharded (GSPMD replicates)
    if mesh is not None and n_data * n_mp > 1:
        from jax.sharding import PartitionSpec as P

        n_local = n // n_data
        block_t = _fit_token_block(n_local)
        if block_t is None:
            raise ValueError(f"fused_linear_ce: 8 must divide {n_local}")
        data_axes = tuple(a for a in ("dp", "fsdp")
                          if dict(mesh.shape).get(a, 1) > 1)
        if n_mp > 1:
            # vocab-parallel: embedding sharded over mp; per-shard stats
            # come back on a MENTIONED mp axis and combine outside (see
            # the vocab-parallel section above)
            v_loc = v // n_mp
            block_v_loc = fit_vocab_block(v_loc)
            if block_v_loc is None:
                raise ValueError(
                    f"fused_linear_ce: vocab shard {v_loc} admits no "
                    "lane-aligned block"
                )

            def body(h_, w_, l_):
                lse1, lab1 = _vp_stats(
                    h_, w_, _local_labels(l_, v_loc, "mp"),
                    block_t, block_v_loc)
                return lse1[None, :], lab1[None, :]

            fn = _shard_map(
                body,
                mesh=mesh,
                in_specs=(P(data_axes, None), P("mp", None), P(data_axes)),
                out_specs=(P("mp", data_axes), P("mp", data_axes)),
                check_vma=False,
            )
            lse_stack, lab_stack = fn(hidden, emb, labels)  # [mp, n]
            return (jax.scipy.special.logsumexp(lse_stack, axis=0)
                    - lab_stack.sum(axis=0))
        fn = _shard_map(
            # custom_vjp statics must stay positional
            lambda h_, w_, l_: _fused_ce(h_, w_, l_, block_t, block_v),
            mesh=mesh,
            in_specs=(P(data_axes, None), P(None, None), P(data_axes)),
            out_specs=P(data_axes),
            check_vma=False,
        )
        return fn(hidden, emb, labels)
    block_t = _fit_token_block(n)
    if block_t is None:
        raise ValueError(f"fused_linear_ce: 8 must divide tokens {n}")
    return _fused_ce(hidden, emb, labels, block_t, block_v)
