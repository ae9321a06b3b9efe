"""Flash attention — Pallas TPU kernels with custom VJP.

The TPU replacement for the reference's fused CUDA softmax-mask kernel +
score-matrix attention (/root/reference/ppfleetx/models/language_model/gpt/
dygraph/single_model.py:216-240 ``core_attn`` +
``incubate.softmax_mask_fuse_upper_triangle``): online-softmax tiling keeps
the [s, s] score matrix out of HBM entirely, so long sequences don't need the
reference's ``recompute_granularity=core_attn`` memory workaround.

Two masking modes, both resolved inside the kernels:
- ``causal=True``: lower-triangular (GPT decoders); k blocks past the
  diagonal are skipped.
- ``kv_lens`` (optional, [batch] int32): right-padding key mask — position
  k attends only if ``k < kv_lens[b]``. This is the contiguous-padding
  form of the reference encoder's ``attention_mask`` (ernie single_model
  builds it from ``input_ids != pad``), so bidirectional ERNIE-style
  encoders ride the flash path too (``causal=False`` + kv_lens).

Attention dropout runs *inside* the kernel with zero extra HBM traffic —
the reference reaches the same determinism via its CUDA RNG tracker
``local_seed`` (/root/reference/ppfleetx/distributed/apis/env.py:49-54).
Two deterministic bit sources:
- default (every backend): a counter-based integer hash (lowbias32
  finalizer) of (seed, GLOBAL batch*head, q_pos, k_pos) — plain int32
  arithmetic the host-side tests reproduce bit-for-bit, and
  layout-invariant across dp/mp/cp shardings by construction;
- ``FLEETX_FLASH_HW_RNG=1`` opt-in (real TPUs): the hardware PRNG
  (``pltpu.prng_seed/prng_random_bits``), seeded per (seed, batch*head,
  q-tile, k-tile). Cheaper per tile, but keyed on TILE ids — only
  self-consistent between identically-tiled kernels, and unverified on
  hardware until the TPU-gated test_hw_rng_* suite passes on a live chip
  (ADVICE r4); flip the default only then. Either source must be held
  fixed for the life of a training run (checkpoints record it).

Layout: q, k, v are [batch, seq, heads, head_dim] (model layout).

Major-block streaming (round-4, second iteration). Two regimes were tried:
whole-row K/V residency (rounds 1-3) caps per-device sequence at ~8-16k
tokens; one-grid-step-per-128-tile streaming (round 4, first cut) lifted the
cap but regressed 1k-seq MFU 23%→15% — per-grid-step overhead swamps the
~4 MFLOP a 128x128 online-softmax update does. This version does both:
the grid's innermost axis streams K/V (or Q for the dK/dV kernel) in
*major* blocks of FLEETX_FLASH_BLOCK_MAJOR rows (default 1024), and an
in-kernel ``fori_loop`` walks the compute tiles inside the resident major
block with an exact causal trip count. VMEM holds one major block per
streamed operand (seq-independent; Mosaic double-buffers the stream), and
at seq <= the major size the grid degenerates to one step per (bh, q-block)
— the exact structure that measured MFU 23% at 1k seq. Causal skipping:
- the streamed operand's index_map clamps at the diagonal, so skipped grid
  steps repeat a block index and are NOT re-fetched (no HBM traffic);
- ``pl.when`` guards the compute, so skipped steps retire immediately;
- inside a live step the fori_loop trip count covers exactly the tiles at
  or before the diagonal.
The innermost grid axis is sequential on TPU ("arbitrary" dimension
semantics), which is what makes the scratch carry across major steps valid;
(batch*head, fixed-block) are marked parallel for megacore partitioning.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "KERNEL_NAMES", "kernels_enabled", "on_tpu"]

# Stable kernel names (forward, dq, dk/dv): they appear in the compiled
# HLO's custom-call instruction names (chip_smoke.py asserts the train
# step holds all three) and in profiler traces.
KERNEL_NAMES = ("fleetx_flash_fwd", "fleetx_flash_dq", "fleetx_flash_dkv")

import os as _os


def _env_block(name: str, default: int) -> int:
    """Env-tunable block size; validated once at import (ADVICE r3 #4:
    a 0/negative override used to surface as ZeroDivisionError at dispatch)."""
    raw = _os.environ.get(name)
    if raw is None:
        return default
    try:
        val = int(raw)
    except ValueError as e:
        raise ValueError(f"{name}={raw!r} is not an integer") from e
    if val <= 0 or val % 8:
        # blocks tile the (second-to-last) sequence dim, so only sublane (8)
        # alignment is required — head_dim carries the 128-lane constraint
        raise ValueError(
            f"{name}={val} invalid: block sizes must be positive multiples "
            "of the 8-row TPU sublane tile"
        )
    return val


# overridable without code changes (nothing sweeps them any more: PR 30
# read 128 / 256 / 512 within 1.5%; they go with ROADMAP D11's cure).
# 512x512 default from the round-4 v5e sweep: at 345M/seq1024/b8 it measured
# 23.8k tok/s vs 18.1k at 128x128 (the per-cell VPU work of online softmax
# amortizes over bigger tiles, and fewer grid steps means less fixed
# overhead); 1024x512 regressed (megacore q-block parallelism lost).
DEFAULT_BLOCK_Q = _env_block("FLEETX_FLASH_BLOCK_Q", 512)
DEFAULT_BLOCK_K = _env_block("FLEETX_FLASH_BLOCK_K", 512)
# rows of the streamed operand resident in VMEM per grid step (the unit of
# HBM->VMEM DMA); compute tiles walk inside it
DEFAULT_BLOCK_MAJOR = _env_block("FLEETX_FLASH_BLOCK_MAJOR", 1024)
if DEFAULT_BLOCK_Q % DEFAULT_BLOCK_K:
    # the dispatch-time tileability check requires block_k | block_q; catch
    # a bad override pair at import instead of silently routing every call
    # to the XLA fallback
    raise ValueError(
        f"FLEETX_FLASH_BLOCK_Q={DEFAULT_BLOCK_Q} must be a multiple of "
        f"FLEETX_FLASH_BLOCK_K={DEFAULT_BLOCK_K}"
    )
NEG_INF = -1e30

# lowbias32 mixing constants (public-domain integer hash); stored as wrapped
# int32 because Pallas TPU integer math is int32.
_MIX1 = np.int32(np.uint32(0x7FEB352D))
_MIX2 = np.int32(np.uint32(0x846CA68B))
_C1 = np.int32(np.uint32(0x9E3779B1))
_C2 = np.int32(np.uint32(0x85EBCA77))
_C3 = np.int32(np.uint32(0xC2B2AE3D))


def on_tpu() -> bool:
    """True iff the default backend is a TPU: THE backend test of every
    kernel dispatch (ops/attention.py, decode_attention.py, ce_loss.py)."""
    return jax.default_backend() == "tpu"


def kernels_enabled() -> bool:
    """Whether the Pallas kernels may be dispatched: compiled on a TPU, or
    interpreted off-TPU when a CPU test forces them (FLEETX_FORCE_FLASH=1)."""
    return on_tpu() or _os.environ.get("FLEETX_FORCE_FLASH") == "1"


def _interpret() -> bool:
    """Pallas interpreter mode: off-TPU only (CPU tests of kernel math).
    On a TPU backend every ``pallas_call`` in the repo compiles."""
    return not on_tpu()


def _compiler_params():
    # innermost grid axis carries the online-softmax scratch state, so it
    # must stay sequential; the outer two can partition over megacores
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


def _shr(x, n):
    return jax.lax.shift_right_logical(x, jnp.int32(n))


def dropout_keep_scale(seed, bh, q_pos, k_pos, rate: float):
    """Deterministic dropout scale in {0, 1/(1-rate)} for each (q, k) cell.

    seed: int32 scalar; bh: int32 scalar batch*head index; q_pos/k_pos: int32
    grids of global positions (any broadcast-compatible shapes). Pure int32
    jnp ops so forward/backward kernels (and test references) can regenerate
    the exact mask. Grouped so that when callers pass a [bq, 1] q column and
    a [1, bk] k row, the multiplies stay on the vectors (int32 multiply is
    multi-op on the VPU) and only the combine + mix rounds touch the full
    [bq, bk] tile; int32 + is modular, so the grouping does not change the
    hash value vs the original flat expression.
    """
    x = (q_pos * _C1 + (bh * _C3 + seed)) + k_pos * _C2
    x = x ^ _shr(x, 16)
    x = x * _MIX1
    x = x ^ _shr(x, 15)
    x = x * _MIX2
    x = x ^ _shr(x, 16)
    # 31 uniform bits; drop iff below the threshold.
    threshold = jnp.int32(int(rate * (1 << 31)))
    keep = (x & jnp.int32(0x7FFFFFFF)) >= threshold
    return keep.astype(jnp.float32) / (1.0 - rate)


# FLEETX_FLASH_HW_RNG=1 switches real-TPU dropout bits to the hardware
# PRNG (pltpu.prng_*); the default is the lowbias32 hash on every backend.
# Default OFF (ADVICE r4 medium): the HW path assumes bit-layout agreement
# across the three separately-compiled kernels, which only the TPU-gated
# test_hw_rng_* tests can certify — and they have not yet run on a live
# chip. Flip the default only after they pass on hardware. Either source
# must be held constant across a training run: the realized masks differ,
# so toggling mid-run (or resuming on the other setting) changes the
# noise stream.
HW_RNG = _os.environ.get("FLEETX_FLASH_HW_RNG", "0") == "1"


def _tile_keep_scale(seed, bh, qb, kb, q_col, k_row, shape, rate: float,
                     hw_rng: bool = True):
    """Dropout keep/scale for one [block_q, block_k] score tile.

    seed/bh: int32 scalars; qb/kb: GLOBAL tile indices (int32, traced);
    q_col/k_row: [bq, 1] / [1, bk] global positions for the hash fallback.
    All three kernels tile scores congruently ([block_q, block_k], q rows x
    k cols), so (qb, kb) identifies the same cells everywhere.

    ``hw_rng=False`` forces the position-keyed hash even on real TPUs: the
    HW PRNG stream is keyed on TILE ids and tile-shaped draws, so it is
    only reproducible between kernels that tile identically — ring-CP pair
    calls (fit to s_blk, not s) must use the hash to keep the realized
    mask equal to the unsharded kernel's for every cp layout.
    """
    if hw_rng and HW_RNG and not _interpret():
        # Mosaic on v5e seeds the PRNG from at most two words, so the four
        # ids fold into two (wrapping int32 multiply-add, the hash path's
        # constants); every kernel folds identically, which is all the
        # fwd/dq/dkv agreement needs
        pltpu.prng_seed(seed + bh * _C3, qb * _C1 + kb * _C2)
        bits = pltpu.prng_random_bits(shape)
        bits = jax.lax.bitcast_convert_type(bits, jnp.int32)
        threshold = jnp.int32(int(rate * (1 << 31)))
        keep = (bits & jnp.int32(0x7FFFFFFF)) >= threshold
        return keep.astype(jnp.float32) / (1.0 - rate)
    return dropout_keep_scale(seed, bh, q_col, k_row, rate)


def _mm_dtype(dtype):
    """MXU operand dtype: bf16 operands run the MXU at full rate (f32
    accumulation comes from preferred_element_type); any other input dtype
    computes in f32 so the f32 parity tests stay tight."""
    return jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32


# dot_general dimension numbers of the 2-D products the kernels use:
# A @ B^T, A @ B and A^T @ B
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims):
    """MXU product with f32 accumulation. bf16 operands pin DEFAULT
    precision: with ``precision=None`` a global
    ``jax_default_matmul_precision`` of "highest" (tests/conftest.py sets
    it on the chip so the XLA references stay f32-exact) would ask Mosaic
    for an fp32 contraction of bf16 operands, which it refuses ("Bad lhs
    type"). f32 operands keep ``None``: Mosaic's default for them is
    already the exact f32 product."""
    precision = (jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16
                 else None)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _score_mask(q_pos, k_pos, kvlen, causal: bool):
    """Bool mask for a score tile: causal triangle ∧ key inside kv_lens."""
    mask = k_pos < kvlen
    if causal:
        mask &= q_pos >= k_pos
    return mask


def fit_blocks(s: int, want_q: int, want_k: int):
    """Largest (block_q, block_k) <= the requested sizes with
    block_k | block_q | s — so sequence lengths that are NOT multiples of
    the default 512 (e.g. 768, 1920) shrink the tile instead of being
    demoted to the XLA fallback path. Returns (None, None) when no 8-row
    tile divides ``s``. Trace-time Python only."""
    want_q = min(want_q, s)
    want_k = min(want_k, s, want_q)  # block_k | block_q requires bk <= bq
    block_k = next(
        (bk for bk in range(want_k - want_k % 8, 7, -8) if s % bk == 0), None
    )
    if block_k is None:
        return None, None
    block_q = next(
        bq for bq in range(want_q - want_q % block_k, 0, -block_k)
        if s % bq == 0
    )  # always terminates: bq == block_k divides s
    return block_q, block_k


def _major_block(s: int, tile: int, want: int) -> int:
    """Largest multiple of ``tile`` that divides ``s`` and is <= want
    (but at least ``tile``): the resident-block row count."""
    n = s // tile
    t = min(n, max(want // tile, 1))
    while n % t:
        t -= 1
    return t * tile


def _last_major(i, block_q: int, major: int, causal: bool, n_major: int):
    """Index of the last K/V major block the i-th q block attends to."""
    if not causal:
        return n_major - 1
    return ((i + 1) * block_q - 1) // major


def _kv_index_map(block_q: int, major: int, causal: bool, n_major: int):
    """K/V major-block index for grid step (bh, i, jm): clamped at the causal
    diagonal so steps past it repeat the previous index (no DMA)."""

    def index_map(b, i, jm):
        return b, jnp.minimum(jm, _last_major(i, block_q, major, causal,
                                              n_major)), 0

    return index_map


def _global_ids(meta_ref, bh):
    """Resolve the LOCAL batch*head grid index to the GLOBAL batch*head id
    plus global q/k position offsets, from the SMEM ``meta`` array
    [b0, h0, h_local, h_total, q_off, k_off]. Under ``shard_map`` (TP/DP
    sharding, ring-CP block calls) these keep the dropout bit stream keyed
    on global coordinates — mesh-layout-invariant by construction. The
    unsharded identity meta [0, 0, h, h, 0, 0] reproduces the exact
    pre-meta bit stream (gbh == bh, offsets 0)."""
    h_loc = meta_ref[2]
    gbh = ((meta_ref[0] + bh // h_loc) * meta_ref[3]
           + meta_ref[1] + bh % h_loc)
    return gbh, meta_ref[4], meta_ref[5]


def _fwd_kernel(seed_ref, kvlens_ref, meta_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, block_k: int, major: int,
                scale: float, dropout_rate: float, causal: bool,
                n_major: int, hw_rng: bool = True):
    """Grid step (bh, q-block i, K/V major block jm): online-softmax updates
    over the compute tiles inside the resident major block."""
    bq, d = q_ref.shape
    bh = pl.program_id(0)
    i = pl.program_id(1)
    jm = pl.program_id(2)
    last_jm = _last_major(i, bq, major, causal, n_major)
    tiles = major // block_k

    @pl.when(jm == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(jm <= last_jm)
    def _step():
        # bf16 inputs stay bf16 INTO the MXU (f32 accumulation via
        # preferred_element_type) — f32 operands would run the MXU at
        # quarter rate; f32 inputs keep the full-precision path (tests)
        mm_dt = _mm_dtype(q_ref.dtype)
        q = q_ref[:].astype(mm_dt)
        kvlen = kvlens_ref[bh]
        gbh, q_off, k_off = _global_ids(meta_ref, bh)
        # positions as a [bq, 1] column / [1, bk] row: masking and the
        # dropout hash broadcast them, keeping per-cell VPU work minimal;
        # GLOBAL positions (q_off/k_off are 0 unless sharded)
        q_col = q_off + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

        def body(t, carry, masked: bool):
            m, l, acc = carry
            k_blk = k_ref[pl.ds(t * block_k, block_k), :].astype(mm_dt)
            v_blk = v_ref[pl.ds(t * block_k, block_k), :].astype(mm_dt)
            # [bq, block_k]; scale post-dot keeps it f32
            s = _dot(q, k_blk, _NT) * scale
            k_row = (k_off + jm * major + t * block_k
                     + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
            if masked:
                s = jnp.where(_score_mask(q_col, k_row, kvlen, causal),
                              s, NEG_INF)

            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked:
                # fully-masked rows: keep p exactly 0 (avoids exp(NEG-NEG)=1
                # garbage rows feeding dV through p in the backward kernels)
                p = jnp.where(s > NEG_INF / 2, p, 0.0)
            alpha = jnp.exp(m - m_new)
            # The softmax normalizer sums the *undropped* probabilities;
            # dropout scales only the value path (out = drop(softmax(s)) @ v).
            l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            if dropout_rate > 0.0:
                p = p * _tile_keep_scale(
                    seed_ref[0], gbh, q_off // bq + i,
                    k_off // block_k + jm * tiles + t, q_col, k_row,
                    (bq, block_k), dropout_rate, hw_rng,
                )
            acc_new = alpha * acc + _dot(p.astype(mm_dt), v_blk, _NN)
            return m_new, l_new, acc_new

        # two-phase walk: tiles strictly inside the causal triangle AND
        # fully below kv_lens skip all mask work (the bulk of the VPU cost);
        # only diagonal-crossing / kv-cut tiles run the masked body.
        # kvlen and the causal diagonal live in GLOBAL positions; the local
        # tile walk subtracts the offsets (both 0 unless sharded).
        kv_rel = kvlen - k_off
        dq_off = q_off - k_off
        n_kv_full = jnp.clip((kv_rel - jm * major) // block_k, 0, tiles)
        n_kv_any = jnp.clip(
            (kv_rel - jm * major + block_k - 1) // block_k, 0, tiles
        )
        if causal:
            n_causal = jnp.clip((dq_off + (i + 1) * bq - jm * major)
                                // block_k, 0, tiles)
            n_causal_free = jnp.clip((dq_off + i * bq - jm * major + 1)
                                     // block_k, 0, tiles)
            n_inner = jnp.minimum(n_causal, n_kv_any)
            n_free = jnp.minimum(n_causal_free, n_kv_full)
        else:
            n_inner = n_kv_any
            n_free = n_kv_full
        n_free = jnp.minimum(n_free, n_inner)
        carry = (m_scr[:], l_scr[:], acc_scr[:])
        carry = jax.lax.fori_loop(
            0, n_free, functools.partial(body, masked=False), carry
        )
        m, l, acc = jax.lax.fori_loop(
            n_free, n_inner, functools.partial(body, masked=True), carry
        )
        m_scr[:] = m
        l_scr[:] = l
        acc_scr[:] = acc

    @pl.when(jm == last_jm)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l > 0.0, l, 1.0)  # fully-masked rows emit zeros
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[:] = m_scr[:] + jnp.log(l_safe)  # [bq, 1] tile of (bh, s, 1)


def _bwd_dq_kernel(seed_ref, kvlens_ref, meta_ref, q_ref, k_ref, v_ref,
                   do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *,
                   block_k: int, major: int, scale: float,
                   dropout_rate: float, causal: bool, n_major: int,
                   hw_rng: bool = True):
    bq, d = q_ref.shape
    bh = pl.program_id(0)
    i = pl.program_id(1)
    jm = pl.program_id(2)
    last_jm = _last_major(i, bq, major, causal, n_major)
    tiles = major // block_k

    @pl.when(jm == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(jm <= last_jm)
    def _step():
        mm_dt = _mm_dtype(q_ref.dtype)
        q = q_ref[:].astype(mm_dt)
        do = do_ref[:].astype(mm_dt)
        lse = lse_ref[:]      # [bq, 1]
        delta = delta_ref[:]  # [bq, 1]
        kvlen = kvlens_ref[bh]
        gbh, q_off, k_off = _global_ids(meta_ref, bh)
        q_col = q_off + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

        def body(t, dq, masked: bool):
            k_blk = k_ref[pl.ds(t * block_k, block_k), :].astype(mm_dt)
            v_blk = v_ref[pl.ds(t * block_k, block_k), :].astype(mm_dt)
            s = _dot(q, k_blk, _NT) * scale
            k_row = (k_off + jm * major + t * block_k
                     + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1))
            if masked:
                mask = _score_mask(q_col, k_row, kvlen, causal)
                p = jnp.where(mask, jnp.exp(s - lse), 0.0)
            else:
                p = jnp.exp(s - lse)
            dp = _dot(do, v_blk, _NT)
            if dropout_rate > 0.0:
                # dP = (dO @ V^T) ∘ mask; delta already equals rowsum(P ∘ dP)
                # because delta = rowsum(dO ∘ O) and O = (P ∘ mask) @ V.
                dp = dp * _tile_keep_scale(
                    seed_ref[0], gbh, q_off // bq + i,
                    k_off // block_k + jm * tiles + t, q_col, k_row,
                    (bq, block_k), dropout_rate, hw_rng,
                )
            ds = p * (dp - delta)
            return dq + _dot(ds.astype(mm_dt), k_blk, _NN)

        kv_rel = kvlen - k_off
        dq_off = q_off - k_off
        n_kv_full = jnp.clip((kv_rel - jm * major) // block_k, 0, tiles)
        n_kv_any = jnp.clip(
            (kv_rel - jm * major + block_k - 1) // block_k, 0, tiles
        )
        if causal:
            n_causal = jnp.clip((dq_off + (i + 1) * bq - jm * major)
                                // block_k, 0, tiles)
            n_causal_free = jnp.clip((dq_off + i * bq - jm * major + 1)
                                     // block_k, 0, tiles)
            n_inner = jnp.minimum(n_causal, n_kv_any)
            n_free = jnp.minimum(n_causal_free, n_kv_full)
        else:
            n_inner = n_kv_any
            n_free = n_kv_full
        n_free = jnp.minimum(n_free, n_inner)
        dq = jax.lax.fori_loop(
            0, n_free, functools.partial(body, masked=False), dq_scr[:]
        )
        dq_scr[:] = jax.lax.fori_loop(
            n_free, n_inner, functools.partial(body, masked=True), dq
        )

    @pl.when(jm == last_jm)
    def _finalize():
        dq_ref[:] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _first_major(j, block_k: int, major: int, causal: bool):
    """Index of the first Q major block that sees the j-th k block."""
    if not causal:
        return 0
    return (j * block_k) // major


def _q_stream_index_map(block_k: int, major: int, causal: bool):
    """Q-side major-block index for dkv grid step (bh, j, im): clamped below
    at the causal diagonal so pre-diagonal steps repeat one index (no DMA)."""

    def index_map(b, j, im):
        return b, jnp.maximum(im, _first_major(j, block_k, major, causal)), 0

    return index_map


def _bwd_dkv_kernel(seed_ref, kvlens_ref, meta_ref, q_ref, k_ref, v_ref,
                    do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr,
                    dv_scr, *, block_q: int, major: int, scale: float,
                    dropout_rate: float, causal: bool, n_major: int,
                    hw_rng: bool = True):
    bk, d = k_ref.shape
    bh = pl.program_id(0)
    j = pl.program_id(1)
    im = pl.program_id(2)
    first_im = _first_major(j, bk, major, causal)
    tiles = major // block_q

    @pl.when(im == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    kvlen = kvlens_ref[bh]
    gbh, q_off, k_off = _global_ids(meta_ref, bh)

    # skip entirely when this k block sits fully past the kv cut: every
    # score is masked, dk/dv stay zero (the init/finalize still run) —
    # saves the all-tiles masked walk for heavily right-padded rows
    @pl.when((im >= first_im) & (k_off + j * bk < kvlen))
    def _step():
        mm_dt = _mm_dtype(k_ref.dtype)
        k = k_ref[:].astype(mm_dt)
        v = v_ref[:].astype(mm_dt)
        k_row = k_off + j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)

        def body(t, carry, masked: bool):
            dk, dv = carry
            q_blk = q_ref[pl.ds(t * block_q, block_q), :].astype(mm_dt)
            do_blk = do_ref[pl.ds(t * block_q, block_q), :].astype(mm_dt)
            lse = lse_ref[pl.ds(t * block_q, block_q), :]      # [block_q, 1]
            delta = delta_ref[pl.ds(t * block_q, block_q), :]  # [block_q, 1]
            s = _dot(q_blk, k, _NT) * scale
            q_col = (q_off + im * major + t * block_q
                     + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0))
            if masked:
                mask = _score_mask(q_col, k_row, kvlen, causal)
                p = jnp.where(mask, jnp.exp(s - lse), 0.0)
            else:
                p = jnp.exp(s - lse)
            dp = _dot(do_blk, v, _NT)
            if dropout_rate > 0.0:
                drop = _tile_keep_scale(
                    seed_ref[0], gbh, q_off // block_q + im * tiles + t,
                    k_off // bk + j, q_col, k_row,
                    (block_q, bk), dropout_rate, hw_rng,
                )
                p_v = p * drop  # dropped probabilities feed dV
                dp = dp * drop
            else:
                p_v = p
            dv_new = dv + _dot(p_v.astype(mm_dt), do_blk, _TN)
            ds = p * (dp - delta)
            dk_new = dk + _dot(ds.astype(mm_dt), q_blk, _TN)
            return dk_new, dv_new

        dk_off = k_off - q_off
        if causal:
            # first q tile inside this major block at/after the diagonal
            t0 = jnp.clip((dk_off + j * bk - im * major) // block_q,
                          0, tiles)
            # first q tile fully past the diagonal (min q >= max k): mask-free
            t_free_c = jnp.clip(
                (dk_off + (j + 1) * bk - 1 - im * major + block_q - 1)
                // block_q, 0, tiles,
            )
        else:
            t0 = jnp.int32(0)
            t_free_c = jnp.int32(0)
        # a kv cut inside this k block masks EVERY q tile (column mask)
        kv_full = k_off + (j + 1) * bk <= kvlen
        t_free = jnp.where(kv_full, jnp.maximum(t_free_c, t0),
                           jnp.int32(tiles))
        carry = (dk_scr[:], dv_scr[:])
        carry = jax.lax.fori_loop(
            t0, jnp.minimum(t_free, tiles),
            functools.partial(body, masked=True), carry,
        )
        dk, dv = jax.lax.fori_loop(
            t_free, tiles, functools.partial(body, masked=False), carry
        )
        dk_scr[:] = dk
        dv_scr[:] = dv

    @pl.when(im == n_major - 1)
    def _finalize():
        # q was loaded UNSCALED (bf16 MXU path), so the chain rule's scale
        # factor lands here: dL/dk = scale * ds^T @ q
        dk_ref[:] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _to_bh(x):
    """[b, s, h, d] -> [b*h, s, d]"""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _seed_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _fwd_call(seed, kvlens, meta, q3, k3, v3, block_q, block_k, scale,
              dropout_rate, causal, hw_rng=True):
    bh, s, d = q3.shape
    major = _major_block(s, block_k, DEFAULT_BLOCK_MAJOR)
    n_major = s // major
    grid = (bh, s // block_q, n_major)
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, major=major, scale=scale,
        dropout_rate=dropout_rate, causal=causal, n_major=n_major,
        hw_rng=hw_rng,
    )
    kv_map = _kv_index_map(block_q, major, causal, n_major)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _seed_spec(),
            _seed_spec(),
            _seed_spec(),
            pl.BlockSpec((None, block_q, d), lambda b, i, jm: (b, i, 0)),
            pl.BlockSpec((None, major, d), kv_map),
            pl.BlockSpec((None, major, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, jm: (b, i, 0)),
            # trailing singleton dim: Mosaic requires the last block dim to
            # divide 128 or equal the array dim — (block_q, 1) satisfies it
            pl.BlockSpec((None, block_q, 1), lambda b, i, jm: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running normalizer l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name=KERNEL_NAMES[0],
    )(seed, kvlens, meta, q3, k3, v3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _flash(q, k, v, seed, kvlens, meta, block_q, block_k, dropout_rate,
           causal):
    out, _ = _flash_fwd(q, k, v, seed, kvlens, meta, block_q, block_k,
                        dropout_rate, causal)
    return out


def _flash_fwd(q, k, v, seed, kvlens, meta, block_q, block_k, dropout_rate,
               causal):
    b, s, h, d = q.shape
    q3, k3, v3 = _to_bh(q), _to_bh(k), _to_bh(v)
    o3, lse = _fwd_call(seed, kvlens, meta, q3, k3, v3, block_q, block_k,
                        1.0 / (d**0.5), dropout_rate, causal)
    # named, so core_attn recompute saves what the backward reads; lse [bh, s]
    out = checkpoint_name(_from_bh(o3, b, h), "core_attn_out")
    lse = checkpoint_name(lse[..., 0], "core_attn_lse")
    return out, (q3, k3, v3, out, lse, seed, kvlens, meta)


def _dq_call(seed, kvlens, meta, q3, k3, v3, do3, lse, delta, block_q,
             block_k, scale, dropout_rate, causal, hw_rng=True):
    """dq kernel dispatch ([bh, s, d] operands; lse/delta [bh, s, 1])."""
    bh, s, d = q3.shape
    kv_major = _major_block(s, block_k, DEFAULT_BLOCK_MAJOR)
    n_kv_major = s // kv_major
    kv_map = _kv_index_map(block_q, kv_major, causal, n_kv_major)
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_k=block_k, major=kv_major, scale=scale,
            dropout_rate=dropout_rate, causal=causal, n_major=n_kv_major,
            hw_rng=hw_rng,
        ),
        grid=(bh, s // block_q, n_kv_major),
        in_specs=[
            _seed_spec(),
            _seed_spec(),
            _seed_spec(),
            pl.BlockSpec((None, block_q, d), lambda b_, i, jm: (b_, i, 0)),
            pl.BlockSpec((None, kv_major, d), kv_map),
            pl.BlockSpec((None, kv_major, d), kv_map),
            pl.BlockSpec((None, block_q, d), lambda b_, i, jm: (b_, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b_, i, jm: (b_, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b_, i, jm: (b_, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda b_, i, jm: (b_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name=KERNEL_NAMES[1],
    )(seed, kvlens, meta, q3, k3, v3, do3, lse, delta)


def _dkv_call(seed, kvlens, meta, q3, k3, v3, do3, lse, delta, block_q,
              block_k, scale, dropout_rate, causal, hw_rng=True):
    """dk/dv kernel dispatch ([bh, s, d] operands; lse/delta [bh, s, 1])."""
    bh, s, d = q3.shape
    q_major = _major_block(s, block_q, DEFAULT_BLOCK_MAJOR)
    n_q_major = s // q_major
    q_map = _q_stream_index_map(block_k, q_major, causal)
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=block_q, major=q_major, scale=scale,
            dropout_rate=dropout_rate, causal=causal, n_major=n_q_major,
            hw_rng=hw_rng,
        ),
        grid=(bh, s // block_k, n_q_major),
        in_specs=[
            _seed_spec(),
            _seed_spec(),
            _seed_spec(),
            pl.BlockSpec((None, q_major, d), q_map),
            pl.BlockSpec((None, block_k, d), lambda b_, j, im: (b_, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b_, j, im: (b_, j, 0)),
            pl.BlockSpec((None, q_major, d), q_map),
            pl.BlockSpec((None, q_major, 1), q_map),
            pl.BlockSpec((None, q_major, 1), q_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda b_, j, im: (b_, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b_, j, im: (b_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret(),
        name=KERNEL_NAMES[2],
    )(seed, kvlens, meta, q3, k3, v3, do3, lse, delta)


def _flash_bwd(block_q, block_k, dropout_rate, causal, res, g):
    q3, k3, v3, out, lse, seed, kvlens, meta = res
    b, _, h, d = out.shape
    scale, lse = 1.0 / (d**0.5), lse[..., None]  # the kernels' [bh, s, 1]
    do3, o3 = _to_bh(g), _to_bh(out)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [bh, s, 1]
    dq3 = _dq_call(seed, kvlens, meta, q3, k3, v3, do3, lse, delta,
                   block_q, block_k, scale, dropout_rate, causal)
    dk3, dv3 = _dkv_call(seed, kvlens, meta, q3, k3, v3, do3, lse, delta,
                         block_q, block_k, scale, dropout_rate, causal)

    dq = _from_bh(dq3, b, h)
    dk = _from_bh(dk3, b, h)
    dv = _from_bh(dv3, b, h)
    # seed/kvlens/meta are integer-dtype: their cotangent type is float0
    dseed = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    dkvlens = np.zeros(kvlens.shape, dtype=jax.dtypes.float0)
    dmeta = np.zeros(meta.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dseed, dkvlens, dmeta


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------- ring-CP building blocks
# Per-(q-block, kv-block) kernel entry points for ring attention
# (parallel/context_parallel.py): [b, s, h, d] operands, explicit global
# position offsets via ``meta``, and the log-sum-exp exposed so hops can be
# merged in (out, lse) space. The ring owns its own custom VJP (re-rotating
# KV), so these are raw primal/cotangent dispatches, not custom_vjp'd.
#
# Offset rule: ``causal=True`` requires meta's q_off == k_off (the DMA
# index-map diagonal clamp assumes an aligned diagonal — exactly the ring's
# same-block-id case); cross-block pairs are fully ordered and call with
# causal=False.

def _ring_blocks(s: int):
    bq, bk = fit_blocks(s, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)
    if bq is None:
        raise ValueError(f"ring block seq {s} not tileable (multiple of 8)")
    return bq, bk


def _lse_to_bsh(lse3, b, h):
    """[b*h, s, 1] f32 -> [b, s, h]"""
    bh, s, _ = lse3.shape
    return lse3[..., 0].reshape(b, h, s).transpose(0, 2, 1)


def _lse_from_bsh(lse, b, h):
    """[b, s, h] f32 -> [b*h, s, 1]"""
    s = lse.shape[1]
    return lse.transpose(0, 2, 1).reshape(b * h, s, 1)


def block_fwd_lse(q, k, v, seed, meta, *, causal, dropout_rate, kv_len):
    """Flash forward on one (q-block, kv-block) pair.

    Returns (out [b, s, h, d], lse [b, s, h] f32). ``kv_len`` is the GLOBAL
    total key length (keys are masked at k_pos >= kv_len; pass the full
    sequence length when there is no padding)."""
    b, s, h, d = q.shape
    block_q, block_k = _ring_blocks(s)
    kvlens = jnp.full((b * h,), kv_len, jnp.int32)
    o3, lse3 = _fwd_call(
        seed, kvlens, meta, _to_bh(q), _to_bh(k), _to_bh(v), block_q,
        block_k, 1.0 / (d**0.5), dropout_rate, causal, hw_rng=False,
    )
    return _from_bh(o3, b, h), _lse_to_bsh(lse3, b, h)


def block_dq(q, k, v, do, lse, delta, seed, meta, *, causal, dropout_rate,
             kv_len):
    """dq of one pair given the MERGED lse/delta ([b, s, h] f32) of the q
    rows — the flash-attention identity lets each hop's dq be computed
    against the global softmax statistics."""
    b, s, h, d = q.shape
    block_q, block_k = _ring_blocks(s)
    kvlens = jnp.full((b * h,), kv_len, jnp.int32)
    dq3 = _dq_call(
        seed, kvlens, meta, _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(do),
        _lse_from_bsh(lse, b, h), _lse_from_bsh(delta, b, h), block_q,
        block_k, 1.0 / (d**0.5), dropout_rate, causal, hw_rng=False,
    )
    return _from_bh(dq3, b, h)


def block_dkv(q, k, v, do, lse, delta, seed, meta, *, causal, dropout_rate,
              kv_len):
    """(dk, dv) of one pair given merged lse/delta of the q rows."""
    b, s, h, d = q.shape
    block_q, block_k = _ring_blocks(s)
    kvlens = jnp.full((b * h,), kv_len, jnp.int32)
    dk3, dv3 = _dkv_call(
        seed, kvlens, meta, _to_bh(q), _to_bh(k), _to_bh(v), _to_bh(do),
        _lse_from_bsh(lse, b, h), _lse_from_bsh(delta, b, h), block_q,
        block_k, 1.0 / (d**0.5), dropout_rate, causal, hw_rng=False,
    )
    return _from_bh(dk3, b, h), _from_bh(dv3, b, h)


def _identity_meta(h: int) -> jax.Array:
    """Meta for an unsharded call: global ids == local ids, offsets 0."""
    return jnp.asarray([0, 0, h, h, 0, 0], jnp.int32)


def _shardable_mesh(q, h: int):
    """The ambient mesh to shard_map the kernel over, or None.

    Engaged only when a mesh with a non-trivial dp/fsdp/mp extent is active
    and the batch/head dims divide it. Returns None inside a vmap trace
    (the GSPMD pipeline applies stages under nn.vmap — a nested shard_map
    there would conflict with the stage sharding; callers on the pp path
    pass mesh_shard=False at the ops/attention.py level as the primary
    guard, this tracer check is the backstop for direct vmapped calls)."""
    # jax 0.9 deprecated the public jax.interpreters.batching.BatchTracer
    from jax._src.interpreters.batching import BatchTracer

    if isinstance(q, BatchTracer):
        return None
    from fleetx_tpu.parallel.mesh import ambient_mesh

    mesh = ambient_mesh()
    if mesh is None:
        return None
    n_data, n_mp = _mesh_extents(mesh)
    if n_data * n_mp <= 1:
        return None
    if q.shape[0] % n_data or h % n_mp:
        return None
    return mesh


def _mesh_extents(mesh):
    """(data world, mp world) — single source for the wrapper's degrees."""
    sizes = dict(mesh.shape)
    return sizes.get("dp", 1) * sizes.get("fsdp", 1), sizes.get("mp", 1)


def _sharded_flash(mesh, q, k, v, seed, kv_lens, block_q, block_k,
                   dropout_rate, causal):
    """shard_map the kernel over (batch -> dp/fsdp, heads -> mp).

    Without this, GSPMD treats the Pallas call as an opaque custom call and
    replicates q/k/v — i.e. an all-gather of the TP-sharded heads right
    around the flagship kernel (VERDICT r4 weak #3). The manual region keeps
    heads sharded exactly like the reference's column-parallel qkv implies
    (hybrid_model.py:131-174: heads-sharded core_attn). Dropout bits stay
    identical to the unsharded call because the kernel hashes/seeds on
    GLOBAL (batch*head, position) ids via ``meta``."""
    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh.shape)
    b, s, h, _ = q.shape
    data_axes = tuple(a for a in ("dp", "fsdp") if sizes.get(a, 1) > 1)
    head_axis = "mp" if sizes.get("mp", 1) > 1 else None
    n_data, n_mp = _mesh_extents(mesh)
    b_loc, h_loc = b // n_data, h // n_mp

    def body(q, k, v, seed, kvl):
        d_idx = jnp.int32(0)
        for a in data_axes:
            d_idx = d_idx * sizes[a] + jax.lax.axis_index(a)
        h_idx = jax.lax.axis_index(head_axis) if head_axis else jnp.int32(0)
        meta = jnp.stack([
            d_idx * b_loc,               # global batch offset
            h_idx * h_loc,               # global head offset
            jnp.int32(h_loc), jnp.int32(h),
            jnp.int32(0), jnp.int32(0),  # seq not sharded here
        ])
        kvlens_bh = jnp.repeat(kvl, h_loc)
        return _flash(q, k, v, seed, kvlens_bh, meta, block_q, block_k,
                      dropout_rate, causal)

    spec = P(data_axes or None, None, head_axis, None)
    from fleetx_tpu.parallel.mesh import shard_map

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec, P(None), P(data_axes or None)),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v, seed, kv_lens)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    *,
    causal: bool = True,
    kv_lens: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    mesh_shard: bool = True,
) -> jax.Array:
    """Flash attention, [b, s, h, d] layout. Sequence length must be a
    multiple of the block sizes (callers fall back to the XLA path
    otherwise — fleetx_tpu/ops/attention.py). ``kv_lens`` [b] int32 masks
    right-padded keys (position k valid iff k < kv_lens[b]); ``causal=False``
    gives bidirectional (encoder) attention. ``dropout_rate > 0`` requires a
    ``dropout_rng`` key; the mask is generated inside the kernel.

    When a device mesh with dp/fsdp/mp extents is ambient (Trainer's
    ``use_mesh``), the kernel is wrapped in ``shard_map`` over
    (batch -> data axes, heads -> mp) so GSPMD shards the custom call
    instead of replicating it; ``mesh_shard=False`` opts out (the pp>1
    stage-vmap path must — see fleetx_tpu/ops/attention.py)."""
    b, s, h, _ = q.shape
    want_q = block_q
    block_q, block_k = fit_blocks(s, block_q, block_k)
    if block_q is None:
        raise ValueError(f"seq {s} not tileable (must be a multiple of 8)")
    if block_q < min(128, want_q) and block_q != s:
        # the model path pre-screens with _tileable (ops/attention.py), but
        # direct callers can land on sequences whose largest divisor tile is
        # tiny — a silent 10x+ perf cliff vs the requested blocks
        import warnings

        warnings.warn(
            f"flash_attention: seq {s} only admits {block_q}x{block_k} "
            f"tiles (requested {want_q}); per-grid-step overhead will "
            "dominate — pad the sequence to a multiple of 128 or use the "
            "XLA path",
            stacklevel=2,
        )
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        seed = jax.random.bits(dropout_rng, (1,), "uint32").astype(jnp.int32)
    else:
        seed = jnp.zeros((1,), jnp.int32)
    mesh = _shardable_mesh(q, h) if mesh_shard else None
    if mesh is not None:
        kv_lens_b = (jnp.full((b,), s, jnp.int32) if kv_lens is None
                     else kv_lens.astype(jnp.int32))
        return _sharded_flash(mesh, q, k, v, seed, kv_lens_b, block_q,
                              block_k, float(dropout_rate), bool(causal))
    if kv_lens is None:
        kvlens_bh = jnp.full((b * h,), s, jnp.int32)
    else:
        kvlens_bh = jnp.repeat(kv_lens.astype(jnp.int32), h)  # [b*h]
    return _flash(q, k, v, seed, kvlens_bh, _identity_meta(h), block_q,
                  block_k, float(dropout_rate), bool(causal))
