"""Paged decode attention over a LATENT cache: a Pallas TPU kernel.

Latent attention (``models/gpt/latent.py``) caches, a token and layer, one
compressed vector ``c_kv`` (``kv_lora_rank`` wide) and one rotary key
``k_r`` (``qk_rope_head_dim`` wide), with no head axis. In the ABSORBED
form a decode step's query of head ``h`` is ``[q~_h | q_r_h]`` (``q~_h =
q_nope_h W_UK_h^T``), its score against a cached row ``[q~_h | q_r_h] .
[c_kv | k_r]`` and its value the row's ``c_kv`` itself. So EVERY head reads
the SAME row: the heads are the rows of one matmul operand ``[heads, 576]``
against the row tile ``[rows, 576]``, and the value product takes the
tile's first 512 columns again. A cached row is copied from HBM once a
step, for all heads, keys and values alike; the block-diagonal trick of
``decode_attention.py`` (one lane-dense row of all heads' keys) has nothing
to do here.

**Cost** a live row: ``heads x (576 + 512) x 2`` operations on ``(512 +
64) x 2`` bytes, 121 FLOP a byte at 64 heads: under the v5e's ridge of 240,
so the HBM read bounds it, but by a factor of two and not of a hundred as
a full-head cache (``perfbench/flops_mla.py`` counts both).

**Form.** Grid ``(lanes, blocks)``; a step's tile is ``pages`` pages of
one lane, gathered through the lane's block table by one async copy a LIVE
page and pool into its place in a ``[2, rows, width]`` buffer, the next
live step's copies started before this step's are waited for
(``decode_attention._paged_block_call``'s scheme: the pools stay in HBM,
both grid axes are sequential, the buffer half passes from step to step in
SMEM). Rows past the lane's ``end`` are masked by position; a lane whose
``end`` is 0 runs no step and returns zeros. The online softmax (running
maximum, sum and the ``[heads, 512]`` accumulator) is float32; the
probabilities enter the value product in the cache's type.

Named ``fleetx_mla_decode_paged`` in compiled HLO and in device traces
(docs/OBSERVABILITY.md). No gradient: a decode kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu.ops.pallas.flash_attention import _interpret

__all__ = ["KERNEL_NAME", "BLOCK_ROWS", "mla_decode_paged",
           "mla_decode_reference"]

KERNEL_NAME = "fleetx_mla_decode_paged"
# cache rows of one grid step (a whole number of pages)
BLOCK_ROWS = 512
_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def mla_decode_reference(q_c, q_r, ckv_pool, kr_pool, *, tables, end,
                         scale: float):
    """What the kernel computes, in plain ``jax.numpy`` (the path off the
    TPU, and the kernel's test): ``softmax(([q_c | q_r] . [c_kv | k_r]) *
    scale) c_kv`` over each lane's rows ``[0, end)``, gathered through its
    table. ``q_c`` ``[b, h, c]``, ``q_r`` ``[b, h, r]``; ``[b, h, c]``."""
    b = q_c.shape[0]
    ckv = ckv_pool[tables].reshape(b, -1, ckv_pool.shape[-1])
    kr = kr_pool[tables].reshape(b, -1, kr_pool.shape[-1])
    scores = (jnp.einsum("bhc,btc->bht", q_c, ckv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bhr,btr->bht", q_r, kr,
                           preferred_element_type=jnp.float32)) * scale
    live = jnp.arange(ckv.shape[1])[None, :] < end[:, None]
    scores = jnp.where(live[:, None, :], scores, _NEG)
    top = scores.max(-1, keepdims=True)
    p = jnp.where(live[:, None, :], jnp.exp(scores - top), 0.0)
    out = jnp.einsum("bht,btc->bhc", p.astype(ckv.dtype), ckv,
                     preferred_element_type=jnp.float32)
    total = p.sum(-1, keepdims=True)
    return (out / jnp.where(total > 0, total, 1.0)).astype(q_c.dtype)


def mla_decode_paged(q_c, q_r, ckv_pool, kr_pool, *, tables, end,
                     scale: float, block_rows: int = BLOCK_ROWS):
    """Absorbed latent attention of ONE query a lane against the paged
    latent cache. ``q_c`` ``[b, heads, c]`` (the query through ``W_UK``),
    ``q_r`` ``[b, heads, r]`` (its rotary part); ``ckv_pool`` ``[pages,
    page_size, c]`` and ``kr_pool`` ``[pages, page_size, r]``, the flat
    pools; ``tables`` ``[b, pages of a row]`` int32 the lanes' pages in
    logical order (the layer's base added); ``end`` ``[b]`` int32 the rows
    each lane attends over, ``[0, end)``. Returns ``P c_kv`` ``[b, heads,
    c]``, which the caller takes through ``W_UV``."""
    b, heads, c = q_c.shape
    r = q_r.shape[-1]
    ps = ckv_pool.shape[1]
    n_pages = tables.shape[1]
    pages = max(1, min(block_rows // ps, n_pages))
    rows = pages * ps
    pools = (ckv_pool, kr_pool)

    def kernel(ends_ref, tables_ref, qc_ref, qr_ref, ckv_hbm, kr_hbm, o_ref,
               m_scr, l_scr, acc_scr, cbuf, rbuf, sem, state):
        bi, jm = pl.program_id(0), pl.program_id(1)
        end = ends_ref[bi]
        last_jm = (end - 1) // rows
        bufs = (cbuf, rbuf)

        @pl.when((bi == 0) & (jm == 0))
        def _reset():
            state[0] = 0  # buffer half of the next live step
            state[1] = 0  # 1: that step's copies are already on their way
            for buf in bufs:  # a row no copy wrote must be finite (p is 0)
                buf[...] = jnp.zeros(buf.shape, buf.dtype)

        def copies(lane, blk, half, wait):
            last = jnp.minimum((ends_ref[lane] - 1) // ps, n_pages - 1)
            hi = jnp.clip(last + 1 - blk * pages, 0, pages)

            def one(i, carry):
                page = tables_ref[lane, blk * pages + i]
                row0 = pl.multiple_of(i * ps, ps)
                for pool, buf in zip((ckv_hbm, kr_hbm), bufs):
                    dma = pltpu.make_async_copy(
                        pool.at[page], buf.at[half, pl.ds(row0, ps), :],
                        sem.at[half])
                    if wait:
                        dma.wait()
                    else:
                        dma.start()
                return carry

            jax.lax.fori_loop(0, hi, one, 0)

        @pl.when(jm == 0)
        def _init():
            m_scr[:] = jnp.full(m_scr.shape, _NEG, jnp.float32)
            l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

        @pl.when((end > 0) & (jm <= last_jm))
        def _step():
            half = state[0]

            @pl.when(state[1] == 0)
            def _own():
                copies(bi, jm, half, wait=False)

            # the next live step: this lane's next block, else the first
            # block of the next lane that has rows (lanes without rows
            # start nothing, and the step after them starts its own)
            same = jm < last_jm
            lane = jnp.minimum(jnp.where(same, bi, bi + 1), b - 1)
            blk = jnp.where(same, jm + 1, 0)
            ahead = same | ((bi + 1 < b) & (ends_ref[lane] > 0))

            @pl.when(ahead)
            def _next():
                copies(lane, blk, 1 - half, wait=False)

            state[0] = 1 - half
            state[1] = ahead.astype(jnp.int32)
            copies(bi, jm, half, wait=True)
            ckv = cbuf[half]                                   # [rows, c]
            s = (jax.lax.dot_general(qc_ref[...], ckv, _NT,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[...], rbuf[half], _NT,
                                       preferred_element_type=jnp.float32)
                 ) * scale                                     # [heads, rows]
            k_row = jm * rows + jax.lax.broadcasted_iota(
                jnp.int32, (1, rows), 1)
            live = k_row < end
            s = jnp.where(live, s, _NEG)
            m = m_scr[:]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(live, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:] = alpha * acc_scr[:] + jnp.dot(
                p.astype(ckv.dtype), ckv, preferred_element_type=jnp.float32)
            m_scr[:] = m_new

        @pl.when(jm == pl.num_programs(1) - 1)
        def _finalize():
            total = l_scr[:]
            o_ref[...] = (acc_scr[:] / jnp.where(total > 0.0, total, 1.0)
                          ).astype(o_ref.dtype)

    def q_map(bi, jm, *_):
        return bi, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, -(-n_pages // pages)),
        in_specs=[pl.BlockSpec((None, heads, c), q_map),
                  pl.BlockSpec((None, heads, r), q_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, heads, c), q_map),
        scratch_shapes=[
            pltpu.VMEM((heads, 1), jnp.float32),     # running max
            pltpu.VMEM((heads, 1), jnp.float32),     # normaliser
            pltpu.VMEM((heads, c), jnp.float32),     # accumulator
        ] + [pltpu.VMEM((2, rows, x.shape[-1]), x.dtype) for x in pools]
        + [pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((2,), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, c), q_c.dtype),
        compiler_params=pltpu.CompilerParams(
            # the lane axis too: a lane's last step starts the next
            # lane's first copies
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(end.astype(jnp.int32), tables.astype(jnp.int32), q_c, q_r, *pools)
