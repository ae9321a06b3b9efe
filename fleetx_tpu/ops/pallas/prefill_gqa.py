"""A prefill chunk's attention of GROUPED heads over the paged cache, window
and full layers alike: a Pallas TPU kernel.

A layer of ``models/gpt/hybrid.py`` caches ``kv_heads`` key and value heads
of ``d`` columns a row, lane-dense (``[rows, kv_heads * d]``), and
``heads`` query heads read them ``heads // kv_heads`` to one. A chunk of
``s`` queries at positions ``start + [0, s)`` attends over the lane's rows
as gathered in front of the kernel: the whole lane in a full layer, the
window plus the chunk in a window layer (``base`` is the position of
gathered row 0), either made whole key blocks by entries of the layer's
trash page behind the last (``padded_rows``; masked by position). In plain
XLA (``hybrid.grouped_attention``, this kernel's twin) the float32 scores
``[kv_heads, group, s, rows]`` of ALL gathered rows, seen or not, pass
through HBM some four times; here a block's scores exist in VMEM alone, and
a block no query sees is no step at all.

**Form.** Grid ``(query heads: parallel, key blocks: arbitrary)``. A step
copies ONE block of ``BLOCK_ROWS`` rows of the keys and values of the query
head's KEY head (a 128-aligned column block of the rows as the pool holds
them: no transpose, no key repeated in HBM) and folds it into the head's
online softmax: scores with float32 accumulation over ``sqrt(d)``, running
maximum, sum and the ``[s, d]`` accumulator float32, the probabilities into
the value product in the cache's type (``grouped_attention``'s types). BOTH
bounds of the second axis are dynamic: the first block is the one that
holds the first query's oldest visible key (block 0 in a full layer), the
last the one that holds the chunk's last row. The mask (``k_pos <= q_pos``
and ``q_pos - k_pos < window``) is applied only in the blocks that cross
the chunk's own rows or a window's edge; there, rows no query sees are also
taken out of both products (a window layer's table points released pages at
the trash page: whatever it holds, a NaN too, changes nothing). The kind of
layer is data: ``window`` is a prefetched scalar, none = 2^30.

**Measured** (TPU v5e, PR 44, 28 heads over 4 of 128, a chunk of 512): key
blocks of 1,024 rows run a full layer at a context of 12k in 0.77 ms (58%
of the MXU's peak on the rows seen) where 512 take 1.26 and 256 2.27 (a
step's fixed work is the ``[s, 1]`` statistics and the accumulator's
rescale), 2,048 no less; the seven heads of a group in ONE step (one copy
of the block, one mask) gain 6% there and nothing in the served cell, at
five times the compile time, so a head is a grid step.

**Cost** a key row, query head and chunk of ``s``: ``4 s d`` operations on
``4 d`` bytes copied: MXU- and VPU-bound by far (one ``exp`` for every
``4 d`` MXU operations).

Named ``fleetx_prefill_gqa`` in compiled HLO and in device traces
(docs/OBSERVABILITY.md): 1 call a layer and chunk, under the layer's
``attn_full`` / ``attn_window`` scope. No gradient.

**Under a learned indexer** (``models/gpt/indexer.py``: every query of the
chunk attends over ITS OWN set of rows) the position test gives way to a
mask: :func:`gqa_sparse_prefill`, the same grid and step over a full
layer's rows with an int8 block ``[s, BLOCK_ROWS]`` of the mask ``[s,
rows]`` copied beside the keys and values (as ``fleetx_dsa_prefill`` takes
its own, ``ops/pallas/mla_prefill.py``), named ``fleetx_gqa_sparse_prefill``,
under the scope ``dsa_attn``. It visits EVERY key block up to the chunk's
last row, chosen from or not: a kernel that skips the blocks no row of the
chunk chose is a later issue's. Its plain twin is ``hybrid.
grouped_attention`` handed the mask.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu.ops.pallas.flash_attention import _interpret

__all__ = ["KERNEL_NAME", "SPARSE_KERNEL_NAME", "BLOCK_ROWS",
           "gqa_sparse_prefill", "key_rows", "padded_rows", "prefill_gqa",
           "takes"]

KERNEL_NAME = "fleetx_prefill_gqa"
SPARSE_KERNEL_NAME = "fleetx_gqa_sparse_prefill"
# cached rows of one grid step
BLOCK_ROWS = 1024
_NO_WINDOW = 1 << 30
# a chunk of 1,024 rows fits the default 16 MiB, one of 2,048 does not
_VMEM_LIMIT = 64 << 20
_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def padded_rows(t: int) -> int:
    """The rows the kernel is handed for ``t`` gathered ones: whole key
    blocks (one block of their own size where they are fewer than one)."""
    return t if t <= BLOCK_ROWS else -(-t // BLOCK_ROWS) * BLOCK_ROWS


def takes(b: int, s: int, head_dim: int, page_size: int) -> bool:
    """Whether a call of ``b`` lanes x ``s`` rows is a shape of the
    kernel's: one lane, a chunk, a head that is whole 128-lane column
    blocks of the pool's rows, key blocks of whole pages."""
    return (b == 1 and s > 1 and head_dim % 128 == 0
            and BLOCK_ROWS % page_size == 0)


def _live(xp, start, s, base, window, rows: int, blocks: int):
    """First and last key block a query of the chunk can see (``xp``:
    ``jnp`` on traced scalars, ``np`` on ints)."""
    first = xp.maximum(start - window + 1 - base, 0) // rows
    last = xp.minimum((start + s - 1 - base) // rows, blocks - 1)
    return first, last


def key_rows(start: int, s: int, base: int, window, t: int) -> int:
    """The key rows the kernel's live steps cover in ONE layer and key head
    for a chunk of ``s`` rows at ``start`` over ``t`` gathered rows (whole
    blocks: ``padded_rows``) from position ``base``: work and padding
    together."""
    rows = min(BLOCK_ROWS, t)
    first, last = _live(np, start, s, base, window or _NO_WINDOW, rows,
                        t // rows)
    return int(last - first + 1) * rows


def prefill_gqa(q, k, v, start, base, window=None):
    """Attention of ONE lane's chunk. ``q`` ``[s, heads, d]`` at positions
    ``start + [0, s)``; ``k`` and ``v`` ``[t, kv_heads * d]`` the lane's
    gathered rows in order, row 0 at position ``base``, the chunk's own
    among them; ``start`` and ``base`` int32 scalars; ``window`` the keys a
    query sees counting its own (None: every key behind it).
    ``[s, heads, d]``."""
    s, heads, d = q.shape
    t, width = k.shape
    kv_heads = width // d
    group = heads // kv_heads
    rows = min(BLOCK_ROWS, t)
    if t % rows:
        raise ValueError(f"a lane's {t} gathered rows are no whole number "
                         f"of {rows}-row key blocks (padded_rows)")
    scale = 1.0 / (d ** 0.5)
    start = jnp.asarray(start, jnp.int32).reshape(())
    base = jnp.asarray(base, jnp.int32).reshape(())
    window = jnp.int32(window or _NO_WINDOW)
    first, last = _live(jnp, start, s, base, window, rows, t // rows)
    scalars = jnp.stack([start, base, window, first])

    def kernel(scalar_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        j = pl.program_id(1)
        begin, window = scalar_ref[0], scalar_ref[2]
        at = scalar_ref[1] + (scalar_ref[3] + j) * rows  # the block's row 0
        # every query sees every key of a block that ends no later than the
        # first query and begins inside the last query's window
        crosses = jnp.logical_or(at + rows - 1 > begin,
                                 at <= begin + s - 1 - window)

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        def step(masked: bool):
            keys, values = k_ref[...], v_ref[...]
            if masked:
                k_pos = at + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
                q_pos = begin + jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0)
                seen = jnp.logical_and(k_pos <= q_pos,
                                       q_pos - k_pos < window)
                # rows no query sees: out of both products, whatever they
                # hold (every other row is a live one)
                row = at + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                live = jnp.logical_and(row < begin + s, row > begin - window)
                keys = jnp.where(live, keys, jnp.zeros_like(keys))
                values = jnp.where(live, values, jnp.zeros_like(values))
            sc = jax.lax.dot_general(
                q_ref[...], keys, _NT,
                preferred_element_type=jnp.float32) * scale      # [s, rows]
            if masked:
                sc = jnp.where(seen, sc, _NEG)
            m = m_scr[...]
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            # a masked score is exp(_NEG - m) = 0 under a real maximum; a
            # query that has seen no key yet (a block before its window)
            # sums ones here, and its first real maximum takes them out
            # again (alpha = 0): every query sees its own key
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
                p.astype(values.dtype), values,
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        pl.when(jnp.logical_not(crosses))(lambda: step(False))
        pl.when(crosses)(lambda: step(True))

        @pl.when(j == pl.num_programs(1) - 1)
        def _finalize():
            total = l_scr[...]
            o_ref[...] = (acc_scr[...] / jnp.where(total > 0.0, total, 1.0)
                          ).astype(o_ref.dtype)

    def head_map(h, j, scalar_ref):
        return 0, h

    def row_map(h, j, scalar_ref):
        return scalar_ref[3] + j, h // group

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, last - first + 1),
            in_specs=[pl.BlockSpec((s, d), head_map),
                      pl.BlockSpec((rows, d), row_map),
                      pl.BlockSpec((rows, d), row_map)],
            out_specs=pl.BlockSpec((s, d), head_map),
            scratch_shapes=[
                pltpu.VMEM((s, 1), jnp.float32),     # running max
                pltpu.VMEM((s, 1), jnp.float32),     # normaliser
                pltpu.VMEM((s, d), jnp.float32),     # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((s, heads * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(scalars, q.reshape(s, heads * d), k, v)
    return out.reshape(s, heads, d)


def gqa_sparse_prefill(q, k, v, mask, start):
    """Attention of ONE lane's chunk under a selection. ``q`` ``[s, heads,
    d]`` at positions ``start + [0, s)``; ``k`` and ``v`` ``[t, kv_heads *
    d]`` the lane's rows in order from position 0 (whole key blocks:
    ``padded_rows``), the chunk's own among them; ``mask`` ``[s, t]`` bool,
    the rows each query attends over (all of them rows it sees); ``start``
    an int32 scalar. Scores in float32. ``[s, heads, d]``."""
    s, heads, d = q.shape
    t, width = k.shape
    group = heads // (width // d)
    rows = min(BLOCK_ROWS, t)
    if t % rows:
        raise ValueError(f"a lane's {t} gathered rows are no whole number "
                         f"of {rows}-row key blocks (padded_rows)")
    scale = 1.0 / (d ** 0.5)
    start = jnp.asarray(start, jnp.int32).reshape((1,))
    # the blocks up to the chunk's last row: the grid's own (dynamic) bound
    live = jnp.minimum((start[0] + s - 1) // rows, t // rows - 1) + 1

    def kernel(start_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, m_scr, l_scr,
               acc_scr):
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        # rows past the chunk's last (a trash page's, whatever they hold):
        # out of both products
        row = j * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        held = row < start_ref[0] + s
        keys = jnp.where(held, k_ref[...], jnp.zeros_like(k_ref[...]))
        values = jnp.where(held, v_ref[...], jnp.zeros_like(v_ref[...]))
        seen = mask_ref[...] != 0
        sc = jax.lax.dot_general(
            q_ref[...], keys, _NT, preferred_element_type=jnp.float32
        ) * scale                                                 # [s, rows]
        sc = jnp.where(seen, sc, _NEG)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(values.dtype), values,
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

        @pl.when(j == pl.num_programs(1) - 1)
        def _finalize():
            total = l_scr[...]
            o_ref[...] = (acc_scr[...] / jnp.where(total > 0.0, total, 1.0)
                          ).astype(o_ref.dtype)

    def head_map(h, j, start_ref):
        return 0, h

    def row_map(h, j, start_ref):
        return j, h // group

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, live),
            in_specs=[pl.BlockSpec((s, d), head_map),
                      pl.BlockSpec((rows, d), row_map),
                      pl.BlockSpec((rows, d), row_map),
                      pl.BlockSpec((s, rows), lambda h, j, start_ref: (0, j))],
            out_specs=pl.BlockSpec((s, d), head_map),
            scratch_shapes=[
                pltpu.VMEM((s, 1), jnp.float32),     # running max
                pltpu.VMEM((s, 1), jnp.float32),     # normaliser
                pltpu.VMEM((s, d), jnp.float32),     # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((s, heads * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=SPARSE_KERNEL_NAME,
    )(start, q.reshape(s, heads * d), k, v, mask.astype(jnp.int8))
    return out.reshape(s, heads, d)
