"""A prefill chunk's attention over a LATENT cache: a Pallas TPU kernel.

Latent attention (``models/gpt/latent.py``) caches, a token and layer, one
compressed vector ``c_kv`` and one rotary key ``k_r``, with no head axis. A
chunk of ``s`` queries at positions ``start + [0, s)`` attends over the
lane's rows in the MATERIALISED form: a head's keys and values are
``[k_nope | v] = c_kv W_kvb`` of that head, ``k = [k_nope | k_r]``. In plain
XLA (``latent._chunk``, this kernel's twin) each block's float32 scores of
all heads and its expanded keys and values pass through HBM several times;
here they exist in VMEM alone.

**Form.** Grid ``(heads: parallel, key blocks: arbitrary)``. A step expands
THIS head's keys and values of one block of ``BLOCK_ROWS`` cached rows
(``[rows, c] x [c, nope + v]``, float32 accumulation, rounded to the cache's
type), scores ``q_nope k_nope^T + q_r k_r^T`` in ``score_type``
(``latent._SCORE_TYPE``), ``* scale``, and folds the block into the online
softmax: running maximum, sum and the ``[s, v]`` accumulator float32, the
probabilities into the value product in the cache's type. The grid's
second bound is dynamic, the blocks up to the chunk's last row ``start + s
- 1``: a block past it is no step at all (a step that only skips measured
0.17 us, 0.25 ms a call of 64 heads at a context of 2k). The position mask
is applied only in the blocks that reach past ``start``, the chunk's own
rows, where rows no query sees are also taken out of the value product
(whatever they hold, a NaN too, changes nothing). The operands need no
transpose: a head's queries, weights and output are 128-aligned column
blocks of ``[s, heads x width]`` views.

**Cost** a key row, head and chunk of ``s``: ``2 c (nope + v)`` operations
to expand it and ``2 s (nope + r + v)`` to score and sum it (the
materialised form's; ``perfbench/flops_mla.py`` counts them), on ``(c +
r_leaf) x 2`` bytes copied once a head: MXU-bound by a factor of ten.

**Under a selection** (``mask``: a learned indexer's choice of rows for each
query, ``models/gpt/latent.py``) the same steps run with the mask's block
``[s, rows]`` int8 in the place of the position test, in EVERY block (a
query's chosen rows lie anywhere behind it; each is visible to it, so the
mask is the whole test): 1 byte a query and key row beside the work above.
That kernel is named ``fleetx_dsa_prefill``.

Named ``fleetx_mla_prefill`` in compiled HLO and in device traces
(docs/OBSERVABILITY.md): 1 call a layer and chunk. No gradient.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu.ops.pallas.flash_attention import _interpret

__all__ = ["KERNEL_NAME", "SELECTED_KERNEL_NAME", "BLOCK_ROWS", "key_rows",
           "mla_prefill"]

KERNEL_NAME = "fleetx_mla_prefill"
SELECTED_KERNEL_NAME = "fleetx_dsa_prefill"
# cached rows of one grid step
BLOCK_ROWS = 1024
_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def key_rows(rows: int) -> int:
    """The key rows the kernel's live steps cover where the chunk's last
    row is the ``rows``-th of its lane: ``rows`` rounded up to the block."""
    return -(-rows // BLOCK_ROWS) * BLOCK_ROWS


def mla_prefill(q, w_kvb, ckv, kr, start, *, nope: int, scale: float,
                score_type, mask=None):
    """Materialised latent attention of ONE lane's chunk. ``q`` ``[s, heads,
    nope + rope]`` (rotated) at positions ``start + [0, s)``; ``w_kvb``
    ``[c, heads, nope + v]``; ``ckv`` ``[t, c]`` and ``kr`` ``[t, r_leaf]``
    the lane's cached rows in order, the chunk's own among them (``kr`` the
    leaf as held: ``r_leaf >= rope`` columns, zeros past the key); ``start``
    an int32 scalar; ``mask`` ``[s, t]`` bool, where given, the rows each
    query attends over (all visible to it). ``[s, heads, v]``."""
    s, heads, _ = q.shape
    t, c = ckv.shape
    r_leaf = kr.shape[-1]
    kv_width = w_kvb.shape[-1]
    vd = kv_width - nope
    rows = min(BLOCK_ROWS, t)
    if t % rows:
        raise ValueError(f"a lane's {t} rows are no whole number of "
                         f"{rows}-row key blocks")
    blocks = t // rows
    # a head's columns of [s, heads x width] views: no transpose
    q_nope = q[..., :nope].reshape(s, heads * nope)
    q_rope = jnp.pad(q[..., nope:], ((0, 0), (0, 0), (
        0, r_leaf - (q.shape[-1] - nope)))).reshape(s, heads * r_leaf)

    start = jnp.reshape(start, (1,)).astype(jnp.int32)
    # the blocks up to the chunk's last row: the grid's own (dynamic) bound
    live = jnp.minimum((start[0] + s - 1) // rows, blocks - 1) + 1

    def kernel(start_ref, qn_ref, qr_ref, w_ref, ckv_ref, kr_ref, *rest):
        mask_ref = rest[0] if mask is not None else None
        o_ref, m_scr, l_scr, acc_scr = rest[mask is not None:]
        j = pl.program_id(1)
        first = start_ref[0]
        # a block whose last row is no later than the first query's: all seen
        crosses = (j + 1) * rows - 1 > first

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full(m_scr.shape, _NEG, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        def step(masked: bool):
            kv = jnp.dot(ckv_ref[...], w_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(ckv_ref.dtype)            # [rows, nope + v]
            k_nope, v = kv[:, :nope], kv[:, nope:]

            def scores_of(a, b):  # rounded as the plain form's einsum is
                return jax.lax.dot_general(
                    a, b, _NT, preferred_element_type=jnp.float32
                ).astype(score_type)

            sc = (scores_of(qn_ref[...], k_nope)
                  + scores_of(qr_ref[...], kr_ref[...])
                  ).astype(jnp.float32) * scale             # [s, rows]
            if masked:
                at = j * rows
                seen = (mask_ref[...] != 0) if mask is not None else (
                    at + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
                    <= first + jax.lax.broadcasted_iota(
                        jnp.int32, (s, 1), 0))
                sc = jnp.where(seen, sc, _NEG)
                v = jnp.where(
                    at + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                    < first + s, v, jnp.zeros_like(v))
            m = m_scr[...]
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            if masked:
                p = jnp.where(seen, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        if mask is not None:
            step(True)
        else:
            pl.when(jnp.logical_not(crosses))(lambda: step(False))
            pl.when(crosses)(lambda: step(True))

        @pl.when(j == pl.num_programs(1) - 1)
        def _finalize():
            total = l_scr[...]
            o_ref[...] = (acc_scr[...] / jnp.where(total > 0.0, total, 1.0)
                          ).astype(o_ref.dtype)

    def head_map(h, j, start_ref):
        return 0, h

    def row_map(h, j, start_ref):
        return j, 0

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, live),
            in_specs=[pl.BlockSpec((s, nope), head_map),
                      pl.BlockSpec((s, r_leaf), head_map),
                      pl.BlockSpec((c, kv_width), head_map),
                      pl.BlockSpec((rows, c), row_map),
                      pl.BlockSpec((rows, r_leaf), row_map)]
            + ([pl.BlockSpec((s, rows), lambda h, j, start_ref: (0, j))]
               if mask is not None else []),
            out_specs=pl.BlockSpec((s, vd), head_map),
            scratch_shapes=[
                pltpu.VMEM((s, 1), jnp.float32),     # running max
                pltpu.VMEM((s, 1), jnp.float32),     # normaliser
                pltpu.VMEM((s, vd), jnp.float32),    # accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((s, heads * vd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name=KERNEL_NAME if mask is None else SELECTED_KERNEL_NAME,
    )(start, q_nope, q_rope,
      w_kvb.reshape(c, heads * kv_width), ckv, kr,
      *(() if mask is None else (mask.astype(jnp.int8),)))
    return out.reshape(s, heads, vd)
