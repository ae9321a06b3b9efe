"""Grouped matmuls of a dropless expert layer: Pallas TPU kernels.

Rows arrive SORTED BY EXPERT with every expert's group padded to a whole
number of ``tm``-row tiles (``parallel/moe.py`` lays them out), so one
tile belongs to one expert and the kernel needs no mask: tile ``i`` of the
rows meets the weights of expert ``tile_expert[i]``, looked up from scalar
prefetch inside the weights' index map. Consecutive tiles of one expert
name the same weight block, which Pallas then leaves in VMEM: every
expert that has rows is read from HBM once, and an expert without rows is
never read. That is the point at decode shapes (32 lanes x 8 experts a
token over 64 experts: 4 rows an expert), where the layer is the read of
the weights and nothing else.

Two kernels, named for the device trace (docs/OBSERVABILITY.md):

- ``fleetx_moe_gate_up``: ``act(x @ w_gate[e]) * (x @ w_up[e])``, ``act``
  ``silu`` or ``relu`` (ReGLU), both products and the activation in
  float32, one rounding on the way out;
- ``fleetx_moe_down``: ``a @ w_down[e]``.

The row layout is bounded statically (``rows + experts * (tm - 1)``
rounded up to a tile, plus one more: XLA needs the shapes) and filled
dynamically: ``num_tiles``, at least 1, says how many tiles hold rows, and
it is the BOUND of the grid's row axis, ``grid=(n // tn, num_tiles)``. A
call walks the tiles that hold rows and no other (a step over a tile
without rows cost 0.12-0.2 us here, once for every column block: PERF.md,
PR 60); the rows of the result from tile ``num_tiles`` on are never
written. Rows of padding inside a walked tile compute garbage from
whatever token the layout gathered there; nobody reads them.

The weights are the WHOLE layer stack ``[layers, experts, k, n]`` with
``layer`` saying which one: the layer is picked inside the index map too.
A Mosaic call's operand has to be a whole buffer, so handing it the layer
loop's slice of the stack makes XLA copy that slice first (268 MB a
matrix at OLMoE's widths, three a layer, every program: a v5e compile of
the 32-lane tick holds 673 MB of temporaries that way and 0.7 MB this
way); indexing the stack reads only the experts that have rows.

No gradient is defined: training takes ``jax.lax.ragged_dot``
(``parallel/moe.py``), which XLA differentiates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu.ops.pallas.flash_attention import _interpret

__all__ = ["GATE_UP_KERNEL_NAME", "DOWN_KERNEL_NAME", "grouped_gate_up",
           "grouped_down", "row_tile"]

GATE_UP_KERNEL_NAME = "fleetx_moe_gate_up"
DOWN_KERNEL_NAME = "fleetx_moe_down"
# a whole [K, N] weight of one expert is one block while it stays under
# this (double-buffered: gate and up at 2048 x 1024 bf16 hold 16 MiB)
_BLOCK_BYTES = 4 << 20
_VMEM_LIMIT = 48 << 20
# the gate's activation: gated SiLU (OLMoE) or ReGLU
_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def row_tile(rows: int, experts: int) -> int:
    """Rows of one tile: the power of two nearest under the mean group,
    held to [16, 128] (16 rows are one packed bf16 sublane tile; 128 fill
    the MXU's height)."""
    mean = max(rows // experts, 1)
    return min(max(1 << (mean.bit_length() - 1), 16), 128)


def _col_tile(k: int, n: int, itemsize: int) -> int:
    tn = n
    while k * tn * itemsize > _BLOCK_BYTES and tn % 256 == 0:
        tn //= 2
    return tn


def _kernel(te_ref, layer_ref, x_ref, *refs, act):
    del te_ref, layer_ref  # read by the index maps
    o_ref = refs[-1]
    x = x_ref[...]
    out = jnp.dot(x, refs[0][...], preferred_element_type=jnp.float32)
    if act is not None:
        up = jnp.dot(x, refs[1][...], preferred_element_type=jnp.float32)
        out = _ACTS[act](out) * up
    o_ref[...] = out.astype(o_ref.dtype)


def _grouped(name, x, weights, tile_expert, num_tiles, tm, layer, act=None):
    rows, k = x.shape
    if (act is None) != (len(weights) == 1) or (act and act not in _ACTS):
        raise ValueError(f"{name}: activation {act!r} (of {sorted(_ACTS)}) "
                         f"over {len(weights)} weights")
    if weights[0].ndim != 4:
        raise ValueError(f"{name}: weights are the layer stack [layers, "
                         f"experts, k, n], not {weights[0].shape}")
    wk, n = weights[0].shape[-2:]
    if wk != k or rows % tm:
        raise ValueError(f"{name}: rows {x.shape} (tile {tm}) against "
                         f"weights {weights[0].shape}")
    tn = _col_tile(k, n, weights[0].dtype.itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # columns outside, rows inside: along the rows the weight block
        # changes only where the expert does. The row bound is the traced
        # count of tiles that hold rows
        grid=(n // tn, num_tiles),
        in_specs=[pl.BlockSpec((tm, k), lambda j, i, te, li: (i, 0))]
        + [pl.BlockSpec((None, None, k, tn),
                        lambda j, i, te, li: (li[0], te[i], 0, j))
           for _ in weights],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, te, li: (i, j)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=name,
    )(tile_expert.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      x, *weights)
    return out


def grouped_gate_up(x, w_gate, w_up, tile_expert, num_tiles, *, tm: int,
                    layer, act: str = "silu"):
    """``act(x @ w_gate[l, e]) * (x @ w_up[l, e])`` for rows ``x`` ``[rows,
    k]`` laid out in ``tm``-row tiles, tile ``i`` of expert
    ``tile_expert[i]``; weights the stack ``[layers, experts, k, n]`` and
    ``layer`` (a traced scalar) the layer ``l``. The grid walks the first
    ``num_tiles`` tiles (a traced scalar, at least 1): the rows of the
    result past them are not defined."""
    return _grouped(GATE_UP_KERNEL_NAME, x, (w_gate, w_up), tile_expert,
                    num_tiles, tm, layer, act)


def grouped_down(x, w_down, tile_expert, num_tiles, *, tm: int, layer):
    """``x @ w_down[l, e]`` over the same layout."""
    return _grouped(DOWN_KERNEL_NAME, x, (w_down,), tile_expert, num_tiles,
                    tm, layer)
