"""The selective scan of a Mamba-1 layer (``models/gpt/mixed_stack.py``):

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) outer B_t
    y_t = h_t . C_t

with ``u, dt`` ``[.., D]`` (``D`` the layer's inner width), ``B, C`` ``[..,
N]`` (``N`` the state of one channel), ``A`` ``[N, D]`` and the state ``h``
``[N, D]``, everything float32. ``D * u`` and the gate are the caller's.

**Layout.** The state is held ``[N, D]``, the channels along the 128 lanes
of a vector register and the ``N`` state values along its sublanes: ``[D,
N]`` with ``N`` = 16 last would pad every row of 16 to 128 lanes, eight
times the bytes in HBM and in VMEM. ``y_t`` is then a reduction over
sublanes, and ``B_t``, ``C_t`` enter as ``[N, 128]`` (the value of each
state index along all lanes: the caller's broadcast, 8 KB a row), which one
register holds for every 128 channels.

**Two kernels.**

``fleetx_ssm_scan`` (a call of more than one row: a prefill or a chunk of
one): grid ``(batch, chunks of rows, blocks of channels)``, both inner axes
sequential. The state is the kernel's resident output block ``[blocks, N,
bd]``: a grid step takes its block of channels, runs the chunk's rows over
it in registers (eight rows a loop step, ``bd`` = 512 channels: the state
and ``A`` are 8 registers each) and puts it back. ``u, dt, y`` are read and
written once; ``B, C`` once a chunk (their block index does not change along
the inner axis, so the pipeline does not fetch them again); ``A`` once a
chunk. The materialised ``exp(dt A)`` and ``dt u B`` of a plain XLA scan,
``[rows, D, N]`` float32 each, never exist. A row that is no token has ``dt
= 0`` (the caller's mask): ``exp(0) = 1`` and ``0 * u * B = 0`` leave the
state as it was. ``skip`` (a traced flag) makes the whole call hand the
initial state back: the layer loop calls it in every layer, and a layer of
another kind skips.

``fleetx_ssm_step`` (one row a lane: the decode tick): takes the WHOLE
lane-resident leaf ``[layers, lanes, N, D]`` and the layer's index, aliases
it to its output, and reads and writes only that layer's blocks: the leaf
is updated in place (2.2 GB at the served size: a slice taken out, updated
and put back would move it three times). Grid ``(lanes / 8, blocks of
channels)``. A lane that is not decoding has ``dt = 0`` and keeps its
state; ``fresh`` lanes start from zero. Under ``skip`` (a layer of another
kind, which has no ``h``) the grid is ONE row of steps on one block, copied
through once: the layer's 168 MB at the served size stay where they are.

Off the TPU both fall back to plain ``jax.numpy`` (a ``lax.scan`` over the
rows), which is also what the interpret-mode tests compare the kernels
with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu.ops.pallas.flash_attention import _interpret, kernels_enabled

__all__ = ["SCAN_KERNEL_NAME", "STEP_KERNEL_NAME", "selective_scan",
           "selective_scan_plain", "selective_step", "selective_step_plain"]

SCAN_KERNEL_NAME = "fleetx_ssm_scan"
STEP_KERNEL_NAME = "fleetx_ssm_step"
_LANES = 128       # channels of one register
_ROWS = 8          # rows of one loop step: one float32 tile of u, dt, y
_VMEM_LIMIT = 48 << 20


def _divisor(n: int, options) -> int:
    return next((o for o in options if n % o == 0), 0)


def _along_lanes(tile, copies: int):
    """``[N, 128]`` repeated along the lanes to ``[N, 128 * copies]``."""
    return tile if copies == 1 else jnp.concatenate([tile] * copies, axis=1)


def _flag(skip):
    """``skip`` (None, or a traced bool) as the ``[1]`` int32 a kernel is
    handed ahead of its grid."""
    return jnp.reshape(False if skip is None else skip, (1,)).astype(jnp.int32)


def _advance(h, a, dt, u, b, c):
    """One row over one block of channels: ``h, a`` ``[N, bd]``, ``dt, u``
    ``[1, bd]``, ``b, c`` ``[N, bd]``; the new state and ``y`` ``[1, bd]``."""
    h = jnp.exp(dt * a) * h + (dt * u) * b
    return h, jnp.sum(h * c, axis=0, keepdims=True)


# ------------------------------------------------------------------ plain

def selective_scan_plain(u, dt, a, b, c, h0):
    """The scan in ``jax.numpy``: ``u, dt`` ``[batch, rows, D]``, ``b, c``
    ``[batch, rows, N]``, ``a`` ``[N, D]``, ``h0`` ``[batch, N, D]``;
    returns ``y`` ``[batch, rows, D]`` and the last state."""
    def step(h, row):
        dt_t, u_t, b_t, c_t = row                      # [batch, D | N]
        h = (jnp.exp(dt_t[:, None, :] * a) * h
             + (dt_t * u_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    rows = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, u, b, c))
    h, y = jax.lax.scan(step, h0, rows)
    return jnp.moveaxis(y, 0, 1), h


def selective_step_plain(state, layer, u, dt, a, b, c, fresh):
    """One row a lane over the leaf ``state`` ``[layers, lanes, N, D]`` at
    ``layer``: ``u, dt`` ``[lanes, D]``, ``b, c`` ``[lanes, N]``, ``fresh``
    ``[lanes]`` bool (start from zero). Returns ``y`` and the leaf."""
    h = jnp.where(fresh[:, None, None], 0.0, state[layer])
    y, h = selective_scan_plain(u[:, None], dt[:, None], a, b[:, None],
                                c[:, None], h)
    return y[:, 0], state.at[layer].set(h)


# ------------------------------------------------------------------- scan

def _scan_kernel(skip_ref, u_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref,
                 y_ref, h_ref, *, rows: int, copies: int):
    chunk, block = pl.program_id(1), pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        h_ref[0, block] = h0_ref[0, block]

    @pl.when(skip_ref[0] == 0)
    def _():
        a = a_ref[...]

        def group(g, h):
            at = pl.ds(pl.multiple_of(g * _ROWS, _ROWS), _ROWS)
            dt, u = dt_ref[0, at, :], u_ref[0, at, :]          # [8, bd]
            b, c = b_ref[0, at], c_ref[0, at]                  # [8, N, 128]
            ys = []
            for r in range(_ROWS):
                h, y = _advance(h, a, dt[r:r + 1], u[r:r + 1],
                                _along_lanes(b[r], copies),
                                _along_lanes(c[r], copies))
                ys.append(y)
            y_ref[0, at, :] = jnp.concatenate(ys, axis=0)
            return h

        h_ref[0, block] = jax.lax.fori_loop(0, rows // _ROWS, group,
                                            h_ref[0, block])


def _scan_tiles(rows: int, width: int):
    """``(rows of a chunk, channels of a block)`` the kernel takes, or None
    where the shapes do not tile (the caller then takes the plain scan)."""
    chunk = _divisor(rows, (256, 128, 64, 32, 16, 8))
    block = _divisor(width, (512, 256, 128))
    return (chunk, block) if chunk and block else None


def selective_scan(u, dt, a, b, c, h0, *, skip=None, kernel: bool = True):
    """``y`` ``[batch, rows, D]`` and the last state ``[batch, N, D]`` of
    the scan from ``h0`` (shapes as :func:`selective_scan_plain`); ``skip``
    (a traced bool): hand ``h0`` back and leave ``y`` undefined. The kernel
    where ``kernel`` and the shapes allow, else the plain scan."""
    batch, rows, width = u.shape
    n = a.shape[0]
    tiles = _scan_tiles(rows, width) if kernel and kernels_enabled() else None
    if tiles is None:
        y, h = selective_scan_plain(u, dt, a, b, c, h0)
        return (y, h) if skip is None else (y, jnp.where(skip, h0, h))
    chunk, bd = tiles
    blocks = width // bd
    wide = [jnp.broadcast_to(t[..., None], (batch, rows, n, _LANES))
            for t in (b, c)]
    # [batch, N, D] <-> [batch, blocks, N, bd]: a block of channels is then
    # an index of a leading axis, not a dynamic slice of the lanes
    h0 = h0.reshape(batch, n, blocks, bd).transpose(0, 2, 1, 3)
    row_block = pl.BlockSpec((1, chunk, bd), lambda i, ch, j, s: (i, ch, j))
    bc_block = pl.BlockSpec((1, chunk, n, _LANES),
                            lambda i, ch, j, s: (i, ch, 0, 0))
    h_block = pl.BlockSpec((1, blocks, n, bd), lambda i, ch, j, s: (i, 0, 0, 0))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, rows=chunk, copies=bd // _LANES),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, rows // chunk, blocks),
            in_specs=[row_block, row_block, bc_block, bc_block,
                      pl.BlockSpec((n, bd), lambda i, ch, j, s: (0, j)),
                      h_block],
            out_specs=[row_block, h_block]),
        out_shape=[jax.ShapeDtypeStruct((batch, rows, width), jnp.float32),
                   jax.ShapeDtypeStruct(h0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=SCAN_KERNEL_NAME,
    )(_flag(skip), u, dt, *wide, a, h0)
    return y, h.transpose(0, 2, 1, 3).reshape(batch, n, width)


# ------------------------------------------------------------------- step

def _step_kernel(layer_ref, skip_ref, fresh_ref, u_ref, dt_ref, b_ref, c_ref,
                 a_ref, h_ref, y_ref, out_ref, *, lanes: int, copies: int):
    del layer_ref  # read by the index maps
    first = pl.program_id(0) * lanes

    @pl.when(skip_ref[0] == 0)
    def _():
        a = a_ref[...]
        ys = []
        for r in range(lanes):
            h = jnp.where(fresh_ref[first + r] != 0, 0.0, h_ref[r])
            h, y = _advance(h, a, dt_ref[r:r + 1, :], u_ref[r:r + 1, :],
                            _along_lanes(b_ref[r], copies),
                            _along_lanes(c_ref[r], copies))
            out_ref[r] = h
            ys.append(y)
        y_ref[...] = jnp.concatenate(ys, axis=0)

    # (a skipped call's steps all map to one block: copied through once)
    @pl.when((skip_ref[0] != 0) & (pl.program_id(1) == 0))
    def _():
        out_ref[...] = h_ref[...]


def _step_tiles(lanes: int, width: int):
    group = _ROWS if lanes % _ROWS == 0 else lanes if lanes < _ROWS else 0
    block = _divisor(width, (1280, 1024, 512, 256, 128))
    return (group, block) if group and block else None


def selective_step(state, layer, u, dt, a, b, c, fresh, *, skip=None,
                   kernel: bool = True):
    """``y`` ``[lanes, D]`` and the leaf ``state`` ``[layers, lanes, N, D]``
    with ``layer``'s states advanced by one row a lane (shapes as
    :func:`selective_step_plain`); under ``skip`` (a traced bool) the leaf
    as it was and ``y`` undefined. The kernel updates the leaf in place
    (module docstring); a caller that donates the leaf holds no copy."""
    lanes, width = u.shape
    n = a.shape[0]
    tiles = _step_tiles(lanes, width) if kernel and kernels_enabled() else None
    if tiles is None:
        y, new = selective_step_plain(state, layer, u, dt, a, b, c, fresh)
        return (y, new) if skip is None else (y, jnp.where(skip, state, new))
    group, bd = tiles
    skip = _flag(skip)
    wide = [jnp.broadcast_to(t[..., None], (lanes, n, _LANES)) for t in (b, c)]

    def block(j, sk):
        """The block of channels of step ``j``: a skipped call's all map to
        the first, which is copied through once."""
        return j * (1 - sk[0])

    row_block = pl.BlockSpec((group, bd),
                             lambda i, j, li, sk, fr: (i, block(j, sk)))
    bc_block = pl.BlockSpec((group, n, _LANES),
                            lambda i, j, li, sk, fr: (i, 0, 0))
    h_block = pl.BlockSpec((None, group, n, bd),
                           lambda i, j, li, sk, fr: (li[0], i, 0, block(j, sk)))
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, lanes=group, copies=bd // _LANES),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # (a skipped call: one row of steps, not one a group of lanes)
            grid=(jnp.where(skip[0] != 0, 1, lanes // group), width // bd),
            in_specs=[row_block, row_block, bc_block, bc_block,
                      pl.BlockSpec((n, bd),
                                   lambda i, j, li, sk, fr: (0, block(j, sk))),
                      h_block],
            out_specs=[row_block, h_block]),
        out_shape=[jax.ShapeDtypeStruct((lanes, width), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the three prefetched scalars): the leaf itself
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=STEP_KERNEL_NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), skip,
      fresh.astype(jnp.int32), u, dt, *wide, a, state)
    return y, state
