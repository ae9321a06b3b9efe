"""The gated delta rule of a KDA layer (Kimi Delta Attention: the linear
attention layers of ``models/gpt/mixed_stack.py`` ``KDAMixer``), per head
with a state ``S`` ``[d_k, d_v]`` in float32:

    S   = diag(exp(g_t)) S_{t-1}                  g_t [d_k] <= 0: a log decay
    S_t = S + beta_t k_t (v_t - S^T k_t)^T        beta_t in (0, 2)
    o_t = S_t^T q_t

``q, k, v, g`` ``[.., heads, d]`` float32 and ``beta`` ``[.., heads]``; the
normalisation of ``q`` and ``k``, the filter before them and the gate after
``o`` are the caller's.

**Layout.** The state is held ``[d_k, heads, d_v]``: the values of one key
channel, of EIGHT HEADS along the sublanes of a vector register and ``d_v``
along its lanes. A row's ``q, k, v, g`` of those eight heads are then whole
``[8, 128]`` tiles, ``S^T k`` and ``S^T q`` are sums of 128 tiles each times
one key channel's value broadcast along the lanes (no reduction across lanes
or sublanes), and the rank-one correction writes the same tiles. ``[heads,
d_k, d_v]`` would make every row's step a transpose.

**Form: the row recurrence, the state resident in VMEM.** Both kernels run
the three lines above row by row, every quantity float32, the decay applied
as ``exp(g_t)`` of ONE row: exact for any decay (nothing is ever divided by
a cumulated ``exp``, which a chunkwise form with ``exp(-cumsum g)`` does and
float32 cannot hold past a summed log decay of -88), and the same arithmetic
as the float32 reference's scan over tokens. The chunkwise form (a
triangular solve a block of rows, the products on the MXU) does about a
sixth of the vector work: PERF.md section 7 queues it.

**Two kernels.**

``fleetx_kda_chunk`` (a call of more than one row of ONE lane: a prefill or
a chunk of one): grid ``(groups of 8 heads, blocks of rows)``, the rows
sequential. The state is the kernel's resident output block ``[d_k, 8,
d_v]`` (512 KB): read from the lane's state at the first block of rows,
advanced in place, written back once a call. A row that is no token has
``g = 0`` and ``beta = 0`` (the caller's mask) and leaves the state as it
was. ``skip`` (a traced flag) hands the initial state back: the layer loop
calls the kernel in every layer, and a layer of another kind skips.

``fleetx_kda_step`` (one row a lane: the decode tick): takes the WHOLE
lane-resident leaf ``[layers, lanes, d_k, heads, d_v]`` and the layer's
index, aliases it to its output, and reads and writes only that layer's
blocks (``ssm_scan.py``'s step in form). Grid ``(lanes, groups of 8
heads)``. A lane that is not decoding has ``g = beta = 0`` and keeps its
state; ``fresh`` lanes start from zero; under ``skip`` every grid step maps
to ONE block, which is copied through, so a layer of another kind moves 1 MB
and not the layer's whole state.

Off the TPU both fall back to plain ``jax.numpy`` (a ``lax.scan`` over the
rows), which is also what the interpret-mode tests compare the kernels with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu.ops.pallas.flash_attention import _interpret, kernels_enabled
from fleetx_tpu.ops.pallas.ssm_scan import _divisor

__all__ = ["CHUNK_KERNEL_NAME", "STEP_KERNEL_NAME", "kda_chunk",
           "kda_chunk_plain", "kda_step", "kda_step_plain"]

CHUNK_KERNEL_NAME = "fleetx_kda_chunk"
STEP_KERNEL_NAME = "fleetx_kda_step"
_HEADS = 8         # heads of one register: its sublanes
_VMEM_LIMIT = 48 << 20


# ------------------------------------------------------------------ plain

def _advance_plain(s, q, k, v, g, beta):
    """One row over every head: ``s`` ``[d_k, heads, d_v]``, ``q, k, g``
    ``[heads, d_k]``, ``v`` ``[heads, d_v]``, ``beta`` ``[heads]``."""
    s = s * jnp.exp(g).T[:, :, None]
    u = beta[:, None] * (v - jnp.einsum("khv,hk->hv", s, k))
    s = s + k.T[:, :, None] * u[None]
    return s, jnp.einsum("khv,hk->hv", s, q)


def kda_chunk_plain(q, k, v, g, beta, s0):
    """The recurrence in ``jax.numpy``: ``q, k, g`` ``[rows, heads, d_k]``,
    ``v`` ``[rows, heads, d_v]``, ``beta`` ``[rows, heads]``, ``s0`` ``[d_k,
    heads, d_v]``; returns ``o`` ``[rows, heads, d_v]`` and the last state."""
    s, o = jax.lax.scan(lambda s, row: _advance_plain(s, *row), s0,
                        (q, k, v, g, beta))
    return o, s


def kda_step_plain(state, layer, q, k, v, g, beta, fresh):
    """One row a lane over the leaf ``state`` ``[layers, lanes, d_k, heads,
    d_v]`` at ``layer``: ``q, k, v, g`` ``[lanes, heads, d]``, ``beta``
    ``[lanes, heads]``, ``fresh`` ``[lanes]`` bool (start from zero).
    Returns ``o`` ``[lanes, heads, d_v]`` and the leaf."""
    s = jnp.where(fresh[:, None, None, None], 0.0, state[layer])
    s, o = jax.vmap(_advance_plain)(s, q, k, v, g, beta)
    return o, state.at[layer].set(s)


# ----------------------------------------------------------------- kernels

def _along_lanes(tile, i: int):
    """Column ``i`` of an ``[8, 128]`` tile along all its lanes."""
    return jnp.broadcast_to(tile[:, i:i + 1], tile.shape)


def _advance(s_ref, q, k, v, g, beta):
    """One row of eight heads over the state block ``s_ref`` ``[d_k, 8,
    d_v]`` in place: ``q, k, g`` ``[8, d_k]``, ``v, beta`` ``[8, d_v]``
    (``beta`` along the lanes); returns ``o`` ``[8, d_v]``. Two passes over
    the 128 key channels: the decay with both products, then the
    correction."""
    decay = jnp.exp(g)
    sk = sq = jnp.zeros(v.shape, jnp.float32)
    for i in range(s_ref.shape[0]):
        s = s_ref[i] * _along_lanes(decay, i)
        s_ref[i] = s
        sk = sk + s * _along_lanes(k, i)
        sq = sq + s * _along_lanes(q, i)
    u = beta * (v - sk)
    for i in range(s_ref.shape[0]):
        s_ref[i] = s_ref[i] + _along_lanes(k, i) * u
    # S_t^T q = S^T q + u (k . q)
    return sq + u * jnp.sum(k * q, axis=1, keepdims=True)


def _chunk_kernel(skip_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
                  o_ref, s_ref, *, rows: int):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    @pl.when(skip_ref[0] == 0)
    def _():
        def row(t, carry):
            o_ref[t] = _advance(s_ref, q_ref[t], k_ref[t], v_ref[t],
                                g_ref[t], beta_ref[t])
            return carry

        jax.lax.fori_loop(0, rows, row, 0)


def _group(heads: int) -> int:
    """Heads of one grid step: a register's eight, or all of fewer (a toy
    size: the block is then the whole axis); 0 where neither divides."""
    return _HEADS if heads % _HEADS == 0 else heads if heads < _HEADS else 0


def _kernel_group(kernel: bool, heads: int, d_k: int, d_v: int) -> int:
    """:func:`_group` where the kernels run at all (``q, k, g`` are d_k wide
    and ``v, beta, o`` d_v: one block shape where the two are equal, which
    every configuration's are), else 0: the plain twin."""
    return _group(heads) if (kernel and kernels_enabled()
                             and d_k == d_v) else 0


def _flag(skip):
    """``skip`` (None: False) as the one int32 a kernel prefetches."""
    return jnp.reshape(False if skip is None else skip, (1,)).astype(jnp.int32)


def _wide(beta, d_v: int):
    """``beta`` ``[.., heads]`` along the lanes of its heads' tiles."""
    return jnp.broadcast_to(beta[..., None], beta.shape + (d_v,))


def kda_chunk(q, k, v, g, beta, s0, *, skip=None, kernel: bool = True):
    """``o`` ``[rows, heads, d_v]`` and the last state ``[d_k, heads, d_v]``
    of ONE lane's rows from ``s0`` (shapes as :func:`kda_chunk_plain`);
    ``skip`` (a traced bool): hand ``s0`` back and leave ``o`` undefined.
    The kernel where ``kernel`` and the shapes allow, else the plain scan."""
    rows, heads, d_k = q.shape
    d_v = v.shape[-1]
    group = _kernel_group(kernel, heads, d_k, d_v)
    block = rows if rows <= 256 else _divisor(rows, (256, 128, 64, 32, 16, 8))
    if not (group and block):
        o, s = kda_chunk_plain(q, k, v, g, beta, s0)
        return (o, s) if skip is None else (o, jnp.where(skip, s0, s))
    row_block = pl.BlockSpec((block, group, d_v), lambda j, r, s: (r, j, 0))
    s_block = pl.BlockSpec((d_k, group, d_v), lambda j, r, s: (0, j, 0))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, rows=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads // group, rows // block),
            in_specs=[row_block] * 5 + [s_block],
            out_specs=[row_block, s_block]),
        out_shape=[jax.ShapeDtypeStruct((rows, heads, d_v), jnp.float32),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=CHUNK_KERNEL_NAME,
    )(_flag(skip), q, k, v, g, _wide(beta, d_v), s0)


def _step_kernel(layer_ref, skip_ref, fresh_ref, q_ref, k_ref, v_ref, g_ref,
                 beta_ref, s_ref, o_ref, out_ref):
    del layer_ref  # read by the index maps
    lane, group = pl.program_id(0), pl.program_id(1)

    @pl.when(skip_ref[0] == 0)
    def _():
        out_ref[...] = jnp.where(fresh_ref[lane] != 0, 0.0, s_ref[...])
        o_ref[...] = _advance(out_ref, q_ref[...], k_ref[...], v_ref[...],
                              g_ref[...], beta_ref[...])

    # (a skipped call's steps all map to one block: copied through once)
    @pl.when((skip_ref[0] != 0) & (lane == 0) & (group == 0))
    def _():
        out_ref[...] = s_ref[...]


def kda_step(state, layer, q, k, v, g, beta, fresh, *, skip=None,
             kernel: bool = True):
    """``o`` ``[lanes, heads, d_v]`` and the leaf ``state`` ``[layers,
    lanes, d_k, heads, d_v]`` with ``layer``'s states advanced by one row a
    lane (shapes as :func:`kda_step_plain`); under ``skip`` (a traced bool)
    the leaf as it was and ``o`` undefined. The kernel updates the leaf in
    place (module docstring); a caller that donates the leaf holds no copy."""
    lanes, heads, d_k = q.shape
    d_v = v.shape[-1]
    group = _kernel_group(kernel, heads, d_k, d_v)
    if not group:
        o, new = kda_step_plain(state, layer, q, k, v, g, beta, fresh)
        return (o, new) if skip is None else (o, jnp.where(skip, state, new))

    def row_map(i, j, li, sk, fr):
        live = 1 - sk[0]
        return (i * live, j * live, 0)

    def state_map(i, j, li, sk, fr):
        live = 1 - sk[0]
        return (li[0], i * live, 0, j * live, 0)

    row_block = pl.BlockSpec((None, group, d_v), row_map)
    s_block = pl.BlockSpec((None, None, d_k, group, d_v), state_map)
    o, state = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(lanes, heads // group),
            in_specs=[row_block] * 5 + [s_block],
            out_specs=[row_block, s_block]),
        out_shape=[jax.ShapeDtypeStruct((lanes, heads, d_v), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the three prefetched scalars): the leaf itself
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=STEP_KERNEL_NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), _flag(skip),
      fresh.astype(jnp.int32), q, k, v, g, _wide(beta, d_v), state)
    return o, state
