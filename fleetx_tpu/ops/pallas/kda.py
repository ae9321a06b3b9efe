"""The gated delta rule of a KDA layer (Kimi Delta Attention: the linear
attention layers of ``models/gpt/mixed_stack.py`` ``KDAMixer``), per head
with a state ``S`` ``[d_k, d_v]`` in float32:

    S   = diag(exp(g_t)) S_{t-1}                  g_t [d_k] <= 0: a log decay
    S_t = S + beta_t k_t (v_t - S^T k_t)^T        beta_t in (0, 2)
    o_t = S_t^T q_t

``q, k, v, g`` ``[.., heads, d]`` float32 and ``beta`` ``[.., heads]``; the
normalisation of ``q`` and ``k``, the filter before them and the gate after
``o`` are the caller's.

**Home layout.** The lane-resident state is held ``[d_k, heads, d_v]``: the
values of one key channel, of EIGHT HEADS along the sublanes of a vector
register and ``d_v`` along its lanes. For the one-row step a row's ``q, k,
v, g`` of those eight heads are then whole ``[8, 128]`` tiles, ``S^T k`` and
``S^T q`` are sums of 128 tiles each times one key channel's value broadcast
along the lanes (no reduction across lanes or sublanes), and the rank-one
correction writes the same tiles.

**Two kernels, two forms.**

``fleetx_kda_chunk`` (a call of more than one row of ONE lane: a prefill or
a chunk of one) takes the rule IN BLOCKS OF ROWS, the products on the MXU.
With ``D(j, t]`` the decay of the rows after j up to and with t (per key
channel: the product of their ``exp(g_i)``; ``D(j, j] = 1``), row 0 the one
before the block's first and ``S_0`` the state the block starts from:

    A[t,j] = sum_c k_t[c] k_j[c] D(j, t][c]               j <  t
    B[t,j] = sum_c q_t[c] k_j[c] D(j, t][c]               j <= t
    (I + diag(beta) A) U = diag(beta) (V - (D(0, t] * K) S_0)
    O   = (D(0, t] * Q) S_0 + B U
    S_C = diag(D(0, C]) S_0 + (D(j, C] * K)^T U

which is the three lines above unrolled over the block's rows (``U``'s row t
is ``beta_t (v_t - S^T k_t)``; in logs, ``D(j, t] = exp(G_t - G_j)`` with
``G`` the running sum of ``g``). THE ONLY EXPONENT TAKEN IS ``g_t`` OF ONE
ROW, which is never positive, and every decay between two rows is a product
of such ``exp(g_i)``, each at most one: nothing is ever divided by, or
multiplied with the inverse of, a cumulated ``exp`` (``exp(-G)`` passes
float32 at a summed log decay of -88; the source bounds the decay by
nothing), so the form is exact for any decay, as the row form is. Products
and not ``exp`` of running sums for a second reason, read on the chip at PR
57: the TPU's ``exp`` is good to 1.2e-6 of its value (4.5e-6 at worst, XLA's
and Mosaic's alike), so ``exp(g_1 + .. + g_n)`` and ``exp(g_1) .. exp(g_n)``
differ by some ``sqrt(n)`` times that, and a reference that decays row by
row (``kda_chunk_plain``; the benchmark's) read 1.4e-5 to 3.2e-5 off a
kernel that took the exponent of sums, 2.6e-7 off this one.

The decay is per key CHANNEL, so ``A`` and ``B`` are no product of two
matrices; they are taken at two levels. Inside a diagonal sub-block of 16
rows pair by pair on the vector unit, a row t at a time: a ``[16, d_k]``
tile holds ``D(j, t]`` for the sub-block's rows j before t and takes row
t's decay at each step; the row's sums over the channels come out along the
sublanes (j), so a sub-block is gathered TRANSPOSED and the MXU turns the
block's gathering over (the identity times its transpose). A sub-block
BELOW the diagonal is one product of ``D(ref, t] * k_t`` with ``D(j, ref] *
k_j``, ``ref`` the row before t's sub-block: both factors at most one. The
running products inside a sub-block are log steps of register rolls. The
unit lower triangular system is solved by substitution a column at a time
on the vector unit (once row j of ``U`` is final every later row loses its
share of it: the recurrence's own arithmetic, no inverse is formed). The
products with 128 channels (``K S_0`` and ``Q S_0`` as one, the sub-blocks
below the diagonal, ``B U``, ``K^T U``) run on the MXU with float32
operands at ``Precision.HIGHEST``, Mosaic's float32 contraction (7e-8 of a
product): its default for float32 operands is ONE bfloat16 pass (3e-3 of
the output, read on the chip at PR 57).

Grid ``(heads, blocks of rows)``, the blocks sequential: a head a grid step,
its 128 columns of the projections' own ``[rows, heads x d]`` (no relayout
of the operands; ``beta`` ``[rows, heads]`` whole, the head's column picked
inside) and its state TRANSPOSED ``[d_v, d_k]``, so that the block's decay
``D(0, C]`` (one value a key channel) runs along the lanes; the caller's
``[d_k, heads, d_v]`` is transposed to ``[heads, d_v, d_k]`` and back by
XLA at the call's ends (4 MB each way). The state is the kernel's resident
output block (64 KB): read at the first block of rows, advanced in place,
written back once a head. The block is 64 rows (the largest of 64, 32, 16,
8 that divides the call's rows; on the chip 128 rows read 1.31 ms a layer
and chunk of 512 rows, 64 rows 1.21, 32 rows 1.59, and sub-blocks of 8
1.24), chosen from the shapes alone. A row that is no token has ``g = 0``
and ``beta = 0`` (the caller's mask): its ``U`` is zero and its decay one,
so it leaves the state as it was. ``skip`` (a traced flag) hands the initial
state back: the layer loop calls the kernel in every layer, and a layer of
another kind skips.

``fleetx_kda_step`` (one row a lane: the decode tick) runs the three lines
as they stand, every quantity float32, the decay applied as ``exp(g_t)`` of
ONE row. It takes the WHOLE lane-resident leaf ``[layers, lanes, d_k, heads,
d_v]`` and the layer's index, aliases it to its output, and reads and writes
only that layer's blocks (``ssm_scan.py``'s step in form). Grid ``(lanes,
groups of 8 heads)``. A lane that is not decoding has ``g = beta = 0`` and
keeps its state; ``fresh`` lanes start from zero; under ``skip`` every grid
step maps to ONE block, which is copied through, so a layer of another kind
moves 1 MB and not the layer's whole state.

Off the TPU, and for a call whose rows no block divides, both fall back to
plain ``jax.numpy`` (a ``lax.scan`` over the rows: the three lines as they
stand), which is also what the interpret-mode tests compare the kernels with.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu.ops.pallas.flash_attention import (
    _NN, _NT, _TN, _interpret, kernels_enabled)
from fleetx_tpu.ops.pallas.ssm_scan import _divisor

__all__ = ["CHUNK_KERNEL_NAME", "STEP_KERNEL_NAME", "kda_chunk",
           "kda_chunk_plain", "kda_step", "kda_step_plain"]

CHUNK_KERNEL_NAME = "fleetx_kda_chunk"
STEP_KERNEL_NAME = "fleetx_kda_step"
_HEADS = 8         # heads of one register: its sublanes
_SUBLANES = 8      # rows of one float32 register
_BLOCKS = (64, 32, 16, 8)   # rows of a block
_SUB = 16          # rows of a sub-block: its pairs are taken one by one
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


def _dot(a, b, dims):
    """The float32 product of float32 operands, exactly: ``HIGHEST`` is
    Mosaic's float32 contraction (7e-8 of a ``[64, 128] x [128, 128]``
    product on the chip, PR 57); its default for float32 operands is ONE
    bfloat16 pass (3e-3 of the rule's output)."""
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _chunk_kernel(skip_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
                  o_ref, s_ref, *, sub: int):
    """One head's block of rows over its state, held TRANSPOSED ``[d_v,
    d_k]`` (module docstring): ``q, k, g`` ``[rows, d_k]``, ``v, o``
    ``[rows, d_v]``, ``beta`` ``[rows, heads]``, sub-blocks of ``sub`` rows."""
    head = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    @pl.when(skip_ref[0] == 0)
    def _():
        # the head's column of beta [rows, heads], along the sublanes
        beta = beta_ref[...]
        column = jax.lax.broadcasted_iota(jnp.int32, beta.shape, 1)
        beta = jnp.sum(jnp.where(column == head, beta, 0.0), axis=1,
                       keepdims=True)
        o_ref[...], s_ref[...] = _advance_block(
            q_ref[...], k_ref[...], v_ref[...], jnp.exp(g_ref[...]), beta,
            s_ref[...], sub)


def _pieces(x, size: int):
    """``x`` ``[rows, .]`` in pieces of ``size`` rows."""
    return [x[i:i + size] for i in range(0, x.shape[0], size)]


def _running_products(decay, sub: int):
    """Products of ``decay``'s rows inside each sub-block of ``sub`` rows
    (one register or two): ``lead[t]`` over the rows up to and with t,
    ``rest[t]`` over the rows after t: log steps inside a register, then a
    register's whole product onto the next."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, 1), 0)
    lead = back = _pieces(decay, _SUBLANES)
    for step in (1, 2, 4):
        lead = [x * jnp.where(row >= step, pltpu.roll(x, step, 0), 1.0)
                for x in lead]
        back = [x * jnp.where(row < _SUBLANES - step,
                              pltpu.roll(x, _SUBLANES - step, 0), 1.0)
                for x in back]
    registers = sub // _SUBLANES     # of a sub-block
    if registers == 2:
        lead = [x if t % 2 == 0 else x * lead[t - 1][_SUBLANES - 1:]
                for t, x in enumerate(lead)]
        back = [x if t % 2 else x * back[t + 1][:1]
                for t, x in enumerate(back)]
    # rest[t] = back[t + 1]: a row up, the next register's first row in
    rest = [jnp.where(row < _SUBLANES - 1, pltpu.roll(x, _SUBLANES - 1, 0),
                      back[t + 1][:1] if (t + 1) % registers else 1.0)
            for t, x in enumerate(back)]
    return jnp.concatenate(lead), jnp.concatenate(rest)


def _advance_block(q, k, v, decay, beta, st, sub: int):
    """``o`` ``[rows, d_v]`` and the state ``[d_v, d_k]`` after a block of
    rows from ``st``; ``decay`` = ``exp(g)`` ``[rows, d_k]``, ``beta``
    ``[rows, 1]``. The decay between two rows is a PRODUCT of the rows' own
    ``exp(g_t)`` (module docstring), every factor at most one."""
    rows, n = q.shape[0], q.shape[0] // sub
    lead, rest = (_pieces(x, sub) for x in _running_products(decay, sub))
    whole = [x[sub - 1:] for x in lead]        # a sub-block's whole decay
    qs, ks, decays = (_pieces(x, sub) for x in (q, k, decay))

    # the decay from the block's first row to t, and from j to its last
    before, after = [jnp.ones_like(whole[0])], [jnp.ones_like(whole[0])]
    for i in range(n - 1):
        before.append(before[-1] * whole[i])
        after.insert(0, after[0] * whole[n - 1 - i])
    from_start = jnp.concatenate([x * b for x, b in zip(lead, before)])
    to_end = jnp.concatenate([x * a for x, a in zip(rest, after)])

    # what the rows read of the state the block starts from
    read = _dot(jnp.concatenate([k * from_start, q * from_start]), st, _NT)
    rhs = beta * (v - read[:rows])

    lane = jax.lax.broadcasted_iota(jnp.int32, (sub, 2 * rows), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
    below, inside, back = [], [], None
    for i in range(n):
        if i:
            # the sub-blocks below the diagonal as ONE product: the rows'
            # side decayed from i's first row, the earlier rows' to it
            back = rest[0] if i == 1 else jnp.concatenate(
                [back * whole[i - 1], rest[i - 1]])
            earlier = jnp.concatenate(  # (zeros: a product [., rows] wide)
                [jnp.concatenate(ks[:i]) * back,
                 jnp.zeros(((n - i) * sub,) + k.shape[1:])])
            below.append(_dot(jnp.concatenate(
                [ks[i] * lead[i], qs[i] * lead[i]]), earlier, _NT))
        else:
            below.append(jnp.zeros((2 * sub, rows), jnp.float32))
        # the diagonal sub-block pair by pair, a row t at a time: ``pair``
        # holds the decay from each row j before t up to t. What comes out
        # is row t of A and of B ALONG THE SUBLANES (j), so the sub-block
        # is gathered transposed: A's in lanes [0, rows), B's after them
        pair = jnp.ones_like(ks[i])
        gathered = jnp.zeros((sub, 2 * rows), jnp.float32)
        for t in range(sub):
            if t:
                pair = jnp.where(row < t, pair * decays[i][t:t + 1], 1.0)
            weighed = ks[i] * pair
            a_row = jnp.sum(weighed * ks[i][t:t + 1], axis=1, keepdims=True)
            b_row = jnp.sum(weighed * qs[i][t:t + 1], axis=1, keepdims=True)
            at = i * sub + t
            gathered = jnp.where(lane == at, jnp.where(row < t, a_row, 0.0),
                                 gathered)
            gathered = jnp.where(lane == rows + at,
                                 jnp.where(row <= t, b_row, 0.0), gathered)
        inside.append(gathered)
    # (turned over by the MXU: the identity times its transpose)
    r = jax.lax.broadcasted_iota(jnp.int32, (2 * rows, 2 * rows), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (2 * rows, 2 * rows), 1)
    inside = _dot(jnp.where(r == c, 1.0, 0.0), jnp.concatenate(inside), _NT)
    a = beta * (jnp.concatenate([x[:sub] for x in below]) + inside[:rows])
    b = jnp.concatenate([x[sub:] for x in below]) + inside[rows:]

    # (I + diag(beta) A) U = rhs, a column at a time: once row j is final,
    # every later row loses its share of it
    u, a = _pieces(rhs, _SUBLANES), _pieces(a, _SUBLANES)
    for j in range(rows - 1):
        first, at = divmod(j, _SUBLANES)
        final = u[first][at:at + 1]
        for t in range(first, len(u)):
            u[t] = u[t] - a[t][:, j:j + 1] * final
    u = jnp.concatenate(u)
    o = read[rows:] + _dot(b, u, _NN)
    st = st * (before[-1] * whole[-1]) + _dot(u, k * to_end, _TN)
    return o, st


def _group(heads: int) -> int:
    """Heads of one grid step: a register's eight, or all of fewer (a toy
    size: the block is then the whole axis); 0 where neither divides."""
    return _HEADS if heads % _HEADS == 0 else heads if heads < _HEADS else 0


def _kernel_group(kernel: bool, heads: int, d_k: int, d_v: int) -> int:
    """:func:`_group` where the step kernel runs at all (``q, k, g`` are d_k
    wide and ``v, beta, o`` d_v: one block shape where the two are equal,
    which every configuration's are), else 0: the plain twin."""
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
    block = _divisor(rows, _BLOCKS)
    if not (kernel and kernels_enabled() and d_k == d_v and block):
        o, s = kda_chunk_plain(q, k, v, g, beta, s0)
        return (o, s) if skip is None else (o, jnp.where(skip, s0, s))
    # a head a grid step: its columns of the projections' own [rows, heads
    # x d], and its state [d_v, d_k]
    row_block = pl.BlockSpec((block, d_v), lambda h, r, s: (r, h))
    beta_block = pl.BlockSpec((block, heads), lambda h, r, s: (r, 0))
    s_block = pl.BlockSpec((None, d_v, d_k), lambda h, r, s: (h, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, sub=min(block, _SUB)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, rows // block),
            in_specs=[row_block] * 4 + [beta_block, s_block],
            out_specs=[row_block, s_block]),
        out_shape=[jax.ShapeDtypeStruct((rows, heads * d_v), jnp.float32),
                   jax.ShapeDtypeStruct((heads, d_v, d_k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=CHUNK_KERNEL_NAME,
    )(_flag(skip), *(x.reshape(rows, -1) for x in (q, k, v, g)), beta,
      jnp.transpose(s0, (1, 2, 0)))
    return o.reshape(rows, heads, d_v), jnp.transpose(state, (2, 0, 1))


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
