"""The tensor-parallel products carry their own collectives.

Between two tensor-parallel blocks the activations live sharded on the
sequence over ``mp`` (``parallel/sharding.py`` ``ACT_AXES["residual"]``).
A column-parallel product (``qkv_proj``, ``up_proj``) then needs every row
of its input and a row-parallel one (``out_proj``, ``down_proj``) hands
each device the sums of its own rows: an all-gather before the one, a
reduce-scatter after the other, each on the critical path by data
dependence. Here the product is cut into as many steps as ``mp`` has
devices. A column product multiplies the rows it holds while a
collective-permute brings the neighbour's; a row product computes the
neighbour's rows first and sends those partial sums while it computes its
own. The sums are the same, in another order.

One ``shard_map`` over the whole mesh a product; ``dot_general`` of
:func:`for_kernel` is what ``nn.DenseGeneral`` calls in place of
``lax.dot_general``, so the parameters keep their names, shapes and
partitioning. Each product's gradient is the other product (a column
product's ``dX`` is a row product, and the other way round).
"""

from __future__ import annotations

import functools
import math
import re
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from fleetx_tpu.parallel.mesh import ambient_mesh, shard_map
from fleetx_tpu.parallel.sharding import ACT_AXES, _fit_spec, _spec_axes

__all__ = ["COLLECTIVE_KINDS", "count_collectives", "for_kernel"]

AXIS = "mp"


def _shift(x, n):
    """Every device's ``x`` to its right-hand neighbour on the ``mp`` ring."""
    return lax.ppermute(x, AXIS, [(j, (j + 1) % n) for j in range(n)])


def _by_device(n, make):
    """``make(d)`` as device ``d`` of the ring needs it, chosen by the
    traced position on the ring: every case is built from STATIC slices and
    concatenations, which fuse into whatever reads the result (a
    ``dynamic_update_slice`` into a buffer of zeros does not: on the chip it
    cost three passes over a product's output, and its transpose as many)."""
    me = lax.axis_index(AXIS)
    return jax.tree.map(lambda *xs: lax.select_n(me, *xs),
                        *[make(d) for d in range(n)])


def _as_they_arrive(d, i):
    """The block of rows device ``d`` holds at step ``i`` of a gathered
    product: its own, then its left-hand neighbours' in turn."""
    return d - i


def _towards_their_owner(d, i):
    """The block device ``d`` works on at step ``i`` of a scattered product:
    the farthest owner's first (``d + 1``'s sums have ``n - 1`` hops to
    make), its own last."""
    return d - 1 - i


def _blocks(x, n, order):
    """The ``n`` blocks of ``x``'s rows, block ``order(d, i)`` (taken mod
    ``n``) as item ``i`` on device ``d``."""
    rows = x.shape[1] // n
    return _by_device(n, lambda d: [
        lax.slice_in_dim(x, k * rows, (k + 1) * rows, axis=1)
        for k in (order(d, i) % n for i in range(n))])


def _weight_grad(xs, dys):
    """``sum_i xs[i]^T dys[i]`` over rows and batch, summed in float32."""
    return sum(jnp.einsum("brk,brn->kn", x, dy,
                          preferred_element_type=jnp.float32)
               for x, dy in zip(xs, dys))


def _gather(x, w, n):
    """``[b, r, K] x [K, N] -> [b, n r, N]``: step ``i`` multiplies the rows
    that came from the device ``i`` to the left while the next ones travel.
    Also the row blocks as they came, for the weight's gradient."""
    xs = [x]
    for _ in range(n - 1):
        xs.append(_shift(xs[-1], n))
    ys = [jnp.einsum("brk,kn->brn", x, w) for x in xs]
    # device d holds block k of the rows as the product of step (d - k) % n
    out = _by_device(n, lambda d: jnp.concatenate(
        [ys[(d - k) % n] for k in range(n)], axis=1))
    return out, xs


def _scatter(x, w, n):
    """``[b, n r, K] x [K, N] -> [b, r, N]`` summed over the ring: the
    partial sums of a block of rows travel towards their owner while the
    next block's are computed; the device's own rows come last."""
    acc = None
    for block in _blocks(x, n, _towards_their_owner):
        y = jnp.einsum("brk,kn->brn", block, w)
        if acc is None:
            acc = y
        else:
            # keep the sum out of the product's own fusion: fused, the
            # product would wait for the sums still on the wire
            acc = _shift(acc, n) + lax.optimization_barrier(y)
    return acc


# Each product's backward pass is the other product (a gathered product's
# input gradient is scattered, a scattered product's gathered), written out
# so that no gradient is assembled by padding blocks with zeros.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather_product(x, w, n):
    return _gather(x, w, n)[0]


def _gather_product_fwd(x, w, n):
    out, xs = _gather(x, w, n)
    return out, (xs, w)


def _gather_product_bwd(n, res, dy):
    xs, w = res
    dw = _weight_grad(xs, _blocks(dy, n, _as_they_arrive))
    return _scatter_product(dy, w.T, n), dw.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _scatter_product(x, w, n):
    return _scatter(x, w, n)


def _scatter_product_fwd(x, w, n):
    return _scatter(x, w, n), (x, w)


def _scatter_product_bwd(n, res, dz):
    x, w = res
    dx, dzs = _gather(dz, w.T, n)
    dw = _weight_grad(_blocks(x, n, _as_they_arrive), dzs)
    return dx, dw.astype(w.dtype)


_gather_product.defvjp(_gather_product_fwd, _gather_product_bwd)
_scatter_product.defvjp(_scatter_product_fwd, _scatter_product_bwd)


def _as_matrices(product, n, contracted, x, w):
    """``product`` on ``x`` as ``[b, rows, K]`` and ``w`` as ``[K, N]``
    (``nn.DenseGeneral`` contracts ``x``'s last axes with ``w``'s first)."""
    lead = x.shape[:x.ndim - contracted]
    y = product(x.reshape(lead[0], lead[1], -1),
                w.reshape(math.prod(w.shape[:contracted]), -1), n)
    return y.reshape(y.shape[:2] + lead[2:] + w.shape[contracted:])


COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute")
_COLLECTIVE_OPCODE = re.compile(
    r" (%s)(?:-start)?\(" % "|".join(COLLECTIVE_KINDS))


def count_collectives(hlo_text: str) -> Dict[str, int]:
    """Instructions of each collective kind in a compiled program's text:
    the opcode after the result's shape, so neither an instruction's NAME
    nor the ``-done`` half of an asynchronous pair counts, and an
    asynchronous ``-start`` counts as its kind. A computation is counted
    where it is written, so a scanned layer loop's body counts once. (The
    TPU writes a reduce-scatter as a fusion NAMED ``all-reduce-scatter``
    around an all-reduce: it counts as the all-reduce it holds.) What the
    gauge ``fleetx_train_step_collectives`` reports (core/engine.py): with
    the products below in the step, the layer loops hold collective-permutes
    where they held activation-sized all-reduces."""
    counts = dict.fromkeys(COLLECTIVE_KINDS, 0)
    for m in _COLLECTIVE_OPCODE.finditer(hlo_text):
        counts[m.group(1)] += 1
    return counts


def for_kernel(logical_axes: Sequence[Optional[str]]):
    """A ``dot_general`` for ``nn.DenseGeneral`` whose kernel carries
    ``logical_axes``, or None where ``lax.dot_general`` is the right one: no
    multi-device ``mp`` axis on the ambient mesh, or a kernel no axis of
    which is on ``mp``.

    Where the rows between the blocks are sharded over exactly ``mp`` and
    no pipeline stage is in the way (they run under ``nn.vmap``), it is the
    product that carries its collective; a call whose rows do not divide (a
    decode tick) takes the plain product. Elsewhere (``Model.
    sequence_parallel`` off, context parallel, ``pp > 1``) it is the plain
    product with a column-parallel product's OUTPUT laid out by name: every
    row of the device's ``cp`` share, a share of the kernel's axis. Unnamed,
    the partitioner gathers the weights under sequence-sharded rows, the
    smaller operand at 16,384 rows a replica, and re-lays attention out
    with an all-to-all (v5e compile, PR 48)."""
    mesh = ambient_mesh()
    if mesh is None or mesh.shape.get(AXIS, 1) < 2:
        return None
    kernel_spec = list(nn.logical_to_mesh_axes(tuple(logical_axes)))
    if kernel_spec.count(AXIS) != 1:
        return None
    sharded = kernel_spec.index(AXIS)
    data, rows_on, _ = nn.logical_to_mesh_axes(ACT_AXES["residual"])
    inner_rows = nn.logical_to_mesh_axes(ACT_AXES["whole"])[1]
    n = int(mesh.shape[AXIS])
    replicas = math.prod(int(mesh.shape[a]) for a in _spec_axes(data))
    carried = rows_on == AXIS and mesh.shape.get("pp", 1) == 1

    def dot_general(x, w, dims, precision=None):
        (x_contract, w_contract), (x_batch, _) = dims
        k = len(x_contract)
        plain = functools.partial(lax.dot_general, dimension_numbers=dims,
                                  precision=precision)
        if (x_batch or precision is not None or x.ndim - k < 2
                or tuple(x_contract) != tuple(range(x.ndim - k, x.ndim))
                or tuple(w_contract) != tuple(range(k))):
            return plain(x, w)
        column = sharded >= k  # else row-parallel: the kernel's contracted axis
        out_spec = [data] + [None] * (x.ndim - k - 1 + w.ndim - k)
        if not carried:
            if not column:
                return plain(x, w)
            out_spec[1], out_spec[x.ndim - 2 * k + sharded] = inner_rows, AXIS
            return jax.lax.with_sharding_constraint(
                plain(x, w), NamedSharding(mesh, _fit_spec(
                    P(*out_spec), x.shape[:x.ndim - k] + w.shape[k:], mesh)))
        if x.shape[1] % n or x.shape[0] % replicas:
            return plain(x, w)
        x_spec = [data] + [None] * (x.ndim - 1)
        w_spec = [None] * w.ndim
        w_spec[sharded] = AXIS
        if column:
            # x holds a share of the rows, the result a share of the
            # kernel's axis
            x_spec[1] = out_spec[x.ndim - 2 * k + sharded] = AXIS
        else:
            # x holds a share of the contracted axis, and each device ends
            # with the sums of its own rows
            x_spec[x.ndim - k + sharded] = out_spec[1] = AXIS
        product = _gather_product if column else _scatter_product
        return shard_map(
            functools.partial(_as_matrices, product, n, k),
            mesh=mesh, in_specs=(P(*x_spec), P(*w_spec)),
            out_specs=P(*out_spec), check_vma=False)(x, w)

    return dot_general
