"""``fleetx_write_rows``: a call's rows into page pools under a flag that the
kernel BRANCHES on (``models/gpt/paged_write.py`` ``write_rows_or_skip`` has
who calls it and why).

The pools (``[pages, page_size, width]`` each) stay in HBM (``pl.ANY``) and
are aliased to the outputs: nothing of a pool moves but the pages written,
and under ``keep`` = 0 nothing moves at all (the whole body sits under one
``pl.when``; what is left is the launch and three or four prefetched
scalars). Two forms, as the scatter it stands in for has:

- :func:`write_a_row_a_lane` (a decode tick): a lane's one row goes into the
  lane's own page. A copy of ONE row into a packed page tile is not a copy
  Mosaic takes (a bfloat16 pool's HBM tiles hold 16 rows; a slice of 1 is
  "not aligned to tiling"), so a lane's page is read (4 KB at 128 wide), the
  row set in VMEM and the page written back. Lanes that decode own distinct
  pages; the lanes that do not all aim at the trash page, which takes their
  writes in any order.
- :func:`write_a_span` (one sequence's rows over whole pages, at any
  offset): the span's first and last page are read, the rows laid between
  what the two hold before and behind the span (a roll along the sublanes by
  the offset, in float32: exact for every pool dtype narrower), and the
  pages that ``written`` names copied out.

Both leave the bits the scatter leaves, outside the trash page.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fleetx_tpu.ops.pallas.flash_attention import _interpret

__all__ = ["KERNEL_NAME", "takes", "write_a_row_a_lane", "write_a_span"]

KERNEL_NAME = "fleetx_write_rows"
_TILE = 16               # rows that one blend step loads and places
_VMEM_LIMIT = 64 << 20
_SCRATCH_LIMIT = 40 << 20


def takes(pools, rows, pages: int) -> bool:
    """Whether the kernel takes this call: floating pools of whole 128-lane
    rows, and what it holds at once (the rows and ``pages`` pages a pool)
    within its VMEM."""
    held = sum((new.shape[0] + pages * pool.shape[1]) * pool.shape[2]
               * pool.dtype.itemsize for pool, new in zip(pools, rows))
    return held <= _SCRATCH_LIMIT and all(
        pool.shape[2] % 128 == 0 and jnp.issubdtype(pool.dtype, jnp.floating)
        for pool in pools)


def _call(kernel, scalars, pools, rows, scratch):
    """``kernel(*scalars, *rows, *pools, *pools' outputs, *scratch)`` as one
    grid step over refs in HBM, every pool aliased to its output."""
    n = len(pools)
    return list(pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (2 * n),
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n,
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={len(scalars) + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret(),
        name=KERNEL_NAME,
    )(*(jnp.reshape(x, (-1,)).astype(jnp.int32) for x in scalars),
      *rows, *pools))


def _each(count, copies, act: str, only=None):
    """``act`` (``start`` or ``wait``) on the copies ``copies(i)`` for every
    ``i`` below ``count`` (where ``only[i]`` is set, under ``only``)."""
    def step(i, carry):
        @pl.when(True if only is None else only[i] != 0)
        def _():
            for copy in copies(i):
                getattr(copy, act)()
        return carry

    jax.lax.fori_loop(0, count, step, 0)


def _row_kernel(keep_ref, page_ref, off_ref, *refs, n: int, lanes: int):
    rows, pools, out = refs[:n], refs[n:2 * n], refs[2 * n:3 * n]
    held, fresh, sem = refs[3 * n:4 * n], refs[4 * n:5 * n], refs[5 * n]

    @pl.when(keep_ref[0] != 0)
    def _():
        def reads(r):
            return [pltpu.make_async_copy(pools[p].at[page_ref[r]],
                                          held[p].at[r], sem.at[p])
                    for p in range(n)]

        def writes(r):
            return [pltpu.make_async_copy(held[p].at[r],
                                          out[p].at[page_ref[r]], sem.at[p])
                    for p in range(n)]

        news = [pltpu.make_async_copy(rows[p], fresh[p], sem.at[n + p])
                for p in range(n)]
        for copy in news:
            copy.start()
        _each(lanes, reads, "start")
        for copy in news:
            copy.wait()
        _each(lanes, reads, "wait")

        def blend(first, count: int):
            """Rows ``first + [0, count)`` into their pages: one load of the
            rows' tile, a select a page."""
            for p in range(n):
                tile = fresh[p][pl.ds(first, count), :]
                row_of = jax.lax.broadcasted_iota(jnp.int32,
                                                  held[p].shape[1:], 0)
                for i in range(count):
                    page = held[p][first + i]
                    held[p][first + i] = jnp.where(
                        row_of == off_ref[first + i],
                        jnp.broadcast_to(tile[i:i + 1, :], page.shape), page)

        def tiles(g, carry):
            blend(pl.multiple_of(g * _TILE, _TILE), _TILE)
            return carry

        if lanes >= _TILE:
            jax.lax.fori_loop(0, lanes // _TILE, tiles, 0)
        if lanes % _TILE:
            blend(lanes - lanes % _TILE, lanes % _TILE)
        _each(lanes, writes, "start")
        _each(lanes, writes, "wait")


def write_a_row_a_lane(pools, rows, page, off, keep):
    """``pools`` with row ``r`` of each of ``rows`` (``[lanes, width]``,
    pool for pool) at ``[page[r], off[r]]``, or as they were where ``keep``
    (a traced scalar) is 0. Rows that share a page must be the trash
    page's."""
    n, lanes = len(pools), rows[0].shape[0]
    return _call(
        functools.partial(_row_kernel, n=n, lanes=lanes),
        (keep, page, off), pools, rows,
        [pltpu.VMEM((lanes,) + p.shape[1:], p.dtype) for p in pools]
        + [pltpu.VMEM(r.shape, r.dtype) for r in rows]
        + [pltpu.SemaphoreType.DMA((2 * n,))])


def _span_kernel(keep_ref, lead_ref, page_ref, written_ref, *refs, n: int,
                 pages: int):
    rows, pools, out = refs[:n], refs[n:2 * n], refs[2 * n:3 * n]
    span, fresh, sem = refs[3 * n:4 * n], refs[4 * n:5 * n], refs[5 * n]
    ps = pools[0].shape[1]
    s = pages * ps

    @pl.when(keep_ref[0] != 0)
    def _():
        lead = lead_ref[0]
        reads = []
        for p in range(n):
            reads += [
                pltpu.make_async_copy(rows[p], fresh[p], sem.at[p]),
                pltpu.make_async_copy(pools[p].at[page_ref[0]],
                                      span[p].at[0], sem.at[p]),
                pltpu.make_async_copy(pools[p].at[page_ref[pages]],
                                      span[p].at[pages], sem.at[p])]
        for copy in reads:
            copy.start()
        for copy in reads:
            copy.wait()
        for p in range(n):
            width = rows[p].shape[1]
            block = next(o for o in (512, 256, 128) if width % o == 0)
            gap = jnp.zeros((ps, block), jnp.float32)
            at = jax.lax.broadcasted_iota(jnp.int32, (s + ps, block), 0)
            inside = (at >= lead) & (at < lead + s)
            for j in range(width // block):
                cols = slice(j * block, (j + 1) * block)
                # row ``i`` of the rows at ``lead + i`` of the span
                moved = pltpu.roll(jnp.concatenate(
                    [fresh[p][:, cols].astype(jnp.float32), gap]), lead, 0)
                ends = jnp.concatenate(
                    [span[p][0, :, cols].astype(jnp.float32)]
                    + [gap] * (pages - 1)
                    + [span[p][pages, :, cols].astype(jnp.float32)])
                span[p][:, :, cols] = jnp.where(inside, moved, ends).astype(
                    span[p].dtype).reshape(pages + 1, ps, block)

        def writes(j):
            return [pltpu.make_async_copy(span[p].at[j],
                                          out[p].at[page_ref[j]], sem.at[p])
                    for p in range(n)]

        _each(pages + 1, writes, "start", written_ref)
        _each(pages + 1, writes, "wait", written_ref)


def write_a_span(pools, rows, lead, page, written, keep):
    """``pools`` with ``rows`` (``[n x page_size, width]`` each, pool for
    pool) laid from row ``lead`` of page ``page[0]`` on through ``page``
    (``[n + 1]``), the pages that ``written`` (``[n + 1]``) names written
    back; as they were where ``keep`` (a traced scalar) is 0."""
    n, pages = len(pools), rows[0].shape[0] // pools[0].shape[1]
    return _call(
        functools.partial(_span_kernel, n=n, pages=pages),
        (keep, lead, page, written), pools, rows,
        [pltpu.VMEM((pages + 1,) + p.shape[1:], p.dtype) for p in pools]
        + [pltpu.VMEM(r.shape, r.dtype) for r in rows]
        + [pltpu.SemaphoreType.DMA((n,))])
