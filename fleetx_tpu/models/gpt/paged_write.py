"""THE write of a call's keys and values into the page pool, through the
block table: ``SelfAttention._update_paged_cache`` (models/gpt/model.py),
``hybrid.write_rows`` (models/gpt/hybrid.py) and a stack of layer KINDS
(models/gpt/mixed_stack.py, through :func:`write_rows_or_skip`) end here.

One algorithm, rows through a table, whose UNIT follows the call's static
shape (:func:`page_writes`):

- **a row at a time** (a decode tick's one row a lane, a speculative
  verify's ``k + 1``, a bucket that is no whole number of pages): one index
  pair ``(page, offset)`` a row, one update a row;
- **a page at a time** (one sequence's rows over a whole number of pages:
  every prefill program, every chunk, every replay): one table lookup and
  one update a PAGE, right at ANY offset (the model cannot see a traced
  position to be page-aligned): the span's first and last page are read, the
  new rows laid between what they hold before and behind the span, and the
  ``rows // page_size + 1`` pages written back, less the last at an aligned
  offset and any page past the table, which hold none of the span.

Both forms leave the same bits in the same places outside the trash page
(bucket-tail rows and zeroed table entries land there in both, in no defined
order in either); rows past the cache's last position are no defined write.
A write under ``keep`` False (a layer of another kind in a scanned body) is
DROPPED: by the scatter update for update, at its full price; by
:func:`write_rows_or_skip`'s kernel for a launch. No ``lax.cond`` holds
either: XLA copies a pool whole that a conditional hands back. New code goes
to the file's END: these lines are in every traced program's cache key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["decode_end", "page_writes", "write_rows", "write_rows_or_skip"]


def decode_end(tables, wpos, page_size: int):
    """``[b]`` ends of the windows a one-row call's lanes attend over:
    ``wpos + 1``, and 0 for a lane whose row went to the trash page. Such a
    lane decodes no token (the serving engine pins a lane without one to the
    last row of its table, which a free lane's zeroed table sends to page
    0), so its window is empty and the decode kernels run no step for it.
    A lane that holds a real page there attends as ever. ``tables`` is the
    lanes' ``[b, pages of a row]`` as the engine hands it over: NO layer's
    base added, so page 0 is the trash page."""
    page = jnp.minimum(wpos // page_size, tables.shape[1] - 1)
    live = jnp.take_along_axis(tables, page[:, None], axis=1)[:, 0] != 0
    return jnp.where(live, wpos + 1, 0)


def page_writes(batch: int, rows: int, page_size: int) -> int:
    """Pages a pool and layer that a call of ``[batch, rows]`` writes a page
    at a time; 0 where it writes a row at a time. The one predicate: the
    model's write branches on it, and the serving engine counts by it
    (``serving.prefill``'s ``page_writes``, ``snapshot()``'s
    ``prefill_page_writes`` / ``prefill_row_writes``)."""
    if batch == 1 and rows > 1 and rows % page_size == 0:
        return rows // page_size
    return 0


def write_rows(pools, rows, tables, wpos, max_len: int, keep=None):
    """The flat ``pools`` (``[pages, page_size, width]`` each) with this
    call's ``rows`` (``[b * s, width]`` each, pool for pool) written at the
    logical positions ``wpos[b] + [0, s)`` through ``tables`` ``[b,
    pages of a row]`` (the pool's own page numbers: a layer's base added).
    ``keep`` (a traced bool) False: nothing is written."""
    batch = wpos.shape[0]
    s = rows[0].shape[0] // batch
    with jax.named_scope("cache_write"):
        if page_writes(batch, s, pools[0].shape[1]):
            return _by_page(pools, rows, tables[0], wpos[0], max_len, keep)
        return _by_row(pools, rows, tables, wpos, s, max_len, keep)


def _by_row(pools, rows, tables, wpos, s, max_len, keep):
    ps = pools[0].shape[1]
    pos = wpos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    pos = jnp.minimum(pos, max_len - 1)        # [b, s] logical
    page = jnp.take_along_axis(tables, pos // ps, axis=1)
    page, off = page.reshape(-1), (pos % ps).reshape(-1)
    if keep is not None:  # a page past the pool: the update is dropped
        page = jnp.where(keep, page, pools[0].shape[0])
    mode = None if keep is None else "drop"
    return [pool.at[page, off].set(new, mode=mode)
            for pool, new in zip(pools, rows)]


def _by_page(pools, rows, table, wpos, max_len, keep):
    ps = pools[0].shape[1]
    n = rows[0].shape[0] // ps
    # the n + 1 table entries the span touches, and the rows of the first
    # page that lie before it (as many of the last lie inside it)
    index = jnp.arange(n + 1, dtype=jnp.int32)
    entry, lead = wpos // ps + index, wpos % ps
    page = table[jnp.minimum(entry, table.shape[0] - 1)]
    # (the last page at an aligned offset, and a page past the table, hold
    # none of the span)
    written = (entry < min(table.shape[0], max_len // ps)) & (
        (index < n) | (lead > 0))
    if keep is not None:
        written &= keep
    target = jnp.where(written, page, pools[0].shape[0])
    out = []
    for pool, new in zip(pools, rows):
        between = jnp.zeros(((n - 1) * ps,) + new.shape[1:], pool.dtype)
        span = jax.lax.dynamic_update_slice(
            jnp.concatenate([pool[page[0]], between, pool[page[n]]]),
            new, (lead, jnp.zeros_like(lead)))
        out.append(pool.at[target].set(
            span.reshape((n + 1,) + pool.shape[1:]), mode="drop"))
    return out


def write_rows_or_skip(pools, rows, tables, wpos, max_len: int, keep, *,
                       kernel: bool = True):
    """:func:`write_rows` for a caller whose ``keep`` (a traced bool) is
    False in most of its calls: a stack of layer kinds, whose one scanned
    body writes keys and values in EVERY layer and keeps them in the
    attention layers alone (``mixed_stack.py`` ``operator``; Jamba2: 2 of
    28). The scatter of :func:`write_rows` cannot branch on ``keep``: it aims
    a dropped row at a page past the pool and still walks every update (40
    us a layer of a 256-lane tick, 16-21 us of a prefill's 17-49 pages,
    alone on a v5e: PERF.md, PR 62), and a ``lax.cond`` around it hands the
    pools back, which XLA copies whole. Here the write is the kernel ``fleetx_write_rows``
    (``ops/pallas/write_rows.py``), which takes ``keep``, the pages and the
    offsets as scalars and has its whole body under ``keep``: the pools are
    aliased through it in place and cross no conditional, and a dropped
    write costs the launch (5-8 us with the index arithmetic before it). It takes a tick (one row a lane) and the
    page-at-a-time form; any other shape, a pool it cannot hold, or a
    backend without the kernels (``kernel``) is :func:`write_rows`'s. The
    index arithmetic is ``_by_row``'s and ``_by_page``'s, repeated here so
    that no line above moves (the module docstring's last paragraph)."""
    from fleetx_tpu.ops.pallas import write_rows as writer
    from fleetx_tpu.ops.pallas.flash_attention import kernels_enabled

    batch, ps = wpos.shape[0], pools[0].shape[1]
    s = rows[0].shape[0] // batch
    n = page_writes(batch, s, ps)
    if not (kernel and kernels_enabled() and (n or s == 1)
            and writer.takes(pools, rows, n + 1 if n else batch)):
        return write_rows(pools, rows, tables, wpos, max_len, keep)
    with jax.named_scope("cache_write"):
        if not n:
            pos = jnp.minimum(wpos, max_len - 1)
            page = jnp.take_along_axis(tables, pos[:, None] // ps, axis=1)
            return writer.write_a_row_a_lane(pools, rows, page, pos % ps,
                                             keep)
        table, index = tables[0], jnp.arange(n + 1, dtype=jnp.int32)
        entry, lead = wpos[0] // ps + index, wpos[0] % ps
        written = (entry < min(table.shape[0], max_len // ps)) & (
            (index < n) | (lead > 0))
        return writer.write_a_span(
            pools, rows, lead, table[jnp.minimum(entry, table.shape[0] - 1)],
            written, keep)
