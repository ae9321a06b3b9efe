"""THE write of a call's keys and values into the page pool, through the
block table: ``SelfAttention._update_paged_cache`` (models/gpt/model.py) and
``hybrid.write_rows`` (models/gpt/hybrid.py) both end here.

One algorithm, rows through a table, whose UNIT follows the call's static
shape (:func:`page_writes`):

- **a row at a time** (a decode tick's one row a lane, a speculative
  verify's ``k + 1``, a bucket that is no whole number of pages): one index
  pair ``(page, offset)`` a row, one update a row;
- **a page at a time** (one sequence's rows over a whole number of pages:
  every prefill program, every chunk, every replay): one table lookup and
  one update a PAGE. The write position is a traced value that the model
  cannot see to be page-aligned, so the form is right at ANY offset: the
  span's first and last page are read, the new rows laid between what they
  hold before and behind the span, and the ``rows // page_size + 1`` pages
  written back. At an aligned offset the last of them holds none of the
  span and is dropped, as is a page past the table (a span that ends at the
  cache's end).

Both forms leave the same bits in the same places outside the trash page
(bucket-tail rows and zeroed table entries land there in both, in no
defined order in either). Rows past the cache's last position are not a
defined write in either form.

A module of its own, and not a part of model.py, so that a change here moves
no line of the code that training traces (models/gpt/resident.py has why).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["decode_end", "page_writes", "write_rows"]


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
