"""EVA attention as EvaByte's published modelling code gives it ("Efficient
Attention via Control Variates", Zheng et al., ICLR 2023, in its
deterministic form): the operator of a configuration with
``eva_window_size`` W and ``eva_chunk_size`` C (``block_fields.py``), run by
``hybrid.HybridSelfAttention`` in the place of its window/full attention.

Chunk ``c`` holds positions ``[C c, C c + C)``. Two learned vectors a head
(``eva_mu``, ``eva_phi`` ``[heads, d]``) pool a chunk's ROTATED keys and its
values into one row each, ``k~_c = sum_j softmax_j(mu . k_j) k_j`` and ``v~_c
= sum_j softmax_j(phi . k_j) v_j`` (both softmaxes over the chunk's C rows,
in float32, no further scale), WHEN ITS LAST ROW EXISTS and never before.
The query at position ``t``, in window ``w = t // W``, takes ONE softmax (in
float32, scores over ``sqrt(d)``) over the exact rows ``[W w, t]`` of its own
window and the pooled rows of the chunks ``c < (W / C) w`` of every window
before it; the chunks of its own window are never among them.

**Serving** keeps two classes of page in the layer's part of the flat pool
(``hybrid.init_cache``): ``decode_num_pages`` SUMMARY pages, whose row ``c``
is chunk ``c``'s pooled row (a lane's table for the class is addressed by
chunk index), then ``decode_window_pages`` WINDOW pages, which hold the exact
rows of the lane's current window alone (its table is addressed by position;
``serving/cache_manager.py`` releases a window's pages all at once when the
lane crosses into the next). Both hold rows of the same width, so a program
COMPOSES one table a lane (:func:`composed_tables`): the ``W / C /
page_size`` whole summary pages of every closed window, then the window's
pages. In composed coordinates every pooled row lies before every exact row
and position ``t`` is row ``(W / C) w + t % W``, so the causal rule is the
ordinary one: a tick is ``fleetx_decode_paged`` over ``[0, row + 1)`` and one
lane's chunk ``fleetx_prefill_gqa`` with ``start`` the chunk's first
composed row (no new attention kernel). A page is one chunk (``page_size ==
eva_chunk_size``), so

- a prefill chunk pools the whole chunks among its own TRUE rows from the
  keys and values it has just computed (a padded row of its bucket closes
  nothing) and writes them to the summary rows ``[start / C, ..)``;
- a tick pools the newest window page of every lane, and keeps the pooled
  row where the lane's position is a chunk's last (``p % C == C - 1``; any
  other lane's goes to the trash page): ONE program whatever the lanes'
  phases, and the open chunk a prompt leaves is closed by the tick that
  writes its last row.

The two poolings and the pooled rows' write run under the device scope
``eva_pool``, the attention call under ``attn_window``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

__all__ = ["UnitOffsetRMSNorm", "composed_tables", "dense_attention",
           "paged_attention", "pool_chunks", "pool_vectors"]


# ---- seams: ``perfbench/probe_evabyte.py`` plants one fault in each

def _norm_gain(w):
    """What the normed rows are multiplied by: one plus the weight."""
    return 1.0 + w


def _pool_vectors(mu, phi):
    """``(for the keys, for the values)``."""
    return mu, phi


def _pool_dtype():
    """The dtype of the pooling's softmaxes and sums."""
    return jnp.float32


def _keys_to_pool(rotated, raw):
    """The keys a chunk program pools: as the cache holds them."""
    del raw
    return rotated


def _window_start(cfg, pos):
    """The first exact row a query at ``pos`` sees: its window's first."""
    return pos // cfg.eva_window_size * cfg.eva_window_size


def _summary_rows_visible(cfg, pos):
    """The pooled rows a query at ``pos`` sees: those of the windows before
    its own (whole summary pages)."""
    return pos // cfg.eva_window_size * (
        cfg.eva_window_size // cfg.eva_chunk_size)


def _chunks_closed(rows_true, rows: int, chunk: int):
    """Which of a chunk program's ``rows // chunk`` chunks its TRUE rows
    close: ``[rows // chunk]`` bool."""
    return (jnp.arange(1, rows // chunk + 1, dtype=jnp.int32) * chunk
            <= rows_true)


def _tick_closes(wpos, chunk: int):
    """Which lanes of a tick close a chunk: ``[b]`` bool."""
    return wpos % chunk == chunk - 1


# (exact window pages beside the window's own in a composed table: none; the
# probe's sliding window holds a summary page's positions more)
_MORE_WINDOW_PAGES = 0


class UnitOffsetRMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + w)`` in float32, returned in
    ``dtype``; the weight ``scale`` is the offset from one."""

    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.with_logical_partitioning(
            nn.initializers.zeros_init(), ("norm",)), (x.shape[-1],),
            jnp.float32)
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + self.eps)
        return (x * _norm_gain(w)).astype(self.dtype)


def pool_vectors(module):
    """The layer's two pooling vectors ``[heads, d]`` float32, drawn as the
    published initialiser draws them: ``clip(normal, -1, 1) * d ** -0.5``."""
    cfg = module.cfg

    def init(key, shape, dtype):
        return jnp.clip(jax.random.normal(key, shape, dtype), -1.0,
                        1.0) * cfg.head_dim ** -0.5

    shape = (cfg.num_attention_heads, cfg.head_dim)
    return tuple(module.param(
        name, nn.with_logical_partitioning(init, ("heads", "kv")), shape,
        jnp.float32) for name in ("eva_mu", "eva_phi"))


def pool_chunks(k, v, mu, phi):
    """``(k~, v~)`` ``[..., heads * d]`` of whole chunks' rows ``k``, ``v``
    ``[..., C, heads * d]`` (the keys rotated), in their dtype: module
    docstring."""
    heads, d = mu.shape
    dtype = _pool_dtype()
    mu, phi = (t.astype(dtype) for t in _pool_vectors(mu, phi))
    kf = k.astype(dtype).reshape(*k.shape[:-1], heads, d)
    vf = v.astype(dtype).reshape(*v.shape[:-1], heads, d)
    by_key = jax.nn.softmax(jnp.einsum("...chd,hd->...ch", kf, mu), axis=-2)
    by_value = jax.nn.softmax(jnp.einsum("...chd,hd->...ch", kf, phi), axis=-2)
    pooled_k = jnp.einsum("...ch,...chd->...hd", by_key, kf)
    pooled_v = jnp.einsum("...ch,...chd->...hd", by_value, vf)
    return (pooled_k.reshape(*k.shape[:-2], heads * d).astype(k.dtype),
            pooled_v.reshape(*v.shape[:-2], heads * d).astype(v.dtype))


def dense_attention(cfg, q, k, v, k_raw, mu, phi, attn_mask=None):
    """A forward outside the cache: every position at once. ``q`` ``[b, s,
    heads, d]``, ``k`` (rotated), ``v`` and ``k_raw`` ``[b, s, heads * d]``;
    positions count from 0. The pooled rows of the sequence's whole chunks
    stand before its exact rows, and one mask says who sees which."""
    from fleetx_tpu.models.gpt.hybrid import grouped_attention

    if attn_mask is not None:
        raise NotImplementedError("a key mask over EVA attention outside "
                                  "the cache: no test covers it")
    b, s = q.shape[:2]
    chunk = cfg.eva_chunk_size
    n = s // chunk
    with jax.named_scope("eva_pool"):
        width = k.shape[-1]
        pooled_k, pooled_v = pool_chunks(
            _keys_to_pool(k, k_raw)[:, :n * chunk].reshape(b, n, chunk,
                                                           width),
            v[:, :n * chunk].reshape(b, n, chunk, width), mu, phi)
    pos = jnp.arange(s, dtype=jnp.int32)
    exact = (pos[None, :] <= pos[:, None]) & (
        pos[None, :] >= _window_start(cfg, pos)[:, None])
    pooled = (jnp.arange(n, dtype=jnp.int32)[None, :]
              < _summary_rows_visible(cfg, pos)[:, None])
    with jax.named_scope("attn_window"):
        return grouped_attention(
            q, jnp.concatenate([pooled_k, k], axis=1),
            jnp.concatenate([pooled_v, v], axis=1),
            jnp.concatenate([pooled, exact], axis=1)[None, None])


def composed_tables(cfg, tables, wpos, base):
    """``(composed [b, pages], row [b], summary [b, pages of the class])``
    for lanes at positions ``wpos``: each lane's composed table in the flat
    pool's own page numbers (module docstring), the composed row of
    ``wpos``, and the lane's summary table (the layer's base added).
    ``tables`` is ``[2, b, pages of a row]`` as the engine hands it over,
    summary then window, page 0 of each its trash page; ``base`` the layer's
    first page. An entry past a lane's last window page is the window
    class's trash page."""
    ps = cfg.decode_page_size
    summary, window = tables[0] + base, tables[1]
    first = _window_start(cfg, wpos)                 # [b] first exact row
    pooled = _summary_rows_visible(cfg, wpos)        # [b] pooled rows seen
    pages = cfg.eva_composed_pages + _MORE_WINDOW_PAGES
    at = jnp.arange(pages, dtype=jnp.int32)[None, :]
    seen = pooled[:, None] // ps
    own = jnp.take_along_axis(
        window, jnp.clip(first[:, None] // ps + at - seen, 0,
                         window.shape[1] - 1), axis=1)
    # past one window's pages nothing is held (a clipped index could name a
    # page again)
    own = jnp.where(at - seen < cfg.eva_window_size // ps
                    + _MORE_WINDOW_PAGES, own, 0)
    composed = jnp.where(
        at < seen, summary[:, :pages] if summary.shape[1] >= pages
        else jnp.pad(summary, ((0, 0), (0, pages - summary.shape[1]))),
        own + base + cfg.decode_num_pages)
    return composed, pooled + wpos - first, summary


def paged_attention(module, q, k, v, k_raw, cache_positions, block_tables,
                    layer_index, rows_true, mu, phi, deterministic):
    """Write this call's keys and values into the lane's window pages, pool
    the chunks the call closes into its summary pages, and attend through
    the composed table (module docstring); None at the cache's init.
    ``rows_true`` ``[b, s]`` bool or None: which rows are tokens (a padded
    row of a bucket, an idle lane of a tick, closes no chunk)."""
    from fleetx_tpu.models.gpt import hybrid, paged_write
    from fleetx_tpu.ops.pallas import prefill_gqa
    from fleetx_tpu.ops.pallas.decode_attention import (
        flash_decode_paged_attention,
        paged_gather_kv,
    )

    cfg = module.cfg
    ps, chunk = cfg.decode_page_size, cfg.eva_chunk_size
    b, s, width = q.shape[0], q.shape[1], cfg.kv_heads * cfg.head_dim
    is_init = not module.has_variable("cache", "cached_key")
    ck = module.variable("cache", "cached_key", jnp.zeros, (1, ps, width),
                         q.dtype)
    cv = module.variable("cache", "cached_value", jnp.zeros, (1, ps, width),
                         q.dtype)
    module.variable("cache", "cache_index", lambda: jnp.array(0, jnp.int32))
    if is_init:
        return None
    if cache_positions is None or block_tables is None or (
            block_tables.ndim != 3):
        raise ValueError(
            "EVA attention through the cache needs cache_positions AND the "
            "block tables of both classes [2, lanes, pages] (the serving "
            "engine threads both)")
    if ps != chunk:
        raise NotImplementedError(
            f"EVA attention through a pool of {ps}-row pages: a page is one "
            f"chunk (eva_chunk_size {chunk})")
    wpos = cache_positions.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    base = jnp.asarray(hybrid.layer_bases(cfg))[layer_index]
    composed, row, summary = composed_tables(cfg, tables, wpos, base)
    ck.value, cv.value = hybrid.write_rows(cfg, ck.value, cv.value, composed,
                                           row, k, v)
    n_true = (jnp.full((b,), s, jnp.int32) if rows_true is None
              else rows_true.astype(jnp.int32).sum(-1))

    if s == 1:
        # a tick: every lane's newest page pooled, kept where it is whole
        with jax.named_scope("eva_pool"):
            newest = jnp.take_along_axis(composed, (row // ps)[:, None],
                                         axis=1)[:, 0]
            pooled_k, pooled_v = pool_chunks(ck.value[newest],
                                             cv.value[newest], mu, phi)
            closes = _tick_closes(wpos, chunk) & (n_true > 0)
            ck.value, cv.value = paged_write.write_rows(
                [ck.value, cv.value], [pooled_k, pooled_v],
                jnp.where(closes[:, None], summary, base), wpos // chunk,
                cfg.decode_cache_len)
        # a lane whose row went to the trash page attends over nothing
        end = jnp.where(paged_write.decode_end(tables[1], wpos, ps) > 0,
                        row + 1, 0)
        with jax.named_scope("attn_window"):
            if module._flash_decode_ok(None, composed.shape[1] * ps,
                                       deterministic, tile_len=ps):
                return flash_decode_paged_attention(
                    q, ck.value, cv.value, tables=composed, end=end)
            live = (jnp.arange(composed.shape[1] * ps, dtype=jnp.int32)
                    [None, :] < end[:, None])
            return hybrid.grouped_attention(
                q, paged_gather_kv(ck.value, composed),
                paged_gather_kv(cv.value, composed), live[:, None, None, :])

    if b != 1:
        raise NotImplementedError(
            "EVA attention through the cache takes a tick (one row a lane) "
            f"or a chunk of ONE lane, not {b} lanes x {s} rows")
    n = s // chunk
    if n:
        # a chunk program: the whole chunks among its own true rows
        with jax.named_scope("eva_pool"):
            pooled_k, pooled_v = pool_chunks(
                _keys_to_pool(k, k_raw)[0, :n * chunk].reshape(n, chunk,
                                                               width),
                v[0, :n * chunk].reshape(n, chunk, width), mu, phi)
            closed = _chunks_closed(n_true[0], s, chunk)
            ck.value, cv.value = paged_write.write_rows(
                [ck.value, cv.value], [pooled_k, pooled_v],
                jnp.where(closed[:, None], summary, base),
                wpos[0] // chunk + jnp.arange(n, dtype=jnp.int32),
                cfg.decode_cache_len)
    kernel = module._chunk_kernel(b, s)
    held = composed
    if kernel:  # whole key blocks: the layer's trash page behind the last
        more = prefill_gqa.padded_rows(held.shape[1] * ps) // ps - (
            held.shape[1])
        held = jnp.concatenate(
            [held, jnp.broadcast_to(base, (b, more))], axis=1)
    with jax.named_scope("attn_window"):
        keys = paged_gather_kv(ck.value, held)
        values = paged_gather_kv(cv.value, held)
        if kernel:
            return prefill_gqa.prefill_gqa(q[0], keys[0], values[0], row[0],
                                           0)[None]
        at = jnp.arange(held.shape[1] * ps, dtype=jnp.int32)
        allowed = at[None, None, :] <= (
            row[:, None, None] + jnp.arange(s, dtype=jnp.int32)[None, :, None])
        return hybrid.grouped_attention(q, keys, values, allowed[:, None])
