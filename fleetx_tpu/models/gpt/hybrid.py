"""The decoder layer for blocks whose layers are not all alike
(``models/gpt/block_fields.py``): grouped-query heads with a head size of
their own, window and full attention layers mixed, rotary and position-free
layers mixed, and a router that reads the block's input.

``GPTModel._decoder_stack`` scans ONE body over the layers whatever the
depth: a layer's kind is data. Each layer gets its own index and looks its
kind up in the configuration's layout lists (constants of the program);
``rope`` on or off is a ``where``, window or full attention a ``lax.cond``
whose two branches are the same calls with other bounds, each under a
device scope of its own (``attn_full`` / ``attn_window``) so that a trace
tells them apart. A scan over whole periods of the pattern would compile
the body once too, but as many layers as a period has, hold the parameters
as ``[periods, ...]`` by place in the period (another tree for the engine,
``resident.py``, the expert kernels' layer index and the references), and
fix the pattern's period in the tree; per-layer flags cost two small
conditionals a layer.

Serving keeps TWO CLASSES OF PAGE in one flat pool ``[pages, page_size,
kv_heads * head_dim]`` (the layer loop's carry): a full-attention layer
owns ``decode_num_pages`` pages and a lane's table for it grows with the
context; a window layer owns ``decode_window_pages`` and a lane's table for
it holds only the pages a live query can still see (``serving/
cache_manager.py`` ``WindowPagePool`` releases the others). The model is
handed both tables ``[2, lanes, pages of a row]`` in LOGICAL page order and
picks the one of the layer's kind; entry 0 is the layer's own trash page,
as in the one-class pool. A window layer passes the decode kernels ``starts
= max(0, end - window)``, which their ``starts`` / ``ends`` contract has
always had, and a prefill chunk gathers the window plus the chunk and not
the whole row. One lane's chunk then runs ONE kernel a layer over the rows
gathered, ``fleetx_prefill_gqa`` (``ops/pallas/prefill_gqa.py``: window and
full layers alike, the window a scalar of the call; no step for a key block
past the chunk's last row or before its first query's window), where the
decode kernels run and the head size is whole 128-lane column blocks of the
pool's rows (``_chunk_kernel``); every other call through the cache takes
its plain twin ``grouped_attention`` over the same rows.

**Under a learned indexer** (``index_topk`` > 0 on a stack of
``full_attention`` layers: ``block_fields._check_grouped_indexer``; the
functions are ``models/gpt/indexer.py``'s, which latent attention calls
too). Beside q, k and v the layer projects, FROM ITS NORMED INPUT ``a``
(there is no query latent): ``qI = a W_Iq`` (``index_n_heads`` heads of
``index_head_dim``), ONE key a row ``kI = LayerNorm(a W_Ik)``, head weights
``wI = (a W_Iw) * heads^-0.5 * head_dim^-0.5`` (float32); every pair of
``qI`` and ``kI`` rotates, by angles of their own (the call's ``rope`` is
then four tables: the heads' two and the indexer's two,
``mixed_stack.MixedStack``). ``kI`` is the pool's THIRD leaf
``cached_index`` (held :func:`index_leaf_width` wide), written through
``write_rows`` beside K and V. A tick scores each lane's rows (scope
``dsa_index``), takes the top ``index_topk`` (``dsa_select``), gathers
their K and V rows into a compact pool and runs ``fleetx_decode_paged`` over
it (``dsa_attn``); a chunk scores, selects by threshold, and attends under
its mask ``[s, rows]`` in ``fleetx_gqa_sparse_prefill`` (its plain twin:
``grouped_attention`` handed the mask).

Forward only where it differs from ``model.py``: a forward outside the
cache takes the plain path (``grouped_attention``); training this block,
with grouped heads and a window in the flash kernels, is ROADMAP R4.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from fleetx_tpu.models.gpt import indexer, paged_write
from fleetx_tpu.models.gpt.model import (
    MLP,
    GPTConfig,
    SelfAttention,
    _constrain_act,
    _dense,
    _dropout,
    _layer_norm,
    apply_rope,
)
from fleetx_tpu.ops.pallas import prefill_gqa
from fleetx_tpu.ops.pallas.flash_attention import kernels_enabled

__all__ = ["POOL_LEAVES", "HybridDecoderLayer", "HybridSelfAttention",
           "chunk_key_rows", "grouped_attention", "index_leaf_width",
           "init_cache", "layer_bases", "total_pages", "window_gather",
           "write_rows"]

_NEG = -1e30  # a masked score: finite, so a row of padding stays finite
# the leaves of the flat pool, under the one block table (``cached_index``:
# the third leaf of a latent pool whose model has an indexer)
POOL_LEAVES = ("cached_key", "cached_value", "cached_index")


def _rotated_index_key(ki, rope):
    """The indexer's key ``[b, s, d]``, every pair rotated, as the cache
    takes it (a seam: ``perfbench/probe_keyevl2.py`` plants a fault here)."""
    return apply_rope(ki[:, :, None], rope)[:, :, 0]


def index_leaf_width(cfg: GPTConfig) -> int:
    """Columns of the index key's leaf: ``index_head_dim`` rounded up to the
    device's 128-lane tile, the columns past the key zeros (a 64-wide leaf
    occupies 128 lanes a row in HBM whatever its declared shape, and a page
    of it cannot be copied out of that tiling by itself:
    ``latent.rope_leaf_width``)."""
    return -(-cfg.index_head_dim // 128) * 128


def _pages_of(cfg: GPTConfig):
    """Pages of every layer in the flat pool, by the layer's kind."""
    full, window = cfg.decode_num_pages, cfg.decode_window_pages
    if cfg.eva:  # every layer holds both classes: summary, then window
        if window is None:
            raise ValueError(
                "a paged decode cache over EVA layers needs "
                "decode_window_pages beside decode_num_pages (the summary "
                "class's; the serving engine sets both)")
        return [full + window] * cfg.num_layers
    if any(cfg.window_layers) and window is None:
        raise ValueError(
            "a paged decode cache over window layers needs "
            "decode_window_pages beside decode_num_pages (the serving "
            "engine sets both)")
    # (of a ``layer_types`` stack only the attention layers hold keys and
    # values, and are counted among themselves: models/gpt/mixed_stack.py)
    return [window if w else full
            for w in cfg.of_attention_layers(cfg.window_layers)]


def layer_bases(cfg: GPTConfig) -> np.ndarray:
    """First page of every layer in the flat pool (its trash page)."""
    pages = _pages_of(cfg)
    return np.concatenate([[0], np.cumsum(pages)[:-1]]).astype(np.int32)


def total_pages(cfg: GPTConfig) -> int:
    """Pages of the flat pool: every layer's, both classes."""
    return int(sum(_pages_of(cfg)))


def init_cache(model, batch: int):
    """The zero decode cache of a model with layer kinds over a page pool:
    the tree ``model.init`` declares, with the key and value leaves made
    the ONE flat pool of both classes (module docstring)."""
    cfg = model.cfg
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32),
        jnp.zeros((batch, 1), jnp.int32), decode=True))["cache"]
    pool = (total_pages(cfg), cfg.decode_page_size,
            cfg.kv_heads * cfg.head_dim)

    def one(path, x):
        kv = path[-1].key in POOL_LEAVES  # (own widths)
        return jnp.zeros((x.shape, pool[:2] + x.shape[-1:])[kv], x.dtype)

    return jax.tree_util.tree_map_with_path(one, shapes)


def write_rows(cfg: GPTConfig, k_pool, v_pool, tables, wpos, k, v, keep=None,
               more=()):
    """The pools with this call's keys and values ``[b, s, width]`` written
    at positions ``wpos + [0, s)`` through ``tables`` (a layer's own:
    its base added). ``keep`` (a traced bool) False: nothing is written.
    ``more``: further ``(pool, rows)`` pairs under the same table (latent
    attention's index keys), returned after the two. The write itself is
    ``paged_write.write_rows``: a page at a time where the call is one
    sequence over whole pages, else a row at a time."""
    max_len = cfg.decode_cache_len or cfg.max_position_embeddings
    (b, s), width = k.shape[:2], -1  # (each pool's own width)
    return paged_write.write_rows(
        [k_pool, v_pool] + [pool for pool, _ in more],
        [rows.reshape(b * s, width) for rows in [k, v] + [r for _, r in more]],
        tables, wpos, max_len, keep)


def grouped_attention(q, k, v, allowed):
    """Dense attention of grouped heads: ``q`` ``[b, s, heads, d]``, ``k``
    and ``v`` ``[b, t, kv_heads * d]`` (lane-dense, as the cache holds
    them), ``allowed`` bool broadcastable to ``[b, 1, s, t]``. Query head
    ``h`` reads key head ``h // group``; scores over ``sqrt(d)``, softmax
    in float32. No key is repeated: the group is an axis of the product."""
    b, s, heads, d = q.shape
    t = k.shape[1]
    kv_heads = k.shape[-1] // d
    k = k.reshape(b, t, kv_heads, d)
    v = v.reshape(b, t, kv_heads, d)
    q = q.reshape(b, s, kv_heads, heads // kv_heads, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) / (d ** 0.5)
    scores = jnp.where(allowed[:, :, None], scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, heads, d)


def window_gather(cfg: GPTConfig, wpos, s: int, n: int):
    """``(pages, first)``: the logical pages of an ``n``-page lane that a
    window layer gathers for a chunk of ``s`` rows at ``wpos`` (an int, or
    ``[b]`` for ``first`` ``[b]``): the window plus the chunk."""
    ps, window = cfg.decode_page_size, cfg.sliding_window
    pages = min(n, -(-(window + s - 1) // ps) + 1)
    xp = np if isinstance(wpos, int) else jnp
    return pages, xp.clip((wpos - window + 1) // ps, 0, n - pages)


def chunk_key_rows(cfg: GPTConfig, s: int, start: int) -> dict:
    """Span fields of a chunk program of ``s`` rows at ``start``: the key
    rows the live steps of the kernel ``fleetx_prefill_gqa`` cover in ONE
    full and ONE window layer and key head (whole blocks: work and padding
    together), for the kinds of layer the configuration has, and the rows
    of queries each of those steps takes; none for a shape the kernel does
    not take."""
    ps = cfg.decode_page_size
    if not prefill_gqa.takes(1, s, cfg.head_dim, ps):
        return {}
    n = -(-(cfg.decode_cache_len or cfg.max_position_embeddings) // ps)
    fields = {"attn_query_rows": s}  # the program's rows, padding included
    if 0 in cfg.window_layers:
        fields["attn_full_key_rows"] = prefill_gqa.key_rows(
            start, s, 0, None, prefill_gqa.padded_rows(n * ps))
    if 1 in cfg.window_layers:
        pages, first = window_gather(cfg, start, s, n)
        fields["attn_window_key_rows"] = prefill_gqa.key_rows(
            start, s, int(first) * ps, cfg.sliding_window,
            prefill_gqa.padded_rows(pages * ps))
    return fields


def _gate_reads(x):
    """What the output gate's projection reads: the layer's normed input,
    which the queries are made from (a function of its own:
    ``perfbench/probe_trinity.py`` plants a fault here)."""
    return x


class HybridSelfAttention(SelfAttention):
    """``SelfAttention`` with ``kv_heads`` key/value heads of ``head_dim``
    and a kind a layer (module docstring). The out-projection, the
    flash-decode dispatch check and the kernels are the base's. Under
    ``attention_gate: sigmoid`` the heads' output is multiplied by
    ``sigmoid(x W_g)`` before the out-projection (``gate_proj``, as wide as
    the queries', reading what they read; device scope ``attn_gate``).
    ``layer_index`` counts the layers that hold keys and values."""

    @nn.compact
    def __call__(self, x, attn_mask=None, *, deterministic=True, decode=False,
                 cache_positions=None, block_tables=None, layer_index=None,
                 rope=None, phase=None):
        """``phase`` splits a cached forward in two for a caller that writes
        the cache itself between them (``models/gpt/mixed_stack.py``):
        "project" returns ``(q, k, v)`` as the cache takes them, "attend"
        takes that ``q`` as ``x`` and attends through the cache as it
        stands (under an output gate ``q`` carries the gate's rows behind
        its heads from the one phase to the other)."""
        cfg = self.cfg
        nh, kvh, hd = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        if layer_index is None:
            raise NotImplementedError(
                "layers with a kind run under the layer scan, which hands "
                "each its index (scan_layers)")
        gated = cfg.attention_gate == "sigmoid"
        windowed = jnp.asarray(cfg.of_attention_layers(cfg.window_layers),
                               bool)[layer_index]
        if phase == "attend":
            if cfg.indexed:  # (q, qI, wI): ``_check_grouped_indexer``: no gate
                return self._out_proj(checkpoint_name(self._indexed_attention(
                    *x, cache_positions, block_tables, layer_index),
                    "core_attn_out"))
            x, gate = jnp.split(x, 2, axis=-2) if gated else (x, None)
            return self._out_proj(self._gate(checkpoint_name(
                self._paged_attention(
                    x, None, None, cache_positions, block_tables,
                    layer_index, windowed, deterministic),
                "core_attn_out"), gate))
        if not deterministic and cfg.attention_probs_dropout_prob > 0.0:
            raise NotImplementedError(
                "attention dropout over grouped heads or a window "
                "(training this block: ROADMAP R4)")
        proj = functools.partial(_dense, logical_axes=("embed", "heads", "kv"),
                                 use_bias=cfg.use_bias, dtype=cfg.dtype)
        if cfg.fuse_attn_qkv:
            # one product for all three, split along the HEADS axis (the
            # fused GPT-2 kernel splits the last: the head counts differ)
            qkv = checkpoint_name(
                proj((nh + 2 * kvh, hd), name="qkv_proj")(x), "qkv_out")
            q, k, v = jnp.split(qkv, (nh, nh + kvh), axis=-2)
        else:
            q = proj((nh, hd), name="q_proj")(x)
            k = proj((kvh, hd), name="k_proj")(x)
            v = proj((kvh, hd), name="v_proj")(x)
            q, k, v = (checkpoint_name(t, "qkv_out") for t in (q, k, v))
        if cfg.qk_norm:  # each head's own values, one weight [head_dim]
            # for q and one for k (``check`` lets no other scope in here)
            q, k = (nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                               param_dtype=jnp.float32, name=name)(t)
                    for name, t in (("q_norm", q), ("k_norm", k)))
        gate = None
        if gated:
            with jax.named_scope("attn_gate"):
                gate = proj((nh, hd), name="gate_proj")(_gate_reads(x))
        k_raw = k
        if rope is not None and any(cfg.rope_layers):
            rotates = jnp.asarray(cfg.of_attention_layers(cfg.rope_layers),
                                  bool)[layer_index]
            q = jnp.where(rotates, apply_rope(q, rope[:2]), q)
            k = jnp.where(rotates, apply_rope(k, rope[:2]), k)
        b, s = q.shape[:2]
        k, v = k.reshape(b, s, kvh * hd), v.reshape(b, s, kvh * hd)
        if cfg.eva:
            return self._out_proj(checkpoint_name(self._eva_attention(
                q, k, v, k_raw.reshape(k.shape), attn_mask, decode,
                cache_positions, block_tables, layer_index, deterministic),
                "core_attn_out"))
        if cfg.indexed:
            qi, ki, head_w = self._index_projections(x, rope)
            if phase == "project":  # (the third leaf's rows, as it holds them)
                return (q, qi, head_w), k, v, jnp.pad(ki, ((0, 0), (0, 0), (
                    0, index_leaf_width(cfg) - cfg.index_head_dim)))
            if decode:
                raise NotImplementedError(
                    "a cached forward under the indexer outside the layer "
                    "loop's two phases (models/gpt/mixed_stack.py)")
            return self._out_proj(checkpoint_name(self._indexed_dense(
                q, k, v, qi, ki, head_w, attn_mask), "core_attn_out"))
        if phase == "project":
            return (jnp.concatenate([q, gate], -2) if gated else q), k, v

        out = None
        if decode:
            if cfg.decode_num_pages is None:
                raise NotImplementedError(
                    "a contiguous decode cache over grouped heads or window "
                    "layers (one-shot generate()): serve the model through "
                    "ServingEngine, whose page pool holds both")
            if attn_mask is not None:
                raise NotImplementedError(
                    "a key mask over the paged cache of grouped heads")
            out = self._paged_attention(q, k, v, cache_positions,
                                        block_tables, layer_index, windowed,
                                        deterministic)
        if out is None:  # no cache (or its init): every position at once
            pos = jnp.arange(s)
            allowed = self._visible(pos[:, None], pos[None, :], windowed)
            if attn_mask is not None:
                allowed = allowed & attn_mask.astype(bool)
            out = grouped_attention(q, k, v, allowed[None, None])
        out = checkpoint_name(out, "core_attn_out")
        return self._out_proj(self._gate(out, gate))

    def _eva_attention(self, q, k, v, k_raw, rows, decode, cache_positions,
                       block_tables, layer_index, deterministic):
        """EVA attention (``models/gpt/eva.py``): through the two classes of
        page in a cached forward, whose ``attn_mask`` ``rows`` says which
        rows are tokens; every position at once outside the cache (and at
        its init)."""
        from fleetx_tpu.models.gpt import eva

        cfg = self.cfg
        mu, phi = eva.pool_vectors(self)
        out = None
        if decode:
            if cfg.decode_num_pages is None:
                raise NotImplementedError(
                    "a contiguous decode cache over EVA layers (one-shot "
                    "generate()): serve the model through ServingEngine, "
                    "whose page pool holds both classes")
            out = eva.paged_attention(
                self, q, k, v, k_raw, cache_positions, block_tables,
                layer_index, rows, mu, phi, deterministic)
        if out is None:  # no cache (or its init): every position at once
            out = eva.dense_attention(cfg, q, k, v, k_raw, mu, phi,
                                      None if decode else rows)
        return out

    def _index_projections(self, x, rope):
        """``(qI, kI, wI)`` of the layer's normed input ``x`` (module
        docstring): ``[b, s, heads, d]`` and ``[b, s, d]`` rotated by the
        indexer's own tables ``rope[2:]``, ``[b, s, heads]`` float32."""
        cfg = self.cfg
        ni, di = cfg.index_n_heads, cfg.index_head_dim
        with jax.named_scope("dsa_index"):
            qi = _dense((ni, di), ("embed", "heads", "kv"), "index_q_proj",
                        use_bias=False, dtype=cfg.dtype)(x)
            ki = nn.LayerNorm(
                epsilon=cfg.norm_eps, dtype=cfg.dtype,
                param_dtype=jnp.float32, name="index_k_norm")(
                    _dense(di, ("embed", None), "index_k_proj",
                           use_bias=False, dtype=cfg.dtype)(x))
            head_w = indexer._index_head_weights(
                _dense(ni, ("embed", None), "index_w_proj", use_bias=False,
                       dtype=cfg.dtype)(x).astype(jnp.float32)
                * (ni * di) ** -0.5)
            if rope is not None:
                qi = apply_rope(qi, rope[2:])
                ki = _rotated_index_key(ki, rope[2:])
        return qi, ki, head_w

    def _indexed_dense(self, q, k, v, qi, ki, head_w, attn_mask):
        """A forward outside the cache under the indexer: every position at
        once, the dense scores masked by each query's own set."""
        if attn_mask is not None:
            raise NotImplementedError(
                "a key mask under the indexer outside the cache: no test "
                "covers it")
        pos = jnp.arange(q.shape[1])
        allowed = (pos[None, :] <= pos[:, None])[None]
        with jax.named_scope("dsa_select"):
            index = indexer.index_scores(qi, head_w, ki)
            allowed = indexer.select_rows(
                index, indexer._visible(allowed), self.cfg.index_topk
            ) & allowed
        indexer.sow_selection(self, index, allowed)
        with jax.named_scope("dsa_attn"):
            return grouped_attention(q, k, v, allowed[:, None])

    def _indexed_attention(self, q, qi, head_w, cache_positions, block_tables,
                           layer_index):
        """Attend through the pool as it stands (the layer loop has written
        the call's rows of all three leaves) under the indexer: a tick (one
        row a lane) or a chunk of ONE lane."""
        from fleetx_tpu.ops.pallas.decode_attention import (
            flash_decode_paged_attention,
            paged_gather_kv,
        )

        cfg = self.cfg
        ps, di = cfg.decode_page_size, cfg.index_head_dim
        k_pool, v_pool, ki_pool = (self.get_variable("cache", name)
                                   for name in POOL_LEAVES)
        b, s = q.shape[:2]
        wpos = cache_positions.astype(jnp.int32)
        own = block_tables.astype(jnp.int32)
        base = jnp.asarray(layer_bases(cfg))[layer_index]
        tables = own + base
        n = tables.shape[1]
        if s == 1:
            end = paged_write.decode_end(own, wpos, ps)
            t = n * ps
            with jax.named_scope("dsa_index"):
                ki = ki_pool[tables].reshape(b, t, -1)[..., :di]
                scores = indexer.index_scores(qi, head_w, ki)[:, 0]
            with jax.named_scope("dsa_select"):
                seen = jnp.arange(t, dtype=jnp.int32)[None, :] < end[:, None]
                chosen, count = indexer.top_rows(
                    scores, indexer._visible(seen), end,
                    min(cfg.index_topk, t))
            if self.is_mutable_collection("routing"):
                indexer.sow_selection(self, scores[:, None],
                                      indexer.chosen_mask(chosen, t))
            with jax.named_scope("dsa_attn"):
                (keys, values), compact = indexer.gather_rows(
                    (k_pool, v_pool), tables, chosen)
                if self._flash_decode_ok(None, t, True, tile_len=ps):
                    return flash_decode_paged_attention(
                        q, keys, values, tables=compact, end=count)
                live = (jnp.arange(compact.shape[1] * ps, dtype=jnp.int32)
                        [None, :] < count[:, None])
                return grouped_attention(
                    q, paged_gather_kv(keys, compact),
                    paged_gather_kv(values, compact), live[:, None, None, :])
        if b != 1:
            raise NotImplementedError(
                "attention under the indexer takes a tick (one row a lane) "
                f"or a chunk of ONE lane, not {b} lanes x {s} rows")
        # whole key blocks: the layer's trash page behind the lane's last
        more = prefill_gqa.padded_rows(n * ps) // ps - n
        held = jnp.concatenate([tables, jnp.broadcast_to(base, (1, more))], 1)
        with jax.named_scope("dsa_index"):
            ki = paged_gather_kv(ki_pool, held)[0][:, :di]
            scores = indexer._chunk_index_scores(qi[0], head_w[0], ki,
                                                 wpos[0])
        with jax.named_scope("dsa_select"):
            seen = (jnp.arange(ki.shape[0], dtype=jnp.int32)[None, :]
                    <= wpos[0] + jnp.arange(s, dtype=jnp.int32)[:, None])
            mask = indexer.select_rows(scores, indexer._visible(seen),
                                       cfg.index_topk)
        # (a chunk's scores, [rows, cache rows] float32 a layer, are not sown)
        indexer.sow_selection(self, None, mask[None])
        with jax.named_scope("dsa_attn"):
            keys = paged_gather_kv(k_pool, held)
            values = paged_gather_kv(v_pool, held)
            if self._chunk_kernel(b, s):
                return prefill_gqa.gqa_sparse_prefill(
                    q[0], keys[0], values[0], mask, wpos[0])[None]
            return grouped_attention(q, keys, values, mask[None, None])

    def _gate(self, out, gate):
        """The heads' output ``[b, s, heads, d]`` under the output gate:
        times ``sigmoid(gate)``, the sigmoid in float32 (as it is without
        one)."""
        if gate is None:
            return out
        with jax.named_scope("attn_gate"):
            return (out * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(
                out.dtype)

    def _visible(self, q_pos, k_pos, windowed):
        """Whether the query at ``q_pos`` sees the key at ``k_pos``."""
        window = self.cfg.sliding_window
        seen = k_pos <= q_pos
        if window and any(self.cfg.window_layers):
            seen = seen & (~windowed | (q_pos - k_pos < window))
        return seen

    def _by_kind(self, windowed, full, window):
        """``window()`` in a window layer, ``full()`` in a full one, each
        under its device scope; a conditional only where the configuration
        has both kinds."""
        kinds = set(self.cfg.window_layers)

        def scoped(name, fn):
            def run():
                with jax.named_scope(name):
                    return fn()
            return run

        full, window = scoped("attn_full", full), scoped("attn_window", window)
        if kinds == {0}:
            return full()
        if kinds == {1}:
            return window()
        return jax.lax.cond(windowed, window, full)

    def _paged_attention(self, q, k, v, cache_positions, block_tables,
                         layer_index, windowed, deterministic):
        """Write this call's keys and values into the layer's pages and
        attend through them; None at the cache's init."""
        from fleetx_tpu.ops.pallas.decode_attention import (
            flash_decode_paged_attention,
            paged_gather_kv,
        )

        cfg = self.cfg
        ps, window = cfg.decode_page_size, cfg.sliding_window
        b, s, width = q.shape[0], q.shape[1], cfg.kv_heads * cfg.head_dim
        is_init = not self.has_variable("cache", "cached_key")
        # one page at the init: ``init_cache`` makes the leaves the flat pool
        ck = self.variable("cache", "cached_key", jnp.zeros, (1, ps, width),
                           q.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (1, ps, width), q.dtype)
        self.variable("cache", "cache_index", lambda: jnp.array(0, jnp.int32))
        if is_init:
            return None
        if cache_positions is None or block_tables is None:
            raise ValueError(
                "a paged decode cache needs cache_positions AND "
                "block_tables (the serving engine threads both)")
        wpos = cache_positions.astype(jnp.int32)               # [b]
        tables = block_tables.astype(jnp.int32)
        if tables.ndim == 3:     # [class, lanes, pages]: 0 full, 1 window
            tables = jnp.where(windowed, tables[1], tables[0])
        own = tables    # no layer's base added: page 0 is the trash page
        tables = tables + jnp.asarray(layer_bases(cfg))[layer_index]
        if k is not None:  # (phase "attend": the caller has written them)
            ck.value, cv.value = write_rows(cfg, ck.value, cv.value, tables,
                                            wpos, k, v)
        k_pool, v_pool = ck.value, cv.value
        n = tables.shape[1]

        if s == 1 and self._flash_decode_ok(None, n * ps, deterministic,
                                            tile_len=ps):
            end = paged_write.decode_end(own, wpos, ps)

            # the kernel's grid follows what a lane can have live
            # (``decode_attention.paged_grid``): a full layer's call walks
            # the table; a window layer's, told the window, walks from the
            # block its window starts in the steps ``window`` rows can touch
            def kernel(starts, max_live):
                return flash_decode_paged_attention(
                    q, k_pool, v_pool, tables=tables, end=end, starts=starts,
                    max_live=max_live)

            return self._by_kind(
                windowed, lambda: kernel(None, None),
                lambda: kernel(jnp.maximum(end - window, 0), window))

        # a prefill chunk (and every call the decode kernel does not take):
        # gather the rows the chunk's queries can see, the whole row for a
        # full layer, the window plus the chunk for a window layer; ONE
        # lane's chunk then runs the kernel ``fleetx_prefill_gqa`` over
        # them where ``_chunk_kernel`` says so, else ``grouped_attention``
        kernel = self._chunk_kernel(b, s)
        q_pos = wpos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

        def dense(first, pages, window):
            held = tables if pages == n else jax.vmap(
                lambda row, at: jax.lax.dynamic_slice(row, (at,), (pages,))
            )(tables, first)
            if kernel:
                # whole key blocks: the layer's trash page behind the last
                # page gathered, at positions no query of the chunk sees
                more = prefill_gqa.padded_rows(pages * ps) // ps - pages
                held = jnp.concatenate([held, jnp.broadcast_to(
                    jnp.asarray(layer_bases(cfg))[layer_index], (b, more))], 1)
            keys = paged_gather_kv(k_pool, held)
            values = paged_gather_kv(v_pool, held)
            if kernel:
                return prefill_gqa.prefill_gqa(
                    q[0], keys[0], values[0], wpos[0], first[0] * ps,
                    window=window)[None]
            k_pos = (first[:, None] * ps
                     + jnp.arange(pages * ps, dtype=jnp.int32)[None, :])
            allowed = self._visible(q_pos[:, :, None], k_pos[:, None, :],
                                    windowed)
            return grouped_attention(q, keys, values, allowed[:, None])

        def in_window():
            pages, first = window_gather(cfg, wpos, s, n)
            return dense(first, pages, window)

        return self._by_kind(
            windowed, lambda: dense(jnp.zeros((b,), jnp.int32), n, None),
            in_window)

    def _chunk_kernel(self, b: int, s: int) -> bool:
        """Whether this call's attention through the cache runs the chunk
        kernel (``ops/pallas/prefill_gqa.py``): where the decode kernels
        run (``use_flash_attention`` on a TPU, or forced on the CPU) outside
        a multi-device mesh, on a shape the kernel takes."""
        return (self.cfg.use_flash_attention and kernels_enabled()
                and self._decode_shard_mesh() is None
                and prefill_gqa.takes(b, s, self.cfg.head_dim,
                                      self.cfg.decode_page_size))


class HybridDecoderLayer(nn.Module):
    """``model.DecoderLayer`` over :class:`HybridSelfAttention`, the router
    of a softmax top-k expert layer reading the block's input where
    ``router_input`` says so. Same call, same parameter names (the router
    stays inside ``moe_mlp``, which is handed its input apart from its
    experts')."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, attn_mask=None, deterministic=True, decode=False,
                 cache_positions=None, block_tables=None, rope=None,
                 expert_stack=None, layer_index=None):
        cfg = self.cfg
        x = _constrain_act(x, cfg)
        y = _layer_norm(cfg, "norm1")(x)
        y = HybridSelfAttention(cfg, name="attn")(
            y, attn_mask, deterministic=deterministic, decode=decode,
            cache_positions=cache_positions, block_tables=block_tables,
            layer_index=layer_index, rope=rope)
        y = _dropout(cfg, "attn_dropout")(y, deterministic=deterministic)
        h = x + y
        y = _layer_norm(cfg, "norm2")(h)
        if cfg.expert_mode and cfg.gate == "softmax_topk":
            from fleetx_tpu.parallel.moe import DroplessMoEMLP

            # the layer's index reaches the expert layer only where the
            # loop carries the cache and so hands the experts' whole stack
            # over (its counters and kernels index the stack with it)
            y = DroplessMoEMLP(cfg, name="moe_mlp")(
                y, decode=decode, expert_stack=expert_stack,
                layer_index=None if expert_stack is None else layer_index,
                router_input=x if cfg.router_input == "block_input" else None)
        elif cfg.expert_mode:
            from fleetx_tpu.parallel.moe import MoEMLP

            y = MoEMLP(cfg, name="moe_mlp")(y)
        else:
            y = MLP(cfg, name="mlp")(y)
        y = _dropout(cfg, "mlp_dropout")(y, deterministic=deterministic)
        return _constrain_act(h + y, cfg)
