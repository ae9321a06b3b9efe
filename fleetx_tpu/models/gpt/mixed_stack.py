"""The decoder stack for layers that do not hold the same parameters
(``layer_types``, ``models/gpt/block_fields.py``): gated short-convolution
layers beside grouped-query attention layers, dense feed-forward layers
before expert layers.

**The form.** ``model.GPTModel._decoder_stack`` scans one flax body over
layers that share one parameter tree; here a convolution layer holds ``[h,
3h] + [h, L] + [h, h]``, an attention layer four projections and two ``[head]``
norms, a dense layer a wide MLP and an expert layer a router and its
experts. The parameters are therefore STACKED BY KIND (``conv``,
``attention``, ``dense``, ``experts``: each the kind's own module's tree
with the kind's layers as the leading axis), and ONE ``lax.scan`` body runs
every layer: it looks the layer's two kinds and its place in each kind's
stack up in constants of the program, and a ``lax.cond`` picks the operator
and another the feed-forward part. So the program holds one body with each
kind in it once, whatever the depth and whatever the order of the kinds
(the published list is not periodic at its end); no layer carries the other
kind's parameters (a tree with both operators in every layer would hold a
dead third of the operators' weights); and the expert kernels index the
experts' own stack, which skips the dense layers. Stacks of the two leading
layers and of the rest would have compiled two loops and fixed where the
dense layers stand. The kinds' modules are the ones the other stacks use
(``hybrid.HybridSelfAttention``, ``model.MLP``, ``parallel/moe.py``
``DroplessMoEMLP``), applied to their slice of the stack.

**State.** A lane keeps two kinds of state in ONE page pool under one block
table (``serving/cache_manager.py``): keys and values in the attention
layers (``cached_key`` / ``cached_value``: the flat pool of ``hybrid.py``,
counted over the attention layers alone), and in every convolution layer
the operator's last ``L - 1`` inputs ``z = B * u`` (``conv_state``). The
convolution layers own TAIL PAGES: page ``p`` of the pool has, in every
convolution layer, ``L - 1`` rows, and ``z`` of position ``t`` is kept in row
``t % (L - 1)`` of the page that holds position ``t``. A call reads the
state it starts from through the table at the positions just before its
first, and writes, of its own positions, the last ``L - 1`` of every page
it touches. A page that is full therefore holds the state as it stands at
the page's end, and keeps it as long as the page lives: a prompt that
matches a prefix up to any page boundary starts every convolution layer
from what a prefill from the start would hold there, with nothing copied
and no other bookkeeping than the page's own (its refcount, its parking,
its eviction). A separate state of the lane plus snapshots copied into the
pages at registration would have needed a copy program, a lane install
and a second lifecycle to keep in step with the first. The rows are written
before they are read, as keys and values are, so a recycled page needs no
zeroing; a call's rows that are no tokens (a padded bucket's tail, a lane
that is not decoding) write nothing: the call is handed which rows are
tokens, as its ``attn_mask`` ``[batch, rows]``.

Forward only: training this stack is ROADMAP R5.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from fleetx_tpu.models.gpt.hybrid import (
    HybridSelfAttention,
    layer_bases,
    write_rows,
)
from fleetx_tpu.models.gpt.model import (
    MLP,
    GPTConfig,
    _constrain_act,
    _dense,
)

__all__ = ["MixedStack", "ShortConv", "layer_plan", "state_rows"]


def layer_plan(cfg: GPTConfig) -> dict:
    """Each layer's two kinds and its place among its kind, as arrays of
    ``num_layers`` entries: ``attention`` (1: attention, 0: convolution),
    ``operator_index``, ``experts`` (1: expert layer, 0: dense),
    ``ffn_index``; and the four counts."""
    attention = np.asarray([t == "full_attention" for t in cfg.layer_types])
    experts = np.arange(cfg.num_layers) >= cfg.num_dense_layers

    def place(mask):  # the layer's index among the layers of its own kind
        return np.where(mask, np.cumsum(mask) - 1, np.cumsum(~mask) - 1)

    return {"attention": attention.astype(np.int32),
            "operator_index": place(attention).astype(np.int32),
            "experts": experts.astype(np.int32),
            "ffn_index": place(experts).astype(np.int32),
            "counts": {"conv": int((~attention).sum()),
                       "attention": int(attention.sum()),
                       "dense": int((~experts).sum()),
                       "experts": int(experts.sum())}}


def state_rows(cfg: GPTConfig) -> int:
    """Rows of one tail page: the operator's state, ``conv_L_cache - 1``."""
    return cfg.conv_L_cache - 1


def _torch_conv_init(key, shape, dtype=jnp.float32):
    """A depthwise filter as ``torch.nn.Conv1d`` draws it: uniform in
    ``+-1/sqrt(taps)`` (each channel's fan-in is its taps)."""
    bound = 1.0 / np.sqrt(shape[-1])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _thirds(projected):
    """``B, C, u`` of the input projection: its thirds, in this order."""
    return jnp.split(projected, 3, axis=-1)


class ShortConv(nn.Module):
    """The gated short convolution: ``B, C, u = split(in_proj(a), 3)``; ``z
    = B * u``; ``c_t = sum_i w[:, i] * z_{t - (L - 1) + i}`` (depthwise,
    causal, ``L = conv_L_cache`` taps, the last tap on the position itself);
    ``out_proj(C * c)``. No bias, no activation of its own. ``state`` ``[b,
    L - 1, h]`` is ``z`` at the positions before the call's first (zeros
    before a sequence's start); returns the output and ``z`` of the state
    and the call together ``[b, L - 1 + s, h]``."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, a, state):
        cfg = self.cfg
        h, taps, s = cfg.hidden_size, cfg.conv_L_cache, a.shape[1]
        bcu = _dense(3 * h, ("embed", "mlp"), "in_proj", use_bias=False,
                     dtype=cfg.dtype)(a)
        gate_in, gate_out, u = _thirds(bcu)
        w = self.param("conv_kernel", nn.with_logical_partitioning(
            _torch_conv_init, ("embed", None)), (h, taps), jnp.float32)
        z = jnp.concatenate([state.astype(bcu.dtype), gate_in * u], axis=1)
        mixed = sum(w[:, i] * z[:, i:i + s].astype(jnp.float32)
                    for i in range(taps))
        y = (gate_out.astype(jnp.float32) * mixed).astype(cfg.dtype)
        return _dense(h, ("mlp", "embed"), "out_proj", use_bias=False,
                      dtype=cfg.dtype)(y), z


def _stacked(module, count: int, rng, *example, **kwargs):
    """``module``'s parameter tree drawn ``count`` times, stacked along a
    new leading axis (plain arrays: the kinds' own partitioning boxes name
    no layer axis)."""
    def one(key):
        return nn.meta.unbox(module.init(key, *example, **kwargs)["params"])

    return jax.vmap(one)(jax.random.split(rng, count))


def _norm(cfg: GPTConfig):
    """The RMSNorm before an operator or a feed-forward part, detached."""
    return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                      param_dtype=jnp.float32, parent=None)


def _at(tree, index):
    return jax.tree.map(lambda leaf: leaf[index], tree)


class MixedStack(nn.Module):
    """The layers of a configuration with ``layer_types`` (module
    docstring), called as ``GPTModel._decoder_stack`` is. In a cached
    forward ``attn_mask`` is not a key mask but ``[batch, rows]`` bool:
    which of the call's rows are tokens (None: all)."""

    cfg: GPTConfig

    def _kinds(self):
        """The four kinds' modules (detached: their parameters are slices
        of this module's stacks) with an example input each."""
        cfg = self.cfg
        x = jnp.zeros((1, 1, cfg.hidden_size), cfg.dtype)
        dense_cfg = dataclasses.replace(
            cfg, ffn_hidden_size=cfg.dense_ffn_hidden_size or cfg.ffn_size)
        from fleetx_tpu.parallel.moe import DroplessMoEMLP

        rope = (jnp.ones((1, 1, cfg.head_dim // 2), jnp.float32),) * 2
        return {
            "conv": (ShortConv(cfg, parent=None),
                     (x, jnp.zeros((1, state_rows(cfg), cfg.hidden_size),
                                   cfg.dtype)), {}),
            "attention": (HybridSelfAttention(cfg, parent=None), (x,),
                          {"layer_index": jnp.int32(0), "rope": rope}),
            "dense": (MLP(dense_cfg, parent=None), (x,), {}),
            "experts": (DroplessMoEMLP(cfg, parent=None), (x,), {}),
        }

    @nn.compact
    def __call__(self, x, attn_mask=None, *, deterministic=True, decode=False,
                 cache_positions=None, block_tables=None, rope=None):
        cfg = self.cfg
        plan, kinds = layer_plan(cfg), self._kinds()
        params = {}
        for name, (module, args, kwargs) in kinds.items():
            count = plan["counts"][name]
            if not count:
                continue
            params[name] = self.param(
                name, lambda rng, m=module, n=count, a=args, k=kwargs: {
                    "norm": _stacked(_norm(cfg), n, rng, a[0]),
                    "op": _stacked(m, n, rng, *a, **k)})
        cache = self._cache(decode, plan)
        if decode and cache is not None and (cache_positions is None
                                             or block_tables is None):
            raise ValueError("a paged decode cache needs cache_positions AND "
                             "block_tables (the serving engine threads both)")
        return self._decoder_stack(
            x, params, cache, plan, kinds, rows=attn_mask if decode else None,
            key_mask=None if decode else attn_mask,
            deterministic=deterministic, cache_positions=cache_positions,
            block_tables=block_tables, rope=rope)

    def _cache(self, decode: bool, plan: dict):
        """The cache collection's variables (None outside a cached forward
        and at its init, which only declares them): the attention layers'
        flat pool (``hybrid.init_cache`` sizes it), the convolution layers'
        tail pages, the expert layers' counters."""
        cfg = self.cfg
        if not decode:
            return None
        if cfg.decode_num_pages is None:
            raise NotImplementedError(
                "a contiguous decode cache over layers with a convolution "
                "state (one-shot generate()): serve the model through "
                "ServingEngine, whose page pool holds both kinds of state")
        from fleetx_tpu.parallel.moe import MOE_STATS

        ps, rows = cfg.decode_page_size, state_rows(cfg)
        if ps % rows:
            raise ValueError(f"decode_page_size {ps} is no multiple of the "
                             f"convolution state's {rows} rows")
        fresh = not self.has_variable("cache", "cached_key")
        width, counts = cfg.kv_heads * cfg.head_dim, plan["counts"]
        held = {
            "cached_key": self.variable(
                "cache", "cached_key", jnp.zeros, (1, ps, width), cfg.dtype),
            "cached_value": self.variable(
                "cache", "cached_value", jnp.zeros, (1, ps, width), cfg.dtype),
            "conv_state": self.variable(
                "cache", "conv_state", jnp.zeros,
                (max(counts["conv"], 1) * cfg.decode_num_pages, rows,
                 cfg.hidden_size), cfg.dtype),
            "moe_stats": self.variable(
                "cache", "moe_stats", jnp.zeros,
                (max(counts["experts"], 1), 2 * len(MOE_STATS) * 2),
                jnp.uint32),
        }
        return None if fresh else held

    def _decoder_stack(self, x, params, cache, plan, kinds, *, rows, key_mask,
                       deterministic, cache_positions, block_tables, rope):
        """One scanned body over the layers (the method's name is the one
        the device trace's readers know the layer loop by)."""
        cfg, counts = self.cfg, plan["counts"]
        cached = cache is not None
        probed = (self.is_mutable_collection("routing")
                  and not self.is_initializing())
        pools = {k: v.value for k, v in cache.items()} if cached else {}
        b, s, h = x.shape
        state_shape = (b, state_rows(cfg), h)
        if cached:
            tables = block_tables.astype(jnp.int32)
            wpos = cache_positions.astype(jnp.int32)
            rows = (jnp.ones((b, s), bool) if rows is None
                    else rows.astype(bool))
        norm = _norm(cfg)
        conv_op, attn_op = kinds["conv"][0], kinds["attention"][0]
        both = (counts["attention"], counts["conv"])

        def normed(kind, index, value):
            return norm.apply({"params": _at(params[kind]["norm"], index)},
                              value)

        def pick(flag, counts, yes, no, *args):
            """``yes`` or ``no`` by the layer's kind; a conditional only
            where the configuration has both."""
            if not counts[0]:
                return no(*args)
            if not counts[1]:
                return yes(*args)
            return jax.lax.cond(flag, yes, no, *args)

        def conv(value, index, state_pool=None):
            """The convolution's output and ``z`` of the call's rows; the
            state it starts from read from ``state_pool``, or zeros."""
            a = normed("conv", index, value)
            with jax.named_scope("cache_write"), jax.named_scope("conv_state"):
                state = (jnp.zeros(state_shape, cfg.dtype)
                         if state_pool is None else
                         _read_state(cfg, state_pool, tables, wpos, index))
            with jax.named_scope("conv_mix"):
                y, z = conv_op.apply(
                    {"params": _at(params["conv"]["op"], index)}, a, state)
            return y, z[:, state_shape[1]:]

        def attention(value, index, *args, **kwargs):
            return attn_op.apply(
                {"params": _at(params["attention"]["op"], index),
                 **kwargs.pop("variables", {})}, value, *args,
                deterministic=deterministic, layer_index=index, **kwargs)

        def operator(value, mixes, index, pools):
            """The operator over the pool, in three steps: a conditional
            that computes (the convolution whole, reading its state; the
            attention's queries, keys and values), the writes of BOTH kinds
            of state outside every conditional (the other kind's rows write
            nothing), and a conditional that attends. A pool that a
            conditional hands back is copied whole by XLA: 1.3 GB in every
            attention layer at the served sizes."""
            heads = (b, s, cfg.num_attention_heads, cfg.head_dim)
            kv = (b, s, cfg.kv_heads * cfg.head_dim)

            def conv_step():
                y, z = conv(value, index, pools["conv_state"])
                return (y, z, jnp.zeros(heads, cfg.dtype),
                        jnp.zeros(kv, cfg.dtype), jnp.zeros(kv, cfg.dtype))

            def project_step():
                return (jnp.zeros_like(value), jnp.zeros_like(value),
                        *attention(normed("attention", index, value), index,
                                   rope=rope, phase="project"))

            y, z, q, k, v = pick(mixes, both, project_step, conv_step)
            pools = dict(pools)
            if counts["conv"]:
                with jax.named_scope("cache_write"), \
                        jax.named_scope("conv_state"):
                    pools["conv_state"] = _write_state(
                        cfg, pools["conv_state"], tables, wpos,
                        rows & ~mixes, index, z)
            if counts["attention"]:
                pools["cached_key"], pools["cached_value"] = write_rows(
                    cfg, pools["cached_key"], pools["cached_value"],
                    tables + jnp.asarray(layer_bases(cfg))[index], wpos, k, v,
                    keep=mixes)

            def attend_step():
                return attention(
                    q, index, decode=True, cache_positions=wpos,
                    block_tables=tables, phase="attend", mutable=["cache"],
                    variables={"cache": {n: pools[n] for n in (
                        "cached_key", "cached_value")}})[0]

            return pick(mixes, both, attend_step, lambda: y), pools

        def plain(value, mixes, index, pools):
            """The operator outside a cache: every position at once."""
            return pick(
                mixes, both,
                lambda: attention(normed("attention", index, value), index,
                                  key_mask, rope=rope),
                lambda: conv(value, index)[0]), pools

        def dense(value, index, stats):
            y = kinds["dense"][0].apply(
                {"params": _at(params["dense"]["op"], index)},
                normed("dense", index, value))
            # where an expert layer gives its routing a dense layer gives
            # zeros of the same shapes: the two are branches of one conditional
            return y, stats, jax.tree.map(
                lambda t: jnp.zeros(t.shape, t.dtype),
                jax.eval_shape(lambda: experts(value, index, stats)[2]))

        def experts(value, index, stats):
            held = params["experts"]["op"]
            variables = {"params": _at(held, index)}
            mutable = ["routing"] if probed else []
            if cached:
                variables["cache"] = {"moe_stats": stats}
                mutable.append("cache")
            with jax.named_scope("moe_mlp"):
                y, mut = kinds["experts"][0].apply(
                    variables, normed("experts", index, value),
                    decode=cached, layer_index=index if cached else None,
                    expert_stack=tuple(held[k] for k in (
                        "w_gate", "w_up", "w_down")) if cached else None,
                    mutable=mutable)
            if cached:
                stats = mut["cache"]["moe_stats"]
            sown = ({k: v[0] for k, v in mut["routing"].items()}
                    if probed else {})
            return y, stats, sown

        def body(carry, layer):
            value, pools = carry[0], dict(carry[1])
            value = _constrain_act(value, cfg)
            with jax.named_scope("layer"):
                stats = pools.pop("moe_stats", None)
                with jax.named_scope("attn"):
                    y, pools = (operator if cached else plain)(
                        value, jnp.asarray(plan["attention"])[layer] == 1,
                        jnp.asarray(plan["operator_index"])[layer], pools)
                value = value + y
                with jax.named_scope("mlp"):
                    y, stats, sown = pick(
                        jnp.asarray(plan["experts"])[layer] == 1,
                        (counts["experts"], counts["dense"]), experts, dense,
                        value, jnp.asarray(plan["ffn_index"])[layer], stats)
                if cached:
                    pools["moe_stats"] = stats
            return (_constrain_act(value + y, cfg), pools), sown

        (x, pools), sown = jax.lax.scan(
            body, (x, pools), jnp.arange(cfg.num_layers, dtype=jnp.int32))
        for name, leaf in pools.items():
            cache[name].value = leaf
        if probed:  # the expert layers' rows, as a layer scan would stack them
            for name, leaf in sown.items():
                self.sow("routing", name, leaf[cfg.num_dense_layers:])
        return x


def _pages(cfg: GPTConfig, tables, pos, index):
    """The tail page of every position ``pos`` ``[b, n]`` of the
    convolution layer ``index`` (each layer's pages follow the last's)."""
    page = jnp.take_along_axis(
        tables, jnp.maximum(pos, 0) // cfg.decode_page_size, axis=1)
    return page + index * cfg.decode_num_pages


def _read_state(cfg: GPTConfig, pool, tables, wpos, index):
    """``z`` at the ``L - 1`` positions before ``wpos`` ``[b]``, oldest
    first, zeros before position 0: ``[b, L - 1, h]``."""
    rows = state_rows(cfg)
    pos = wpos[:, None] - jnp.arange(rows, 0, -1, dtype=jnp.int32)[None, :]
    held = pool[_pages(cfg, tables, pos, index), jnp.maximum(pos, 0) % rows]
    return jnp.where((pos >= 0)[..., None], held, 0)


def _write_state(cfg: GPTConfig, pool, tables, wpos, valid, index, z):
    """``pool`` with ``z`` ``[b, s, h]`` of the call's positions ``wpos +
    [0, s)`` written where they stay the state: of the rows that are tokens
    (``valid``, a prefix of each lane's rows), the last ``L - 1`` of every
    page touched. The others write nothing (their index lies past the
    pool)."""
    rows, ps = state_rows(cfg), cfg.decode_page_size
    b, s, h = z.shape
    max_len = cfg.decode_cache_len or cfg.max_position_embeddings
    pos = wpos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    last = wpos + valid.sum(axis=1).astype(jnp.int32) - 1         # [b]
    page_end = (pos // ps + 1) * ps - 1
    keep = (valid & (pos + rows > jnp.minimum(last[:, None], page_end))
            & (pos < max_len))
    page = jnp.where(keep, _pages(cfg, tables, jnp.minimum(pos, max_len - 1),
                                  index), pool.shape[0])
    return pool.at[page.reshape(-1), (pos % rows).reshape(-1)].set(
        z.reshape(b * s, h), mode="drop")
