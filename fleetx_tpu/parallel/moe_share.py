"""An expert layer that holds a SHARE of the routed experts, beside shared
experts that every token meets: ``DroplessMoEMLP`` (``parallel/moe.py``)
for one chip of a deployment whose every layer is divided over several
(``models/gpt/block_fields.py`` has the fields).

- **The router keeps its published width.** Scores ``sigmoid(x W_g)`` in
  float32 over ALL ``num_routed_experts``; with ``n_group`` > 1 a group's
  score is the sum of its two highest, the ``topk_group`` best groups stay
  and the ``top_k`` largest scores are chosen among what stays (the
  DeepSeek-V3 family's group-limited choice). Under ``use_expert_bias``
  (the family's ``topk_method: "noaux_tc"``) a float32 leaf ``expert_bias``
  ``[num_routed_experts]`` joins the scores for the groups' sums and for
  the choice, NEVER for the weights; without it (A.X-K1) nothing is added.
  Weights: the chosen RAW scores over their sum + 1e-20
  (``norm_topk_prob``) times ``routed_scaling_factor``.
- **This program holds ``num_experts`` of them**, ``[first_expert_held,
  first_expert_held + num_experts)``: the weights ``w_gate / w_up /
  w_down`` ``[held, in, out]``. Only the (token, slot) pairs whose expert is
  held are laid out for the grouped matmuls; the others take no row and no
  kernel step and add nothing. What the absent experts would have added is
  LEFT OUT: the layer's output is this share's part of the routed result.
  Nothing stands in for the other chips or their exchange. With every
  routed expert held and one group the layer is ``DroplessMoEMLP``'s
  ``sigmoid_topk`` without a bias, value for value.
- **Shared experts** (``num_shared_experts`` x ``ffn_hidden_size`` wide, one
  gated MLP) see every token and are added once (scope ``moe_shared``).

- **Gate ``softmax_bias_topk``** (LongCat-Flash): the scores are
  ``softmax(x W_g)`` in float32 over ``num_routed_experts +
  num_zero_experts`` outputs, the choice is the ``top_k`` largest of ``score
  + expert_bias`` (ONE choice function, :func:`group_limited_topk`, with one
  group), and the weights are the chosen RAW scores times
  ``routed_scaling_factor`` (not renormalised unless ``norm_topk_prob``).
- **Zero-compute experts** are the router's LAST ``num_zero_experts``
  outputs: one chosen returns its input, weighed. It holds no weights and
  needs no exchange, so it is computed where the token lives: HERE, for
  every token of the call, whatever share of the routed experts is held
  (as a shared expert is). Its number lies past every held expert's, so
  ``held_row_layout`` gives it no row, no tile and no kernel step; its part
  of the result is ``(sum of the chosen zero experts' weights) * x``, under
  the scope ``moe_zero``.

The counters in the cache's ``moe_stats`` leaf count what THIS program did:
the pairs laid out here and the held experts that had a row
(``GPTExecutor.counters`` reads them as ``moe_{tick,prefill}_pairs`` and
``_experts_read``), and the tiles of rows its kernels walked against the
tiles the static layout laid (``_tiles_walked`` / ``_tiles_laid``); all
the pairs routed are ``rows x top_k`` of a call, which the host knows.
With zero-compute experts the leaf is ``ZERO_WORDS``
wider (:func:`stats_words`): the pairs whose expert is zero-compute, of
ticks and of prefills (``moe_{tick,prefill}_zero_pairs``), and the most
and the fewest routed (non-zero) experts any ONE row of a tick chose
(``moe_tick_routed_pairs_max`` / ``_min``: what a token costs varies). The collection ``routing`` holds the input, ALL
``top_k`` experts chosen (by their routed number), their weights and the
output, for whoever holds the layer to a reference.

Forward only, as the kernels it uses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from fleetx_tpu.models.gpt import model as gpt_model
from fleetx_tpu.parallel.moe import (
    MOE_STATS,
    DroplessMoEMLP,
    _running_count,
)

__all__ = ["GROUP_WORDS", "SharedMoEMLP", "ZERO_WORDS", "extra_counters",
           "group_limited_topk", "held_row_layout", "stats_words",
           "zero_counters"]

# the words a configuration with zero-compute experts adds to a layer's
# ``moe_stats``, after ``MOE_STATS``'s: the zero pairs of ticks and of
# prefills (two words each, low then high), then the most routed
# (non-zero) experts and the most zero-compute experts one row of a tick
# chose (a maximum each: top_k less the second is the fewest routed)
ZERO_WORDS = 6


# the words a share that is exactly ONE ROUTER GROUP (``cfg.held_group``)
# adds after those: the rows of ticks that chose an expert of the held
# group, the tokens a deployment would send this chip (low word, high word)
GROUP_WORDS = 2


def stats_words(cfg) -> int:
    """Words of one layer's ``moe_stats``."""
    return (2 * len(MOE_STATS) * 2
            + (ZERO_WORDS if cfg.num_zero_experts else 0)
            + (GROUP_WORDS if cfg.held_group is not None else 0))


def extra_counters(cfg, words) -> dict:
    """The counters of the words ``[layers, ...]`` behind ``MOE_STATS``'s
    (``serving/model_protocol.py`` adds them): the zero-compute experts'
    (:func:`zero_counters`), then a held group's ``moe_tick_group_tokens``,
    the rows of ticks (free lanes among them, as in ``moe_tick_pairs``) with
    at least one chosen expert in the group held here: the pairs they
    brought are ``moe_tick_pairs``."""
    import numpy as np

    out, at = {}, 0
    if cfg.num_zero_experts:
        out.update(zero_counters(words[:, :ZERO_WORDS], int(cfg.top_k)))
        at = ZERO_WORDS
    if cfg.held_group is not None:
        group = np.asarray(words[:, at:at + GROUP_WORDS]).astype(np.uint64)
        out["moe_tick_group_tokens"] = int(
            (group[:, 0] + (group[:, 1] << np.uint64(32))).sum())
    return out


def zero_counters(words, top_k: int) -> dict:
    """The counters of the ``ZERO_WORDS`` words ``[layers, ZERO_WORDS]``
    (``serving/model_protocol.py`` adds them to ``MOE_STATS``'s)."""
    import numpy as np

    words = np.asarray(words).astype(np.uint64)
    pairs = (words[:, 0:4:2] + (words[:, 1:4:2] << np.uint64(32))).sum(0)
    return {"moe_tick_zero_pairs": int(pairs[0]),
            "moe_prefill_zero_pairs": int(pairs[1]),
            "moe_tick_routed_pairs_max": int(words[:, 4].max()),
            "moe_tick_routed_pairs_min": top_k - int(words[:, 5].max())}


def _shared_expert(t, gate, up, down):
    """The shared expert's gated MLP on every token ``t`` ``[n, h]`` (a
    function of its own: ``perfbench/probe_axk1.py`` plants a fault here,
    as in ``group_limited_topk`` and ``held_row_layout``)."""
    return (jax.nn.silu(t @ gate) * (t @ up)) @ down


def _weighed(scores, ranked):
    """What the chosen experts' weights are taken from: the raw scores
    (``ranked`` carries the selection bias; a fault is planted here)."""
    del ranked
    return scores


def _scored(logits, gate: str):
    """The router's scores, float32: ``sigmoid`` of each output, or under
    ``softmax_bias_topk`` the softmax over ALL of them (zero-compute
    experts among them; a fault is planted here)."""
    if gate == "softmax_bias_topk":
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jax.nn.sigmoid(logits).astype(jnp.float32)  # (whatever its type)


def _zero_experts(tokens, weights, zero):
    """What the chosen zero-compute experts give: each its input, weighed,
    so ``(sum of their weights) * x``, float32 (``zero`` ``[n, k]`` marks
    them; a fault is planted here)."""
    by = jnp.where(zero, weights, 0.0).sum(-1, keepdims=True)
    return by * tokens.astype(jnp.float32)


def group_limited_topk(scores, top_k: int, n_group: int, topk_group: int,
                       bias=None):
    """The ``top_k`` largest of ``scores`` ``[n, E]`` (``+ bias`` ``[E]``,
    where one is given: in the groups' sums and in the choice alike) inside
    the ``topk_group`` groups (of ``n_group`` equal, consecutive ones) whose
    two highest sum highest: ``[n, top_k]`` expert numbers."""
    if bias is not None:
        scores = scores + bias
    if n_group > 1:
        n, experts = scores.shape
        grouped = scores.reshape(n, n_group, experts // n_group)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)     # [n, groups]
        kept = jax.lax.top_k(group_score, topk_group)[1]
        stays = (kept[..., None] == jnp.arange(n_group)).any(-2)
        scores = jnp.where(jnp.repeat(stays, experts // n_group, axis=-1),
                           scores, -jnp.inf)
    return jax.lax.top_k(scores, top_k)[1]


def held_row_layout(topk_idx, first: int, count: int, tm: int):
    """``moe.expert_row_layout`` over the pairs whose expert lies in
    ``[first, first + count)`` alone: ``(dest, src, sizes, tile_expert,
    num_tiles, held)``. ``dest`` of a pair that is not held lies past the
    rows (a scatter drops it, a gather clamps it: its weight is zeroed by
    ``held`` ``[n, k]``); ``sizes`` ``[count]`` and the tiles are of the held
    experts, numbered from 0. The rows are bounded as if every pair were
    held (a static shape, which XLA needs); ``num_tiles`` counts the tiles
    that hold rows and is the bound of the kernels' grid over them, so a
    call steps over those alone. It is at least 1: where NO pair of the
    call is held (a tick of few lanes) the kernels step over tile 0, whose
    rows no pair reads."""
    n, k = topk_idx.shape
    m = n * k
    local = topk_idx.reshape(m).astype(jnp.int32) - first
    held = (local >= 0) & (local < count)
    onehot = ((local[:, None] == jnp.arange(count, dtype=jnp.int32))
              ).astype(jnp.int32)                              # [m, held]
    sizes = onehot.sum(axis=0)
    at = jnp.clip(local, 0, count - 1)
    rank = jnp.take_along_axis(_running_count(onehot), at[:, None],
                               axis=1)[:, 0] - 1
    padded = (sizes + tm - 1) // tm * tm
    ends = jnp.cumsum(padded)
    rows = m if tm == 1 else (-(-(m + count * (tm - 1)) // tm) + 1) * tm
    dest = jnp.where(held, (ends - padded)[at] + rank, rows)
    src = jnp.zeros((rows,), jnp.int32).at[dest].set(
        jnp.arange(m, dtype=jnp.int32) // k, mode="drop")
    first_row = jnp.arange(rows // tm, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        (ends[None, :] <= first_row[:, None]).sum(axis=1), count - 1)
    return (dest, src, sizes, tile_expert.astype(jnp.int32),
            jnp.maximum(ends[-1] // tm, 1), held.reshape(n, k))


class SharedMoEMLP(DroplessMoEMLP):
    """Module docstring. Called as ``DroplessMoEMLP`` is; its three expert
    leaves carry the same names, so the layer loop hands it the same
    ``expert_stack``."""

    @nn.compact
    def __call__(self, x, *, decode: bool = False, layer_index=None,
                 expert_stack=None, router_input=None):
        cfg = self.cfg
        if router_input is not None or cfg.mlp_act != "swiglu":
            raise NotImplementedError(
                "a held share with a router input of its own or a gate "
                "other than SiLU: no test covers it")
        b, s, h = x.shape
        (first, held_n), routed = cfg.experts_held, cfg.routed_experts
        k, f, n, dt = cfg.top_k, cfg.ffn_size, b * s, cfg.dtype
        width = cfg.router_width      # the zero-compute experts come last
        router = nn.DenseGeneral(
            features=width, use_bias=False, dtype=jnp.float32,
            param_dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
            kernel_init=nn.with_logical_partitioning(
                gpt_model.default_kernel_init, ("embed", None)),
            name="router")

        def weight(name, shape, axes):
            return self.param(
                name, nn.with_logical_partitioning(
                    gpt_model.default_kernel_init, axes), shape, jnp.float32)

        w_gate = weight("w_gate", (held_n, h, f), ("expert", "embed", "mlp"))
        w_up = weight("w_up", (held_n, h, f), ("expert", "embed", "mlp"))
        w_down = weight("w_down", (held_n, f, h), ("expert", "mlp", "embed"))
        bias = self._expert_bias(width) if cfg.use_expert_bias else None
        shared_f = cfg.num_shared_experts * f
        if shared_f:
            shared = [weight(name, shape, axes) for name, shape, axes in (
                ("shared_gate", (h, shared_f), ("embed", "mlp")),
                ("shared_up", (h, shared_f), ("embed", "mlp")),
                ("shared_down", (shared_f, h), ("mlp", "embed")))]

        from fleetx_tpu.ops.pallas import moe_gmm
        from fleetx_tpu.ops.pallas.flash_attention import kernels_enabled

        kernel = (decode and expert_stack is not None
                  and cfg.use_flash_attention and kernels_enabled())
        # the tile of the pairs a share sees were routing even
        tm = (moe_gmm.row_tile(max(n * k * held_n // width, 1), held_n)
              if kernel else 1)
        tokens = x.reshape(n, h)
        with jax.named_scope("moe_route"):
            scores = _scored(router(tokens.astype(jnp.float32)), cfg.gate)
            # (without a bias, called as ever: a probe plants its own here)
            topk_idx = group_limited_topk(
                scores, k, cfg.n_group, cfg.topk_group,
                **({} if bias is None else {"bias": bias}))
            weights = jnp.take_along_axis(
                _weighed(scores, scores if bias is None else scores + bias),
                topk_idx, axis=-1)
            if cfg.norm_topk_prob:
                weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
            weights = weights * cfg.routed_scaling_factor
            dest, src, sizes, tile_expert, num_tiles, held = held_row_layout(
                topk_idx, first, held_n, tm)
            rows = tokens.astype(dt)[src]
        probed = (self.is_mutable_collection("routing")
                  and not self.is_initializing())
        if probed:
            self.sow("routing", "input", x)
            self.sow("routing", "experts", topk_idx.reshape(b, s, k))
            self.sow("routing", "weights", weights.reshape(b, s, k))
        # (the pairs laid out HERE, which only the device knows)
        self._count(sizes, sizes.sum(),
                    (num_tiles, len(tile_expert) - (tm > 1)), s, decode,
                    layer_index)
        if cfg.num_zero_experts:
            zero = topk_idx >= routed
            self._count_zero(zero, s, decode, layer_index)
        if cfg.held_group is not None:
            self._count_group(held, s, decode, layer_index)
        with jax.named_scope("moe_experts"):
            if kernel:
                w_gate, w_up, w_down = (w.astype(dt) for w in expert_stack)
                hidden = moe_gmm.grouped_gate_up(
                    rows, w_gate, w_up, tile_expert, num_tiles, tm=tm,
                    layer=layer_index, act="silu")
                out = moe_gmm.grouped_down(hidden, w_down, tile_expert,
                                           num_tiles, tm=tm, layer=layer_index)
            else:
                w_gate, w_up, w_down = (w.astype(dt)
                                        for w in (w_gate, w_up, w_down))
                # rows past the held pairs belong to no group: ragged_dot
                # leaves them zero
                hidden = (jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, sizes))
                          * jax.lax.ragged_dot(rows, w_up, sizes))
                out = jax.lax.ragged_dot(hidden, w_down, sizes)
        with jax.named_scope("moe_route"):
            picked = out[dest].reshape(n, k, h).astype(jnp.float32)
            y = (jnp.where(held[..., None], picked, 0.0)
                 * weights[..., None]).sum(axis=1)
        if shared_f:
            with jax.named_scope("moe_shared"):
                gate, up, down = (w.astype(dt) for w in shared)
                t = tokens.astype(dt)
                y = y + _shared_expert(t, gate, up, down).astype(jnp.float32)
        if cfg.num_zero_experts:
            with jax.named_scope("moe_zero"):
                y = y + _zero_experts(tokens, weights, zero)
        y = y.astype(dt).reshape(b, s, h)
        if probed:
            self.sow("routing", "output", y)
        return y

    def _count_zero(self, zero, seq: int, decode: bool, layer_index):
        """Add the call's zero-compute pairs (``zero`` ``[n, k]`` bool) to
        the layer's ``ZERO_WORDS`` of ``moe_stats``, as ``_count`` adds its
        own; of a tick also the most routed and the most zero-compute
        experts one row chose."""
        if not decode or not self.has_variable("cache", "moe_stats"):
            return
        stats = self.get_variable("cache", "moe_stats")
        layer = () if layer_index is None else (layer_index,)

        def word(i):  # the layer scan carries the whole stack [L, words]
            return (*layer, 2 * len(MOE_STATS) * 2 + i)

        with jax.named_scope("moe_route"):
            each = zero.sum(-1).astype(jnp.uint32)             # a row's
            low = 0 if seq == 1 else 2
            was = stats[word(low)]
            now = was + each.sum()                 # wraps at 2**32 ...
            value = stats.at[word(low)].set(now).at[word(low + 1)].add(
                (now < was).astype(jnp.uint32))    # ... into the high word
            if seq == 1:
                value = value.at[word(4)].max(
                    jnp.uint32(zero.shape[-1]) - each.min()).at[word(5)].max(
                        each.max())
            self.put_variable("cache", "moe_stats", value)

    def _count_group(self, held, seq: int, decode: bool, layer_index):
        """Add a tick's rows that chose an expert of the held group
        (``held`` ``[n, k]`` bool: the pairs laid out here) to the layer's
        ``GROUP_WORDS`` of ``moe_stats``, as ``_count`` adds its own."""
        if not decode or seq != 1 or not self.has_variable("cache",
                                                           "moe_stats"):
            return
        stats = self.get_variable("cache", "moe_stats")
        layer = () if layer_index is None else (layer_index,)
        low = (*layer, stats_words(self.cfg) - GROUP_WORDS)
        high = (*layer, stats_words(self.cfg) - GROUP_WORDS + 1)
        with jax.named_scope("moe_route"):
            was = stats[low]
            now = was + held.any(-1).sum().astype(jnp.uint32)
            # (wraps at 2**32 into the high word, as ``_count_zero``'s)
            self.put_variable("cache", "moe_stats", stats.at[low].set(
                now).at[high].add((now < was).astype(jnp.uint32)))
