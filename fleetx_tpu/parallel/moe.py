"""Mixture-of-Experts layer with expert parallelism — GShard-style dense
dispatch/combine einsums.

Capability parity with the reference MoE stack (/root/reference/ppfleetx/
distributed/moe/moe_layer.py:33-235 ``MoELayer`` + comm_ops.py ``MoEScatter``/
``MoEGather`` + gate/*.py ``NaiveGate``/``GShardGate``/``SwitchGate`` +
utils.py ``limit_by_capacity``), redesigned TPU-first: instead of explicit
count_by_gate + NCCL all-to-all scatter/gather, routing builds dispatch and
combine tensors and three einsums move tokens; with expert weights sharded
over the ('dp','fsdp') mesh axes GSPMD lowers the einsums to exactly the
all-to-all exchange the reference hand-writes. Capacity dropping, top-k
weighting, aux balance loss, and gate-noise semantics are preserved.

Gates:
- naive   — top-k softmax, no capacity drop (naive_gate.py:28)
- gshard  — top-2, capacity, aux balance loss, probabilistic 2nd-expert
            (random routing, gshard_gate.py:29-73)
- switch  — top-1, capacity, jitter noise, switch balance loss
            (switch_gate.py:29)

``gate: softmax_topk`` is another layer, :class:`DroplessMoEMLP` (the
OLMoE / Mixtral kind): softmax over all experts in float32, the ``top_k``
largest kept, NO capacity and no dropped token, gated-SiLU experts
without biases. Its tokens are sorted by expert and the three expert
matmuls are grouped matmuls over the sorted rows: at 64 experts and 8 a
token a dense ``[n, E, C]`` dispatch is seven eighths zeros in prefill and
reads every expert for one token in decode.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from fleetx_tpu.models.gpt import model as gpt_model

__all__ = ["DroplessMoEMLP", "MoEMLP", "compute_routing",
           "compute_routing_indices", "expert_row_layout"]


def _balance_loss(gate_probs: jax.Array, expert_mask: jax.Array) -> jax.Array:
    """GShard/Switch auxiliary load-balance loss:
    E * sum_e mean(prob_e) * mean(assigned_e)."""
    num_experts = gate_probs.shape[-1]
    density = expert_mask.mean(axis=0)  # fraction of tokens per expert
    density_proxy = gate_probs.mean(axis=0)  # mean router prob per expert
    return num_experts * jnp.sum(density * density_proxy)


def compute_routing_indices(
    gate_logits: jax.Array,  # [n_tokens, E]
    top_k: int,
    capacity: int,
    gate_type: str = "gshard",
    rng: Optional[jax.Array] = None,
):
    """Sparse routing decisions: per (token, slot) the chosen expert, its
    queue position, the combine weight, and the keep flag, plus the aux
    balance loss. Tokens beyond an expert's capacity are dropped (reference
    limit_by_capacity, moe/utils.py:125). O(n*k) memory — the scalable form
    both dispatch implementations derive from."""
    n, num_experts = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    topk_probs, topk_idx = jax.lax.top_k(probs, top_k)

    if gate_type == "gshard" and top_k >= 2 and rng is not None:
        # random routing: 2nd expert kept with prob proportional to its gate
        # weight (reference gshard_gate.py:67-72)
        keep2 = jax.random.uniform(rng, (n,)) < (2.0 * topk_probs[:, 1])
        topk_probs = topk_probs.at[:, 1].set(
            jnp.where(keep2, topk_probs[:, 1], 0.0)
        )

    # normalize kept weights
    denom = jnp.maximum(topk_probs.sum(axis=-1, keepdims=True), 1e-9)
    topk_weights = topk_probs / denom

    # aux loss uses the top-1 assignment mask (Switch/GShard convention)
    top1_mask = jax.nn.one_hot(topk_idx[:, 0], num_experts)
    aux = _balance_loss(probs, top1_mask)

    # queue position of each (token, slot) in its expert, slots filled in
    # priority order (slot 0 of all tokens first — GShard convention)
    pos = jnp.zeros((n, top_k), jnp.int32)
    keep = jnp.zeros((n, top_k), jnp.bool_)
    fill = jnp.zeros((num_experts,), jnp.int32)
    for slot in range(top_k):
        e = topk_idx[:, slot]
        onehot = jax.nn.one_hot(e, num_experts, dtype=jnp.int32)
        pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot) + fill[None, :]
        p = jnp.take_along_axis(pos_in_expert, e[:, None], axis=1)[:, 0]
        k = (p < capacity) & (topk_weights[:, slot] > 0)
        pos = pos.at[:, slot].set(jnp.clip(p, 0, capacity - 1))
        keep = keep.at[:, slot].set(k)
        fill = fill + onehot.sum(axis=0)

    return topk_idx, pos, topk_weights, keep, aux


def compute_routing(
    gate_logits: jax.Array,  # [n_tokens, E]
    top_k: int,
    capacity: int,
    gate_type: str = "gshard",
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dense form: (dispatch [n, E, C] bool, combine [n, E, C] float,
    aux_loss), materialized from the sparse decisions. Memory scales as
    n*E*C — fine for small expert counts, use the index path at scale."""
    n, num_experts = gate_logits.shape
    topk_idx, pos, topk_weights, keep, aux = compute_routing_indices(
        gate_logits, top_k, capacity, gate_type, rng
    )
    dispatch = jnp.zeros((n, num_experts, capacity), jnp.bool_)
    combine = jnp.zeros((n, num_experts, capacity), jnp.float32)
    rows = jnp.arange(n)
    for slot in range(topk_idx.shape[1]):
        e = topk_idx[:, slot]
        p = pos[:, slot]
        k = keep[:, slot]
        dispatch = dispatch.at[rows, e, p].max(k)
        combine = combine.at[rows, e, p].add(
            jnp.where(k, topk_weights[:, slot], 0.0)
        )
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense MLP inside a decoder layer
    (reference ExpertLayer + MoELayer wiring, single_model.py:45-65,433-444).

    Expert FFN weights are stacked [E, ...] with the 'expert' logical axis
    sharded over the data axes; per-expert compute is batched einsum."""

    cfg: "gpt_model.GPTConfig"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        b, s, h = x.shape
        f = cfg.ffn_size
        E = cfg.num_experts
        n = b * s
        # switch gate is top-1 regardless of cfg.top_k; capacity must use the
        # effective k or switch capacity doubles vs the reference semantics
        eff_top_k = 1 if cfg.gate == "switch" else cfg.top_k
        capacity = max(1, int(cfg.capacity_factor * n * eff_top_k / E))

        tokens = x.reshape(n, h)

        gate_logits = nn.DenseGeneral(
            features=E,
            use_bias=False,
            dtype=jnp.float32,
            param_dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                gpt_model.default_kernel_init, ("embed", None)
            ),
            name="gate",
        )(tokens.astype(jnp.float32))

        if cfg.gate == "switch" and self.has_rng("dropout"):
            # switch jitter noise
            noise = jax.random.uniform(
                self.make_rng("dropout"), gate_logits.shape, minval=0.98, maxval=1.02
            )
            gate_logits = gate_logits * noise

        rng = self.make_rng("dropout") if (cfg.gate == "gshard" and self.has_rng("dropout")) else None
        mode = getattr(cfg, "moe_dispatch", "auto")
        if mode not in ("auto", "einsum", "scatter"):
            raise ValueError(
                f"moe_dispatch={mode!r}; choose auto | einsum | scatter")
        if mode == "auto":
            # dense masks cost n*E*C floats; the scatter path costs n*h
            # gathers — switch over when the masks would exceed the
            # activations they route (capacity is ~n*k/E, so the dense form
            # grows quadratically in tokens)
            mode = "scatter" if n * E * capacity > 8 * n * h else "einsum"

        def ffn_param(name, shape, axes):
            return self.param(
                name,
                nn.with_logical_partitioning(gpt_model.default_kernel_init, axes),
                shape,
                jnp.float32,
            )

        w_up = ffn_param("w_up", (E, h, f), ("expert", "embed", "mlp"))
        b_up = ffn_param("b_up", (E, f), ("expert", "mlp"))
        w_down = ffn_param("w_down", (E, f, h), ("expert", "mlp", "embed"))
        b_down = ffn_param("b_down", (E, h), ("expert", "embed"))

        dt = cfg.dtype
        if mode == "scatter":
            # index dispatch (reference MoEScatter/MoEGather all-to-all
            # semantics, comm_ops.py:28-118): scatter-add tokens into the
            # per-expert buffers, gather weighted results back. GSPMD lowers
            # the token->expert reshuffle to the all-to-all the reference
            # hand-writes; no [n, E, C] mask is ever materialized.
            topk_idx, pos, weights, keep, aux = compute_routing_indices(
                gate_logits, eff_top_k, capacity, cfg.gate, rng
            )
            self.sow("intermediates", "balance_loss", aux)
            buf = jnp.zeros((E * capacity, h), dt)
            for slot in range(eff_top_k):
                flat = topk_idx[:, slot] * capacity + pos[:, slot]
                contrib = tokens.astype(dt) * keep[:, slot, None].astype(dt)
                buf = buf.at[flat].add(contrib)
            expert_in = buf.reshape(E, capacity, h)
            hidden = jax.nn.gelu(
                jnp.einsum("ech,ehf->ecf", expert_in, w_up.astype(dt))
                + b_up[:, None, :].astype(dt),
                approximate=True,
            )
            expert_out = (
                jnp.einsum("ecf,efh->ech", hidden, w_down.astype(dt))
                + b_down[:, None, :].astype(dt)
            ).reshape(E * capacity, h)
            out = jnp.zeros((n, h), dt)
            for slot in range(eff_top_k):
                flat = topk_idx[:, slot] * capacity + pos[:, slot]
                w = (weights[:, slot] * keep[:, slot]).astype(dt)[:, None]
                out = out + expert_out[flat] * w
            return out.reshape(b, s, h)

        dispatch, combine, aux = compute_routing(
            gate_logits, eff_top_k, capacity, cfg.gate, rng
        )
        self.sow("intermediates", "balance_loss", aux)
        expert_in = jnp.einsum(
            "nh,nec->ech", tokens.astype(dt), dispatch.astype(dt)
        )
        hidden = jax.nn.gelu(
            jnp.einsum("ech,ehf->ecf", expert_in, w_up.astype(dt))
            + b_up[:, None, :].astype(dt),
            approximate=True,
        )
        expert_out = (
            jnp.einsum("ecf,efh->ech", hidden, w_down.astype(dt))
            + b_down[:, None, :].astype(dt)
        )
        out = jnp.einsum(
            "ech,nec->nh", expert_out, combine.astype(dt)
        )
        return out.reshape(b, s, h)


# per layer, in the decode cache's ``moe_stats`` leaf: for one-token calls
# (a decode tick) and for longer ones (a prefill) the calls, the
# token-expert pairs routed, the experts that had a row, the rows of the
# largest expert, the tiles of rows the grouped matmuls walked (the ones
# that held rows: their grid's bound) and the tiles the static layout laid,
# each summed over the calls as a count of two uint32 words, low then high
# (serving/model_protocol.py reads them)
MOE_STATS = ("calls", "pairs", "experts_read", "largest_load",
             "tiles_walked", "tiles_laid")


# rows of one block of ``_running_count``
_COUNT_BLOCK = 128


def _running_count(onehot: jax.Array) -> jax.Array:
    """Inclusive cumulative sum of ``onehot`` ``[m, E]`` (0/1, int32) along
    its rows, taken inside blocks of 128 rows by a product with a triangle
    of ones (exact: whole numbers under 2**24 in float32) plus the blocks
    before. XLA's cumulative sum is a ``reduce-window`` over the whole
    column: over the thousands of pairs a prefill chunk routes it took 1.6%
    of the device's time in the SmallThinker cell, outside every scope (the
    compiler drops its name)."""
    m, experts = onehot.shape
    blocks = -(-m // _COUNT_BLOCK)
    x = jnp.pad(onehot, ((0, blocks * _COUNT_BLOCK - m), (0, 0))).reshape(
        blocks, _COUNT_BLOCK, experts).astype(jnp.float32)
    triangle = jnp.tril(jnp.ones((_COUNT_BLOCK, _COUNT_BLOCK), jnp.float32))
    inside = jnp.einsum("ij,bje->bie", triangle, x,
                        precision=jax.lax.Precision.HIGHEST)
    sums = x.sum(axis=1)
    before = jnp.cumsum(sums, axis=0) - sums
    return (inside + before[:, None, :]).reshape(-1, experts)[:m].astype(
        jnp.int32)


def expert_row_layout(topk_idx: jax.Array, num_experts: int, tm: int):
    """Where each (token, slot) pair goes when rows are sorted by expert
    and every expert's group is padded to whole ``tm``-row tiles (``tm`` 1:
    the plain sorted order ``jax.lax.ragged_dot`` takes).

    ``topk_idx`` ``[n, k]``. Returns ``(dest, src, sizes, tile_expert,
    num_tiles)``: ``dest`` ``[n*k]`` the row of each pair, ``src``
    ``[rows]`` the token each row holds (padding rows hold token 0),
    ``sizes`` ``[E]`` the pairs of each expert, ``tile_expert`` ``[rows //
    tm]`` and ``num_tiles`` as ``ops/pallas/moe_gmm.py`` takes them.
    ``rows`` is static: ``n*k + E*(tm-1)`` rounded up to a tile, plus one
    spare tile when ``tm > 1``. No sort: a pair's rank inside its expert
    is a running count."""
    n, k = topk_idx.shape
    m = n * k
    flat = topk_idx.reshape(m).astype(jnp.int32)
    onehot = (flat[:, None] == jnp.arange(num_experts, dtype=jnp.int32)
              ).astype(jnp.int32)                           # [m, E]
    sizes = onehot.sum(axis=0)
    rank = jnp.take_along_axis(_running_count(onehot), flat[:, None],
                               axis=1)[:, 0] - 1
    padded = (sizes + tm - 1) // tm * tm
    ends = jnp.cumsum(padded)
    dest = (ends - padded)[flat] + rank
    rows = m if tm == 1 else (-(-(m + num_experts * (tm - 1)) // tm) + 1) * tm
    src = jnp.zeros((rows,), jnp.int32).at[dest].set(
        jnp.arange(m, dtype=jnp.int32) // k, unique_indices=True)
    tiles = rows // tm
    num_tiles = ends[-1] // tm
    first_row = jnp.arange(tiles, dtype=jnp.int32) * tm
    tile_expert = jnp.minimum(
        (ends[None, :] <= first_row[:, None]).sum(axis=1), num_experts - 1)
    return dest, src, sizes, tile_expert.astype(jnp.int32), num_tiles


class DroplessMoEMLP(nn.Module):
    """Top-k experts without capacity (module docstring):
    ``y = sum over the top_k chosen e of p_e * down_e(silu(gate_e(x)) *
    up_e(x))``, ``p = softmax(router(x))`` over all experts in float32, the
    weights left as they are unless ``cfg.norm_topk_prob`` (gate
    ``softmax_topk``), or sigmoid scores chosen under a bias
    (``sigmoid_topk``: :meth:`_sigmoid_topk`). ``mlp_act:
    reglu`` makes the gate ``relu``; ``router_input`` is what the router
    reads where that is not ``x`` (a router placed before attention reads
    the block's input, its experts the normed post-attention stream).

    Handed ``expert_stack`` in a cached forward on a TPU, the grouped
    matmuls are the Pallas kernels of ``ops/pallas/moe_gmm.py`` (no
    gradient); everywhere else ``jax.lax.ragged_dot``. Scopes ``moe_route``
    and ``moe_experts`` mark the two halves on the device trace
    (docs/OBSERVABILITY.md).

    ``expert_stack`` is the three expert weights of ALL layers as the
    layer loop holds them, ``[layers, experts, in, out]``, with
    ``layer_index`` this layer's place in them
    (``GPTModel._expert_stack``): the kernels pick the layer themselves,
    where the loop's own slice would be copied first, so they take the
    stack and nothing else."""

    cfg: "gpt_model.GPTConfig"

    @nn.compact
    def __call__(self, x: jax.Array, *, decode: bool = False,
                 layer_index=None, expert_stack=None,
                 router_input=None) -> jax.Array:
        cfg = self.cfg
        b, s, h = x.shape
        # the gate's activation: ``reglu`` is ``relu(gate) * up``
        act = "relu" if cfg.mlp_act == "reglu" else "silu"
        E, k, f, n, dt = cfg.num_experts, cfg.top_k, cfg.ffn_size, b * s, cfg.dtype
        router = nn.DenseGeneral(
            features=E, use_bias=False, dtype=jnp.float32,
            param_dtype=jnp.float32, precision=jax.lax.Precision.HIGHEST,
            kernel_init=nn.with_logical_partitioning(
                gpt_model.default_kernel_init, ("embed", None)),
            name="router")

        def experts(name, shape, axes):
            return self.param(
                name, nn.with_logical_partitioning(
                    gpt_model.default_kernel_init, axes), shape, jnp.float32)

        w_gate = experts("w_gate", (E, h, f), ("expert", "embed", "mlp"))
        w_up = experts("w_up", (E, h, f), ("expert", "embed", "mlp"))
        w_down = experts("w_down", (E, f, h), ("expert", "mlp", "embed"))

        from fleetx_tpu.ops.pallas import moe_gmm
        from fleetx_tpu.ops.pallas.flash_attention import kernels_enabled

        kernel = (decode and expert_stack is not None
                  and cfg.use_flash_attention and kernels_enabled())
        tm = moe_gmm.row_tile(n * k, E) if kernel else 1
        tokens = x.reshape(n, h)
        # the router reads the experts' input unless it is handed one of
        # its own (``router_input: block_input``, models/gpt/hybrid.py)
        routed = tokens if router_input is None else router_input.reshape(n, h)
        with jax.named_scope("moe_route"):
            if cfg.gate == "sigmoid_topk":
                probs, weights, topk_idx = self._sigmoid_topk(
                    router(routed.astype(jnp.float32)))
            else:
                probs = jax.nn.softmax(router(routed.astype(jnp.float32)),
                                       axis=-1)
                weights, topk_idx = jax.lax.top_k(probs, k)
                if cfg.norm_topk_prob:
                    weights = weights / weights.sum(axis=-1, keepdims=True)
            dest, src, sizes, tile_expert, num_tiles = expert_row_layout(
                topk_idx, E, tm)
            rows = tokens.astype(dt)[src]
        if self.is_mutable_collection("intermediates"):
            self.sow("intermediates", "balance_loss", _balance_loss(
                probs, jax.nn.one_hot(topk_idx, E).sum(axis=1) / k))
        # what the layer saw, chose and gave, for whoever asks (collection
        # ``routing``: the benchmark's reference check holds the layer to
        # its reference on the input it really had)
        probed = (self.is_mutable_collection("routing")
                  and not self.is_initializing())
        if probed:
            self.sow("routing", "input", x)
            if router_input is not None:
                self.sow("routing", "router_input", router_input)
            self.sow("routing", "experts", topk_idx.reshape(b, s, k))
            self.sow("routing", "weights", weights.reshape(b, s, k))
        self._count(sizes, n * k, (num_tiles, len(tile_expert) - (tm > 1)),
                    s, decode, layer_index)
        with jax.named_scope("moe_experts"):
            if kernel:
                w_gate, w_up, w_down = (w.astype(dt) for w in expert_stack)
                hidden = moe_gmm.grouped_gate_up(
                    rows, w_gate, w_up, tile_expert, num_tiles, tm=tm,
                    layer=layer_index, act=act)
                out = moe_gmm.grouped_down(hidden, w_down, tile_expert,
                                           num_tiles, tm=tm, layer=layer_index)
            else:
                w_gate, w_up, w_down = (w.astype(dt)
                                        for w in (w_gate, w_up, w_down))
                hidden = (getattr(jax.nn, act)(
                    jax.lax.ragged_dot(rows, w_gate, sizes))
                    * jax.lax.ragged_dot(rows, w_up, sizes))
                out = jax.lax.ragged_dot(hidden, w_down, sizes)
        with jax.named_scope("moe_route"):
            picked = out[dest].reshape(n, k, h).astype(jnp.float32)
            y = (picked * weights[..., None]).sum(axis=1)
        y = y.astype(dt).reshape(b, s, h)
        if probed:
            self.sow("routing", "output", y)
        return y

    def _sigmoid_topk(self, logits):
        """Gate ``sigmoid_topk``: ``(scores, weights, chosen)``. The scores
        are ``sigmoid(logits)`` in float32; the ``top_k`` largest of ``score
        + expert_bias`` are chosen (the bias, a float32 leaf of its own,
        steers the CHOICE alone); a chosen expert's weight is its score,
        over the chosen scores' sum + 1e-6 under ``norm_topk_prob``, times
        ``routed_scaling_factor``."""
        cfg = self.cfg
        scores = jax.nn.sigmoid(logits)
        ranked = scores
        if cfg.use_expert_bias:
            ranked = scores + self._expert_bias(cfg.num_experts)
        _, chosen = jax.lax.top_k(ranked, cfg.top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if cfg.norm_topk_prob:
            weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
        return scores, weights * cfg.routed_scaling_factor, chosen

    def _expert_bias(self, experts: int):
        """The selection bias, a float32 leaf ``[experts]`` (drawn normal at
        ``expert_bias_init_std`` from a seed; 0: zeros)."""
        std = self.cfg.expert_bias_init_std
        return self.param(
            "expert_bias", nn.with_logical_partitioning(
                nn.initializers.normal(std) if std
                else nn.initializers.zeros_init(), (None,)),
            (experts,), jnp.float32)

    def _count(self, sizes, pairs: int, tiles, seq: int, decode: bool,
               layer_index):
        """Add this call to the ``moe_stats`` leaf of the decode cache (the
        engine carries that tree from program to program and fetches none
        of it: ``ServingMetrics.snapshot()`` does, through the executor).
        ``tiles`` is the layout's ``(num_tiles, tiles that can hold
        rows)``: what the kernels' grid walks and its static bound (the
        spare tile at the end not counted)."""
        if not decode:
            return
        fresh = not self.has_variable("cache", "moe_stats")
        stats = self.variable("cache", "moe_stats", jnp.zeros,
                              (2 * len(MOE_STATS) * 2,), jnp.uint32)
        if fresh:
            return
        with jax.named_scope("moe_route"):
            add = jnp.stack([jnp.uint32(1), jnp.uint32(pairs),
                             (sizes > 0).sum().astype(jnp.uint32),
                             sizes.max().astype(jnp.uint32),
                             jnp.uint32(tiles[0]), jnp.uint32(tiles[1])])
            low = 2 * ((0 if seq == 1 else len(MOE_STATS))
                       + jnp.arange(len(MOE_STATS)))
            # the layer scan carries the whole stack [L, words]
            at = (low,) if layer_index is None else (layer_index, low)
            up = (low + 1,) if layer_index is None else (layer_index, low + 1)
            was = stats.value[at]
            now = was + add                      # wraps at 2**32 ...
            stats.value = stats.value.at[at].set(now).at[up].add(
                (now < was).astype(jnp.uint32))  # ... into the high word
