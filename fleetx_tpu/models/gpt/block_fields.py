"""``GPTConfig``'s fields for a block whose layers are not all alike:
grouped-query heads, a head size of its own, window and full attention
layers mixed, rotary and position-free layers mixed, a router that reads
the block's input; and for a stack whose layers do not even hold the same
parameters (``layer_types``: gated short-convolution layers beside attention
layers, Mamba-1 selective-scan layers beside attention layers, KDA
delta-rule linear attention layers beside gated attention layers, dense
feed-forward layers before expert layers or throughout, a sigmoid router
with a selection bias, window and full attention layers side by side with an
output gate and norms after each part as well as before, double layers
whose expert layer leaves the stream at the first half and lands after the
second, a softmax router over routed and zero-compute experts). ``GPTConfig``
inherits them, ``check`` is the part of
its ``__post_init__`` that refuses what nobody wrote, ``layer_class`` picks
the layer that runs the first group (``models/gpt/hybrid.py``) and
``stack_of`` the stack that runs the second (``models/gpt/mixed_stack.py``).

A module of its own, and not a part of model.py, for the reason
``models/gpt/resident.py`` gives: a line added there makes every training
program a new program (ROADMAP D11). It imports nothing of the model.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

__all__ = ["BlockLayoutFields", "LANE_STATE_LEAVES", "LAYOUT_FIELDS",
           "LAYER_TYPES", "RECURRENT_TYPES", "VISION_FIELDS", "check",
           "fold_mrope", "layer_class", "rows_in", "stack_of",
           "unit_offset_norm"]

# per-layer lists (a YAML or JSON list becomes a tuple: the configuration is
# a module attribute and has to hash)
LAYOUT_FIELDS = ("rope_layout", "sliding_window_layout", "layer_types")
# the operators a layer of ``layer_types`` can name, under the source's names
LAYER_TYPES = ("conv", "mamba", "kda", "full_attention", "sliding_attention",
               "latent_attention")
# the recurrent operators (a stack holds ONE beside attention), and of them
# those whose state is held ONCE A LANE, outside the page pool: the state
# kind's name and the cache leaves a lane holds of it, ``[layers of the
# kind, lanes, ...]`` each (serving/cache_manager.py "Kinds of state")
RECURRENT_TYPES = ("conv", "mamba", "kda")
LANE_STATE_LEAVES = {"mamba": ("ssm", ("ssm_state", "ssm_conv")),
                     "kda": ("kda", ("kda_state", "kda_conv"))}
# the gates that choose under a selection bias (``use_expert_bias``)
_BIAS_GATES = ("sigmoid_topk", "softmax_bias_topk")


@dataclasses.dataclass(frozen=True)
class BlockLayoutFields:
    """The fields, every default the GPT-2 block's (and OLMoE's)."""

    # grouped-query attention: key/value heads, each shared by
    # ``num_attention_heads // num_key_value_heads`` query heads (query head
    # h reads key head ``h // group``); None: one for every query head
    num_key_value_heads: Optional[int] = None
    # the size of one head where it is not ``hidden_size //
    # num_attention_heads`` (``GPTConfig.head_dim`` resolves it; a stored
    # ``head_dim`` would go stale under ``dataclasses.replace``)
    head_size: Optional[int] = None
    # window attention: query i sees key j where ``j <= i`` and ``i - j <
    # sliding_window``, in the layers ``sliding_window_layout`` marks 1 (a
    # list of ``num_layers`` entries; None with a window: every layer)
    sliding_window: Optional[int] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    # under ``position_embedding: rope``, the layers that rotate (1) and
    # those that take no position at all (0); None: every layer rotates
    rope_layout: Optional[Tuple[int, ...]] = None
    # what a softmax top-k router reads: the stream its experts read
    # ("mlp_norm": norm2 of the post-attention stream) or the block's input
    # before norm1 ("block_input")
    router_input: str = "mlp_norm"
    # ---- a stack whose layers hold different parameters (mixed_stack.py).
    # The operator of every layer, ``num_layers`` entries of LAYER_TYPES:
    # "conv" is the gated short convolution ``out((C * conv(B * u)))`` with
    # ``B, C, u = split(in(x), 3)`` and a causal depthwise filter of
    # ``conv_L_cache`` taps, whose state is the last ``conv_L_cache - 1``
    # rows of ``B * u``; "full_attention" the grouped attention of hybrid.py;
    # "mamba" the Mamba-1 selective scan (mixed_stack.MambaMixer): inner
    # width ``mamba_expand * hidden_size``, a state of ``mamba_d_state`` a
    # channel held in float32, a causal depthwise filter of
    # ``mamba_d_conv`` taps, ``dt`` of rank ``mamba_dt_rank``;
    # "sliding_attention" the same grouped attention through the window
    # ``sliding_window`` (a stack may say which of its attention layers are
    # window layers here, under the source's name, or in
    # ``sliding_window_layout``; where it gives both they agree)
    layer_types: Optional[Tuple[str, ...]] = None
    conv_L_cache: int = 3
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: Optional[int] = None
    # "kda" (mixed_stack.KDAMixer: Kimi Delta Attention, a gated delta-rule
    # linear attention): ``kda_num_heads`` heads of ``kda_head_dim`` (keys
    # and values alike), each of q, k, v through a causal depthwise filter
    # of ``kda_conv_size`` taps; a per-channel log decay and the output gate
    # through low-rank projections of ``kda_gate_rank``; ``beta =
    # sigmoid(..)``, doubled under ``kda_neg_eigval`` (the transition then
    # has eigenvalues in (-1, 1)); a state ``[heads, d, d]`` float32 a layer
    # and lane, held once a lane
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_size: int = 4
    kda_gate_rank: int = 0
    kda_neg_eigval: bool = False
    # ``kda_no_lora``: the decay's and the output gate's projections are
    # FULL ``[hidden, heads x d]`` matrices (``kda_gate_rank`` 0; the decay's
    # keeps ``dt_bias``, the gate's has no bias). ``kda_safe_gate``: the log
    # decay is bounded, ``g = kda_lower_bound * sigmoid(exp(A_log) * (a W_f +
    # dt_bias))`` in ``(kda_lower_bound, 0)``, in the place of ``-exp(A_log)
    # * softplus(..)``
    kda_no_lora: bool = False
    kda_safe_gate: bool = False
    kda_lower_bound: float = 0.0
    # the first ``num_dense_layers`` layers take the dense MLP, of width
    # ``dense_ffn_hidden_size``; the others experts of ``ffn_hidden_size``
    # (``num_dense_layers == num_layers``: no expert layer at all)
    num_dense_layers: int = 0
    dense_ffn_hidden_size: Optional[int] = None
    # what ``qk_norm`` normalises: the whole projection (all heads, one
    # weight per element: OLMoE) or each "head" (one weight [head_dim] for
    # q and one for k), either before the rotation
    qk_norm_scope: str = "projection"
    # gate "sigmoid_topk" (parallel/moe.py): scores ``sigmoid(router(x))``
    # in float32; the ``top_k`` largest of ``score + expert_bias`` chosen
    # (``use_expert_bias``: a float32 leaf [experts], read for the CHOICE
    # only); weights the chosen scores, over their sum + 1e-6 under
    # ``norm_topk_prob``, times ``routed_scaling_factor``. A bias made from
    # a seed is drawn normal at ``expert_bias_init_std`` (0: zeros)
    use_expert_bias: bool = False
    expert_bias_init_std: float = 0.0
    routed_scaling_factor: float = 1.0
    # ---- what a ``layer_types`` stack of grouped attention layers may add
    # to the block (mixed_stack.py, hybrid.py). ``attention_gate``
    # "sigmoid": the heads' output times ``sigmoid(a W_g)`` before the
    # out-projection, ``a`` the normed input the queries are made from and
    # ``W_g`` a projection of its own, as wide as the queries'
    # ("sigmoid_head": ONE value a head, ``W_g`` ``[hidden, heads]``: the
    # gate of the latent layers beside delta-rule layers, latent.py).
    # ``sandwich_norm``: an RMSNorm with a weight of its own on each part's
    # OUTPUT before it joins the residual stream (``x + norm(attn(norm(x)))``
    # and the same around the feed-forward part). ``embedding_multiplier``:
    # the embedding rows times this before the first layer
    attention_gate: str = "none"
    sandwich_norm: bool = False
    embedding_multiplier: float = 1.0
    # serving, set by the engine beside ``decode_num_pages`` (which then
    # counts one full-attention layer's pages): the pages of one WINDOW
    # layer, whose lanes keep only the rows a live query can still see
    decode_window_pages: Optional[int] = None
    # ---- "latent_attention" (models/gpt/latent.py; every layer of the
    # stack then is one): queries through a latent of ``q_lora_rank``, keys
    # and values through ONE latent of ``kv_lora_rank`` a token beside ONE
    # rotary key of ``qk_rope_head_dim`` shared by all heads (what the
    # cache holds); a head's query and key are ``qk_nope_head_dim`` +
    # ``qk_rope_head_dim`` wide, its value ``v_head_dim``. The rotary
    # frequencies are YaRN's (``rope_scaling_*``, the source's block) over
    # ``rope_theta``
    q_lora_rank: Optional[int] = None
    kv_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    rope_scaling_factor: float = 1.0
    rope_scaling_beta_fast: float = 32.0
    rope_scaling_beta_slow: float = 1.0
    rope_scaling_mscale: float = 1.0
    rope_scaling_mscale_all_dim: float = 0.0
    rope_scaling_original_max_position: int = 4096
    # ---- a learned indexer before latent attention (``index_topk`` > 0:
    # sparse attention, models/gpt/latent.py): ``index_n_heads`` heads of
    # ``index_head_dim`` score every cached row of a query's lane from ONE
    # key a token (``cached_index``, the pool's third leaf), and the query
    # attends over the ``index_topk`` highest-scoring rows alone
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # The same indexer before GROUPED attention (a ``layer_types`` stack of
    # ``full_attention`` layers with ``qk_norm_scope: head``,
    # models/gpt/hybrid.py): its three projections read the layer's normed
    # input (there is no query latent), the chosen rows are gathered from
    # the K and V pages, and ALL ``index_head_dim`` columns rotate, in the
    # axes ``index_rope_section`` gives (below)
    index_rope_section: Optional[Tuple[int, ...]] = None
    # ---- rotary positions of three axes (time, height, width): rotary pair
    # ``j`` of a head's ``head_dim / 2`` takes the position of the axis
    # whose section of ``mrope_section`` it falls in (three counts that sum
    # to ``head_dim / 2``; None: one axis). A call is then handed positions
    # ``[3, b, s]`` (``[b, s]``: the three alike), and a row's position is
    # no longer its place in the cache (:func:`fold_mrope`)
    mrope_section: Optional[Tuple[int, ...]] = None
    # ---- a vision tower whose rows enter the stack beside token rows
    # (models/vision/vit.py ``VisionTower``; serving/engine.py admits them):
    # a group of ``hidden_size``, ``num_layers``, ``num_heads``,
    # ``intermediate_size``, ``patch_size``, ``grid`` (the learned position
    # table's side), ``merge`` (2: a row is 2 x 2 patches) and
    # ``image_token_id`` (the id that marks an image's rows in a prompt); a
    # mapping in a YAML, held as a sorted tuple of pairs (``vision_fields``)
    vision: Optional[tuple] = None
    # ---- an expert layer that holds a SHARE (parallel/moe_share.py): the
    # router is ``num_routed_experts`` wide and every token chooses among
    # all of them; this program holds ``num_experts`` of them, from
    # ``first_expert_held`` on, and computes their part of the result (None:
    # every routed expert is held, the layer of parallel/moe.py). Gate
    # ``sigmoid_topk`` with ``n_group`` > 1 chooses inside the
    # ``topk_group`` groups (of ``n_group`` equal ones) whose two highest
    # scores sum highest. ``num_shared_experts`` gated experts of
    # ``ffn_hidden_size`` each see every token
    num_routed_experts: Optional[int] = None
    first_expert_held: int = 0
    n_group: int = 1
    topk_group: int = 1
    num_shared_experts: int = 0
    # ---- gate "softmax_bias_topk" (parallel/moe_share.py): scores
    # ``softmax(router(x))`` in float32 over ``num_routed_experts +
    # num_zero_experts`` outputs; the ``top_k`` largest of ``score +
    # expert_bias`` chosen (``use_expert_bias``: the CHOICE only); weights
    # the chosen RAW scores times ``routed_scaling_factor`` (over their sum
    # under ``norm_topk_prob``). The last ``num_zero_experts`` outputs are
    # ZERO-COMPUTE experts: one chosen returns its input, weighed; it holds
    # no weights, takes no row of the grouped matmuls and is computed where
    # the token lives
    num_zero_experts: int = 0
    # ---- shortcut-connected feed-forward parts (LongCat-Flash; every entry
    # of ``layer_types`` then is one HALF of a double layer, a
    # ``latent_attention``): every half runs its attention and a dense MLP
    # of ``dense_ffn_hidden_size``; at an EVEN half the expert layer reads
    # the dense MLP's normed input and its output LEAVES the stream's path,
    # to LAND on the stream after the next (odd) half's dense MLP. The
    # experts' stack has ``num_layers // 2`` entries and no norm of its own
    moe_shortcut: bool = False
    # latent attention: the queries times ``sqrt(hidden_size /
    # q_lora_rank)`` after ``W_qb``, the compressed keys/values times
    # ``sqrt(hidden_size / kv_lora_rank)`` after their low-rank norm (what
    # the cache then holds); the rotary key is not scaled
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # ---- latent attention layers BESIDE delta-rule layers in one stack
    # (``layer_types`` of ``kda`` and ``latent_attention``: the latent pool
    # under the lane's pages and the matrix state under the lane, both in
    # one scanned body). Such a stack may leave the query latent out
    # (``q_lora_rank`` None: ``q = a W_q``), norm each head's query and the
    # shared rotary key before the rotation (``qk_norm`` with
    # ``qk_norm_scope: head``: weights ``[nope + rope]`` and ``[rope]``) and
    # gate each head's output (``attention_gate: sigmoid_head``).
    # The clamp of a gated expert's two products (the source's
    # ``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``
    # entry of a layer) is carried and REFUSED where it is not 0: no
    # equation for it is published (ROADMAP R6)
    expert_swiglu_limit: float = 0.0
    shared_expert_swiglu_limit: float = 0.0
    # ---- EVA attention (models/gpt/eva.py; every layer then is one):
    # position ``p`` sees the rows of its own TUMBLING window ``[W * (p //
    # W), p]`` exactly (``eva_window_size`` W) and, of every window before
    # it, ONE pooled key/value row for each chunk of ``eva_chunk_size`` rows
    # (two learned vectors a head, ``eva_mu`` and ``eva_phi``, pool a chunk
    # when its last row exists), in one softmax. Serving keeps two classes
    # of page for it, "summary" and "window"
    # (serving/cache_manager.py "EVA's two classes")
    eva_chunk_size: int = 0
    eva_window_size: int = 0
    # the residual stream's dtype where it is not the compute dtype
    # ("float32": the blocks' sums in float32 under bfloat16 weights)
    residual_dtype: Optional[str] = None
    # RMSNorm as ``x^ * (1 + w)`` (the weight is the offset from one)
    norm_unit_offset: bool = False
    # prediction heads in ONE untied ``lm_head`` ``[heads * vocab, hidden]``:
    # columns ``[vocab * j, vocab * (j + 1))`` predict the token at ``t + 1 +
    # j``; serving samples head 0 (``GPTExecutor.forward``)
    num_pred_heads: int = 1

    @property
    def eva(self) -> bool:
        """Whether the attention layers are EVA's."""
        return self.eva_window_size > 0

    @property
    def head_rows(self) -> int:
        """Rows of the output head's table: every prediction head's."""
        return self.vocab_size * self.num_pred_heads

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def latent(self) -> bool:
        """Whether the attention layers are latent attention."""
        return "latent_attention" in (self.layer_types or ())

    @property
    def latent_beside_kda(self) -> bool:
        """Whether the stack holds latent attention layers beside
        delta-rule layers (and nothing else)."""
        return set(self.layer_types or ()) == {"kda", "latent_attention"}

    @property
    def held_group(self) -> Optional[int]:
        """The router group whose experts are EXACTLY the share held here
        (``n_group`` > 1 and ``[first_expert_held, + num_experts)`` one
        whole group of at least 8 experts, the floor of a share that stands
        for a deployment's: the tiny presets of the other share
        configurations hold a group of 4 by coincidence, and their programs
        stay what they were); None for any other share."""
        if not (self.expert_mode and self.n_group > 1):
            return None
        size = self.routed_experts // self.n_group
        first, count = self.experts_held
        whole = count == size >= 8 and not first % size
        return first // size if whole else None

    @property
    def indexed(self) -> bool:
        """Whether a learned indexer selects the rows latent attention
        reads."""
        return self.index_topk > 0

    @property
    def vision_fields(self) -> dict:
        """The ``vision`` group as a mapping (empty without a tower)."""
        return dict(self.vision or ())

    def selected(self, rows):
        """Of ``rows`` cached rows behind a query (itself among them), those
        it attends over (an int or an array of them)."""
        return np.minimum(rows, self.index_topk) if self.indexed else rows

    @property
    def experts_held(self) -> Tuple[int, int]:
        """``(first, count)`` of the routed experts this program holds."""
        return self.first_expert_held, self.num_experts

    @property
    def routed_experts(self) -> int:
        """The router's width: every expert a token can choose."""
        return self.num_routed_experts or self.num_experts

    @property
    def router_width(self) -> int:
        """The router's outputs: the routed experts and, after them, the
        zero-compute ones."""
        return self.routed_experts + self.num_zero_experts

    @property
    def expert_share(self) -> bool:
        """Whether the expert layers are parallel/moe_share.py's: a held
        share, a group-limited choice, a shared expert, zero-compute
        experts or the softmax gate that chooses under a bias."""
        return bool(self.num_routed_experts or self.n_group > 1
                    or self.num_shared_experts or self.num_zero_experts
                    or self.gate == "softmax_bias_topk")

    @property
    def expert_layers(self) -> int:
        """The stack's expert layers: one a double layer under
        ``moe_shortcut``, else every layer after the leading dense ones."""
        if self.moe_shortcut:
            return self.num_layers // 2
        return self.num_layers - self.num_dense_layers

    @property
    def mla_scales(self) -> Tuple[float, float]:
        """``(s_q, s_kv)``: what latent attention multiplies its queries
        and its compressed keys/values by (1.0: not at all)."""
        return tuple(
            float(np.sqrt(self.hidden_size / rank)) if on else 1.0
            for on, rank in ((self.mla_scale_q_lora, self.q_lora_rank),
                             (self.mla_scale_kv_lora, self.kv_lora_rank)))

    @property
    def rows_span_field(self) -> str:
        """The name under which a tick's span gives the live rows of one
        attention layer."""
        return "latent_rows" if self.latent else "attn_rows"

    @property
    def layer_kinds(self) -> bool:
        """Whether the layers differ from each other or from the one block
        ``models/gpt/model.py`` writes: ``models/gpt/hybrid.py`` runs them."""
        return bool(self.kv_heads != self.num_attention_heads
                    or self.head_size or self.sliding_window
                    or self.rope_layout or self.router_input != "mlp_norm"
                    or self.layer_types or self.qk_norm_scope != "projection"
                    or self.eva)

    @property
    def state_kinds(self) -> Tuple[str, ...]:
        """What a lane keeps: in the page pool, keys and values ("kv") in
        every attention layer and, in a gated short-convolution layer, the
        operator's last inputs ("conv"); once a lane and outside the pool,
        a selective-scan layer's state ("ssm") or a delta-rule layer's
        ("kda")."""
        kinds = {"full_attention" if t == "sliding_attention" else t
                 for t in self.layer_types or ("full_attention",)}
        return tuple(name for name, kind in (
            ("kv", "full_attention"), ("conv", "conv"), ("ssm", "mamba"),
            ("kda", "kda"), ("latent", "latent_attention")) if kind in kinds)

    @property
    def lane_state(self) -> Tuple[str, Tuple[str, ...]]:
        """``(state kind, its cache leaves)`` of the state a lane holds ONCE
        A LANE, outside the page pool (``LANE_STATE_LEAVES``); ``("", ())``
        for a model without such a kind."""
        return next((LANE_STATE_LEAVES[t] for t in self.layer_types or ()
                     if t in LANE_STATE_LEAVES), ("", ()))

    def span_pairs(self, rows: int) -> dict:
        """Span field of a call of ``rows`` rows through an expert layer
        that holds a share: ALL the (token, expert) pairs its routers
        choose, over every expert layer (how many of them meet a held expert
        only the device knows: the counters ``moe_*_pairs``). Empty for any
        other layer."""
        if not (self.expert_mode and self.expert_share):
            return {}
        return {"pairs": rows * self.top_k * self.expert_layers}

    @property
    def eva_composed_pages(self) -> int:
        """Pages of a lane's COMPOSED table under EVA (models/gpt/eva.py):
        the summary pages of every window a cache row can have behind it,
        then one window's pages."""
        ps, held = self.decode_page_size, self.decode_cache_len
        per_window = self.eva_window_size // self.eva_chunk_size // ps
        return ((held - 1) // self.eva_window_size * per_window
                + self.eva_window_size // ps)

    def eva_rows(self, pos):
        """``(window rows, summary rows)`` a query at position ``pos`` (an
        int or an array of them) attends over in ONE EVA layer: the exact
        rows of its own window up to itself, and one pooled row for every
        chunk of the windows before."""
        pos = np.asarray(pos)
        window = self.eva_window_size
        return (pos % window + 1,
                pos // window * (window // self.eva_chunk_size))

    def eva_spans(self, pos, rows=None) -> dict:
        """Span fields of a program over EVA layers. A tick (``pos``: its
        lanes' positions): the rows ONE layer attends over, summed over
        the lanes, the positions they stand at (what full attention would
        read), and the chunks the tick closes. A chunk of ``rows`` rows
        behind ``pos``: the rows its queries attend over TOGETHER (the last
        query's: what the chunk reads), and the chunks it closes."""
        chunk = self.eva_chunk_size
        if rows is None:
            pos = np.asarray(pos)
            exact, pooled = self.eva_rows(pos)
            closed = int((pos % chunk == chunk - 1).sum())
            at = int((pos + 1).sum())
        else:
            exact, pooled = self.eva_rows(pos + rows - 1)
            closed = (pos + rows) // chunk - pos // chunk
            at = pos + rows
        return {"eva_window_rows": int(np.sum(exact)),
                "eva_summary_rows": int(np.sum(pooled)),
                "eva_positions": at, "eva_chunks_closed": closed}

    def decode_kernel_steps(self, lanes: int, head_shards: int = 1) -> int:
        """Span field ``kernel_steps`` of a decode tick of ``lanes`` lanes:
        the grid steps of the kernel ``fleetx_decode_paged`` over ALL the
        attention layers' calls, each layer's by the function the kernel
        sizes its own grid with (``decode_attention.paged_grid``: a full
        layer's call walks the table, a window layer's its window), over a
        pool whose row holds this device's ``1 / head_shards`` of the key
        heads. 0 over latent attention, another kernel's."""
        if self.latent:
            return 0
        import jax
        import jax.numpy as jnp

        from fleetx_tpu.ops.pallas.decode_attention import paged_grid

        ps = self.decode_page_size
        quant = self.decode_kv_dtype == "int8"
        heads = self.kv_heads // head_shards
        pool = jax.ShapeDtypeStruct(
            (1, ps, heads * self.head_dim), jnp.int8 if quant else self.dtype)
        scale = jax.ShapeDtypeStruct((1, ps, heads), jnp.float32)
        pools = [pool] * 2 + [scale] * (2 * quant)
        kinds = self.of_attention_layers(self.window_layers)
        # (under an indexer the kernel walks the compact pool of chosen rows)
        held = self.selected(self.decode_cache_len)
        if self.eva:  # (the kernel walks a lane's composed table)
            held = self.eva_composed_pages * ps
        return lanes * sum(
            paged_grid(pools, -(-int(held) // ps),
                       max_live=self.sliding_window if windowed else None)[1]
            for windowed in kinds)

    def spans(self, rows: int, behind: int, program_rows: int = 0) -> dict:
        """Span fields of a prefill call of ``rows`` tokens (a program of
        ``program_rows`` rows, padding included) behind ``behind`` cached
        ones. Over latent attention: the live rows ONE layer attends over
        (and re-expands), the key rows the live steps of the kernel
        ``fleetx_mla_prefill`` cover for them (whole blocks: work and
        padding together), and the pairs routed. Over grouped or window
        attention layers of a shape the kernel ``fleetx_prefill_gqa``
        takes: the key rows its live steps cover in one full and one window
        layer (``hybrid.chunk_key_rows``)."""
        if self.eva:
            return self.eva_spans(behind, rows)
        if not self.latent:
            if not self.layer_kinds:
                return {}
            from fleetx_tpu.models.gpt.hybrid import chunk_key_rows

            return {**chunk_key_rows(self, program_rows or rows, behind),
                    **self.span_pairs(rows), **self._span_selection(
                        rows, behind)}
        from fleetx_tpu.ops.pallas.mla_prefill import key_rows

        return {"latent_rows": behind + rows,
                "latent_key_rows": key_rows(behind + rows),
                **self.span_pairs(rows),
                **self._span_selection(rows, behind)}

    def _span_selection(self, rows: int, behind: int) -> dict:
        """Under an indexer, summed over a call's ``rows``: the index keys
        each scores (every row up to its own) and the rows it then attends
        over."""
        if not self.indexed:
            return {}
        each = behind + 1 + np.arange(rows)
        return {"index_rows": int(each.sum()),
                "selected_rows": int(self.selected(each).sum())}

    @property
    def mamba_inner(self) -> int:
        """A selective-scan layer's inner width."""
        return self.mamba_expand * self.hidden_size

    @property
    def kda_inner(self) -> int:
        """A delta-rule layer's width: its heads side by side."""
        return self.kda_num_heads * self.kda_head_dim

    @property
    def window_layers(self) -> Tuple[int, ...]:
        """1 for every layer that attends through the window."""
        if not self.sliding_window:
            return (0,) * self.num_layers
        if self.sliding_window_layout:
            return tuple(self.sliding_window_layout)
        if "sliding_attention" in (self.layer_types or ()):
            return tuple(int(t == "sliding_attention")
                         for t in self.layer_types)
        return (1,) * self.num_layers

    @property
    def rope_layers(self) -> Tuple[int, ...]:
        """1 for every layer that rotates its queries and keys."""
        if self.position_embedding != "rope":
            return (0,) * self.num_layers
        return tuple(self.rope_layout or (1,) * self.num_layers)

    def of_attention_layers(self, per_layer) -> tuple:
        """``per_layer``'s entries of the layers that hold keys and values,
        in order: an attention layer of a ``layer_types`` stack is counted
        among the attention layers alone (mixed_stack.py hands it that
        index, and the pool's pages follow it); every layer elsewhere."""
        if not self.layer_types:
            return tuple(per_layer)
        return tuple(v for v, t in zip(per_layer, self.layer_types)
                     if t.endswith("attention"))


def check(cfg) -> None:
    """Refuse, with the field's name, what the layers cannot be built from;
    lists arrive as tuples afterwards."""
    for name in LAYOUT_FIELDS:
        value = getattr(cfg, name)
        if value is None:
            continue
        allowed = LAYER_TYPES if name == "layer_types" else (0, 1)
        value = tuple(v if isinstance(v, str) else int(v) for v in value)
        object.__setattr__(cfg, name, value)
        if len(value) != cfg.num_layers or set(value) - set(allowed):
            raise ValueError(
                f"{name} has {len(value)} entries {value}; it needs "
                f"num_layers = {cfg.num_layers} entries of "
                + " | ".join(map(str, allowed)))
    for name in ("mrope_section", "index_rope_section"):
        if getattr(cfg, name) is not None:
            object.__setattr__(cfg, name, tuple(
                int(v) for v in getattr(cfg, name)) or None)
    if cfg.vision is not None:
        object.__setattr__(cfg, "vision", tuple(sorted(
            dict(cfg.vision).items())) or None)
    _check_mixed(cfg)
    _check_positions_and_tower(cfg)
    _check_eva(cfg)
    if cfg.router_input not in ("mlp_norm", "block_input"):
        raise ValueError(f"router_input={cfg.router_input!r}; choose "
                         "mlp_norm | block_input")
    if cfg.num_attention_heads % cfg.kv_heads:
        raise ValueError(
            f"num_key_value_heads {cfg.kv_heads} does not divide "
            f"num_attention_heads {cfg.num_attention_heads}")
    if cfg.mlp_act == "reglu" and not (cfg.expert_mode
                                       and cfg.gate == "softmax_topk"):
        raise ValueError("mlp_act='reglu' is the gate of the softmax top-k "
                         "expert layer (gate: softmax_topk); the dense MLP "
                         "has gelu and swiglu")
    if cfg.sliding_window_layout and not cfg.sliding_window:
        raise ValueError("sliding_window_layout without sliding_window")
    if cfg.rope_layout and cfg.position_embedding != "rope":
        raise ValueError("rope_layout without position_embedding='rope'")
    if cfg.router_input != "mlp_norm" and not (
            cfg.expert_mode and cfg.gate == "softmax_topk"):
        raise ValueError(f"router_input={cfg.router_input!r} without a "
                         "softmax top-k expert layer")
    if cfg.sliding_window is not None:
        held = cfg.decode_cache_len or cfg.max_position_embeddings
        if not 0 < cfg.sliding_window <= held:
            raise ValueError(
                f"sliding_window {cfg.sliding_window} needs a cache length "
                f"that can hold it (decode_cache_len or "
                f"max_position_embeddings: {held})")
    if not cfg.layer_kinds:
        return
    for field, why in (
            ("sequence_parallel", "no test covers it"),
            ("no_recompute_layers", "the layers run as ONE scanned body, "
                                    "whatever the depth")):
        if getattr(cfg, field):
            raise NotImplementedError(
                f"{field} with grouped heads, a head size of its own or "
                f"mixed layers: {why}")
    if cfg.pp_degree > 1 or cfg.cp_degree > 1 or not cfg.scan_layers:
        raise NotImplementedError(
            "grouped heads, a head size of its own or mixed layers need "
            "scan_layers and no pipeline or context parallelism (the stage "
            "and ring paths carry no layer kind)")
    if cfg.decode_kv_dtype is not None:
        raise NotImplementedError(
            "decode_kv_dtype with grouped heads: the decode kernels take "
            "int8 scales a head, for as many key heads as query heads")


def _check_mixed(cfg) -> None:
    """The fields of a stack with layer types, a sigmoid gate or a per-head
    QK-norm, each refusal with the field's name."""
    if cfg.qk_norm_scope not in ("projection", "head"):
        raise ValueError(f"qk_norm_scope={cfg.qk_norm_scope!r}; choose "
                         "projection | head")
    if cfg.qk_norm_scope == "head" and not cfg.qk_norm:
        raise ValueError("qk_norm_scope='head' without qk_norm")
    if (cfg.qk_norm and cfg.qk_norm_scope == "projection"
            and cfg.layer_kinds):
        raise NotImplementedError(
            "qk_norm over the whole projection with grouped heads, a head "
            "size of its own or mixed layers: no test covers it "
            "(qk_norm_scope: head is the per-head norm)")
    biased = cfg.expert_mode and cfg.gate in _BIAS_GATES
    if biased and not 1 <= cfg.top_k <= cfg.router_width:
        raise ValueError(f"top_k {cfg.top_k} of {cfg.router_width} experts")
    if (cfg.use_expert_bias or cfg.expert_bias_init_std) and not biased:
        raise ValueError("use_expert_bias (expert_bias_init_std) without "
                         "gate: sigmoid_topk | softmax_bias_topk, the gates "
                         "that read the bias")
    if biased and not cfg.layer_types:
        raise NotImplementedError(
            f"gate: {cfg.gate} without layer_types: the stack of "
            "models/gpt/mixed_stack.py is the one that runs it")
    if cfg.num_zero_experts < 0 or (cfg.num_zero_experts and not (
            cfg.expert_mode and cfg.gate == "softmax_bias_topk")):
        raise ValueError(
            f"num_zero_experts {cfg.num_zero_experts} without gate: "
            "softmax_bias_topk over layer_types, the gate that weighs a "
            "zero-compute expert (parallel/moe_share.py)")
    if not 0 <= cfg.num_dense_layers <= cfg.num_layers:
        raise ValueError(f"num_dense_layers {cfg.num_dense_layers} of "
                         f"num_layers {cfg.num_layers}")
    if cfg.conv_L_cache < 2:
        raise ValueError(f"conv_L_cache {cfg.conv_L_cache}: a short "
                         "convolution has at least 2 taps")
    if min(cfg.mamba_expand, cfg.mamba_d_state) < 1 or cfg.mamba_d_conv < 2:
        raise ValueError(
            f"mamba_expand {cfg.mamba_expand}, mamba_d_state "
            f"{cfg.mamba_d_state}, mamba_d_conv {cfg.mamba_d_conv}: widths "
            "of at least 1 and a filter of at least 2 taps")
    if cfg.attention_gate not in ("none", "sigmoid", "sigmoid_head"):
        raise ValueError(f"attention_gate={cfg.attention_gate!r}; choose "
                         "none | sigmoid | sigmoid_head")
    if cfg.expert_swiglu_limit or cfg.shared_expert_swiglu_limit:
        raise NotImplementedError(
            f"expert_swiglu_limit {cfg.expert_swiglu_limit} / "
            f"shared_expert_swiglu_limit {cfg.shared_expert_swiglu_limit}: "
            "the clamp of a gated expert's products has no published "
            "equation here (parallel/moe.py, moe_share.py compute none: "
            "ROADMAP R6); only a limit of 0 (no clamp) is served")
    if not cfg.layer_types:
        if cfg.num_dense_layers or cfg.dense_ffn_hidden_size:
            raise ValueError("num_dense_layers / dense_ffn_hidden_size "
                             "without layer_types")
        if (cfg.attention_gate != "none" or cfg.sandwich_norm
                or cfg.embedding_multiplier != 1.0 or cfg.moe_shortcut):
            raise NotImplementedError(
                "attention_gate / sandwich_norm / embedding_multiplier / "
                "moe_shortcut without layer_types: the stack of "
                "models/gpt/mixed_stack.py is the one that runs them")
        scales = [n for n in ("mla_scale_q_lora", "mla_scale_kv_lora")
                  if getattr(cfg, n)]
        if scales:
            raise ValueError(f"{scales} without a latent_attention layer")
        return
    _check_shortcut(cfg)
    if not cfg.moe_shortcut and (
            bool(cfg.num_dense_layers) != bool(cfg.dense_ffn_hidden_size)):
        raise ValueError("num_dense_layers and dense_ffn_hidden_size (the "
                         "dense layers' width) come together")
    if cfg.num_dense_layers < cfg.num_layers and not (
            cfg.expert_mode
            and cfg.gate in ("softmax_topk", *_BIAS_GATES)):
        raise ValueError(
            "layer_types: the layers after num_dense_layers are dropless "
            "experts (gate: softmax_topk | sigmoid_topk | softmax_bias_topk, "
            "num_experts > 1)")
    if cfg.mlp_act != "swiglu" or cfg.use_bias or cfg.norm != "rmsnorm":
        raise NotImplementedError(
            "layer_types with mlp_act other than swiglu, with biases or "
            "without rmsnorm: no test covers it")
    _check_latent(cfg)
    recurrent = [t for t in RECURRENT_TYPES if t in cfg.layer_types]
    if len(recurrent) > 1:
        raise NotImplementedError(
            f"layer_types with {' AND '.join(recurrent)} layers: no test "
            "covers a stack with two recurrent operators")
    kda = [n for n in ("kda_num_heads", "kda_head_dim", "kda_gate_rank",
                       "kda_neg_eigval", "kda_no_lora", "kda_safe_gate",
                       "kda_lower_bound") if getattr(cfg, n)]
    if "kda" in cfg.layer_types:
        # (full projections have no rank: ``kda_no_lora`` takes its place)
        if min(cfg.kda_num_heads, cfg.kda_head_dim,
               cfg.kda_gate_rank or cfg.kda_no_lora) < 1 \
                or cfg.kda_conv_size < 2:
            raise ValueError(
                f"layer_types with kda layers needs kda_num_heads "
                f"{cfg.kda_num_heads}, kda_head_dim {cfg.kda_head_dim}, "
                f"kda_gate_rank {cfg.kda_gate_rank} of at least 1 and "
                f"kda_conv_size {cfg.kda_conv_size} of at least 2 (the "
                "source states them)")
        if cfg.kda_no_lora and cfg.kda_gate_rank:
            raise ValueError(
                f"kda_no_lora with kda_gate_rank {cfg.kda_gate_rank}: full "
                "decay and gate projections have no rank")
        if cfg.kda_safe_gate != (cfg.kda_lower_bound < 0) or (
                cfg.kda_lower_bound > 0):
            raise ValueError(
                f"kda_safe_gate {cfg.kda_safe_gate} with kda_lower_bound "
                f"{cfg.kda_lower_bound}: the bounded log decay lies in "
                "(kda_lower_bound, 0), a bound below 0 that comes with it")
    elif kda:
        raise ValueError(f"{kda} without a kda layer")
    if "mamba" in cfg.layer_types:
        if not cfg.mamba_dt_rank:
            raise ValueError("layer_types with mamba layers needs "
                             "mamba_dt_rank (the source states it)")
    if cfg.use_recompute:
        raise NotImplementedError(
            "use_recompute with layer_types: training this stack (ROADMAP "
            "R5) in a stack of mixed operators")
    named = tuple(int(t == "sliding_attention") for t in cfg.layer_types)
    if any(named) and (not cfg.sliding_window or cfg.window_layers != named):
        raise ValueError(
            "layer_types with sliding_attention layers needs sliding_window, "
            "and a sliding_window_layout that marks the same layers")
    attention_only = not set(cfg.layer_types) - {"full_attention",
                                                 "sliding_attention"}
    mixed_rope = cfg.rope_layout and 0 < sum(cfg.rope_layout) < cfg.num_layers
    if not attention_only:
        # beside a recurrent operator or latent attention only what a test
        # covers: one class of page; every layer rotating or (rope_layout
        # all 0) none, which also keeps the position table out of the tree
        added = [n for n, on in (
            ("sliding_window", cfg.sliding_window),
            ("rope_layout (rotating some layers and not others)", mixed_rope),
            # (the gate beside delta-rule layers is covered:
            # tests/test_solar2_serving.py; one value a head over the
            # latent layers beside them: tests/test_ling3_serving.py)
            ("attention_gate", cfg.attention_gate != "none"
             and "kda" not in cfg.layer_types),
            ("sandwich_norm", cfg.sandwich_norm),
            ("embedding_multiplier", cfg.embedding_multiplier != 1.0)) if on]
        if added:
            raise NotImplementedError(
                f"{added} in a layer_types stack with conv, mamba, kda or "
                "latent_attention layers: no test covers it (a stack of "
                "full_attention | sliding_attention layers takes them, one "
                "with kda layers the attention_gate)")
    if cfg.router_input != "mlp_norm":
        raise NotImplementedError("router_input with layer_types")


def _check_shortcut(cfg) -> None:
    """``moe_shortcut``: halves of double layers, each refusal with the
    field's name."""
    if not cfg.moe_shortcut:
        return
    others = sorted(set(cfg.layer_types) - {"latent_attention"})
    if others or cfg.sliding_window:
        raise NotImplementedError(
            f"moe_shortcut beside {others or 'sliding_window'}: every half "
            "of a shortcut-connected double layer is a latent_attention "
            "layer; no test covers the shortcut beside a recurrent kind or "
            "a window")
    if cfg.num_layers % 2 or cfg.num_dense_layers or not (
            cfg.dense_ffn_hidden_size):
        raise ValueError(
            f"moe_shortcut over num_layers {cfg.num_layers}, "
            f"num_dense_layers {cfg.num_dense_layers}, dense_ffn_hidden_size "
            f"{cfg.dense_ffn_hidden_size}: an even number of halves, no "
            "leading dense layer, and the width of the dense MLP every half "
            "runs")
    if not (cfg.expert_mode and cfg.expert_share):
        raise NotImplementedError(
            "moe_shortcut without an expert layer of parallel/moe_share.py "
            "(num_routed_experts / num_zero_experts / gate: "
            "softmax_bias_topk): no test covers it")


def _check_latent(cfg) -> None:
    """The fields of latent attention and of an expert layer that holds a
    share, each refusal with the field's name."""
    share = cfg.expert_share
    if share and not (cfg.expert_mode and cfg.gate in _BIAS_GATES):
        raise ValueError(
            "num_routed_experts / n_group / num_shared_experts without "
            "gate: sigmoid_topk | softmax_bias_topk over layer_types "
            "(parallel/moe_share.py)")
    first, count = cfg.experts_held
    if share and not (0 <= first and first + count <= cfg.routed_experts
                      and cfg.top_k <= cfg.router_width):
        raise ValueError(
            f"experts held [{first}, {first + count}) and top_k {cfg.top_k} "
            f"of num_routed_experts {cfg.routed_experts}")
    if cfg.gate == "softmax_bias_topk" and cfg.n_group > 1:
        raise NotImplementedError(
            f"n_group {cfg.n_group} under gate: softmax_bias_topk: no test "
            "covers a group limit over softmax scores")
    if cfg.n_group < 1 or cfg.routed_experts % cfg.n_group or not (
            1 <= cfg.topk_group <= cfg.n_group) or (
            cfg.n_group > 1 and cfg.routed_experts // cfg.n_group < 2):
        raise ValueError(
            f"n_group {cfg.n_group} (topk_group {cfg.topk_group}) over "
            f"{cfg.routed_experts} routed experts: equal groups of at least "
            "two, of which 1 .. n_group stay")
    if not cfg.latent:
        widths = [n for n in ("q_lora_rank", "kv_lora_rank",
                              "qk_nope_head_dim", "qk_rope_head_dim",
                              "v_head_dim", "mla_scale_q_lora",
                              "mla_scale_kv_lora") if getattr(cfg, n)]
        if widths:
            raise ValueError(f"{widths} without a latent_attention layer")
        if cfg.attention_gate == "sigmoid_head":
            raise NotImplementedError(
                "attention_gate: sigmoid_head without latent_attention "
                "layers beside kda layers (models/gpt/latent.py applies it)")
        _check_grouped_indexer(cfg)
        return
    if cfg.index_rope_section:
        raise ValueError(
            "index_rope_section over latent attention: its indexer rotates "
            "a head's first qk_rope_head_dim columns by the rotary key's "
            "angles")
    beside_kda = cfg.latent_beside_kda
    if set(cfg.layer_types) != {"latent_attention"} and not beside_kda:
        raise NotImplementedError(
            "layer_types with latent_attention beside another operator than "
            "kda: no test covers a stack that mixes them (latent layers "
            "beside delta-rule layers: tests/test_ling3_serving.py)")
    sizes = (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk)
    if beside_kda and (any(sizes) or cfg.moe_shortcut or cfg.mla_scale_q_lora
                       or cfg.mla_scale_kv_lora):
        raise NotImplementedError(
            "a learned indexer, moe_shortcut or mla_scale_* over latent "
            "layers beside kda layers: no test covers it")
    # (beside delta-rule layers the queries may come straight from the
    # normed input: there is then no query latent to state)
    missing = [n for n in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                           "qk_rope_head_dim", "v_head_dim")
               if not getattr(cfg, n)
               and not (beside_kda and n == "q_lora_rank")]
    if missing or cfg.qk_rope_head_dim % 2:
        raise ValueError(
            f"latent_attention needs {missing or 'an even qk_rope_head_dim'} "
            "(the source states every width)")
    if cfg.position_embedding != "rope" or cfg.kv_heads != (
            cfg.num_attention_heads):
        raise ValueError(
            "latent_attention takes position_embedding: rope (its rotary "
            "key) and no grouped heads: the latent has no head")
    if cfg.qk_norm and not (beside_kda and cfg.qk_norm_scope == "head"
                            and not cfg.q_lora_rank):
        raise ValueError(
            "qk_norm over latent attention: only the per-head norm of queries "
            "made WITHOUT a query latent (qk_norm_scope: head, q_lora_rank "
            "None) beside kda layers, with a norm of the shared rotary key; "
            "a query latent has its own norm (q_a_norm) and the latent has "
            "no head")
    if (cfg.attention_gate != "none") != (
            beside_kda and cfg.attention_gate == "sigmoid_head"):
        raise NotImplementedError(
            f"attention_gate {cfg.attention_gate!r} over latent attention: "
            "the latent layers beside kda layers take sigmoid_head (one "
            "value a head), and nothing else takes that")
    if cfg.rope_scaling_factor < 1.0:
        raise ValueError(f"rope_scaling_factor {cfg.rope_scaling_factor}")
    if any(sizes) and (cfg.mla_scale_q_lora or cfg.mla_scale_kv_lora):
        raise NotImplementedError(
            "mla_scale_q_lora / mla_scale_kv_lora under a learned indexer "
            "(index_topk): no test covers it")
    if any(sizes) and (min(sizes) < 1
                       or cfg.index_head_dim < cfg.qk_rope_head_dim):
        raise ValueError(
            f"index_n_heads {sizes[0]}, index_head_dim {sizes[1]}, "
            f"index_topk {sizes[2]}: the indexer needs all three, and a "
            "head at least qk_rope_head_dim wide (its first columns rotate)")


def _check_grouped_indexer(cfg) -> None:
    """The indexer's fields on a stack WITHOUT latent attention: all three,
    over ``full_attention`` layers alone with per-head QK-norm, rotating."""
    sizes = (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk)
    if not any(sizes):
        if cfg.index_rope_section:
            raise ValueError("index_rope_section without an indexer")
        return
    if set(cfg.layer_types) != {"full_attention"} or (
            cfg.qk_norm_scope != "head") or cfg.attention_gate != "none" or (
            cfg.position_embedding != "rope") or cfg.rope_layout:
        raise ValueError(
            "index_n_heads / index_head_dim / index_topk without a "
            "latent_attention layer take a layer_types stack of "
            "full_attention layers alone (no window: the chosen rows are "
            "gathered from ONE class of page), qk_norm_scope: head, every "
            "layer rotating and no attention_gate: what "
            "tests/test_keyevl2_serving.py covers")
    if min(sizes) < 1 or cfg.index_head_dim % 2 or (
            cfg.head_dim % cfg.index_head_dim):
        raise ValueError(
            f"index_n_heads {sizes[0]}, index_head_dim {sizes[1]}, "
            f"index_topk {sizes[2]}: the indexer needs all three, and an "
            "even head that divides head_dim (every pair of it rotates, at "
            "every head_dim / index_head_dim-th of the heads' frequencies)")
    section = cfg.index_rope_section
    if section and (len(section) != 3 or min(section) < 0
                    or sum(section) != cfg.index_head_dim // 2
                    or not cfg.mrope_section):
        raise ValueError(
            f"index_rope_section {section}: three counts that sum to "
            f"index_head_dim / 2 = {cfg.index_head_dim // 2}, beside "
            "mrope_section")
    if cfg.mrope_section and not section:
        raise ValueError("mrope_section with an indexer needs "
                         "index_rope_section (its pairs' axes)")


def _check_eva(cfg) -> None:
    """EVA attention's fields and what rides with the configuration that
    has them (a float32 stream, the unit-offset norm, several prediction
    heads), each refusal with the field's name."""
    if cfg.residual_dtype not in (None, "float32"):
        raise ValueError(f"residual_dtype={cfg.residual_dtype!r}; choose "
                         "float32 (None: the compute dtype)")
    if cfg.residual_dtype and cfg.layer_types:
        raise NotImplementedError(
            "residual_dtype with layer_types: the stack of "
            "models/gpt/mixed_stack.py carries the stream in the compute "
            "dtype; no test covers another")
    if cfg.norm_unit_offset and cfg.norm != "rmsnorm":
        raise ValueError("norm_unit_offset without norm: rmsnorm")
    if cfg.num_pred_heads < 1 or (cfg.num_pred_heads > 1
                                  and cfg.tie_word_embeddings):
        raise ValueError(
            f"num_pred_heads {cfg.num_pred_heads}: at least 1, and more than "
            "one only in an untied head (tie_word_embeddings: False): the "
            "word table has one row a token")
    chunk, window = cfg.eva_chunk_size, cfg.eva_window_size
    if not (chunk or window):
        return
    if min(chunk, window) < 1 or window % chunk:
        raise ValueError(
            f"eva_chunk_size {chunk} and eva_window_size {window} come "
            "together, whole chunks to a window")
    beside = [n for n, on in (
        ("sliding_window", cfg.sliding_window),
        ("num_key_value_heads (grouped heads)",
         cfg.kv_heads != cfg.num_attention_heads),
        ("a latent (kv_lora_rank)", cfg.kv_lora_rank),
        ("an indexer (index_topk)", cfg.index_topk),
        ("layer_types", cfg.layer_types),
        ("qk_norm", cfg.qk_norm),
        ("an expert layer", cfg.expert_mode),
        ("rope_layout", cfg.rope_layout)) if on]
    if beside:
        raise NotImplementedError(
            f"EVA attention (eva_window_size) beside {beside}: nobody wrote "
            "the pooled rows of a chunk for them (models/gpt/eva.py: as many "
            "key heads as query heads, every layer alike)")
    if cfg.position_embedding != "rope" or cfg.use_bias:
        raise NotImplementedError(
            "EVA attention takes position_embedding: rope (its keys are "
            "pooled after the rotation) and no biases: what "
            "tests/test_evabyte_serving.py covers")


VISION_FIELDS = ("hidden_size", "num_layers", "num_heads",
                 "intermediate_size", "patch_size", "grid", "merge",
                 "image_token_id")


def _check_positions_and_tower(cfg) -> None:
    """``mrope_section`` and the ``vision`` group, each refusal with the
    field's name."""
    section = cfg.mrope_section
    if section is not None:
        if cfg.position_embedding != "rope" or cfg.latent or not (
                cfg.layer_types) or cfg.rope_layout:
            raise NotImplementedError(
                "mrope_section needs position_embedding: rope in a "
                "layer_types stack of grouped attention layers, every layer "
                "rotating (models/gpt/mixed_stack.py folds the three axes; "
                "latent attention computes YaRN's angles itself)")
        if len(section) != 3 or min(section) < 0 or (
                sum(section) != cfg.head_dim // 2):
            raise ValueError(
                f"mrope_section {section}: three counts (time, height, "
                f"width) that sum to head_dim / 2 = {cfg.head_dim // 2}")
    if cfg.vision is None:
        return
    tower = cfg.vision_fields
    missing = [n for n in VISION_FIELDS if n not in tower]
    unknown = sorted(set(tower) - set(VISION_FIELDS))
    if missing or unknown:
        raise ValueError(f"vision group: missing {missing}, unknown "
                         f"{unknown}; it holds {list(VISION_FIELDS)}")
    if not cfg.layer_types or cfg.position_embedding != "rope":
        raise NotImplementedError(
            "a vision group needs a layer_types stack with rotary positions "
            "(its rows enter models/gpt/mixed_stack.py's stack)")
    if tower["hidden_size"] % tower["num_heads"] or tower["merge"] != 2 or (
            min(tower["num_layers"], tower["grid"], tower["patch_size"]) < 1):
        raise ValueError(
            f"vision group {tower}: num_heads divides hidden_size, merge is "
            "2 (a row is 2 x 2 patches), and layers, grid and patch_size "
            "are at least 1")
    if not 0 <= tower["image_token_id"] < cfg.vocab_size:
        raise ValueError(f"vision.image_token_id {tower['image_token_id']} "
                         f"outside the vocabulary of {cfg.vocab_size}")


def rows_in(word_emb, input_ids, input_rows):
    """The rows that enter the stack: the word table's at ``input_ids``,
    and, where a caller hands ``input_rows`` = ``(rows [b, s, hidden],
    is_image [b, s] bool)``, a tower's rows in the places ``is_image`` marks
    (serving/engine.py: the rows a vision tower made of an image)."""
    x = word_emb[input_ids]
    if input_rows is None:
        return x
    rows, is_image = input_rows
    import jax.numpy as jnp

    return jnp.where(is_image[..., None], rows.astype(x.dtype), x)


def fold_mrope(rope, section):
    """``(cos, sin)`` ``[3, b, s, pairs]`` of the three axes' angles (what
    ``model.rope_tables`` gives for positions ``[3, b, s]``) folded to ``[b,
    s, pairs]``: pair ``j`` from the axis whose section it falls in.
    Tables of ONE axis ``[b, s, pairs]`` pass as they are, so a text-only
    call with three equal axes reads the same bits either way."""
    if not section or rope[0].ndim == 3:
        return rope
    import jax.numpy as jnp

    pick = jnp.asarray(np.repeat(np.arange(3), section))[None, None, None, :]
    return tuple(jnp.take_along_axis(t, pick, axis=0)[0] for t in rope)


def stack_of(model):
    """What ``GPTModel`` runs its layers with: its own ``_decoder_stack``
    (one scanned body over layers that hold the same parameters) or, for a
    configuration with ``layer_types``, ``mixed_stack.MixedStack`` (for
    latent attention its subclass ``latent.LatentStack``, which hands the
    same body the latent operator and its two leaves)."""
    if not model.cfg.layer_types:
        if model.cfg.residual_dtype:
            # the stream enters the layer loop in its own dtype (a row of
            # the word table is exact in the compute dtype its weights have)
            import jax.numpy as jnp

            return lambda x, *args, **kwargs: model._decoder_stack(
                x.astype(jnp.dtype(model.cfg.residual_dtype)), *args,
                **kwargs)
        return model._decoder_stack
    if model.cfg.latent:
        from fleetx_tpu.models.gpt.latent import LatentStack

        return LatentStack(model.cfg, name="layers")
    from fleetx_tpu.models.gpt.mixed_stack import MixedStack

    return MixedStack(model.cfg, name="layers")


def unit_offset_norm(cfg, name):
    """RMSNorm as ``x^ * (1 + w)`` (``norm_unit_offset``; the module is
    ``models/gpt/eva.py``'s)."""
    from fleetx_tpu.models.gpt.eva import UnitOffsetRMSNorm

    return UnitOffsetRMSNorm(cfg.norm_eps, cfg.dtype, name=name)


def layer_class(cfg, default):
    """The decoder layer class for ``cfg``: ``default`` (model.py's) for
    the blocks it writes, ``hybrid.HybridDecoderLayer`` otherwise."""
    if not cfg.layer_kinds:
        return default
    from fleetx_tpu.models.gpt.hybrid import HybridDecoderLayer

    return HybridDecoderLayer
