"""GPT model family — TPU-native Flax implementation.

Capability parity with the reference's THREE hand-written GPT variants —
single-card (/root/reference/ppfleetx/models/language_model/gpt/dygraph/
single_model.py:68-1247), TP/PP/SP hybrid (dygraph/hybrid_model.py:49-1096)
and auto-parallel (auto/auto_model.py:88-697) — collapsed into ONE model:
logical-axis annotations (vocab/heads/mlp/embed) make the same module run
single-device, tensor-parallel (Column/RowParallelLinear semantics via GSPMD),
ZeRO-sharded, and sequence-parallel, with pipeline handled by the stage axis
in fleetx_tpu/parallel/pipeline.py.

Reference feature map:
- fuse_attn_qkv (single_model.py:108-131)        -> ``fuse_attn_qkv`` flag
- selective recompute full/full_attn/core_attn + no_recompute_layers
  (single_model.py:270-345,473-475)              -> ``remat_*`` fields, named
  checkpoint policies over the scanned layer stack
- sequence_parallel [s/n,b,h] Scatter/Gather ops (sequence_parallel_utils.py)
  -> ``act_seq`` sharding constraint; XLA emits the all-gather/reduce-scatter
- tied-embedding logits via parallel_matmul (hybrid_model.py:49-71)
  -> einsum against the (vocab, embed)-partitioned embedding table
- kv-cache generation (single_model.py:781-1247) -> flax 'cache' collection
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from fleetx_tpu.models.gpt import block_fields
from fleetx_tpu.ops.attention import causal_attention
Dtype = Any

default_kernel_init = nn.initializers.normal(stddev=0.02)


@dataclasses.dataclass(frozen=True)
class GPTConfig(block_fields.BlockLayoutFields):
    """GPT model hyperparameters incl. parallel/remat/flash switches
    (reference GPTModel construction args; block_fields.py has more)."""
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_attention_heads: int = 16
    ffn_hidden_size: Optional[int] = None
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 1024
    initializer_range: float = 0.02
    fuse_attn_qkv: bool = True
    sequence_parallel: bool = False
    use_recompute: bool = False
    recompute_granularity: Optional[str] = None  # full | full_attn | core_attn
    # extra checkpoint_name'd tensors to SAVE on top of the granularity's
    # base save-set: 'qkv_out', 'ffn_gelu' (the widest), 'mlp_out',
    # 'attn_out'. core_attn's base is what the flash backward kernels read:
    # 'core_attn_out' and the row statistic 'core_attn_lse' ([b*h, s]
    # float32: 1 MB a layer at 345M, batch 16). docs/PERFORMANCE.md.
    recompute_extra_saves: Optional[Tuple[str, ...]] = None
    no_recompute_layers: Optional[Tuple[int, ...]] = None
    use_flash_attention: bool = True
    # hidden dropouts via the lowbias32 counter hash (ops/dropout.py) —
    # one threefry fold per call instead of a per-element keystream;
    # measured ~12%/step on v5e at 345M. False restores nn.Dropout.
    fast_dropout: bool = True
    scan_layers: bool = True
    dtype: Dtype = jnp.bfloat16  # compute dtype; params always fp32
    # pipeline parallelism (consumed by fleetx_tpu/parallel/pipeline.py)
    pp_degree: int = 1
    num_microbatches: int = 1
    # context parallelism: ring attention over the 'cp' mesh axis; inputs
    # must be in zig-zag sequence order (parallel/context_parallel.py)
    cp_degree: int = 1
    # MoE (consumed by fleetx_tpu/parallel/moe.py when num_experts > 1)
    num_experts: int = 1
    expert_mode: bool = False
    gate: str = "gshard"
    top_k: int = 2
    capacity_factor: float = 1.2
    # 'einsum' = dense [n,E,C] dispatch masks (fastest at small E);
    # 'scatter' = index scatter/gather, O(n) dispatch memory (large E);
    # 'auto' picks scatter once the dense masks would dominate memory
    moe_dispatch: str = "auto"
    # gate "softmax_topk" (parallel/moe.py DroplessMoEMLP): softmax over
    # all experts in float32, the ``top_k`` largest kept with their
    # weights as they are (``norm_topk_prob`` renormalises them to sum to
    # one), no capacity and no dropped token; gated-SiLU experts of width
    # ``ffn_hidden_size`` without biases
    norm_topk_prob: bool = False
    # ---- the decoder block's kind. The defaults are the GPT-2 block
    # (learned positions, LayerNorm, GELU MLP, biases, tied head); a YAML's
    # ``Model`` section sets the others (configs/nlp/olmoe/).
    # "rope": no position table; q and k are rotated (whole head, two
    # halves, base ``rope_theta``) at ``position_ids`` BEFORE the cache
    # write, so a cached key is rotated once
    position_embedding: str = "learned"   # learned | rope
    rope_theta: float = 10000.0
    norm: str = "layernorm"               # layernorm | rmsnorm
    norm_eps: float = 1e-5
    mlp_act: str = "gelu"    # gelu | swiglu (gated SiLU) | reglu (experts)
    use_bias: bool = True
    # RMSNorm with a learned weight over the WHOLE q and k projection
    # (all heads), before the split into heads and the rotation
    qk_norm: bool = False
    tie_word_embeddings: bool = True      # False: an ``lm_head`` of its own
    # the serving family name (/healthz ``model``, /v1/models)
    family: str = "gpt"
    # virtual/interleaved pipeline: each physical stage owns this many
    # non-contiguous layer chunks (reference num_virtual_pipeline_stages,
    # hybrid_model.py:1095)
    virtual_pp_degree: int = 1
    # virtual-chunk schedule: True fuses the v chunk passes into one
    # streamed scan (parallel/pipeline.py module docstring), False chains
    # per-chunk scans; None resolves from FLEETX_VPP_STREAM (default on)
    virtual_pp_stream: Optional[bool] = None
    balance_loss_weight: float = 0.01
    # decode kv-cache length; None = max_position_embeddings. Generation
    # drivers set this to prompt_len + max_length so per-step cache traffic
    # (attention reads, beam reorders) scales with the actual decode span,
    # not the model's position ceiling.
    decode_cache_len: Optional[int] = None
    # paged decode cache (serving/cache_manager.py): when decode_num_pages
    # is set, decode-mode kv caches are ONE shared pool of
    # [decode_num_pages, decode_page_size, heads*head_dim] pages instead
    # of per-row [b, decode_cache_len, ...] buffers; each row addresses
    # its logical [0, decode_cache_len) window through a block table of
    # page indices (``block_tables`` threading). decode_page_size must be
    # a multiple of 8 for the paged flash-decode kernel, and
    # decode_cache_len a multiple of decode_page_size.
    decode_num_pages: Optional[int] = None
    decode_page_size: Optional[int] = None
    # decode kv-cache precision: None keeps K/V at the compute dtype;
    # "int8" stores both the slot cache and the paged pool as int8 with
    # per-vector fp32 scales (ops/quant.quantize_kv) — ~2x tokens per HBM
    # byte on the bandwidth-bound decode path. The flash-decode kernels
    # dequantize in VMEM; dense fallbacks dequantize via the shared
    # helper, so every attention path sees identical values
    # (docs/QUANTIZATION.md; FLEETX_SERVING_KV_DTYPE wires it in serving).
    decode_kv_dtype: Optional[str] = None
    # fuse the LM head matmul + cross-entropy into the Pallas blockwise
    # kernel (ops/pallas/ce_loss.py): the [tokens, vocab] logits never
    # materialize. Opt-in; intended for mp=1 runs (a vocab-sharded
    # embedding would be gathered around the kernel).
    fused_ce: bool = False

    @property
    def head_dim(self) -> int:
        return self.head_size or self.hidden_size // self.num_attention_heads

    @property
    def ffn_size(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @classmethod
    def from_model_config(cls, model_cfg) -> "GPTConfig":
        """Build from a YAML ``Model`` section (reference schema)."""
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in dict(model_cfg).items() if k in known and v is not None}
        if isinstance(kw.get("dtype"), str):
            kw["dtype"] = jnp.dtype(kw["dtype"]).type
        for name in ("no_recompute_layers", *block_fields.LAYOUT_FIELDS):
            if kw.get(name) is not None:
                kw[name] = tuple(kw[name])
        res = kw.get("recompute_extra_saves")
        if res is not None:
            if isinstance(res, str):  # "qkv_out,ffn_gelu" CLI/-o form
                res = [s for s in res.split(",") if s]
            kw["recompute_extra_saves"] = tuple(res)
        if model_cfg.get("num_experts") and model_cfg["num_experts"] > 1:
            kw["expert_mode"] = True
        return cls(**kw)

    def __post_init__(self) -> None:
        """Refuse block kinds nobody wrote and combinations no test runs."""
        for field, allowed in (("position_embedding", ("learned", "rope")),
                               ("norm", ("layernorm", "rmsnorm")),
                               ("mlp_act", ("gelu", "swiglu", "reglu"))):
            if getattr(self, field) not in allowed:
                raise ValueError(
                    f"{field}={getattr(self, field)!r}; choose "
                    + " | ".join(allowed))
        if self.position_embedding == "rope":
            if self.head_dim % 2:
                raise ValueError("rope needs an even head size")
            if self.pp_degree > 1 or self.cp_degree > 1:
                raise NotImplementedError(
                    "rotary positions under pipeline or context parallelism "
                    "(the stage and ring paths do not carry positions)")
        if self.gate == "softmax_topk" and self.expert_mode and not (
                1 <= self.top_k <= self.num_experts):
            raise ValueError(f"top_k {self.top_k} of {self.num_experts} experts")
        block_fields.check(self)


def _dense(features, logical_axes, name, use_bias=True, dtype=jnp.bfloat16):
    """Dense with logical-axis-partitioned kernel; bias follows the kernel's
    output axes. The axes make it column (out on mp) or row parallel (in on mp)."""
    from fleetx_tpu.parallel import collective_matmul
    return nn.DenseGeneral(
        features=features,
        axis=-1,
        use_bias=use_bias,
        dtype=dtype,
        param_dtype=jnp.float32,
        kernel_init=nn.with_logical_partitioning(default_kernel_init, logical_axes),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(), logical_axes[1:]),
        name=name, dot_general=collective_matmul.for_kernel(logical_axes),
    )


def attn_out_dense(hidden_size, dtype, name="out_proj", use_bias=True):
    """Row-parallel [.., heads, kv] -> [.., embed]; GPT/ERNIE/ViT share it."""
    from fleetx_tpu.parallel import collective_matmul
    return nn.DenseGeneral(
        features=hidden_size,
        axis=(-2, -1),
        use_bias=use_bias,
        dtype=dtype,
        param_dtype=jnp.float32,
        kernel_init=nn.with_logical_partitioning(
            default_kernel_init, ("heads", "kv", "embed")
        ),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(), ("norm",)),
        name=name, dot_general=collective_matmul.for_kernel(("heads", "kv", "embed")),
    )


class SelfAttention(nn.Module):
    """Causal self-attention with optional fused qkv and kv-cache decode.

    TP semantics: q/k/v projections are column-parallel over ``heads``,
    out-projection row-parallel over ``embed`` (reference
    hybrid_model.py:131-174's ColumnParallelLinear/RowParallelLinear)."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, attn_mask=None, *, deterministic=True, decode=False,
                 cache_positions=None, block_tables=None, layer_index=None,
                 rope=None):
        cfg = self.cfg
        h, nh, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
        proj = functools.partial(_dense, logical_axes=("embed", "heads", "kv"),
                                 use_bias=cfg.use_bias, dtype=cfg.dtype)

        if cfg.fuse_attn_qkv:
            qkv = proj((nh, 3 * hd), name="qkv_proj")(x)
            qkv = checkpoint_name(qkv, "qkv_out")
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            q = proj((nh, hd), name="q_proj")(x)
            k = proj((nh, hd), name="k_proj")(x)
            v = proj((nh, hd), name="v_proj")(x)
            q, k, v = (checkpoint_name(t, "qkv_out") for t in (q, k, v))
        if cfg.qk_norm:
            q = _qk_norm(cfg, "q_norm")(q)
            k = _qk_norm(cfg, "k_norm")(k)
        if rope is not None:
            # before the cache write: a cached key is rotated once, at the
            # position it was written for, and the kernels see plain keys
            q, k = apply_rope(q, rope), apply_rope(k, rope)

        causal = True
        if decode:
            kv_pad_mask = attn_mask  # pre-causal-merge mask: left-pad layout
            k, v, attn_mask, decode_end, paged, kv_scales = self._update_cache(
                k, v, attn_mask, cache_positions, block_tables, layer_index
            )
            causal = False  # the cache mask encodes absolute-position causality
            if paged is not None:
                # Page-granular cache (serving): k/v above are the RAW
                # shared page pools. Single-query steps take the paged
                # flash kernel (block table rides scalar prefetch, HBM
                # traffic = the row's live pages); everything else gathers
                # each row's logical buffer and joins the dense fallback.
                from fleetx_tpu.ops.pallas.decode_attention import (
                    flash_decode_paged_attention,
                    paged_gather_kv,
                )

                tables = paged
                if decode_end is not None and self._flash_decode_ok(
                    kv_pad_mask, tables.shape[1] * cfg.decode_page_size,
                    deterministic, tile_len=cfg.decode_page_size,
                ):
                    out = flash_decode_paged_attention(
                        q, k, v, tables=tables, end=decode_end,
                        starts=self._pad_starts(kv_pad_mask, q.shape[0]),
                        k_scale=kv_scales and kv_scales[0],
                        v_scale=kv_scales and kv_scales[1],
                        mesh=self._decode_shard_mesh(),
                    )
                    out = checkpoint_name(out, "core_attn_out")
                    return self._out_proj(out)
                # dense fallback: gather each row's pages (and, over an
                # int8 pool, its scale pages) through the same table
                k = paged_gather_kv(k, tables)
                v = paged_gather_kv(v, tables)
                if kv_scales is not None:
                    kv_scales = tuple(
                        paged_gather_kv(s, tables) for s in kv_scales)
            elif decode_end is not None and self._flash_decode_ok(
                kv_pad_mask, k.shape[1], deterministic, batch=q.shape[0]
            ):
                # Single-query fast path: the Pallas flash-decode kernel reads
                # only the KV blocks inside [starts, cache_index) — per-step
                # HBM traffic scales with the decoded prefix, not the cache
                # capacity (fleetx_tpu/ops/pallas/decode_attention.py).
                from fleetx_tpu.ops.pallas.decode_attention import (
                    flash_decode_attention,
                )

                out = flash_decode_attention(
                    q, k, v, end=decode_end,
                    starts=self._pad_starts(kv_pad_mask, q.shape[0]),
                    k_scale=kv_scales and kv_scales[0],
                    v_scale=kv_scales and kv_scales[1],
                    mesh=self._decode_shard_mesh(),
                )
                out = checkpoint_name(out, "core_attn_out")
                return self._out_proj(out)
            # dense fallback (prefill, custom masks, off-TPU): unfold the
            # lane-dense buffers [b, len, nh*hd] back to per-head form
            # and, over an int8 cache, dequantize them via the shared
            # helper — correctness paths cost what dense always cost, the
            # flash paths above never materialize this
            k = k.reshape(*k.shape[:2], nh, hd)
            v = v.reshape(*v.shape[:2], nh, hd)
            if kv_scales is not None:
                from fleetx_tpu.ops.quant import dequantize_kv

                k = dequantize_kv(k, kv_scales[0][..., None], q.dtype)
                v = dequantize_kv(v, kv_scales[1][..., None], q.dtype)

        if cfg.cp_degree > 1 and not decode:
            # Ring attention: sequence stays sharded over the cp axis; KV
            # blocks rotate with ppermute (parallel/context_parallel.py).
            # Attention dropout runs inside the per-hop flash kernels and is
            # keyed on global positions — the mask matches the non-cp path.
            if attn_mask is not None:
                raise NotImplementedError(
                    "context parallelism does not support a custom attn_mask"
                )
            from fleetx_tpu.parallel.context_parallel import ring_self_attention

            cp_dropout_rng = None
            if cfg.attention_probs_dropout_prob > 0.0 and not deterministic:
                cp_dropout_rng = self.make_rng("dropout")
            out = ring_self_attention(
                q, k, v, causal=causal, expected_cp=cfg.cp_degree,
                dropout_rate=(0.0 if deterministic
                              else cfg.attention_probs_dropout_prob),
                dropout_rng=cp_dropout_rng,
            )
            out = checkpoint_name(out, "core_attn_out")
            return self._out_proj(out)

        dropout_rng = None
        if cfg.attention_probs_dropout_prob > 0.0 and not deterministic:
            dropout_rng = self.make_rng("dropout")
        out = causal_attention(
            q,
            k,
            v,
            causal=causal,
            attn_mask=attn_mask,
            dropout_rate=cfg.attention_probs_dropout_prob,
            dropout_rng=dropout_rng,
            deterministic=deterministic,
            # decode steps that miss the flash-decode fast path (prefill,
            # custom masks) land here; causal_attention's own shape checks
            # route them to the XLA path, so the flag no longer needs the
            # `and not decode` guard
            use_flash=cfg.use_flash_attention,
            # pp>1 applies stages under nn.vmap; a nested shard_map there
            # would fight the stage sharding (parallel/pipeline.py)
            mesh_shard=cfg.pp_degree == 1,
        )
        # (causal_attention names its result "core_attn_out", on either path)
        return self._out_proj(out)

    def _out_proj(self, out):
        cfg = self.cfg
        out = attn_out_dense(cfg.hidden_size, cfg.dtype,
                             use_bias=cfg.use_bias)(out)
        return checkpoint_name(out, "attn_out")

    def _update_cache(self, k, v, attn_mask, cache_positions=None,
                      block_tables=None, layer_index=None):
        """Incremental decode: append this step's k/v at cache_index and
        build the absolute-position causal mask (query i at absolute position
        start+i may see cache positions <= start+i). Cache layout
        [batch, max_len, heads*head_dim]: lane-dense, the layout the
        flash-decode kernels block (ops/pallas/decode_attention.py
        "Layout"); the dense fallback unfolds it at the call site.

        ``cache_positions`` ([b] int32, optional) gives each batch row its
        OWN write offset instead of the shared scalar ``cache_index`` — the
        continuous-batching serving path (fleetx_tpu/serving/) runs slots at
        different decode depths in one batched step, so row b writes at
        ``cache_positions[b]`` and attends the per-row causal window
        ``[0, cache_positions[b] + s)``. The scalar ``cache_index`` is still
        advanced (to the max write end) so one-shot callers interleaving
        both styles stay consistent. Multi-token calls (s > 1) with
        ``cache_positions`` are the CHUNKED-prefill seam: successive calls
        at increasing offsets write a prompt's K/V incrementally, and the
        absolute-position causal mask keeps each chunk's queries reading
        exactly the prefix earlier chunks wrote — byte-identical to one
        whole-prompt call (docs/SERVING.md chunked prefill).

        When ``cfg.decode_num_pages`` is set the cache is page-granular and
        ``block_tables`` ([b, pages_per_row] int32) must come along with
        ``cache_positions`` — see :meth:`_update_paged_cache`.

        When ``cfg.decode_kv_dtype == "int8"`` the cache leaves store int8
        values plus ``cached_key_scale``/``cached_value_scale`` fp32 leaves
        of per-vector scales (``[..., max_len, nh]``): this step's k/v
        quantize on write via ``ops/quant.quantize_kv``, and the returned
        buffers are the RAW int8 caches with ``kv_scales`` carrying the
        scale buffers — the flash kernel applies them in VMEM, the dense
        fallback dequantizes in the caller.

        Returns ``(k, v, attn_mask, decode_end, paged, kv_scales)``:
        ``decode_end`` is the number of live cache positions after this
        step's write (the single-query flash-decode kernel's upper bound;
        per-row [b] under ``cache_positions``) — None during init and for
        multi-token (prefill) calls, where the fast path does not apply.
        ``paged`` is None on this contiguous layout (the paged branch
        returns the block tables and RAW page pools instead of gathered
        buffers); ``kv_scales`` is None at the native kv dtype."""
        if self.cfg.decode_num_pages is not None:
            return self._update_paged_cache(
                k, v, attn_mask, cache_positions, block_tables, layer_index
            )
        quant = self.cfg.decode_kv_dtype == "int8"
        is_init = not self.has_variable("cache", "cached_key")
        b, s, nh, hd = k.shape
        max_len = (self.cfg.decode_cache_len
                   if self.cfg.decode_cache_len is not None
                   else self.cfg.max_position_embeddings)
        ck = self.variable(
            "cache", "cached_key", jnp.zeros, (b, max_len, nh * hd),
            jnp.int8 if quant else k.dtype
        )
        cv = self.variable(
            "cache", "cached_value", jnp.zeros, (b, max_len, nh * hd),
            jnp.int8 if quant else v.dtype
        )
        if quant:
            # per-vector fp32 scales: rank 3 with the batch axis at -3,
            # so tree walkers address them like K/V leaves (ops/quant.py)
            cks = self.variable(
                "cache", "cached_key_scale", jnp.zeros,
                (b, max_len, nh), jnp.float32
            )
            cvs = self.variable(
                "cache", "cached_value_scale", jnp.zeros,
                (b, max_len, nh), jnp.float32
            )
        idx = self.variable("cache", "cache_index", lambda: jnp.array(0, jnp.int32))
        decode_end = None
        kv_scales = None
        if not is_init:
            if quant:
                from fleetx_tpu.ops.quant import quantize_kv

                k_w, k_s = quantize_kv(k)
                v_w, v_s = quantize_kv(v)
                k_s, v_s = k_s[..., 0], v_s[..., 0]  # [b, s, nh]
            else:
                k_w, v_w = k, v
            k_w = k_w.reshape(b, s, nh * hd)
            v_w = v_w.reshape(b, s, nh * hd)
            k_pos = jnp.arange(max_len)
            if cache_positions is None:
                start = idx.value
                with jax.named_scope("cache_write"):
                    ck.value = jax.lax.dynamic_update_slice(ck.value, k_w, (0, start, 0))
                    cv.value = jax.lax.dynamic_update_slice(cv.value, v_w, (0, start, 0))
                    if quant:
                        cks.value = jax.lax.dynamic_update_slice(
                            cks.value, k_s, (0, start, 0))
                        cvs.value = jax.lax.dynamic_update_slice(
                            cvs.value, v_s, (0, start, 0))
                idx.value = start + s
                if s == 1:
                    decode_end = idx.value
                q_pos = start + jnp.arange(s)  # absolute query positions
                causal = (k_pos[None, :] <= q_pos[:, None])[None, None, :, :]
            else:
                wpos = cache_positions.astype(jnp.int32)  # [b] write offsets
                row_update = jax.vmap(
                    lambda buf, new, p: jax.lax.dynamic_update_slice(
                        buf, new, (p, 0))
                )
                with jax.named_scope("cache_write"):
                    ck.value = row_update(ck.value, k_w, wpos)
                    cv.value = row_update(cv.value, v_w, wpos)
                    if quant:
                        cks.value = row_update(cks.value, k_s, wpos)
                        cvs.value = row_update(cvs.value, v_s, wpos)
                idx.value = jnp.max(wpos) + s
                if s == 1:
                    decode_end = wpos + 1  # [b]: per-row live window end
                q_pos = wpos[:, None] + jnp.arange(s)[None, :]  # [b, s]
                causal = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None, :, :]
            k, v = ck.value, cv.value
            if quant:
                kv_scales = (cks.value, cvs.value)
            attn_mask = (
                causal
                if attn_mask is None
                else (attn_mask.astype(bool) & causal)
            )
        return k, v, attn_mask, decode_end, None, kv_scales

    def _update_paged_cache(self, k, v, attn_mask, cache_positions,
                            block_tables, layer_index=None):
        """Page-granular decode cache write (``cfg.decode_num_pages`` set).

        The cache leaves are ONE pool of ``[num_pages, page_size, nh*hd]``
        shared lane-dense pages; logical position ``p`` of row ``b`` lives at physical
        page ``block_tables[b, p // page_size]``, offset ``p % page_size``.
        This step's k/v rows scatter through the tables (positions clamped
        to the logical capacity: bucket-tail/pinned writes land on the
        row's LAST logical slot or — through a zeroed table entry — on the
        reserved trash page 0, both beyond every live window; see
        serving/cache_manager.py for the safety argument). The causal mask
        is built over LOGICAL positions, so the dense fallback can consume
        it after :func:`paged_gather_kv` unchanged.

        When ``cfg.decode_kv_dtype == "int8"`` the pools store int8 with
        per-vector fp32 scale pools (``[num_pages, ps, nh]``) scattered
        through the same block tables — see :meth:`_update_cache`.

        ``layer_index`` (traced int32 scalar) says the layer scan CARRIES
        the cache (:meth:`GPTModel._decoder_stack`): the leaves are then the
        whole stack ``[L, num_pages, ps, w]``, read and written as the flat
        pool ``[L*num_pages, ps, w]`` through ``block_tables + layer_index *
        num_pages``, so a zeroed table entry of layer ``i`` lands on layer
        ``i``'s own page 0. The pools and tables handed back are the flat
        ones; the paged kernel and ``paged_gather_kv`` take them as they are.

        Returns ``(k_pages, v_pages, attn_mask, decode_end, tables,
        kv_scales)``: raw pools + tables so the caller picks paged-flash
        vs gather-dense without materializing both."""
        cfg = self.cfg
        quant = cfg.decode_kv_dtype == "int8"
        is_init = not self.has_variable("cache", "cached_key")
        b, s, nh, hd = k.shape
        ps = cfg.decode_page_size
        if ps is None or ps % 8:
            raise ValueError(
                f"decode_page_size must be a multiple of 8, got {ps}")
        max_len = (cfg.decode_cache_len if cfg.decode_cache_len is not None
                   else cfg.max_position_embeddings)
        if max_len % ps:
            raise ValueError(
                f"decode_cache_len {max_len} must be a multiple of "
                f"decode_page_size {ps}")
        ck = self.variable(
            "cache", "cached_key", jnp.zeros,
            (cfg.decode_num_pages, ps, nh * hd),
            jnp.int8 if quant else k.dtype
        )
        cv = self.variable(
            "cache", "cached_value", jnp.zeros,
            (cfg.decode_num_pages, ps, nh * hd),
            jnp.int8 if quant else v.dtype
        )
        if quant:
            cks = self.variable(
                "cache", "cached_key_scale", jnp.zeros,
                (cfg.decode_num_pages, ps, nh), jnp.float32
            )
            cvs = self.variable(
                "cache", "cached_value_scale", jnp.zeros,
                (cfg.decode_num_pages, ps, nh), jnp.float32
            )
        idx = self.variable("cache", "cache_index", lambda: jnp.array(0, jnp.int32))
        decode_end = None
        paged = None
        kv_scales = None
        if not is_init:
            if cache_positions is None or block_tables is None:
                raise ValueError(
                    "a paged decode cache needs cache_positions AND "
                    "block_tables (the serving engine threads both)")
            if quant:
                from fleetx_tpu.ops.quant import quantize_kv

                k_w, k_s = quantize_kv(k)
                v_w, v_s = quantize_kv(v)
            else:
                k_w, v_w = k, v
            wpos = cache_positions.astype(jnp.int32)       # [b] write offsets
            tables = block_tables.astype(jnp.int32)        # [b, n_pages_row]
            cached = [ck, cv] + ([cks, cvs] if quant else [])
            rows = [k_w, v_w] + ([k_s, v_s] if quant else [])
            if layer_index is not None:
                with jax.named_scope("cache_write"):
                    # carried stack [L, P, ps, w]: layer i's pages are
                    # [i*P, (i+1)*P) of the flat pool, its trash page i*P
                    tables = tables + layer_index * ck.value.shape[1]
            # THE write (a page at a time where this call is one sequence
            # over whole pages, every prefill program; else a row at a
            # time) lives in a module of its own, imported here and not at
            # the head of this file: a line added above would move every
            # line below it, and with them every training program's key in
            # the compile cache (models/gpt/resident.py has why)
            from fleetx_tpu.models.gpt import paged_write
            # merging the leading axes is a bitcast (and a no-op on one
            # layer's own [P, ps, w] pool)
            pools = paged_write.write_rows(
                [c.value.reshape((-1,) + c.value.shape[-2:]) for c in cached],
                [new.reshape(b * s, -1) for new in rows], tables, wpos,
                max_len)
            for var, pool in zip(cached, pools):
                var.value = pool.reshape(var.value.shape)
            if quant:
                kv_scales = tuple(pools[2:])
            if layer_index is None:
                idx.value = jnp.max(wpos) + s
            else:
                idx.value = idx.value.at[layer_index].set(jnp.max(wpos) + s)
            if s == 1:
                decode_end = paged_write.decode_end(block_tables, wpos, ps)
            k_pos = jnp.arange(max_len)
            q_pos = wpos[:, None] + jnp.arange(s)[None, :]  # [b, s] logical
            causal = (k_pos[None, None, :] <= q_pos[:, :, None])[:, None, :, :]
            attn_mask = (causal if attn_mask is None
                         else attn_mask.astype(bool) & causal)
            paged = tables
            k, v = pools[:2]
        return k, v, attn_mask, decode_end, paged, kv_scales

    def _flash_decode_ok(self, kv_pad_mask, cache_len: int,
                         deterministic: bool, tile_len: Optional[int] = None,
                         batch: Optional[int] = None) -> bool:
        """Static dispatch check for the single-query flash-decode path.

        The kernel handles exactly the generation-loop mask shape: an
        optional [b, 1, 1, cache_len] key-validity mask whose False slots
        are the contiguous left-pad prefix (generate()/beam_search() build
        exactly this). Anything else — arbitrary masks, attention dropout,
        untileable cache lengths — falls back to the dense XLA path.

        An ambient multi-device mesh no longer forces the fallback (the
        PR 1 guard): when the heads divide over the ``mp`` extent the
        kernels run per-shard inside ``shard_map`` over the local head
        slice (``mesh=`` on the kernel entry points). Meshes whose mp
        does not divide the heads — or, on the CONTIGUOUS layout, whose
        dp/fsdp extent does not divide ``batch`` (one-shot callers keep
        the cache batch-sharded over those axes; a shard_map that
        replicated it would all-gather the cache per step) — still fall
        back to the dense path.

        ``tile_len`` is the buffer length the kernel must tile: the page
        size on the paged path (one page is the DMA/gather unit there),
        defaulting to ``cache_len`` on the contiguous path. ``batch``
        engages the data-axis divisibility check (contiguous layout
        only — the paged pools are serving-owned and batch-replicated)."""
        cfg = self.cfg
        if not cfg.use_flash_attention:
            return False
        if not (deterministic or cfg.attention_probs_dropout_prob == 0.0):
            return False
        if kv_pad_mask is not None and (
            kv_pad_mask.ndim != 4
            or kv_pad_mask.shape[1] != 1
            or kv_pad_mask.shape[2] != 1
            or kv_pad_mask.shape[3] != cache_len
        ):
            return False
        from fleetx_tpu.ops.pallas.decode_attention import (
            decode_flash_supported,
            decode_mesh_shardable,
        )

        mesh = self._decode_shard_mesh()
        if mesh is not None and not decode_mesh_shardable(
                mesh, cfg.num_attention_heads, batch):
            return False
        return decode_flash_supported(
            cache_len if tile_len is None else tile_len)

    @staticmethod
    def _decode_shard_mesh():
        """The ambient mesh the flash-decode kernels shard_map over, or
        None for the bare (single-device) kernel call."""
        from fleetx_tpu.parallel.mesh import ambient_mesh

        mesh = ambient_mesh()
        if mesh is None or mesh.size <= 1:
            return None
        return mesh

    @staticmethod
    def _pad_starts(kv_pad_mask, batch: int):
        """Per-row first live cache position from the [b, 1, 1, cache_len]
        key-validity mask; None mask = no padding.

        The window the kernel attends is [starts, cache_index), so the mask
        contract is: False slots form a contiguous left-pad prefix (the
        generation loop's layout), with any further False slots only at
        positions the cache index has not reached yet (a right-padded
        layout is therefore also exact). Taking the FIRST True — rather
        than counting all False slots — keeps right-padded masks correct;
        arbitrary interior holes are outside the fast path's contract
        (docs/PERFORMANCE.md) and cannot be detected at trace time."""
        if kv_pad_mask is None:
            return None
        starts = jnp.argmax(
            kv_pad_mask.astype(bool)[:, 0, 0, :], axis=-1
        ).astype(jnp.int32)
        return jnp.broadcast_to(starts, (batch,))


class MLP(nn.Module):
    """FFN: column-parallel up (embed→mlp), gelu, row-parallel down
    (mlp→embed) — reference linear1/linear2 (hybrid_model.py:546-563).
    ``mlp_act: swiglu`` is the gated form: ``down(silu(gate(x)) * up(x))``."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = functools.partial(_dense, use_bias=cfg.use_bias,
                                  dtype=cfg.dtype)
        up = dense(cfg.ffn_size, ("embed", "mlp"), "up_proj")
        if cfg.mlp_act == "swiglu":
            gate = dense(cfg.ffn_size, ("embed", "mlp"), "gate_proj")(x)
            x = checkpoint_name(nn.silu(gate) * up(x), "ffn_gelu")
        else:
            x = checkpoint_name(nn.gelu(up(x), approximate=True), "ffn_gelu")
        x = dense(cfg.hidden_size, ("mlp", "embed"), "down_proj")(x)
        return checkpoint_name(x, "mlp_out")


def _dropout(cfg, name):
    """Hidden-dropout layer: hash-based by default (see ops/dropout.py);
    ``fast_dropout: False`` restores flax's threefry nn.Dropout."""
    from fleetx_tpu.ops.dropout import dropout_layer

    return dropout_layer(cfg.hidden_dropout_prob, name, cfg.fast_dropout)


def _qk_norm(cfg, name):
    """RMSNorm over a whole projection ``[.., heads, head_dim]`` (both
    axes reduced, one learned weight per element)."""
    return nn.RMSNorm(
        epsilon=cfg.norm_eps, dtype=cfg.dtype, param_dtype=jnp.float32,
        reduction_axes=(-2, -1), feature_axes=(-2, -1),
        scale_init=nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("heads", "kv")),
        name=name)


def rope_tables(position_ids, head_dim: int, theta: float):
    """``(cos, sin)`` ``[b, s, head_dim/2]`` float32 of the rotary angles
    ``position * theta**(-2i/head_dim)``."""
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = position_ids.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, rope):
    """Rotate ``x`` ``[b, s, heads, head_dim]``: the head's two halves
    ``(x1, x2)`` become ``(x1 cos - x2 sin, x2 cos + x1 sin)``, in float32."""
    cos, sin = (t[:, :, None, :] for t in rope)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _layer_norm(cfg, name):
    if getattr(cfg, "norm_unit_offset", False):  # (kept to the lines it had)
        return block_fields.unit_offset_norm(cfg, name)
    eps = getattr(cfg, "norm_eps", 1e-5)  # (ERNIE, ViT: no ``norm`` kind)
    if getattr(cfg, "norm", "layernorm") == "rmsnorm":
        return nn.RMSNorm(
            epsilon=eps, dtype=cfg.dtype, param_dtype=jnp.float32,
            scale_init=nn.with_logical_partitioning(
                nn.initializers.ones_init(), ("norm",)),
            name=name)
    return nn.LayerNorm(
        epsilon=eps,
        dtype=cfg.dtype,
        param_dtype=jnp.float32,
        scale_init=nn.with_logical_partitioning(nn.initializers.ones_init(), ("norm",)),
        bias_init=nn.with_logical_partitioning(nn.initializers.zeros_init(), ("norm",)),
        name=name,
    )


class DecoderLayer(nn.Module):
    """Pre-LN transformer decoder layer (reference TransformerDecoderLayer,
    single_model.py:286-505)."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, attn_mask=None, deterministic=True, decode=False,
                 cache_positions=None, block_tables=None, rope=None,
                 expert_stack=None, layer_index=None):
        cfg = self.cfg
        x = _constrain_act(x, cfg)
        residual = x
        y = _layer_norm(cfg, "norm1")(x)
        y = SelfAttention(cfg, name="attn")(
            y, attn_mask, deterministic=deterministic, decode=decode,
            cache_positions=cache_positions, block_tables=block_tables,
            layer_index=layer_index, rope=rope,
        )
        y = _dropout(cfg, "attn_dropout")(y, deterministic=deterministic)
        x = residual + y
        x = residual = _constrain_act(x, cfg)
        y = _layer_norm(cfg, "norm2")(x)
        if cfg.expert_mode and cfg.gate == "softmax_topk":
            from fleetx_tpu.parallel.moe import DroplessMoEMLP

            y = DroplessMoEMLP(cfg, name="moe_mlp")(
                y, decode=decode, layer_index=layer_index,
                expert_stack=expert_stack)
        elif cfg.expert_mode:
            from fleetx_tpu.parallel.moe import MoEMLP

            y = MoEMLP(cfg, name="moe_mlp")(y)
        else:
            y = MLP(cfg, name="mlp")(y)
        y = _dropout(cfg, "mlp_dropout")(y, deterministic=deterministic)
        x = residual + y
        return _constrain_act(x, cfg)


def _constrain_act(x, cfg: GPTConfig, point="residual"):
    """Activation sharding at a named point of the block (sharding.ACT_AXES):
    batch over the data axes; between two blocks, seq over mp iff sequence parallel."""
    from fleetx_tpu.parallel.sharding import ACT_AXES, with_logical_constraint
    axes = ACT_AXES[point]
    return with_logical_constraint(x, axes) if x.ndim == len(axes) else x



class _ScanLayer(nn.Module):
    """Adapter giving DecoderLayer the (carry, out) contract nn.scan wants."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, attn_mask, deterministic, decode,
                 cache_positions=None, block_tables=None, rope=None,
                 expert_stack=None, layer_index=None):
        x = block_fields.layer_class(self.cfg, DecoderLayer)(self.cfg, name="layer")(
            x, attn_mask, deterministic, decode, cache_positions,
            block_tables, rope, expert_stack, layer_index
        )
        return x, None


# every checkpoint_name site in this model; a typo'd save name would
# otherwise silently match nothing and masquerade as the base save-set
_CHECKPOINT_NAMES = frozenset({"qkv_out", "core_attn_out", "core_attn_lse",
                               "attn_out", "ffn_gelu", "mlp_out"})
# ("core_attn_lse" is named in ops/pallas/flash_attention.py's forward rule)


def _remat_policy(cfg: GPTConfig):
    if not cfg.use_recompute:
        return None
    g = cfg.recompute_granularity or "full"
    extra = tuple(cfg.recompute_extra_saves or ())
    unknown = set(extra) - _CHECKPOINT_NAMES
    if unknown:
        raise ValueError(
            f"recompute_extra_saves {sorted(unknown)} match no "
            f"checkpoint_name site; known: {sorted(_CHECKPOINT_NAMES)}"
        )
    if g == "full":
        if extra:  # 'full' + saves = a graded point between full and attn
            return jax.checkpoint_policies.save_only_these_names(*extra)
        return jax.checkpoint_policies.nothing_saveable
    if g == "full_attn":
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", *extra)
    if g == "core_attn":  # what the flash backward kernels read
        return jax.checkpoint_policies.save_only_these_names(
            "core_attn_out", "core_attn_lse", *extra)
    raise ValueError(f"unknown recompute_granularity {g!r}")


class GPTModel(nn.Module):
    """Embeddings + decoder stack + final LN (reference GPTModel,
    single_model.py:548-657). Returns hidden states [b, s, h]."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None, attn_mask=None, *,
                 deterministic=True, decode=False, cache_positions=None,
                 block_tables=None, input_rows=None):
        cfg = self.cfg
        word_emb = self.param(
            "word_embeddings",
            nn.with_logical_partitioning(
                nn.initializers.normal(cfg.initializer_range), ("vocab", "embed")
            ),
            (cfg.vocab_size, cfg.hidden_size),
            jnp.float32,
        )
        rotary = cfg.position_embedding == "rope"
        if not rotary:
            pos_emb = self.param(
                "position_embeddings",
                nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.initializer_range), (None, "embed")
                ),
                (cfg.max_position_embeddings, cfg.hidden_size),
                jnp.float32,
            )
        rope = None
        with jax.named_scope("embed"):
            if position_ids is None:
                # decode callers must pass explicit position_ids per step
                position_ids = jnp.arange(input_ids.shape[1])[None, :]
                position_ids = jnp.broadcast_to(position_ids, input_ids.shape)
            if rotary:
                # the angles once, for every layer (they ride the layer
                # loop as a broadcast input)
                rope = rope_tables(position_ids, cfg.head_dim, cfg.rope_theta)
                x = block_fields.rows_in(word_emb, input_ids, input_rows)
            else:
                x = word_emb[input_ids] + pos_emb[position_ids]
            x = x.astype(cfg.dtype)
        x = _constrain_act(x, cfg)
        x = _dropout(cfg, "embed_dropout")(x, deterministic=deterministic)

        x = block_fields.stack_of(self)(x, attn_mask, deterministic=deterministic,
                                decode=decode, cache_positions=cache_positions,
                                block_tables=block_tables, rope=rope)
        x = _layer_norm(cfg, "final_norm")(x)
        return _constrain_act(x, cfg, "whole")

    def _expert_stack(self):
        """The three expert weights of ALL layers as the layer loop holds
        them, ``[layers, experts, in, out]``, for a softmax top-k expert
        layer in a cached forward (None otherwise): handed to every layer
        next to its own slice, which it then leaves alone. The layer's
        Mosaic kernels pick the layer and the experts that have rows in
        their index maps, where the loop's slice of a 268 MB matrix would
        first be copied (ops/pallas/moe_gmm.py)."""
        cfg = self.cfg
        if (not cfg.expert_mode or cfg.gate != "softmax_topk"
                or self.is_initializing()):
            return None
        held = nn.meta.unbox(
            self.variables["params"]["layers"]["layer"]["moe_mlp"])
        return tuple(held[k] for k in ("w_gate", "w_up", "w_down"))

    def _decoder_stack(self, x, attn_mask, *, deterministic, decode,
                       cache_positions=None, block_tables=None, rope=None):
        cfg = self.cfg
        policy = _remat_policy(cfg)
        selective = cfg.no_recompute_layers
        if cfg.pp_degree > 1 and not decode:
            from fleetx_tpu.parallel.pipeline import PipelinedStack

            layer_cls = _ScanLayer
            if policy is not None:
                layer_cls = nn.remat(
                    _ScanLayer, policy=policy, prevent_cse=False, static_argnums=(3, 4)
                )
            return PipelinedStack(
                cfg,
                layer_cls,
                cfg.pp_degree,
                max(cfg.num_microbatches, 1),
                virtual_pp=max(cfg.virtual_pp_degree, 1),
                stream=cfg.virtual_pp_stream,
                name="layers",
            )(x, attn_mask, deterministic)
        if cfg.scan_layers and not selective:
            layer_cls = _ScanLayer
            if policy is not None:
                layer_cls = nn.remat(
                    _ScanLayer,
                    policy=policy,
                    prevent_cse=False,
                    static_argnums=(3, 4),
                )
            # Paged serving: the page pools ride the layer loop as a CARRY
            # and every layer scatters into its own pages of the whole
            # stack, which XLA updates in place. A scanned input and
            # stacked output (the form training and the contiguous layout
            # keep) has each layer's pool sliced out and restacked and the
            # donated stack copied: the whole pool moves three times a
            # program to write a few rows (PERF.md, PR 24).
            carried = (decode and cfg.decode_num_pages is not None
                       and block_tables is not None)
            expert_stack = self._expert_stack() if carried else None
            # ``rope`` and ``expert_stack`` are None (empty trees: no
            # input) for the GPT-2 block
            args = (x, attn_mask, deterministic, decode, cache_positions,
                    block_tables, rope, expert_stack)
            if carried or cfg.layer_kinds:  # each layer's own index
                args += (jnp.arange(cfg.num_layers, dtype=jnp.int32),)
            stack = nn.scan(
                layer_cls,
                variable_axes={"params": 0, "intermediates": 0, "routing": 0,
                               **({} if carried else {"cache": 0})},
                variable_carry="cache" if carried else False,
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast,) * 7 + ((0,) * (len(args) - 8)),
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            x, _ = stack(cfg, name="layers")(*args)
            return x
        # Unrolled path: needed for per-layer recompute opt-out
        # (no_recompute_layers, reference single_model.py:473-475).
        skip = set(selective or ())
        for i in range(cfg.num_layers):
            layer_cls = DecoderLayer
            if policy is not None and i not in skip:
                layer_cls = nn.remat(
                    DecoderLayer, policy=policy, prevent_cse=False, static_argnums=(3, 4)
                )
            x = layer_cls(cfg, name=f"layer_{i}")(
                x, attn_mask, deterministic, decode, cache_positions,
                block_tables, rope
            )
        return x


class GPTForPretraining(nn.Module):
    """LM head with tied embeddings: logits = h @ word_emb^T (reference
    GPTForPretraining + parallel_matmul, single_model.py:660-699,
    hybrid_model.py:49-71 — the vocab-parallel matmul + allgather is GSPMD's
    job here)."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None, attn_mask=None, *,
                 deterministic=True, decode=False, cache_positions=None,
                 block_tables=None, labels=None, input_rows=None):
        backbone = GPTModel(self.cfg, name="gpt")
        x = backbone(
            input_ids,
            position_ids,
            attn_mask,
            deterministic=deterministic,
            decode=decode,
            cache_positions=cache_positions,
            block_tables=block_tables, input_rows=input_rows,
        )
        if self.cfg.tie_word_embeddings:
            word_emb = backbone.variables["params"]["word_embeddings"]
            emb = (word_emb.value if isinstance(word_emb, nn.Partitioned)
                   else word_emb)
        else:  # an output head of its own, laid out as the embedding is
            emb = self.param(
                "lm_head",
                nn.with_logical_partitioning(
                    nn.initializers.normal(self.cfg.initializer_range),
                    ("vocab", "embed")),
                (self.cfg.head_rows, self.cfg.hidden_size), jnp.float32)
        if labels is not None and self.cfg.fused_ce:
            # blockwise fused LM-head + CE: returns PER-TOKEN loss [b, s]
            # (callers apply loss_mask); the [b, s, vocab] logits never
            # exist — ops/pallas/ce_loss.py
            from fleetx_tpu.ops.pallas.ce_loss import fused_linear_ce

            b, s, hd = x.shape
            with jax.named_scope("loss"):
                tok = fused_linear_ce(
                    x.reshape(b * s, hd), emb.astype(self.cfg.dtype),
                    labels.reshape(-1),
                )
            return tok.reshape(b, s)
        with jax.named_scope("logits"):
            logits = jnp.einsum(
                "bsh,vh->bsv", x, emb.astype(self.cfg.dtype),
                preferred_element_type=jnp.float32,
            )
        return logits


class GPTForSequenceClassification(nn.Module):
    """Classification over the last non-pad token's hidden state (reference
    GPTForSequenceClassification, single_model.py:739-778: score head,
    gather at sequence end)."""

    cfg: GPTConfig
    num_classes: int = 2

    @nn.compact
    def __call__(self, input_ids, position_ids=None, attn_mask=None,
                 seq_lens=None, *, deterministic=True):
        x = GPTModel(self.cfg, name="gpt")(
            input_ids, position_ids, attn_mask, deterministic=deterministic
        )
        if seq_lens is None:
            last = jnp.full((input_ids.shape[0],), input_ids.shape[1] - 1, jnp.int32)
        else:
            last = jnp.maximum(seq_lens - 1, 0).astype(jnp.int32)
        pooled = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        return _dense(self.num_classes, ("embed", None), "score",
                      dtype=jnp.float32, use_bias=False)(pooled.astype(jnp.float32))


def convert_qkv_layout(gpt_params: dict, to_fused: bool) -> dict:
    """Convert attention projection params between the fused single-matmul
    layout (``qkv_proj``: kernel [..., embed, heads, 3*kv]) and the split
    layout (``q_proj``/``k_proj``/``v_proj``: kernel [..., embed, heads, kv])
    — the reference's finetune checkpoint converter
    (/root/reference/ppfleetx/models/language_model/language_module.py:
    293-372 ``process_qkv_weight``). Pure tree rewrite; works on raw arrays
    (callers unbox first) at any nesting depth, including scan-stacked
    [num_layers, ...] leaves."""
    import numpy as _np

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if k == "qkv_proj" and not to_fused and isinstance(v, dict):
                kern, bias = v.get("kernel"), v.get("bias")
                for idx, name in enumerate(("q_proj", "k_proj", "v_proj")):
                    part = {}
                    if kern is not None:
                        part["kernel"] = _np.array_split(_np.asarray(kern), 3, axis=-1)[idx]
                    if bias is not None:
                        part["bias"] = _np.array_split(_np.asarray(bias), 3, axis=-1)[idx]
                    out[name] = part
            elif k == "q_proj" and to_fused and isinstance(v, dict):
                parts = [node[n] for n in ("q_proj", "k_proj", "v_proj")]
                fused = {}
                if parts[0].get("kernel") is not None:
                    fused["kernel"] = _np.concatenate(
                        [_np.asarray(pp["kernel"]) for pp in parts], axis=-1
                    )
                if parts[0].get("bias") is not None:
                    fused["bias"] = _np.concatenate(
                        [_np.asarray(pp["bias"]) for pp in parts], axis=-1
                    )
                out["qkv_proj"] = fused
            elif k in ("k_proj", "v_proj") and to_fused:
                continue  # folded into qkv_proj above
            else:
                out[k] = walk(v)
        return out

    return walk(gpt_params)


def masked_loss_mean(token_loss: jax.Array, loss_mask: jax.Array):
    """Loss-mask-weighted mean of per-token losses (the reference
    criterion's reduction, single_model.py:727-736)."""
    with jax.named_scope("loss"):
        loss_mask = loss_mask.astype(jnp.float32).reshape(token_loss.shape)
        return ((token_loss * loss_mask).sum()
                / jnp.maximum(loss_mask.sum(), 1.0))


def pretraining_loss(logits: jax.Array, labels: jax.Array, loss_mask: jax.Array):
    """Masked LM cross-entropy (reference GPTPretrainingCriterion,
    single_model.py:702-736; the TP ParallelCrossEntropy variant
    hybrid_model.py:857-904 is unnecessary — logits arrive vocab-sharded and
    XLA handles the sharded log-softmax reduction)."""
    with jax.named_scope("loss"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        label_logits = jnp.take_along_axis(
            logits, labels[..., None], axis=-1)[..., 0]
        return masked_loss_mean(logz - label_logits, loss_mask)
