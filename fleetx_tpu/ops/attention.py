"""Core attention ops.

Replaces the reference's ``core_attn`` + CUDA ``softmax_mask_fuse_upper_
triangle`` (/root/reference/ppfleetx/models/language_model/gpt/dygraph/
single_model.py:216-240): on TPU the causal-masked softmax is either fused by
XLA from this straight-line jnp implementation or dispatched to the Pallas
flash-attention kernel (fleetx_tpu/ops/pallas/flash_attention.py) which never
materializes the [b, heads, s, s] score matrix — that memory saving is what
lets long-context configs run without the reference's recompute tricks.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from fleetx_tpu.ops.pallas import flash_attention as _fa

__all__ = ["causal_attention", "NEG_INF"]

NEG_INF = -1e9  # large-but-finite; -inf breaks softmax when a row is all-masked


def _reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    attn_mask: Optional[jax.Array],
    dropout_rate: float,
    dropout_rng: Optional[jax.Array],
    deterministic: bool,
) -> jax.Array:
    """Plain XLA attention. Shapes: q,k,v [batch, seq, heads, head_dim]
    (kv seq may differ from q seq for cached decode)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, dtype=jnp.float32))
    # [b, h, sq, sk]; accumulate scores in fp32 for softmax stability.
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if causal:
        # offset aligns the last q position with the last k position so the
        # same code serves full-sequence and incremental-decode calls.
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)
        k_pos = jnp.arange(sk)[None, :]
        scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)
    if attn_mask is not None:
        # mask: 1 = attend, 0 = hide; broadcastable to [b, h, sq, sk]
        scores = jnp.where(attn_mask.astype(bool), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if dropout_rate > 0.0 and not deterministic:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    attn_mask: Optional[jax.Array] = None,
    kv_lens: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    use_flash: bool = True,
    mesh_shard: bool = True,
) -> jax.Array:
    """Multi-head scaled-dot-product attention, [b, s, h, d] layout.

    Routes to the Pallas flash kernel when profitable (TPU, train-time
    shapes, mask expressible as causal and/or right-padding ``kv_lens``);
    falls back to the XLA path for arbitrary ``attn_mask`` tensors or
    decode shapes. Attention dropout runs inside the kernel (hardware PRNG
    on real TPUs, counter-hash on the interpreter — see
    fleetx_tpu/ops/pallas/flash_attention.py), so dropout>0 training
    configs stay on the flash path. Both paths produce identical
    math in the deterministic case (kernel is tested against this
    reference implementation). Non-causal + kv_lens covers the ERNIE-style
    bidirectional encoder with right-padded batches.

    ``mesh_shard=False`` disables the kernel's mesh shard_map wrapper —
    required on the pp>1 path where attention runs under the pipeline's
    stage vmap (see flash_attention's docstring).
    """
    effective_dropout = 0.0 if deterministic else dropout_rate

    def _tileable(s: int) -> bool:
        # mirror flash_attention's block fitting: blocks shrink to the
        # largest divisor of the sequence. Route to the kernel only when a
        # reasonably-sized tile fits — a sequence like 1016 = 8*127 only
        # admits 8-row tiles, where per-grid-step overhead makes the
        # kernel slower than the XLA path it would replace.
        bq, bk = _fa.fit_blocks(s, _fa.DEFAULT_BLOCK_Q, _fa.DEFAULT_BLOCK_K)
        # bq == s: the whole sequence is one tile (short seqs) — no grid
        # overhead regardless of size
        return bq is not None and (bq >= 128 or bq == s)

    def _pad_to_tileable(s: int):
        """Smallest padded length with a kernel-worthy tile, or None.
        Padded KEYS are masked via kv_lens; padded QUERY rows are computed
        and sliced off (their cotangent is zero, so gradients are exact).
        Fixes e.g. ViT's 197 (-> 200, one tile) and 1016 (-> 1024, 512
        tiles) instead of falling back to the XLA path."""
        for s_pad in range(s + (-s % 8), s + 129, 8):
            if _tileable(s_pad):
                return s_pad
        return None

    def _unwrapped_under_tp() -> bool:
        # mesh_shard=False (the pp stage-vmap path) with an ambient mp>1
        # mesh: the bare Pallas call would make GSPMD replicate the
        # heads-sharded q/k/v — strictly worse than the XLA attention it
        # replaces, which GSPMD shards natively. Prefer the XLA path.
        if mesh_shard:
            return False
        from fleetx_tpu.parallel.mesh import ambient_mesh

        mesh = ambient_mesh()
        return mesh is not None and dict(mesh.shape).get("mp", 1) > 1

    s = q.shape[1]
    s_pad = s if _tileable(s) else _pad_to_tileable(s)
    can_flash = (
        use_flash
        and attn_mask is None
        and (effective_dropout == 0.0 or dropout_rng is not None)
        and q.shape[1] == k.shape[1]  # not incremental decode
        and s_pad is not None
        and not _unwrapped_under_tp()
        # compiled on a TPU; interpreted on the CPU only where a test or
        # the multichip dryrun forces it (FLEETX_FORCE_FLASH=1)
        and _fa.kernels_enabled()
    )
    if can_flash:

        if s_pad != s:
            pad = ((0, 0), (0, s_pad - s), (0, 0), (0, 0))
            if kv_lens is None:
                kv_lens = jnp.full((q.shape[0],), s, jnp.int32)
            q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
        out = _fa.flash_attention(
            q, k, v, causal=causal, kv_lens=kv_lens,
            dropout_rate=effective_dropout, dropout_rng=dropout_rng,
            mesh_shard=mesh_shard,
        )
        return out[:, :s] if s_pad != s else out
    if kv_lens is not None:
        key_valid = (
            jnp.arange(k.shape[1])[None, :] < kv_lens[:, None]
        )[:, None, None, :]  # [b, 1, 1, sk]
        attn_mask = (
            key_valid if attn_mask is None
            else attn_mask.astype(bool) & key_valid
        )
    out = (_reference_attention(  # "(": the call keeps its column (D11)
        q,
        k,
        v,
        causal=causal,
        attn_mask=attn_mask,
        dropout_rate=dropout_rate,
        dropout_rng=dropout_rng,
        deterministic=deterministic,
    ))
    # the flash path names its output inside the kernel's forward rule
    return checkpoint_name(out, "core_attn_out")
