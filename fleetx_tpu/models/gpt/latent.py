"""Latent attention (MLA) as a kind of ``mixed_stack.py``'s one scanned
body: ``layer_types`` entries ``latent_attention``
(``models/gpt/block_fields.py`` has the fields).

**The operator**, a token ``x``: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``,
a head ``[q_nope | q_r]``; ``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm
(c_kv)``; ``q_r, k_r`` rotated (ONE rotary key for all heads), the angles
YaRN's (:func:`yarn_frequencies`). Under ``mla_scale_q_lora`` /
``mla_scale_kv_lora`` (LongCat-Flash) ``q`` is multiplied by ``sqrt(hidden /
q_lora_rank)`` and the normed ``c_kv`` by ``sqrt(hidden / kv_lora_rank)``,
each in float32 before its cast; ``k_r`` is not, and the pool holds ``c_kv``
as scaled, which is what both forms below read. **The cache holds ``c_kv``
and ``k_r`` and nothing else**: the pool's two leaves ``cached_key`` (``c_kv``,
``kv_lora_rank`` a row) and ``cached_value`` (``k_r``, ``qk_rope_head_dim``
a row, held in the 128 lanes a tile has: :func:`rope_leaf_width`), flat over
the layers under the ONE block table, written by
``paged_write.write_rows`` as any other pool's rows (the names are the
pool's two places, which the layer body, the trie, the spill tiers' readers
and the benchmark's checks know; a row has no head axis, so nothing of it
can be divided over ``mp``). Two forms read it:

- *materialised* (a prefill chunk, and a forward outside the cache): ``[k_nope
  | v] = c_kv W_kvb`` a head, ``k = [k_nope | k_r]``, scores ``q k^T *
  scale``, causal softmax in float32, ``(P v) W_o``. A chunk attends over
  the lane's cached rows AND its own (written just before) in blocks of
  rows with a running maximum and sum, each block's keys and values
  re-expanded from its latents, and blocks past the chunk's last row are
  not computed. Where the decode kernel runs (``use_flash_attention`` and
  a TPU, or interpreted under ``FLEETX_FORCE_FLASH=1``) that is ONE kernel,
  ``ops/pallas/mla_prefill.py``: a head's keys and values of a block are
  expanded, scored and summed in VMEM, and neither they nor a score ever
  exist in HBM. Elsewhere (the CPU, the tests) it is the kernel's plain
  twin :func:`_chunk` in blocks of ``KEY_BLOCK`` rows (the re-expansion
  under the scope ``mla_kv_up``), the same arithmetic in the same types,
  whose ``[heads, s, KEY_BLOCK]`` float32 scores pass through HBM;
- *absorbed* (a decode tick): ``q~ = q_nope W_UK^T``, scores ``([q~ | q_r]
  . [c_kv | k_r]) * scale``, ``o = (P c_kv) W_UV``, where ``W_UK`` and
  ``W_UV`` are the two halves of ``W_kvb`` a head: the kernel
  ``ops/pallas/mla_decode.py`` attends over the latent itself and no key or
  value of any head is ever made. A lane whose row lands in the trash page
  (not decoding) attends over nothing.

``scale = (nope + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor)
+ 1`` (YaRN's correction of the softmax's temperature).

**The indexer** (``index_topk`` > 0: learned sparse attention, DeepSeek-V3.2;
absent, nothing below is traced and the operator is the one above). Beside
the projections above, from the SAME ``c_q`` and ``x``: ``qI = c_q W_Iq``
(``index_n_heads`` heads of ``index_head_dim``, a head's first
``qk_rope_head_dim`` columns rotated), ONE key a token ``kI = LayerNorm(x
W_Ik)`` (first columns rotated alike), head weights ``w = (x W_Iw) *
heads^-0.5 * head_dim^-0.5`` (float32). **``kI`` is the pool's third leaf**,
``cached_index``, written, parked at a prefix and resumed with the other
two. A query at position ``t`` scores every row ``s <= t`` of its lane,
``I_ts = sum_j w_j ReLU(qI_j . kI_s)`` (float32), keeps the ``min(index_topk,
t + 1)`` highest (EXACTLY: :func:`select_rows`; ties to the lower position)
and attends over those alone, in both forms:

- a chunk scores the lane's index keys in blocks of ``KEY_BLOCK`` rows up
  to its last row (scope ``dsa_index``), selects by threshold, a mask ``[s,
  t]`` with each row's own set (``dsa_select``), and attends materialised
  UNDER THE MASK (``dsa_attn``): the kernel ``fleetx_dsa_prefill``
  (``ops/pallas/mla_prefill.py`` with the mask in the place of its position
  test), else :func:`_chunk` handed the mask;
- a tick scores every lane's index keys (``dsa_index``), takes the
  top ``index_topk`` positions a lane (``dsa_select``), GATHERS the chosen
  rows of ``c_kv`` and ``k_r`` into a compact pool of ``index_topk`` rows a
  lane in position order (``dsa_attn``) and runs the absorbed kernel
  ``fleetx_mla_decode_paged`` over that, as it stands.

A forward outside the cache masks its dense scores the same way.

Device scopes (docs/OBSERVABILITY.md): ``mla_proj`` (the low-rank
projections, norms, rotation, and the output projection),
``mla_attn_prefill`` (a chunk's gather of its lane's rows and the kernel
``fleetx_mla_prefill``; in the plain twin the scores, softmax and value
products), ``mla_kv_up`` (the plain twin's re-expansion: the kernel has it
inside), ``mla_absorb``; a tick's kernel is ``fleetx_mla_decode_paged``.

:class:`LatentStack` is ``MixedStack`` with this operator as its attention
kind, the two leaves at their own widths, the angles computed from the
call's positions (a cached call's rows stand at ``cache_positions + [0,
s)``; a forward outside the cache counts from 0), and, where the
configuration says so, ``parallel/moe_share.py``'s expert layer.

Forward only, as the stack it is a kind of.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from fleetx_tpu.models.gpt import paged_write
from fleetx_tpu.models.gpt.hybrid import layer_bases
from fleetx_tpu.models.gpt.indexer import (  # noqa: F401 (its names are
    # read from this module too: the probes' seams, the tests' functions)
    _INDEX_TYPE,
    KEY_BLOCK,
    _index_act,
    _index_head_weights,
    _visible,
    chosen_mask,
    gather_rows,
    select_rows,
    top_rows,
)
from fleetx_tpu.models.gpt import indexer
from fleetx_tpu.models.gpt.mixed_stack import MixedStack
from fleetx_tpu.models.gpt.model import (
    GPTConfig,
    apply_rope,
    default_kernel_init,
)

__all__ = ["KEY_BLOCK", "LatentAttention", "LatentStack", "index_scores",
           "rope_leaf_width", "select_rows", "softmax_scale",
           "yarn_frequencies", "yarn_tables"]

# (``KEY_BLOCK``, ``indexer.py``'s: key rows of one block of a chunk's
# attention in plain XLA, :func:`_chunk`, as of its index scores)
_NEG = -1e30


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: GPTConfig) -> float:
    """``(nope + rope)^-0.5 * m^2`` (module docstring)."""
    m = _mscale(cfg.rope_scaling_factor, cfg.rope_scaling_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def yarn_frequencies(cfg: GPTConfig) -> np.ndarray:
    """The rotary key's ``qk_rope_head_dim / 2`` angular frequencies: those
    of ``rope_theta``, the slow ones divided by ``rope_scaling_factor``
    (interpolated), the fast ones kept (extrapolated), a linear ramp between
    the dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
    original context."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    factor = cfg.rope_scaling_factor
    kept = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1.0:
        return kept.astype(np.float32)

    def turns_at(turns):  # the dimension that turns so often
        return (dim * math.log(cfg.rope_scaling_original_max_position
                               / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(turns_at(cfg.rope_scaling_beta_fast)), 0)
    high = min(math.ceil(turns_at(cfg.rope_scaling_beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (kept / factor * ramp + kept * (1.0 - ramp)).astype(np.float32)


def yarn_tables(cfg: GPTConfig, positions):
    """``(cos, sin)`` ``[b, s, rope / 2]`` float32 at ``positions``, scaled
    by ``mscale / mscale_all_dim`` as YaRN scales them."""
    angles = (positions.astype(jnp.float32)[..., None]
              * jnp.asarray(yarn_frequencies(cfg)))
    by = (_mscale(cfg.rope_scaling_factor, cfg.rope_scaling_mscale)
          / _mscale(cfg.rope_scaling_factor, cfg.rope_scaling_mscale_all_dim))
    return jnp.cos(angles) * by, jnp.sin(angles) * by


def rope_leaf_width(cfg: GPTConfig) -> int:
    """Columns of the rotary key's leaf: ``qk_rope_head_dim`` rounded up to
    the device's 128-lane tile. A 64-wide bfloat16 leaf occupies 128 lanes
    a row in HBM whatever its declared shape, and a page of it cannot be
    copied out of that tiling by itself (Mosaic refuses the slice), so the
    leaf is declared as wide as it is held, the columns past the key zeros:
    a cached row is ``(kv_lora_rank + this) * 2`` bytes, and that is what
    the pool's bytes are counted from."""
    return -(-cfg.qk_rope_head_dim // 128) * 128


def _latent_norm(cfg: GPTConfig, name: str, scaled: bool = False):
    """A low-rank norm; ``scaled``: its output stays float32 for the scale
    that follows (``mla_scale_kv_lora``), which casts it."""
    return nn.RMSNorm(epsilon=cfg.norm_eps,
                      dtype=jnp.float32 if scaled else cfg.dtype,
                      param_dtype=jnp.float32, name=name)


class LatentAttention(nn.Module):
    """The operator (module docstring), called as ``mixed_stack.py`` calls
    its attention kind: ``phase="project"`` returns ``(q, c_kv, k_r)`` as
    the cache takes them (``q`` ``[b, s, heads, nope + rope]``, rotated),
    ``phase="attend"`` takes that ``q`` as ``x`` and attends through the
    cache as it stands; without a phase, every position at once."""

    cfg: GPTConfig

    def _weight(self, name, shape, axes):
        return self.param(name, nn.with_logical_partitioning(
            default_kernel_init, axes), shape, jnp.float32).astype(
                self.cfg.dtype)

    @nn.compact
    def __call__(self, x, attn_mask=None, *, deterministic=True, decode=False,
                 cache_positions=None, block_tables=None, layer_index=None,
                 rope=None, phase=None):
        cfg = self.cfg
        nh, nope, rot, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim, cfg.v_head_dim)
        h, qr, c = cfg.hidden_size, cfg.q_lora_rank, cfg.kv_lora_rank
        # declared in every phase: the layer loop applies one slice of the
        # stack to each
        if qr:
            w_qa = self._weight("q_a_proj", (h, qr), ("embed", None))
            w_qb = self._weight("q_b_proj", (qr, nh, nope + rot),
                                (None, "heads", "kv"))
        else:  # no query latent (beside delta-rule layers)
            w_q = self._weight("q_proj", (h, nh, nope + rot),
                               ("embed", "heads", "kv"))
        w_kva = self._weight("kv_a_proj", (h, c + rot), ("embed", None))
        w_kvb = self._weight("kv_b_proj", (c, nh, nope + vd),
                             (None, "heads", "kv"))
        w_o = self._weight("out_proj", (nh, vd, h), ("heads", "kv", "embed"))
        s_q, s_kv = cfg.mla_scales
        kv_norm = _latent_norm(cfg, "kv_a_norm", scaled=s_kv != 1.0)
        if qr:
            q_norm = _latent_norm(cfg, "q_a_norm")
        if cfg.qk_norm:  # a head's query, and the rotary key all heads share
            q_head_norm, kr_norm = (_latent_norm(cfg, "q_norm"),
                                    _latent_norm(cfg, "k_rope_norm"))
        gated = cfg.attention_gate == "sigmoid_head"
        if gated:
            w_gate = self._weight("gate_proj", (h, nh), ("embed", "heads"))
        if cfg.indexed:
            ni, di = cfg.index_n_heads, cfg.index_head_dim
            w_iq = self._weight("index_q_proj", (qr, ni, di),
                                (None, "heads", "kv"))
            w_ik = self._weight("index_k_proj", (h, di), ("embed", None))
            w_iw = self._weight("index_w_proj", (h, ni), ("embed", None))
            index_norm = nn.LayerNorm(
                epsilon=cfg.norm_eps, dtype=cfg.dtype,
                param_dtype=jnp.float32, name="index_k_norm")
        if phase == "attend":
            x, gate = x if gated else (x, None)
            with jax.named_scope("attn_full"):
                out = self._cached(x, w_kvb, cache_positions, block_tables,
                                   layer_index)
            out = _head_gated(out, gate)
            with jax.named_scope("mla_proj"):
                return jnp.einsum("bshv,hvd->bsd", out, w_o)
        if rope is None:
            raise ValueError("latent attention rotates: it is handed the "
                             "angles (LatentStack computes them)")
        with jax.named_scope("mla_proj"):
            if not qr:
                q = jnp.einsum("bsd,dhk->bshk", x, w_q)
            else:
                c_q = q_norm(x @ w_qa)
                if s_q == 1.0:
                    q = jnp.einsum("bsr,rhd->bshd", c_q, w_qb)
                else:  # (scaled in float32, before the product is cast)
                    q = (jnp.einsum("bsr,rhd->bshd", c_q, w_qb,
                                    preferred_element_type=jnp.float32)
                         * s_q).astype(cfg.dtype)
            if cfg.qk_norm:
                with jax.named_scope("mla_qk_norm"):
                    q = q_head_norm(q)
            q = jnp.concatenate(
                [q[..., :nope], apply_rope(q[..., nope:], rope)], axis=-1)
            latent = x @ w_kva
            ckv = kv_norm(latent[..., :c])
            if s_kv != 1.0:  # (the norm's float32 output: ONE rounding)
                ckv = (ckv * s_kv).astype(cfg.dtype)
            key = latent[..., c:]
            if cfg.qk_norm:
                with jax.named_scope("mla_qk_norm"):
                    key = _normed_rotary_key(kr_norm, key)
            kr = _rotated_key(key, rope)
        gate = None
        if gated:
            with jax.named_scope("attn_gate"):
                gate = x @ w_gate  # (from the normed input the queries read)
        if cfg.indexed:
            with jax.named_scope("dsa_index"):
                qi = jnp.einsum("bsr,rhd->bshd", c_q, w_iq)
                qi = jnp.concatenate(
                    [apply_rope(qi[..., :rot], rope), qi[..., rot:]], axis=-1)
                ki = _rotated_index_key(index_norm(x @ w_ik), rope, rot)
                head_w = _index_head_weights(
                    (x @ w_iw).astype(jnp.float32) * (ni * di) ** -0.5)
        if phase == "project":  # (the leaf's width: ``rope_leaf_width``)
            kr = jnp.pad(
                kr, ((0, 0), (0, 0), (0, rope_leaf_width(cfg) - rot)))
            if cfg.indexed:  # the attend phase takes all three as its ``x``
                return (q, qi, head_w), ckv, kr, ki
            # (under the head-wise gate ``q`` carries the gate's rows to the
            # attend phase)
            return (q, gate) if gated else q, ckv, kr
        # no cache (or its init): every position at once, materialised
        s = x.shape[1]
        pos = jnp.arange(s)
        allowed = (pos[None, :] <= pos[:, None])[None]
        if attn_mask is not None:
            raise NotImplementedError(
                "a key mask over latent attention outside the cache: no "
                "test covers it")
        with jax.named_scope("mla_kv_up"):
            k_nope, v = _expand(ckv, w_kvb, nope)
        with jax.named_scope("mla_attn_prefill"):
            scores = (jnp.einsum("bshd,bthd->bhst", q[..., :nope], k_nope,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bshd,btd->bhst", q[..., nope:], kr,
                                   preferred_element_type=jnp.float32))
            if cfg.indexed:
                with jax.named_scope("dsa_select"):
                    index = index_scores(qi, head_w, ki)
                    allowed = select_rows(index, _visible(allowed),
                                          cfg.index_topk) & allowed
                indexer.sow_selection(self, index, allowed)
            scores = jnp.where(allowed[:, None], scores * softmax_scale(cfg),
                               _NEG)
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            out = jnp.einsum("bhst,bthv->bshv", probs, v)
        out = _head_gated(out, gate)
        with jax.named_scope("mla_proj"):
            return jnp.einsum("bshv,hvd->bsd", out, w_o)

    def _cached(self, q, w_kvb, cache_positions, block_tables, layer_index):
        """Attend through the pool as it stands (the caller has written the
        call's rows): ``[b, s, heads, v]``."""
        cfg = self.cfg
        nope = cfg.qk_nope_head_dim
        ckv_pool = self.get_variable("cache", "cached_key")
        kr_pool = self.get_variable("cache", "cached_value")
        ps = cfg.decode_page_size
        if cfg.indexed:
            q, qi, head_w = q
            ki_pool = self.get_variable("cache", "cached_index")
        b, s = q.shape[:2]
        wpos = cache_positions.astype(jnp.int32)
        tables = block_tables.astype(jnp.int32)
        scale = softmax_scale(cfg)
        if s == 1:
            end = paged_write.decode_end(tables, wpos, ps)
            tables = tables + jnp.asarray(layer_bases(cfg))[layer_index]
            with jax.named_scope("mla_absorb"):
                q_c = jnp.einsum("bhd,chd->bhc", q[:, 0, :, :nope],
                                 w_kvb[..., :nope])
            q_r = jnp.pad(q[:, 0, :, nope:], ((0, 0), (0, 0), (
                0, kr_pool.shape[-1] - cfg.qk_rope_head_dim)))
            if cfg.indexed:
                out, index, chosen = _sparse_decode(
                    cfg, q_c, q_r, qi[:, 0], head_w[:, 0],
                    (ckv_pool, kr_pool, ki_pool), tables, end, scale)
                if self.is_mutable_collection("routing"):
                    indexer.sow_selection(
                        self, index[:, None],
                        chosen_mask(chosen, index.shape[1]))
            else:
                out = _decode(cfg, q_c, q_r, ckv_pool, kr_pool, tables, end,
                              scale)
            with jax.named_scope("mla_absorb"):
                return _through_w_uv(out, w_kvb[..., nope:])[:, None]
        if b != 1:
            raise NotImplementedError(
                "latent attention over the cache takes a tick (one row a "
                f"lane) or a chunk of ONE lane, not {b} lanes x {s} rows")
        table = tables[0] + jnp.asarray(layer_bases(cfg))[layer_index]
        with jax.named_scope("mla_attn_prefill"):  # the lane's rows, in order
            ckv = ckv_pool[table].reshape(-1, ckv_pool.shape[-1])
            kr = kr_pool[table].reshape(-1, kr_pool.shape[-1])
        if not cfg.indexed:
            return _prefill(cfg, q[0], w_kvb, ckv, kr, wpos[0], scale)[None]
        with jax.named_scope("dsa_index"):
            ki = ki_pool[table].reshape(-1, ki_pool.shape[-1])
            scores = _chunk_index_scores(qi[0], head_w[0], ki, wpos[0])
        with jax.named_scope("dsa_select"):
            seen = (jnp.arange(ki.shape[0], dtype=jnp.int32)[None, :]
                    <= wpos[0] + jnp.arange(s, dtype=jnp.int32)[:, None])
            mask = select_rows(scores, _visible(seen), cfg.index_topk)
        # (a chunk's scores, [rows, cache rows] float32 a layer, are not sown:
        # 103 MB a layer of the check's program at the served sizes)
        indexer.sow_selection(self, None, mask[None])
        return _prefill(cfg, q[0], w_kvb, ckv, kr, wpos[0], scale,
                        mask=mask)[None]


# the seams ``perfbench/probe_axk1.py`` plants its faults in
_SCORE_TYPE = jnp.float32     # what a chunk's scores are accumulated in
# (the indexer's seams are ``indexer.py``'s, looked up HERE by its functions:
# ``_SEAMS``; ``perfbench/probe_dsv32.py`` sets them on this module)
_SEAMS = sys.modules[__name__]


def _rotated_index_key(ki, rope, rot: int):
    """The indexer's key ``[b, s, d]``, its first ``rot`` columns rotated,
    as the cache takes it."""
    return jnp.concatenate([_rotated_key(ki[..., :rot], rope), ki[..., rot:]],
                           axis=-1)


def index_scores(qi, w, ki):
    """``indexer.index_scores`` under this module's seams."""
    return indexer.index_scores(qi, w, ki, _SEAMS)


def _chunk_index_scores(qi, w, ki, start):
    """``indexer._chunk_index_scores`` under this module's seams."""
    return indexer._chunk_index_scores(qi, w, ki, start, _SEAMS)


def _sparse_decode(cfg: GPTConfig, q_c, q_r, qi, w, pools, tables, end,
                   scale):
    """A tick under the indexer: every lane's query ``qi`` ``[b, heads, d]``
    scores the lane's index keys, the ``index_topk`` best positions of its
    rows ``[0, end)`` are gathered, in position order, into a compact pool
    of their own, and the absorbed form (:func:`_decode`) attends over
    that. ``tables`` carry the layer's base. Beside the output, the scores
    ``[b, t]`` and the positions chosen ``[b, index_topk]`` (``t`` where a
    lane has fewer rows)."""
    ckv_pool, kr_pool, ki_pool = pools
    b, ps = q_c.shape[0], ckv_pool.shape[1]
    t = tables.shape[1] * ps
    k = min(cfg.index_topk, t)
    with jax.named_scope("dsa_index"):
        ki = ki_pool[tables].reshape(b, t, ki_pool.shape[-1])
        scores = index_scores(qi[:, None], w[:, None], ki)[:, 0]
    with jax.named_scope("dsa_select"):
        seen = jnp.arange(t, dtype=jnp.int32)[None, :] < end[:, None]
        # in position order; the places past ``count`` name no row
        chosen, count = top_rows(scores, _visible(seen), end, k)
    with jax.named_scope("dsa_attn"):
        (ckv, kr), compact = gather_rows((ckv_pool, kr_pool), tables, chosen)
    return (_decode(cfg, q_c, q_r, ckv, kr, compact, count, scale), scores,
            chosen)


def _head_gated(out, gate):
    """The heads' output ``[b, s, heads, v]`` times ``sigmoid(gate)`` ``[b,
    s, heads]``, one value a head, the sigmoid in float32 (as it is without
    one; a seam: ``perfbench/probe_ling3.py`` plants a fault here)."""
    if gate is None:
        return out
    with jax.named_scope("attn_gate"):
        return (out * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
                ).astype(out.dtype)


def _normed_rotary_key(norm, key):
    """The shared rotary key ``[b, s, rope]`` through its norm, before the
    rotation (a seam: ``perfbench/probe_ling3.py`` leaves the norm out
    here)."""
    return norm(key)


def _rotated_key(kr, rope):
    """The rotary key ``[b, s, r]`` rotated, as the cache takes it."""
    return apply_rope(kr[:, :, None], rope)[:, :, 0]


def _through_w_uv(out, w_uv):
    """``(P c_kv) W_UV``: the absorbed form's way back to a head's value."""
    return jnp.einsum("bhc,chv->bhv", out, w_uv)


def _expand(ckv, w_kvb, nope: int):
    """``(k_nope, v)`` ``[..., heads, nope | v]`` of the latents ``ckv``."""
    kv = jnp.einsum("...c,chd->...hd", ckv, w_kvb)
    return kv[..., :nope], kv[..., nope:]


def _kernels(cfg: GPTConfig) -> bool:
    """Whether both forms take their Pallas kernel (else its plain twin)."""
    from fleetx_tpu.ops.pallas.flash_attention import kernels_enabled

    return cfg.use_flash_attention and kernels_enabled()


def _decode(cfg: GPTConfig, q_c, q_r, ckv_pool, kr_pool, tables, end, scale):
    from fleetx_tpu.ops.pallas import mla_decode

    kernel = (mla_decode.mla_decode_paged if _kernels(cfg)
              else mla_decode.mla_decode_reference)
    return kernel(q_c, q_r, ckv_pool, kr_pool, tables=tables, end=end,
                  scale=scale)


def _prefill(cfg: GPTConfig, q, w_kvb, ckv, kr, start, scale: float,
             mask=None):
    """One lane's chunk over its rows as gathered (``kr`` the leaf as held):
    the kernel where ``_decode`` takes its own, else :func:`_chunk`. Under
    ``mask`` ``[s, t]`` bool (each query's selected rows, all of them
    visible to it) the kernel is ``fleetx_dsa_prefill``."""
    from fleetx_tpu.ops.pallas import mla_prefill

    if not _kernels(cfg):
        return _chunk(cfg, q, w_kvb, ckv, kr[:, :cfg.qk_rope_head_dim],
                      start, scale, mask)
    with jax.named_scope("mla_attn_prefill" if mask is None else "dsa_attn"):
        return mla_prefill.mla_prefill(
            q, w_kvb, ckv, kr, start, nope=cfg.qk_nope_head_dim, scale=scale,
            score_type=_SCORE_TYPE, mask=mask)


def _chunk(cfg: GPTConfig, q, w_kvb, ckv, kr, start, scale: float,
           mask=None):
    """One lane's chunk, materialised, in plain XLA (the kernel's twin: the
    CPU, the tests): ``q`` ``[s, heads, nope + rope]`` at positions ``start
    + [0, s)`` over the lane's rows ``ckv`` ``[t, c]`` and ``kr`` ``[t, r]``
    (its own among them), in blocks of ``KEY_BLOCK`` keys with a running
    maximum and sum; blocks past the chunk's last row are not computed.
    ``mask`` ``[s, t]`` bool: the rows each query attends over, of those it
    sees (under scope ``dsa_attn`` then). ``[s, heads, v]``."""
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    s, nh = q.shape[:2]
    t = ckv.shape[0]
    block = min(KEY_BLOCK, t)
    if t % block:
        raise ValueError(f"a lane's {t} rows are no whole number of "
                         f"{block}-row key blocks")
    q_pos = start + jnp.arange(s, dtype=jnp.int32)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    attn_scope = "mla_attn_prefill" if mask is None else "dsa_attn"

    def one(i, carry):
        top, total, acc = carry
        at = i * block
        with jax.named_scope("mla_kv_up"):
            k_nope, v = _expand(
                jax.lax.dynamic_slice_in_dim(ckv, at, block), w_kvb, nope)
        with jax.named_scope(attn_scope):
            scores = (jnp.einsum("shd,thd->hst", q_nope, k_nope,
                                 preferred_element_type=_SCORE_TYPE)
                      + jnp.einsum(
                          "shd,td->hst", q_rope,
                          jax.lax.dynamic_slice_in_dim(kr, at, block),
                          preferred_element_type=_SCORE_TYPE)).astype(
                              jnp.float32) * scale
            seen = (at + jnp.arange(block, dtype=jnp.int32)[None, :]
                    <= q_pos[:, None])[None]
            if mask is not None:
                seen &= jax.lax.dynamic_slice_in_dim(mask, at, block, 1)[None]
            scores = jnp.where(seen, scores, _NEG)
            new_top = jnp.maximum(top, scores.max(-1))
            p = jnp.where(seen, jnp.exp(scores - new_top[..., None]), 0.0)
            alpha = jnp.exp(top - new_top)
            acc = alpha[..., None] * acc + jnp.einsum(
                "hst,thv->hsv", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            return new_top, alpha * total + p.sum(-1), acc

    with jax.named_scope("mla_attn_prefill"):
        carry = (jnp.full((nh, s), _NEG, jnp.float32),
                 jnp.zeros((nh, s), jnp.float32),
                 jnp.zeros((nh, s, vd), jnp.float32))
        blocks = jnp.minimum((start + s + block - 1) // block, t // block)
    _, total, acc = jax.lax.fori_loop(0, blocks, one, carry)
    with jax.named_scope("mla_attn_prefill"):
        out = acc / jnp.where(total > 0, total, 1.0)[..., None]
        return out.transpose(1, 0, 2).astype(q.dtype)


class LatentStack(MixedStack):
    """``MixedStack`` whose attention kind is :class:`LatentAttention`
    (module docstring)."""

    def _kinds(self):
        cfg = self.cfg
        kinds = super()._kinds()
        x = kinds["attention"][1][0]
        rope = (jnp.ones((1, 1, cfg.qk_rope_head_dim // 2), jnp.float32),) * 2
        kinds["attention"] = (LatentAttention(cfg, parent=None), (x,),
                              {"layer_index": jnp.int32(0), "rope": rope})
        return kinds

    def _cache(self, decode: bool, plan: dict, lanes: int):
        """The two latent leaves at their own widths (``hybrid.init_cache``
        makes their first axis the flat pool) and the expert layers'
        counters."""
        cfg = self.cfg
        if not decode:
            return None
        if cfg.decode_num_pages is None:
            raise NotImplementedError(
                "a contiguous decode cache over latent attention (one-shot "
                "generate()): serve the model through ServingEngine, whose "
                "page pool holds the latents")
        from fleetx_tpu.parallel.moe_share import stats_words

        ps = cfg.decode_page_size
        fresh = not self.has_variable("cache", "cached_key")
        held = {
            "cached_key": self.variable(
                "cache", "cached_key", jnp.zeros,
                (1, ps, cfg.kv_lora_rank), cfg.dtype),
            "cached_value": self.variable(
                "cache", "cached_value", jnp.zeros,
                (1, ps, rope_leaf_width(cfg)), cfg.dtype),
            "moe_stats": self.variable(
                "cache", "moe_stats", jnp.zeros,
                (max(plan["counts"]["experts"], 1), stats_words(cfg)),
                jnp.uint32),
        }
        if cfg.indexed:
            held["cached_index"] = self.variable(
                "cache", "cached_index", jnp.zeros,
                (1, ps, cfg.index_head_dim), cfg.dtype)
        # beside delta-rule layers a lane holds their state too, ONCE A
        # LANE, under column 0 of its block table (mixed_stack.py "State")
        held.update(self._lane_leaves(plan["counts"], lanes))
        return None if fresh else held

    def _decoder_stack(self, x, params, cache, plan, kinds, *, rope,
                       cache_positions, **kwargs):
        """The one scanned body, handed YaRN's angles at the call's own
        positions in the place of the model's plain ones."""
        del rope
        at = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
        if cache is not None:
            at = at + cache_positions.astype(jnp.int32)[:, None]
        with jax.named_scope("embed"):
            rope = yarn_tables(self.cfg, jnp.broadcast_to(at, x.shape[:2]))
        return super()._decoder_stack(
            x, params, cache, plan, kinds, rope=rope,
            cache_positions=cache_positions, **kwargs)
