"""Quantization ops: int8 weight-only PTQ for serving + fake-quant QAT.

The reference's quantization story is paddleslim QAT configs
(qat_gpt_*.yaml; utils/export.py quant-aware export path). TPU-native
equivalents:

- **PTQ (serving)**: per-channel absmax int8 of dense kernels — halves (vs
  bf16) or quarters (vs fp32) the HBM a served model needs; matmuls
  dequantize on the fly (XLA fuses the scale multiply into the consumer).
- **QAT (training)**: straight-through-estimator fake quantization applied
  to weights inside the jitted loss; gradients flow as identity
  (lax.stop_gradient trick), matching paddleslim's weight-quant QAT
  semantics without graph surgery.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

__all__ = [
    "QUANT_PREFIX_BUDGET",
    "common_prefix_len",
    "quant_parity_frac",
    "quantize_int8",
    "dequantize_int8",
    "quantize_kv",
    "dequantize_kv",
    "fake_quant",
    "fake_quant_act",
    "quantize_tree_int8",
    "dequantize_tree_int8",
    "fake_quant_tree",
    "resolve_serving_dtype",
    "serving_weight_params",
]

# The repo-wide tolerance budget for quantized serving configs
# (docs/QUANTIZATION.md "Tolerance contract"): a quantized greedy stream
# may diverge from the bf16 reference only in its trailing this-fraction
# of tokens (greedy decode is chaotic after a first argmax flip, so the
# longest common prefix is the meaningful measure). Consumed by
# tests/serving_parity.py (QUANT_ATOL), which every quantized serving
# test reads — ONE number, change it here with hardware evidence.
QUANT_PREFIX_BUDGET = 0.25


def resolve_serving_dtype(value, env_var, label=None) -> str:
    """Resolve a serving precision knob to ``"bf16"`` | ``"int8"``:
    explicit ``value`` wins, else ``env_var``, else bf16; anything else
    raises. The ONE parser behind ``FLEETX_SERVING_KV_DTYPE`` /
    ``FLEETX_SERVING_WEIGHT_DTYPE`` and the eval CLI's
    ``Offline_Eval.weight_dtype`` — adding a format (fp8) lands in every
    consumer at once."""
    import os

    out = str(value or (os.environ.get(env_var) if env_var else "")
              or "bf16").lower()
    if out not in ("bf16", "int8"):
        raise ValueError(
            f"{label or env_var} must be bf16|int8, got {out!r}")
    return out


def serving_weight_params(params, weight_dtype: str):
    """Apply the serving weight-only PTQ: at ``"int8"`` the tree becomes
    int8 + per-channel scales (idempotent — pre-quantized artifacts pass
    through). At ``"bf16"`` a float tree passes through THIS function as
    it is (``ServingEngine`` then hands it to its executor's
    ``resident_params``, which converts what the model computes in
    ``cfg.dtype``), but a tree that already carries ``{"_q8", "_scale"}``
    leaves RAISES — the bf16 path has no dequant seam, so serving it would
    crash deep inside the first traced ``model.apply`` instead of here
    with a cause."""
    if weight_dtype == "int8":
        return quantize_tree_int8(params)
    if any(_is_qdict(leaf)
           for leaf in jax.tree.leaves(params, is_leaf=_is_qdict)):
        raise ValueError(
            "params are already int8-quantized ({'_q8', '_scale'} leaves) "
            f"but weight_dtype is {weight_dtype!r} — serve them with "
            "weight_dtype='int8' (the in-jit dequant seam) or expand them "
            "with dequantize_tree_int8 first")
    return params


def common_prefix_len(got, want) -> int:
    """Length of the longest common leading run of two token streams —
    where a quantized greedy stream diverged from its reference (the
    ``QUANT_PREFIX_BUDGET`` contract's measure)."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    n = min(len(got), len(want))
    neq = np.nonzero(got[:n] != want[:n])[0]
    return int(neq[0]) if len(neq) else n


def quant_parity_frac(got, want) -> float:
    """THE contract measure for a quantized stream vs its reference: 0.0
    on a length mismatch (the budget tolerates diverging tails, not
    missing tokens — a truncated stream fails outright), otherwise
    common-prefix length over the reference length. A stream passes when
    this is >= ``1 - QUANT_PREFIX_BUDGET``. Shared by the test harness
    (tests/serving_parity.py) and the bench gate so they cannot drift."""
    if len(got) != len(want):
        return 0.0
    return common_prefix_len(got, want) / max(len(want), 1)


def quantize_int8(w: jax.Array, axis: int = -1):
    """(int8 values, fp32 scales) with per-channel absmax along ``axis``
    kept; scale shape broadcasts back against w."""
    w = w.astype(jnp.float32)
    reduce_axes = tuple(i for i in range(w.ndim) if i != (axis % w.ndim))
    absmax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True)
    scale = absmax / 127.0
    q = jnp.clip(jnp.round(w / jnp.maximum(scale, 1e-12)), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_int8(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    """Inverse of quantize_int8: int8 values x per-channel scales -> float."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_kv(x: jax.Array):
    """Per-vector int8 for KV caches: absmax over the trailing (head_dim)
    axis, one fp32 scale per cached (row, head) vector.

    Returns ``(int8 values, fp32 scales [..., 1])`` for an in-flight
    ``[..., heads, head_dim]`` tensor. The decode cache stores both
    lane-dense — values ``[..., len, heads*head_dim]``, scales
    ``[..., len, heads]`` (models/gpt/model.py folds them on write) — so
    scale leaves share the K/V leaves' trailing rank and every tree
    walker that addresses K/V by it (``serving.spec.scatter_slot``, the
    paged page scatter, block-spec index maps) handles scales unchanged.
    Per-vector granularity is what the flash-decode kernels stream: one
    scale per (row, head) factored out of the dot products
    (ops/pallas/decode_attention.py)."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = absmax / 127.0
    q = jnp.clip(jnp.round(x32 / jnp.maximum(scale, 1e-12)), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv` — THE dequant the dense/XLA decode
    fallback shares with the flash kernels, so every attention path
    (prefill, custom masks, meshes, interpret) sees identical values.
    Same math as :func:`dequantize_int8`; the distinct name marks the KV
    contract (per-vector scales, [..., 1] layout) at call sites."""
    return dequantize_int8(q, scale, dtype)


def fake_quant(w: jax.Array, bits: int = 8, axis: int = -1):
    """Quantize-dequantize with a straight-through gradient."""
    maxq = 2 ** (bits - 1) - 1
    w32 = w.astype(jnp.float32)
    reduce_axes = tuple(i for i in range(w32.ndim) if i != (axis % w32.ndim))
    absmax = jnp.max(jnp.abs(w32), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(absmax / maxq, 1e-12)
    deq = jnp.clip(jnp.round(w32 / scale), -maxq, maxq) * scale
    # STE: forward = deq, backward = identity
    return (w32 + jax.lax.stop_gradient(deq - w32)).astype(w.dtype)


def fake_quant_act(x: jax.Array, bits: int = 8) -> jax.Array:
    """Activation fake-quant: per-tensor DYNAMIC absmax (paddleslim
    ``abs_max`` activation observer), straight-through gradient.

    Per-tensor (not per-channel) matches quantized-serving kernels, which
    need one scale per activation tensor; dynamic (recomputed each step
    from the live tensor) is the jit-native form — no observer state
    threaded through the train step. The reference default
    ``moving_average_abs_max`` exists to accumulate *static serving
    scales*; our int8 export is weight-only (activations stay float at
    serving), so training-time dynamic scales carry the same QAT signal
    without the EMA state."""
    maxq = 2 ** (bits - 1) - 1
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32))
    scale = jnp.maximum(absmax / maxq, 1e-12)
    deq = jnp.clip(jnp.round(x32 / scale), -maxq, maxq) * scale
    return (x32 + jax.lax.stop_gradient(deq - x32)).astype(x.dtype)


def _is_weight(path, leaf) -> bool:
    """Dense/conv kernels only: >=2-D and named kernel/embedding-ish."""
    if not hasattr(leaf, "ndim") or leaf.ndim < 2:
        return False
    names = [str(getattr(k, "key", k)) for k in path]
    return any("kernel" in n or "embedding" in n.lower() for n in names)


def _is_qdict(x) -> bool:
    """An already-quantized {"_q8", "_scale"} leaf pair."""
    return isinstance(x, dict) and set(x) == {"_q8", "_scale"}


def quantize_tree_int8(params) -> Any:
    """PTQ a param pytree: each eligible weight becomes
    {"_q8": int8, "_scale": fp32}; everything else passes through.
    Idempotent: already-quantized subtrees pass through untouched, so a
    ServingEngine handed an InferenceEngine's pre-quantized params does
    not double-quantize."""
    def one(path, leaf):
        if _is_qdict(leaf) or not _is_weight(path, leaf):
            return leaf
        q, s = quantize_int8(leaf)
        return {"_q8": q, "_scale": s}

    return jax.tree_util.tree_map_with_path(one, params, is_leaf=_is_qdict)


def dequantize_tree_int8(tree, dtype=jnp.float32):
    """Inverse of quantize_tree_int8 (leaves the original dtype choice to
    the caller — serving usually wants bf16)."""
    return jax.tree.map(
        lambda x: (dequantize_int8(x["_q8"], x["_scale"], dtype)
                   if _is_qdict(x) else x),
        tree,
        is_leaf=_is_qdict,
    )


def fake_quant_tree(params, bits: int = 8):
    """QAT: fake-quantize every eligible weight in a param tree."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: fake_quant(l, bits) if _is_weight(p, l) else l, params
    )
