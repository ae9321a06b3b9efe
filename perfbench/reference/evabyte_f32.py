"""Plain float32 EvaByte: the reference the benchmark holds the system to for
``EvaByte/EvaByte`` (``model_type`` ``evabyte``, ``attention_class`` ``eva``).

Straightforward ``jax.numpy`` after the published configuration
(``config.json``: the catalog's row) and the equations of EVA ("Efficient
Attention via Control Variates", Zheng, Yuan, Wang, Kong, ICLR 2023,
arXiv:2302.04542) in the deterministic form EvaByte's published modelling
code gives them; every product under ``default_matmul_precision("highest")``,
a full forward over the whole sequence with the sets ``E_t`` and ``R_t`` as
MASKS over exact and pooled rows: no kernel, no cache, no page, no batching.

``h`` hidden, ``H`` heads of ``d`` (as many key heads as query heads),
``eps``, no bias anywhere. The stream ``x`` is float32. ``rms(x) = x /
sqrt(mean(x^2) + eps) * (1 + w)``. A block::

    x += attn(rms_1(x))
    a  = rms_2(x);  x += W_down(silu(W_gate a) * (W_up a))

then a final ``rms`` and ``logits = x W_head`` with ``W_head`` ``[h, P V]``
untied: columns ``[V j, V (j + 1))`` are head ``j``, which predicts the byte
at ``t + 1 + j``.

**EVA attention** (``a`` the normed input, position ``t``, chunk ``C``,
window ``W``, ``s = d^-0.5``)::

    q_t, k_t, v_t = a W_q, a W_k, a W_v       # per head
    q_t, k_t rotated over all d dimensions, half-split pairs, theta, at the
    TRUE position t
    chunk c holds positions [C c, C c + C); with the head's learned vectors
    mu, phi in R^d:
        k~_c = sum_j softmax_j(mu . k_j) k_j        # k_j the ROTATED key
        v~_c = sum_j softmax_j(phi . k_j) v_j       # both softmaxes over the
                                                    # chunk's C rows, no scale
    query t in window w = t // W sees the rows E_t = {j : W w <= j <= t}
    exactly and the chunks R_t = {c : c < (W / C) w} pooled:
        o_t = (sum_{E_t} e^{s q_t.k_j} v_j + sum_{R_t} e^{s q_t.k~_c} v~_c)
            / (sum_{E_t} e^{s q_t.k_j}     + sum_{R_t} e^{s q_t.k~_c})
    one softmax; then W_o.

The chunks of the query's OWN window are never in ``R_t``; the first query of
a window sees itself and pooled rows only. Every pooled row is over exactly
``C`` rows: a chunk whose last row does not exist (the sequence's open tail)
is pooled by nobody, and no query could see it anyway.

Readings the published configuration leaves open, which the program and this
file take alike (the configuration file's ``assumed`` gives each with its
other reading): the pooling weights above; keys pooled AFTER the rotation;
no ``s`` inside the pooling softmaxes; the head ONE ``[h, P V]`` matrix;
windows and chunks counted from position 0.

Departures from the published implementation, each deliberate:

- float32 throughout (pooled rows too, where the program caches them in
  bfloat16): that is what makes it the reference.
- attention is computed a block of ``q_block`` queries at a time against all
  keys and all pooled rows, so that it fits beside a serving engine; a
  block's scores are masked by position, as the whole matrix would be.
- one sequence at a time (``tokens`` ``[s]``).
- it reads the system's parameter tree (layers stacked on a leading axis; a
  fused ``qkv_proj`` kernel ``[layers, hidden, 3 heads, d]`` split q|k|v
  along the HEADS axis, or three separate kernels; ``eva_mu`` / ``eva_phi``
  ``[layers, heads, d]``; norm weights ``scale``; head ``lm_head`` ``[P V,
  hidden]``) and upcasts it a layer at a time. That layout is the only thing
  it takes from the program.
- heads, chunk, window, ``theta`` and ``eps`` are arguments (the
  configuration's values), so that one file serves the published sizes and
  the tests' tiny ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["configured", "logits"]

_NEG = -1e30


def _unboxed(tree):
    return jax.tree.map(lambda x: getattr(x, "value", x), tree,
                        is_leaf=lambda x: hasattr(x, "value"))


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (
        1.0 + weight)


def _rope(x, theta):
    """``x`` ``[s, heads, d]`` at positions ``0..s-1``: the head's two halves
    ``(x1, x2)`` become ``(x1 cos - x2 sin, x2 cos + x1 sin)``."""
    s, _, d = x.shape
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, p, *, heads, chunk, window, theta, q_block):
    s, hidden = a.shape
    if "qkv_proj" in p:
        q, k, v = jnp.split(jnp.einsum("sh,hnd->snd", a,
                                       p["qkv_proj"]["kernel"]), 3, axis=1)
    else:
        q, k, v = (jnp.einsum("sh,hnd->snd", a, p[name]["kernel"])
                   for name in ("q_proj", "k_proj", "v_proj"))
    d = q.shape[-1]
    q, k = _rope(q, theta), _rope(k, theta)
    # the pooled rows of the sequence's whole chunks
    n = s // chunk
    kc = k[:n * chunk].reshape(n, chunk, heads, d)
    vc = v[:n * chunk].reshape(n, chunk, heads, d)
    by_key = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, p["eva_mu"]), 1)
    by_value = jax.nn.softmax(jnp.einsum("cjhd,hd->cjh", kc, p["eva_phi"]), 1)
    k_pooled = jnp.einsum("cjh,cjhd->chd", by_key, kc)
    v_pooled = jnp.einsum("cjh,cjhd->chd", by_value, vc)
    scale = d ** -0.5
    blocks = -(-s // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - s), (0, 0), (0, 0)))
    key_pos = jnp.arange(s)
    chunk_at = jnp.arange(n)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block)
        t = start + jnp.arange(q_block)
        w = t // window
        exact = (key_pos[None, :] <= t[:, None]) & (
            key_pos[None, :] >= (window * w)[:, None])          # E_t
        pooled = chunk_at[None, :] < (window // chunk * w)[:, None]  # R_t
        scores = jnp.concatenate([
            jnp.where(exact[None], jnp.einsum("qhd,khd->hqk", qb, k), _NEG),
            jnp.where(pooled[None],
                      jnp.einsum("qhd,chd->hqc", qb, k_pooled), _NEG)],
            axis=-1) * scale
        probs = jax.nn.softmax(scores, axis=-1)
        return (jnp.einsum("hqk,khd->qhd", probs[..., :s], v)
                + jnp.einsum("hqc,chd->qhd", probs[..., s:], v_pooled))

    out = jax.lax.map(block, jnp.arange(blocks) * q_block)
    out = out.reshape(blocks * q_block, heads, d)[:s]
    return (jnp.einsum("snd,ndh->sh", out, p["out_proj"]["kernel"]),
            jnp.stack([k_pooled, v_pooled]).reshape(2, n, heads * d))


def _forward(gpt, tokens, *, eps, **attention):
    x = gpt["word_embeddings"].astype(jnp.float32)[tokens]

    def layer(x, p):  # a scan only so that the layers compile once
        p = _f32(p)
        out, pooled = _attention(_rms_norm(x, p["norm1"]["scale"], eps),
                                 p["attn"], **attention)
        x = x + out
        a = _rms_norm(x, p["norm2"]["scale"], eps)
        mlp = p["mlp"]
        x = x + (jax.nn.silu(a @ mlp["gate_proj"]["kernel"])
                 * (a @ mlp["up_proj"]["kernel"])) @ mlp["down_proj"]["kernel"]
        return x, pooled

    x, pooled = jax.lax.scan(layer, x, gpt["layers"]["layer"])
    return _rms_norm(x, gpt["final_norm"]["scale"].astype(jnp.float32),
                     eps), pooled


def logits(params, tokens, *, heads: int, chunk: int, window: int,
           theta: float, eps: float, tail: int = 0, q_block: int = 512,
           with_pooled: bool = False):
    """Float32 logits ``[s, P V]`` of every prediction head for ``tokens``
    ``[s]`` at positions ``0..s-1`` (``tail`` > 0: of the last ``tail``
    positions alone), from the system's parameter tree ``params``.
    ``with_pooled``: also every layer's pooled rows ``[layers, 2, s // C,
    heads * d]``, keys then values (what a cache's summary rows stand for)."""
    params = _unboxed(params)
    with jax.default_matmul_precision("highest"):
        x, pooled = _forward(params["gpt"], jnp.asarray(tokens, jnp.int32),
                             heads=heads, chunk=chunk, window=window,
                             theta=theta, eps=eps,
                             q_block=min(q_block, len(tokens)))
        out = x[-tail:] @ params["lm_head"].astype(jnp.float32).T
        return (out, pooled) if with_pooled else out


def _settings(model: dict) -> dict:
    return {"heads": model["num_attention_heads"],
            "chunk": model["eva_chunk_size"],
            "window": model["eva_window_size"],
            "theta": float(model["rope_theta"]),
            "eps": float(model["norm_eps"])}


def configured(model: dict):
    """``logits(params, tokens, tail=0)`` at the configuration ``model``
    (the ``model`` group of ``perfbench/configs/evabyte-6.5b-pp4-l8.json``)."""
    return functools.partial(logits, **_settings(model))
