"""Plain float32 OLMoE: the reference the benchmark holds the system to.

Straightforward ``jax.numpy`` after the published description
(``allenai/OLMoE-1B-7B-0125-Instruct``, ``modeling_olmoe.py`` of the
``transformers`` library), every product under
``default_matmul_precision("highest")``, no kernel, no cache, no sort and
no grouping of tokens: every expert is applied to every token and the
result is weighted by that token's routing weight for the expert, which is
zero outside its top ``k``.

A layer, on ``x`` ``[b, s, h]``::

    n1 = rmsnorm(x)
    q, k, v = q_proj(n1), k_proj(n1), v_proj(n1)           # no biases
    q, k = rmsnorm_w(q), rmsnorm_w(k)    # over the WHOLE projection (all
                                         # heads), a learned weight each
    q, k = rope(q), rope(k)              # per head, the two halves rotated
    h = x + o_proj(softmax(causal(q k^T / sqrt(d))) v)
    n2 = rmsnorm(h)
    p = softmax(router(n2))              # over all experts, float32
    y = h + sum over the top-k e of p_e * down_e(silu(gate_e(n2)) * up_e(n2))

then a final RMSNorm and an output head of its own (not tied). The top-k
weights are NOT renormalised (``norm_topk_prob`` false) and no token is
ever dropped. ``rmsnorm(x) = x / sqrt(mean(x^2) + eps) * weight``.

Departures from the published implementation, each deliberate:

- float32 throughout, where the published checkpoint computes in
  bfloat16 (the router's softmax is float32 there too): that is what makes
  it the reference.
- the experts run over ALL tokens and are masked, where ``transformers``
  gathers each expert's tokens: the same sum, no routing code to trust.
- it reads the system's parameter tree (layers stacked on a leading axis;
  a fused ``qkv_proj`` kernel ``[layers, hidden, heads, 3*head_dim]`` split
  q|k|v along the last axis, or three separate kernels; expert weights
  ``[layers, experts, in, out]``; head ``lm_head`` ``[vocab, hidden]``) and
  upcasts it one layer, and inside a layer one expert, at a time, so that
  a chip can hold it beside a serving engine. That layout is the only
  thing it takes from the program.
- ``top_k``, ``norm_topk_prob``, ``rope_theta`` and ``eps`` are arguments
  (the configuration's values), so that one file serves the published
  sizes and the tests' tiny ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _unboxed(tree):
    """The tree with flax partitioning boxes removed."""
    return jax.tree.map(lambda x: x.unbox() if hasattr(x, "unbox") else x,
                        tree, is_leaf=lambda x: hasattr(x, "unbox"))


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _rms_norm(x, weight, eps, axes=(-1,)):
    return x * jax.lax.rsqrt((x * x).mean(axes, keepdims=True) + eps) * weight


def _rope(x, theta):
    """``x`` ``[b, s, heads, d]`` at positions ``0..s-1``: with ``x1, x2``
    the head's two halves and ``a = position * theta**(-2i/d)``,
    ``(x1 cos a - x2 sin a, x2 cos a + x1 sin a)``."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(y, p, theta, eps):
    if "qkv_proj" in p:
        qkv = jnp.einsum("bse,ehk->bshk", y, p["qkv_proj"]["kernel"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
    else:
        q, k, v = (jnp.einsum("bse,ehd->bshd", y, p[n]["kernel"])
                   for n in ("q_proj", "k_proj", "v_proj"))
    q = _rms_norm(q, p["q_norm"]["scale"], eps, axes=(-2, -1))
    k = _rms_norm(k, p["k_norm"]["scale"], eps, axes=(-2, -1))
    q, k = _rope(q, theta), _rope(k, theta)
    s = y.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None], scores,
                       -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return jnp.einsum("bqhd,hde->bqe", ctx, p["out_proj"]["kernel"])


def _experts(y, p, top_k, norm_topk_prob, chosen=None):
    """``(sum, chosen, probs)``: the weighted sum of the chosen experts for
    every token of ``y`` ``[b, s, h]``, the indices chosen ``[b, s, k]``
    (the ``top_k`` most probable, unless ``chosen`` names them) and the
    router's probabilities ``[b, s, E]``."""
    probs = jax.nn.softmax(
        y @ p["router"]["kernel"].astype(jnp.float32), axis=-1)  # [b, s, E]
    if chosen is None:
        weight, chosen = jax.lax.top_k(probs, top_k)
    else:
        weight = jnp.take_along_axis(probs, chosen, axis=-1)
    if norm_topk_prob:
        weight = weight / weight.sum(-1, keepdims=True)
    # [b, s, E]: the routing weight of every expert, 0 outside the top k
    dense = (jax.nn.one_hot(chosen, probs.shape[-1]) * weight[..., None]).sum(-2)

    def one(total, e):  # every token through expert e, then weighted
        gate, up, down, w = e
        out = (jax.nn.silu(y @ gate.astype(jnp.float32))
               * (y @ up.astype(jnp.float32))) @ down.astype(jnp.float32)
        return total + w[..., None] * out, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        p["w_gate"], p["w_up"], p["w_down"], jnp.moveaxis(dense, -1, 0)))
    return total, chosen, probs


def _layer(x, p, top_k, norm_topk_prob, theta, eps):
    """One decoder layer; ``p`` has no layer axis. The expert weights stay
    in the type they have until one expert is used."""
    moe = p["moe_mlp"]
    p = _f32({k: v for k, v in p.items() if k != "moe_mlp"})
    h = x + _attention(_rms_norm(x, p["norm1"]["scale"], eps), p["attn"],
                       theta, eps)
    out, chosen, probs = _experts(_rms_norm(h, p["norm2"]["scale"], eps), moe,
                                  top_k, norm_topk_prob)
    return h + out, (chosen, probs)


def logits(params, tokens, *, top_k: int, norm_topk_prob: bool = False,
           rope_theta: float = 10000.0, eps: float = 1e-5,
           with_experts: bool = False):
    """Float32 logits ``[b, s, vocab]`` of ``tokens`` ``[b, s]`` (positions
    0..s-1) under ``params`` (the ``params`` tree of the served model);
    with ``with_experts`` also, per layer, the experts chosen ``[layers, b,
    s, k]`` and the router's probabilities ``[layers, b, s, E]``."""
    params = _unboxed(params)
    with jax.default_matmul_precision("highest"):
        gpt = params["gpt"]
        x = jnp.asarray(gpt["word_embeddings"], jnp.float32)[tokens]

        def layer(x, p):  # a scan only so that the layers compile once
            return _layer(x, p, top_k, norm_topk_prob, rope_theta, eps)

        x, (chosen, probs) = jax.lax.scan(layer, x, gpt["layers"]["layer"])
        x = _rms_norm(x, jnp.asarray(gpt["final_norm"]["scale"], jnp.float32),
                      eps)
        out = jnp.einsum("bse,ve->bsv", x,
                         jnp.asarray(params["lm_head"], jnp.float32))
    return (out, chosen, probs) if with_experts else out


def expert_layers(params, inputs, chosen, *, top_k: int,
                  norm_topk_prob: bool = False):
    """The expert layer of EVERY layer alone, each on an input of its own:
    ``inputs`` ``[layers, s, h]`` (what the layer is handed: ``n2`` of the
    module docstring), ``chosen`` ``[layers, s, k]`` the experts to sum
    over. Returns ``(sums, probs)``: ``[layers, s, h]`` the sum over those
    experts, each weighted by the probability THIS router gives it, and
    ``[layers, s, E]`` the router's probabilities, all float32. The
    benchmark holds the system's layer to it on the input that layer
    really saw, whatever the layers before it did."""
    moe = _unboxed(params)["gpt"]["layers"]["layer"]["moe_mlp"]
    with jax.default_matmul_precision("highest"):
        def layer(_, each):
            p, y, picked = each
            total, _, probs = _experts(jnp.asarray(y, jnp.float32)[None], p,
                                       top_k, norm_topk_prob, picked[None])
            return None, (total[0], probs[0])

        return jax.lax.scan(layer, None, (moe, inputs, chosen))[1]


def configured(model: dict):
    """:func:`logits` with the routing and norm settings of a configuration
    file's ``model`` group (in ``GPTConfig``'s names)."""
    return functools.partial(
        logits, top_k=model["top_k"],
        norm_topk_prob=model.get("norm_topk_prob", False),
        rope_theta=model.get("rope_theta", 10000.0),
        eps=model.get("norm_eps", 1e-5))


def configured_layers(model: dict):
    """:func:`expert_layers` with a configuration's routing settings."""
    return functools.partial(
        expert_layers, top_k=model["top_k"],
        norm_topk_prob=model.get("norm_topk_prob", False))
