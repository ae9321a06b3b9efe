"""Plain float32 GPT: the reference the benchmark holds the system to.

Straightforward ``jax.numpy``: learned position embeddings, pre-LayerNorm
decoder layers (LayerNorm eps 1e-5, full causal multi-head attention scaled
by 1/sqrt(head_dim), GELU (tanh form) MLP), a final LayerNorm and a head
tied to the word embeddings; the loss is the masked mean of the token
cross-entropies. No kernels, no cache, no batching tricks, every product
under ``default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs as one bf16 pass).

It reads the parameter tree of ``GPTForPretraining`` as the system holds it
(layers stacked on a leading axis, fused ``qkv_proj`` kernel
``[layers, hidden, heads, 3*head_dim]`` split q|k|v along the last axis) and
computes in float32 whatever type the leaves have. That layout is the only
thing it takes from the program; the mathematics follows the published GPT-2
/ Megatron description the reference system implements.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _values(tree):
    """The tree with flax partitioning boxes removed, leaves as float32."""
    def unbox(x):
        return x.unbox() if hasattr(x, "unbox") else x
    tree = jax.tree.map(unbox, tree, is_leaf=lambda x: hasattr(x, "unbox"))
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _layer(x, p):
    """One decoder layer on ``x`` [b, s, h]; ``p`` has no layer axis."""
    b, s, _ = x.shape
    y = _layer_norm(x, p["norm1"])
    qkv = jnp.einsum("bse,ehk->bshk", y, p["attn"]["qkv_proj"]["kernel"])
    qkv = qkv + p["attn"]["qkv_proj"]["bias"]
    q, k, v = jnp.split(qkv, 3, axis=-1)           # each [b, s, heads, d]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    out = jnp.einsum("bqhd,hde->bqe", ctx, p["attn"]["out_proj"]["kernel"])
    x = x + out + p["attn"]["out_proj"]["bias"]
    y = _layer_norm(x, p["norm2"])
    y = _gelu_tanh(y @ p["mlp"]["up_proj"]["kernel"]
                   + p["mlp"]["up_proj"]["bias"])
    return x + y @ p["mlp"]["down_proj"]["kernel"] + p["mlp"]["down_proj"]["bias"]


def logits(params, tokens):
    """Float32 logits ``[b, s, vocab]`` of ``tokens`` ``[b, s]`` (positions
    0..s-1) under ``params`` (the ``params`` tree of GPTForPretraining)."""
    with jax.default_matmul_precision("highest"):
        gpt = _values(params)["gpt"]
        s = tokens.shape[1]
        x = gpt["word_embeddings"][tokens] + gpt["position_embeddings"][:s]
        stacked = gpt["layers"]["layer"]
        # the layers in order (a scan only so that 24 of them compile once)
        x, _ = jax.lax.scan(lambda x, p: (_layer(x, p), None), x, stacked)
        x = _layer_norm(x, gpt["final_norm"])
        return jnp.einsum("bse,ve->bsv", x, gpt["word_embeddings"])


def token_losses(params, tokens, labels):
    """Float32 cross-entropy ``[b, s]`` of ``labels`` under the logits."""
    lg = logits(params, tokens)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return logz - picked


def loss(params, tokens, labels, loss_mask):
    """Masked mean cross-entropy of next-token prediction, float32."""
    mask = loss_mask.astype(jnp.float32)
    return (token_losses(params, tokens, labels) * mask).sum() / mask.sum()
