"""Plain float32 LFM2 (mixture-of-experts): the reference the benchmark holds
the system to for ``LiquidAI/LFM2-8B-A1B``.

Straightforward ``jax.numpy`` after the published configuration
(``config.json``: the catalog's row, ``layer_types``, ``conv_L_cache``,
``num_dense_layers``, ``use_expert_bias``, ``norm_topk_prob``,
``routed_scaling_factor``) and the family's description (gated
short-convolution layers beside grouped-query attention layers), every
product under ``default_matmul_precision("highest")``, no kernel, no cache,
no state, no page, no batching of requests, no sort and no grouping of
tokens: the convolution is the sum of its taps over the whole sequence, and
every expert is applied to every token and weighted by that token's routing
weight for it, which is zero outside its chosen ``k``.

A layer ``l``, on ``x`` ``[s, h]`` (positions ``0..s-1``)::

    a = rmsnorm_op(x)
    if layer_types[l] == "conv":
        B, C, u = split(a @ W_in, 3)                 # thirds in this order
        z   = B * u
        c_t = w[:, 0] * z_{t-2} + w[:, 1] * z_{t-1} + w[:, 2] * z_t    # z before 0 is 0
        x'  = x + (C * c) @ W_out
    else:
        q, k, v = a @ W_q, a @ W_k, a @ W_v          # 32 / 8 / 8 heads of 64, no bias
        q, k = rmsnorm over each head's values (weights [64], one for q, one for k)
        q, k = rope(q), rope(k)                      # whole head, two halves, theta
        head g of q reads head g // 4 of k and v, causal; scores / sqrt(64); softmax
        x'  = x + concat(heads) @ W_o
    m = rmsnorm_ffn(x')
    if l < num_dense_layers:  y = (silu(m @ W_1) * (m @ W_3)) @ W_2
    else:
        s = sigmoid(m @ W_r)
        S = the k largest of s + b                   # the bias in the CHOICE only
        p_e = s_e / (sum over S of s + 1e-6) * routed_scaling_factor
        y = sum over e in S of p_e * W_down,e(silu(m @ W_gate,e) * (m @ W_up,e))
    out = x' + y

then a final RMSNorm, and the head is the embedding transposed.
``rmsnorm(x) = x / sqrt(mean(x^2) + eps) * weight``.

Readings the published configuration does not settle, which the program and
this file take alike (the configuration file's ``assumed``): the head is
tied; the thirds of the input projection are ``B, C, u`` in this order and
the operator has no activation of its own; QK-norm is per head and before
the rotation; the dense layers' width is ``intermediate_size`` as given; the
bias enters the choice and not the weight, and the normaliser adds 1e-6.

Departures from the published implementation, each deliberate:

- float32 throughout, where the published checkpoint computes in bfloat16:
  that is what makes it the reference.
- the experts run over ALL tokens and are masked: the same sum, no routing
  code to trust.
- attention is computed a block of ``q_block`` queries at a time against all
  keys, so that 4.9k positions fit beside a serving engine; a block's scores
  are masked by position, as the whole matrix would be.
- one sequence at a time (``tokens`` ``[s]``; a batch is a ``lax.map``).
- it reads the system's parameter tree: under ``gpt/layers`` the kinds
  ``conv``, ``attention``, ``dense``, ``experts``, each ``{"norm", "op"}``
  with the kind's layers stacked on a leading axis (a layer is found by its
  place among its kind); attention kernels fused (``qkv_proj`` split q|k|v
  along the heads) or three; and upcasts it a layer, and inside a layer an
  expert, at a time. That layout is the only thing it takes from the
  program. The layers are a plain Python loop.
- the layer list, head counts, ``top_k``, taps, ``theta``, ``eps`` and the
  scaling factor are arguments (the configuration's values), so that one
  file serves the published sizes and the tests' tiny ones.
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


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _rope(x, theta):
    """``x`` ``[s, heads, d]`` at positions ``0..s-1``: with ``x1, x2`` the
    head's two halves and ``a = position * theta**(-2i/d)``,
    ``(x1 cos a - x2 sin a, x2 cos a + x1 sin a)``."""
    s, d = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _conv(a, p, taps):
    """The gated short convolution of ``a`` ``[s, h]``: the sum of
    ``taps`` shifted copies of ``z = B * u``, the last tap on the position
    itself."""
    gate_in, gate_out, u = jnp.split(a @ p["in_proj"]["kernel"], 3, axis=-1)
    z, s = gate_in * u, a.shape[0]
    padded = jnp.pad(z, ((taps - 1, 0), (0, 0)))      # z before 0 is 0
    mixed = sum(p["conv_kernel"][:, i] * padded[i:i + s] for i in range(taps))
    return (gate_out * mixed) @ p["out_proj"]["kernel"]


def _attention(a, p, *, heads, kv_heads, theta, eps, q_block):
    if "qkv_proj" in p:
        qkv = jnp.einsum("se,ehd->shd", a, p["qkv_proj"]["kernel"])
        q, k, v = jnp.split(qkv, (heads, heads + kv_heads), axis=1)
    else:
        q, k, v = (jnp.einsum("se,ehd->shd", a, p[n]["kernel"])
                   for n in ("q_proj", "k_proj", "v_proj"))
    q = _rope(_rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"]["scale"], eps), theta)
    s, d = q.shape[0], q.shape[-1]
    q = q.reshape(s, kv_heads, heads // kv_heads, d)
    q_block = min(q_block, s)
    blocks = -(-s // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - s), (0, 0), (0, 0), (0, 0)))
    k_pos = jnp.arange(s)

    def block(start):
        mine = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=0)
        seen = k_pos[None, :] <= (start + jnp.arange(q_block))[:, None]
        scores = jnp.einsum("qkgd,tkd->kgqt", mine, k) / jnp.sqrt(
            jnp.float32(d))
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(block, jnp.arange(blocks) * q_block)
    ctx = ctx.reshape(blocks * q_block, heads, d)[:s]
    return jnp.einsum("shd,hde->se", ctx, p["out_proj"]["kernel"])


def _dense(m, p):
    return (jax.nn.silu(m @ p["gate_proj"]["kernel"])
            * (m @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def _experts(m, moe, layer, top_k, scaling, given=None):
    """``(sum, chosen, scores, ranked)`` of expert layer ``layer`` (its place
    in ``moe``, the experts' stack ``[layers, ...]``) on ``m`` ``[s, h]``:
    the weighted sum over each token's experts ``[s, h]``, the ``top_k``
    experts the router CHOSE ``[s, k]`` (the largest of ``ranked = scores +
    bias``), the sigmoid scores ``[s, E]`` and ``ranked`` ``[s, E]``. The
    sum runs over the chosen, except at the last ``given.shape[0]``
    positions, where it runs over the experts ``given`` names; a summed
    expert's weight is its score over the summed scores' sum + 1e-6, times
    ``scaling``. One expert's three matrices are taken from the stack and
    upcast at a time."""
    scores = jax.nn.sigmoid(
        m @ jnp.asarray(moe["router"]["kernel"][layer], jnp.float32))
    ranked = scores
    if "expert_bias" in moe:
        ranked = scores + jnp.asarray(moe["expert_bias"][layer], jnp.float32)
    chosen = summed = jax.lax.top_k(ranked, top_k)[1]
    if given is not None and given.shape[0]:
        summed = summed.at[-given.shape[0]:].set(given)
    weight = jnp.take_along_axis(scores, summed, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-6) * scaling
    dense = (jax.nn.one_hot(summed, scores.shape[-1])
             * weight[..., None]).sum(-2)

    def matrix(name, e):
        stack = moe[name]
        return jax.lax.dynamic_slice(
            stack, (layer, e, 0, 0), (1, 1, *stack.shape[2:]))[0, 0].astype(
                jnp.float32)

    def one(total, e):  # every token through expert e, then weighted
        out = (jax.nn.silu(m @ matrix("w_gate", e))
               * (m @ matrix("w_up", e))) @ matrix("w_down", e)
        return total + dense[:, e][:, None] * out, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(m),
                            jnp.arange(scores.shape[-1]))
    return total, chosen, scores, ranked


def _layer_of(stack, index):
    return jax.tree.map(lambda leaf: leaf[index], stack)


def _forward(gpt, tokens, given, *, layer_types, num_dense, heads, kv_heads,
             top_k, taps, theta, eps, scaling, q_block):
    x = jnp.asarray(gpt["word_embeddings"], jnp.float32)[tokens]
    kinds = gpt["layers"]
    seen = {"conv": 0, "attention": 0, "dense": 0, "experts": 0}
    chosen, scores = [], []
    for l, kind in enumerate(layer_types):
        kind = "conv" if kind == "conv" else "attention"
        p = _f32(_layer_of(kinds[kind], seen[kind]))
        seen[kind] += 1
        a = _rms_norm(x, p["norm"]["scale"], eps)
        x = x + (_conv(a, p["op"], taps) if kind == "conv" else _attention(
            a, p["op"], heads=heads, kv_heads=kv_heads, theta=theta, eps=eps,
            q_block=q_block))
        kind = "dense" if l < num_dense else "experts"
        m = _rms_norm(x, jnp.asarray(
            kinds[kind]["norm"]["scale"][seen[kind]], jnp.float32), eps)
        if kind == "dense":
            x = x + _dense(m, _f32(_layer_of(kinds[kind]["op"], seen[kind])))
        else:
            out, picked, score, _ = _experts(
                m, kinds[kind]["op"], seen[kind], top_k, scaling,
                None if given is None else given[seen[kind]])
            chosen.append(picked)
            scores.append(score)
            x = x + out
        seen[kind] += 1
    return (_rms_norm(x, jnp.asarray(gpt["final_norm"]["scale"], jnp.float32),
                      eps), jnp.stack(chosen), jnp.stack(scores))


def logits(params, tokens, *, layer_types, num_dense: int, heads: int,
           kv_heads: int, top_k: int, taps: int, theta: float, eps: float,
           scaling: float = 1.0, q_block: int = 256, tail: int = 0,
           with_experts: bool = False, given=None):
    """Float32 logits of ``tokens`` ``[s]`` or ``[b, s]`` (positions
    0..s-1) under ``params`` (the ``params`` tree of the served model), at
    the last ``tail`` positions (0: at all); with ``with_experts`` also,
    per expert layer, the experts the router chose ``[layers, (b,) s, k]``
    and its sigmoid scores ``[layers, (b,) s, E]``.

    ``given`` ``[expert layers, m, k]`` (one sequence only) names the
    experts to sum over at the LAST ``m`` positions in place of the
    router's own choice. Sigmoid scores of random weights lie close: where
    two experts' ranks lie closer than the rounding of the layers before, a
    system in bfloat16 takes the other one, rightly (the benchmark holds its
    choice to the router on the input it really saw), and an expert
    exchanged moves the logits by more than any rounding does: with the
    system's choice given at the positions compared, what is left is the
    arithmetic. The choice returned stays the router's."""
    params = _unboxed(params)
    tokens = jnp.asarray(tokens)
    settings = dict(layer_types=layer_types, num_dense=num_dense, heads=heads,
                    kv_heads=kv_heads, top_k=top_k, taps=taps, theta=theta,
                    eps=eps, scaling=scaling, q_block=q_block)
    if tokens.ndim == 2:
        out = jax.lax.map(lambda row: logits(
            params, row, tail=tail, with_experts=with_experts, **settings),
            tokens)
        return ((out[0], *(jnp.moveaxis(t, 0, 1) for t in out[1:]))
                if with_experts else out)
    with jax.default_matmul_precision("highest"):
        x, chosen, scores = _forward(
            params["gpt"], tokens,
            None if given is None else jnp.asarray(given, jnp.int32),
            **settings)
        out = jnp.einsum("se,ve->sv", x[-tail:], jnp.asarray(
            params["gpt"]["word_embeddings"], jnp.float32))
    return (out, chosen, scores) if with_experts else out


def expert_layers(params, inputs, chosen, *, top_k: int, scaling: float = 1.0):
    """The expert layer of EVERY expert layer alone, each on an input of
    its own: ``inputs`` ``[layers, s, h]`` what its router and experts read
    (``m`` of the module docstring), ``chosen`` ``[layers, s, k]`` the
    experts to sum over. Returns ``(sums, scores, ranked)``: ``[layers, s,
    h]`` the sum over those experts, each weighted by what THIS router
    gives it normalised over them; ``[layers, s, E]`` the router's sigmoid
    scores; and ``[layers, s, E]`` the scores plus the bias, which decide
    the choice; float32. The benchmark holds the system's layer to it on
    the input that layer really saw, whatever the layers before did."""
    moe = _unboxed(params)["gpt"]["layers"]["experts"]["op"]
    with jax.default_matmul_precision("highest"):
        def layer(_, each):
            index, m, picked = each
            total, _, scores, ranked = _experts(
                jnp.asarray(m, jnp.float32), moe, index, top_k, scaling,
                picked)
            return None, (total, scores, ranked)

        return jax.lax.scan(layer, None, (
            jnp.arange(inputs.shape[0]), inputs, chosen))[1]


def _settings(model: dict) -> dict:
    heads = model["num_attention_heads"]
    return dict(
        layer_types=tuple(model["layer_types"]),
        num_dense=int(model.get("num_dense_layers", 0)), heads=heads,
        kv_heads=model.get("num_key_value_heads") or heads,
        top_k=model["top_k"], taps=int(model.get("conv_L_cache", 3)),
        theta=float(model.get("rope_theta", 10000.0)),
        eps=float(model.get("norm_eps", 1e-5)),
        scaling=float(model.get("routed_scaling_factor", 1.0)))


def configured(model: dict):
    """:func:`logits` with the settings of a configuration file's ``model``
    group (in ``GPTConfig``'s names)."""
    return functools.partial(logits, **_settings(model))


def configured_layers(model: dict):
    """:func:`expert_layers` with a configuration's routing settings."""
    return functools.partial(
        expert_layers, top_k=model["top_k"],
        scaling=float(model.get("routed_scaling_factor", 1.0)))
