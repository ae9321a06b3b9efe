"""Plain float32 SmallThinker: the reference the benchmark holds the system
to for ``PowerInfer/SmallThinker-21BA3B-Instruct``.

Straightforward ``jax.numpy`` after the published configuration
(``config.json``: the catalog's row) and the family's description
("SWA(4096); NoPE global", "64 experts, top-6, 0 shared; sparse ReGLU;
router placed before attention"), every product under
``default_matmul_precision("highest")``, no kernel, no cache, no page, no
batching of requests, no sort and no grouping of tokens: every expert is
applied to every token and weighted by that token's routing weight for it,
which is zero outside its top ``k``.

A layer ``l``, on ``x`` ``[s, h]`` (positions ``0..s-1``)::

    r   = x @ W_r                                   # the router, BEFORE attention
    a   = rmsnorm1(x)
    q, k, v = a @ W_q, a @ W_k, a @ W_v             # 28 / 4 / 4 heads of 128, no bias
    if rope_layout[l]:  q, k = rope(q), rope(k)     # whole head, two halves, theta
    seen(i, j) = j <= i and (not sliding_window_layout[l] or i - j < window)
    head g of q reads head g // group of k and v; scores / sqrt(d); softmax
    x'  = x + concat(heads) @ W_o
    m   = rmsnorm2(x')
    p   = softmax(r over its k largest)             # = softmax over all, renormalised
    out = x' + sum over those e of p_e * W_down,e(relu(m @ W_gate,e) * (m @ W_up,e))

then a final RMSNorm and an output head of its own (not tied).
``rmsnorm(x) = x / sqrt(mean(x^2) + eps) * weight``.

Three readings the published configuration does not settle, which the
program and this file take alike (the configuration file's ``assumed``):
the router reads the block's input ``x`` before ``rmsnorm1`` (the other
reading of "router placed before attention" is ``rmsnorm1(x)``); the window
admits ``i - j < window``, that is ``window`` keys with the query's own;
attention has no bias and scale ``1 / sqrt(head_dim)``.

Departures from the published implementation, each deliberate:

- float32 throughout, where the published checkpoint computes in bfloat16:
  that is what makes it the reference.
- the family's "secondary experts" are a sparsity of the ReGLU neurons at
  inference and the configuration carries no key for them: the dense ReGLU
  sum above is the mathematics.
- the experts run over ALL tokens and are masked: the same sum, no routing
  code to trust.
- attention is computed a block of ``q_block`` queries at a time against
  all keys, so that 6k-13k positions fit beside a serving engine; a block's
  scores are masked by position, as the whole matrix would be.
- one sequence at a time (``tokens`` ``[s]``; a batch is a ``lax.map``).
- it reads the system's parameter tree (layers stacked on a leading axis; a
  fused ``qkv_proj`` kernel ``[layers, hidden, heads + 2 kv_heads,
  head_dim]`` split q|k|v along the HEADS axis, or three separate kernels;
  router ``moe_mlp/router``; expert weights ``[layers, experts, in, out]``;
  head ``lm_head`` ``[vocab, hidden]``) and upcasts it a layer, and inside
  a layer an expert, at a time. That layout is the only thing it takes from
  the program.
- the layouts, head counts, window, ``top_k``, ``theta`` and ``eps`` are
  arguments (the configuration's values), so that one file serves the
  published sizes and the tests' tiny ones.
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


def _attention(y, p, rotates, windowed, *, heads, kv_heads, window, theta,
               q_block):
    """``y`` ``[s, h]``; ``rotates`` and ``windowed`` the layer's two flags
    (traced: the layers run as one scanned body)."""
    if "qkv_proj" in p:
        qkv = jnp.einsum("se,ehd->shd", y, p["qkv_proj"]["kernel"])
        q, k, v = jnp.split(qkv, (heads, heads + kv_heads), axis=1)
    else:
        q, k, v = (jnp.einsum("se,ehd->shd", y, p[n]["kernel"])
                   for n in ("q_proj", "k_proj", "v_proj"))
    q = jnp.where(rotates, _rope(q, theta), q)
    k = jnp.where(rotates, _rope(k, theta), k)
    s, d = q.shape[0], q.shape[-1]
    group = heads // kv_heads
    q = q.reshape(s, kv_heads, group, d)
    blocks = -(-s // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - s), (0, 0), (0, 0), (0, 0)))
    k_pos = jnp.arange(s)

    def block(start):
        mine = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=0)
        q_pos = start + jnp.arange(q_block)
        seen = k_pos[None, :] <= q_pos[:, None]
        if window:
            seen &= ~windowed | (q_pos[:, None] - k_pos[None, :] < window)
        scores = jnp.einsum("qkgd,tkd->kgqt", mine, k) / jnp.sqrt(
            jnp.float32(d))
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, -1), v)

    ctx = jax.lax.map(block, jnp.arange(blocks) * q_block)
    ctx = ctx.reshape(blocks * q_block, heads, d)[:s]
    return jnp.einsum("shd,hde->se", ctx, p["out_proj"]["kernel"])


def _experts(routed, y, p, top_k, given=None):
    """``(sum, chosen, probs)``: for every token, the weighted sum over its
    experts of ``down(relu(gate(y)) * up(y))`` ``[s, h]``, the ``top_k``
    experts the router CHOSE ``[s, k]`` (the largest of its logits on
    ``routed``) and its softmax over ALL experts ``[s, E]``. The sum runs
    over the chosen, except at the last ``given.shape[0]`` positions, where
    it runs over the experts ``given`` names. The routing weight is the
    softmax over the logits of the experts summed (equal to the softmax
    over all renormalised over them)."""
    scores = routed @ p["router"]["kernel"].astype(jnp.float32)
    probs = jax.nn.softmax(scores, axis=-1)
    chosen = summed = jax.lax.top_k(scores, top_k)[1]
    if given is not None and given.shape[0]:
        summed = summed.at[-given.shape[0]:].set(given)
    weight = jax.nn.softmax(jnp.take_along_axis(scores, summed, axis=-1), -1)
    dense = (jax.nn.one_hot(summed, probs.shape[-1]) * weight[..., None]).sum(-2)

    def one(total, e):  # every token through expert e, then weighted
        gate, up, down, w = e
        out = (jax.nn.relu(y @ gate.astype(jnp.float32))
               * (y @ up.astype(jnp.float32))) @ down.astype(jnp.float32)
        return total + w[:, None] * out, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        p["w_gate"], p["w_up"], p["w_down"], dense.T))
    return total, chosen, probs


def _forward(gpt, tokens, given, *, heads, kv_heads, top_k, window,
             rope_layout, window_layout, theta, eps, q_block):
    x = jnp.asarray(gpt["word_embeddings"], jnp.float32)[tokens]

    def layer(x, each):  # a scan only so that the layers compile once
        p, rotates, windowed, given = each
        moe = p["moe_mlp"]
        p = _f32({k: v for k, v in p.items() if k != "moe_mlp"})
        h = x + _attention(
            _rms_norm(x, p["norm1"]["scale"], eps), p["attn"], rotates,
            windowed, heads=heads, kv_heads=kv_heads, window=window,
            theta=theta, q_block=q_block)
        out, chosen, probs = _experts(
            x, _rms_norm(h, p["norm2"]["scale"], eps), moe, top_k, given)
        return h + out, (chosen, probs)

    x, (chosen, probs) = jax.lax.scan(layer, x, (
        gpt["layers"]["layer"], jnp.asarray(rope_layout, bool),
        jnp.asarray(window_layout, bool), given))
    return _rms_norm(x, jnp.asarray(gpt["final_norm"]["scale"], jnp.float32),
                     eps), chosen, probs


def logits(params, tokens, *, heads: int, kv_heads: int, top_k: int,
           window: int, rope_layout, window_layout, theta: float,
           eps: float, q_block: int = 256, tail: int = 0,
           with_experts: bool = False, given=None):
    """Float32 logits of ``tokens`` ``[s]`` or ``[b, s]`` (positions
    0..s-1) under ``params`` (the ``params`` tree of the served model), at
    the last ``tail`` positions (0: at all); with ``with_experts`` also,
    per layer, the experts the router chose ``[layers, (b,) s, k]`` and its
    probabilities ``[layers, (b,) s, E]``.

    ``given`` ``[layers, m, k]`` (one sequence only) names the experts to
    sum over at the LAST ``m`` positions in place of the router's own
    choice. Where two experts' logits lie closer than the rounding of the
    layers before, a system in bfloat16 takes the other one, rightly (the
    benchmark holds its choice to the router on the input it really saw),
    and an expert exchanged moves the logits by more than any rounding
    does: with the system's choice given at the positions compared, what
    is left is the arithmetic. The choice returned stays the router's."""
    params = _unboxed(params)
    tokens = jnp.asarray(tokens)
    if given is None:
        given = jnp.zeros((len(rope_layout), 0, top_k), jnp.int32)
    if tokens.ndim == 2:
        out = jax.lax.map(lambda row: logits(
            params, row, heads=heads, kv_heads=kv_heads, top_k=top_k,
            window=window, rope_layout=rope_layout,
            window_layout=window_layout, theta=theta, eps=eps,
            q_block=q_block, tail=tail, with_experts=with_experts), tokens)
        return ((out[0], *(jnp.moveaxis(t, 0, 1) for t in out[1:]))
                if with_experts else out)
    with jax.default_matmul_precision("highest"):
        x, chosen, probs = _forward(
            params["gpt"], tokens, jnp.asarray(given, jnp.int32),
            heads=heads, kv_heads=kv_heads,
            top_k=top_k, window=window, rope_layout=rope_layout,
            window_layout=window_layout, theta=theta, eps=eps,
            q_block=min(q_block, tokens.shape[0]))
        out = jnp.einsum("se,ve->sv", x[-tail:],
                         jnp.asarray(params["lm_head"], jnp.float32))
    return (out, chosen, probs) if with_experts else out


def expert_layers(params, router_inputs, inputs, chosen, *, top_k: int):
    """The expert layer of EVERY layer alone, each on inputs of its own:
    ``router_inputs`` ``[layers, s, h]`` what the layer's router read (the
    block's input), ``inputs`` ``[layers, s, h]`` what its experts read
    (``m`` of the module docstring), ``chosen`` ``[layers, s, k]`` the
    experts to sum over. Returns ``(sums, probs)``: ``[layers, s, h]`` the
    sum over those experts, each weighted by what THIS router gives it
    renormalised over them, and ``[layers, s, E]`` the router's softmax
    over all experts, float32. The benchmark holds the system's layer to it
    on the inputs that layer really saw, whatever the layers before did."""
    moe = _unboxed(params)["gpt"]["layers"]["layer"]["moe_mlp"]
    with jax.default_matmul_precision("highest"):
        def layer(_, each):
            p, routed, y, picked = each
            total, _, probs = _experts(
                jnp.asarray(routed, jnp.float32),
                jnp.asarray(y, jnp.float32), p, top_k, picked)  # all given
            return None, (total, probs)

        return jax.lax.scan(layer, None,
                            (moe, router_inputs, inputs, chosen))[1]


def _settings(model: dict) -> dict:
    layers = model["num_layers"]
    return dict(
        heads=model["num_attention_heads"],
        kv_heads=model.get("num_key_value_heads")
        or model["num_attention_heads"],
        top_k=model["top_k"], window=model.get("sliding_window") or 0,
        rope_layout=tuple(model.get("rope_layout") or (1,) * layers),
        window_layout=tuple(model.get("sliding_window_layout")
                            or (1 if model.get("sliding_window") else 0,)
                            * layers),
        theta=float(model.get("rope_theta", 10000.0)),
        eps=float(model.get("norm_eps", 1e-5)))


def configured(model: dict):
    """:func:`logits` with the settings of a configuration file's ``model``
    group (in ``GPTConfig``'s names)."""
    return functools.partial(logits, **_settings(model))


def configured_layers(model: dict):
    """:func:`expert_layers` with a configuration's routing settings."""
    return functools.partial(expert_layers, top_k=model["top_k"])
