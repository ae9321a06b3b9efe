"""Plain float32 Trinity (``model_type: afmoe``): the reference the benchmark
holds the system to for ``arcee-ai/Trinity-Large-Preview``, as ONE CHIP'S
SHARE of a deployment computes it (a held range of the routed experts; what
the absent experts would add is left out, here as in the program).

Straightforward ``jax.numpy`` after the published configuration
(``config.json``: the catalog's row) and the family's description ("SWA(4096)
gated; global every 4th", "256 experts, top-4, 1 shared; sigmoid routing,
SMEBU bias", "depth-scaled sandwich norm"), every product under
``default_matmul_precision("highest")``, no kernel, no cache, no page, no
batching of requests, no sort and no grouping of tokens: every held expert
is applied to every token and weighted by that token's routing weight for
it, which is zero outside its top ``k``.

With ``RMS_n(x) = x / sqrt(mean(x^2) + eps) * w_n``, on ``x`` ``[s, h]``
(positions ``0..s-1``)::

    x_0   = E[ids] * embedding_multiplier                 (sqrt(h): mup_enabled)
    a     = RMS_in(x)
    q,k,v = a W_q [heads x d], a W_k [kv_heads x d], a W_v [kv_heads x d]
    g     = a W_g [heads x d]                             (the output gate)
    q, k  = RMS_q(q), RMS_k(k)       per head, one weight [d] each, BEFORE the rotation
    window layer:  q, k rotated (whole head, halves (x1, x2), theta);
                   key j seen by query i iff j <= i and i - j < window
    full layer:    no position of any kind; j <= i
    o     = softmax(q k^T / sqrt(d)) v           head g of q reads head g // group of k, v
    x     = x + RMS_post_attn((o * sigmoid(g)) W_o)
    b     = RMS_pre_mlp(x)
    dense layer:   m = (silu(b W_gate) * (b W_up)) W_down
    expert layer:  s = sigmoid(b W_r)                     [routed experts]
                   C = the k largest of (s + bias)        (bias: CHOICE only)
                   w_e = s_e / (sum_{e in C} s_e + 1e-20) * routed_scaling_factor
                   m = shared(b) + sum_{e in C, e held} w_e expert_e(b)
    x     = x + RMS_post_mlp(m)
    logits = RMS_final(x_L) W_head                        (untied)

What the published configuration does not settle and the program and this
file take alike is the configuration file's ``assumed``.

Departures from the published implementation, each deliberate:

- float32 throughout, where the published checkpoint computes in bfloat16:
  that is what makes it the reference.
- the held experts run over ALL tokens and are masked: the same sum, no
  routing code to trust. The experts that are not held add nothing (the
  share; module docstring of ``fleetx_tpu/parallel/moe_share.py``).
- attention is computed a block of ``q_block`` queries at a time against
  all keys, the dense MLP a block of its width at a time and an expert at a
  time, each upcast alone, so that 6k-7k positions fit beside a serving
  engine that fills the chip.
- one sequence at a time (``tokens`` ``[s]``).
- it reads the system's parameter tree: under ``gpt/layers`` the kinds
  ``attention``, ``dense``, ``experts``, each ``{"norm", "op",
  "post_norm"}`` with the kind's layers stacked on a leading axis; the
  attention's ``qkv_proj`` kernel ``[layers, hidden, heads + 2 kv_heads, d]``
  split q|k|v along the HEADS axis (or three separate kernels) beside
  ``gate_proj``; the head ``lm_head`` ``[vocab, hidden]``. That layout is
  the only thing it takes from the program.
- the layouts, head counts, window, ``top_k``, ``theta``, ``eps``, the
  multiplier and the held range are arguments (the configuration's values),
  so that one file serves the published sizes and the tests' tiny ones.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["configured", "configured_layers", "expert_layers", "logits"]


def _unboxed(tree):
    return jax.tree.map(lambda x: x.unbox() if hasattr(x, "unbox") else x,
                        tree, is_leaf=lambda x: hasattr(x, "unbox"))


def _f32(x):
    return jnp.asarray(x, jnp.float32)


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


def _attention(a, p, s, rotates: bool, windowed: bool):
    """The gated attention of one layer on its normed input ``a`` ``[n,
    h]``; ``p`` the layer's own slice of the attention stack, float32."""
    heads, kv_heads, eps = s["heads"], s["kv_heads"], s["eps"]
    if "qkv_proj" in p:
        qkv = jnp.einsum("se,ehd->shd", a, p["qkv_proj"]["kernel"])
        q, k, v = jnp.split(qkv, (heads, heads + kv_heads), axis=1)
    else:
        q, k, v = (jnp.einsum("se,ehd->shd", a, p[n]["kernel"])
                   for n in ("q_proj", "k_proj", "v_proj"))
    if "q_norm" in p:
        q = _rms_norm(q, p["q_norm"]["scale"], eps)
        k = _rms_norm(k, p["k_norm"]["scale"], eps)
    if rotates:
        q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    n, d = q.shape[0], q.shape[-1]
    q = q.reshape(n, kv_heads, heads // kv_heads, d)
    q_block = min(s["q_block"], n)
    blocks = -(-n // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - n), (0, 0), (0, 0), (0, 0)))
    k_pos = jnp.arange(n)

    def block(start):
        mine = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=0)
        q_pos = start + jnp.arange(q_block)
        seen = k_pos[None, :] <= q_pos[:, None]
        if windowed:
            seen &= q_pos[:, None] - k_pos[None, :] < s["window"]
        scores = jnp.einsum("qkgd,tkd->kgqt", mine, k) / jnp.sqrt(
            jnp.float32(d))
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(block, jnp.arange(blocks) * q_block)
    out = out.reshape(blocks * q_block, heads, d)[:n]
    if "gate_proj" in p:
        out = out * jax.nn.sigmoid(
            jnp.einsum("se,ehd->shd", a, p["gate_proj"]["kernel"]))
    return jnp.einsum("shd,hde->se", out, p["out_proj"]["kernel"])


def _dense(b, p, layer: int, block: int):
    """The dense layer's gated MLP, ``block`` columns of its width at a
    time (the same sum). ``p`` holds the dense layers' stack, as handed
    over."""
    gate, up, down = (p[name]["kernel"] for name in (
        "gate_proj", "up_proj", "down_proj"))
    h, f = gate.shape[1:]
    block = min(block, f)
    if f % block:
        raise ValueError(f"dense width {f} in blocks of {block}")

    def one(total, start):
        g, u = (_f32(jax.lax.dynamic_slice(
            w, (layer, 0, start), (1, h, block))[0]) for w in (gate, up))
        d = _f32(jax.lax.dynamic_slice(
            down, (layer, start, 0), (1, block, h))[0])
        return total + (jax.nn.silu(b @ g) * (b @ u)) @ d, None

    return jax.lax.scan(one, jnp.zeros_like(b),
                        jnp.arange(f // block) * block)[0]


def _experts(b, moe, layer, s, given=None):
    """``(sum, chosen, scores, ranked)`` of expert layer ``layer`` (its place
    in ``moe``, the experts' stack) on ``b`` ``[n, h]``: the weighted sum
    over each token's chosen experts THAT ARE HELD plus the shared expert;
    the ``top_k`` chosen ``[n, k]`` (routed numbers); the sigmoid scores
    ``[n, E]`` and what the choice ranks, the scores plus the selection
    bias. ``given`` ``[m, k]`` names the experts to sum over at the LAST
    ``m`` positions in the router's place (the choice returned stays the
    router's)."""
    first, held = s["first"], moe["w_gate"].shape[1]
    scores = jax.nn.sigmoid(b @ _f32(moe["router"]["kernel"][layer]))
    ranked = scores
    if "expert_bias" in moe:
        ranked = scores + _f32(moe["expert_bias"][layer])
    chosen = summed = jax.lax.top_k(ranked, s["top_k"])[1]
    if given is not None and given.shape[0]:
        summed = summed.at[-given.shape[0]:].set(given)
    weight = jnp.take_along_axis(scores, summed, axis=-1)
    if s["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = weight * s["scaling"]
    dense = (jax.nn.one_hot(summed, scores.shape[-1])
             * weight[..., None]).sum(-2)                 # [n, routed]

    def matrix(name, e):
        stack = moe[name]
        return _f32(jax.lax.dynamic_slice(
            stack, (layer, e, 0, 0), (1, 1, *stack.shape[2:]))[0, 0])

    def one(total, e):  # every token through held expert e, then weighted
        out = (jax.nn.silu(b @ matrix("w_gate", e))
               * (b @ matrix("w_up", e))) @ matrix("w_down", e)
        return total + jax.lax.dynamic_slice_in_dim(
            dense, first + e, 1, axis=1) * out, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(b), jnp.arange(held))
    if "shared_gate" in moe:
        gate, up, down = (_f32(moe[name][layer]) for name in (
            "shared_gate", "shared_up", "shared_down"))
        total = total + (jax.nn.silu(b @ gate) * (b @ up)) @ down
    return total, chosen, scores, ranked


def _scale_of(kind, name, layer):
    return _f32(kind[name]["scale"][layer])


def logits(params, tokens, *, settings: dict, tail: int = 0,
           with_experts: bool = False, given=None):
    """Float32 logits of ``tokens`` ``[s]`` (positions 0..s-1) under
    ``params`` (the ``params`` tree of the served model), at the last
    ``tail`` positions (0: at all); with ``with_experts`` also, per expert
    layer, the experts the router chose ``[expert layers, s, k]`` and what
    its choice ranks (score + bias) ``[expert layers, s, E]``.

    ``given`` ``[expert layers, m, k]`` names the experts to sum over at
    the LAST ``m`` positions in place of the router's own choice (the choice
    returned stays the router's). Where two experts' ranks lie closer than
    the rounding of the layers before, a system in bfloat16 takes the other
    one, rightly (the benchmark holds its choice to the router on the input
    it really saw: :func:`expert_layers`), and an expert exchanged moves the
    logits by more than any rounding does: with the system's choice given
    at the positions compared, what is left is the arithmetic. May be
    wrapped in ``jax.jit`` (``tail`` and ``with_experts`` static)."""
    s = settings
    params = _unboxed(params)
    gpt = params["gpt"]
    kinds, eps = gpt["layers"], s["eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(gpt["word_embeddings"])[jnp.asarray(tokens)] * s["multiplier"]
        chosen, ranks = [], []
        for l in range(s["layers"]):
            kind = kinds["attention"]
            p = jax.tree.map(lambda leaf, l=l: _f32(leaf[l]), kind["op"])
            y = _attention(_rms_norm(x, _scale_of(kind, "norm", l), eps), p,
                           s, bool(s["rope_layout"][l]),
                           bool(s["window_layout"][l]))
            if "post_norm" in kind:
                y = _rms_norm(y, _scale_of(kind, "post_norm", l), eps)
            x = x + y
            dense = l < s["num_dense"]
            kind = kinds["dense" if dense else "experts"]
            at = l if dense else l - s["num_dense"]
            b = _rms_norm(x, _scale_of(kind, "norm", at), eps)
            if dense:
                m = _dense(b, kind["op"], at, s["dense_block"])
            else:
                m, picked, _, ranked = _experts(
                    b, kind["op"], at, s,
                    None if given is None else jnp.asarray(given[at],
                                                           jnp.int32))
                chosen.append(picked)
                ranks.append(ranked)
            if "post_norm" in kind:
                m = _rms_norm(m, _scale_of(kind, "post_norm", at), eps)
            x = x + m
        x = _rms_norm(x[-tail:], _f32(gpt["final_norm"]["scale"]), eps)
        out = jnp.einsum("se,ve->sv", x, _f32(params["lm_head"]))
    return (out, jnp.stack(chosen), jnp.stack(ranks)) if with_experts else out


def expert_layers(params, inputs, chosen, *, settings: dict):
    """EVERY expert layer alone, each on an input of its own: ``inputs``
    ``[layers, s, h]`` what its router and experts read, ``chosen``
    ``[layers, s, k]`` the experts to sum over (those of them that are
    held; the shared expert is added). Returns ``(sums, scores, ranked)``:
    ``[layers, s, h]``; the router's sigmoid scores ``[layers, s, E]``; and
    the scores plus the selection bias, which decide the choice. The
    benchmark holds the system's layer to it on the input that layer really
    saw, whatever the layers before did."""
    moe = _unboxed(params)["gpt"]["layers"]["experts"]["op"]

    @jax.jit
    def alone(moe, inputs, chosen):
        with jax.default_matmul_precision("highest"):
            def layer(_, each):
                index, b, picked = each
                total, _, scores, ranked = _experts(
                    _f32(b), moe, index, settings, picked)
                return None, (total, scores, ranked)

            return jax.lax.scan(layer, None, (
                jnp.arange(inputs.shape[0]), inputs, chosen))[1]

    return alone(moe, jnp.asarray(inputs), jnp.asarray(chosen, jnp.int32))


def _settings(model: dict, q_block: int = 256,
              dense_block: int = 2048) -> dict:
    layers = int(model["num_layers"])
    kinds = model.get("layer_types") or ("full_attention",) * layers
    window = int(model.get("sliding_window") or 0)
    return dict(
        layers=layers, num_dense=int(model.get("num_dense_layers", 0)),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model.get("num_key_value_heads")
                     or model["num_attention_heads"]),
        window=window,
        window_layout=tuple(
            model.get("sliding_window_layout")
            or [int(bool(window) and (t == "sliding_attention"
                                      or "sliding_attention" not in kinds))
                for t in kinds]),
        rope_layout=tuple(model.get("rope_layout") or (1,) * layers),
        theta=float(model.get("rope_theta", 10000.0)),
        eps=float(model.get("norm_eps", 1e-5)),
        multiplier=float(model.get("embedding_multiplier", 1.0)),
        top_k=int(model["top_k"]),
        norm_topk_prob=bool(model.get("norm_topk_prob", False)),
        scaling=float(model.get("routed_scaling_factor", 1.0)),
        first=int(model.get("first_expert_held", 0)), q_block=q_block,
        dense_block=dense_block)


def configured(model: dict):
    """:func:`logits` with the settings of a configuration file's ``model``
    group (in ``GPTConfig``'s names)."""
    return functools.partial(logits, settings=_settings(model))


def configured_layers(model: dict):
    """:func:`expert_layers` with a configuration's routing settings."""
    return functools.partial(expert_layers, settings=_settings(model))
