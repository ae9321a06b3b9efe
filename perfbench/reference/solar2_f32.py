"""Plain float32 Solar-Open2 (``model_type: solar_open2``): the reference the
benchmark holds the system to for ``upstage/Solar-Open2-250B``, as ONE CHIP'S
SHARE of a deployment computes it (a held range of the routed experts; what
the absent experts would add is left out, here as in the program).

Straightforward ``jax.numpy`` after the published configuration
(``config.json``: the catalog's row) and Kimi Delta Attention as Kimi Linear
describes it (arXiv:2510.26692; the ``kda_*`` keys name it), every product
under ``default_matmul_precision("highest")``, no kernel, no cache, no page,
no chunk form: the delta rule is a ``lax.scan`` over TOKENS, and every held
expert is applied to every token and weighted by that token's routing weight
for it, which is zero outside its top ``k``.

With ``RMS_n(x) = x / sqrt(mean(x^2) + eps) * w_n``, on ``x`` ``[s, h]``
(positions ``0..s-1``); layer ``i`` is a GQA layer where ``layer_types[i]``
says ``full_attention`` (``i % 4 == 0``), else a KDA layer::

    KDA(a), a = RMS_in(x); H heads of d:
      [q~ | k~ | v~] = a W_qkv                              each H x d wide
      q', k', v = silu(conv(q~)), silu(conv(k~)), silu(conv(v~))
                    conv: causal depthwise, ``taps`` taps, no bias
      q = q' / sqrt(sum q'^2 + 1e-6) * d^-0.5,  k = k' / sqrt(sum k'^2 + 1e-6)
      g = -exp(A_log[head]) * softplus((a W_fa) W_fb + dt_bias)     [H, d]
      beta = sigmoid(a W_b) (x 2: kda_neg_eigval)                   [H]
      per head, S [d, d], S_0 = 0:
        S   = diag(exp(g_t)) S_{t-1}
        S_t = S + beta_t k_t (v_t - S^T k_t)^T
        o_t = S_t^T q_t
      x = x + (RMS_o(o_t) * sigmoid((a W_ga) W_gb + b_g)) W_o   RMS_o per head

    GQA(a), a = RMS_in(x); no position of any kind, no QK-norm:
      o = softmax_causal(q k^T / sqrt(d)) v     head g of q reads head g // group
      x = x + (o * sigmoid(a W_g)) W_o

    MoE(b), b = RMS_post(x):
      s = sigmoid(b W_r) over ALL routed experts;  C = the k largest of (s + bias)
      w_e = s_e / (sum_C s + 1e-20) * routed_scaling_factor
      x = x + shared(b) + sum_{e in C, e held} w_e expert_e(b)

    logits = RMS_final(x_L) W_head                          (untied)

What the published configuration does not settle and the program and this
file take alike is the configuration file's ``assumed``.

Departures from the published implementation, each deliberate:

- float32 throughout, where the published checkpoint computes in bfloat16:
  that is what makes it the reference.
- the held experts run over ALL tokens and are masked: the same sum, no
  routing code to trust. The experts that are not held add nothing.
- attention is computed a block of ``q_block`` queries at a time against all
  keys and an expert at a time, each upcast alone, so that a few thousand
  positions fit beside a serving engine that fills the chip.
- one sequence at a time (``tokens`` ``[s]``).
- it reads the system's parameter tree: under ``gpt/layers`` the kinds
  ``attention``, ``kda``, ``experts``, each ``{"norm", "op"}`` with the
  kind's layers stacked on a leading axis; the head ``lm_head`` ``[vocab,
  hidden]``. That layout is the only thing it takes from the program (its
  state is ``[heads, d_k, d_v]`` here; the program holds ``[d_k, heads,
  d_v]``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["configured", "configured_layers", "delta_rule", "expert_layers",
           "logits"]


def _unboxed(tree):
    return jax.tree.map(lambda x: x.unbox() if hasattr(x, "unbox") else x,
                        tree, is_leaf=lambda x: hasattr(x, "unbox"))


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _l2(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _low_rank(a, p, name):
    return (a @ p[name + "_a"]["kernel"]) @ p[name + "_b"]["kernel"] + p[
        name + "_b"]["bias"]


def delta_rule(q, k, v, g, beta, state):
    """The gated delta rule as a scan over TOKENS: ``q, k, g`` ``[n, H,
    d_k]``, ``v`` ``[n, H, d_v]``, ``beta`` ``[n, H]`` from ``state`` ``[H,
    d_k, d_v]``; returns ``o`` ``[n, H, d_v]`` and the last state. The
    benchmark also holds the system's kernels to it alone, on the rows they
    really saw."""
    def step(state, row):
        q_t, k_t, v_t, g_t, b_t = row
        state = state * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    with jax.default_matmul_precision("highest"):
        state, o = jax.lax.scan(step, _f32(state), tuple(
            _f32(t) for t in (q, k, v, g, beta)))
    return o, state


def _kda(a, p, s, states_at):
    """The KDA operator of one layer on its normed input ``a`` ``[n, h]``;
    ``p`` the layer's own slice of the kda stack, float32. Returns the
    output ``[n, h]``, the state ``S`` ``[len(states_at), H, d, d]`` after
    each of the positions ``states_at`` (ascending; each the COUNT of tokens
    the state has seen) and the filter's inputs at the ``taps - 1``
    positions before each ``[len(states_at), taps - 1, 3 H d]``."""
    heads, d, taps = s["kda_heads"], s["kda_dim"], s["kda_taps"]
    n = a.shape[0]
    qkv = a @ p["qkv_proj"]["kernel"]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(p["conv_kernel"][:, i] * padded[i:i + n]
                            for i in range(taps)))
    q, k, v = (t.reshape(n, heads, d) for t in jnp.split(mixed, 3, axis=-1))
    q, k = _l2(q) * d ** -0.5, _l2(k)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        _low_rank(a, p, "f").reshape(n, heads, d))
    beta = jax.nn.sigmoid(a @ p["b_proj"]["kernel"]) * s["kda_beta_scale"]

    state, outs, states = jnp.zeros((heads, d, d), jnp.float32), [], []
    for lo, hi in zip((0,) + tuple(states_at), tuple(states_at) + (n,)):
        if hi > lo:
            o, state = delta_rule(*(t[lo:hi] for t in (q, k, v, g, beta)),
                                  state)
            outs.append(o)
        if hi in states_at:
            states.append(state)
    o = _rms_norm(jnp.concatenate(outs), p["o_norm"]["scale"], s["eps"])
    y = (o.reshape(n, -1) * jax.nn.sigmoid(_low_rank(a, p, "g"))) @ p[
        "out_proj"]["kernel"]
    rows = [padded[at:at + taps - 1] for at in states_at]
    return (y, jnp.stack(states) if states else None,
            jnp.stack(rows) if rows else None)


def _attention(a, p, s):
    """The gated grouped attention of one layer on its normed input ``a``
    ``[n, h]``, position-free; also the keys and values ``[2, n, kv_heads x
    d]`` it would cache."""
    heads, kv_heads = s["heads"], s["kv_heads"]
    if "qkv_proj" in p:
        qkv = jnp.einsum("se,ehd->shd", a, p["qkv_proj"]["kernel"])
        q, k, v = jnp.split(qkv, (heads, heads + kv_heads), axis=1)
    else:
        q, k, v = (jnp.einsum("se,ehd->shd", a, p[n]["kernel"])
                   for n in ("q_proj", "k_proj", "v_proj"))
    n, d = q.shape[0], q.shape[-1]
    q = q.reshape(n, kv_heads, heads // kv_heads, d)
    q_block = min(s["q_block"], n)
    blocks = -(-n // q_block)
    q = jnp.pad(q, ((0, blocks * q_block - n), (0, 0), (0, 0), (0, 0)))
    k_pos = jnp.arange(n)

    def block(start):
        mine = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=0)
        seen = k_pos[None, :] <= (start + jnp.arange(q_block))[:, None]
        scores = jnp.einsum("qkgd,tkd->kgqt", mine, k) / jnp.sqrt(
            jnp.float32(d))
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("kgqt,tkd->qkgd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(block, jnp.arange(blocks) * q_block)
    out = out.reshape(blocks * q_block, heads, d)[:n]
    if "gate_proj" in p:
        out = out * jax.nn.sigmoid(
            jnp.einsum("se,ehd->shd", a, p["gate_proj"]["kernel"]))
    return (jnp.einsum("shd,hde->se", out, p["out_proj"]["kernel"]),
            jnp.stack([k.reshape(n, -1), v.reshape(n, -1)]))


def _experts(b, moe, layer, s, given=None):
    """``(sum, chosen, scores, ranked)`` of expert layer ``layer`` (its place
    in ``moe``, the experts' stack) on ``b`` ``[n, h]``: the weighted sum
    over each token's chosen experts THAT ARE HELD plus the shared expert;
    the ``top_k`` chosen ``[n, k]`` (routed numbers; ties to the lower
    index); the sigmoid scores ``[n, E]`` and what the choice ranks, the
    scores plus the selection bias. ``given`` ``[m, k]`` names the experts to
    sum over at the LAST ``m`` positions in the router's place (the choice
    returned stays the router's)."""
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


def logits(params, tokens, *, settings: dict, tail: int = 0,
           with_parts: bool = False, given=None, states_at=()):
    """Float32 logits of ``tokens`` ``[s]`` (positions 0..s-1) under
    ``params`` (the ``params`` tree of the served model), at the last
    ``tail`` positions (0: at all). With ``with_parts`` a dictionary:
    ``logits``; ``chosen`` ``[expert layers, s, k]`` and ``ranked`` ``[expert
    layers, s, E]`` (the router's choice and what it ranks); ``kv`` ``[GQA
    layers, 2, tail, kv_heads x d]``, what each GQA layer would cache at the
    last ``tail`` positions; ``state`` ``[KDA layers, len(states_at), H, d,
    d]`` and ``rows`` ``[KDA layers, len(states_at), taps - 1, 3 H d]``, each
    KDA layer's matrix state and filter rows after ``states_at`` tokens.

    ``given`` ``[expert layers, m, k]`` names the experts to sum over at the
    LAST ``m`` positions in place of the router's own choice (the choice
    returned stays the router's): where two experts' ranks lie closer than
    the rounding of the layers before, a system in bfloat16 takes the other
    one, rightly, and an expert exchanged moves the logits by more than any
    rounding does."""
    s = settings
    params = _unboxed(params)
    gpt = params["gpt"]
    kinds, eps = gpt["layers"], s["eps"]
    states_at = tuple(int(at) for at in states_at)
    places = {"attention": 0, "kda": 0}
    parts = {"chosen": [], "ranked": [], "kv": [], "state": [], "rows": []}
    with jax.default_matmul_precision("highest"):
        x = _f32(gpt["word_embeddings"])[jnp.asarray(tokens)]
        for l, kind_name in enumerate(s["layer_types"]):
            name = "attention" if kind_name.endswith("attention") else "kda"
            kind, at = kinds[name], places[name]
            places[name] += 1
            p = jax.tree.map(lambda leaf, at=at: _f32(leaf[at]), kind["op"])
            a = _rms_norm(x, _f32(kind["norm"]["scale"][at]), eps)
            if name == "attention":
                y, kv = _attention(a, p, s)
                parts["kv"].append(kv[:, -tail:])
            else:
                y, state, rows = _kda(a, p, s, states_at)
                parts["state"].append(state)
                parts["rows"].append(rows)
            x = x + y
            kind = kinds["experts"]
            b = _rms_norm(x, _f32(kind["norm"]["scale"][l]), eps)
            m, picked, _, ranked = _experts(
                b, kind["op"], l, s,
                None if given is None else jnp.asarray(given[l], jnp.int32))
            parts["chosen"].append(picked)
            parts["ranked"].append(ranked)
            x = x + m
        x = _rms_norm(x[-tail:], _f32(gpt["final_norm"]["scale"]), eps)
        out = jnp.einsum("se,ve->sv", x, _f32(params["lm_head"]))
    if not with_parts:
        return out
    return {"logits": out, **{
        k: jnp.stack(v) for k, v in parts.items()
        if v and v[0] is not None}}


def expert_layers(params, inputs, chosen, *, settings: dict):
    """EVERY expert layer alone, each on an input of its own: ``inputs``
    ``[layers, s, h]`` what its router and experts read, ``chosen``
    ``[layers, s, k]`` the experts to sum over (those of them that are
    held; the shared expert is added). Returns ``(sums, scores, ranked)``:
    ``[layers, s, h]``; the router's sigmoid scores ``[layers, s, E]``; and
    the scores plus the selection bias, which decide the choice."""
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


def _settings(model: dict, q_block: int = 256) -> dict:
    return dict(
        layer_types=tuple(model["layer_types"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model.get("num_key_value_heads")
                     or model["num_attention_heads"]),
        kda_heads=int(model["kda_num_heads"]),
        kda_dim=int(model["kda_head_dim"]),
        kda_taps=int(model.get("kda_conv_size", 4)),
        kda_beta_scale=2.0 if model.get("kda_neg_eigval") else 1.0,
        eps=float(model.get("norm_eps", 1e-5)),
        top_k=int(model["top_k"]),
        norm_topk_prob=bool(model.get("norm_topk_prob", False)),
        scaling=float(model.get("routed_scaling_factor", 1.0)),
        first=int(model.get("first_expert_held", 0)), q_block=q_block)


def configured(model: dict):
    """:func:`logits` with the settings of a configuration file's ``model``
    group (in ``GPTConfig``'s names)."""
    return functools.partial(logits, settings=_settings(model))


def configured_layers(model: dict):
    """:func:`expert_layers` with a configuration's routing settings."""
    return functools.partial(expert_layers, settings=_settings(model))
