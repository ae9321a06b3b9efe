"""Plain float32 Ling-3.0-flash (``model_type: bailing_hybrid``): the
reference the benchmark holds the system to for ``inclusionAI/Ling-3.0-flash``,
as ONE CHIP'S SHARE of a deployment of eight computes it (one router group
of the routed experts held; what the other seven groups' experts would add
is left out, here as in the program).

Straightforward ``jax.numpy`` after the published configuration
(``config.json``: the catalog's row), every product under
``default_matmul_precision("highest")``: no kernel, no cache, no page, no
batch; the delta rule is a recurrence over ROWS (``lax.scan`` a token), the
latent attention is materialised (every head's keys and values expanded from
the latents), and every held expert meets every token, weighed by that
token's routing weight for it (zero outside its eight).

``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``, ``eps`` 1e-6; pre-norm
residual blocks ``x += op(RMS(x)); x += ffn(RMS(x))``; ``layer_types[i]``
names layer ``i``'s operator (published: latent where ``(i + 1) % 6 == 0``,
else KDA); the first ``num_dense_layers`` take the dense gated MLP, the rest
the expert layer; final RMS, untied head::

    KDA(a); H heads of d; keys and values have as many heads as queries:
      [q~ | k~ | v~] = a W_qkv
      q', k', v = silu(filter(q~)), silu(filter(k~)), silu(filter(v~))
                    filter: causal, depthwise, ``taps`` taps, no bias
      q = q' / sqrt(sum q'^2 + 1e-6) * d^-0.5,  k = k' / sqrt(sum k'^2 + 1e-6)
      g = lower_bound * sigmoid(exp(A_log[head]) * (a W_f + dt_bias))  [H, d]
            W_f FULL [h, H d]; g in (lower_bound, 0)     (kda_safe_gate)
      beta = sigmoid(a W_b)                                          [H]
      per head, S [d, d] float32, S_0 = 0:
        S   = Diag(exp(g_t)) S_{t-1}
        S_t = S + beta_t k_t (v_t - S^T k_t)^T
                = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
      y = (RMS(o_t; w_o [d]) * sigmoid(a W_g)) W_o      W_g FULL [h, H d]
      no rotary.

    MLA(a); c = kv_lora_rank, nope, rope, v the head's widths; NO query latent:
      q_h = RMS((a W_q)_h; w_q [nope + rope]); its last ``rope`` columns rotated
      [c_kv | k_r] = a W_dkv;  c_kv = RMS(c_kv; w_c);  k_r = rot(RMS(k_r; w_r))
      [k_nope_h | v_h] = c_kv W_ukv
      o_h = softmax_causal(([q_nope_h | q_r_h] . [k_nope_h | k_r]) (nope + rope)^-0.5) v_h
      y = (o_h * sigmoid(a W_gate)_h) W_o               W_gate [h, heads]
      the cache of a token: [c_kv | k_r], c + rope values.

    MoE(b): s = sigmoid(b W_r) over ALL routed experts; r = s + bias;
      a group's mark the sum of its two largest r; the ``topk_group`` best
      groups stay; C = the ``top_k`` largest r inside them;
      w_e = s_e / (sum_C s + 1e-20) * routed_scaling_factor
      y = shared(b) + sum_{e in C, e held} w_e expert_e(b)   gated SiLU each

What the published configuration does not settle, and the program and this
file take alike, is the configuration file's ``assumed`` ((a)-(g): the safe
gate's form, where ``use_qk_norm`` lands in a latent layer, KDA position-
free, the head-wise gate the latent layers', SiLU after the filters, the
drawn ``A_log`` / ``dt_bias`` / bias).

Departures from the published implementation, each deliberate:

- float32 throughout, where the checkpoint computes in bfloat16: that is what
  makes it the reference;
- THE SHARE (above); the multi-token-prediction module is not computed (the
  main model's outputs are the same without it); the SwiGLU clamp is 0 in
  every layer kept and not written;
- the rotary pairs are the two HALVES of the 64 columns (column ``j`` with
  ``j + 32``), where the source interleaves them (``rope_interleave``: ``2j``
  with ``2j + 1``): a fixed permutation of ``W_q``'s and ``W_dkv``'s rotary
  columns and of the two norms' weights there, the same function of the
  permuted weights (the scores sum over the pairs);
- attention a block of ``q_block`` queries at a time and an expert at a
  time, so that a few thousand positions fit beside an engine that fills the
  chip; one sequence at a time (``tokens`` ``[s]``);
- it reads the system's parameter tree (under ``gpt/layers`` the kinds
  ``attention``, ``kda``, ``dense``, ``experts``, each ``{"norm", "op"}``
  with the kind's layers stacked on a leading axis; ``lm_head`` ``[vocab,
  hidden]``): that layout is all it takes from the program. The state is
  ``[heads, d_k, d_v]`` here; the program holds ``[d_k, heads, d_v]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["configured", "configured_layers", "delta_rule", "expert_layers",
           "logits", "route"]


def _plain(tree):
    return jax.tree.map(lambda x: x.unbox() if hasattr(x, "unbox") else x,
                        tree, is_leaf=lambda x: hasattr(x, "unbox"))


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _rms(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, state):
    """The gated delta rule, a row at a time: ``q, k, g`` ``[n, H, d_k]``,
    ``v`` ``[n, H, d_v]``, ``beta`` ``[n, H]`` from ``state`` ``[H, d_k,
    d_v]``; ``o`` ``[n, H, d_v]`` and the last state. The benchmark holds the
    system's two kernels to it alone, on the rows they really saw."""
    def row(held, each):
        q_t, k_t, v_t, g_t, b_t = each
        held = jnp.exp(g_t)[..., None] * held
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", held, k_t))
        held = held + k_t[..., None] * delta[:, None, :]
        return held, jnp.einsum("hkv,hk->hv", held, q_t)

    with jax.default_matmul_precision("highest"):
        state, o = jax.lax.scan(row, _f32(state),
                                tuple(_f32(t) for t in (q, k, v, g, beta)))
    return o, state


def _kda(a, p, s, states_at):
    """One KDA operator on its normed input ``a`` ``[n, h]`` (``p``: the
    layer's slice of the kda stack, float32): the output ``[n, h]``; the
    state ``[len(states_at), H, d, d]`` after each COUNT of tokens in
    ``states_at`` (ascending) and the filters' inputs at the ``taps - 1``
    positions before each ``[len(states_at), taps - 1, 3 H d]``."""
    heads, d, taps, n = s["kda_heads"], s["kda_dim"], s["kda_taps"], a.shape[0]
    streams = jnp.pad(a @ p["qkv_proj"]["kernel"], ((taps - 1, 0), (0, 0)))
    filtered = jax.nn.silu(sum(
        p["conv_kernel"][:, tap] * streams[tap:tap + n] for tap in range(taps)))
    q, k, v = (t.reshape(n, heads, d) for t in jnp.split(filtered, 3, -1))
    q, k = _unit(q) * d ** -0.5, _unit(k)
    decay = (a @ p["f_proj"]["kernel"] + p["f_proj"]["bias"]).reshape(
        n, heads, d)
    g = s["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None] * decay)
    beta = jax.nn.sigmoid(a @ p["b_proj"]["kernel"])
    state = jnp.zeros((heads, d, d), jnp.float32)
    outs, states = [], []
    edges = (0,) + tuple(states_at) + (n,)
    for lo, hi in zip(edges[:-1], edges[1:]):
        if hi > lo:
            o, state = delta_rule(*(t[lo:hi] for t in (q, k, v, g, beta)),
                                  state)
            outs.append(o)
        if hi in states_at:
            states.append(state)
    o = _rms(jnp.concatenate(outs), p["o_norm"]["scale"], s["eps"])
    gate = jax.nn.sigmoid(a @ p["g_proj"]["kernel"])
    rows = [streams[at:at + taps - 1] for at in states_at]
    return ((o.reshape(n, -1) * gate) @ p["out_proj"]["kernel"],
            jnp.stack(states) if states else None,
            jnp.stack(rows) if rows else None)


def _rotated(x, s):
    """``x`` ``[n, ..., rope]`` at positions 0..n-1: the halves ``(x1, x2)``
    become ``(x1 cos - x2 sin, x2 cos + x1 sin)``; frequency ``j`` is
    ``theta^(-2j / rope)``, no scaling."""
    n, d = x.shape[0], x.shape[-1]
    frequency = s["theta"] ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(n, dtype=jnp.float32)[:, None] * frequency[None, :]
    angle = angle.reshape((n,) + (1,) * (x.ndim - 2) + (d // 2,))
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
                            x2 * jnp.cos(angle) + x1 * jnp.sin(angle)], -1)


def _latent(a, p, s):
    """One latent attention operator on its normed input ``a`` ``[n, h]``:
    the output and what a cache would hold of every token ``[n, c + rope]``."""
    nope, rope, eps, n = s["nope"], s["rope"], s["eps"], a.shape[0]
    c = p["kv_a_norm"]["scale"].shape[0]
    q = _rms(jnp.einsum("sd,dhk->shk", a, p["q_proj"]),
             p["q_norm"]["scale"], eps)
    q_nope, q_r = q[..., :nope], _rotated(q[..., nope:], s)
    down = a @ p["kv_a_proj"]
    c_kv = _rms(down[:, :c], p["kv_a_norm"]["scale"], eps)
    k_r = _rotated(_rms(down[:, c:], p["k_rope_norm"]["scale"], eps), s)
    up = jnp.einsum("tc,chd->thd", c_kv, p["kv_b_proj"])
    k_nope, v = up[..., :nope], up[..., nope:]
    block = min(s["q_block"], n)
    blocks = -(-n // block)
    q_nope, q_r = (jnp.pad(t, ((0, blocks * block - n), (0, 0), (0, 0)))
                   for t in (q_nope, q_r))
    key_at = jnp.arange(n)

    def some(start):
        mine, mine_r = (jax.lax.dynamic_slice_in_dim(t, start, block)
                        for t in (q_nope, q_r))
        scores = (jnp.einsum("shd,thd->hst", mine, k_nope)
                  + jnp.einsum("shd,td->hst", mine_r, k_r)) * (
                      (nope + rope) ** -0.5)
        seen = key_at[None, :] <= (start + jnp.arange(block))[:, None]
        weights = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hst,thv->shv", weights, v)

    out = jax.lax.map(some, jnp.arange(blocks) * block)
    out = out.reshape(blocks * block, *out.shape[2:])[:n]
    out = out * jax.nn.sigmoid(a @ p["gate_proj"])[..., None]
    return (jnp.einsum("shv,hvd->sd", out, p["out_proj"]),
            jnp.concatenate([c_kv, k_r], -1))


def _dense(b, p, layer):
    gate, up, down = (_f32(p[name]["kernel"][layer]) for name in (
        "gate_proj", "up_proj", "down_proj"))
    return (jax.nn.silu(b @ gate) * (b @ up)) @ down


def route(scores, bias, s):
    """``(chosen [n, k], ranked [n, E])``: ``scores + bias`` inside the
    groups that stay (``-inf`` outside: a group's mark is the sum of its two
    largest, the ``topk_group`` best stay), and the ``top_k`` largest of
    them (ties to the lower number)."""
    ranked = scores if bias is None else scores + bias
    groups = s["n_group"]
    if groups > 1:
        n, experts = ranked.shape
        marks = jnp.sort(ranked.reshape(n, groups, -1), -1)[..., -2:].sum(-1)
        stays = jax.nn.one_hot(jax.lax.top_k(marks, s["topk_group"])[1],
                               groups).sum(-2) > 0
        ranked = jnp.where(jnp.repeat(stays, experts // groups, -1), ranked,
                           -jnp.inf)
    return jax.lax.top_k(ranked, s["top_k"])[1], ranked


def _experts(b, moe, layer, s, given=None):
    """``(sum, chosen, scores, ranked)`` of expert layer ``layer`` of the
    experts' stack ``moe`` on ``b`` ``[n, h]``: the weighted sum over each
    token's chosen experts THAT ARE HELD plus the shared expert; the router's
    choice ``[n, k]`` (routed numbers); the sigmoid scores ``[n, E]`` and
    what the choice ranks. ``given`` ``[m, k]`` names the experts to sum over
    at the LAST ``m`` positions in the router's place (the choice returned
    stays the router's)."""
    first, held = s["first"], moe["w_gate"].shape[1]
    scores = jax.nn.sigmoid(b @ _f32(moe["router"]["kernel"][layer]))
    bias = _f32(moe["expert_bias"][layer]) if "expert_bias" in moe else None
    chosen, ranked = route(scores, bias, s)
    summed = chosen
    if given is not None and given.shape[0]:
        summed = summed.at[-given.shape[0]:].set(given)
    weight = jnp.take_along_axis(scores, summed, -1)
    if s["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = weight * s["scaling"]
    by_expert = (jax.nn.one_hot(summed, scores.shape[-1])
                 * weight[..., None]).sum(-2)                  # [n, routed]

    def matrix(name, e):
        stack = moe[name]
        return _f32(jax.lax.dynamic_slice(
            stack, (layer, e, 0, 0), (1, 1, *stack.shape[2:]))[0, 0])

    def one(total, e):  # every token through held expert e, then weighed
        out = (jax.nn.silu(b @ matrix("w_gate", e))
               * (b @ matrix("w_up", e))) @ matrix("w_down", e)
        return total + jax.lax.dynamic_slice_in_dim(
            by_expert, first + e, 1, 1) * out, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(b), jnp.arange(held))
    if "shared_gate" in moe:
        gate, up, down = (_f32(moe[name][layer]) for name in (
            "shared_gate", "shared_up", "shared_down"))
        total = total + (jax.nn.silu(b @ gate) * (b @ up)) @ down
    return total, chosen, scores, ranked


def logits(params, tokens, *, settings: dict, tail: int = 0,
           with_parts: bool = False, given=None, states_at=()):
    """Float32 logits of ``tokens`` ``[s]`` (positions 0..s-1) under
    ``params`` (the served model's ``params`` tree) at the last ``tail``
    positions (0: at all). With ``with_parts`` a dictionary: ``logits``;
    ``chosen`` ``[expert layers, s, k]`` and ``ranked`` ``[expert layers, s,
    E]``; ``kv`` ``[latent layers, 1, tail, c + rope]``, what each latent
    layer would cache at the last ``tail`` positions; ``state`` ``[KDA
    layers, len(states_at), H, d, d]`` and ``rows`` ``[KDA layers,
    len(states_at), taps - 1, 3 H d]`` after ``states_at`` tokens.

    ``given`` ``[expert layers, m, k]`` names the experts to sum over at the
    LAST ``m`` positions in the router's place (the choice returned stays
    the router's): where two ranks lie closer than the rounding of the
    layers before, a system in bfloat16 rightly takes the other expert, and
    an expert exchanged moves the logits by more than any rounding does."""
    s = settings
    params = _plain(params)
    gpt = params["gpt"]
    kinds, eps = gpt["layers"], s["eps"]
    states_at = tuple(int(at) for at in states_at)
    place = {"attention": 0, "kda": 0}
    parts = {"chosen": [], "ranked": [], "kv": [], "state": [], "rows": []}
    with jax.default_matmul_precision("highest"):
        x = _f32(gpt["word_embeddings"])[jnp.asarray(tokens)]
        for layer, kind_name in enumerate(s["layer_types"]):
            name = "kda" if kind_name == "kda" else "attention"
            kind, at = kinds[name], place[name]
            place[name] += 1
            p = jax.tree.map(lambda leaf, at=at: _f32(leaf[at]), kind["op"])
            a = _rms(x, _f32(kind["norm"]["scale"][at]), eps)
            if name == "kda":
                y, state, rows = _kda(a, p, s, states_at)
                parts["state"].append(state)
                parts["rows"].append(rows)
            else:
                y, cached = _latent(a, p, s)
                parts["kv"].append(cached[None, -tail:])
            x = x + y
            if layer < s["num_dense"]:
                kind = kinds["dense"]
                b = _rms(x, _f32(kind["norm"]["scale"][layer]), eps)
                x = x + _dense(b, kind["op"], layer)
                continue
            kind, at = kinds["experts"], layer - s["num_dense"]
            b = _rms(x, _f32(kind["norm"]["scale"][at]), eps)
            y, picked, _, ranked = _experts(
                b, kind["op"], at, s,
                None if given is None else jnp.asarray(given[at], jnp.int32))
            parts["chosen"].append(picked)
            parts["ranked"].append(ranked)
            x = x + y
        x = _rms(x[-tail:], _f32(gpt["final_norm"]["scale"]), eps)
        out = jnp.einsum("se,ve->sv", x, _f32(params["lm_head"]))
    if not with_parts:
        return out
    return {"logits": out, **{k: jnp.stack(v) for k, v in parts.items()
                              if v and v[0] is not None}}


def expert_layers(params, inputs, chosen, *, settings: dict):
    """EVERY expert layer alone, each on an input of its own: ``inputs``
    ``[layers, s, h]`` what its router and experts read, ``chosen``
    ``[layers, s, k]`` the experts to sum over (those of them that are held;
    the shared expert is added). ``(sums, scores, ranked)``: ``[layers, s,
    h]``; the sigmoid scores ``[layers, s, E]``; what the choice ranks (the
    scores plus the bias inside the groups that stay, ``-inf`` outside)."""
    moe = _plain(params)["gpt"]["layers"]["experts"]["op"]

    @jax.jit
    def alone(moe, inputs, chosen):
        with jax.default_matmul_precision("highest"):
            def layer(_, each):
                index, b, picked = each
                total, _, scores, ranked = _experts(_f32(b), moe, index,
                                                    settings, picked)
                return None, (total, scores, ranked)

            return jax.lax.scan(layer, None, (
                jnp.arange(inputs.shape[0]), inputs, chosen))[1]

    return alone(moe, jnp.asarray(inputs), jnp.asarray(chosen, jnp.int32))


def _settings(model: dict, q_block: int = 256) -> dict:
    return dict(
        layer_types=tuple(model["layer_types"]),
        num_dense=int(model.get("num_dense_layers", 0)),
        kda_heads=int(model["kda_num_heads"]),
        kda_dim=int(model["kda_head_dim"]),
        kda_taps=int(model.get("kda_conv_size", 4)),
        kda_lower_bound=float(model["kda_lower_bound"]),
        nope=int(model["qk_nope_head_dim"]), rope=int(model["qk_rope_head_dim"]),
        theta=float(model["rope_theta"]),
        eps=float(model.get("norm_eps", 1e-6)),
        top_k=int(model["top_k"]), n_group=int(model.get("n_group", 1)),
        topk_group=int(model.get("topk_group", 1)),
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
