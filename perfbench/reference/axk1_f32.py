"""Plain float32 A.X-K1: the reference the benchmark holds the system to for
``skt/A.X-K1`` (latent attention, one leading dense layer, then expert
layers with a group-limited sigmoid router, routed experts and one shared
expert), GIVEN THE SAME SHARE as the program: the experts ``[first, first
+ held)`` of the routed ones, the shared expert, a slice of the vocabulary.

Straightforward ``jax.numpy`` after the published configuration
(``config.json``: the catalog's row), every product under
``default_matmul_precision("highest")``, whole-sequence, MATERIALISED form
only: no cache, no page, no kernel, no absorbed product, no sort and no
grouping of tokens. A layer ``l``, on ``x`` ``[s, h]`` (positions 0..s-1)::

    a   = rmsnorm_op(x)
    c_q = rmsnorm(a @ W_qa);  q = c_q @ W_qb            # heads x (nope + rope)
    [c_kv | k_r] = a @ W_kva;  c_kv = rmsnorm(c_kv)
    q_r, k_r = rope(q_r), rope(k_r)                     # ONE k_r for all heads
    [k_nope | v] = c_kv @ W_kvb                         # heads x (nope + v)
    scores = (q_nope . k_nope + q_r . k_r) * scale, causal; softmax
    x'  = x + concat(P v) @ W_o
    m   = rmsnorm_ffn(x')
    if l < num_dense_layers:  y = (silu(m @ W_1) * (m @ W_3)) @ W_2
    else:
        s = sigmoid(m @ W_g)                            # ALL routed experts
        group score = sum of the 2 highest s in each of n_group groups
        S = the top_k largest s inside the topk_group best groups
        p_e = s_e / (sum over S of s + 1e-20) * routed_scaling_factor
        y = sum over e in S AND HELD of p_e * expert_e(m)  +  shared(m)
    out = x' + y

then a final RMSNorm and the head (``lm_head``, untied). ``scale = (nope +
rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; the
rotary frequencies are YaRN's (written out in :func:`_yarn_frequencies`,
not imported from the program). ``rmsnorm(x) = x / sqrt(mean(x^2) + eps) *
weight``.

Readings the published configuration does not settle, which the program and
this file take alike (the configuration file's ``assumed``): ``topk_method:
"none"`` beside ``n_group`` / ``topk_group`` is the family's group-limited
choice WITHOUT a selection bias; the rotary part's pair layout is the two
halves of the 64 columns (the family's port de-interleaves its pairs into
exactly that).

Departures from the published implementation, each deliberate:

- float32 throughout, where the published checkpoint computes in bfloat16:
  that is what makes it the reference.
- THE SHARE: what the experts outside ``[first, first + held)`` would add is
  left out, as in the program, and the vocabulary is the slice. ``uncut``
  mode (every routed expert held: ``first`` 0, ``held`` = routed) is the
  whole layer; tests/test_axk1_serving.py adds the shares up to it.
- the held experts run over ALL tokens and are masked by their weight: the
  same sum, no routing code to trust.
- attention a block of ``q_block`` queries at a time against all keys, the
  dense layer ``dense_block`` columns of its width at a time, a held expert
  at a time, and on the chip ONE LAYER A PROGRAM (each layer's function is
  jitted by itself and called in turn on the kind's stack and the layer's
  place in it, the weights it needs upcast inside), so that it fits beside
  a serving engine that holds 13 GB.
- one sequence at a time (``tokens`` ``[s]``).
- it reads the system's parameter tree: under ``gpt/layers`` the kinds
  ``attention``, ``dense``, ``experts``, each ``{"norm", "op"}`` with the
  kind's layers stacked on a leading axis.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["configured", "configured_layers", "expert_layers", "logits"]


def _unboxed(tree):
    return jax.tree.map(lambda x: x.unbox() if hasattr(x, "unbox") else x,
                        tree, is_leaf=lambda x: hasattr(x, "unbox"))


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * weight


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_frequencies(s):
    """YaRN: frequency ``i`` of ``theta^(-2i/d)`` is divided by ``factor``
    where the dimension turns fewer than ``beta_slow`` times over the
    original context, kept where it turns more than ``beta_fast`` times,
    and blended linearly between."""
    d, base, factor = s["rope"], s["theta"], s["factor"]
    kept = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if factor <= 1:
        return kept

    def dim_of(turns):
        return (d * math.log(s["original"] / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(dim_of(s["beta_fast"])), 0)
    high = min(math.ceil(dim_of(s["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return kept / factor * ramp + kept * (1 - ramp)


def _rope(x, s):
    """``x`` ``[positions, ..., d]`` at positions 0..: halves ``(x1, x2)``
    become ``(x1 cos - x2 sin, x2 cos + x1 sin)``, scaled by ``mscale /
    mscale_all_dim``."""
    n, d = x.shape[0], x.shape[-1]
    angle = (np.arange(n, dtype=np.float64)[:, None]
             * _yarn_frequencies(s)[None, :])
    by = (_mscale(s["factor"], s["mscale"])
          / _mscale(s["factor"], s["mscale_all_dim"]))
    shape = (n,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(angle) * by, jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(angle) * by, jnp.float32).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, p, s):
    nope, rope, eps = s["nope"], s["rope"], s["eps"]
    c = p["kv_a_norm"]["scale"].shape[0]
    q = jnp.einsum("sr,rhd->shd", _rms_norm(
        a @ p["q_a_proj"], p["q_a_norm"]["scale"], eps), p["q_b_proj"])
    latent = a @ p["kv_a_proj"]
    ckv = _rms_norm(latent[:, :c], p["kv_a_norm"]["scale"], eps)
    k_r = _rope(latent[:, c:], s)
    q_nope, q_r = q[..., :nope], _rope(q[..., nope:], s)
    kv = jnp.einsum("tc,chd->thd", ckv, p["kv_b_proj"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    m = _mscale(s["factor"], s["mscale_all_dim"])
    scale = (nope + rope) ** -0.5 * m * m
    n = a.shape[0]
    q_block = min(s["q_block"], n)
    blocks = -(-n // q_block)
    pad = blocks * q_block - n
    q_nope, q_r = (jnp.pad(t, ((0, pad), (0, 0), (0, 0)))
                   for t in (q_nope, q_r))
    k_pos = jnp.arange(n)

    def block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, q_block)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, q_block)
        scores = (jnp.einsum("shd,thd->hst", qn, k_nope)
                  + jnp.einsum("shd,td->hst", qr, k_r)) * scale
        seen = k_pos[None, :] <= (start + jnp.arange(q_block))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30), -1)
        return jnp.einsum("hst,thv->shv", probs, v)

    out = jax.lax.map(block, jnp.arange(blocks) * q_block)
    out = out.reshape(blocks * q_block, *out.shape[2:])[:n]
    # beside the output, what a cache of this layer would hold of the tokens
    return (jnp.einsum("shv,hvd->sd", out, p["out_proj"]),
            jnp.concatenate([ckv, k_r], axis=-1))


def _dense(m, p, layer, block):
    """The dense layer's gated MLP, ``block`` columns of its width at a
    time (the same sum; on the chip a block's float32 weights are 176 MB
    where the whole layer's would be 1.6 GB beside a full engine). ``p``
    holds the dense layers' stack, as handed over."""
    gate, up, down = (p[name]["kernel"] for name in (
        "gate_proj", "up_proj", "down_proj"))
    h, f = gate.shape[1:]
    block = min(block, f)
    if f % block:
        raise ValueError(f"dense width {f} in blocks of {block}")

    def one(total, start):
        g, u = (jax.lax.dynamic_slice(w, (layer, 0, start), (1, h, block))[
            0].astype(jnp.float32) for w in (gate, up))
        d = jax.lax.dynamic_slice(down, (layer, start, 0), (1, block, h))[
            0].astype(jnp.float32)
        return total + (jax.nn.silu(m @ g) * (m @ u)) @ d, None

    return jax.lax.scan(one, jnp.zeros_like(m),
                        jnp.arange(f // block) * block)[0]


def _route(scores, s):
    """``(chosen [n, k], ranked [n, E])``: the scores inside the groups that
    stay (``-inf`` outside), and the ``top_k`` largest of them."""
    n, experts = scores.shape
    groups = s["n_group"]
    ranked = scores
    if groups > 1:
        per = scores.reshape(n, groups, experts // groups)
        group_score = jnp.sort(per, -1)[..., -2:].sum(-1)
        kept = jax.lax.top_k(group_score, s["topk_group"])[1]
        stays = jax.nn.one_hot(kept, groups).sum(-2) > 0
        ranked = jnp.where(jnp.repeat(stays, experts // groups, -1), scores,
                           -jnp.inf)
    return jax.lax.top_k(ranked, s["top_k"])[1], ranked


def _experts(m, moe, layer, s, given=None):
    """``(sum, chosen, scores, ranked)`` of expert layer ``layer`` (its place
    in ``moe``, the experts' stack) on ``m`` ``[n, h]``: the weighted sum
    over each token's chosen experts THAT ARE HELD plus the shared expert;
    the ``top_k`` chosen ``[n, k]`` (routed numbers); the sigmoid scores and
    the scores inside the groups that stay ``[n, E]``. ``given`` ``[m, k]``
    names the experts to sum over at the LAST ``m`` positions in the
    router's place (the choice returned stays the router's)."""
    first, held = s["first"], moe["w_gate"].shape[1]
    scores = jax.nn.sigmoid(
        m @ jnp.asarray(moe["router"]["kernel"][layer], jnp.float32))
    chosen, ranked = _route(scores, s)
    summed = chosen
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
        return jax.lax.dynamic_slice(
            stack, (layer, e, 0, 0), (1, 1, *stack.shape[2:]))[0, 0].astype(
                jnp.float32)

    def one(total, e):  # every token through held expert e, then weighted
        out = (jax.nn.silu(m @ matrix("w_gate", e))
               * (m @ matrix("w_up", e))) @ matrix("w_down", e)
        return total + jax.lax.dynamic_slice_in_dim(
            dense, first + e, 1, axis=1) * out, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(held))
    if "shared_gate" in moe:
        gate, up, down = (jnp.asarray(moe[name][layer], jnp.float32)
                          for name in ("shared_gate", "shared_up",
                                       "shared_down"))
        total = total + (jax.nn.silu(m @ gate) * (m @ up)) @ down
    return total, chosen, scores, ranked


def _layer_of(stack, index):
    return jax.tree.map(lambda leaf: leaf[index], stack)


def _key(s: dict):
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("key",))
def _attention_layer(x, kind, layer, key):
    s = dict(key)
    with jax.default_matmul_precision("highest"):
        p = _f32(_layer_of(kind, layer))
        out, latents = _attention(
            _rms_norm(x, p["norm"]["scale"], s["eps"]), p["op"], s)
        return x + out, latents


@functools.partial(jax.jit, static_argnames=("key",))
def _dense_layer(x, kind, layer, key):
    s = dict(key)
    with jax.default_matmul_precision("highest"):
        m = _rms_norm(x, jnp.asarray(kind["norm"]["scale"][layer],
                                     jnp.float32), s["eps"])
        return x + _dense(m, kind["op"], layer, s["dense_block"])


@functools.partial(jax.jit, static_argnames=("key",))
def _expert_layer(x, kind, layer, given, key):
    s = dict(key)
    with jax.default_matmul_precision("highest"):
        m = _rms_norm(x, jnp.asarray(kind["norm"]["scale"][layer],
                                     jnp.float32), s["eps"])
        out, chosen, scores, _ = _experts(m, kind["op"], layer, s, given)
        return x + out, chosen, scores


@functools.partial(jax.jit, static_argnames=("eps", "tail"))
def _head(x, norm, head, eps, tail):
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x[-tail:], jnp.asarray(norm, jnp.float32), eps)
        return jnp.einsum("se,ve->sv", x, jnp.asarray(head, jnp.float32))


def logits(params, tokens, *, settings: dict, tail: int = 0,
           with_experts: bool = False, given=None,
           with_latents: bool = False):
    """Float32 logits of ``tokens`` ``[s]`` (positions 0..s-1) under
    ``params`` (the ``params`` tree of the served model), at the last
    ``tail`` positions (0: at all); with ``with_experts`` also, per expert
    layer, the experts the router chose ``[layers, s, k]`` and its sigmoid
    scores ``[layers, s, E]``. ``given`` ``[expert layers, m, k]`` names the
    experts to sum over at the LAST ``m`` positions in place of the router's
    own choice (the choice returned stays the router's): with random weights
    the eighth and ninth scores lie a rounding apart, so the bfloat16 of the
    layers before hands an expert over at many positions, and an expert
    exchanged moves the logits by more than any arithmetic does; with the
    system's choice given at the positions compared, what is left is the
    arithmetic (the choice itself is :func:`expert_layers`' to hold). With
    ``with_latents`` also what every layer's cache would hold of the last
    ``tail`` tokens, ``[layers, tail, c_kv + k_r]``. Each layer is a program
    of its own."""
    params = _unboxed(params)
    gpt, key = params["gpt"], _key(settings)
    kinds = gpt["layers"]
    x = jnp.asarray(gpt["word_embeddings"], jnp.float32)[jnp.asarray(tokens)]
    chosen, scores, latents = [], [], []
    for l in range(settings["layers"]):
        x, held = _attention_layer(x, kinds["attention"], l, key)
        latents.append(held[-(tail or held.shape[0]):])
        if l < settings["num_dense"]:
            x = _dense_layer(x, kinds["dense"], l, key)
        else:
            at = l - settings["num_dense"]
            x, picked, score = _expert_layer(
                x, kinds["experts"], at,
                None if given is None else jnp.asarray(given[at], jnp.int32),
                key)
            chosen.append(picked)
            scores.append(score)
    head = params["lm_head"] if "lm_head" in params else gpt["word_embeddings"]
    out = _head(x, gpt["final_norm"]["scale"], head, settings["eps"],
                tail or x.shape[0])
    out = (out, jnp.stack(chosen), jnp.stack(scores)) if with_experts else out
    if with_latents:  # [layers, tail, c_kv + k_r]: the normed latent, the
        # rotated key, as a cache would hold them
        return (*out, jnp.stack(latents)) if with_experts else (
            out, jnp.stack(latents))
    return out


@functools.partial(jax.jit, static_argnames=("key",))
def _layers_alone(moe, inputs, chosen, key):
    s = dict(key)
    with jax.default_matmul_precision("highest"):
        def layer(_, each):
            index, m, picked = each
            total, _, scores, ranked = _experts(
                jnp.asarray(m, jnp.float32), moe, index, s, picked)
            return None, (total, scores, ranked)

        return jax.lax.scan(layer, None, (
            jnp.arange(inputs.shape[0]), inputs, chosen))[1]


def expert_layers(params, inputs, chosen, *, settings: dict):
    """EVERY expert layer alone, each on an input of its own: ``inputs``
    ``[layers, s, h]`` what its router and experts read, ``chosen``
    ``[layers, s, k]`` the experts to sum over (those of them that are
    held; the shared expert is added). Returns ``(sums, scores, ranked)``:
    ``[layers, s, h]``; the router's sigmoid scores ``[layers, s, E]``; and
    the scores inside the groups that stay (``-inf`` outside), which decide
    the choice. The benchmark holds the system's layer to it on the input
    that layer really saw."""
    moe = _unboxed(params)["gpt"]["layers"]["experts"]["op"]
    return _layers_alone(moe, jnp.asarray(inputs), jnp.asarray(
        chosen, jnp.int32), _key(settings))


def _settings(model: dict, q_block: int = 256,
              dense_block: int = 2048) -> dict:
    return dict(
        layers=int(model["num_layers"]),
        num_dense=int(model.get("num_dense_layers", 0)),
        nope=int(model["qk_nope_head_dim"]), rope=int(model["qk_rope_head_dim"]),
        theta=float(model.get("rope_theta", 10000.0)),
        factor=float(model.get("rope_scaling_factor", 1.0)),
        beta_fast=float(model.get("rope_scaling_beta_fast", 32.0)),
        beta_slow=float(model.get("rope_scaling_beta_slow", 1.0)),
        mscale=float(model.get("rope_scaling_mscale", 1.0)),
        mscale_all_dim=float(model.get("rope_scaling_mscale_all_dim", 0.0)),
        original=int(model.get("rope_scaling_original_max_position", 4096)),
        eps=float(model.get("norm_eps", 1e-5)), top_k=int(model["top_k"]),
        n_group=int(model.get("n_group", 1)),
        topk_group=int(model.get("topk_group", 1)),
        norm_topk_prob=bool(model.get("norm_topk_prob", False)),
        scaling=float(model.get("routed_scaling_factor", 1.0)),
        first=int(model.get("first_expert_held", 0)), q_block=q_block,
        dense_block=dense_block)


def configured(model: dict):
    """:func:`logits` with the settings of a configuration file's ``model``
    group (in ``GPTConfig``'s names). NOT to be wrapped in ``jax.jit``: it
    runs a layer a program."""
    return functools.partial(logits, settings=_settings(model))


def configured_layers(model: dict):
    """:func:`expert_layers` with a configuration's routing settings."""
    return functools.partial(expert_layers, settings=_settings(model))
