"""Plain float32 LongCat-Flash (the language model of ``meituan-longcat/
LongCat-Flash-Omni``): the reference the benchmark holds the system to for
shortcut-connected double layers (two latent attentions, two dense MLPs and
ONE expert layer whose input is the first half's normed stream and whose
output joins the stream after the second half's MLP), a softmax router over
routed AND zero-compute experts with a selection bias, and latent attention
whose queries and compressed keys/values are scaled after their low-rank
norms; GIVEN THE SAME SHARE as the program: the experts ``[first, first +
held)`` of the routed ones, every zero-compute expert, a slice of the
vocabulary.

Straightforward ``jax.numpy`` after the published configuration
(``config.json``: the catalog's row) and the family's technical report
(arXiv:2509.01322), every product under ``default_matmul_precision
("highest")``, whole-sequence, MATERIALISED form only: no cache, no page, no
kernel, no absorbed product, no sort and no grouping of tokens. With ``h``
the hidden size, ``s_q = sqrt(h / q_lora_rank)`` and ``s_kv = sqrt(h /
kv_lora_rank)`` where the configuration's two flags are set (else 1)::

    MLA_j(a):  c_q = rmsnorm(a @ W_qa);  q = (c_q @ W_qb) * s_q   # heads x (nope + rope)
               [c | k_r] = a @ W_kva;    c_kv = rmsnorm(c) * s_kv # k_r is NOT scaled
               q_r, k_r = rope(q_r), rope(k_r)                    # ONE k_r for all heads
               [k_nope | v] = c_kv @ W_kvb                        # heads x (nope + v)
               scores = (q_nope . k_nope + q_r . k_r) * (nope + rope)^-0.5, causal; softmax
               MLA = concat(P v) @ W_o

    double layer l, stream x:
      x = x + MLA_0(rmsnorm_in0(x))
      b = rmsnorm_post0(x)
      z = MoE(b)                          # the shortcut LEAVES here
      x = x + FFN_0(b)                    # gated SiLU, the dense width
      x = x + MLA_1(rmsnorm_in1(x))
      x = x + FFN_1(rmsnorm_post1(x)) + z # the shortcut LANDS here

    MoE(b):  s = softmax(b @ W_r)         # ALL routed + zero-compute experts
             C = the top_k largest of (s + bias)       # bias: the CHOICE only
             w_e = routed_scaling_factor * s_e         # NOT renormalised
             z = sum over e in C, routed AND HELD of w_e * expert_e(b)
                 + (sum over e in C, zero-compute of w_e) * b

then a final RMSNorm and the head (``lm_head``, untied). ``rmsnorm(x) = x /
sqrt(mean(x^2) + eps) * weight``; the rotary frequencies are ``theta^(-2i /
d)`` (no scaling block).

Readings the published configuration does not settle, which the program and
this file take alike (the configuration file's ``assumed``): the weights are
not renormalised and the router has no additive output bias; the two scales
are ``sqrt(hidden / rank)`` applied after the low-rank norms; the shortcut's
two ends are as above; the rotary pair layout is the two halves; a tie in
the choice goes to the lower index.

Departures from the published implementation, each deliberate:

- float32 throughout, where the published checkpoint computes in bfloat16:
  that is what makes it the reference.
- THE LANGUAGE MODEL ALONE: the audio and vision encoders and the codec
  decoder are not served and not here.
- THE SHARE: what the routed experts outside ``[first, first + held)`` would
  add is left out, as in the program, and the vocabulary is the slice. The
  zero-compute experts need no weights and no exchange, so every share
  computes them for its own tokens. ``uncut`` (every routed expert held) is
  the whole layer; tests/test_longcat_serving.py adds the shares up to it
  with the zero-compute experts counted once.
- the held experts run over ALL tokens and are masked by their weight: the
  same sum, no routing code to trust.
- attention a block of ``q_block`` queries at a time against all keys, a
  dense MLP ``dense_block`` columns of its width at a time, a held expert at
  a time, and on the chip ONE PART A PROGRAM (each half's attention, each
  dense MLP and each expert layer is jitted by itself and called in turn on
  the kind's stack and its place in it, the weights it needs upcast
  inside), so that it fits beside a serving engine that holds 13 GB.
- one sequence at a time (``tokens`` ``[s]``).
- it reads the system's parameter tree: under ``gpt/layers`` the kinds
  ``attention``, ``dense`` (``{"norm", "op"}``, the kind's ``2N`` halves
  stacked on a leading axis) and ``experts`` (``{"op"}``, ``N`` entries: it
  has no norm of its own).
"""

from __future__ import annotations

import functools

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


def _rope(x, s):
    """``x`` ``[positions, ..., d]`` at positions 0..: halves ``(x1, x2)``
    become ``(x1 cos - x2 sin, x2 cos + x1 sin)`` at the angles ``position x
    theta^(-2i / d)``."""
    n, d = x.shape[0], x.shape[-1]
    angle = (np.arange(n, dtype=np.float64)[:, None]
             * s["theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d)[None])
    shape = (n,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(angle), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(angle), jnp.float32).reshape(shape)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, p, s):
    nope, rope, eps = s["nope"], s["rope"], s["eps"]
    c = p["kv_a_norm"]["scale"].shape[0]
    q = jnp.einsum("sr,rhd->shd", _rms_norm(
        a @ p["q_a_proj"], p["q_a_norm"]["scale"], eps),
        p["q_b_proj"]) * s["s_q"]
    latent = a @ p["kv_a_proj"]
    ckv = _rms_norm(latent[:, :c], p["kv_a_norm"]["scale"], eps) * s["s_kv"]
    k_r = _rope(latent[:, c:], s)
    q_nope, q_r = q[..., :nope], _rope(q[..., nope:], s)
    kv = jnp.einsum("tc,chd->thd", ckv, p["kv_b_proj"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + rope) ** -0.5
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
    # beside the output, what a cache of this half would hold of the tokens
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


def _experts(m, moe, layer, s, given=None):
    """``(sum, chosen, scores, ranked)`` of expert layer ``layer`` (its place
    in ``moe``, the experts' stack) on ``m`` ``[n, h]``: the weighted sum
    over each token's chosen ROUTED experts THAT ARE HELD plus its chosen
    zero-compute experts' weights times ``m``; the ``top_k`` chosen ``[n,
    k]`` (the router's numbers: routed first, then zero-compute); the
    softmax scores and the scores under the selection bias ``[n, routed +
    zero]``, which decide the choice. ``given`` ``[m, k]`` names the experts
    to sum over at the LAST ``m`` positions in the router's place (the
    choice returned stays the router's)."""
    first, held = s["first"], moe["w_gate"].shape[1]
    scores = jax.nn.softmax(
        m @ jnp.asarray(moe["router"]["kernel"][layer], jnp.float32), -1)
    ranked = scores
    if "expert_bias" in moe:
        ranked = scores + jnp.asarray(moe["expert_bias"][layer], jnp.float32)
    chosen = jax.lax.top_k(ranked, s["top_k"])[1]   # (a tie: the lower index)
    summed = chosen
    if given is not None and given.shape[0]:
        summed = summed.at[-given.shape[0]:].set(given)
    weight = jnp.take_along_axis(scores, summed, axis=-1)
    if s["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    weight = weight * s["scaling"]
    dense = (jax.nn.one_hot(summed, scores.shape[-1])
             * weight[..., None]).sum(-2)                 # [n, routed + zero]

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
    # a zero-compute expert returns its input: identity, weighed
    total = total + dense[:, s["routed"]:].sum(-1, keepdims=True) * m
    return total, chosen, scores, ranked


def _layer_of(stack, index):
    return jax.tree.map(lambda leaf: leaf[index], stack)


def _key(s: dict):
    return tuple(sorted(s.items()))


@functools.partial(jax.jit, static_argnames=("key",))
def _attention_half(x, kind, half, key):
    s = dict(key)
    with jax.default_matmul_precision("highest"):
        p = _f32(_layer_of(kind, half))
        out, latents = _attention(
            _rms_norm(x, p["norm"]["scale"], s["eps"]), p["op"], s)
        return x + out, latents


@functools.partial(jax.jit, static_argnames=("key",))
def _dense_half(x, kind, half, landing, key):
    """``x + FFN_half(rmsnorm_post(x)) + landing`` (the shortcut, or zeros)."""
    s = dict(key)
    with jax.default_matmul_precision("highest"):
        m = _rms_norm(x, jnp.asarray(kind["norm"]["scale"][half],
                                     jnp.float32), s["eps"])
        return x + _dense(m, kind["op"], half, s["dense_block"]) + landing


@functools.partial(jax.jit, static_argnames=("key",))
def _expert_layer(x, norm, kind, half, layer, given, key):
    """The shortcut that leaves at half ``half``: the expert layer ``layer``
    on the dense MLP's normed input."""
    s = dict(key)
    with jax.default_matmul_precision("highest"):
        m = _rms_norm(x, jnp.asarray(norm[half], jnp.float32), s["eps"])
        out, chosen, scores, _ = _experts(m, kind["op"], layer, s, given)
        return out, chosen, scores


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
    layer, the experts the router chose ``[layers, s, k]`` and its softmax
    scores ``[layers, s, routed + zero]``. ``given`` ``[expert layers, m,
    k]`` names the experts to sum over at the LAST ``m`` positions in place
    of the router's own choice (the choice returned stays the router's):
    with random weights the twelfth and thirteenth scores lie a rounding
    apart, so the bfloat16 of the layers before hands an expert over at many
    positions, and an expert exchanged moves the logits by more than any
    arithmetic does; with the system's choice given at the positions
    compared, what is left is the arithmetic (the choice itself is
    :func:`expert_layers`' to hold). With ``with_latents`` also what every
    half's cache would hold of the last ``tail`` tokens, ``[halves, tail,
    c_kv + k_r]``. Each part is a program of its own."""
    params = _unboxed(params)
    gpt, key = params["gpt"], _key(settings)
    kinds = gpt["layers"]
    x = jnp.asarray(gpt["word_embeddings"], jnp.float32)[jnp.asarray(tokens)]
    chosen, scores, latents = [], [], []
    for layer in range(settings["layers"] // 2):
        first, second = 2 * layer, 2 * layer + 1
        x, held = _attention_half(x, kinds["attention"], first, key)
        latents.append(held[-(tail or held.shape[0]):])
        z, picked, score = _expert_layer(
            x, kinds["dense"]["norm"]["scale"], kinds["experts"], first,
            layer,
            None if given is None else jnp.asarray(given[layer], jnp.int32),
            key)
        chosen.append(picked)
        scores.append(score)
        x = _dense_half(x, kinds["dense"], first, jnp.zeros_like(x), key)
        x, held = _attention_half(x, kinds["attention"], second, key)
        latents.append(held[-(tail or held.shape[0]):])
        x = _dense_half(x, kinds["dense"], second, z, key)
    head = params["lm_head"] if "lm_head" in params else gpt["word_embeddings"]
    out = _head(x, gpt["final_norm"]["scale"], head, settings["eps"],
                tail or x.shape[0])
    out = (out, jnp.stack(chosen), jnp.stack(scores)) if with_experts else out
    if with_latents:  # [halves, tail, c_kv + k_r]: the normed, scaled latent
        # and the rotated key, as a cache would hold them
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
    ``[layers, s, k]`` the experts to sum over (the routed ones of them that
    are held, and the zero-compute ones). Returns ``(sums, scores,
    ranked)``: ``[layers, s, h]``; the router's softmax scores ``[layers, s,
    routed + zero]``; and the scores under the selection bias, which decide
    the choice. The benchmark holds the system's layer to it on the input
    that layer really saw."""
    moe = _unboxed(params)["gpt"]["layers"]["experts"]["op"]
    return _layers_alone(moe, jnp.asarray(inputs), jnp.asarray(
        chosen, jnp.int32), _key(settings))


def _settings(model: dict, q_block: int = 256,
              dense_block: int = 2048) -> dict:
    hidden = float(model["hidden_size"])
    return dict(
        layers=int(model["num_layers"]),
        nope=int(model["qk_nope_head_dim"]), rope=int(model["qk_rope_head_dim"]),
        theta=float(model.get("rope_theta", 10000.0)),
        s_q=float(np.sqrt(hidden / model["q_lora_rank"]))
        if model.get("mla_scale_q_lora") else 1.0,
        s_kv=float(np.sqrt(hidden / model["kv_lora_rank"]))
        if model.get("mla_scale_kv_lora") else 1.0,
        eps=float(model.get("norm_eps", 1e-5)), top_k=int(model["top_k"]),
        norm_topk_prob=bool(model.get("norm_topk_prob", False)),
        scaling=float(model.get("routed_scaling_factor", 1.0)),
        routed=int(model.get("num_routed_experts") or model["num_experts"]),
        first=int(model.get("first_expert_held", 0)), q_block=q_block,
        dense_block=dense_block)


def configured(model: dict):
    """:func:`logits` with the settings of a configuration file's ``model``
    group (in ``GPTConfig``'s names). NOT to be wrapped in ``jax.jit``: it
    runs a part a program."""
    return functools.partial(logits, settings=_settings(model))


def configured_layers(model: dict):
    """:func:`expert_layers` with a configuration's routing settings."""
    return functools.partial(expert_layers, settings=_settings(model))
