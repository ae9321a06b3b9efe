"""Plain float32 DeepSeek-V3.2: the reference the benchmark holds the system
to for ``deepseek-ai/DeepSeek-V3.2`` (latent attention UNDER A LEARNED
INDEXER, one leading dense layer, then expert layers with the group-limited
sigmoid router WITH its selection bias, routed experts and one shared
expert), GIVEN THE SAME SHARE as the program: the experts ``[first, first +
held)`` of the routed ones, the shared expert, a slice of the vocabulary.

Straightforward ``jax.numpy`` after the published configuration and the
published ``inference/model.py``, every product under
``default_matmul_precision("highest")``, whole-sequence, MATERIALISED form
only: no cache, no page, no kernel, no absorbed product, no gather. A layer,
on ``x`` ``[s, h]`` (positions 0..s-1)::

    a   = rmsnorm_op(x)
    c_q = rmsnorm(a @ W_qa);  q = c_q @ W_qb            # heads x (nope + rope)
    [c_kv | k_r] = a @ W_kva;  c_kv = rmsnorm(c_kv)
    q_r, k_r = rope(q_r), rope(k_r)                     # ONE k_r for all heads
    [k_nope | v] = c_kv @ W_kvb                         # heads x (nope + v)
    # the indexer: index_n_heads heads of index_head_dim, ONE key a token
    qI  = c_q @ W_Iq;          qI[:, :, :rope] = rope(qI[:, :, :rope])
    kI  = layernorm(a @ W_Ik); kI[:, :rope]    = rope(kI[:, :rope])
    w   = (a @ W_Iw) * heads_I^-0.5 * dim_I^-0.5
    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])   for s <= t
    S_t = the min(index_topk, t + 1) positions of the largest I[t, :t + 1]
          (a tie goes to the lower position)
    scores = (q_nope . k_nope + q_r . k_r) * scale over s in S_t ONLY; softmax
    x'  = x + concat(P v) @ W_o
    m   = rmsnorm_ffn(x')
    if l < num_dense_layers:  y = (silu(m @ W_1) * (m @ W_3)) @ W_2
    else:
        s = sigmoid(m @ W_g);  r = s + bias             # ALL routed experts
        group score = sum of the 2 highest r in each of n_group groups
        S = the top_k largest r inside the topk_group best groups
        p_e = s_e / (sum over S of s + 1e-20) * routed_scaling_factor
        y = sum over e in S AND HELD of p_e * expert_e(m)  +  shared(m)
    out = x' + y

then a final RMSNorm and the head. ``scale``, the YaRN frequencies, the
rotation's pair layout (the two halves) and ``rmsnorm`` are
``axk1_f32.py``'s, which this file imports (both are the benchmark's own and
neither reads the program); ``layernorm(x) = (x - mean) / sqrt(var + eps) *
weight + bias``.

Departures from the published implementation, each deliberate (the
configuration file's ``departures`` says the same):

- the published code rotates ``qI`` and ``kI`` by a Hadamard matrix and
  quantises them to FP8 before their product. The rotation is orthogonal and
  changes no score; this configuration states bfloat16 for the indexer as
  for everything else: both are left out, here and in the program.
- the multi-token-prediction module (``num_nextn_predict_layers`` 1) is not
  served: the main model's outputs are what they are with it.
- float32 throughout; THE SHARE (``axk1_f32.py`` has both).
- ``assumed``: the rotary pair layout of the indexer's rotated columns (the
  two halves, as for MLA's); a tie in the top-k goes to the lower position;
  the indexer's LayerNorm at ``norm_eps``.

Computed a block of ``q_block`` queries at a time against all keys (the
softmax besides ``head_block`` heads at a time), a layer a program, so that a document of several thousand tokens fits beside a
serving engine. For whoever compares it also returns ``I`` and ``S`` at the
last positions, the ``kI`` rows a cache would hold, and the experts chosen;
and it attends over GIVEN sets in the place of its own (``given_sets``),
as it sums over given experts: a discrete choice sits before the softmax,
and a set that differs by one row of two that score alike moves the logits
by more than any arithmetic does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference.axk1_f32 import (
    _dense_layer,
    _f32,
    _head,
    _key,
    _layer_of,
    _mscale,
    _rms_norm,
    _rope,
    _unboxed,
)

__all__ = ["configured", "configured_layers", "expert_layers", "logits",
           "select"]


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _part_rope(x, rot, s):
    """``x`` ``[positions, ..., d]`` with its first ``rot`` columns rotated."""
    return jnp.concatenate([_rope(x[..., :rot], s), x[..., rot:]], -1)


def select(scores, seen, k: int):
    """``S``: bool ``[q, t]``, the ``min(k, rows seen)`` largest of ``scores``
    among the rows ``seen``, a tie to the lower position (a stable sort)."""
    order = jnp.argsort(-jnp.where(seen, scores, -jnp.inf), axis=-1,
                        stable=True)
    return (jnp.argsort(order, axis=-1) < k) & seen


def _attention(a, p, s, sets, has):
    """The layer's attention output; the rows a cache would hold ``[c_kv |
    k_r | kI]``; ``I`` and ``S`` ``[s, s]``. ``sets`` ``[s, s]`` bool are
    attended over in ``S``'s place at the positions ``has`` ``[s]``."""
    nope, rope, eps = s["nope"], s["rope"], s["eps"]
    c = p["kv_a_norm"]["scale"].shape[0]
    # (``p`` as handed over: a matrix is made float32 where it is used, so
    # that the layer's 800 MB of float32 weights never stand side by side)
    p = {k: _f32(v) if isinstance(v, dict) else v for k, v in p.items()}

    def f32(name):
        return p[name].astype(jnp.float32)

    c_q = _rms_norm(a @ f32("q_a_proj"), p["q_a_norm"]["scale"], eps)
    latent = a @ f32("kv_a_proj")
    ckv = _rms_norm(latent[:, :c], p["kv_a_norm"]["scale"], eps)
    k_r = _rope(latent[:, c:], s)
    q_i = _part_rope(jnp.einsum("sr,rhd->shd", c_q, f32("index_q_proj")),
                     rope, s)
    k_i = _part_rope(_layer_norm(a @ f32("index_k_proj"), p["index_k_norm"],
                                 eps), rope, s)
    heads_i, dim_i = q_i.shape[1:]
    w = (a @ f32("index_w_proj")) * heads_i ** -0.5 * dim_i ** -0.5
    m = _mscale(s["factor"], s["mscale_all_dim"])
    scale = (nope + rope) ** -0.5 * m * m
    n, heads = a.shape[0], p["q_b_proj"].shape[1]
    q_block = min(s["q_block"], n)
    blocks = -(-n // q_block)
    pad = blocks * q_block - n
    q_i, w, sets, has = (
        jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        for t in (q_i, w, sets, has))
    k_pos = jnp.arange(n)
    starts = jnp.arange(blocks) * q_block

    def rows(t, start):
        return jax.lax.dynamic_slice_in_dim(t, start, q_block)

    def choose(start):  # I, S and the rows attended over, a block of queries
        seen = k_pos[None, :] <= (start + jnp.arange(q_block))[:, None]
        index = (jax.nn.relu(jnp.einsum("shd,td->sht", rows(q_i, start), k_i))
                 * rows(w, start)[..., None]).sum(1)                # [q, t]
        chosen = select(index, seen, s["index_topk"])
        over = jnp.where(rows(has, start)[:, None],
                         rows(sets, start) & seen, chosen)
        return jnp.where(seen, index, 0.0), chosen, over

    index, chosen, over = jax.lax.map(choose, starts)

    # the softmax a group of heads at a time, from the group's own queries,
    # keys and values through to its share of the output projection: all
    # heads' float32 queries, keys, values and scores of several thousand
    # tokens would not fit beside a serving engine
    group = min(s["head_block"], heads)
    if heads % group:
        raise ValueError(f"{heads} heads in groups of {group}")

    def heads_of(total, g):
        def mine(name, axis):
            return jax.lax.dynamic_slice_in_dim(
                p[name], g * group, group, axis).astype(jnp.float32)

        q = jnp.pad(jnp.einsum("sr,rhd->shd", c_q, mine("q_b_proj", 1)),
                    ((0, pad), (0, 0), (0, 0)))
        qn, qr = q[..., :nope], _rope(q[..., nope:], s)
        kv = jnp.einsum("tc,chd->thd", ckv, mine("kv_b_proj", 1))
        k_nope, v = kv[..., :nope], kv[..., nope:]

        def block(each):
            start, allowed = each
            scores = (jnp.einsum("shd,thd->hst", rows(qn, start), k_nope)
                      + jnp.einsum("shd,td->hst", rows(qr, start), k_r)
                      ) * scale
            probs = jax.nn.softmax(
                jnp.where(allowed[None], scores, -1e30), -1)
            return jnp.einsum("hst,thv->shv",
                              jnp.where(allowed[None], probs, 0.0), v)

        out = jax.lax.map(block, (starts, over))       # [blocks, q, group, v]
        out = out.reshape(blocks * q_block, group, -1)[:n]
        return total + jnp.einsum("shv,hvd->sd", out,
                                  mine("out_proj", 0)), None

    out, _ = jax.lax.scan(heads_of, jnp.zeros_like(a),
                          jnp.arange(heads // group))
    index, chosen = (t.reshape(blocks * q_block, n)[:n]
                     for t in (index, chosen))
    return (out, jnp.concatenate([ckv, k_r, k_i], axis=-1), index, chosen)


def _route(scores, bias, s):
    """``(chosen [n, k], ranked [n, E])``: ``scores + bias`` inside the
    groups that stay (``-inf`` outside), and the ``top_k`` largest of them."""
    n, experts = scores.shape
    groups = s["n_group"]
    ranked = scores + bias
    if groups > 1:
        per = ranked.reshape(n, groups, experts // groups)
        group_score = jnp.sort(per, -1)[..., -2:].sum(-1)
        kept = jax.lax.top_k(group_score, s["topk_group"])[1]
        stays = jax.nn.one_hot(kept, groups).sum(-2) > 0
        ranked = jnp.where(jnp.repeat(stays, experts // groups, -1), ranked,
                           -jnp.inf)
    return jax.lax.top_k(ranked, s["top_k"])[1], ranked


def _experts(m, moe, layer, s, given=None):
    """``axk1_f32._experts`` with the selection bias: ``(sum, chosen,
    scores, ranked)``; the bias (``expert_bias``, zeros where the tree has
    none) is in ``ranked`` and the choice, never in a weight."""
    first, held = s["first"], moe["w_gate"].shape[1]
    scores = jax.nn.sigmoid(
        m @ jnp.asarray(moe["router"]["kernel"][layer], jnp.float32))
    bias = (jnp.asarray(moe["expert_bias"][layer], jnp.float32)
            if "expert_bias" in moe else 0.0)
    chosen, ranked = _route(scores, bias, s)
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


@functools.partial(jax.jit, static_argnames=("key", "tail"))
def _attention_layer(x, kind, layer, sets, key, tail):
    s = dict(key)
    n = x.shape[0]
    with jax.default_matmul_precision("highest"):
        p = _layer_of(kind, layer)
        m = sets.shape[0]
        full = jnp.zeros((n, n), bool).at[n - m:].set(sets)
        out, held, index, chosen = _attention(
            _rms_norm(x, _f32(p["norm"]["scale"]), s["eps"]), p["op"], s,
            full,
            jnp.arange(n) >= n - m)
        return x + out, held[-tail:], index[-tail:], chosen[-tail:]


@functools.partial(jax.jit, static_argnames=("key",))
def _expert_layer(x, kind, layer, given, key):
    s = dict(key)
    with jax.default_matmul_precision("highest"):
        m = _rms_norm(x, jnp.asarray(kind["norm"]["scale"][layer],
                                     jnp.float32), s["eps"])
        out, chosen, scores, _ = _experts(m, kind["op"], layer, s, given)
        return x + out, chosen, scores


def logits(params, tokens, *, settings: dict, tail: int = 0, given=None,
           given_sets=None, with_all: bool = False):
    """Float32 logits of ``tokens`` ``[s]`` (positions 0..s-1) under
    ``params`` (the ``params`` tree of the served model), at the last
    ``tail`` positions (0: at all). ``given`` ``[expert layers, m, k]``
    names the experts to sum over at the LAST ``m`` positions in the
    router's place, ``given_sets`` ``[layers, m, s]`` bool the rows to
    attend over at the last ``m`` positions in the indexer's place (what is
    returned stays the reference's own choice). With ``with_all`` a
    dictionary: ``logits``; ``experts`` ``[expert layers, s, k]`` and
    ``scores`` ``[expert layers, s, E]``; and at the last ``tail``
    positions ``rows`` ``[layers, tail, c_kv + k_r + kI]`` (what a cache
    would hold), ``index`` ``[layers, tail, s]`` (``I``, 0 past the query)
    and ``sets`` ``[layers, tail, s]`` bool (``S``). Each layer is a program
    of its own."""
    params = _unboxed(params)
    gpt, key = params["gpt"], _key(settings)
    kinds = gpt["layers"]
    x = jnp.asarray(gpt["word_embeddings"], jnp.float32)[jnp.asarray(tokens)]
    n = x.shape[0]
    keep = tail or n
    chosen, scores, rows, index, sets = [], [], [], [], []
    for l in range(settings["layers"]):
        mine = (jnp.zeros((0, n), bool) if given_sets is None
                else jnp.asarray(given_sets[l], bool))
        x, held, scored, picked = _attention_layer(
            x, kinds["attention"], l, mine, key, keep)
        if with_all:
            rows.append(held)
            index.append(scored)
            sets.append(picked)
        if l < settings["num_dense"]:
            x = _dense_layer(x, kinds["dense"], l, key)
        else:
            at = l - settings["num_dense"]
            x, experts, score = _expert_layer(
                x, kinds["experts"], at,
                None if given is None else jnp.asarray(given[at], jnp.int32),
                key)
            chosen.append(experts)
            scores.append(score)
    head = params["lm_head"] if "lm_head" in params else gpt["word_embeddings"]
    out = _head(x, gpt["final_norm"]["scale"], head, settings["eps"], keep)
    if not with_all:
        return out
    return {"logits": out, "experts": jnp.stack(chosen),
            "scores": jnp.stack(scores), "rows": jnp.stack(rows),
            "index": jnp.stack(index), "sets": jnp.stack(sets)}


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
    """EVERY expert layer alone, each on an input of its own (``axk1_f32.
    expert_layers``, with the bias): ``(sums, scores, ranked)``; ``scores``
    the RAW sigmoid scores the weights are made of, ``ranked`` ``scores +
    bias`` inside the groups that stay (``-inf`` outside), which decide the
    choice."""
    moe = _unboxed(params)["gpt"]["layers"]["experts"]["op"]
    return _layers_alone(moe, jnp.asarray(inputs), jnp.asarray(
        chosen, jnp.int32), _key(settings))


def _settings(model: dict, q_block: int = 64, dense_block: int = 2048,
              head_block: int = 16) -> dict:
    from perfbench.reference import axk1_f32

    return dict(axk1_f32._settings(model, q_block, dense_block),
                index_topk=int(model["index_topk"]), head_block=head_block)


def configured(model: dict):
    """:func:`logits` with the settings of a configuration file's ``model``
    group (in ``GPTConfig``'s names). NOT to be wrapped in ``jax.jit``: it
    runs a layer a program."""
    return functools.partial(logits, settings=_settings(model))


def configured_layers(model: dict):
    """:func:`expert_layers` with a configuration's routing settings."""
    return functools.partial(expert_layers, settings=_settings(model))
