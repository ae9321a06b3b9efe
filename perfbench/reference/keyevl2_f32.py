"""Plain float32 Keye-VL-2.0: the reference the benchmark holds the system to
for ``Kwai-Keye/Keye-VL-2.0-30B-A3B`` (grouped attention 32 over 4 of 128
with per-head QK-norm UNDER A LEARNED INDEXER, three-axis rotary positions,
128 softmax-routed experts of which 8 a token, and rows that a vision tower
makes of images), cut in depth as the configuration says.

Straightforward ``jax.numpy`` after the published configuration, every
product under ``default_matmul_precision("highest")``, whole-sequence: no
cache, no page, no kernel, no gather of chosen rows, no bucket, no stage.
With ``h`` the hidden size, all layers alike::

    rows in:   x_t = E[id_t]  for a text row;  the tower's row for an image row
    positions  p_t = (p^t, p^h, p^w): a text row (n, n, n), n one past the
               largest position so far; an image of h x w rows beginning at
               n: (n, n + r, n + c) for row r, column c; then n += max(h, w)

    a  = rmsnorm_in(x)
    q  = rmsnorm_q(a W_q) per head;  k = rmsnorm_k(a W_k) per head;  v = a W_v
    q, k rotated: pair j of head_dim / 2 is (x_j, x_{j + head_dim/2}), angle
               p^{axis(j)} * theta^(-2j / head_dim), axis(j) by mrope_section
    qI = a W_Iq [heads_I, d_I];  kI = layernorm(a W_Ik);  wI = a W_Iw * heads_I^-0.5 * d_I^-0.5
    qI, kI rotated over ALL their d_I / 2 pairs, angle p^{axis} * theta^(-2j / d_I),
               axis by index_rope_section
    I[t, s] = sum_j wI[t, j] * relu(qI[t, j] . kI[s])        s <= t
    S_t = the min(index_topk, t + 1) highest of I[t, :t + 1], a tie to the lower row
    o[t, g] = softmax over s in S_t of (q[t, g] . k[s, kv(g)] * head_dim^-0.5) v[s, kv(g)]
    x' = x + o W_o
    b  = rmsnorm_post(x');  p = softmax(b W_r);  the top_k highest, weights / their sum
    out = x' + sum_e w_e W_d,e(silu(b W_g,e) * (b W_u,e))

then a final RMSNorm and the untied head. The tower, an image of ``2h x 2w``
patches of ``patch x patch x 3`` pixels (``(pixel / 127.5) - 1``)::

    z = patch W_p + b_p + pos,  pos the learned grid x grid table read
        bilinearly at the patch's centre (half-pixel centres, edges clamped)
    every layer: z = z + MHA(LN(z)) (biases; every patch sees every patch of
        ITS image);  z = z + W_2 gelu_tanh(W_1 LN(z) + b_1) + b_2
    z = LN_post(z);  rows = W_b gelu(W_a [the 2 x 2 neighbours of LN_m(z)] + b_a) + b_b

``assumed`` (the configuration file says the same): what the indexer's
projections read, its rotation over all pairs in sections of its own, the
per-head QK-norm, the two-halves pair layout, a tie to the lower row, and
every size of the tower.

Computed a block of ``q_block`` queries at a time, a layer a program, so
that a document of several thousand rows fits beside a serving engine. For
whoever compares, it also returns ``I`` and ``S`` at the last positions, the
``K | V | kI`` rows a cache would hold, the experts chosen, and the tower's
rows; and it attends over GIVEN sets in the place of its own and sums over
GIVEN experts (``given_sets``, ``given``): a discrete choice sits before
the softmax, as ``dsv32_f32.py`` says of its own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.axk1_f32 import (
    _f32,
    _head,
    _key,
    _layer_of,
    _rms_norm,
    _unboxed,
)
from perfbench.reference.dsv32_f32 import _layer_norm, select

__all__ = ["configured", "configured_layers", "expert_layers", "logits",
           "positions_of", "tower_rows"]


def positions_of(tokens, grids, token: int) -> np.ndarray:
    """``[3, s]`` int32: the three-axis position of every row (module
    docstring); ``grids`` the ``(h, w)`` of every image in order, each
    taking the next ``h x w`` rows that hold ``token``."""
    tokens = np.asarray(tokens)
    out, n, at, grids = np.zeros((3, len(tokens)), np.int32), 0, 0, list(grids)
    while at < len(tokens):
        if tokens[at] != token:
            out[:, at] = n
            n, at = n + 1, at + 1
            continue
        h, w = grids.pop(0)
        r, c = np.divmod(np.arange(h * w), w)
        out[:, at:at + h * w] = n + np.stack([0 * r, r, c])
        n, at = n + max(h, w), at + h * w
    return out


def _angles(positions, theta: float, d: int, section):
    """``(cos, sin)`` float32 ``[s, d / 2]`` of a head of ``d`` columns:
    pair ``j`` turns by ``p^{axis(j)} * theta^(-2j / d)``, its axis the one
    whose section of ``section`` it falls in (in float64 on the host)."""
    axis = np.repeat(np.arange(3), section)                   # [d / 2]
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.asarray(positions, np.float64)[axis, :].T * freq[None, :]
    return (np.cos(angle).astype(np.float32),
            np.sin(angle).astype(np.float32))


def _rotated(x, angles):
    """``x`` ``[s, ..., d]`` rotated by ``angles`` = ``(cos, sin)`` ``[s, d
    / 2]``: the head's two halves ``(x1, x2)`` become ``(x1 cos - x2 sin,
    x2 cos + x1 sin)``."""
    d = x.shape[-1]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = (t.reshape(shape) for t in angles)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, p, angles, s, sets, has):
    """The layer's attention output; the rows a cache would hold ``[K | V |
    kI]``; ``I`` and ``S`` ``[n, n]``. ``sets`` are attended over in ``S``'s
    place at the positions ``has``."""
    heads, kv_heads, eps = s["heads"], s["kv_heads"], s["eps"]
    qkv = jnp.einsum("se,ehd->shd", a, p["qkv_proj"]["kernel"])
    q, k, v = jnp.split(qkv, (heads, heads + kv_heads), axis=1)
    q = _rotated(_rms_norm(q, p["q_norm"]["scale"], eps), angles[0])
    k = _rotated(_rms_norm(k, p["k_norm"]["scale"], eps), angles[0])
    q_i = _rotated(jnp.einsum("se,ehd->shd", a, p["index_q_proj"]["kernel"]),
                   angles[1])
    k_i = _rotated(_layer_norm(a @ p["index_k_proj"]["kernel"],
                               p["index_k_norm"], eps), angles[1])
    heads_i, dim_i = q_i.shape[1:]
    w = (a @ p["index_w_proj"]["kernel"]) * heads_i ** -0.5 * dim_i ** -0.5
    n, d = a.shape[0], q.shape[-1]
    q = q.reshape(n, kv_heads, heads // kv_heads, d)
    q_block = min(s["q_block"], n)
    blocks = -(-n // q_block)
    pad = blocks * q_block - n
    q, q_i, w, sets, has = (
        jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        for t in (q, q_i, w, sets, has))
    k_pos = jnp.arange(n)

    def rows(t, start):
        return jax.lax.dynamic_slice_in_dim(t, start, q_block)

    def block(start):
        seen = k_pos[None, :] <= (start + jnp.arange(q_block))[:, None]
        index = (jax.nn.relu(jnp.einsum("shd,td->sht", rows(q_i, start), k_i))
                 * rows(w, start)[..., None]).sum(1)                # [q, t]
        chosen = select(index, seen, s["index_topk"])
        over = jnp.where(rows(has, start)[:, None],
                         rows(sets, start) & seen, chosen)
        scores = jnp.einsum("qkgd,tkd->kgqt", rows(q, start), k) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(over[None, None], scores, -1e30), -1)
        out = jnp.einsum("kgqt,tkd->qkgd",
                         jnp.where(over[None, None], probs, 0.0), v)
        return out, jnp.where(seen, index, 0.0), chosen

    out, index, chosen = jax.lax.map(block, jnp.arange(blocks) * q_block)
    out = out.reshape(blocks * q_block, heads, d)[:n]
    index, chosen = (t.reshape(blocks * q_block, n)[:n]
                     for t in (index, chosen))
    held = jnp.concatenate([k.reshape(n, -1), v.reshape(n, -1), k_i], -1)
    return (jnp.einsum("shd,hde->se", out, p["out_proj"]["kernel"]), held,
            index, chosen)


def _experts(b, moe, layer, s, given=None):
    """``(sum, chosen, probs)`` of expert layer ``layer`` on ``b`` ``[n,
    h]``: the softmax router's probabilities, the ``top_k`` highest ``[n,
    k]`` and the weighted sum over them (``given`` ``[m, k]`` in their
    place at the LAST ``m`` positions), the weights over their sum."""
    probs = jax.nn.softmax(
        b @ jnp.asarray(moe["router"]["kernel"][layer], jnp.float32), -1)
    chosen = summed = jax.lax.top_k(probs, s["top_k"])[1]
    if given is not None and given.shape[0]:
        summed = summed.at[-given.shape[0]:].set(given)
    weight = jnp.take_along_axis(probs, summed, axis=-1)
    if s["norm_topk_prob"]:
        weight = weight / weight.sum(-1, keepdims=True)
    dense = (jax.nn.one_hot(summed, probs.shape[-1])
             * weight[..., None]).sum(-2)                 # [n, experts]

    def matrix(name, e):
        stack = moe[name]
        return jax.lax.dynamic_slice(
            stack, (layer, e, 0, 0), (1, 1, *stack.shape[2:]))[0, 0].astype(
                jnp.float32)

    def one(total, e):  # every token through expert e, then weighted
        out = (jax.nn.silu(b @ matrix("w_gate", e))
               * (b @ matrix("w_up", e))) @ matrix("w_down", e)
        return total + jax.lax.dynamic_slice_in_dim(
            dense, e, 1, axis=1) * out, None

    total, _ = jax.lax.scan(one, jnp.zeros_like(b),
                            jnp.arange(moe["w_gate"].shape[1]))
    return total, chosen, probs


@functools.partial(jax.jit, static_argnames=("key", "tail"))
def _attention_layer(x, kind, layer, angles, sets, key, tail):
    s = dict(key)
    n, m = x.shape[0], sets.shape[0]
    with jax.default_matmul_precision("highest"):
        p = _f32(_layer_of(kind, layer))
        full = jnp.zeros((n, n), bool).at[n - m:].set(sets)
        out, held, index, chosen = _attention(
            _rms_norm(x, p["norm"]["scale"], s["eps"]), p["op"], angles,
            s, full, jnp.arange(n) >= n - m)
        return x + out, held[-tail:], index[-tail:], chosen[-tail:]


@functools.partial(jax.jit, static_argnames=("key",))
def _expert_layer(x, kind, layer, given, key):
    s = dict(key)
    with jax.default_matmul_precision("highest"):
        b = _rms_norm(x, jnp.asarray(kind["norm"]["scale"][layer],
                                     jnp.float32), s["eps"])
        out, chosen, probs = _experts(b, kind["op"], layer, s, given)
        return x + out, chosen, probs


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("key", "grid"))
def _tower(p, pixels, key, grid):
    """One image's rows: ``pixels`` ``[2h, 2w, patch x patch x 3]`` float32
    in raster order; ``grid`` = ``(2h, 2w)``."""
    s = dict(key)
    eps, heads = s["vision_eps"], s["vision_heads"]
    rows, cols = grid
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        z = pixels.reshape(rows * cols, -1) @ p["patch_embed"]["kernel"] \
            + p["patch_embed"]["bias"]
        side = p["pos_embed"].shape[0]

        def centres(n):  # (low, high, weight of high) along one side
            y = np.clip((np.arange(n) + 0.5) * side / n - 0.5, 0, side - 1)
            lo = np.floor(y).astype(np.int64)
            return lo, np.minimum(lo + 1, side - 1), jnp.asarray(
                y - lo, jnp.float32)

        (y0, y1, wy), (x0, x1, wx) = centres(rows), centres(cols)
        table = p["pos_embed"]
        wy, wx = wy[:, None, None], wx[None, :, None]
        pos = ((table[y0][:, x0] * (1 - wx) + table[y0][:, x1] * wx)
               * (1 - wy)
               + (table[y1][:, x0] * (1 - wx) + table[y1][:, x1] * wx) * wy)
        z = z + pos.reshape(rows * cols, -1)
        n, width = z.shape
        d = width // heads
        q_block = min(s["q_block"] * 4, n)
        for name in sorted((k for k in p if k.startswith("block_")),
                           key=lambda k: int(k.split("_")[1])):
            b = p[name]
            a = _layer_norm(z, b["norm1"], eps)
            qkv = jnp.einsum("se,ehd->shd", a, b["qkv_proj"]["kernel"]) \
                + b["qkv_proj"]["bias"]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            pad = -n % q_block
            q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))

            def block(start, k=k, v=v, q=q):
                mine = jax.lax.dynamic_slice_in_dim(q, start, q_block)
                scores = jnp.einsum("qhd,thd->hqt", mine, k) * d ** -0.5
                return jnp.einsum("hqt,thd->qhd",
                                  jax.nn.softmax(scores, -1), v)

            o = jax.lax.map(block, jnp.arange((n + pad) // q_block) * q_block)
            o = o.reshape(n + pad, heads, d)[:n]
            z = z + jnp.einsum("shd,hde->se", o, b["out_proj"]["kernel"]) \
                + b["out_proj"]["bias"]
            a = _layer_norm(z, b["norm2"], eps)
            z = z + _gelu_tanh(a @ b["fc1"]["kernel"] + b["fc1"]["bias"]) \
                @ b["fc2"]["kernel"] + b["fc2"]["bias"]
        z = _layer_norm(_layer_norm(z, p["post_norm"], eps), p["merge_norm"],
                        eps)
        m = s["merge"]
        z = z.reshape(rows // m, m, cols // m, m, width).transpose(
            0, 2, 1, 3, 4).reshape(rows * cols // (m * m), m * m * width)
        z = jax.nn.gelu(z @ p["project_in"]["kernel"]
                        + p["project_in"]["bias"], approximate=False)
        return z @ p["project_out"]["kernel"] + p["project_out"]["bias"]


def tower_rows(params, image, *, settings: dict):
    """The ``h x w`` rows ``[h x w, hidden]`` the tower makes of ``image``
    (uint8 ``[H, W, 3]``), float32."""
    patch, m = settings["patch"], settings["merge"]
    image = np.asarray(image)
    rows, cols = image.shape[0] // patch, image.shape[1] // patch
    pixels = (image.astype(np.float32) / 127.5 - 1.0).reshape(
        rows, patch, cols, patch, -1).transpose(0, 2, 1, 3, 4).reshape(
            rows, cols, -1)
    if rows % m or cols % m:
        raise ValueError(f"an image of {rows} x {cols} patches and merge {m}")
    return _tower(_unboxed(params)["vision"], jnp.asarray(pixels),
                  _key(settings), (rows, cols))


def logits(params, tokens, *, settings: dict, images=(), tail: int = 0,
           given=None, given_sets=None, with_all: bool = False):
    """Float32 logits of the rows ``tokens`` ``[s]`` (an image's rows hold
    ``image_token_id``; ``images`` the uint8 images in order) under
    ``params`` (the ``params`` tree of the served model, the tower's under
    ``vision``), at the last ``tail`` positions (0: at all). ``given``
    ``[layers, m, k]`` names the experts to sum over at the LAST ``m``
    positions, ``given_sets`` ``[layers, m, s]`` bool the rows to attend
    over there (what is returned stays the reference's own choice). With
    ``with_all`` a dictionary: ``logits``; ``experts`` ``[layers, s, k]``,
    ``scores`` ``[layers, s, E]``; at the last ``tail`` positions ``rows``
    ``[layers, tail, K | V | kI]``, ``index`` and ``sets`` ``[layers, tail,
    s]``; ``tower`` the images' rows one after the other; ``positions``.
    Each layer is a program of its own."""
    s = settings
    params = _unboxed(params)
    gpt, key = params["gpt"], _key(s)
    kinds = gpt["layers"]
    tokens = np.asarray(tokens)
    # (the rows first, then float32: the whole table is 1.2 GB in float32)
    x = jnp.asarray(jnp.asarray(gpt["word_embeddings"])[jnp.asarray(tokens)],
                    jnp.float32)
    made = [tower_rows(params, image, settings=s) for image in images]
    m = s["merge"] * s["patch"]
    grids = [(np.shape(i)[0] // m, np.shape(i)[1] // m) for i in images]
    marked = np.flatnonzero(tokens == s["image_token_id"])
    if made:
        tower = jnp.concatenate(made)
        if len(marked) != tower.shape[0]:
            raise ValueError(f"{len(marked)} rows marked for "
                             f"{tower.shape[0]} rows of the images")
        x = x.at[marked].set(tower)
    elif len(marked):
        raise ValueError("rows marked image_token_id and no image")
    positions = positions_of(tokens, grids, s["image_token_id"])
    angles = (_angles(positions, s["theta"], s["head_dim"], s["mrope"]),
              _angles(positions, s["theta"], s["index_dim"],
                      s["index_section"]))
    n = x.shape[0]
    keep = tail or n
    chosen, scores, rows, index, sets = [], [], [], [], []
    for l in range(s["layers"]):
        mine = (jnp.zeros((0, n), bool) if given_sets is None
                else jnp.asarray(given_sets[l], bool))
        x, held, scored, picked = _attention_layer(
            x, kinds["attention"], l, angles, mine, key, keep)
        if with_all:
            rows.append(held)
            index.append(scored)
            sets.append(picked)
        x, experts, probs = _expert_layer(
            x, kinds["experts"], l,
            None if given is None else jnp.asarray(given[l], jnp.int32), key)
        chosen.append(experts)
        scores.append(probs)
    out = _head(x, gpt["final_norm"]["scale"], params["lm_head"], s["eps"],
                keep)
    if not with_all:
        return out
    return {"logits": out, "experts": jnp.stack(chosen),
            "scores": jnp.stack(scores), "rows": jnp.stack(rows),
            "index": jnp.stack(index), "sets": jnp.stack(sets),
            "tower": jnp.concatenate(made) if made else None,
            "positions": positions}


@functools.partial(jax.jit, static_argnames=("key",))
def _layers_alone(moe, inputs, chosen, key):
    s = dict(key)
    with jax.default_matmul_precision("highest"):
        def layer(_, each):
            index, b, picked = each
            total, _, probs = _experts(jnp.asarray(b, jnp.float32), moe,
                                       index, s, picked)
            return None, (total, probs, probs)

        return jax.lax.scan(layer, None, (
            jnp.arange(inputs.shape[0]), inputs, chosen))[1]


def expert_layers(params, inputs, chosen, *, settings: dict):
    """EVERY expert layer alone, each on an input of its own: ``(sums,
    probs, ranked)``, the sum over the experts ``chosen`` ``[layers, s,
    k]``, each weighted by the probability THIS router gives it (over
    their sum), and the router's probabilities twice (what the weights are
    made of, and what the choice ranks: no bias here)."""
    moe = _unboxed(params)["gpt"]["layers"]["experts"]["op"]
    return _layers_alone(moe, jnp.asarray(inputs), jnp.asarray(
        chosen, jnp.int32), _key(settings))


def _settings(model: dict, q_block: int = 64) -> dict:
    vision = dict(model["vision"])
    return dict(
        layers=int(model["num_layers"]),
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        theta=float(model["rope_theta"]), eps=float(model["norm_eps"]),
        head_dim=int(model.get("head_size") or model["hidden_size"]
                     // model["num_attention_heads"]),
        index_dim=int(model["index_head_dim"]),
        mrope=tuple(model["mrope_section"]),
        index_section=tuple(model["index_rope_section"]),
        index_topk=int(model["index_topk"]), top_k=int(model["top_k"]),
        norm_topk_prob=bool(model.get("norm_topk_prob", False)),
        q_block=q_block, patch=int(vision["patch_size"]),
        merge=int(vision["merge"]), vision_heads=int(vision["num_heads"]),
        vision_eps=1e-6, image_token_id=int(vision["image_token_id"]))


def configured(model: dict):
    """:func:`logits` with the settings of a configuration file's ``model``
    group (in ``GPTConfig``'s names). NOT to be wrapped in ``jax.jit``: it
    runs a layer a program."""
    return functools.partial(logits, settings=_settings(model))


def configured_layers(model: dict):
    """:func:`expert_layers` with a configuration's routing settings."""
    return functools.partial(expert_layers, settings=_settings(model))


def configured_tower(model: dict):
    """:func:`tower_rows` with a configuration's settings."""
    return functools.partial(tower_rows, settings=_settings(model))
