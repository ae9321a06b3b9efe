"""Plain float32 Jamba (the dense 3B of the family): the reference the
benchmark holds the system to for ``ai21labs/AI21-Jamba2-3B``.

Straightforward ``jax.numpy`` after the published configuration
(``config.json``, ``model_type`` ``jamba``: ``attn_layer_period``,
``attn_layer_offset``, ``mamba_expand``, ``mamba_d_state``, ``mamba_d_conv``,
``mamba_dt_rank``, ``mamba_conv_bias``, ``mamba_proj_bias``, ``num_experts``
1) and the family's layer equations, every product under
``default_matmul_precision("highest")``, no kernel, no cache, no state kept
between calls, no page, no batching of requests: the filter is the sum of
its taps over the whole sequence and the selective scan a ``lax.scan`` over
the positions, one at a time.

A layer ``l``, on ``x`` ``[s, h]`` (positions ``0..s-1``)::

    a = rmsnorm_op(x)
    if layer_types[l] == "mamba":
        u, z = split(a @ W_in, 2)                       # h -> 2 x inner
        u   = silu(b_conv + sum_i w[:, i] * u_{t - (taps - 1) + i})   # u before 0 is 0
        r, B, C = split(u @ W_x, (dt_rank, d_state, d_state))
        r, B, C = rmsnorm_dt(r), rmsnorm_b(B), rmsnorm_c(C)           # learned weights
        dt  = softplus(r @ W_dt + b_dt)                 # [s, inner]
        A   = -exp(A_log)                               # [d_state, inner]
        h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) * B_t[:, None]   # h_{-1} = 0
        y_t = sum_n h_t[n] * C_t[n] + D * u_t
        x'  = x + (y * silu(z)) @ W_out
    else:
        q, k, v = a @ W_q, a @ W_k, a @ W_v             # 20 / 1 / 1 heads of 128, no bias
        every query head reads the one key head, causal, NO position of any
        kind; scores / sqrt(128); softmax
        x'  = x + concat(heads) @ W_o
    m   = rmsnorm_ffn(x')
    out = x' + (silu(m @ W_gate) * (m @ W_up)) @ W_down

then a final RMSNorm, and the head is the embedding transposed.
``rmsnorm(x) = x / sqrt(mean(x^2) + eps) * weight``.

Readings the published configuration does not settle, which the program and
this file take alike (the configuration file's ``assumed``): layer ``i`` is
attention where ``i % attn_layer_period == attn_layer_offset`` (here the
list ``layer_types`` is an argument); the state ``h`` is float32.

Departures from the published implementation, each deliberate:

- float32 throughout, where the published checkpoint computes in bfloat16:
  that is what makes it the reference.
- ``A_log`` and ``h`` are held ``[d_state, inner]``, the transpose of the
  published ``[inner, d_state]``: the same numbers (it reads the system's
  tree, below).
- one sequence at a time (``tokens`` ``[s]``; a batch is a ``lax.map``).
- it reads the system's parameter tree: under ``gpt/layers`` the kinds
  ``mamba``, ``attention``, ``dense``, each ``{"norm", "op"}`` with the
  kind's layers stacked on a leading axis (a layer is found by its place
  among its kind); attention kernels fused (``qkv_proj`` split q|k|v along
  the heads) or three; and upcasts it a layer at a time, so that the float32
  copy of a 6 GB tree never stands beside a serving engine. That layout is
  the only thing it takes from the program. The layers run in BLOCKS of
  like layers (here 7 Mamba layers, an attention layer, 13, one, 6): a
  plain Python loop over the blocks and a ``lax.scan`` over a block's
  layers, each upcast inside the scan's body, so that the program holds
  five layer bodies and not twenty-eight (a cold compile of the unrolled
  loop took most of a minute of the cell's set-up).
- the layer list, head counts, the state's sizes and ``eps`` are arguments
  (the configuration's values), so that one file serves the published sizes
  and the tests' tiny ones.
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


def _mamba(a, p, *, d_state, dt_rank, eps):
    s = a.shape[0]
    u, z = jnp.split(a @ p["in_proj"]["kernel"], 2, axis=-1)
    taps = p["conv_kernel"].shape[-1]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))         # u before 0 is 0
    u = jax.nn.silu(p["conv_bias"] + sum(
        p["conv_kernel"][:, i] * padded[i:i + s] for i in range(taps)))
    r, b, c = jnp.split(u @ p["x_proj"]["kernel"],
                        (dt_rank, dt_rank + d_state), axis=-1)
    r, b, c = (_rms_norm(t, p[name]["scale"], eps) for name, t in (
        ("dt_norm", r), ("b_norm", b), ("c_norm", c)))
    dt = jax.nn.softplus(r @ p["dt_proj"]["kernel"] + p["dt_proj"]["bias"])
    a_neg = -jnp.exp(p["A_log"])                          # [d_state, inner]

    def position(h, row):
        dt_t, u_t, b_t, c_t = row
        h = jnp.exp(dt_t * a_neg) * h + (dt_t * u_t) * b_t[:, None]
        return h, (h * c_t[:, None]).sum(0)

    _, y = jax.lax.scan(position, jnp.zeros_like(a_neg), (dt, u, b, c))
    return ((y + p["D"] * u) * jax.nn.silu(z)) @ p["out_proj"]["kernel"]


def _attention(a, p, *, heads, kv_heads):
    if "qkv_proj" in p:
        qkv = jnp.einsum("se,ehd->shd", a, p["qkv_proj"]["kernel"])
        q, k, v = jnp.split(qkv, (heads, heads + kv_heads), axis=1)
    else:
        q, k, v = (jnp.einsum("se,ehd->shd", a, p[n]["kernel"])
                   for n in ("q_proj", "k_proj", "v_proj"))
    s, _, d = q.shape
    q = q.reshape(s, kv_heads, heads // kv_heads, d)
    scores = jnp.einsum("skgd,tkd->kgst", q, k) / jnp.sqrt(jnp.float32(d))
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgst,tkd->skgd", probs, v).reshape(s, heads, d)
    return jnp.einsum("shd,hde->se", out, p["out_proj"]["kernel"])


def _dense(m, p):
    return ((jax.nn.silu(m @ p["gate_proj"]["kernel"])
             * (m @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"])


def logits(params, tokens, *, layer_types, heads: int, kv_heads: int,
           d_state: int, dt_rank: int, eps: float, tail: int = 0):
    """Float32 logits of ``tokens`` ``[s]`` or ``[b, s]`` (positions
    0..s-1) under ``params`` (the ``params`` tree of the served model), at
    the last ``tail`` positions (0: at all)."""
    params = _unboxed(params)
    tokens = jnp.asarray(tokens)
    settings = dict(layer_types=layer_types, heads=heads, kv_heads=kv_heads,
                    d_state=d_state, dt_rank=dt_rank, eps=eps)
    if tokens.ndim == 2:
        return jax.lax.map(lambda row: logits(params, row, tail=tail,
                                              **settings), tokens)
    gpt = params["gpt"]
    kinds, seen = gpt["layers"], {"mamba": 0, "attention": 0}
    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(gpt["word_embeddings"], jnp.float32)
        x = table[tokens]
        for kind, first, count in _blocks(layer_types):
            # a layer is taken out of its kind's stack INSIDE the body: a
            # slice of the block taken here would be a second copy of it
            places = (seen[kind] + jnp.arange(count), first + jnp.arange(count))
            seen[kind] += count

            def layer(x, place, kind=kind):
                p = _f32(_layer_of(kinds[kind], place[0]))
                dense = _f32(_layer_of(kinds["dense"], place[1]))
                a = _rms_norm(x, p["norm"]["scale"], eps)
                x = x + (_mamba(a, p["op"], d_state=d_state, dt_rank=dt_rank,
                                eps=eps) if kind == "mamba" else
                         _attention(a, p["op"], heads=heads,
                                    kv_heads=kv_heads))
                m = _rms_norm(x, dense["norm"]["scale"], eps)
                return x + _dense(m, dense["op"]), None

            x, _ = jax.lax.scan(layer, x, places)
        x = _rms_norm(x, jnp.asarray(gpt["final_norm"]["scale"], jnp.float32),
                      eps)
        return jnp.einsum("se,ve->sv", x[-tail:], table)


def _blocks(layer_types):
    """``(kind, first layer, layers)`` of every run of like layers."""
    out = []
    for l, name in enumerate(layer_types):
        kind = "mamba" if name == "mamba" else "attention"
        if out and out[-1][0] == kind:
            out[-1][2] += 1
        else:
            out.append([kind, l, 1])
    return out


def _layer_of(stack, index):
    return jax.tree.map(lambda leaf: leaf[index], stack)


def configured(model: dict):
    """:func:`logits` with the settings of a configuration file's ``model``
    group (in ``GPTConfig``'s names)."""
    heads = model["num_attention_heads"]
    return functools.partial(
        logits, layer_types=tuple(model["layer_types"]), heads=heads,
        kv_heads=model.get("num_key_value_heads") or heads,
        d_state=int(model.get("mamba_d_state", 16)),
        dt_rank=int(model["mamba_dt_rank"]),
        eps=float(model.get("norm_eps", 1e-5)))
