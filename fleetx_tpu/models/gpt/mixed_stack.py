"""The decoder stack for layers that do not hold the same parameters
(``layer_types``, ``models/gpt/block_fields.py``): gated short-convolution
layers, Mamba-1 selective-scan layers or KDA delta-rule linear attention
layers beside grouped-query attention layers, dense feed-forward layers
before expert layers or throughout.

**The form.** ``model.GPTModel._decoder_stack`` scans one flax body over
layers that share one parameter tree; here a convolution layer holds ``[h,
3h] + [h, L] + [h, h]``, a selective-scan layer two wide projections, a
filter, the low-rank ``dt`` path, ``A`` and ``D``, an attention layer four
projections (and two ``[head]`` norms), a dense layer a wide MLP and an
expert layer a router and its experts. The parameters are therefore STACKED
BY KIND (the operators ``conv``, ``mamba``, ``kda``, ``attention``; the feed-forward
parts ``dense``, ``experts``: each the kind's own module's tree with the
kind's layers as the leading axis), and ONE ``lax.scan`` body runs every
layer: it looks the layer's two kinds and its place in each kind's stack up
in constants of the program, and a ``lax.cond`` picks the operator and
another the feed-forward part (a stack holds ONE recurrent kind beside
attention: ``block_fields.check``; a kind that the configuration lacks is
in no conditional). So the program holds one body with each kind in it
once, whatever the depth and whatever the order of the kinds (a published
list need not be periodic); no layer carries another kind's parameters (a
tree with both operators in every layer would hold a dead third of the
operators' weights); and the expert kernels index the experts' own stack,
which skips the dense layers. Stacks of the leading layers and of the rest
would have compiled two loops and fixed where the dense layers stand. The
kinds' modules are the ones the other stacks use
(``hybrid.HybridSelfAttention``, ``model.MLP``, ``parallel/moe.py``
``DroplessMoEMLP``), applied to their slice of the stack.

**State.** A lane's state has TWO HOMES, by what the state costs.

*In the page pool*, under the lane's block table (``serving/
cache_manager.py``): keys and values in the attention layers
(``cached_key`` / ``cached_value``: the flat pool of ``hybrid.py``, counted
over the attention layers alone), and in every convolution layer the
operator's last ``L - 1`` inputs ``z = B * u`` (``conv_state``). The
convolution layers own TAIL PAGES: page ``p`` of the pool has, in every
convolution layer, ``L - 1`` rows, and ``z`` of position ``t`` is kept in row
``t % (L - 1)`` of the page that holds position ``t``. A call reads the
state it starts from through the table at the positions just before its
first, and writes, of its own positions, the last ``L - 1`` of every page
it touches. A page that is full therefore holds the state as it stands at
the page's end, and keeps it as long as the page lives: a prompt that
matches a prefix up to any page boundary starts every convolution layer
from what a prefill from the start would hold there, with nothing copied
and no other bookkeeping than the page's own (its refcount, its parking,
its eviction). That is affordable because the state is small: 8 KB a page
and layer beside 32 KB of keys and values.

*Once a lane*, outside the pool: a selective-scan layer's state, ``h``
``[d_state, inner]`` float32 and the filter's last ``d_conv - 1`` inputs.
At the widths served that is 358,400 bytes a layer: kept a page it would
be 9.3 MB for every 16 tokens over 26 layers, so it is kept where the lane
is: the leaves ``ssm_state`` ``[mamba layers, lanes, d_state, inner]`` and
``ssm_conv`` ``[mamba layers, lanes, (d_conv - 1) x inner]`` are indexed by the
lane, which a call is told as column 0 of its block table (the engine's
tables for such a model: the address of a lane's state beside the addresses
of its pages). A call that starts at position 0 starts from zero, whatever
the lane held (no program resets a lane); a later chunk of a prefill and a
decode tick start from what the lane holds and write it back in place (the
tick through ``ops/pallas/ssm_scan.py``'s step kernel, which aliases the
whole leaf). A delta-rule layer's state has the same home and the same
lifecycle (``KDAMixer``): ``S`` ``[d_k, heads, d_v]`` float32 a lane and
layer (4 MB at 64 heads of 128: thirteen times the selective scan's) in
``kda_state`` ``[kda layers, lanes, d_k, heads, d_v]`` and the last
``kda_conv_size - 1`` rows of its three filtered projections side by side
in ``kda_conv``, advanced by ``ops/pallas/kda.py``'s two kernels. Which
leaves a lane-resident kind holds is ``block_fields.LANE_STATE_LEAVES``'s
to say. Nothing outlives the lane's request: a prefix hit would need
the state as it stood at the match's end, a snapshot this stack does not
take (ROADMAP R5), so the engine refuses prefix reuse for it.

In both homes the rows are written before they are read (or begun from
zero), so a recycled page or lane needs no zeroing, and a call's rows that
are no tokens (a padded bucket's tail, a lane that is not decoding) change
nothing: the call is handed which rows are tokens, as its ``attn_mask``
``[batch, rows]``.

**Attention layers of two kinds.** Whether an attention layer attends
through the window (``sliding_attention``) or over its whole row, and whether
it rotates its queries and keys (``rope_layout``), is data of the one body,
as in ``hybrid.py``: the layer looks both up by its place among the
attention layers. The pool then holds two classes of page over the attention
layers (``hybrid.layer_bases``), the call is handed both block tables ``[2,
lanes, pages]`` and the body picks the one of the layer's kind before it
writes and attends. Such a stack (attention layers alone) may also gate the
heads' output (``attention_gate``, in ``HybridSelfAttention``), norm each
part's output before it joins the stream (``sandwich_norm``: a third stack
``post_norm`` beside every kind's ``norm`` and ``op``; device scope
``post_norm``) and scale the embedding rows (``embedding_multiplier``).

**Shortcut-connected feed-forward parts** (``moe_shortcut``, over latent
attention layers: LongCat-Flash). Every entry of ``layer_types`` is then one
HALF of a double layer: each half runs its attention and a dense MLP, the
expert layer reads the dense MLP's normed input at an EVEN half (its stack
holds no norm of its own) and its output LEAVES the stream's path, to LAND on
the dense MLP's output of the NEXT half (scope ``moe_shortcut``). It is one
more ``[b, s, h]`` in the scan's carry, there only where the configuration
asks: a stack without it traces the program it traced before
(``tests/test_longcat_serving.py`` holds their jaxprs to digests), and the
body still holds each kind once.

Forward only: training this stack is ROADMAP R5.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from fleetx_tpu.models.gpt import paged_write
from fleetx_tpu.models.gpt.block_fields import (
    LANE_STATE_LEAVES,
    RECURRENT_TYPES,
    fold_mrope,
)
from fleetx_tpu.models.gpt.hybrid import (
    POOL_LEAVES,
    HybridSelfAttention,
    index_leaf_width,
    layer_bases,
    write_rows,
)
from fleetx_tpu.models.gpt.model import (
    MLP,
    GPTConfig,
    _constrain_act,
    _dense,
)

__all__ = ["KDAMixer", "MambaMixer", "MixedStack", "ShortConv",
           "layer_plan", "mover_layers", "state_rows"]


def layer_plan(cfg: GPTConfig) -> dict:
    """Each layer's two kinds and its place among its kind, as arrays of
    ``num_layers`` entries: ``attention`` (1: attention, 0: the stack's
    recurrent kind), ``operator_index``, ``experts`` (1: expert layer, 0:
    dense), ``ffn_index``; the counts of every kind; and ``recurrent``, the
    name of the recurrent kind ("conv" | "mamba" | "kda", None without one).

    Under ``moe_shortcut`` every entry is one HALF of a double layer: each
    runs the dense MLP (``ffn_index`` its place among ALL halves) and
    ``experts`` marks the EVEN halves, where the expert layer leaves
    (``shortcut_index`` its place in the experts' own stack, ``N`` entries
    for ``2N`` halves)."""
    attention = np.asarray([t.endswith("attention") for t in cfg.layer_types])
    recurrent = next((t for t in RECURRENT_TYPES if t in cfg.layer_types),
                     None)
    halves = np.arange(cfg.num_layers)
    experts = (halves % 2 == 0 if cfg.moe_shortcut
               else halves >= cfg.num_dense_layers)

    def place(mask):  # the layer's index among the layers of its own kind
        return np.where(mask, np.cumsum(mask) - 1, np.cumsum(~mask) - 1)

    plan = {"attention": attention.astype(np.int32),
            "operator_index": place(attention).astype(np.int32),
            "experts": experts.astype(np.int32),
            "ffn_index": place(experts).astype(np.int32),
            "recurrent": recurrent,
            "counts": {"conv": cfg.layer_types.count("conv"),
                       "mamba": cfg.layer_types.count("mamba"),
                       "kda": cfg.layer_types.count("kda"),
                       "attention": int(attention.sum()),
                       "dense": int((~experts).sum()),
                       "experts": int(experts.sum())}}
    if cfg.moe_shortcut:
        plan.update(ffn_index=halves.astype(np.int32),
                    shortcut_index=(halves // 2).astype(np.int32))
        plan["counts"]["dense"] = cfg.num_layers
    return plan


def mover_layers(cfg) -> dict:
    """Span fields of every prefill call and tick of a model with layer
    types, constants of its plan: ``kv_write_layers``, the layers whose
    key/value write lands (the attention layers), and ``state_layers``, the
    layers whose recurrent state advances. In a layer of the other kind that
    mover is skipped (``MixedStack``'s ``operator``). None without layer
    types."""
    if not getattr(cfg, "layer_types", None):
        return {}
    attention = layer_plan(cfg)["counts"]["attention"]
    return {"kv_write_layers": attention,
            "state_layers": cfg.num_layers - attention}


def state_rows(cfg: GPTConfig) -> int:
    """Rows of one tail page: the operator's state, ``conv_L_cache - 1``."""
    return cfg.conv_L_cache - 1


def _torch_conv_init(key, shape, dtype=jnp.float32):
    """A depthwise filter as ``torch.nn.Conv1d`` draws it: uniform in
    ``+-1/sqrt(taps)`` (each channel's fan-in is its taps)."""
    bound = 1.0 / np.sqrt(shape[-1])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _thirds(projected):
    """``B, C, u`` of the input projection: its thirds, in this order."""
    return jnp.split(projected, 3, axis=-1)


class ShortConv(nn.Module):
    """The gated short convolution: ``B, C, u = split(in_proj(a), 3)``; ``z
    = B * u``; ``c_t = sum_i w[:, i] * z_{t - (L - 1) + i}`` (depthwise,
    causal, ``L = conv_L_cache`` taps, the last tap on the position itself);
    ``out_proj(C * c)``. No bias, no activation of its own. ``state`` ``[b,
    L - 1, h]`` is ``z`` at the positions before the call's first (zeros
    before a sequence's start); returns the output and ``z`` of the state
    and the call together ``[b, L - 1 + s, h]``."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, a, state):
        cfg = self.cfg
        h, taps, s = cfg.hidden_size, cfg.conv_L_cache, a.shape[1]
        bcu = _dense(3 * h, ("embed", "mlp"), "in_proj", use_bias=False,
                     dtype=cfg.dtype)(a)
        gate_in, gate_out, u = _thirds(bcu)
        w = self.param("conv_kernel", nn.with_logical_partitioning(
            _torch_conv_init, ("embed", None)), (h, taps), jnp.float32)
        z = jnp.concatenate([state.astype(bcu.dtype), gate_in * u], axis=1)
        mixed = sum(w[:, i] * z[:, i:i + s].astype(jnp.float32)
                    for i in range(taps))
        y = (gate_out.astype(jnp.float32) * mixed).astype(cfg.dtype)
        return _dense(h, ("mlp", "embed"), "out_proj", use_bias=False,
                      dtype=cfg.dtype)(y), z


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``dt``'s bias as the family draws it: the inverse softplus of a step
    drawn log-uniform in [1e-3, 1e-1], so that a fresh layer forgets over
    tens to thousands of positions."""
    step = jnp.exp(jax.random.uniform(key, shape, dtype)
                   * (np.log(1e-1) - np.log(1e-3)) + np.log(1e-3))
    return step + jnp.log(-jnp.expm1(-step))


def _a_log_init(key, shape, dtype=jnp.float32):
    """``A = -(1 .. d_state)`` in every channel (S4D-real), as its log;
    ``[d_state, inner]``: the layout of ``ops/pallas/ssm_scan.py``."""
    del key
    return jnp.broadcast_to(jnp.log(jnp.arange(
        1, shape[0] + 1, dtype=dtype))[:, None], shape)


def _inner_norm(cfg: GPTConfig, name: str):
    """Jamba's norm of ``dt``'s low-rank input, of ``B`` and of ``C``."""
    return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                      param_dtype=jnp.float32, name=name)


def _gated(y, u, z, skip):
    """``(y + D * u) * silu(z)``, float32."""
    return (y + skip * u) * nn.silu(z.astype(jnp.float32))


def _begins(wpos):
    """The lanes ``[b]`` whose call begins a sequence: their lane-resident
    state is read as zero, whatever the lane holds."""
    return wpos == 0


@contextlib.contextmanager
def _moving_lane_state(leaf: str = "ssm_state"):
    """The device scope of what moves lane-resident state outside the
    kernels (``cache_write/ssm_state``, ``cache_write/kda_state``)."""
    with jax.named_scope("cache_write"), jax.named_scope(leaf):
        yield


def _state_rows(rows):
    """The rows ``[b, s]`` of a call that advance the lane-resident state:
    those that are tokens."""
    return rows


def _filter_inputs(conv_rows, x, taps: int):
    """A causal depthwise filter's inputs for the call's rows ``x`` ``[b, s,
    width]`` behind the ``taps - 1`` rows a lane holds of them, ``conv_rows``
    ``[b, (taps - 1) x width]`` side by side (None: zeros). Returns what the
    caller keeps (of a call of several rows ``xs`` ``[b, taps - 1 + s,
    width]``, those of the state and the call together; of a call of ONE row
    ``tail``, the rows held after it, side by side again: a one-row call
    never leaves that form) and the ``taps`` shifted views the filter sums
    over."""
    b, s, width = x.shape
    if conv_rows is None:
        conv_rows = jnp.zeros((b, (taps - 1) * width), x.dtype)
    conv_rows = conv_rows.astype(x.dtype)
    if s == 1:
        return ({"tail": jnp.concatenate([conv_rows[:, width:], x[:, 0]], -1)},
                [conv_rows[:, None, i * width:(i + 1) * width]
                 for i in range(taps - 1)] + [x])
    xs = jnp.concatenate([conv_rows.reshape(b, taps - 1, width), x], 1)
    return {"xs": xs}, [xs[:, i:i + s] for i in range(taps)]


class MambaMixer(nn.Module):
    """The Mamba-1 mixer with Jamba's inner norms: ``[u, z] = split(in_proj
    (a))``; ``u = silu(conv(u))`` (depthwise, causal, ``mamba_d_conv`` taps,
    with bias); ``[r, B, C] = split(x_proj(u))``, each through an RMSNorm
    with a learned weight; ``dt = softplus(dt_proj(r))``; ``A = -exp
    (A_log)``; the scan (``ops/pallas/ssm_scan.py``); ``out_proj((y + D * u)
    * silu(z))``. ``u`` from the filter on, ``dt``, ``B``, ``C``, the scan
    and ``y`` are float32; the two small projections take float32 inputs at
    the backend's default matmul precision.

    In two phases for a caller that keeps the state itself (the layer loop:
    the scan and the state's writes stand outside its conditionals):
    ``phase="project"`` takes ``a`` and ``conv_rows`` ``[b, (d_conv - 1) x
    inner]`` (the filter's inputs at the positions before the call's first,
    side by side as the lanes' leaf holds them) and returns what the scan
    takes and the filter's inputs the caller keeps: of a call of several
    rows ``xs`` ``[b, d_conv - 1 + s, inner]``, those of the state and the
    call together; of a call of ONE row ``tail``, the rows held after it,
    side by side again (a one-row call never leaves that form: slices and
    joins along the last axis are free, a ``[lanes, 3, inner]`` view is a
    re-layout of 8 MB a layer and tick). ``phase="finish"`` takes that
    dictionary with the scan's ``y`` in it. Without a phase: every position
    at once from a zero state."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, a, conv_rows=None, *, phase=None):
        cfg = self.cfg
        d, n, rank, taps = (cfg.mamba_inner, cfg.mamba_d_state,
                            cfg.mamba_dt_rank, cfg.mamba_d_conv)
        if phase == "finish":
            return self._finish(a)
        b, s = a.shape[:2]
        uz = _dense(2 * d, ("embed", "mlp"), "in_proj", use_bias=False,
                    dtype=cfg.dtype)(a)
        u, z = jnp.split(uz, 2, axis=-1)
        w = self.param("conv_kernel", nn.with_logical_partitioning(
            _torch_conv_init, ("mlp", None)), (d, taps), jnp.float32)
        bias = self.param("conv_bias", nn.with_logical_partitioning(
            lambda key, shape, dtype: _torch_conv_init(
                key, shape + (taps,), dtype)[:, 0], ("mlp",)),
            (d,), jnp.float32)
        kept, taken = _filter_inputs(conv_rows, u, taps)
        u = nn.silu(bias.astype(jnp.float32) + sum(
            w[:, i].astype(jnp.float32) * t.astype(jnp.float32)
            for i, t in enumerate(taken)))
        r, b_in, c_in = jnp.split(
            _dense(rank + 2 * n, ("mlp", None), "x_proj", use_bias=False,
                   dtype=jnp.float32)(u), (rank, rank + n), axis=-1)
        r, b_in, c_in = (_inner_norm(cfg, name)(t) for name, t in (
            ("dt_norm", r), ("b_norm", b_in), ("c_norm", c_in)))
        dt = jax.nn.softplus(nn.DenseGeneral(
            d, dtype=jnp.float32, param_dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(rank ** -0.5), (None, "mlp")),
            bias_init=nn.with_logical_partitioning(_dt_bias_init, ("mlp",)),
            name="dt_proj")(r))
        a_log = self.param("A_log", nn.with_logical_partitioning(
            _a_log_init, (None, "mlp")), (n, d), jnp.float32)
        skip = self.param("D", nn.with_logical_partitioning(
            nn.initializers.ones_init(), ("mlp",)), (d,), jnp.float32)
        # (a served tree may hold every leaf in the compute dtype)
        mixed = {"u": u, "dt": dt, "B": b_in, "C": c_in, "z": z, **kept,
                 "A": -jnp.exp(a_log.astype(jnp.float32)),
                 "D": skip.astype(jnp.float32)}
        if phase == "project":
            return mixed
        from fleetx_tpu.ops.pallas.ssm_scan import selective_scan_plain

        mixed["y"] = selective_scan_plain(
            u, dt, mixed["A"], b_in, c_in, jnp.zeros((b, n, d)))[0]
        return self._finish(mixed)

    def _finish(self, mixed):
        cfg = self.cfg
        y = _gated(mixed["y"], mixed["u"], mixed["z"],
                   mixed["D"]).astype(cfg.dtype)
        return _dense(cfg.hidden_size, ("mlp", "embed"), "out_proj",
                      use_bias=False, dtype=cfg.dtype)(y)


def _kda_a_log_init(key, shape, dtype=jnp.float32):
    """A head's ``A_log``: ``log(uniform(1, 16))``, as the family's public
    port draws it (Mamba-2's)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _l2_normed(x):
    """``x / ||x||_2`` along the head's values (eps 1e-6 under the root)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _low_rank(rank: int, width: int, name: str, dtype, bias_init=None):
    """``(x W_a) W_b (+ b)``: a gate's projection through ``rank``, the bias
    on the second alone; float32 parameters."""
    def apply(x):
        x = nn.DenseGeneral(rank, use_bias=False, dtype=dtype,
                            param_dtype=jnp.float32, name=name + "_a")(x)
        return nn.DenseGeneral(
            width, dtype=dtype, param_dtype=jnp.float32, name=name + "_b",
            bias_init=bias_init or nn.initializers.zeros_init())(x)
    return apply


def _log_decay(cfg, a_log, decay, by_head):
    """The log decay ``by_head`` = ``[b, s, heads, d]`` float32 of the
    projected ``decay`` ``[b, s, heads x d]`` (``dt_bias`` in it):
    ``-exp(A_log[head]) softplus(decay)``, or under ``kda_safe_gate`` the
    bounded ``kda_lower_bound * sigmoid(exp(A_log[head]) * decay)``, which
    lies in ``(kda_lower_bound, 0)``."""
    if cfg.kda_safe_gate:
        return cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None] * decay.reshape(by_head))
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(decay.reshape(by_head))


class KDAMixer(nn.Module):
    """Kimi Delta Attention (a gated delta-rule linear attention), ``heads``
    = ``kda_num_heads`` of ``d`` = ``kda_head_dim``: ``[q~ | k~ | v~] =
    qkv_proj(a)``; each through a causal depthwise filter of
    ``kda_conv_size`` taps (no bias, one filter a channel and stream) and a
    SiLU; ``q = l2(q') d^-0.5``, ``k = l2(k')`` per head; the log decay ``g
    = -exp(A_log[head]) softplus((a W_fa) W_fb + dt_bias)`` ``[heads, d]``
    (:func:`_log_decay`: bounded under ``kda_safe_gate``; under
    ``kda_no_lora`` the decay and the gate each through ONE full matrix,
    ``f_proj`` with ``dt_bias`` and ``g_proj`` without a bias);
    ``beta = sigmoid(a W_b)`` ``[heads]``, doubled under ``kda_neg_eigval``;
    the delta rule (``ops/pallas/kda.py``); ``out_proj(rms_o(o) * sigmoid((a
    W_ga) W_gb + b_g))`` with ``rms_o``'s weight ``[d]``. The filters'
    output, ``q, k, v``, ``g``, ``beta``, the state and ``o`` are float32;
    the decay's and ``beta``'s small projections take float32 inputs at the
    backend's default matmul precision.

    In two phases, as :class:`MambaMixer` is (the layer loop keeps the state
    itself): ``phase="project"`` takes ``a`` and ``conv_rows`` ``[b,
    (taps - 1) x 3 x heads x d]`` (the filters' inputs at the positions
    before the call's first, side by side) and returns what the delta rule
    takes and the filter rows the caller keeps (``xs`` of a call of several
    rows, ``tail`` of a call of one); ``phase="finish"`` takes that
    dictionary with the rule's ``o`` in it. Without a phase: every position
    at once from a zero state."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, a, conv_rows=None, *, phase=None):
        cfg = self.cfg
        heads, d, taps = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_size
        width = 3 * heads * d
        if phase == "finish":
            return self._finish(a)
        b, s = a.shape[:2]
        qkv = _dense(width, ("embed", "mlp"), "qkv_proj", use_bias=False,
                     dtype=cfg.dtype)(a)
        w = self.param("conv_kernel", nn.with_logical_partitioning(
            _torch_conv_init, ("mlp", None)), (width, taps), jnp.float32)
        kept, taken = _filter_inputs(conv_rows, qkv, taps)
        with jax.named_scope("kda_conv"):
            q, k, v = (t.reshape(b, s, heads, d) for t in jnp.split(
                nn.silu(sum(w[:, i].astype(jnp.float32)
                            * t.astype(jnp.float32)
                            for i, t in enumerate(taken))), 3, axis=-1))
        a32 = a.astype(jnp.float32)
        dt_bias = nn.with_logical_partitioning(_dt_bias_init, ("mlp",))
        if cfg.kda_no_lora:  # the decay through ONE full matrix
            decay = nn.DenseGeneral(
                heads * d, dtype=jnp.float32, param_dtype=jnp.float32,
                bias_init=dt_bias, name="f_proj")(a32)
        else:
            decay = _low_rank(cfg.kda_gate_rank, heads * d, "f", jnp.float32,
                              dt_bias)(a32)
        a_log = self.param("A_log", _kda_a_log_init, (heads,), jnp.float32)
        beta = jax.nn.sigmoid(nn.DenseGeneral(
            heads, use_bias=False, dtype=jnp.float32, param_dtype=jnp.float32,
            name="b_proj")(a32))
        if cfg.kda_no_lora:  # (and the gate; no bias)
            gate = _dense(heads * d, ("embed", "mlp"), "g_proj",
                          use_bias=False, dtype=cfg.dtype)(a)
        else:
            gate = _low_rank(cfg.kda_gate_rank, heads * d, "g", cfg.dtype)(a)
        # (a served tree may hold every leaf in the compute dtype)
        mixed = {"q": _l2_normed(q) * d ** -0.5, "k": _l2_normed(k), "v": v,
                 "g": _log_decay(cfg, a_log.astype(jnp.float32), decay,
                                 (b, s, heads, d)),
                 "beta": beta * 2.0 if cfg.kda_neg_eigval else beta,
                 "gate": gate, **kept}
        if phase == "project":
            return mixed
        from fleetx_tpu.ops.pallas.kda import kda_chunk_plain

        rule = [mixed[n] for n in ("q", "k", "v", "g", "beta")]
        mixed["o"] = jax.vmap(kda_chunk_plain)(
            *rule, jnp.zeros((b, d, heads, d)))[0]
        return self._finish(mixed)

    def _finish(self, mixed):
        cfg = self.cfg
        o = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                       param_dtype=jnp.float32, name="o_norm")(mixed["o"])
        b, s = o.shape[:2]
        y = (o.reshape(b, s, -1) * jax.nn.sigmoid(
            mixed["gate"].astype(jnp.float32))).astype(cfg.dtype)
        return _dense(cfg.hidden_size, ("mlp", "embed"), "out_proj",
                      use_bias=False, dtype=cfg.dtype)(y)


def _stacked(module, count: int, rng, *example, **kwargs):
    """``module``'s parameter tree drawn ``count`` times, stacked along a
    new leading axis (plain arrays: the kinds' own partitioning boxes name
    no layer axis)."""
    def one(key):
        return nn.meta.unbox(module.init(key, *example, **kwargs)["params"])

    return jax.vmap(one)(jax.random.split(rng, count))


def _norm(cfg: GPTConfig):
    """The RMSNorm before an operator or a feed-forward part, detached."""
    return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=cfg.dtype,
                      param_dtype=jnp.float32, parent=None)


def _at(tree, index):
    return jax.tree.map(lambda leaf: leaf[index], tree)


class MixedStack(nn.Module):
    """The layers of a configuration with ``layer_types`` (module
    docstring), called as ``GPTModel._decoder_stack`` is. In a cached
    forward ``attn_mask`` is not a key mask but ``[batch, rows]`` bool:
    which of the call's rows are tokens (None: all)."""

    cfg: GPTConfig

    def _kinds(self):
        """The four kinds' modules (detached: their parameters are slices
        of this module's stacks) with an example input each."""
        cfg = self.cfg
        x = jnp.zeros((1, 1, cfg.hidden_size), cfg.dtype)
        dense_cfg = dataclasses.replace(
            cfg, ffn_hidden_size=cfg.dense_ffn_hidden_size or cfg.ffn_size)
        if cfg.expert_share:  # a held share, beside shared experts
            from fleetx_tpu.parallel.moe_share import SharedMoEMLP as Experts
        else:
            from fleetx_tpu.parallel.moe import DroplessMoEMLP as Experts

        rope = (jnp.ones((1, 1, cfg.head_dim // 2), jnp.float32),) * 2
        if cfg.indexed and not cfg.latent:  # (and the indexer's two tables)
            rope += (jnp.ones((1, 1, cfg.index_head_dim // 2),
                              jnp.float32),) * 2
        return {
            "conv": (ShortConv(cfg, parent=None),
                     (x, jnp.zeros((1, state_rows(cfg), cfg.hidden_size),
                                   cfg.dtype)), {}),
            "mamba": (MambaMixer(cfg, parent=None), (x,), {}),
            "kda": (KDAMixer(cfg, parent=None), (x,), {}),
            "attention": (HybridSelfAttention(cfg, parent=None), (x,),
                          {"layer_index": jnp.int32(0), "rope": rope}),
            "dense": (MLP(dense_cfg, parent=None), (x,), {}),
            "experts": (Experts(cfg, parent=None), (x,), {}),
        }

    @nn.compact
    def __call__(self, x, attn_mask=None, *, deterministic=True, decode=False,
                 cache_positions=None, block_tables=None, rope=None):
        cfg = self.cfg
        plan, kinds = layer_plan(cfg), self._kinds()
        params = {}
        for name, (module, args, kwargs) in kinds.items():
            count = plan["counts"][name]
            if not count:
                continue
            # (under the shortcut the experts read the dense MLP's normed
            # input: their stack holds no norm)
            normless = cfg.moe_shortcut and name == "experts"
            params[name] = self.param(
                name, lambda rng, m=module, n=count, a=args, k=kwargs,
                normless=normless: {
                    **({} if normless else {
                        "norm": _stacked(_norm(cfg), n, rng, a[0])}),
                    "op": _stacked(m, n, rng, *a, **k),
                    **({"post_norm": _stacked(_norm(cfg), n, rng, a[0])}
                       if cfg.sandwich_norm else {})})
        if cfg.embedding_multiplier != 1.0:
            with jax.named_scope("embed"):
                # (in float32: bfloat16 would round the multiplier itself)
                x = (x.astype(jnp.float32)
                     * cfg.embedding_multiplier).astype(x.dtype)
        rope = self._rope(rope)
        cache = self._cache(decode, plan, lanes=x.shape[0])
        if decode and cache is not None and (cache_positions is None
                                             or block_tables is None):
            raise ValueError("a paged decode cache needs cache_positions AND "
                             "block_tables (the serving engine threads both)")
        return self._decoder_stack(
            x, params, cache, plan, kinds, rows=attn_mask if decode else None,
            key_mask=None if decode else attn_mask,
            deterministic=deterministic, cache_positions=cache_positions,
            block_tables=block_tables, rope=rope)

    def _rope(self, rope):
        """The angles the attention layers take, from the model's tables
        ``(cos, sin)`` at the call's positions: as they are for ONE axis;
        under ``mrope_section`` (positions ``[3, b, s]``: tables ``[3, b, s,
        pairs]``) each pair from its axis (``block_fields.fold_mrope``);
        under a grouped indexer the indexer's two tables behind the heads'
        two: pair ``j`` of its ``index_head_dim / 2`` turns at
        ``theta^(-2j / index_head_dim)``, which is the heads' pair ``j *
        head_dim / index_head_dim``, in the axes ``index_rope_section``
        gives."""
        cfg = self.cfg
        if rope is None or cfg.latent or not (cfg.mrope_section
                                              or cfg.indexed):
            return rope
        with jax.named_scope("embed"):
            heads = fold_mrope(rope, cfg.mrope_section)
            if not cfg.indexed:
                return heads
            stride = cfg.head_dim // cfg.index_head_dim
            return heads + fold_mrope(
                tuple(t[..., ::stride] for t in rope), cfg.index_rope_section)

    def _cache(self, decode: bool, plan: dict, lanes: int):
        """The cache collection's variables (None outside a cached forward
        and at its init, which only declares them): the attention layers'
        flat pool (``hybrid.init_cache`` sizes it), the convolution layers'
        tail pages or the selective-scan or delta-rule layers' state of
        every lane (the init's batch is the lanes), the expert layers'
        counters."""
        cfg = self.cfg
        if not decode:
            return None
        if cfg.decode_num_pages is None:
            raise NotImplementedError(
                "a contiguous decode cache over layers with a convolution "
                "state (one-shot generate()): serve the model through "
                "ServingEngine, whose page pool holds both kinds of state")
        from fleetx_tpu.parallel.moe_share import stats_words

        ps, rows = cfg.decode_page_size, state_rows(cfg)
        if ps % rows:
            raise ValueError(f"decode_page_size {ps} is no multiple of the "
                             f"convolution state's {rows} rows")
        fresh = not self.has_variable("cache", "cached_key")
        width, counts = cfg.kv_heads * cfg.head_dim, plan["counts"]
        held = {
            "cached_key": self.variable(
                "cache", "cached_key", jnp.zeros, (1, ps, width), cfg.dtype),
            "cached_value": self.variable(
                "cache", "cached_value", jnp.zeros, (1, ps, width), cfg.dtype),
        }
        if cfg.indexed:  # the indexer's keys: the pool's third leaf
            held["cached_index"] = self.variable(
                "cache", "cached_index", jnp.zeros,
                (1, ps, index_leaf_width(cfg)), cfg.dtype)
        held.update(self._lane_leaves(counts, lanes))
        if counts["conv"]:
            held["conv_state"] = self.variable(
                "cache", "conv_state", jnp.zeros,
                (counts["conv"] * cfg.decode_num_pages, rows,
                 cfg.hidden_size), cfg.dtype)
        held.update({
            "moe_stats": self.variable(
                "cache", "moe_stats", jnp.zeros,
                (max(counts["experts"], 1), stats_words(cfg)), jnp.uint32),
        })
        return None if fresh else held

    def _lane_leaves(self, counts: dict, lanes: int) -> dict:
        """The leaves of the state a lane holds ONCE A LANE: the
        selective-scan layers' or the delta-rule layers' (none without
        either)."""
        cfg, held = self.cfg, {}
        if counts["mamba"]:
            # [d_state, inner] and [lanes, rows x inner]: no axis of 16 or of
            # 3 in the last two places, which the device's tiles would pad;
            # a lane's filter rows lie side by side in ONE row of the leaf,
            # which a tick takes whole and a one-lane call slices out
            n, d = counts["mamba"], cfg.mamba_inner
            held["ssm_state"] = self.variable(
                "cache", "ssm_state", jnp.zeros,
                (n, lanes, cfg.mamba_d_state, d), jnp.float32)
            held["ssm_conv"] = self.variable(
                "cache", "ssm_conv", jnp.zeros,
                (n, lanes, (cfg.mamba_d_conv - 1) * d), cfg.dtype)
        elif counts["kda"]:
            # [d_k, heads, d_v]: eight heads along a register's sublanes
            # (ops/pallas/kda.py); the filter rows as the scan's are kept
            n, d = counts["kda"], cfg.kda_head_dim
            held["kda_state"] = self.variable(
                "cache", "kda_state", jnp.zeros,
                (n, lanes, d, cfg.kda_num_heads, d), jnp.float32)
            held["kda_conv"] = self.variable(
                "cache", "kda_conv", jnp.zeros,
                (n, lanes, (cfg.kda_conv_size - 1) * 3 * cfg.kda_inner),
                cfg.dtype)
        return held

    def _decoder_stack(self, x, params, cache, plan, kinds, *, rows, key_mask,
                       deterministic, cache_positions, block_tables, rope):
        """One scanned body over the layers (the method's name is the one
        the device trace's readers know the layer loop by)."""
        cfg, counts = self.cfg, plan["counts"]
        cached = cache is not None
        probed = (self.is_mutable_collection("routing")
                  and not self.is_initializing())
        pools = {k: v.value for k, v in cache.items()} if cached else {}
        b, s, h = x.shape
        state_shape = (b, state_rows(cfg), h)
        recurrent = plan["recurrent"]
        if cached:
            tables = block_tables.astype(jnp.int32)
            wpos = cache_positions.astype(jnp.int32)
            rows = (jnp.ones((b, s), bool) if rows is None
                    else rows.astype(bool))
            if recurrent in LANE_STATE_LEAVES:
                # column 0: the lane, where its state is held (module
                # docstring); a tick is handed every lane in order
                state_leaf, rows_leaf = LANE_STATE_LEAVES[recurrent][1]
                lanes, tables = tables[:, 0], tables[:, 1:]
                tick = s == 1 and b == pools[state_leaf].shape[1]
                if not tick and b != 1:
                    raise NotImplementedError(
                        "state held once a lane takes a tick over every lane "
                        f"in order or a call of ONE lane, not {b} of "
                        f"{pools[state_leaf].shape[1]} lanes")
                begins, advancing = _begins(wpos), _state_rows(rows)
        norm = _norm(cfg)
        conv_op, attn_op = kinds["conv"][0], kinds["attention"][0]
        mamba_op, kda_op = kinds["mamba"][0], kinds["kda"][0]

        def zeros_like_of(fn):
            """What ``fn()`` returns, as zeros: the other branch's share of
            a conditional's result."""
            return jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype),
                                jax.eval_shape(fn))
        both = (counts["attention"], counts[recurrent] if recurrent else 0)

        def normed(kind, index, value):
            return norm.apply({"params": _at(params[kind]["norm"], index)},
                              value)

        def joins(kind, index, value):
            """What a part's output ``value`` adds to the stream: itself,
            or under ``sandwich_norm`` its norm by the kind's own weight."""
            if not cfg.sandwich_norm:
                return value
            with jax.named_scope("post_norm"):
                return norm.apply(
                    {"params": _at(params[kind]["post_norm"], index)}, value)

        # two classes of page: the call's tables are [class, lanes, pages],
        # 0 full, 1 window, and a layer takes its kind's
        windowed = jnp.asarray(cfg.of_attention_layers(cfg.window_layers),
                               bool)

        def pick(flag, counts, yes, no, *args):
            """``yes`` or ``no`` by the layer's kind; a conditional only
            where the configuration has both."""
            if not counts[0]:
                return no(*args)
            if not counts[1]:
                return yes(*args)
            return jax.lax.cond(flag, yes, no, *args)

        def conv(value, index, state_pool=None):
            """The convolution's output and ``z`` of the call's rows; the
            state it starts from read from ``state_pool``, or zeros."""
            a = normed("conv", index, value)
            with jax.named_scope("cache_write"), jax.named_scope("conv_state"):
                state = (jnp.zeros(state_shape, cfg.dtype)
                         if state_pool is None else
                         _read_state(cfg, state_pool, tables, wpos, index))
            with jax.named_scope("conv_mix"):
                y, z = conv_op.apply(
                    {"params": _at(params["conv"]["op"], index)}, a, state)
            return y, z[:, state_shape[1]:]

        def mamba(value, index, held=None):
            """The mixer's projections (``MambaMixer`` phase "project") from
            the filter rows ``held`` ``[b, (taps - 1) x d]`` (None: zeros);
            ``dt`` zero in the rows that are no tokens, which then leave
            ``h`` alone."""
            a = normed("mamba", index, value)
            with jax.named_scope("ssm_mix"):
                mixed = mamba_op.apply(
                    {"params": _at(params["mamba"]["op"], index)}, a, held,
                    phase="project")
            if held is not None:
                mixed["dt"] = jnp.where(advancing[..., None], mixed["dt"],
                                        0.0)
            return mixed

        def mamba_finish(mixed, index):
            with jax.named_scope("ssm_mix"):
                return mamba_op.apply(
                    {"params": _at(params["mamba"]["op"], index)}, mixed,
                    phase="finish")

        def kda(value, index, held=None):
            """The delta-rule operator's projections (``KDAMixer`` phase
            "project") from the filter rows ``held``; ``g`` and ``beta``
            zero in the rows that are no tokens, which then leave ``S``
            alone."""
            a = normed("kda", index, value)
            with jax.named_scope("kda_mix"):
                mixed = kda_op.apply(
                    {"params": _at(params["kda"]["op"], index)}, a, held,
                    phase="project")
            if held is not None:
                mixed["g"] = jnp.where(advancing[..., None, None],
                                       mixed["g"], 0.0)
                mixed["beta"] = jnp.where(advancing[..., None],
                                          mixed["beta"], 0.0)
            if held is not None and not tick:
                # a call of one lane's rows leaves the conditional as the
                # projections lay it out, ``[b, s, heads x d]``, which is
                # what the chunk kernel reads: ``[b, s, heads, d]`` across
                # the conditional is another tiling, a copy there and back
                for name in ("q", "k", "v", "g"):
                    mixed[name] = mixed[name].reshape(b, s, -1)
            return mixed

        def by_head(rows):
            """``[b, s, heads x d]`` (or by head already) ``[b, s, heads,
            d]``."""
            return rows.reshape(b, s, cfg.kda_num_heads, -1)

        def kda_finish(mixed, index):
            with jax.named_scope("kda_mix"):
                return kda_op.apply(
                    {"params": _at(params["kda"]["op"], index)},
                    {**mixed, "o": by_head(mixed["o"])}, phase="finish")

        def scan(mixed, h0, skip=None):
            from fleetx_tpu.ops.pallas.ssm_scan import selective_scan

            with jax.named_scope("ssm_mix"), jax.named_scope("ssm_scan"):
                return selective_scan(
                    mixed["u"], mixed["dt"], mixed["A"], mixed["B"],
                    mixed["C"], h0, skip=skip,
                    kernel=cfg.use_flash_attention)

        # the lane-resident leaves are read and written by SLICES (a tick:
        # one layer of every lane; a call of one lane: that lane of one
        # layer), never gathered: an index vector over the lanes made XLA
        # re-lay the whole ``ssm_conv`` leaf out at each end of a prefill
        def filter_rows(conv_pool, index):
            """The filter rows the call's lanes hold, side by side ``[b,
            (taps - 1) x d]``."""
            with _moving_lane_state(state_leaf):
                return conv_pool[index] if tick else (
                    jax.lax.dynamic_slice_in_dim(conv_pool[index], lanes[0],
                                                 1, axis=0))

        def keep_filter_rows(pools, mixed, held, mixes, index, taps):
            """``pools[rows_leaf]`` takes the filter's inputs at the last
            ``taps - 1`` positions that are tokens: a lane with no token
            keeps the rows it held (``xs`` begins with them), as a layer of
            another kind does."""
            with _moving_lane_state(state_leaf):
                if s == 1:
                    last = jnp.where(advancing, mixed["tail"], held)
                else:
                    last = jax.lax.dynamic_slice_in_dim(
                        mixed["xs"], advancing.sum().astype(jnp.int32),
                        taps - 1, axis=1)
                last = jnp.where(mixes, held, last.reshape(b, -1))[None]
                pools[rows_leaf] = jax.lax.dynamic_update_slice(
                    pools[rows_leaf], last.astype(pools[rows_leaf].dtype),
                    (index, 0 if tick else lanes[0], 0))

        def ssm_update(pools, mixed, held, mixes, index):
            """``y`` of the call's rows; ``pools`` (the caller's own dict)
            takes the leaves with the lanes' state advanced over them, in
            place. A layer of another kind (``mixes``) changes nothing: the
            kernels skip it, so ``h`` stays where it is, and the filter rows
            written are the ones held."""
            from fleetx_tpu.ops.pallas.ssm_scan import selective_step

            state, fresh = pools["ssm_state"], begins & ~mixes
            if tick:
                with jax.named_scope("ssm_mix"), jax.named_scope("ssm_step"):
                    y, state = selective_step(
                        state, index, mixed["u"][:, 0], mixed["dt"][:, 0],
                        mixed["A"], mixed["B"][:, 0], mixed["C"][:, 0],
                        fresh, skip=mixes, kernel=cfg.use_flash_attention)
                    y = y[:, None]
            else:
                at = (index, lanes[0], 0, 0)
                with _moving_lane_state(state_leaf):
                    h0 = jnp.where(fresh[:, None, None], 0.0,
                                   jax.lax.dynamic_slice(
                                       state, at, (1, 1) + state.shape[2:])[0])
                y, h = scan(mixed, h0, skip=mixes)
                with _moving_lane_state(state_leaf):
                    state = jax.lax.dynamic_update_slice(state, h[None], at)
            pools["ssm_state"] = state
            keep_filter_rows(pools, mixed, held, mixes, index,
                             cfg.mamba_d_conv)
            return y

        def kda_update(pools, mixed, held, mixes, index):
            """``o`` of the call's rows; ``pools`` takes the leaves with the
            lanes' state advanced over them, in place, as ``ssm_update``
            does. A layer of another kind (``mixes``) is skipped by the
            kernels and keeps the filter rows held."""
            from fleetx_tpu.ops.pallas.kda import kda_chunk, kda_step

            state, fresh = pools["kda_state"], begins & ~mixes
            rule = [mixed[n] for n in ("q", "k", "v", "g", "beta")]
            if tick:
                with jax.named_scope("kda_mix"), jax.named_scope("kda_step"):
                    o, state = kda_step(
                        state, index, *(t[:, 0] for t in rule), fresh,
                        skip=mixes, kernel=cfg.use_flash_attention)
                    o = o[:, None]
            else:
                at = (index, lanes[0], 0, 0, 0)
                with _moving_lane_state(state_leaf):
                    s0 = jnp.where(fresh[0], 0.0, jax.lax.dynamic_slice(
                        state, at, (1, 1) + state.shape[2:])[0, 0])
                with jax.named_scope("kda_mix"), jax.named_scope("kda_chunk"):
                    o, last = kda_chunk(
                        *(by_head(t)[0] for t in rule[:4]), rule[4][0], s0,
                        skip=mixes, kernel=cfg.use_flash_attention)
                    o = o.reshape(b, s, -1)
                with _moving_lane_state(state_leaf):
                    state = jax.lax.dynamic_update_slice(
                        state, last[None, None], at)
            pools["kda_state"] = state
            keep_filter_rows(pools, mixed, held, mixes, index,
                             cfg.kda_conv_size)
            return o

        # what an attention kind sows for whoever holds it to a reference
        # (latent attention's index scores and sets), every layer's
        probing = ["routing"] if probed else []

        def first_sown(mut):
            return {k: v[0] for k, v in mut.get("routing", {}).items()}

        def attention(value, index, *args, **kwargs):
            return attn_op.apply(
                {"params": _at(params["attention"]["op"], index),
                 **kwargs.pop("variables", {})}, value, *args,
                deterministic=deterministic, layer_index=index, **kwargs)

        def operator(value, mixes, index, pools):
            """The operator over the cache, in three steps: a conditional
            that computes (the recurrent operator up to its state, reading
            what it starts from; the attention's queries, keys and values),
            the writes of BOTH kinds of state outside every conditional, and
            a conditional that finishes (attends; the mixer's gate and output
            projection). A pool that a conditional hands back is copied
            whole by XLA (1.3 GB in every attention layer at the served
            sizes), so the pools and leaves cross none: the mover of the
            OTHER kind's state is handed the layer's kind as a scalar that
            its kernel branches on, and does nothing. The step kernels run
            one block through (``skip``: 10 us where ``fleetx_ssm_step``
            moved 168 MB in 303), and where the stack has both kinds the
            key/value write is ``fleetx_write_rows``, whose body sits under
            ``keep`` (``paged_write.write_rows_or_skip``: a dropped write is
            a launch, where the scatter walked every dropped update: 40 us a
            layer of a 256-lane tick). A stack of one kind keeps the
            scatter, and the program it had."""
            held = (filter_rows(pools[rows_leaf], index)
                    if recurrent in LANE_STATE_LEAVES else None)

            def recur():
                if recurrent == "conv":
                    return dict(zip(("y", "z"),
                                    conv(value, index, pools["conv_state"])))
                # (a lane that begins a sequence begins from zeros)
                return (kda if recurrent == "kda" else mamba)(
                    value, index, jnp.where(begins[:, None], 0, held))

            def project():  # (a fourth: the third leaf's rows)
                return dict(zip(("q", "k", "v", "index"), attention(
                    normed("attention", index, value), index, rope=rope,
                    phase="project")))

            def recur_step():
                return recur(), zeros_like_of(project)

            def project_step():
                return zeros_like_of(recur), project()

            if not counts["attention"]:
                mixed, qkv = recur(), None
            elif not recurrent:
                mixed, qkv = None, project()
            else:
                mixed, qkv = jax.lax.cond(mixes, project_step, recur_step)
            pools = dict(pools)
            own = (jnp.where(windowed[index], tables[1], tables[0])
                   if tables.ndim == 3 else tables)
            if recurrent == "conv":
                with jax.named_scope("cache_write"), \
                        jax.named_scope("conv_state"):
                    pools["conv_state"] = _write_state(
                        cfg, pools["conv_state"], tables, wpos,
                        rows & ~mixes, index, mixed["z"])
            elif recurrent == "mamba":
                mixed["y"] = ssm_update(pools, mixed, held, mixes, index)
            elif recurrent == "kda":
                mixed["o"] = kda_update(pools, mixed, held, mixes, index)
            leaves = [n for n in POOL_LEAVES if n in pools]
            if all(both):
                # most layers of such a stack drop this write: the writer
                # that branches on the layer's kind
                based = own + jnp.asarray(layer_bases(cfg))[index]
                news = [qkv[n] for n in ("k", "v", "index")[:len(leaves)]]
                pools.update(zip(leaves, paged_write.write_rows_or_skip(
                    [pools[n] for n in leaves],
                    [new.reshape(b * s, -1) for new in news], based, wpos,
                    cfg.decode_cache_len or cfg.max_position_embeddings,
                    mixes, kernel=cfg.use_flash_attention)))
            elif counts["attention"]:
                pools.update(zip(leaves, write_rows(
                    cfg, pools["cached_key"], pools["cached_value"],
                    own + jnp.asarray(layer_bases(cfg))[index], wpos,
                    qkv["k"], qkv["v"], keep=mixes,
                    more=[(pools[n], qkv["index"]) for n in leaves[2:]])))

            def attend_step():
                out, mut = attention(
                    qkv["q"], index, decode=True, cache_positions=wpos,
                    block_tables=own, phase="attend",
                    mutable=["cache"] + probing,
                    variables={"cache": {n: pools[n] for n in leaves}})
                return joins("attention", index, out), first_sown(mut)

            def finish_step():
                y = (mixed["y"] if recurrent == "conv"
                     else kda_finish(mixed, index) if recurrent == "kda"
                     else mamba_finish(mixed, index))
                return joins(recurrent, index, y), {}

            y, seen = pick(mixes, both, attend_step, finish_step)
            if probed and recurrent == "kda":
                # what the delta rule was handed and gave, every layer's
                # (zeros in a layer of another kind), for whoever holds the
                # rule to a reference ON THE ROWS IT REALLY SAW
                seen = {**seen, "kda_beta": mixed["beta"],
                        **{"kda_" + n: by_head(mixed[n])
                           for n in ("q", "k", "v", "g", "o")}}
            return y, seen, pools

        def plain(value, mixes, index, pools):
            """The operator outside a cache: every position at once."""
            def recur():
                if recurrent == "conv":
                    return conv(value, index)[0]
                if recurrent == "kda":
                    with jax.named_scope("kda_mix"):
                        return kda_op.apply(
                            {"params": _at(params["kda"]["op"], index)},
                            normed("kda", index, value))
                mixed = mamba(value, index)
                mixed["y"] = scan(mixed, jnp.zeros(
                    (b, cfg.mamba_d_state, cfg.mamba_inner)))[0]
                return mamba_finish(mixed, index)

            def attend():
                out = attention(normed("attention", index, value), index,
                                key_mask, rope=rope,
                                mutable=probing or False)
                out, sown = ((out[0], first_sown(out[1])) if probed
                             else (out, {}))
                return joins("attention", index, out), sown

            return (*pick(mixes, both, attend, lambda: (
                joins(recurrent, index, recur()), {})), pools)

        # (``a`` of the two ``_on`` functions is a thunk of the part's normed
        # input: the weights' slices are traced before it, as they were when
        # each kind normed its own input inline)
        def dense_on(a, index):
            return kinds["dense"][0].apply(
                {"params": _at(params["dense"]["op"], index)}, a())

        def dense(value, index, stats):
            y = dense_on(lambda: normed("dense", index, value), index)
            # where an expert layer gives its routing a dense layer gives
            # zeros of the same shapes: the two are branches of one conditional
            return joins("dense", index, y), stats, (zeros_like_of(
                lambda: experts(value, index, stats)[2])
                if counts["experts"] else {})

        def experts_on(a, index, stats):
            """The expert layer ``index`` of its own stack on the normed
            stream ``a()``: its output, the counters, what it sowed."""
            held = params["experts"]["op"]
            variables = {"params": _at(held, index)}
            mutable = ["routing"] if probed else []
            if cached:
                variables["cache"] = {"moe_stats": stats}
                mutable.append("cache")
            with jax.named_scope("moe_mlp"):
                y, mut = kinds["experts"][0].apply(
                    variables, a(),
                    decode=cached, layer_index=index if cached else None,
                    expert_stack=tuple(held[k] for k in (
                        "w_gate", "w_up", "w_down")) if cached else None,
                    mutable=mutable)
            if cached:
                stats = mut["cache"]["moe_stats"]
            sown = ({k: v[0] for k, v in mut["routing"].items()}
                    if probed else {})
            return y, stats, sown

        def experts(value, index, stats):
            y, stats, sown = experts_on(
                lambda: normed("experts", index, value), index, stats)
            return joins("experts", index, y), stats, sown

        def shortcut_ffn(value, layer, stats, shortcut):
            """A half's feed-forward part under ``moe_shortcut``: the dense
            MLP on ``a``, the half's normed stream; at an even half the
            expert layer reads the same ``a`` and its output LEAVES as the
            new ``shortcut``; at an odd half the shortcut carried LANDS on
            the dense MLP's output (scope ``moe_shortcut``). Returns what
            joins the stream, the counters, what was sown, the shortcut."""
            index = jnp.asarray(plan["ffn_index"])[layer]
            leaving = jnp.asarray(plan["experts"])[layer] == 1
            a = normed("dense", index, value)

            def leaves(stats, shortcut):
                del shortcut
                return experts_on(
                    lambda: a,
                    jnp.asarray(plan["shortcut_index"])[layer], stats)

            def stays(stats, shortcut):
                return shortcut, stats, zeros_like_of(
                    lambda: leaves(stats, shortcut)[2])

            shortcut, stats, sown = jax.lax.cond(leaving, leaves, stays,
                                                 stats, shortcut)
            y = dense_on(lambda: a, index)
            with jax.named_scope("moe_shortcut"):
                y = y + _landing(leaving, shortcut)
            return y, stats, sown, shortcut

        def body(carry, layer):
            value, pools = carry[0], dict(carry[1])
            value = _constrain_act(value, cfg)
            with jax.named_scope("layer"):
                stats = pools.pop("moe_stats", None)
                with jax.named_scope("attn"):
                    y, seen, pools = (operator if cached else plain)(
                        value, jnp.asarray(plan["attention"])[layer] == 1,
                        jnp.asarray(plan["operator_index"])[layer], pools)
                value = value + y
                with jax.named_scope("mlp"):
                    if cfg.moe_shortcut:
                        y, stats, sown, shortcut = shortcut_ffn(
                            value, layer, stats, carry[2])
                    else:
                        y, stats, sown = pick(
                            jnp.asarray(plan["experts"])[layer] == 1,
                            (counts["experts"], counts["dense"]), experts,
                            dense, value,
                            jnp.asarray(plan["ffn_index"])[layer], stats)
                if cached:
                    pools["moe_stats"] = stats
            value = _constrain_act(value + y, cfg)
            # the carry gains the shortcut only where the configuration asks
            return ((value, pools, shortcut) if cfg.moe_shortcut
                    else (value, pools)), (sown, seen)

        (x, pools, *_), (sown, seen) = jax.lax.scan(
            body, (x, pools, jnp.zeros_like(x)) if cfg.moe_shortcut
            else (x, pools), jnp.arange(cfg.num_layers, dtype=jnp.int32))
        for name, leaf in pools.items():
            cache[name].value = leaf
        if probed:  # the expert layers' rows, as a layer scan would stack them
            expert_rows = (slice(0, None, 2) if cfg.moe_shortcut
                           else slice(cfg.num_dense_layers, None))
            for name, leaf in sown.items():
                self.sow("routing", name, leaf[expert_rows])
            for name, leaf in seen.items():
                self.sow("routing", name, leaf)
        return x


def _landing(leaving, shortcut):
    """What of the shortcut lands on a half's dense MLP output: all of it
    at an odd half, nothing at the even half it has just left at (a
    function of its own: ``perfbench/probe_longcat.py`` plants a fault
    here)."""
    return jnp.where(leaving, jnp.zeros_like(shortcut), shortcut)


def _pages(cfg: GPTConfig, tables, pos, index):
    """The tail page of every position ``pos`` ``[b, n]`` of the
    convolution layer ``index`` (each layer's pages follow the last's)."""
    page = jnp.take_along_axis(
        tables, jnp.maximum(pos, 0) // cfg.decode_page_size, axis=1)
    return page + index * cfg.decode_num_pages


def _read_state(cfg: GPTConfig, pool, tables, wpos, index):
    """``z`` at the ``L - 1`` positions before ``wpos`` ``[b]``, oldest
    first, zeros before position 0: ``[b, L - 1, h]``."""
    rows = state_rows(cfg)
    pos = wpos[:, None] - jnp.arange(rows, 0, -1, dtype=jnp.int32)[None, :]
    held = pool[_pages(cfg, tables, pos, index), jnp.maximum(pos, 0) % rows]
    return jnp.where((pos >= 0)[..., None], held, 0)


def _write_state(cfg: GPTConfig, pool, tables, wpos, valid, index, z):
    """``pool`` with ``z`` ``[b, s, h]`` of the call's positions ``wpos +
    [0, s)`` written where they stay the state: of the rows that are tokens
    (``valid``, a prefix of each lane's rows), the last ``L - 1`` of every
    page touched. The others write nothing (their index lies past the
    pool)."""
    rows, ps = state_rows(cfg), cfg.decode_page_size
    b, s, h = z.shape
    max_len = cfg.decode_cache_len or cfg.max_position_embeddings
    pos = wpos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    last = wpos + valid.sum(axis=1).astype(jnp.int32) - 1         # [b]
    page_end = (pos // ps + 1) * ps - 1
    keep = (valid & (pos + rows > jnp.minimum(last[:, None], page_end))
            & (pos < max_len))
    page = jnp.where(keep, _pages(cfg, tables, jnp.minimum(pos, max_len - 1),
                                  index), pool.shape[0])
    return pool.at[page.reshape(-1), (pos % rows).reshape(-1)].set(
        z.reshape(b * s, h), mode="drop")
