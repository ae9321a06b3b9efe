"""Pipeline parallelism — GSPMD time-stepped pipeline.

Replaces the reference's PipelineLayer machinery (/root/reference/ppfleetx/
models/language_model/gpt/dygraph/hybrid_model.py:909-1096
``GPTForPretrainingPipe``: LayerDesc flattening, SharedLayerDesc embedding
tying, seg_method, fleet's 1F1B runtime with NCCL p2p send/recv) with an
SPMD formulation:

- decoder layers are stacked [pp, layers_per_stage, ...]; the leading axis
  carries the 'stage' logical name and is sharded over the ``pp`` mesh axis,
- the activation state buffer [pp, micro_bs, s, h] is likewise pp-sharded,
- one pipeline tick = roll(state, 1, axis=0) (XLA lowers to a collective
  permute between neighboring stages — the p2p send/recv) + a stage-vmapped
  layer application (each pp shard runs only its own stage's layers),
- the time loop is an nn.scan of num_microbatches + pp - 1 ticks with
  parameters broadcast across time.

Shared-embedding tying (reference SharedLayerDesc, hybrid_model.py:1012,1059)
falls out for free: embedding and logits head reference the same variable
outside the pipelined stack, and GSPMD sums its gradient contributions.

Gradient flow is standard autodiff through the scan; per-stage remat bounds
activation memory (the reference's 1F1B memory schedule is a runtime
scheduling choice NCCL needs; under XLA the scan + remat achieves the same
peak-memory class).

Virtual/interleaved stages (reference ``num_virtual_pipeline_stages``,
hybrid_model.py:1095): with ``virtual_pp=v`` each physical stage owns v
layer chunks and a microbatch traverses the stage ring v times. Two
schedules exist:

- **streamed** (default, ``FLEETX_VPP_STREAM=1``): ONE scan over a
  [v*pp, ...] state buffer — every chunk's stage applies in parallel each
  tick, chunk j+1 consumes chunk j's emission stream at pp-tick skew
  (row j*pp+pp-1 rolls straight into row (j+1)*pp), and the whole
  computation drains once: M + v*pp - 1 ticks total instead of the
  sequential schedule's v*(M + pp - 1). For M >> v*pp that is ~v x fewer
  scan ticks (collective permutes, loop iterations, per-tick dispatch),
  bought with dead-row work during the longer single fill/drain —
  not measured on the chip: no benchmark cell runs a pipeline (PERF.md).
  The param layout equals the plain pipe layout with v*pp stage rows
  (row g holds global chunk g = layers [g*lpc, (g+1)*lpc)), so the
  remap helpers and checkpoint converters need no new scopes.
- **sequential** (``FLEETX_VPP_STREAM=0``): chunk pass j is its own scan
  with statically selected chunk parameters, chained on pass j-1's
  emission stream — pass j fully drains (pp-1 dead ticks) before pass
  j+1 starts.

Both match the reference's math exactly (same layer order per
microbatch); the reference's interleaved 1F1B remains a *runtime*
schedule that a single statically-scheduled XLA program does not
express. Raising ``num_microbatches`` stays the primary bubble lever
(microbatches stream through one compiled scan, no host loop), and
virtual stages keep their other role: finer-grained layer placement so
each stage's weights/activations split v ways.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional

import jax.numpy as jnp
from flax import linen as nn

__all__ = [
    "PipelinedStack",
    "sequential_params_to_pipeline",
    "pipeline_params_to_sequential",
    "maybe_pipeline_params_to_sequential",
    "stream_chunks_default",
]


def stream_chunks_default() -> bool:
    """Whether virtual-pp chunks run the fused streamed schedule (module
    docstring). One resolution point so PipelinedStack, the param remap,
    and the init-via-sequential path can never disagree on layout."""
    return os.environ.get("FLEETX_VPP_STREAM", "1") == "1"

_SEQ_PREFIX = "gpt/layers/layer/"
_PIPE_PREFIX = "gpt/layers/pipe/stages/layers/layer/"
# single source of truth for the virtual-chunk scope name: the scan scope
# in PipelinedStack, the forward remap, the inverse regex, and the layout
# detector all derive from this template
_VPIPE_SCOPE = "pipe_chunk{j}"
_VPIPE_RE = "gpt/layers/" + _VPIPE_SCOPE + "/stages/layers/layer/"


def _flatten(variables):
    import flax

    params = variables["params"] if "params" in variables else variables
    flat = flax.traverse_util.flatten_dict(flax.core.unfreeze(params), sep="/")
    return flat, ("params" in variables)


def _unflatten(flat, wrap):
    import flax

    tree = flax.traverse_util.unflatten_dict(flat, sep="/")
    return {"params": tree} if wrap else tree


def sequential_params_to_pipeline(variables, pp: int, virtual_pp: int = 1,
                                  stream: Optional[bool] = None):
    """Remap a sequential-scan param tree (gpt/layers/layer/* with leading
    [num_layers] axis) to the pipeline layout: [pp, layers_per_stage]
    leading axes under gpt/layers/pipe/... — or, with virtual stages,
    either the STREAMED layout (one [v*pp, layers_per_chunk] tree under
    the same pipe scope, row g = global chunk g) or the sequential-chunk
    layout (one [pp, layers_per_chunk] tree per chunk pass, stage p of
    pass j holding global chunk j*pp + p — the reference's interleaved
    chunk placement). ``stream=None`` resolves from FLEETX_VPP_STREAM so
    the remap always matches what PipelinedStack will build."""
    if stream is None:
        stream = stream_chunks_default()
    if virtual_pp > 1 and stream:
        # streamed layout == the plain pipe layout with v*pp stage rows
        return sequential_params_to_pipeline(variables, pp * virtual_pp, 1)
    flat, wrap = _flatten(variables)
    out = {}
    for k, val in flat.items():
        if not k.startswith(_SEQ_PREFIX):
            out[k] = val
            continue
        suffix = k[len(_SEQ_PREFIX):]
        L = val.shape[0]
        if virtual_pp <= 1:
            out[_PIPE_PREFIX + suffix] = val.reshape(
                (pp, L // pp) + val.shape[1:])
            continue
        lpc = L // (pp * virtual_pp)
        # [L,...] -> [v*pp, lpc, ...]; pass j stage p = global chunk j*pp+p
        chunks = val.reshape((virtual_pp * pp, lpc) + val.shape[1:])
        for j in range(virtual_pp):
            out[_VPIPE_RE.format(j=j) + suffix] = chunks[
                j * pp:(j + 1) * pp]
    return _unflatten(out, wrap)


def pipeline_params_to_sequential(variables):
    """Inverse of :func:`sequential_params_to_pipeline` (plain and virtual
    layouts): merge the chunk/stage axes back into [num_layers] so a
    pipeline-trained checkpoint can drive the scan decode/eval path."""
    import re

    flat, wrap = _flatten(variables)
    out = {}
    vchunks = {}
    pattern = re.compile(
        "^" + re.escape(_VPIPE_RE.format(j="@")).replace("@", r"(\d+)") + "(.*)"
    )
    for k, v in flat.items():
        m = pattern.match(k)
        if m:
            j, suffix = int(m.group(1)), m.group(2)
            vchunks.setdefault(suffix, {})[j] = v
        elif k.startswith(_PIPE_PREFIX):
            nk = _SEQ_PREFIX + k[len(_PIPE_PREFIX):]
            out[nk] = v.reshape((v.shape[0] * v.shape[1],) + v.shape[2:])
        else:
            out[k] = v
    for suffix, by_chunk in vchunks.items():
        parts = [by_chunk[j] for j in sorted(by_chunk)]
        stacked = jnp.concatenate(parts, axis=0)  # [v*pp, lpc, ...]
        out[_SEQ_PREFIX + suffix] = stacked.reshape(
            (stacked.shape[0] * stacked.shape[1],) + stacked.shape[2:])
    return _unflatten(out, wrap)


def maybe_pipeline_params_to_sequential(variables):
    """Remap iff the tree holds pipeline-layout params; no-op otherwise."""
    flat, _ = _flatten(variables)
    marker = "/" + _VPIPE_SCOPE.format(j="")
    if any(k.startswith(_PIPE_PREFIX) or marker in k for k in flat):
        return pipeline_params_to_sequential(variables)
    return variables


class _StageStack(nn.Module):
    """layers_per_stage decoder layers applied in sequence (one stage)."""

    cfg: Any
    layer_cls: Callable
    layers_per_stage: int

    @nn.compact
    def __call__(self, x, attn_mask, deterministic):
        stack = nn.scan(
            self.layer_cls,
            variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True, "dropout": True},
            in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
            length=self.layers_per_stage,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )
        x, _ = stack(self.cfg, name="layers")(x, attn_mask, deterministic, False)
        return x


class _PipelineTick(nn.Module):
    """One pipeline time step: shift, inject, apply all stages in parallel.

    ``state``/``inject`` are (x, mask) pairs when a per-example attention
    mask streams with its microbatch (mask=None otherwise — batch-agnostic
    masks broadcast instead of streaming)."""

    cfg: Any
    layer_cls: Callable
    pp: int
    layers_per_stage: int

    @nn.compact
    def __call__(self, state, inject, attn_mask, deterministic):
        # shift: stage k receives stage k-1's output (ppermute over 'pp');
        # stage 0 receives the next microbatch
        x_state, m_state = state
        x_inj, m_inj = inject
        shifted = jnp.roll(x_state, 1, axis=0).at[0].set(x_inj)
        if m_state is not None:
            m_shifted = jnp.roll(m_state, 1, axis=0).at[0].set(m_inj)
            stage_mask_axis = 0
        else:
            m_shifted = attn_mask  # batch-agnostic: same for every stage
            stage_mask_axis = None
        stages = nn.vmap(
            _StageStack,
            in_axes=(0, stage_mask_axis, None),
            out_axes=0,
            variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True, "dropout": True},
            metadata_params={nn.PARTITION_NAME: "stage"},
        )
        shifted = nn.with_logical_constraint(
            shifted, ("stage", "act_batch", "act_seq", "act_embed")
        )
        new_state = stages(
            self.cfg, self.layer_cls, self.layers_per_stage, name="stages"
        )(shifted, m_shifted, deterministic)
        new_state = nn.with_logical_constraint(
            new_state, ("stage", "act_batch", "act_seq", "act_embed")
        )
        return (new_state, m_shifted if m_state is not None else None), \
            new_state[self.pp - 1]


class PipelinedStack(nn.Module):
    """Drop-in decoder stack for pp>1. Input [b, s, h]; b is split into
    ``num_microbatches`` microbatches that stream through the stages
    ``virtual_pp`` times (once per layer chunk). ``stream`` selects the
    fused one-scan virtual-chunk schedule (module docstring); None
    resolves from FLEETX_VPP_STREAM."""

    cfg: Any
    layer_cls: Callable
    pp: int
    num_microbatches: int
    virtual_pp: int = 1
    stream: Optional[bool] = None

    @nn.compact
    def __call__(self, x, attn_mask=None, deterministic=True):
        cfg = self.cfg
        pp = self.pp
        M = self.num_microbatches
        b, s, h = x.shape
        # per-example masks ([b, ...]) stream through the stage buffer with
        # their microbatch; batch-agnostic masks (leading dim 1 or None)
        # broadcast to every stage
        per_example = (
            attn_mask is not None and attn_mask.ndim >= 1
            and attn_mask.shape[0] == b and b > 1
        )
        if (attn_mask is not None and not per_example
                and attn_mask.shape[0] != 1):
            raise ValueError(
                "attn_mask leading dim must be the batch or 1, got "
                f"{attn_mask.shape} for batch {b}"
            )
        v = max(self.virtual_pp, 1)
        if cfg.num_layers % (pp * v):
            raise ValueError(
                f"num_layers {cfg.num_layers} % (pp {pp} * virtual {v}) != 0")
        if b % M:
            raise ValueError(f"batch {b} % num_microbatches {M} != 0")
        layers_per_stage = cfg.num_layers // (pp * v)
        mb = b // M
        streamed = self.stream if self.stream is not None \
            else stream_chunks_default()
        # streamed schedule: one logical pipe of v*pp chunk rows, drained
        # once; sequential schedule: v chained passes of pp rows each
        rows = pp * v if (v > 1 and streamed) else pp

        micro = x.reshape(M, mb, s, h)
        # pad the injection stream with rows-1 dead ticks to drain the pipe
        pad = jnp.zeros((rows - 1, mb, s, h), x.dtype)
        inject_stream = jnp.concatenate([micro, pad], axis=0)

        state0 = jnp.zeros((rows, mb, s, h), x.dtype)
        if per_example:
            m = attn_mask.reshape((M, mb) + attn_mask.shape[1:])
            m_pad = jnp.zeros((rows - 1,) + m.shape[1:], m.dtype)
            m_stream = jnp.concatenate([m, m_pad], axis=0)
            m_state0 = jnp.zeros((rows,) + m.shape[1:], m.dtype)
            bcast_mask = None
        else:
            m_stream = None
            m_state0 = None
            bcast_mask = attn_mask

        def chunk_pass(name, inj_stream):
            tick = nn.scan(
                _PipelineTick,
                variable_broadcast="params",
                variable_axes={"intermediates": 0},
                split_rngs={"params": False, "dropout": True},
                in_axes=((0, 0 if per_example else nn.broadcast), nn.broadcast,
                         nn.broadcast),
                out_axes=0,
                length=M + rows - 1,
            )
            _, emitted = tick(
                cfg, self.layer_cls, rows, layers_per_stage, name=name
            )((state0, m_state0), (inj_stream, m_stream), bcast_mask,
              deterministic)
            # microbatch m exits the last row at tick m + rows - 1
            return emitted[rows - 1:]

        if rows != pp or v == 1:
            # plain pipe (v == 1) and the streamed fusion share one scan
            # AND one param scope: the streamed layout IS the plain layout
            # with v*pp stage rows (row g = global chunk g), so checkpoint
            # remaps need no extra scopes
            out = chunk_pass("pipe", inject_stream)
            return out.reshape(b, s, h)

        stream = inject_stream
        for j in range(v):
            out = chunk_pass(_VPIPE_SCOPE.format(j=j), stream)
            if j < v - 1:
                stream = jnp.concatenate([out, pad], axis=0)
        return out.reshape(b, s, h)
