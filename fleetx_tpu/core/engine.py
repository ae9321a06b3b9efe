"""Trainer — the TPU-native engine (reference EagerEngine,
/root/reference/ppfleetx/core/engine/eager_engine.py:41-820).

Where the reference wraps models in fleet.distributed_model and hand-drives
micro-batch loops, AMP scalers, and sharding wrappers, this engine compiles
ONE jitted train step: grad accumulation is a `lax.scan` inside it, parameter/
optimizer sharding is declared via NamedShardings derived from logical-axis
rules (ZeRO stage 1/2 = fsdp-sharded optimizer state, stage 3 = fsdp-sharded
params too), and every collective is inserted by GSPMD. Pipeline-parallel
configs route the forward through the stage axis (fleetx_tpu/parallel/
pipeline.py). Checkpointing is Orbax (async-capable, preemption-safe) with
step/epoch/consumed-samples resume parity (eager_engine.py:634-725).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from fleetx_tpu.models.module import BasicModule
from fleetx_tpu.obs import http as obs_http
from fleetx_tpu.obs.events import emit as obs_emit
from fleetx_tpu.obs.registry import get_registry
from fleetx_tpu.obs.tracing import span
from fleetx_tpu.optims.lr_scheduler import build_lr_scheduler
from fleetx_tpu.optims.optimizer import build_optimizer
from fleetx_tpu.parallel import env as dist_env
from fleetx_tpu.parallel.mesh import DATA_AXES, MeshConfig, build_mesh, use_mesh
from fleetx_tpu.parallel.sharding import (
    make_rules, param_shardings, zero_update_spec,
)
from fleetx_tpu.resilience.elastic import ElasticMeshMismatch, validate_restore_mesh
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.utils.hw import peak_flops_per_chip
from fleetx_tpu.utils.log import logger
from fleetx_tpu.utils.xla_flags import apply_overlap_flags

__all__ = ["CheckpointUnrestorable", "SentryAbort", "Trainer", "TrainState"]


class CheckpointUnrestorable(RuntimeError):
    """Checkpoints existed but every candidate failed verified restore
    (all quarantined). Distinct from the no-checkpoint-yet case — which
    ``load()`` reports as ``False`` so a first launch can start fresh —
    because resuming a real run from scratch must fail loudly."""


class SentryAbort(RuntimeError):
    """FLEETX_SENTRY_MAX_SKIPS consecutive train steps were skipped by the
    step sentry — the data stream (or the optimization itself) is
    producing nothing but anomalies, so the run stops cleanly instead of
    spinning. Params/opt_state are still the last healthy step's (skipped
    steps never touch them) and a checkpoint is written before raising."""


class TrainState(struct.PyTreeNode):
    """step + params + optimizer state (+ module extra state), the pytree
    threaded through the jitted train step."""
    step: jax.Array
    params: Any
    opt_state: Any
    # module-owned non-parameter training state (e.g. MoCo's momentum
    # encoder + negative queue); None for ordinary modules
    extra: Any = None


def make_grad_fn(module: "BasicModule", accum: int):
    """(params, batch, rng) -> (mean loss, mean grads).

    With accum > 1 the batch's leading axis is [accum, micro, ...] and a
    lax.scan accumulates microbatch grads — the in-jit replacement for the
    reference's host-side micro-batch loop (eager_engine.py:442-483)."""

    def loss_for_micro(params, micro, rng):
        # central QAT hooks: STE weight fake-quant INSIDE the grad
        # computation, and (when configured) activation fake-quant on every
        # Dense input via the module's interceptor context — so every module
        # family quantizes identically (no per-module wiring)
        with module.act_quant_ctx():
            loss, metrics = module.loss_fn(
                module.maybe_fake_quant(params), micro, rng, train=True
            )
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_for_micro, has_aux=True)

    def compute(params, batch, rng):
        if accum == 1:
            (loss, _), grads = grad_fn(params, batch, rng)
            return loss, grads

        def micro_step(carry, micro):
            acc_grads, acc_loss, i = carry
            mrng = jax.random.fold_in(rng, i)
            (loss, _), grads = grad_fn(params, micro, mrng)
            acc_grads = jax.tree.map(jnp.add, acc_grads, grads)
            return (acc_grads, acc_loss + loss, i + 1), None

        zero = _rebox_like(
            jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), _unbox(params)),
            params,
        )
        (grads, loss_sum, _), _ = jax.lax.scan(micro_step, (zero, 0.0, 0), batch)
        grads = jax.tree.map(lambda g: g / accum, grads)
        return loss_sum / accum, grads

    return compute


def make_grad_fn_extra(module: "BasicModule", accum: int):
    """(params, extra, batch, rng) -> (loss, grads, aux, new_extra) for
    modules carrying extra train state (MoCo momentum encoder/queue).
    Extra state updates are inherently sequential, so microbatch grad
    accumulation is not supported on this path."""
    if accum != 1:
        raise NotImplementedError(
            "accumulate_steps > 1 is not supported for modules with extra "
            "state (the queue/EMA update order would be ambiguous)"
        )

    def loss_for(params, extra, batch, rng):
        with module.act_quant_ctx():
            loss, aux, new_extra = module.loss_fn_extra(
                module.maybe_fake_quant(params), extra, batch, rng, train=True
            )
        return loss, (aux, new_extra)

    grad_fn = jax.value_and_grad(loss_for, has_aux=True)

    def compute(params, extra, batch, rng):
        (loss, (aux, new_extra)), grads = grad_fn(params, extra, batch, rng)
        return loss, grads, aux, new_extra

    return compute


from flax.core import meta as flax_meta


def _is_box(x):
    return isinstance(x, flax_meta.AxisMetadata)


def _unbox(tree):
    """Strip flax axis-metadata boxes (Partitioned / LogicallyPartitioned),
    keeping raw arrays."""
    return jax.tree.map(
        lambda x: x.unbox() if _is_box(x) else x, tree, is_leaf=_is_box
    )


def _rebox_like(raw_tree, boxed_tree):
    """Re-wrap raw arrays with the metadata boxes of a reference tree."""
    # prefix-tree map: raw leaves pair with the boxed tree's metadata nodes
    return jax.tree.map(
        lambda new, old: old.replace_boxed(new) if _is_box(old) else new,
        raw_tree,
        boxed_tree,
    )


class Trainer:
    """The engine: builds mesh/shardings/optimizer, compiles the
    train/eval/predict steps, owns fit/evaluate/save/load (see module
    docstring)."""
    def __init__(self, cfg, module: BasicModule, mode: str = "train"):
        self.cfg = cfg
        self.module = module
        self.mode = mode

        eng = cfg.Engine
        glb = cfg.Global
        self.max_steps = eng.max_steps
        self.num_train_epochs = eng.num_train_epochs
        self.accumulate_steps = eng.accumulate_steps or 1
        dist_pp = ((cfg.Distributed or {}).get("pp_degree")) or 1
        if dist_pp > 1:
            # the pipelined model consumes the full local batch and streams
            # microbatches itself; no outer accumulation scan
            self.accumulate_steps = 1
        self.logging_freq = eng.logging_freq
        self.eval_freq = eng.eval_freq
        self.eval_iters = eng.eval_iters
        self.save_steps = (eng.save_load or {}).get("save_steps", 1000)
        self.output_dir = (eng.save_load or {}).get("output_dir", "./output")

        dist = cfg.Distributed or {}
        self.mesh_cfg = MeshConfig.from_dist_config(dist)
        # comms/compute overlap flags must land in XLA_FLAGS before the
        # backend initializes (build_mesh below touches devices); env-gated
        # and TPU-only by default — see utils/xla_flags.py
        apply_overlap_flags()
        self.mesh = build_mesh(self.mesh_cfg)
        # ZeRO weight-update sharding (docs/PERFORMANCE.md "Training
        # overlap", arxiv 2004.13336): reduce-scatter grads over the
        # data-parallel axes, run optax + apply_updates + the sentry gnorm
        # on the 1/N shard, all-gather updated params. On by default
        # whenever a data-parallel axis exists; the optimizer state then
        # LIVES sharded between steps (out_shardings), cutting its HBM by
        # the dp*fsdp factor even at sharding stage 1/2.
        self._zero_update = (
            os.environ.get("FLEETX_ZERO_UPDATE", "1") == "1"
            and self.mesh_cfg.dp * self.mesh_cfg.fsdp > 1
        )
        self._zero_param_shardings = None
        from fleetx_tpu.parallel.dap import dap_rules

        self.rules = make_rules(
            sharding_stage=self.mesh_cfg.sharding_stage,
            sequence_parallel=bool((cfg.Model or {}).get("sequence_parallel")),
            context_parallel=self.mesh_cfg.cp > 1,
        ) + dap_rules()  # folding-trunk axial layout rides the cp axis

        self.root_key = dist_env.set_seed(glb.seed)
        self.lr_schedule = build_lr_scheduler((cfg.Optimizer or {}).get("lr", 1e-4))
        self.tx = build_optimizer(
            cfg.Optimizer or {}, self.lr_schedule,
            weight_decay_mask=module.weight_decay_mask(),
        )

        self._compiled = {}
        self._compiled_raw = {}
        self._abstract_args = {}  # name -> (args, kwargs) avals of first call
        self._restored_step = None
        self._preempted = False
        self._prev_sigterm = None
        self.state: Optional[TrainState] = None
        self.start_epoch = 0
        self._cur_epoch = 0  # epoch the fit loop is currently inside
        self.consumed_samples = 0
        self._ckpt_mgr = None
        # step-shadow snapshot checkpointing (FLEETX_CKPT_ASYNC_SNAPSHOT):
        # save() copies state device->host in the step path and hands the
        # host tree to a background uploader thread, so the step only stalls
        # for the D2H copy. Single-process only: multi-host orbax saves are
        # collective, and a per-host thread would skew the barrier.
        self._ckpt_async = (
            os.environ.get("FLEETX_CKPT_ASYNC_SNAPSHOT", "0") == "1"
            and jax.process_count() == 1)
        self._upload_thread = None  # in-flight snapshot uploader

        # step sentry (docs/RESILIENCE.md): finite/spike check folded into
        # the jitted train step; anomalous steps are skipped, not applied.
        # All thresholds are static at trace time (env read here, once).
        self._sentry_enabled = os.environ.get("FLEETX_SENTRY", "1") == "1"
        self._sentry_loss_max = float(os.environ.get("FLEETX_SENTRY_LOSS_MAX", 0) or 0)
        self._sentry_gnorm_max = float(os.environ.get("FLEETX_SENTRY_GNORM_MAX", 0) or 0)
        self._sentry_max_skips = int(os.environ.get("FLEETX_SENTRY_MAX_SKIPS", 25) or 25)
        self.sentry_skips = 0  # total skipped steps this run
        self._sentry_consecutive = 0
        self.save_failures = 0  # periodic saves that failed (run survived)
        self._last_saved_meta = None  # (step, epoch, consumed_samples)

        # observability (docs/OBSERVABILITY.md): live training gauges on
        # the process registry (FLEETX_OBS_PORT exposes them). Gauges are
        # process-wide last-writer-wins — one Trainer per process is the
        # production shape; counters accumulate across Trainer instances
        # (per-run numbers stay on self.sentry_skips/self.save_failures).
        obs_http.maybe_start_from_env()
        reg = get_registry()
        self._obs_steps = reg.counter(
            "fleetx_train_steps_total", "Optimizer steps applied")
        self._obs_sentry_skips = reg.counter(
            "fleetx_train_sentry_skips_total",
            "Train steps skipped by the anomaly sentry")
        self._obs_save_failures = reg.counter(
            "fleetx_train_save_failures_total",
            "Checkpoint saves that failed (run survived)")
        self._obs_quarantines = reg.counter(
            "fleetx_train_checkpoint_quarantines_total",
            "Corrupt checkpoint steps quarantined during restore")
        self._obs_loss = reg.gauge(
            "fleetx_train_loss", "Loss averaged over the last logging window")
        self._obs_lr = reg.gauge(
            "fleetx_train_learning_rate", "Current learning rate")
        self._obs_step_time = reg.histogram(
            "fleetx_train_step_seconds",
            "Per-step wall clock (logging-window mean samples)")
        self._obs_tokens_per_s = reg.gauge(
            "fleetx_train_tokens_per_second",
            "Training throughput over the last logging window")
        self._obs_mfu = reg.gauge(
            "fleetx_train_mfu",
            "Model-FLOPs utilization: cost_analysis flops / step time / "
            "peak chip FLOPs")
        self._obs_hbm_bytes = reg.gauge(
            "fleetx_train_step_hbm_bytes",
            "Compiled train step per-device HBM bytes accessed "
            "(cost_analysis static estimate)")
        self._obs_opt_bytes = reg.gauge(
            "fleetx_train_opt_state_bytes",
            "Optimizer-state bytes resident per device (ZeRO update "
            "sharding shrinks this by the dp*fsdp factor)")
        self._obs_ckpt_seconds = reg.histogram(
            "fleetx_ckpt_save_seconds",
            "Checkpoint save duration; phase=blocking is the step-path "
            "stall (D2H snapshot under FLEETX_CKPT_ASYNC_SNAPSHOT, the "
            "whole write otherwise), phase=total includes the async upload",
            labelnames=("phase",))
        self._obs_ckpt_bytes = reg.gauge(
            "fleetx_ckpt_bytes",
            "Bytes of train state in the last checkpoint snapshot")
        # expose every instrument at zero immediately (matching the
        # serving metrics, whose children exist from __init__): a healthy
        # run must scrape as 0, not as absent-looking-like-broken
        for fam in (self._obs_steps, self._obs_sentry_skips,
                    self._obs_save_failures, self._obs_quarantines,
                    self._obs_loss, self._obs_lr, self._obs_step_time,
                    self._obs_tokens_per_s, self._obs_mfu,
                    self._obs_hbm_bytes, self._obs_opt_bytes,
                    self._obs_ckpt_bytes):
            fam.labels()
        for phase in ("blocking", "total"):
            self._obs_ckpt_seconds.labels(phase=phase)
        self._flops_per_step = None  # lazy; False = no MFU on this run
        self._peak_flops = None  # set with _flops_per_step
        self._hbm_bytes_per_step = self._collectives_per_step = None  # as _flops_per_step
        self._cost_cache = {}  # name -> (abstract-args spec, Compiled, cost)

    # ------------------------------------------------------------------ init
    def init_state(self, sample_batch: Dict[str, np.ndarray]) -> TrainState:
        """Create sharded params + optimizer state directly on the mesh
        (never materializing an unsharded copy on one device). One
        ``train.build`` span (docs/OBSERVABILITY.md "Start-up"), with
        ``restored`` where the run's own resumable step was loaded."""
        with span("train.build") as at:
            state = self._init_state(sample_batch)
            if self._restored_step is not None:
                at["restored"] = True
        return state

    def _init_state(self, sample_batch):
        micro = self._microbatch(sample_batch)

        def _init(rng):
            variables = self.module.init_params(rng, micro)
            params = variables["params"] if "params" in variables else variables
            opt_state = self.tx.init(_unbox(params))
            extra = self.module.init_extra_state(_unbox(params), micro)
            return TrainState(
                step=jnp.zeros((), jnp.int32), params=params,
                opt_state=opt_state, extra=extra,
            )

        import flax.linen as nn

        with use_mesh(self.mesh), nn.logical_axis_rules(list(self.rules)):
            abstract = jax.eval_shape(_init, self.root_key)
        shardings = self._state_shardings(abstract)
        with use_mesh(self.mesh), nn.logical_axis_rules(list(self.rules)):
            init_fn = jax.jit(_init, out_shardings=shardings)
            self.state = init_fn(self.root_key)
        self._state_sharding_tree = shardings
        n_params = sum(
            int(np.prod(x.shape)) for x in jax.tree.leaves(_unbox(self.state.params))
        )
        logger.info(
            "initialized model: %.1fM params on mesh %s",
            n_params / 1e6,
            dict(self.mesh.shape),
        )
        self.n_params = n_params
        self._obs_opt_bytes.set(float(self.opt_state_device_bytes()))
        resumable = False
        if os.path.isdir(os.path.join(self.output_dir, "checkpoints")):
            resumable = self._ckpt_manager().latest_step() is not None
        if resumable:
            # restore the run's own checkpoint right here (don't just skip
            # the pretrained load: callers only invoke load() when ckpt_dir
            # is set, and a preempted run must not resume from random init).
            # If every checkpoint fails verified restore, load() raises
            # CheckpointUnrestorable (resuming from scratch must be loud);
            # the False branch only covers a checkpoint dir that emptied
            # between the latest_step() probe and the restore.
            loaded = None
            if not self.load():
                loaded = self.module.load_pretrained(_unbox(self.state.params))
        else:
            loaded = self.module.load_pretrained(_unbox(self.state.params))
        if loaded is not None:
            boxed = _rebox_like(loaded, self.state.params)
            boxed = jax.device_put(boxed, self._state_sharding_tree.params)
            self.state = self.state.replace(params=boxed)
        return self.state

    @staticmethod
    def _path_keys(path) -> tuple:
        """Normalize a jax key path to a tuple of strings."""
        out = []
        for k in path:
            for attr in ("key", "idx", "name"):
                if hasattr(k, attr):
                    out.append(str(getattr(k, attr)))
                    break
            else:
                out.append(str(k))
        return tuple(out)

    def _state_shardings(self, abstract: TrainState):
        ps = param_shardings(abstract.params, self.mesh, self.rules)

        if self._zero_update:
            # weight-update shard layout of every param: the in-jit
            # sharding constraints of the train step and (below) the
            # resident layout of the optimizer state
            flat_unboxed, treedef = jax.tree_util.tree_flatten(
                _unbox(abstract.params))
            zero_flat = [
                NamedSharding(
                    self.mesh,
                    zero_update_spec(sh.spec, leaf.shape, self.mesh))
                for leaf, sh in zip(flat_unboxed, jax.tree.leaves(ps))
            ]
            self._zero_param_shardings = jax.tree_util.tree_unflatten(
                treedef, zero_flat)

        # Index param specs by their *tree path*, and match optimizer-state
        # leaves by path suffix: optax moment trees (mu/nu, ...) mirror the
        # param tree under transform-specific prefixes, so the param path is
        # always a suffix of the moment path. Matching by path (not by
        # (shape, dtype)) keeps two same-shaped params with different
        # shardings from colliding.
        flat_params = jax.tree_util.tree_flatten_with_path(_unbox(abstract.params))[0]
        flat_specs = [s.spec for s in jax.tree.leaves(ps)]
        spec_by_path = {}
        for (path, leaf), spec in zip(flat_params, flat_specs):
            spec_by_path[self._path_keys(path)] = (leaf.shape, spec)

        # `sharding_offload` (reference sharding.py CPU offload) = optimizer
        # moments live in host memory; XLA streams them across PCIe at the
        # update. Only TPU backends lower the placement annotation.
        offload = bool(getattr(self.mesh_cfg, "sharding_offload", False))
        if offload and jax.default_backend() != "tpu":
            raise NotImplementedError(
                "Distributed.sharding.sharding_offload=True needs a TPU "
                "backend (host memory placement is not lowered on "
                f"{jax.default_backend()!r})"
            )
        def shard_like_param(path, leaf, kind):
            """Moment tensors mirror the matching param sharding; ZeRO-1/2
            additionally shards moments over fsdp (stage 3 already shards the
            params themselves). Scalars and unmatched leaves replicate."""
            if not hasattr(leaf, "shape") or leaf.ndim == 0:
                return NamedSharding(self.mesh, P(), **kind)
            keys = self._path_keys(path)
            spec = None
            for start in range(len(keys)):
                hit = spec_by_path.get(keys[start:])
                if hit is not None and hit[0] == leaf.shape:
                    spec = hit[1]
                    break
            if spec is None:
                return NamedSharding(self.mesh, P(), **kind)
            if self._zero_update:
                # moments live on the weight-update shard (dp AND fsdp
                # folded in) — strictly more sharded than the stage-1/2
                # fsdp-only layout below
                spec = zero_update_spec(spec, leaf.shape, self.mesh)
            elif self.mesh_cfg.sharding_stage in (1, 2) and self.mesh_cfg.fsdp > 1:
                spec = self._add_fsdp(spec, leaf.shape)
            return NamedSharding(self.mesh, spec, **kind)

        opt_kind = {"memory_kind": "pinned_host"} if offload else {}
        opt_sh = jax.tree_util.tree_map_with_path(
            lambda p, l: shard_like_param(p, l, opt_kind), abstract.opt_state
        )
        # extra state (momentum encoders, queues): same path-matching rule —
        # param-shaped leaves under a mirrored path get the param sharding,
        # everything else replicates. Always on device: extra state feeds the
        # forward pass, so host offload would stall every step.
        extra_sh = (
            None if abstract.extra is None
            else jax.tree_util.tree_map_with_path(
                lambda p, l: shard_like_param(p, l, {}), abstract.extra
            )
        )
        return TrainState(
            step=NamedSharding(self.mesh, P()), params=ps, opt_state=opt_sh,
            extra=extra_sh,
        )

    def _add_fsdp(self, spec: P, shape) -> P:
        if any("fsdp" in (ax if isinstance(ax, tuple) else (ax,)) for ax in spec if ax):
            return spec
        fsdp = self.mesh.shape["fsdp"]
        parts = list(spec) + [None] * (len(shape) - len(spec))
        for i, (ax, dim) in enumerate(zip(parts, shape)):
            if ax is None and dim % fsdp == 0:
                parts[i] = "fsdp"
                return P(*parts)
        return spec

    # ------------------------------------------------------------- train step
    def _build_train_step(self):
        tx = self.tx
        if self.state is not None and self.state.extra is not None:
            grads_fn = make_grad_fn_extra(self.module, self.accumulate_steps)
        else:
            grads_fn = make_grad_fn(self.module, self.accumulate_steps)

        module = self.module
        sentry = self._sentry_enabled
        loss_max = self._sentry_loss_max
        gnorm_max = self._sentry_gnorm_max
        zero_sh = self._zero_param_shardings if self._zero_update else None

        def train_step(state: TrainState, batch, rng):
            params = state.params
            if state.extra is not None:
                loss, grads, aux, new_extra = grads_fn(params, state.extra, batch, rng)
            else:
                loss, grads = grads_fn(params, batch, rng)
                aux, new_extra = {}, None
            raw_grads = _unbox(grads)
            raw_params = _unbox(params)
            with jax.named_scope("optimizer"):
                if zero_sh is not None:
                    # ZeRO update sharding: constraining grads to the update-
                    # shard layout turns the dp/fsdp grad all-reduce into a
                    # reduce-scatter; params slice to the same shard (layout
                    # only, no comms), the whole optax chain + apply_updates
                    # then runs on 1/N elements per device, and the jit's
                    # replicated param out_shardings insert the all-gather —
                    # async under the latency-hiding scheduler (xla_flags.py),
                    # so it floats into the next step's forward.
                    raw_grads = jax.lax.with_sharding_constraint(
                        raw_grads, zero_sh)
                    raw_params = jax.lax.with_sharding_constraint(
                        raw_params, zero_sh)
                updates, new_opt = tx.update(
                    raw_grads, state.opt_state, raw_params
                )
                new_params_raw = optax.apply_updates(raw_params, updates)
                if zero_sh is not None:
                    # keep the post-update tree (and the sentry select below)
                    # on the shard; the gather happens once, at the jit edge
                    new_params_raw = jax.lax.with_sharding_constraint(
                        new_params_raw, zero_sh)
            new_params = _rebox_like(new_params_raw, params)
            if new_extra is not None:
                new_extra = module.post_update_extra(new_params_raw, new_extra)
            with jax.named_scope("optimizer"):
                gnorm = optax.global_norm(raw_grads)
            new_state = TrainState(
                step=state.step + 1, params=new_params, opt_state=new_opt,
                extra=new_extra,
            )
            metrics = {"loss": loss, "grad_norm": gnorm, **aux}
            if sentry:
                # step sentry: a non-finite or spike-over-threshold step is
                # SKIPPED — every state leaf (params, opt_state incl. the
                # optax count, extra) rolls back to the incoming state, so
                # a NaN batch can never poison a later checkpoint. The
                # jnp.where select is the identity when ok, so an anomaly-
                # free run is byte-identical with the sentry on or off.
                with jax.named_scope("sentry"):
                    ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
                    if loss_max > 0:
                        ok &= loss <= loss_max
                    if gnorm_max > 0:
                        ok &= gnorm <= gnorm_max
                    new_state = jax.tree.map(
                        lambda n, o: jnp.where(ok, n, o), new_state, state
                    )
                metrics["sentry_ok"] = ok
            return new_state, metrics

        sh = self._state_sharding_tree
        batch_spec = (
            P(None, DATA_AXES) if self.accumulate_steps > 1 else P(DATA_AXES)
        )
        batch_sh = NamedSharding(self.mesh, batch_spec)
        # no mesh context needed here: jax.jit only traces on first call,
        # which _get() routes through _in_context()'s use_mesh wrapper
        return jax.jit(
            train_step,
            in_shardings=(sh, batch_sh, NamedSharding(self.mesh, P())),
            out_shardings=(sh, NamedSharding(self.mesh, P())),
            donate_argnums=(0,),
        )

    def _build_eval_step(self):
        module = self.module

        def eval_step(state: TrainState, batch):
            params = module.maybe_fake_quant(state.params)
            with module.act_quant_ctx():
                if state.extra is not None:
                    loss, metrics, _ = module.loss_fn_extra(
                        params, state.extra, batch, None, train=False
                    )
                else:
                    loss, metrics = module.loss_fn(params, batch, None,
                                                   train=False)
            return {"loss": loss, **metrics}

        sh = self._state_sharding_tree
        batch_sh = NamedSharding(self.mesh, P(DATA_AXES))
        return jax.jit(
            eval_step,
            in_shardings=(sh, batch_sh),
            out_shardings=NamedSharding(self.mesh, P()),
        )

    def _get(self, name, builder):
        if name not in self._compiled:
            raw = builder()
            self._compiled_raw[name] = raw  # jitted fn, for cost_analysis
            self._compiled[name] = self._in_context(raw, name=name)
        return self._compiled[name]

    def _aot_compiled(self, name="train"):
        """``(avals, Compiled, cost dict)`` of a step that has run, or None.

        jax.jit wrappers expose neither cost_analysis nor the optimized
        HLO; only the AOT Compiled object does. We recorded the abstract
        avals of the first real call, so lower().compile() here hits the
        persistent compilation cache where one is on
        (utils/compile_cache.py) — but even a cache-hit relower costs
        milliseconds, so the result is memoized per compiled-step
        signature (the recorded avals): the per-step mfu/hbm gauges query
        the lowering exactly once."""
        import flax.linen as nn

        fn = self._compiled_raw.get(name)
        spec = self._abstract_args.get(name)
        if fn is None or spec is None:
            return None
        cached = self._cost_cache.get(name)
        if cached is None or cached[0] is not spec:
            args, kwargs = spec
            # same contexts as _in_context: without the logical axis
            # rules, with_logical_constraint silently no-ops and we'd
            # trace (and fully recompile) a differently-sharded program
            # (this jax finds the trace, the lowering and the executable
            # of a step that has run in memory: 3-4 ms on a v5e's host at
            # PR 51, no second lowering)
            with use_mesh(self.mesh), nn.logical_axis_rules(list(self.rules)):
                compiled = fn.lower(*args, **kwargs).compile()
            cached = (spec, compiled, compiled.cost_analysis())
            self._cost_cache[name] = cached
        return cached

    def cost_analysis(self, name="train"):
        """XLA static cost model of a compiled step (flops / bytes
        accessed), None before the step's first call."""
        cached = self._aot_compiled(name)
        return None if cached is None else cached[2]

    def compiled_text(self, name="train") -> Optional[str]:
        """Optimized HLO text of a compiled step (None before its first
        call): what actually runs on the device — e.g. whether the
        attention kernels are Mosaic custom calls or gave way to the XLA
        path (chip_smoke.py asserts the former)."""
        cached = self._aot_compiled(name)
        return None if cached is None else cached[1].as_text()

    def _step_mfu(self, step_time_s: float) -> Optional[float]:
        """Live MFU for the TRAIN log line and the ``fleetx_train_mfu``
        gauge: the compiled train step's XLA flops (``cost_analysis``,
        so remat recompute is included — a hardware utilization number,
        the BENCH records' model-flops MFU stays the cross-config one)
        over ``step_time_s`` and the peak FLOP/s. ``cost_analysis`` runs
        on the SPMD-partitioned PER-DEVICE module, so its flops divide
        by one chip's peak, not the fleet's — the ratio is then mesh-
        size-independent. None ("mfu: -") on a device the peak table
        does not list — every CPU run — and when XLA exposes no flops
        for this step (tried once, then cached)."""
        if self._flops_per_step is None:
            self._flops_per_step = False
            try:
                # the peak first: an unlisted device kind raises before
                # the cost-analysis lowering is paid for nothing
                self._peak_flops = peak_flops_per_chip(jax.devices()[0])
                cost = self.cost_analysis("train")
                flops = float((cost or {}).get("flops", 0.0) or 0.0)
                if flops > 0:
                    self._flops_per_step = flops
            except Exception:  # noqa: BLE001 — observability never aborts
                pass
        if not self._flops_per_step:
            return None
        return (self._flops_per_step / max(step_time_s, 1e-9)
                / self._peak_flops)

    def _step_hbm_bytes(self) -> Optional[float]:
        """Compiled train step's per-device HBM bytes accessed (static
        cost_analysis estimate) for the ``fleetx_train_step_hbm_bytes``
        gauge — tried once, then cached, same contract as the flops."""
        if self._hbm_bytes_per_step is None:
            try:
                cost = self.cost_analysis("train")
                b = float((cost or {}).get("bytes accessed", 0.0) or 0.0)
                self._hbm_bytes_per_step = b if b > 0 else False
            except Exception:  # noqa: BLE001 — observability never aborts
                self._hbm_bytes_per_step = False
        return self._hbm_bytes_per_step or None

    def opt_state_device_bytes(self) -> int:
        """Optimizer-state bytes RESIDENT per device: per-leaf shard shape
        x itemsize — the number the ZeRO update sharding shrinks by the
        dp*fsdp factor (replicated leaves count full size)."""
        total = 0
        for leaf in jax.tree.leaves(self.state.opt_state):
            if not (hasattr(leaf, "shape") and hasattr(leaf, "dtype")):
                continue
            sh = getattr(leaf, "sharding", None)
            if sh is not None and hasattr(sh, "shard_shape"):
                shape = sh.shard_shape(leaf.shape)
            else:
                shape = leaf.shape
            total += int(np.prod(shape, dtype=np.int64)) * leaf.dtype.itemsize
        return total

    def _in_context(self, fn, name=None):
        """Run calls (and hence first-call tracing) inside the mesh + logical
        axis-rules contexts so nn.with_logical_constraint resolves."""
        import flax.linen as nn
        import jax

        def _aval(x):
            if not (hasattr(x, "shape") and hasattr(x, "dtype")):
                return x
            # keep NamedShardings: cost_analysis re-lowers from these avals,
            # and shardingless avals would be a cache MISS (full recompile)
            # of a differently-GSPMD-partitioned program
            sh = getattr(x, "sharding", None)
            if isinstance(sh, jax.sharding.NamedSharding):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
            return jax.ShapeDtypeStruct(x.shape, x.dtype)

        def call(*args, **kwargs):
            if name is not None and name not in self._abstract_args:
                self._abstract_args[name] = jax.tree.map(_aval, (args, kwargs))
            with use_mesh(self.mesh), nn.logical_axis_rules(list(self.rules)):
                return fn(*args, **kwargs)

        return call

    # -------------------------------------------------------------- data prep
    def _microbatch(self, batch):
        """First microbatch slice, host-side, for shape inference. Pipelined
        models consume the full batch (they micro-split internally)."""
        if self.mesh_cfg.pp > 1:
            return {k: np.asarray(v) for k, v in batch.items()}
        micro_total = self._micro_total()
        return {k: np.asarray(v)[:micro_total] for k, v in batch.items()}

    def _micro_total(self):
        glb = self.cfg.Global
        dp_world = self.mesh_cfg.dp * self.mesh_cfg.fsdp
        return glb.micro_batch_size * dp_world

    def _shard_batch(self, batch, for_train=True):
        """Host batch -> device arrays. With grad accum the leading axis
        becomes [accum, micro_total] and the in-jit scan runs over it.

        Single-host feeds the full global batch; multi-host processes each
        feed their contiguous slice (the sampler already sliced it) and the
        global array is assembled per-shard."""
        accum = self.accumulate_steps if for_train else 1
        micro_total = self._micro_total()
        n_proc = jax.process_count()
        out = {}
        for k, v in batch.items():
            arr = np.asarray(v)
            if accum > 1:
                # local rows = micro_total/n_proc per microbatch on this host
                arr = arr.reshape((accum, arr.shape[0] // accum) + arr.shape[1:])
                spec = P(None, DATA_AXES)
            else:
                spec = P(DATA_AXES)
            sharding = NamedSharding(self.mesh, spec)
            if n_proc > 1:
                out[k] = jax.make_array_from_process_local_data(sharding, arr)
            else:
                out[k] = jax.device_put(arr, sharding)
        return out

    # -------------------------------------------------------------------- fit
    def fit(self, train_data: Iterable, valid_data: Optional[Iterable] = None,
            epochs: Optional[int] = None):
        epochs = epochs or self.num_train_epochs
        if self.state is None:
            first = self.module.pretreating_batch(next(iter(train_data)))
            self.init_state(first)
        train_step = self._get("train", self._build_train_step)

        step = int(self.state.step)
        tokens_per_batch = None
        self._profiler_maybe_start(step)
        self._preempted = False  # a fresh fit() must train, not insta-save
        self._install_preemption_handler()
        try:
            self._fit_epochs(train_data, valid_data, epochs, step,
                             tokens_per_batch, train_step)
        finally:
            self._restore_preemption_handler()

    def _fit_epochs(self, train_data, valid_data, epochs, step,
                    tokens_per_batch, train_step):
        for epoch in range(self.start_epoch, epochs):
            self._cur_epoch = epoch  # for emergency saves by outer supervisors
            sampler = getattr(train_data, "batch_sampler", None)
            if sampler is not None and hasattr(sampler, "set_epoch"):
                sampler.set_epoch(epoch)
            dataset = getattr(train_data, "dataset", None)
            if dataset is not None and hasattr(dataset, "set_epoch"):
                dataset.set_epoch(epoch)  # per-epoch re-masking (ERNIE)
            t_last = time.time()
            loss_window = []
            batches = iter(faults.wrap_train_data(train_data))
            while True:
                try:
                    # host data phase: visible in profiler traces next to
                    # the step program (an input-bound run shows up as fat
                    # train.data spans, not mystery gaps)
                    with span("train.data", step=step):
                        batch = next(batches)
                except StopIteration:
                    break
                except Exception:
                    # a dead shard / raising loader mid-epoch: bank the
                    # healthy progress before surfacing the failure, so a
                    # restart resumes here instead of the last periodic save
                    logger.exception(
                        "train data stream raised at step %d; writing an "
                        "emergency checkpoint before re-raising", step,
                    )
                    self._profiler_maybe_stop(summary=False)
                    self._guarded_save(epoch)
                    self.wait_for_checkpoints()
                    raise
                if step >= self.max_steps:
                    break
                if self._preempted:
                    logger.warning(
                        "preemption signal received: checkpointing at step %d "
                        "and exiting fit()", step,
                    )
                    # close the trace first (summary deferred: the grace
                    # window belongs to the checkpoint, not trace parsing)
                    self._profiler_maybe_stop(summary=False)
                    self.save(epoch=epoch)
                    self.wait_for_checkpoints()
                    return
                # elastic failure domain: a matching FLEETX_FAULT_HOST_LOSS
                # plan raises HostLossFault here, BEFORE the step runs — the
                # aborted step's batch was fetched but not applied, so the
                # supervisor's resumed run re-feeds it exactly once
                # (resilience/elastic.py has the recovery loop)
                faults.on_train_step(step)
                with span("train.shard_batch", step=step):
                    batch = self.module.pretreating_batch(batch)
                    if tokens_per_batch is None:
                        # ips accounting: LM batches carry "tokens", encoder/
                        # vision batches "input_ids"/first array respectively
                        arr = batch.get("tokens")
                        if arr is None:
                            arr = batch.get("input_ids")
                        if arr is None:
                            arr = next(iter(batch.values()))
                        tokens_per_batch = int(np.prod(np.asarray(arr).shape))
                    device_batch = self._shard_batch(batch)
                    rng = dist_env.data_rank_key(step)
                # a DISPATCH span: it ends when the step is enqueued; the
                # wait for the device is train.loss_fetch below
                with span("train.step", step=step) as at:
                    if "train" not in self._abstract_args:
                        at["first"] = True  # this call traces and compiles
                    self.state, metrics = train_step(self.state, device_batch,
                                                     rng)
                skipped = False
                if self._sentry_enabled:
                    # the first read of the step's outputs: the host waits
                    # here for the device to finish the step
                    with span("train.loss_fetch", step=step):
                        skipped = not bool(metrics["sentry_ok"])
                if skipped:
                    # skipped step: the batch was consumed from the stream
                    # (consumed_samples advances -> resume won't re-feed it)
                    # but no update was applied, so neither the step counter
                    # nor the per-step rng/lr sequence moves — the applied-
                    # update trajectory stays identical to a run that never
                    # saw this batch.
                    self.consumed_samples += self.cfg.Global.global_batch_size
                    self.sentry_skips += 1
                    self._sentry_consecutive += 1
                    self._obs_sentry_skips.inc()
                    obs_emit("sentry_skip", step=step,
                             loss=float(metrics["loss"]),
                             grad_norm=float(metrics["grad_norm"]),
                             consecutive=self._sentry_consecutive)
                    logger.warning(
                        "sentry: skipped anomalous step %d (loss=%s "
                        "grad_norm=%s; %d skipped total, %d consecutive)",
                        step, float(metrics["loss"]),
                        float(metrics["grad_norm"]), self.sentry_skips,
                        self._sentry_consecutive,
                    )
                    if self._sentry_consecutive >= self._sentry_max_skips:
                        self._profiler_maybe_stop(summary=False)
                        self._guarded_save(epoch)
                        self.wait_for_checkpoints()
                        obs_emit("sentry_abort", step=step,
                                 consecutive=self._sentry_consecutive)
                        raise SentryAbort(
                            f"{self._sentry_consecutive} consecutive train "
                            f"steps skipped by the sentry at step {step} "
                            "(FLEETX_SENTRY_MAX_SKIPS); last healthy state "
                            "checkpointed")
                    continue
                self._sentry_consecutive = 0
                step += 1
                self._obs_steps.inc()
                # tick before the logging/eval/save hooks so the profiled
                # step-time window measures the train step, not a periodic
                # evaluation pass or checkpoint write
                self._profiler_step(step)
                self.consumed_samples += self.cfg.Global.global_batch_size
                loss_window.append(metrics["loss"])

                with span("train.callback", step=step):
                    if step % self.logging_freq == 0:
                        with span("train.loss_fetch", step=step):
                            losses = np.mean([float(l) for l in loss_window])
                        loss_window = []
                        with span("train.log", step=step):
                            dt = (time.time() - t_last) / self.logging_freq
                            t_last = time.time()
                            ips_total = tokens_per_batch / dt
                            lr = float(self.lr_schedule(step))
                            mfu, hbm = self._step_mfu(dt), self._step_hbm_bytes()
                            collectives = self._step_collectives(once=True)
                            self._obs_loss.set(float(losses))
                            self._obs_lr.set(lr)
                            self._obs_step_time.observe(dt)
                            self._obs_tokens_per_s.set(ips_total)
                            if mfu is not None:
                                self._obs_mfu.set(mfu)
                            if hbm is not None:
                                self._obs_hbm_bytes.set(hbm)
                            self.module.training_step_end(
                                {
                                    "epoch": epoch,
                                    "batch": step,
                                    "loss": losses,
                                    "batch_cost": dt,
                                    "ips_total": ips_total,
                                    "ips": ips_total / max(
                                        jax.process_count(), 1),
                                    "lr": lr, "mfu": mfu,
                                    "collectives": collectives,
                                }
                            )
                    if (self.eval_freq and valid_data is not None
                            and step % self.eval_freq == 0):
                        self.evaluate(valid_data, epoch=epoch)
                    if self.save_steps and step % self.save_steps == 0:
                        self._guarded_save(epoch)
            if step >= self.max_steps:
                break
        self._profiler_maybe_stop()
        self.wait_for_checkpoints()

    # ------------------------------------------------------------------- eval
    def evaluate(self, valid_data: Iterable, epoch: int = 0):
        batches = iter(valid_data)
        if self.state is None:
            try:
                first = next(batches)
            except StopIteration:
                return None
            self.init_state(self.module.pretreating_batch(first))
            batches = itertools.chain([first], batches)  # don't drop batch 0
        eval_step = self._get("eval", self._build_eval_step)
        losses = []
        t0 = time.time()
        for i, batch in enumerate(batches):
            if i >= self.eval_iters:
                break
            batch = self.module.pretreating_batch(batch)
            device_batch = self._shard_batch(batch, for_train=False)
            metrics = eval_step(self.state, device_batch)
            losses.append(float(metrics["loss"]))
        if losses:
            self.module.validation_step_end(
                {
                    "epoch": epoch,
                    "batch": int(self.state.step),
                    "loss": float(np.mean(losses)),
                    "batch_cost": (time.time() - t0) / len(losses),
                }
            )
        return float(np.mean(losses)) if losses else None

    def predict(self, data: Iterable):
        """Forward the module over ``data`` batches, returning host outputs
        per batch (reference predict loop, eager_engine.py:502-632;
        serving-grade inference over an export artifact stays in
        InferenceEngine). Uses the module's serving contract so the fed keys
        match what export/inference would serve."""
        from fleetx_tpu.utils.export import serving_contract

        spec = self.module.input_spec() or {}
        fwd, keys = serving_contract(self.module, spec)
        if fwd is None:
            raise NotImplementedError(
                "module has no serving contract; use GenerationModule / "
                "InferenceEngine or override serving_forward()"
            )
        batches = iter(data)
        if self.state is None:
            try:
                first = next(batches)
            except StopIteration:
                return []
            self.init_state(self.module.pretreating_batch(first))
            batches = itertools.chain([first], batches)  # don't drop batch 0

        def _build_predict_step():
            module = self.module

            def predict_step(state: TrainState, feed):
                with module.act_quant_ctx():
                    return fwd(module.maybe_fake_quant(state.params), feed)

            batch_sh = NamedSharding(self.mesh, P(DATA_AXES))
            return jax.jit(
                predict_step,
                in_shardings=(self._state_sharding_tree, batch_sh),
            )

        predict_step = self._get("predict", _build_predict_step)
        outputs = []
        for batch in batches:
            batch = self.module.pretreating_batch(batch)
            feed = {k: batch[k] for k in keys}
            feed = self._shard_batch(feed, for_train=False)
            out = jax.device_get(predict_step(self.state, feed))
            # multi-output contracts (e.g. ERNIE's (mlm, sop)) stay pytrees
            outputs.append(jax.tree.map(np.asarray, out))
        return outputs

    # ------------------------------------------------------------- checkpoint
    def _ckpt_manager(self):
        import orbax.checkpoint as ocp

        if self._ckpt_mgr is None:
            import atexit

            path = os.path.abspath(os.path.join(self.output_dir, "checkpoints"))
            os.makedirs(path, exist_ok=True)
            self._ckpt_mgr = ocp.CheckpointManager(
                path,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=3, create=True, enable_async_checkpointing=True
                ),
            )
            # async saves must finalize before interpreter teardown or the
            # checkpoint stays a *.orbax-checkpoint-tmp and is unloadable.
            # weakref so atexit doesn't pin the Trainer (and its device
            # arrays) alive for the process lifetime.
            import weakref

            ref = weakref.ref(self)
            atexit.register(lambda: ref() and ref().wait_for_checkpoints())
        return self._ckpt_mgr

    def wait_for_checkpoints(self):
        self._join_uploader()
        if self._ckpt_mgr is not None:
            self._ckpt_mgr.wait_until_finished()

    def _join_uploader(self):
        """Block until the in-flight snapshot upload (if any) finishes."""
        t = self._upload_thread
        if t is not None:
            t.join()
            self._upload_thread = None

    def _record_save_failure(self, step: int) -> None:
        """Count + emit one failed checkpoint save (the run survives)."""
        self.save_failures += 1
        self._obs_save_failures.inc()
        obs_emit("save_failure", step=step, failures=self.save_failures)

    def _guarded_save(self, epoch: int = 0):
        """Periodic/emergency save that survives a failed write: a full
        disk or flaky object store must not kill a healthy training run —
        the failure is logged and counted, and the next cadence retries."""
        try:
            self.save(epoch=epoch)
        except Exception:
            self._record_save_failure(int(self.state.step))
            logger.exception(
                "checkpoint save failed at step %d (%d failures so far); "
                "training continues, next save in %d steps",
                int(self.state.step), self.save_failures, self.save_steps,
            )

    def _save_meta(self, epoch: int) -> dict:
        """The JSON side of a checkpoint (resume + compatibility record)."""
        return {
            "epoch": epoch, "consumed_samples": self.consumed_samples,
            # the dropout noise stream is defined by these two switches
            # (ops/dropout.py HashDropout vs nn.Dropout; flash kernel hash
            # vs hardware PRNG) — record them so a resume under flipped
            # flags is detectable instead of silently changing the masks
            "dropout_impl": self._dropout_impl(),
            # the mesh this state was written under: dp/fsdp may change on
            # restore (elastic reshard-on-load), mp/pp/cp must not — their
            # extents are baked into array shapes (resilience/elastic.py)
            "mesh": {"dp": self.mesh_cfg.dp, "fsdp": self.mesh_cfg.fsdp,
                     "mp": self.mesh_cfg.mp, "pp": self.mesh_cfg.pp,
                     "cp": self.mesh_cfg.cp},
        }

    def save(self, epoch: int = 0):
        """Sharded save of {params, opt_state, step} + meta (epoch,
        consumed_samples) — reference meta_state.pdopt semantics
        (eager_engine.py:655-665).

        Under ``FLEETX_CKPT_ASYNC_SNAPSHOT`` (step-shadow snapshot
        checkpointing) the step path blocks only for the device→host copy;
        a background uploader thread feeds the host tree to the orbax
        manager, and an upload failure rides the same counter/event path
        as a synchronous one (``_guarded_save``). A meta-advanced rewrite
        of an existing step detaches the old directory first and reattaches
        it if the replacement save fails — a crash or injected fault in
        the rewrite window can never destroy the only copy of a step."""
        import orbax.checkpoint as ocp

        self._join_uploader()  # serialize with an in-flight snapshot upload
        mgr = self._ckpt_manager()
        step = int(self.state.step)
        meta_sig = (step, epoch, self.consumed_samples)
        t0 = time.perf_counter()
        backup = None
        if step in (mgr.all_steps() or []):
            if meta_sig == self._last_saved_meta:
                # e.g. a preemption save landing right on a periodic-save
                # step: orbax refuses duplicate steps, and that exact state
                # (params AND meta) is already safe
                logger.info("checkpoint for step %d already exists; "
                            "skipping duplicate save", step)
                return
            # same step but the meta moved on — sentry skips advance
            # consumed_samples with the step counter frozen, and stale meta
            # would re-feed the skipped batches on resume. Rewrite it.
            logger.info("checkpoint for step %d exists but meta advanced "
                        "(consumed_samples %s); rewriting", step,
                        self.consumed_samples)
            mgr.wait_until_finished()
            backup = self._detach_step(step)
            mgr = self._ckpt_manager()  # detach may have rebuilt the manager
        try:
            faults.on_checkpoint_save(step)  # chaos injection (inert: no-op)
            meta = self._save_meta(epoch)
            if self._ckpt_async and backup is None:
                # step-shadow snapshot: the D2H copy is the only blocking
                # work; the uploader owns durability from here. (Rewrites
                # stay synchronous — rare, and the reattach guarantee below
                # wants the save outcome known before the backup is dropped.)
                host_state = jax.device_get(_unbox(self.state))
                nbytes = sum(getattr(l, "nbytes", 0)
                             for l in jax.tree.leaves(host_state))
                blocking = time.perf_counter() - t0
                self._obs_ckpt_bytes.set(float(nbytes))
                self._obs_ckpt_seconds.labels(phase="blocking").observe(blocking)
                self._upload_thread = threading.Thread(
                    target=self._upload_snapshot,
                    args=(mgr, step, host_state, meta, meta_sig,
                          t0, blocking, nbytes),
                    name="fleetx-ckpt-upload", daemon=True)
                self._upload_thread.start()
                logger.info(
                    "snapshot of step %d handed to uploader "
                    "(D2H blocked %.3fs, %.1f MB)",
                    step, blocking, nbytes / 1e6)
                return
            mgr.save(
                step,
                args=ocp.args.Composite(
                    state=ocp.args.StandardSave(_unbox(self.state)),
                    meta=ocp.args.JsonSave(meta),
                ),
            )
            if backup is not None:
                # rewrite: the replacement must be durably finalized before
                # the old copy stops being the fallback
                mgr.wait_until_finished()
        except BaseException:
            if backup is not None:
                self._reattach_step(backup, step)
            raise
        if backup is not None:
            import shutil
            shutil.rmtree(backup, ignore_errors=True)
        dt = time.perf_counter() - t0
        nbytes = sum(getattr(l, "nbytes", 0)
                     for l in jax.tree.leaves(_unbox(self.state)))
        self._obs_ckpt_bytes.set(float(nbytes))
        self._obs_ckpt_seconds.labels(phase="blocking").observe(dt)
        self._obs_ckpt_seconds.labels(phase="total").observe(dt)
        obs_emit("checkpoint_saved", step=step, mode="sync",
                 blocking_s=round(dt, 4), total_s=round(dt, 4), bytes=nbytes)
        self._last_saved_meta = meta_sig
        logger.info("saved checkpoint at step %d -> %s", step, self.output_dir)

    def _upload_snapshot(self, mgr, step, host_state, meta, meta_sig,
                         t0, blocking, nbytes):
        """Uploader-thread body: feed a host snapshot to the orbax manager.
        ``_last_saved_meta`` commits only once the write is durably
        finalized; a failure rides the ``_guarded_save`` counter/event
        path so chaos assertions see async and sync failures identically."""
        import orbax.checkpoint as ocp

        try:
            mgr.save(
                step,
                args=ocp.args.Composite(
                    state=ocp.args.StandardSave(host_state),
                    meta=ocp.args.JsonSave(meta),
                ),
            )
            mgr.wait_until_finished()
            self._last_saved_meta = meta_sig
            total = time.perf_counter() - t0
            self._obs_ckpt_seconds.labels(phase="total").observe(total)
            obs_emit("checkpoint_saved", step=step, mode="async_snapshot",
                     blocking_s=round(blocking, 4),
                     total_s=round(total, 4), bytes=nbytes)
            logger.info(
                "saved checkpoint at step %d -> %s (async snapshot: "
                "%.3fs blocking / %.3fs total)",
                step, self.output_dir, blocking, total)
        except Exception:
            self._record_save_failure(step)
            logger.exception(
                "async snapshot upload failed at step %d (%d failures so "
                "far); training continues, next save retries", step,
                self.save_failures)

    def _detach_step(self, step: int):
        """Move an existing step directory aside (to
        ``<output_dir>/rewrite/<step>``) before a meta-advanced rewrite:
        the detached copy — still a complete, restorable checkpoint —
        survives any crash or injected fault in the replacement save,
        and :meth:`_reattach_step` puts it back on failure. One-filesystem
        renames, so both moves are O(1). Returns the backup path (None
        when the manager lists the step but no directory exists)."""
        import shutil

        root = os.path.abspath(os.path.join(self.output_dir, "checkpoints"))
        src = os.path.join(root, str(step))
        if not os.path.isdir(src):
            return None
        hold = os.path.join(self.output_dir, "rewrite")
        os.makedirs(hold, exist_ok=True)
        dst = os.path.join(hold, str(step))
        if os.path.exists(dst):
            shutil.rmtree(dst)  # stale leftover from an older crash
        shutil.move(src, dst)
        self._mgr_refresh()
        return dst

    def _reattach_step(self, backup, step: int) -> None:
        """Restore a detached step directory after a failed rewrite save
        (drops any partial replacement first — the backup is the good
        copy)."""
        import shutil

        if backup is None:
            return
        root = os.path.abspath(os.path.join(self.output_dir, "checkpoints"))
        dst = os.path.join(root, str(step))
        if os.path.exists(dst):
            shutil.rmtree(dst)
        for name in os.listdir(root):
            if name.startswith(f"{step}.") and "orbax-checkpoint-tmp" in name:
                shutil.rmtree(os.path.join(root, name), ignore_errors=True)
        shutil.move(backup, dst)
        self._mgr_refresh()
        logger.warning(
            "rewrite of checkpoint step %d failed; original copy restored",
            step)

    def _mgr_refresh(self) -> None:
        """Refresh the manager's cached step list after the directory
        changed underneath it (quarantine/detach/reattach); falls back to
        a lazy rebuild on orbax versions without ``reload()``."""
        mgr = self._ckpt_mgr
        if mgr is None:
            return
        try:
            mgr.reload()
        except Exception:  # older orbax: rebuild the manager lazily
            try:
                mgr.close()
            except Exception:
                pass
            self._ckpt_mgr = None

    def _dropout_impl(self) -> dict:
        from fleetx_tpu.ops.pallas.flash_attention import HW_RNG

        model_cfg = (getattr(self.cfg, "Model", None) or {})
        return {
            "flash_hw_rng": bool(HW_RNG),
            # HashDropout vs nn.Dropout for the hidden dropouts
            "fast_dropout": bool(model_cfg.get("fast_dropout", True)),
        }

    @span("train.restore")
    def load(self, step: Optional[int] = None):
        """Restore (one ``train.restore`` span); resumes step count, epoch,
        and data order (consumed_samples -> sampler, eager_engine.py:286-288).

        On auto-restore (``step=None``) a corrupt/truncated checkpoint —
        e.g. a kill that landed between an async save and its finalize —
        does not end the run: the bad step directory is quarantined to
        ``<output_dir>/quarantine/`` and the next-older step is tried,
        walking back until one restores (docs/RESILIENCE.md). An explicit
        ``step`` still raises on failure: the caller asked for exactly
        that state, silently substituting another would be worse."""
        self._join_uploader()  # a pending snapshot upload is a candidate too
        mgr = self._ckpt_manager()
        mgr.wait_until_finished()  # never race our own in-flight async save
        candidates = [step] if step is not None else sorted(
            mgr.all_steps(), reverse=True)
        if not candidates:
            logger.warning("no checkpoint found under %s", self.output_dir)
            return False
        newest = candidates[0]
        for cand in candidates:
            if (
                cand == self._restored_step
                and self.state is not None
                and int(self.state.step) == cand
            ):
                # init_state already restored this step (its resumable
                # branch); don't pay the multi-GB orbax restore twice on
                # CLI resume paths
                return True
            if self.state is None:
                raise RuntimeError(
                    "call init_state (or fit) before load, to build shardings")
            try:
                restored = self._restore_step(cand)
            except ElasticMeshMismatch:
                # a checkpoint written under an incompatible mp/pp/cp
                # extent is a CONFIG error, not corruption: re-raise
                # instead of quarantining a healthy checkpoint
                raise
            except Exception as e:
                if step is not None:
                    raise
                logger.error(
                    "checkpoint step %d failed verified restore (%s: %s); "
                    "quarantining it and falling back to the next-older step",
                    cand, type(e).__name__, e,
                )
                self._quarantine_step(cand)
                continue
            self._apply_restored(cand, restored)
            if cand != newest:
                logger.warning(
                    "restored FALLBACK checkpoint step %d — newer step(s) %s "
                    "were corrupt and quarantined; %d step(s) of progress "
                    "lost", cand,
                    [s for s in candidates if s > cand], newest - cand,
                )
            return True
        raise CheckpointUnrestorable(
            f"no restorable checkpoint under {self.output_dir}: every "
            f"candidate step {sorted(candidates, reverse=True)} failed "
            "verified restore and was quarantined")

    def _restore_step(self, step: int):
        """Restore + verify one checkpoint step (raises on any mismatch).

        The meta JSON is read FIRST and its recorded mesh validated
        against this trainer's: a dp/fsdp change is the supported elastic
        reshard (the abstract restore below reshards into THIS mesh's
        shardings — ZeRO update layouts were re-derived by
        ``_state_shardings``, never assumed from the writer), while a
        changed mp/pp/cp extent raises :class:`ElasticMeshMismatch`
        before the state restore can fail in a way that looks like
        corruption (``load()`` re-raises it instead of quarantining)."""
        import orbax.checkpoint as ocp

        mgr = self._ckpt_manager()
        head = mgr.restore(
            step, args=ocp.args.Composite(meta=ocp.args.JsonRestore()))
        saved_mesh = (head["meta"] or {}).get("mesh")
        if saved_mesh:
            validate_restore_mesh(saved_mesh, self.mesh_cfg, step=step)
        abstract = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            _unbox(self.state),
            self._state_sharding_tree,
        )
        restored = mgr.restore(
            step,
            args=ocp.args.Composite(
                state=ocp.args.StandardRestore(abstract),
                meta=ocp.args.JsonRestore(),
            ),
        )
        got = int(restored["state"].step)
        if got != step:
            raise ValueError(
                f"checkpoint dir {step} restored step counter {got}")
        return restored

    def _apply_restored(self, step: int, restored) -> None:
        """Install a verified restore into trainer state + resume meta."""
        flat = restored["state"]
        self.state = TrainState(
            step=flat.step,
            params=_rebox_like(flat.params, self.state.params),
            opt_state=flat.opt_state,
            extra=flat.extra,
        )
        meta = restored["meta"]
        self.start_epoch = meta.get("epoch", 0)
        self.consumed_samples = meta.get("consumed_samples", 0)
        # seed the duplicate-save signature: a save() at this same step with
        # unchanged meta must SKIP, not take the delete-then-rewrite path
        # (which would momentarily leave no restorable copy of this step)
        self._last_saved_meta = (step, self.start_epoch, self.consumed_samples)
        saved_impl = meta.get("dropout_impl")
        if saved_impl is not None and saved_impl != self._dropout_impl():
            logger.warning(
                "checkpoint was trained with dropout_impl=%s but this run "
                "uses %s — the dropout noise stream will differ from an "
                "uninterrupted run (set FLEETX_FLASH_HW_RNG to match)",
                saved_impl, self._dropout_impl(),
            )
        self._restored_step = step
        self._obs_opt_bytes.set(float(self.opt_state_device_bytes()))
        logger.info("restored checkpoint step %d (epoch %d)", step, self.start_epoch)

    def _quarantine_step(self, step: int) -> None:
        """Move a corrupt step directory out of the checkpoint root (to
        ``<output_dir>/quarantine/<step>``) so the manager never offers it
        again, and refresh the manager's cached step list."""
        import shutil

        root = os.path.abspath(os.path.join(self.output_dir, "checkpoints"))
        names = [n for n in os.listdir(root)
                 if n.isdigit() and int(n) == step]
        if not names:
            logger.warning("quarantine: no directory for step %d under %s",
                           step, root)
            return
        qdir = os.path.join(self.output_dir, "quarantine")
        os.makedirs(qdir, exist_ok=True)
        for name in names:
            dst = os.path.join(qdir, name)
            n = 0
            while os.path.exists(dst):
                n += 1
                dst = os.path.join(qdir, f"{name}.{n}")
            shutil.move(os.path.join(root, name), dst)
            self._obs_quarantines.inc()
            obs_emit("checkpoint_quarantine", step=step, moved_to=dst)
            logger.warning("quarantined corrupt checkpoint %s -> %s",
                           os.path.join(root, name), dst)
        self._mgr_refresh()

    # ------------------------------------------------------------ preemption
    def _install_preemption_handler(self):
        """SIGTERM -> finish the in-flight step, checkpoint, exit cleanly.

        TPU-fleet preemptions deliver SIGTERM with a grace window; the
        reference has no preemption handling (SURVEY §5: recovery is
        checkpoint-resume only), so a preempted run there loses everything
        since the last periodic save. Only the main thread may set signal
        handlers — worker-thread callers just skip this."""
        import signal
        import threading

        if threading.current_thread() is not threading.main_thread():
            return

        def on_sigterm(signum, frame):
            self._preempted = True  # the fit loop checkpoints + returns

        try:
            self._prev_sigterm = signal.signal(signal.SIGTERM, on_sigterm)
        except (ValueError, OSError):  # non-main interpreter contexts
            self._prev_sigterm = None

    def _restore_preemption_handler(self):
        """Put back whatever SIGTERM handler fit() displaced."""
        import signal
        import threading

        if (
            self._prev_sigterm is None
            or threading.current_thread() is not threading.main_thread()
        ):
            return
        try:
            signal.signal(signal.SIGTERM, self._prev_sigterm)
        except (ValueError, OSError):
            pass
        self._prev_sigterm = None

    # -------------------------------------------------------------- profiler
    def _profiler_maybe_start(self, step):
        prof = self.cfg.get("Profiler") or {}
        self._prof_enabled = bool(prof.get("enable"))
        if not self._prof_enabled:
            return
        sched = prof.get("scheduler") or [1, 5]
        self._prof_window = tuple(sched)
        self._prof_dir = prof.get("profiler_log", "profiler_log")
        self._prof_running = False

    def _profiler_step(self, step):
        if not getattr(self, "_prof_enabled", False):
            return
        lo, hi = self._prof_window
        if not self._prof_running and step >= lo:
            jax.profiler.start_trace(self._prof_dir)
            self._prof_running = True
            self._prof_ticks = [time.perf_counter()]
        elif self._prof_running:
            self._prof_ticks.append(time.perf_counter())
        if self._prof_running and step >= hi:
            jax.block_until_ready(self.state.params)  # close the async tail
            self._prof_ticks.append(time.perf_counter())
            jax.profiler.stop_trace()
            self._prof_running = False
            self._prof_enabled = False
            logger.info("profiler trace written to %s", self._prof_dir)
            self._print_summary()

    def _print_summary(self):
        """Reference _print_summary (eager_engine.py:761-820): configurable
        overview/model/kernel/mem views after the profiling window."""
        from fleetx_tpu.utils.profiler_summary import print_summary

        ticks = getattr(self, "_prof_ticks", [])
        step_times = [b - a for a, b in zip(ticks, ticks[1:])]
        print_summary(
            self, dict(self.cfg.get("Profiler") or {}), self._prof_dir,
            step_times,
        )

    def _profiler_maybe_stop(self, summary: bool = True):
        """Close an open trace window. ``summary=False`` finalizes the trace
        only — the preemption path uses it so the SIGTERM grace window is
        spent checkpointing, not parsing trace JSON."""
        if getattr(self, "_prof_running", False):
            jax.profiler.stop_trace()
            self._prof_running = False
            if summary:
                self._print_summary()

    def _step_collectives(self, once: bool = False) -> Optional[Dict[str, int]]:
        """How many collectives of each kind the compiled train step holds
        (``collective_matmul.count_collectives`` of its text), for the
        ``fleetx_train_step_collectives`` gauge: what says whether the
        tensor-parallel products carry their collectives (collective-
        permutes in place of the layer loops' activation-sized
        all-reduces). Tried once, then cached, same contract as the flops;
        ``once`` asks for None from every call but the one that fills it
        (the counts ride the first TRAIN line alone). The method and its
        gauge family sit down here, not beside the other gauges: the
        compile cache's keys hold the line of every frame above a traced
        call, ``fit`` and ``evaluate`` among them."""
        if self._collectives_per_step is not None and once:
            return None
        if self._collectives_per_step is None:
            from fleetx_tpu.parallel.collective_matmul import count_collectives

            self._collectives_per_step = False
            try:
                text = self.compiled_text("train")
            except Exception:  # noqa: BLE001 — observability never aborts
                text = None
            if text:
                self._collectives_per_step = count_collectives(text)
                gauge = get_registry().gauge(
                    "fleetx_train_step_collectives",
                    "Collective instructions of each kind in the compiled "
                    "train step's text (-start counted, -done not; a "
                    "scanned layer loop's body counts once whatever the "
                    "depth)", labelnames=("kind",))
                for kind, n in self._collectives_per_step.items():
                    gauge.labels(kind=kind).set(float(n))
        return self._collectives_per_step or None
