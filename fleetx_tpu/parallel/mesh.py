"""Device-mesh construction — the TPU-native replacement for the reference's
three group managers (fleet HybridCommunicateGroup, OrthogonalStrategy,
SingletonCommunicationGroup; /root/reference/ppfleetx/distributed/apis/
env.py:85-114, comm_groups.py:27-153, protein_folding/scg.py:28-224).

One `jax.sharding.Mesh` with named axes replaces them all: collectives are
inserted by GSPMD from sharding annotations, or written explicitly with
``shard_map`` over the same axes. Axis names:

- ``dp``     data parallel (pure replication of params)
- ``fsdp``   data parallel with ZeRO param/opt-state sharding (sharding_degree)
- ``pp``     pipeline stages
- ``cp``     context parallel (ring attention; sequence sharded through attn)
- ``mp``     tensor ("model") parallel; sequence parallel rides this axis
- ``ep``     expert parallel for MoE (folded over dp×fsdp when used)

Mesh axis order is (pp, dp, fsdp, cp, mp): mp innermost so TP collectives
ride the fastest ICI links, cp next so the KV ring permute stays on-chip
neighbors, pp outermost so stage p2p can cross DCN.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "MeshConfig",
    "build_mesh",
    "mesh_from_config",
    "use_mesh",
    "active_mesh",
    "ambient_mesh",
    "shard_map",
    "DATA_AXES",
    "get_data_world",
    "batch_sharding",
]

# Axes over which the batch dimension is sharded (data-parallel world =
# dp_degree * sharding_degree, matching reference env.py:121-141).
DATA_AXES = ("dp", "fsdp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Parallel-degree tuple (pp/dp/fsdp/cp/mp + sharding stage/offload)
    parsed from the Distributed config section."""
    dp: int = 1
    fsdp: int = 1
    mp: int = 1
    pp: int = 1
    cp: int = 1
    sharding_stage: int = 1
    sharding_offload: bool = False  # opt-state in host memory (pinned_host)

    @property
    def nranks(self) -> int:
        return self.dp * self.fsdp * self.mp * self.pp * self.cp

    @classmethod
    def from_dist_config(cls, dist) -> "MeshConfig":
        """Build from a normalized ``Distributed`` config section."""
        sharding = dist.get("sharding") or {}
        return cls(
            dp=dist.get("dp_degree") or 1,
            fsdp=sharding.get("sharding_degree") or 1,
            mp=dist.get("mp_degree") or 1,
            pp=dist.get("pp_degree") or 1,
            cp=dist.get("cp_degree") or 1,
            sharding_stage=sharding.get("sharding_stage") or 1,
            sharding_offload=bool(sharding.get("sharding_offload")),
        )


def build_mesh(
    cfg: MeshConfig,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Create the (pp, dp, fsdp, mp) mesh.

    Uses `jax.experimental.mesh_utils` device assignment on real TPU slices so
    axes map onto the physical torus; trivial reshape elsewhere (CPU tests).
    """
    if devices is None:
        devices = jax.devices()
    shape = (cfg.pp, cfg.dp, cfg.fsdp, cfg.cp, cfg.mp)
    if cfg.nranks < len(devices):
        devices = list(devices)[: cfg.nranks]  # sub-mesh of the first N
    if cfg.nranks != len(devices):
        raise ValueError(
            f"mesh {shape} needs {cfg.nranks} devices, have {len(devices)}"
        )
    if devices[0].platform == "tpu" and cfg.nranks > 1:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    else:
        dev_array = np.asarray(list(devices)).reshape(shape)
    return Mesh(dev_array, ("pp", "dp", "fsdp", "cp", "mp"))


def mesh_from_config(cfg, devices=None) -> Mesh:
    """Mesh straight from a full training config (its Distributed section)."""
    return build_mesh(MeshConfig.from_dist_config(cfg.get("Distributed") or {}), devices)


def get_data_world(mesh: Mesh) -> int:
    """dp*fsdp world size — number of distinct data shards."""
    return mesh.shape["dp"] * mesh.shape["fsdp"]


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for host-fed batches: batch dim over the data axes."""
    return NamedSharding(mesh, P(DATA_AXES))


# ------------------------------------------------------------- mesh context
# jax's legacy `with mesh:` context is only observable through the deprecated
# `pxla.thread_resources`; the modern `jax.sharding.get_mesh()` only sees
# meshes installed via `jax.sharding.set_mesh`. The framework keeps its own
# tiny registry so code deep inside a jitted model (ring attention,
# context_parallel.py) can find the mesh the Trainer entered without any
# deprecated API.

import contextlib
import contextvars

# context-local (so threaded servers with different meshes don't cross-talk,
# matching the thread-locality of jax's own mesh context)
_ACTIVE_MESHES: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "fleetx_active_meshes", default=()
)


def active_mesh() -> Optional[Mesh]:
    """Innermost mesh entered via :func:`use_mesh` (None outside)."""
    stack = _ACTIVE_MESHES.get()
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Enter a mesh for GSPMD lowering AND record it for framework lookups."""
    token = _ACTIVE_MESHES.set(_ACTIVE_MESHES.get() + (mesh,))
    try:
        with mesh:
            yield mesh
    finally:
        _ACTIVE_MESHES.reset(token)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None, **kwargs):
    """``jax.shard_map`` under one call-site contract (keyword
    mesh/in_specs/out_specs, ``check_vma`` only when given) for every
    framework user."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def ambient_mesh() -> Optional[Mesh]:
    """The mesh a model-interior ``shard_map`` should run over, best-effort:
    the modern jax context mesh (jax.sharding.set_mesh) first, then the
    framework's own registry (:func:`use_mesh` — what the Trainer enters).
    No deprecated thread_resources lookups. Used by ring attention
    (parallel/context_parallel.py) and the flash kernel's TP wrapper
    (ops/pallas/flash_attention.py)."""
    try:
        m = jax.sharding.get_mesh()  # set via jax.sharding.set_mesh
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    try:
        m = jax.sharding.get_abstract_mesh()
        if m is not None and not m.empty:  # pragma: no cover - version dependent
            return m
    except Exception:
        pass
    return active_mesh()
