"""Distributed environment + RNG-seed discipline.

Parity with reference env.py (/root/reference/ppfleetx/distributed/apis/
env.py:34-154): ``set_seed`` derives a *global* seed shared by all model-
parallel ranks (replicated tensors, e.g. attention dropout on replicated
activations must agree across mp) and a *local* per-rank component for
sharded tensors. In JAX the mechanism is key derivation rather than stateful
RNG trackers: one root key per run; dropout keys are derived by
``jax.random.fold_in`` of (root, step, data_rank) so they are invariant
across mp ranks by construction, and per-shard randomness comes from
folding in the shard index inside the sharded op itself.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from fleetx_tpu.utils.log import logger

__all__ = ["init_dist_env", "set_seed", "root_key", "global_seed", "data_rank_key"]

_ROOT_KEY = None
_GLOBAL_SEED = None


def init_dist_env(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host init. On a TPU pod slice, `jax.distributed.initialize()`
    discovers peers from the TPU metadata service; coordinator address /
    process count / process id are only needed on CPU/GPU clusters (or come
    from FLEETX_COORDINATOR / FLEETX_NUM_PROCESSES / FLEETX_PROCESS_ID).
    Single-process runs are a no-op.

    Replaces the reference's `fleet.init` + NCCL group construction
    (env.py:85-114) — there are no per-strategy process groups to build;
    the Mesh carries all topology.
    """
    coordinator_address = coordinator_address or os.environ.get("FLEETX_COORDINATOR")
    if num_processes is None and os.environ.get("FLEETX_NUM_PROCESSES"):
        num_processes = int(os.environ["FLEETX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("FLEETX_PROCESS_ID"):
        process_id = int(os.environ["FLEETX_PROCESS_ID"])
    if coordinator_address or num_processes:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        logger.info(
            "distributed init: process %d/%d, %d local / %d global devices",
            jax.process_index(),
            jax.process_count(),
            jax.local_device_count(),
            jax.device_count(),
        )


def set_seed(seed: int) -> jax.Array:
    """Install the run's root PRNG key. Also seeds numpy/python for host-side
    shuffling (dataset index shuffles match the reference's
    np.random.RandomState(seed) usage)."""
    global _ROOT_KEY, _GLOBAL_SEED
    import numpy as np
    import random

    random.seed(seed)
    np.random.seed(seed % (2**32))
    _GLOBAL_SEED = seed
    _ROOT_KEY = jax.random.PRNGKey(seed)
    return _ROOT_KEY


def root_key() -> jax.Array:
    """The process-wide root PRNG key set by set_seed()."""
    if _ROOT_KEY is None:
        raise RuntimeError("call set_seed() first")
    return _ROOT_KEY


def global_seed() -> int:
    """The integer seed set_seed() was called with."""
    if _GLOBAL_SEED is None:
        raise RuntimeError("call set_seed() first")
    return _GLOBAL_SEED


def data_rank_key(step: int, data_rank: int = 0) -> jax.Array:
    """Dropout key for one train step of one data shard: invariant across
    mp/pp ranks (same fold-in inputs), distinct across steps and data ranks —
    the JAX analogue of the reference RNG-tracker global/local seed split
    (env.py:49-57)."""
    key = jax.random.fold_in(root_key(), step)
    return jax.random.fold_in(key, data_rank)
