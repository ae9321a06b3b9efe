"""Logical-axis sharding rules — the TPU-native replacement for the
reference's per-strategy wrappers:

- TP column/row/vocab-parallel layers (hybrid_model.py:49-174,628-680) become
  rules mapping the ``heads``/``mlp``/``vocab`` logical axes to mesh axis
  ``mp``; GSPMD inserts the all-reduce/all-gather that Column/RowParallelLinear
  did by hand.
- ZeRO sharding stages 1-3 (distributed/apis/sharding.py:30-147) become the
  ``fsdp`` mesh axis applied to optimizer state (stage 1/2) and additionally
  to parameters (stage 3).
- Megatron sequence parallel (sequence_parallel_utils.py:40-395) becomes
  activation sharding constraints: between two tensor-parallel blocks the
  rows live sharded over ``mp`` (``act_seq``, :data:`ACT_AXES`), so a
  column-parallel product gathers its input rows and a row-parallel one
  scatters its sums, as ScatterOp/GatherOp/ReduceScatterOp did by hand.
  On by default where ``mp > 1`` (utils/config.py). Left to the
  partitioner those gathers and scatters are synchronous, and neither of
  the compiler's own overlap passes helps (utils/xla_flags.py has what the
  chip showed), so the products of a block carry their collectives
  themselves: ``parallel/collective_matmul.py``, one ``shard_map`` a
  product, used wherever the rows are sharded over ``mp`` alone and no
  pipeline stage is in the way; elsewhere (context parallel, ``pp > 1``)
  the partitioner's synchronous pairs stand, a column product's output
  laid out by name.

Models annotate params/activations with logical axis names (flax
``nn.with_partitioning`` / ``logical_to_mesh``); these tables translate
logical names → mesh axes for a given parallelism configuration.
"""

from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fleetx_tpu.parallel.mesh import ambient_mesh

__all__ = [
    "ACT_AXES",
    "make_rules",
    "logical_to_mesh_sharding",
    "param_shardings",
    "serving_param_shardings",
    "with_logical_constraint",
    "zero_update_spec",
]

Rules = Sequence[Tuple[str, Any]]

# Logical axes of the activations at the named points of a block
# (models/gpt/model.py ``_constrain_act``). Between two tensor-parallel
# blocks (``residual``: a layer's input, both residual sums) the rows live
# where ``act_seq`` puts them; ``whole`` is the head's input, which holds
# every row the device's ``cp`` share has (``act_seq_tp``). A
# column-parallel product's output is laid out by the product itself
# (parallel/collective_matmul.py).
ACT_AXES = {
    "residual": ("act_batch", "act_seq", "act_embed"),
    "whole": ("act_batch", "act_seq_tp", "act_embed"),
}


def make_rules(
    sharding_stage: int = 1,
    sequence_parallel: bool = False,
    fsdp_params: Optional[bool] = None,
    context_parallel: bool = False,
) -> List[Tuple[str, Any]]:
    """Logical→mesh axis rules.

    ``fsdp_params`` overrides whether *parameters* (not just optimizer state)
    are sharded over the fsdp axis; default derives from sharding_stage>=3.
    ``context_parallel`` puts the activation sequence axis on ``cp`` so the
    whole layer stack (embeddings, MLP, logits) — not just attention — holds
    O(s/cp) per device; zig-zag order is position-agnostic for everything
    outside attention, which re-orders via its own shard_map.
    """
    if fsdp_params is None:
        fsdp_params = sharding_stage >= 3
    rules: List[Tuple[str, Any]] = [
        ("batch", ("dp", "fsdp")),
        # TP: vocab-, column- (heads/mlp out), and row-parallel (reduced-in)
        ("vocab", "mp"),
        ("heads", "mp"),
        ("kv", None),
        ("mlp", "mp"),
        # embed is the row-parallel contraction axis of out-proj / mlp.down and
        # the fsdp shard axis for stage-3 param sharding.
        ("embed", "fsdp" if fsdp_params else None),
        ("norm", None),
        ("layers", None),  # stacked (scan) layer axis; pp maps it to stages
        ("stage", "pp"),
        # expert parallelism folds over the data-parallel world (reference
        # HybridCommGroupForMoE fuses moe = dp×mp, comm_groups.py:125-153;
        # here experts shard over dp×fsdp and mp shards within an expert).
        ("expert", ("dp", "fsdp")),
        ("cache_batch", None),
        ("cache_heads", "mp"),
    ]
    # Activation sequence axis: sharded over cp under context parallelism
    # (optionally also mp for Megatron-SP), over mp alone for pure SP, over
    # nothing otherwise. 'act_seq' only tags activations, never params.
    if context_parallel:
        rules.append(("act_seq", ("cp", "mp") if sequence_parallel else "cp"))
    elif sequence_parallel:
        rules.append(("act_seq", "mp"))
    else:
        rules.append(("act_seq", None))
    # the sequence axis INSIDE a tensor-parallel block (a column product's
    # output, ACT_AXES): every row of the device's cp share, never mp
    rules.append(("act_seq_tp", "cp" if context_parallel else None))
    rules.append(("act_batch", ("dp", "fsdp")))
    rules.append(("act_embed", None))
    return rules


def logical_to_mesh_sharding(tree, mesh: Mesh, rules: Rules):
    """Map a pytree of logical PartitionSpecs to NamedShardings on mesh."""
    return nn.logical_to_mesh_sharding(tree, mesh, list(rules))


def param_shardings(abstract_vars, mesh: Mesh, rules: Rules):
    """NamedShardings for a flax variables pytree whose params carry
    ``nn.Partitioned`` logical-axis metadata (from nn.with_partitioning)."""
    logical_specs = nn.get_partition_spec(abstract_vars)
    return logical_to_mesh_sharding(logical_specs, mesh, rules)


def with_logical_constraint(x, logical_axes: Tuple[Optional[str], ...]):
    """Constrain an activation to the mesh axes its logical axes map to
    under the rules in force, on the mesh :func:`use_mesh` entered.

    flax's own ``nn.with_logical_constraint`` looks for the mesh in
    ``jax.sharding.get_abstract_mesh()``, which the ``with mesh:`` context
    that ``use_mesh`` enters does not set: under the Trainer it returned
    ``x`` untouched (flax 0.12.3), so the mesh is handed over here. Entries
    that do not divide their dimension are dropped (:func:`_fit_spec`), and
    a spec that shards nothing (no mesh, no rules, every named axis of
    extent 1) adds no constraint: the program of a one-chip run holds no
    trace of the call."""
    mesh = ambient_mesh()
    if mesh is None:
        return x
    spec = _fit_spec(P(*nn.logical_to_mesh_axes(tuple(logical_axes))),
                     x.shape, mesh)
    if all(mesh.shape[a] == 1 for entry in spec for a in _spec_axes(entry)):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _spec_axes(entry) -> Tuple[str, ...]:
    """Mesh axes named by one PartitionSpec entry (str | tuple | None)."""
    if entry is None:
        return ()
    if isinstance(entry, tuple):
        return tuple(a for a in entry if a)
    return (entry,)


def _fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Clamp a PartitionSpec to the dims it evenly divides: entries whose
    mesh-axis product does not divide the dimension are dropped
    (replicated) instead of erroring — a prime vocab under mp2, or the
    size-1 dims of a per-channel quantization scale, simply stay whole."""
    parts = []
    for i, dim in enumerate(shape):
        entry = spec[i] if i < len(spec) else None
        factor = math.prod(int(mesh.shape[a]) for a in _spec_axes(entry))
        parts.append(entry if dim % factor == 0 else None)
    return P(*parts)


def serving_param_shardings(abstract_params, params, mesh: Mesh,
                            rules: Rules):
    """Per-leaf NamedShardings for a SERVED (inference) param tree.

    ``abstract_params`` is the module's ``eval_shape`` init — its
    ``nn.Partitioned`` metadata is the source of each param's logical
    spec; ``params`` is the tree actually served, which may be unboxed
    and may carry int8-quantized ``{"_q8", "_scale"}`` sub-dicts in
    place of float kernels (``ops/quant.quantize_tree_int8``). A
    ``_q8`` leaf inherits its kernel's spec; a ``_scale`` leaf inherits
    it too but its keepdims-1 dims (and any other non-dividing dim)
    drop their axes via :func:`_fit_spec`, so scales end up replicated
    unless their channel axis is genuinely sharded. Leaves with no
    metadata (or paths the abstract tree lacks) replicate."""
    from jax.tree_util import tree_flatten_with_path, tree_map_with_path

    logical = nn.get_partition_spec(abstract_params)
    mesh_sh = logical_to_mesh_sharding(logical, mesh, list(rules))

    def path_names(path):
        return tuple(str(getattr(k, "key", k)) for k in path)

    by_path = {path_names(p): sh
               for p, sh in tree_flatten_with_path(mesh_sh)[0]}

    def one(path, leaf):
        names = path_names(path)
        if names and names[-1] in ("_q8", "_scale"):
            names = names[:-1]
        sh = by_path.get(names)
        spec = sh.spec if sh is not None else P()
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, _fit_spec(spec, shape, mesh))

    return tree_map_with_path(one, params)


def zero_update_spec(spec: Optional[P], shape, mesh: Mesh,
                     axes: Sequence[str] = ("dp", "fsdp")) -> P:
    """PartitionSpec of one parameter's ZeRO *weight-update shard*
    (arxiv 2004.13336: shard the optimizer update across the data-parallel
    replicas, all-gather the result).

    Folds the not-yet-used data-parallel mesh axes onto the first dimension
    they divide evenly — on top of any existing tensor-parallel sharding, so
    a dp x mp config shards the update dp ways *within* each mp shard. Tries
    the full dp x fsdp product first (maximum shard factor), then each axis
    alone. Leaves that no axis divides (tiny biases, scalars) keep their
    original spec and stay replicated — correct, just not sharded."""
    spec = spec if spec is not None else P()
    if not getattr(shape, "__len__", None) or len(shape) == 0:
        return spec
    used = {a for entry in spec for a in _spec_axes(entry)}
    free = [a for a in axes
            if a in mesh.shape and mesh.shape[a] > 1 and a not in used]
    if not free:
        return spec
    parts = list(spec) + [None] * (len(shape) - len(spec))
    candidates = [tuple(free)]
    if len(free) > 1:
        candidates += [(a,) for a in free]
    for cand in candidates:
        factor = math.prod(int(mesh.shape[a]) for a in cand)
        for i, dim in enumerate(shape):
            cur = _spec_axes(parts[i])
            cur_factor = math.prod(int(mesh.shape[a]) for a in cur)
            if dim % (cur_factor * factor):
                continue
            merged = cur + cand
            parts[i] = merged if len(merged) > 1 else merged[0]
            return P(*parts)
    return spec
