"""The served form of a GPT tree: which leaves a server may keep in the
compute dtype (``GPTExecutor.resident_params``, serving/model_protocol.py).

A module of its own, and not a part of model.py, so that a change here moves
no line of the code that training traces: with op_names and source lines in
the compile cache's key (utils/compile_cache.py), a line added there makes
every training program a new program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fleetx_tpu.models.gpt.model import GPTConfig

__all__ = ["resident_params"]

# Which leaves a served tree may hold in ``cfg.dtype`` (resident_params):
# the modules whose kernel and bias flax converts WHOLE to ``cfg.dtype``
# before their first use (``_dense``, ``attn_out_dense``), and the expert
# weights that parallel/moe.py converts the same way. Everything else stays
# as handed over: what the model uses in float32 (the scales and biases of
# the norms, the router, the position table, the word table as the
# embedding's gather reads it, summed with the position rows in float32 and
# rounded once), and the head's table, tied or not: on a TPU the compiled
# head of a one-token program reads the float32 table without rounding it
# (XLA may keep excess precision), so a table rounded beforehand gives
# other logits there (PERF.md, PR 28).
_WHOLE_CAST = frozenset({
    "qkv_proj", "q_proj", "k_proj", "v_proj", "out_proj",   # modules
    "up_proj", "gate_proj", "down_proj", "in_proj",
    "w_gate", "w_up", "w_down", "b_up", "b_down"})          # expert leaves


def resident_params(cfg: GPTConfig, params):
    """``params`` as a server should hold them: every float leaf that the
    model converts whole to ``cfg.dtype`` before its first use holds that
    converted value (the same bits, made once instead of in every
    program). Decided by what the tree holds: a leaf already in
    ``cfg.dtype`` (a bfloat16 tree, a float32 model) comes back as the
    object it was, and a tree with integer leaves (weight-only int8,
    ``{"_q8", "_scale"}``) comes back untouched."""
    dtype = jnp.dtype(cfg.dtype)
    if not all(jnp.issubdtype(leaf.dtype, jnp.floating)
               for leaf in jax.tree.leaves(params)):
        return params

    def one(path, leaf):
        # the leaf's own name and its module's (a flax box adds no name)
        names = [k.key for k in path if hasattr(k, "key")][-2:]
        whole = not _WHOLE_CAST.isdisjoint(names)
        return leaf.astype(dtype) if whole and leaf.dtype != dtype else leaf

    return jax.tree_util.tree_map_with_path(one, params)
