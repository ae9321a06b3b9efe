"""Body and head of a served GPT applied apart: the cached forward whose
caller reads the logits of some rows only (``GPTExecutor.forward`` with
``logit_rows``, serving/model_protocol.py).

``GPTForPretraining`` is the backbone (embeddings, the stack of whichever
kind ``block_fields.stack_of`` gives, the final norm) and one product with
the head's table, whatever the family. ``decode_step`` applies both to every
row. A prefill program samples from ONE row, and an intermediate chunk of a
chunked prefill from none, so here the backbone is applied by itself on its
own subtrees of the parameters and the cache, the wanted row is sliced from
its output, and the head's product runs on that row: no ``[rows, vocab]``
array exists.

A module of its own, and not lines of model.py, for ``resident.py``'s
reason: a line added above the code that training traces makes every
training program a new program to the compile cache.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from fleetx_tpu.models.gpt.model import GPTModel

__all__ = ["row_logits_step"]


def _head(cfg, params, hidden):
    """``GPTForPretraining``'s head on ``hidden`` ``[b, rows, hidden]``:
    float32 logits ``[b, rows, vocab]`` from the tied word table or the
    untied ``lm_head`` (sharded over the vocabulary under a mesh: the one
    product either way; ``num_pred_heads`` heads side by side in one table
    give ``[b, rows, heads * vocab]``, head ``j`` in columns ``[vocab * j,
    vocab * (j + 1))``)."""
    emb = nn.meta.unbox(params["gpt"]["word_embeddings"]
                        if cfg.tie_word_embeddings else params["lm_head"])
    return jnp.einsum(
        "bsh,vh->bsv", hidden, emb.astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


def row_logits_step(model, params, cache, input_ids, position_ids,
                    kv_mask=None, cache_positions=None, block_tables=None, *,
                    logit_rows, **rows_in):
    """``decode_step`` for a caller that reads one row a batch element:
    ``(logits [b, 1, vocab], new_cache)``, the logits those of row
    ``logit_rows[b]``. A NEGATIVE entry asks for no row of its element;
    where every entry is negative the head is not run at all (the program
    takes the other side of a conditional) and the logits are zeros.
    ``rows_in``: ``input_rows=`` for a model that takes a tower's rows
    beside ids (``block_fields.rows_in``)."""
    hidden, mut = GPTModel(model.cfg).apply(
        {"params": params["gpt"], "cache": cache["gpt"]},
        input_ids,
        position_ids,
        kv_mask,
        decode=True,
        cache_positions=cache_positions,
        block_tables=block_tables,
        mutable=["cache"], **rows_in,
    )
    rows = jnp.asarray(logit_rows, jnp.int32)
    # the model's own scope for its head, around the slice and the
    # conditional too: device time is booked by it
    # (perfbench/layer_metrics/_parts.py)
    with jax.named_scope("logits"):
        picked = jnp.take_along_axis(
            hidden, jnp.maximum(rows, 0)[:, None, None], axis=1)
        logits = jax.lax.cond(
            jnp.any(rows >= 0),
            lambda row: _head(model.cfg, params, row),
            lambda row: jnp.zeros((*row.shape[:2], model.cfg.head_rows),
                                  jnp.float32),
            picked)
    return logits, {**cache, "gpt": mut["cache"]}
