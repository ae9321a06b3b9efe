"""Operations and bytes of learned sparse attention over GROUPED heads
(Keye-VL-2.0: the indexer of ``flops_dsa.py`` before a paged cache with key
and value heads), from the configuration's widths and from the rows the
PROGRAM'S SPANS say were selected (``selected_rows``), not from any kernel's
arguments: the count reads the same work whatever implements it. The
benchmark's own, like ``flops.py``. One multiply-add = 2 operations. The
indexer's own scores are ``flops_dsa.index_cost``'s (the same mechanism: it
reads ``index_n_heads`` and ``index_head_dim``).

What is counted is what the mechanism NEEDS: a query attends over the rows
kept. A program that attends under a mask over every key block (today's
chunk: ``fleetx_gqa_sparse_prefill``) does several times that at a long
context, and a program that first copies the chosen rows into a compact
pool (today's tick) moves them more than once: its share of this roofline
says so."""

from __future__ import annotations


def widths(model: dict) -> tuple[int, int, int]:
    """``(query heads, key/value heads, head size)``."""
    heads = int(model["num_attention_heads"])
    return (heads, int(model.get("num_key_value_heads") or heads),
            int(model.get("head_size") or model["hidden_size"] // heads))


def sparse_chunk_cost(selected_rows: float,
                      model: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of a CHUNK's attention over ``selected_rows``
    (query, chosen row) pairs, one layer: every query head's score and
    value product. The chosen rows' bytes are NOT counted: how many rows
    the chunk's queries share is not in the spans, so the count is a floor
    of what any form needs (compute-bound by far at 512 queries)."""
    heads, _, d = widths(model)
    return selected_rows * heads * 2 * d * 2.0, 0.0


def sparse_decode_cost(selected_rows: float,
                       model: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of a TICK's attention over ``selected_rows``
    (query, chosen row) pairs, one layer: every query head's score against
    the row's key and its value product, the row's key and value read ONCE
    from the pool for all the heads of their group (``2 x kv_heads x d``
    values of 2 B: 2,048 B at the published widths; a tick's lane has ONE
    query, so a row a pair is exact). The index key is the indexer's
    (``flops_dsa.index_cost``)."""
    heads, kv_heads, d = widths(model)
    return (selected_rows * heads * 2 * d * 2.0,
            selected_rows * 2.0 * kv_heads * d * 2)
