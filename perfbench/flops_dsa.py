"""Operations and bytes of learned sparse attention (DeepSeek-V3.2's indexer
and the attention over the rows it keeps), from the configuration's widths
and from the rows the PROGRAM'S SPANS say were scored and selected
(``index_rows``, ``selected_rows``), not from any kernel's arguments: the
count reads the same work whatever implements it. The benchmark's own, like
``flops.py``. One multiply-add = 2 operations.

What is counted is what the mechanism NEEDS: a query scores every index key
behind it, and attends over the rows kept. A program that attends under a
mask over every key block (today's chunk: ``fleetx_dsa_prefill``) does
several times that at a long context, and its share of this roofline says
so."""

from __future__ import annotations

from perfbench import flops_mla


def index_cost(index_rows: float, model: dict,
               itemsize: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` of scoring ``index_rows`` (query, key row)
    pairs, one layer: every index head's product with the key
    (``index_n_heads x index_head_dim`` multiply-adds; the ReLU and the
    weighted sum over heads are small beside it), and the key read once a
    pair (a tick's lane reads each of its keys once; a chunk's rows share
    them, which a kernel may use and the count does not assume)."""
    heads, dim = model["index_n_heads"], model["index_head_dim"]
    return index_rows * 2.0 * heads * dim, index_rows * float(dim * itemsize)


def sparse_decode_cost(selected_rows: float,
                       model: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of a TICK's attention over ``selected_rows``
    (query, chosen row) pairs in the ABSORBED form, one layer: every head's
    score against the row (``c_kv + k_r`` columns) and its value product
    (``c_kv``), the row read once for all heads as the pool's two leaves
    hold it (``flops_mla.row_bytes``: 1,280 B at the published widths; a
    tick's lane has ONE query, so a row a pair is exact)."""
    heads, c, r, _, _ = flops_mla.widths(model)
    return (selected_rows * heads * (c + r + c) * 2.0,
            selected_rows * float(flops_mla.row_bytes(model)))


def sparse_chunk_cost(selected_rows: float,
                      model: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of a CHUNK's attention over ``selected_rows``
    (query, chosen row) pairs, one layer, in the cheaper of the two forms a
    pair: materialised, every head's score (``nope + rope`` columns) and
    value product (``v``). The re-expansion of the chosen rows and their
    bytes are NOT counted: how many rows the chunk's queries share is not
    in the spans, so the count is a floor of what any form needs (the
    absorbed form pays ``(576 + 512) / 320`` = 3.4 times as much a pair and
    no re-expansion)."""
    heads, _, r, nope, v = flops_mla.widths(model)
    return selected_rows * heads * (nope + r + v) * 2.0, 0.0
