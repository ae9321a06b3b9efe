"""Operations and bytes of a prefill chunk's attention over grouped heads
through the paged cache, window and full layers mixed (the kernel
``fleetx_prefill_gqa``), from the configuration's widths and from what the
PROGRAM'S SPANS say its live steps covered (``attn_full_key_rows``,
``attn_window_key_rows``: the key rows of ONE layer and key head, whole
blocks; ``attn_query_rows``: the rows of queries each step takes, a padded
bucket's tail among them). The benchmark's own, like ``flops.py``: a PR that
changes the kernel cannot change what it is measured against. One
multiply-add = 2 operations.

What is counted is what the kernel's live steps DO, work and padding
together: a key block that crosses the chunk's own rows or a window's edge
is counted whole, masked half included, as it passes the MXU whole. The
share of the roofline therefore says how near the steps run to the MXU's
peak, not how many of them a sharper bound on the grid would spare."""

from __future__ import annotations

from perfbench import flops_swa


def chunk_cost(full_key_rows: float, window_key_rows: float,
               query_rows: float, model: dict,
               itemsize: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` of the chunk attention of ONE prefill
    program, all layers: in every full layer each query head's steps cover
    ``full_key_rows`` key rows and in every window layer
    ``window_key_rows``; a key row, query head and chunk of ``query_rows``
    is two products of ``head_size`` a query (``4 x query_rows x
    head_size`` operations) on the row's key and value copied for that
    head (``2 x head_size`` values); the queries in and the outputs out are
    ``query_rows x heads x head_size`` each a layer."""
    full, window = flops_swa.layer_counts(model)
    heads = model["num_attention_heads"]
    head = model.get("head_size") or model["hidden_size"] // heads
    rows = full_key_rows * full + window_key_rows * window
    ops = 4.0 * head * query_rows * heads * rows
    bytes_ = (2.0 * head * itemsize * heads * rows
              + 2.0 * query_rows * heads * head * itemsize * (full + window))
    return ops, bytes_
