"""Operations and bytes of EVA attention's decode calls (a tumbling window of
exact rows beside one pooled row a chunk, both classes in every layer), from
the shapes and from the rows the PROGRAM'S SPANS say a tick attended over
(``eva_window_rows``, ``eva_summary_rows``), not from any kernel's arguments:
whatever kernel implements the read is measured against the same bytes. The
benchmark's own, like ``flops.py``. One multiply-add = 2 operations."""

from __future__ import annotations


def row_bytes(model: dict, itemsize: int = 2) -> int:
    """Bytes of one cached row, exact or pooled, in ONE layer: a key and a
    value of as many heads as the queries have (16,384 at the published
    widths)."""
    heads = model["num_attention_heads"]
    head = model.get("head_size") or model["hidden_size"] // heads
    return 2 * heads * head * itemsize


def decode_tick_cost(window_rows: float, summary_rows: float, lanes: int,
                     model: dict, itemsize: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` of the decode attention calls of ONE tick, all
    layers: ``window_rows`` exact and ``summary_rows`` pooled rows (summed
    over the lanes) are read in EVERY layer; every head takes part in two
    products of its size a row; the queries and outputs (``lanes`` rows of
    ``heads * head`` a layer, in and out) are small beside it."""
    layers, heads = model["num_layers"], model["num_attention_heads"]
    head = model.get("head_size") or model["hidden_size"] // heads
    rows = (window_rows + summary_rows) * layers
    return (2 * 2.0 * rows * heads * head,
            rows * row_bytes(model, itemsize)
            + 2.0 * lanes * heads * head * itemsize * layers)
