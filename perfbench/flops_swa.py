"""Operations and bytes of decode attention over grouped heads and a cache
of two classes of page (window and full attention layers mixed), from the
shapes and from the rows that were really live. The benchmark's own, like
``flops.py``: a PR that changes the kernel or the pool cannot change what
they are measured against. One multiply-add = 2 operations."""

from __future__ import annotations


def layer_counts(model: dict) -> tuple[int, int]:
    """``(full layers, window layers)`` of a configuration's ``model``
    group (``GPTConfig``'s names)."""
    layers = model["num_layers"]
    if not model.get("sliding_window"):
        return layers, 0
    window = sum(model.get("sliding_window_layout") or [1] * layers)
    return layers - window, window


def row_bytes(model: dict, itemsize: int = 2) -> int:
    """Bytes of one token's key and value in ONE layer: the cache holds
    ``num_key_value_heads`` heads of ``head_size``, whatever the number of
    query heads."""
    heads = model["num_attention_heads"]
    kv_heads = model.get("num_key_value_heads") or heads
    head = model.get("head_size") or model["hidden_size"] // heads
    return 2 * kv_heads * head * itemsize


def decode_tick_cost(full_rows: float, window_rows: float, lanes: int,
                     model: dict, itemsize: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` of the decode attention calls of ONE tick,
    all layers: ``full_rows`` live cache rows (summed over the lanes) are
    read in every full layer and ``window_rows`` (each lane's rows inside
    the window) in every window layer, a row being one token's key and
    value (:func:`row_bytes`); every query head takes part in two products
    of ``head_size`` a row; the queries and outputs (``lanes`` rows of
    ``heads * head_size`` a layer, in and out) are small beside it."""
    full, window = layer_counts(model)
    heads = model["num_attention_heads"]
    head = model.get("head_size") or model["hidden_size"] // heads
    rows = full_rows * full + window_rows * window
    ops = 2 * 2.0 * rows * heads * head
    bytes_ = (rows * row_bytes(model, itemsize)
              + 2.0 * lanes * heads * head * itemsize * (full + window))
    return ops, bytes_


def pool_bytes_share(pages_full: float, pages_window: float,
                     model: dict) -> float:
    """Bytes of the pages in use over what ONE class of page would hold
    for the same lanes: a lane then keeps every token in every layer, as
    its full-layer pages do now."""
    full, window = layer_counts(model)
    return ((pages_full * full + pages_window * window)
            / (pages_full * (full + window)))
