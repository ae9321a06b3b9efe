"""Operations and bytes of what a stack of gated short-convolution layers
and grouped-query attention layers does at a decode tick, from the shapes
and from the rows that were really live. The benchmark's own, like
``flops.py``: a PR that changes the kernel, the operator or the pool cannot
change what they are measured against. One multiply-add = 2 operations."""

from __future__ import annotations


def layer_counts(model: dict) -> tuple[int, int]:
    """``(attention layers, convolution layers)`` of a configuration's
    ``model`` group (``GPTConfig``'s names)."""
    types = model["layer_types"]
    attention = sum(t == "full_attention" for t in types)
    return attention, len(types) - attention


def row_bytes(model: dict, itemsize: int = 2) -> int:
    """Bytes of one token's key and value in ONE attention layer: the cache
    holds ``num_key_value_heads`` heads, whatever the number of query
    heads."""
    heads = model["num_attention_heads"]
    kv_heads = model.get("num_key_value_heads") or heads
    head = model.get("head_size") or model["hidden_size"] // heads
    return 2 * kv_heads * head * itemsize


def decode_tick_cost(attn_rows: float, lanes: int, model: dict,
                     itemsize: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` of the decode attention calls of ONE tick:
    ``attn_rows`` live cache rows (summed over the lanes) are read in every
    ATTENTION layer and in no other, a row being one token's key and value
    (:func:`row_bytes`); every query head takes part in two products of the
    head size a row; the queries and outputs (``lanes`` rows of ``heads *
    head`` a layer, in and out) are small beside it."""
    attention, _ = layer_counts(model)
    heads = model["num_attention_heads"]
    head = model.get("head_size") or model["hidden_size"] // heads
    rows = attn_rows * attention
    ops = 2 * 2.0 * rows * heads * head
    bytes_ = (rows * row_bytes(model, itemsize)
              + 2.0 * lanes * heads * head * itemsize * attention)
    return ops, bytes_


def conv_layer_cost(rows: float, model: dict,
                    itemsize: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` of ONE gated short-convolution operator over
    ``rows`` tokens of one program: the input projection ``h -> 3h`` and
    the output projection ``h -> h`` (read once, whole), two gates and
    ``conv_L_cache`` taps a channel, and the state's rows read and
    written."""
    h, taps = model["hidden_size"], model.get("conv_L_cache", 3)
    ops = rows * (2.0 * h * 4 * h + (2 + 2 * taps) * h)
    bytes_ = (4.0 * h * h + 2.0 * rows * h
              + rows * 2.0 * (taps - 1) * h) * itemsize
    return ops, bytes_


def tail_page_bytes(model: dict, itemsize: int = 2) -> int:
    """Bytes one page holds of the convolution state, all layers."""
    _, conv = layer_counts(model)
    return conv * (model.get("conv_L_cache", 3) - 1) * model["hidden_size"] \
        * itemsize
