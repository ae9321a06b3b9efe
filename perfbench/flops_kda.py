"""Operations and bytes of what a KDA (gated delta-rule) layer does at a
decode tick and in a prefill call, FROM THE RECURRENCE and from the rows and
lanes that were really live: the same work whatever form the kernel takes
(row by row or chunkwise). The benchmark's own, like ``flops.py``: a PR that
changes the kernels, the operator or where the state is held cannot change
what they are measured against. One multiply-add = 2 operations."""

from __future__ import annotations

# what one state element costs a row: the decay's product, S^T k (a
# multiply-add), the correction's outer product (a multiply-add) and S^T q
# (a multiply-add)
OPS_PER_STATE_ELEMENT = 7


def kda_layers(model: dict) -> int:
    return sum(t == "kda" for t in model["layer_types"])


def sizes(model: dict) -> tuple[int, int, int]:
    """``(heads, head size, filter rows kept)``."""
    return (model["kda_num_heads"], model["kda_head_dim"],
            model.get("kda_conv_size", 4) - 1)


def lane_state_bytes(model: dict, itemsize: int = 2) -> int:
    """Bytes ONE lane holds in ONE KDA layer: ``S`` ``[heads, d, d]``
    float32 and the three filters' last inputs in the compute type
    (4,194,304 + 147,456 = 4,341,760 at the published widths)."""
    heads, d, rows = sizes(model)
    return heads * d * d * 4 + rows * 3 * heads * d * itemsize


def _row_bytes(model: dict) -> float:
    """A row's ``q, k, v, g`` read and ``o`` written once, float32, and its
    ``beta``."""
    heads, d, _ = sizes(model)
    return (5 * heads * d + heads) * 4.0


def step_cost(lanes: float, model: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the one-row update of ONE tick over
    ``lanes`` decoding lanes, all KDA layers: a lane's ``S`` read and
    written once a layer (2 x 4,194,304 B at the published widths) and its
    row's operands once. The filter's rows move outside the kernel, under
    ``cache_write/kda_state``, and are in neither side."""
    heads, d, _ = sizes(model)
    each = lanes * kda_layers(model)
    return (each * OPS_PER_STATE_ELEMENT * heads * d * d,
            each * (2.0 * heads * d * d * 4 + _row_bytes(model)))


def chunk_cost(rows: float, model: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the delta rule of ONE prefill call over
    ``rows`` rows of one lane (padding included: the kernel runs them), all
    KDA layers: ``7 x d x d`` operations a head and row; the lane's ``S``
    read and written once a layer and call, the rows' operands once."""
    heads, d, _ = sizes(model)
    layers = kda_layers(model)
    return (layers * rows * OPS_PER_STATE_ELEMENT * heads * d * d,
            layers * (rows * _row_bytes(model) + 2.0 * heads * d * d * 4))
