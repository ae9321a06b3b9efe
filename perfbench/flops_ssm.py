"""Operations and bytes of what a Mamba-1 selective-scan layer does at a
decode tick and in a prefill, from the shapes and from the rows and lanes
that were really live. The benchmark's own, like ``flops.py``: a PR that
changes the kernels, the operator or where the state is held cannot change
what they are measured against. One multiply-add = 2 operations."""

from __future__ import annotations

# what one state element costs a row: dt * A, the exponential, its product
# with h, (dt u) * B, the sum, the product with C and the reduction's add
OPS_PER_STATE_ELEMENT = 7


def mamba_layers(model: dict) -> int:
    return sum(t == "mamba" for t in model["layer_types"])


def sizes(model: dict) -> tuple[int, int, int]:
    """``(inner width, state of a channel, filter rows kept)``."""
    return (model.get("mamba_expand", 2) * model["hidden_size"],
            model.get("mamba_d_state", 16), model.get("mamba_d_conv", 4) - 1)


def lane_state_bytes(model: dict, itemsize: int = 2) -> int:
    """Bytes ONE lane holds in ONE selective-scan layer: ``h`` float32 and
    the filter's last inputs in the compute type (358,400 at the published
    widths)."""
    inner, state, rows = sizes(model)
    return state * inner * 4 + rows * inner * itemsize


def step_cost(lanes: float, model: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the one-row update of ONE tick over
    ``lanes`` decoding lanes, all selective-scan layers, as the step KERNEL
    moves them: a lane's ``h`` read and written once a layer (2 x 327,680 B
    at the published widths); its row's ``u, dt, B, C, y`` are small beside
    it and not counted. The filter's rows (2 x 30,720 B more of a lane's
    2 x 358,400) move outside the kernel, under ``cache_write/ssm_state``,
    and their time is not the kernel's: counted here they would flatter it."""
    inner, state, _ = sizes(model)
    each = lanes * mamba_layers(model)
    return (each * OPS_PER_STATE_ELEMENT * state * inner,
            each * 2.0 * state * inner * 4)


def scan_cost(rows: float, model: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of the scans of ONE prefill call over
    ``rows`` rows (padding included: the kernel runs them), all
    selective-scan layers: ``u, dt`` read and ``y`` written once a row
    (float32), ``B, C`` read once a row, and the state read and written
    once a call."""
    inner, state, _ = sizes(model)
    layers = mamba_layers(model)
    row_bytes = (3 * inner + 2 * state) * 4.0
    return (layers * rows * OPS_PER_STATE_ELEMENT * state * inner,
            layers * (rows * row_bytes + 2.0 * state * inner * 4))
