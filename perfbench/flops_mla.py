"""Operations and bytes of latent attention (MLA) at a decode tick and in a
prefill chunk, from the shapes and from the rows that were really live. The
benchmark's own, like ``flops.py``: a PR that changes the kernel, the
operator or the cache's layout cannot change what they are measured
against. One multiply-add = 2 operations.

A cached row is ONE compressed vector ``c_kv`` (``kv_lora_rank``) and ONE
rotary key ``k_r`` (``qk_rope_head_dim``), shared by every head. In HBM the
rotary key's leaf occupies whole 128-lane tiles (``LANES``), so a row costs
``(kv_lora_rank + 128) x itemsize`` bytes to read, not ``(kv_lora_rank +
64) x itemsize``: the bytes are counted AS THE LAYOUT HOLDS THEM, because
that is what the chip has to move."""

from __future__ import annotations

LANES = 128  # columns of one HBM / VMEM tile


def widths(model: dict) -> tuple[int, int, int, int, int]:
    """``(heads, c_kv, k_r, nope, v)``."""
    return (model["num_attention_heads"], model["kv_lora_rank"],
            model["qk_rope_head_dim"], model["qk_nope_head_dim"],
            model["v_head_dim"])


def row_bytes(model: dict, itemsize: int = 2) -> int:
    """Bytes of one cached row of one layer as the pool's two leaves hold
    it (1,280 at the published widths; 1,152 of them are values)."""
    _, c, r, _, _ = widths(model)
    return (c + -(-r // LANES) * LANES) * itemsize


def decode_cost(rows: float, model: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of ONE call of the absorbed decode kernel
    (one layer, one tick) over ``rows`` live cached rows, all lanes
    together: every head's score against the row (``c_kv + k_r`` columns)
    and its value product (``c_kv`` columns), and the row read ONCE for all
    heads, keys and values alike. The queries and outputs (``heads x (c_kv
    + k_r)`` a lane) are small beside it and not counted."""
    heads, c, r, _, _ = widths(model)
    return rows * heads * (c + r + c) * 2.0, rows * float(row_bytes(model))


def reexpansion_cost(rows: float, model: dict) -> float:
    """Operations of re-expanding ``rows`` cached latents into keys and
    values of every head (``c_kv W_kvb``), one layer: what a chunk pays
    again for every row before it."""
    heads, c, _, nope, v = widths(model)
    return rows * 2.0 * c * heads * (nope + v)


def chunk_attention_cost(chunk: float, rows: float, model: dict) -> float:
    """Operations of a chunk of ``chunk`` queries scoring ``rows`` keys and
    summing their values, materialised, one layer (masked pairs counted: the
    program computes whole key blocks)."""
    heads, _, r, nope, v = widths(model)
    return chunk * rows * heads * (nope + r + v) * 2.0
