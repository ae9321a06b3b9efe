"""Operations and bytes the algorithm needs, computed from shapes.

These are the benchmark's own: a PR that changes a kernel cannot change
what the kernel is measured against. All counts are of the mathematics
(recomputed work is not counted), one multiply-add = 2 operations.
"""

from __future__ import annotations


def gpt_param_count(model: dict) -> int:
    """Parameters of the GPT in a configuration file's ``model`` group
    (tied head, learned positions, biases and LayerNorms included)."""
    h, layers = model["hidden_size"], model["num_layers"]
    ffn = model.get("ffn_hidden_size") or 4 * h
    per_layer = (h * 3 * h + 3 * h      # fused qkv
                 + h * h + h            # attention out
                 + h * ffn + ffn        # mlp up
                 + ffn * h + h          # mlp down
                 + 4 * h)               # two LayerNorms
    return (model["vocab_size"] * h + model["max_position_embeddings"] * h
            + layers * per_layer + 2 * h)


def train_flops_per_token(model: dict, seq: int) -> float:
    """Model FLOPs of one trained token (copied from ``bench.py``
    ``model_flops_per_token``): 6 per parameter (forward 2, backward 4, the
    tied head through the shared weight) plus causal attention's score and
    value products (forward 4*s*h per layer, halved for causality, x3 for
    forward and backward). Recomputation is excluded."""
    return (6.0 * gpt_param_count(model)
            + model["num_layers"] * 6.0 * seq * model["hidden_size"])


def flash_call_cost(kind: str, batch: int, heads: int, q_len: int,
                    kv_len: int, head_dim: int, causal: bool = True,
                    itemsize: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` one flash attention call needs.

    ``kind`` is ``fwd`` (QK^T and PV: 2 products), ``dq`` (recompute S, dP,
    dQ: 3 products) or ``dkv`` (recompute S, dP, dV, dK: 4 products). Each
    product is 2*q*kv*d per head, halved under a causal mask. Bytes are
    each operand read once and each result written once (Q, K, V, O and
    for the backward kernels dO and the gradients), which a kernel that
    re-reads K/V per query block exceeds.
    """
    products = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    pair = q_len * kv_len * (0.5 if causal else 1.0)
    ops = products * 2.0 * batch * heads * pair * head_dim
    q_bytes = batch * heads * q_len * head_dim * itemsize
    kv_bytes = batch * heads * kv_len * head_dim * itemsize
    tensors = {"fwd": 2 * q_bytes + 2 * kv_bytes,          # Q,O + K,V
               "dq": 4 * q_bytes + 2 * kv_bytes,           # Q,O,dO,dQ + K,V
               "dkv": 3 * q_bytes + 4 * kv_bytes}[kind]    # Q,O,dO + K,V,dK,dV
    return ops, float(tensors)


def paged_decode_call_cost(live_tokens: int, heads: int, head_dim: int,
                           lanes: int, itemsize: int = 2
                           ) -> tuple[float, float]:
    """``(operations, bytes)`` of one paged decode attention call over
    ``live_tokens`` cached positions in all (summed over the lanes): each
    position's K and V row is read once and takes part in two products of
    ``head_dim`` per head; the query and output rows are small beside it."""
    row = heads * head_dim
    ops = 2 * 2.0 * live_tokens * row
    bytes_ = 2.0 * live_tokens * row * itemsize + 2.0 * lanes * row * itemsize
    return ops, bytes_


def roofline_seconds(ops: float, bytes_: float, peaks: dict,
                     ops_key: str = "bf16_flops") -> tuple[float, str]:
    """The least time the chip could take and which bound sets it."""
    t_ops = ops / peaks[ops_key]
    t_mem = bytes_ / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
