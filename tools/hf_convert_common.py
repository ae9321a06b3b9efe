"""Shared pieces of the HF checkpoint converters (convert_hf_bert /
convert_hf_vit): transposed-Linear extraction and the per-head qkv packing
that must stay in lockstep with the models' fused ``qkv_proj`` layout
([..., embed, heads, 3*head_dim], q|k|v packed per head along the last
axis)."""

import numpy as np


def linear_t(sd, name):
    """HF Linear params: weight [out, in] -> [in, out], plus bias."""
    return sd[name + ".weight"].T, sd[name + ".bias"]


def pack_qkv(sd, prefix, n_head: int, head_dim: int):
    """Separate q/k/v Linears -> fused per-head layout.

    ``{prefix}{query,key,value}`` [h, h] Linears become kernel
    [h, n_head, 3*head_dim] and bias [n_head, 3*head_dim].
    """
    h = n_head * head_dim
    kerns, biases = [], []
    for part in ("query", "key", "value"):
        w, b = linear_t(sd, prefix + part)
        kerns.append(w.reshape(h, n_head, head_dim))
        biases.append(b.reshape(n_head, head_dim))
    return np.concatenate(kerns, axis=-1), np.concatenate(biases, axis=-1)
