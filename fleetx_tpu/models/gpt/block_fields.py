"""``GPTConfig``'s fields for a block whose layers are not all alike:
grouped-query heads, a head size of its own, window and full attention
layers mixed, rotary and position-free layers mixed, a router that reads
the block's input. ``GPTConfig`` inherits them, ``check`` is the part of its
``__post_init__`` that refuses what nobody wrote, and ``layer_class`` picks
the layer that runs them (``models/gpt/hybrid.py``).

A module of its own, and not a part of model.py, for the reason
``models/gpt/resident.py`` gives: a line added there makes every training
program a new program (ROADMAP D11). It imports nothing of the model.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["BlockLayoutFields", "LAYOUT_FIELDS", "check", "layer_class"]

# per-layer lists (a YAML or JSON list becomes a tuple: the configuration is
# a module attribute and has to hash)
LAYOUT_FIELDS = ("rope_layout", "sliding_window_layout")


@dataclasses.dataclass(frozen=True)
class BlockLayoutFields:
    """The fields, every default the GPT-2 block's (and OLMoE's)."""

    # grouped-query attention: key/value heads, each shared by
    # ``num_attention_heads // num_key_value_heads`` query heads (query head
    # h reads key head ``h // group``); None: one for every query head
    num_key_value_heads: Optional[int] = None
    # the size of one head where it is not ``hidden_size //
    # num_attention_heads`` (``GPTConfig.head_dim`` resolves it; a stored
    # ``head_dim`` would go stale under ``dataclasses.replace``)
    head_size: Optional[int] = None
    # window attention: query i sees key j where ``j <= i`` and ``i - j <
    # sliding_window``, in the layers ``sliding_window_layout`` marks 1 (a
    # list of ``num_layers`` entries; None with a window: every layer)
    sliding_window: Optional[int] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    # under ``position_embedding: rope``, the layers that rotate (1) and
    # those that take no position at all (0); None: every layer rotates
    rope_layout: Optional[Tuple[int, ...]] = None
    # what a softmax top-k router reads: the stream its experts read
    # ("mlp_norm": norm2 of the post-attention stream) or the block's input
    # before norm1 ("block_input")
    router_input: str = "mlp_norm"
    # serving, set by the engine beside ``decode_num_pages`` (which then
    # counts one full-attention layer's pages): the pages of one WINDOW
    # layer, whose lanes keep only the rows a live query can still see
    decode_window_pages: Optional[int] = None

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def layer_kinds(self) -> bool:
        """Whether the layers differ from each other or from the one block
        ``models/gpt/model.py`` writes: ``models/gpt/hybrid.py`` runs them."""
        return bool(self.kv_heads != self.num_attention_heads
                    or self.head_size or self.sliding_window
                    or self.rope_layout or self.router_input != "mlp_norm")

    @property
    def window_layers(self) -> Tuple[int, ...]:
        """1 for every layer that attends through the window."""
        if not self.sliding_window:
            return (0,) * self.num_layers
        return tuple(self.sliding_window_layout or (1,) * self.num_layers)

    @property
    def rope_layers(self) -> Tuple[int, ...]:
        """1 for every layer that rotates its queries and keys."""
        if self.position_embedding != "rope":
            return (0,) * self.num_layers
        return tuple(self.rope_layout or (1,) * self.num_layers)


def check(cfg) -> None:
    """Refuse, with the field's name, what the layers cannot be built from;
    lists arrive as tuples afterwards."""
    for name in LAYOUT_FIELDS:
        value = getattr(cfg, name)
        if value is None:
            continue
        value = tuple(int(v) for v in value)
        object.__setattr__(cfg, name, value)
        if len(value) != cfg.num_layers or set(value) - {0, 1}:
            raise ValueError(
                f"{name} has {len(value)} entries {value}; it needs "
                f"num_layers = {cfg.num_layers} entries of 0 or 1")
    if cfg.router_input not in ("mlp_norm", "block_input"):
        raise ValueError(f"router_input={cfg.router_input!r}; choose "
                         "mlp_norm | block_input")
    if cfg.num_attention_heads % cfg.kv_heads:
        raise ValueError(
            f"num_key_value_heads {cfg.kv_heads} does not divide "
            f"num_attention_heads {cfg.num_attention_heads}")
    if cfg.mlp_act == "reglu" and not (cfg.expert_mode
                                       and cfg.gate == "softmax_topk"):
        raise ValueError("mlp_act='reglu' is the gate of the softmax top-k "
                         "expert layer (gate: softmax_topk); the dense MLP "
                         "has gelu and swiglu")
    if cfg.sliding_window_layout and not cfg.sliding_window:
        raise ValueError("sliding_window_layout without sliding_window")
    if cfg.rope_layout and cfg.position_embedding != "rope":
        raise ValueError("rope_layout without position_embedding='rope'")
    if cfg.router_input != "mlp_norm" and not (
            cfg.expert_mode and cfg.gate == "softmax_topk"):
        raise ValueError(f"router_input={cfg.router_input!r} without a "
                         "softmax top-k expert layer")
    if cfg.sliding_window is not None:
        held = cfg.decode_cache_len or cfg.max_position_embeddings
        if not 0 < cfg.sliding_window <= held:
            raise ValueError(
                f"sliding_window {cfg.sliding_window} needs a cache length "
                f"that can hold it (decode_cache_len or "
                f"max_position_embeddings: {held})")
    if not cfg.layer_kinds:
        return
    for field, why in (
            ("qk_norm", "QK-norm over grouped heads: no test covers it"),
            ("sequence_parallel", "no test covers it"),
            ("no_recompute_layers", "the layers run as ONE scanned body, "
                                    "whatever the depth")):
        if getattr(cfg, field):
            raise NotImplementedError(
                f"{field} with grouped heads, a head size of its own or "
                f"mixed layers: {why}")
    if cfg.pp_degree > 1 or cfg.cp_degree > 1 or not cfg.scan_layers:
        raise NotImplementedError(
            "grouped heads, a head size of its own or mixed layers need "
            "scan_layers and no pipeline or context parallelism (the stage "
            "and ring paths carry no layer kind)")
    if cfg.decode_kv_dtype is not None:
        raise NotImplementedError(
            "decode_kv_dtype with grouped heads: the decode kernels take "
            "int8 scales a head, for as many key heads as query heads")


def layer_class(cfg, default):
    """The decoder layer class for ``cfg``: ``default`` (model.py's) for
    the blocks it writes, ``hybrid.HybridDecoderLayer`` otherwise."""
    if not cfg.layer_kinds:
        return default
    from fleetx_tpu.models.gpt.hybrid import HybridDecoderLayer

    return HybridDecoderLayer
