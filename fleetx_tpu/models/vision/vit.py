"""Vision Transformer, TPU-native flax implementation.

Capability parity with the reference ViT zoo
(/root/reference/ppfleetx/models/vision_model/vit/vit.py:100-443 and
vision_model/layers/: patch embedding, fused-qkv attention, MLP, droppath,
class-token pooling, 14 size presets up to ViT-6B).

TPU-first: patch embedding is a Conv (maps to MXU), attention reuses the
shared fused path (ops/attention.py), TP sharding is the same logical-axis
annotation scheme as GPT/ERNIE so ViT-G/6B presets shard over mp/fsdp
without model changes.

**A tower served** (:class:`VisionTower`; ``serving/engine.py`` runs it a
bucket of patches a program, docs/SERVING.md "Rows from a tower"): the same
``ViTBlock``s over ONE image's patches with no class token, a learned
position table of ``grid x grid`` entries interpolated bilinearly to the
image's own grid, every patch seeing every patch of ITS image (the bucket's
padding masked: ``kv_lens``), then a 2 x 2 merge and an MLP projector to the
language model's width: ``h x w`` rows of an image of ``2h x 2w`` patches.
Device scopes ``vit_attn``, ``vit_mlp`` (a block's two halves) and
``vit_project`` (the merge and projector). Plain attention (head size 72 is
no kernel's), a block of ``attn_q_block`` queries at a time.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from fleetx_tpu.models.gpt.model import (
    _constrain_act,
    _dense,
    _layer_norm,
    attn_out_dense,
    default_kernel_init,
)
from fleetx_tpu.ops.attention import causal_attention
from fleetx_tpu.ops.dropout import dropout_layer

Dtype = Any

__all__ = ["ViTConfig", "ViT", "VIT_PRESETS", "VisionTower",
           "build_vision_model", "image_patches", "output_grid", "tower_of"]


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    """ViT backbone hyperparameters (reference vit.py presets)."""
    image_size: int = 224
    patch_size: int = 16
    in_channels: int = 3
    num_classes: int = 1000
    hidden_size: int = 768
    num_layers: int = 12
    num_attention_heads: int = 12
    mlp_ratio: float = 4.0
    qkv_bias: bool = True
    drop_rate: float = 0.0
    attn_drop_rate: float = 0.0
    drop_path_rate: float = 0.0
    representation_size: Optional[int] = None
    # 'gelu_tanh' (reference default) or 'gelu' (erf; HF ViT checkpoints)
    hidden_act: str = "gelu_tanh"
    # hash-based hidden dropout (ops/dropout.py); False restores nn.Dropout
    fast_dropout: bool = True
    # flash attention for the encoder blocks (seq 197 pads to a single
    # 200-row kernel tile in ops/attention.py); False restores XLA attention
    use_flash_attention: bool = True
    use_recompute: bool = False
    dtype: Dtype = jnp.bfloat16
    # a served tower's (VisionTower): the MLP's width where it is no ratio
    # of the hidden size, the norms' epsilon, and the queries a step of its
    # plain attention takes (0: all at once)
    mlp_hidden_size: Optional[int] = None
    norm_eps: float = 1e-5
    attn_q_block: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mlp_size(self) -> int:
        return self.mlp_hidden_size or int(self.hidden_size * self.mlp_ratio)

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @classmethod
    def from_model_config(cls, model_cfg) -> "ViTConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in dict(model_cfg).items() if k in known and v is not None}
        if isinstance(kw.get("dtype"), str):
            kw["dtype"] = jnp.dtype(kw["dtype"]).type
        return cls(**kw)


# name -> config overrides (reference vit.py:261-443 presets)
VIT_PRESETS = {
    "ViT_tiny_patch16_224": dict(patch_size=16, hidden_size=192, num_layers=12, num_attention_heads=3),
    "ViT_small_patch16_224": dict(patch_size=16, hidden_size=384, num_layers=12, num_attention_heads=6),
    "ViT_base_patch16_224": dict(patch_size=16, hidden_size=768, num_layers=12, num_attention_heads=12),
    "ViT_base_patch16_384": dict(image_size=384, patch_size=16, hidden_size=768, num_layers=12, num_attention_heads=12),
    "ViT_base_patch32_224": dict(patch_size=32, hidden_size=768, num_layers=12, num_attention_heads=12),
    "ViT_base_patch32_384": dict(image_size=384, patch_size=32, hidden_size=768, num_layers=12, num_attention_heads=12),
    "ViT_large_patch16_224": dict(patch_size=16, hidden_size=1024, num_layers=24, num_attention_heads=16),
    "ViT_large_patch16_384": dict(image_size=384, patch_size=16, hidden_size=1024, num_layers=24, num_attention_heads=16),
    "ViT_large_patch32_224": dict(patch_size=32, hidden_size=1024, num_layers=24, num_attention_heads=16),
    "ViT_large_patch32_384": dict(image_size=384, patch_size=32, hidden_size=1024, num_layers=24, num_attention_heads=16),
    "ViT_huge_patch14_224": dict(patch_size=14, hidden_size=1280, num_layers=32, num_attention_heads=16),
    "ViT_huge_patch14_384": dict(image_size=384, patch_size=14, hidden_size=1280, num_layers=32, num_attention_heads=16),
    "ViT_g_patch14_224": dict(patch_size=14, hidden_size=1408, num_layers=40, num_attention_heads=16, mlp_ratio=48 / 11),
    "ViT_G_patch14_224": dict(patch_size=14, hidden_size=1664, num_layers=48, num_attention_heads=16, mlp_ratio=64 / 13),
    "ViT_6B_patch14_224": dict(patch_size=14, hidden_size=2320, num_layers=80, num_attention_heads=16),
}


class DropPath(nn.Module):
    """Stochastic depth — drop whole residual branches per sample
    (reference vision_model/layers/droppath.py)."""

    rate: float

    @nn.compact
    def __call__(self, x, deterministic=True):
        if self.rate == 0.0 or deterministic:
            return x
        keep = 1.0 - self.rate
        rng = self.make_rng("dropout")
        mask_shape = (x.shape[0],) + (1,) * (x.ndim - 1)
        mask = jax.random.bernoulli(rng, keep, mask_shape)
        return jnp.where(mask, x / keep, 0.0)


class ViTBlock(nn.Module):
    """Pre-LN transformer encoder block with droppath (reference
    vision_model/layers)."""
    cfg: ViTConfig
    drop_path: float = 0.0

    @nn.compact
    def __call__(self, x, deterministic=True, kv_lens=None):
        """``kv_lens`` ``[b]``: the patches of each sequence that are real
        (a served tower's padded bucket); None: all."""
        cfg = self.cfg
        nh, hd = cfg.num_attention_heads, cfg.head_dim
        with jax.named_scope("vit_attn"):
            y = _layer_norm(cfg, "norm1")(x)
            qkv = _dense((nh, 3 * hd), ("embed", "heads", "kv"), "qkv_proj", dtype=cfg.dtype,
                         use_bias=cfg.qkv_bias)(y)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            dropout_rng = None
            if cfg.attn_drop_rate > 0.0 and not deterministic:
                dropout_rng = self.make_rng("dropout")

            def attend(q):
                return causal_attention(
                    q, k, v,
                    causal=False, kv_lens=kv_lens,
                    dropout_rate=cfg.attn_drop_rate,
                    dropout_rng=dropout_rng,
                    deterministic=deterministic,
                    # seq 197 (196 patches + cls) pads to 200 inside the dispatch
                    # (one kernel tile); use_flash_attention: False restores XLA
                    use_flash=cfg.use_flash_attention,
                )

            block = cfg.attn_q_block
            if block and q.shape[1] > block and q.shape[1] % block == 0:
                # a block of queries at a time: [heads, block, patches] scores
                y = jax.lax.map(attend, jnp.moveaxis(q.reshape(
                    q.shape[0], -1, block, nh, hd), 1, 0))
                y = jnp.moveaxis(y, 0, 1).reshape(q.shape)
            else:
                y = attend(q)
            y = attn_out_dense(cfg.hidden_size, cfg.dtype)(y)
            y = dropout_layer(cfg.drop_rate, "proj_drop", cfg.fast_dropout)(y, deterministic=deterministic)
            x = x + DropPath(self.drop_path, name="drop_path1")(y, deterministic)

        with jax.named_scope("vit_mlp"):
            y = _layer_norm(cfg, "norm2")(x)
            y = _dense(cfg.mlp_size, ("embed", "mlp"), "fc1", dtype=cfg.dtype)(y)
            y = nn.gelu(y, approximate=cfg.hidden_act != "gelu")
            y = _dense(cfg.hidden_size, ("mlp", "embed"), "fc2", dtype=cfg.dtype)(y)
            y = dropout_layer(cfg.drop_rate, "mlp_drop", cfg.fast_dropout)(y, deterministic=deterministic)
            x = x + DropPath(self.drop_path, name="drop_path2")(y, deterministic)
        return _constrain_act(x, cfg)


class ViT(nn.Module):
    """Patch embed + cls token + encoder + classification head. Input images
    are channels-last [b, H, W, C] (TPU conv layout)."""

    cfg: ViTConfig

    @nn.compact
    def __call__(self, images, *, deterministic=True):
        cfg = self.cfg
        b = images.shape[0]
        x = nn.Conv(
            features=cfg.hidden_size,
            kernel_size=(cfg.patch_size, cfg.patch_size),
            strides=(cfg.patch_size, cfg.patch_size),
            padding="VALID",
            dtype=cfg.dtype,
            param_dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                default_kernel_init, (None, None, None, "embed")
            ),
            name="patch_embed",
        )(images.astype(cfg.dtype))
        x = x.reshape(b, -1, cfg.hidden_size)  # [b, patches, h]

        cls_token = self.param(
            "cls_token",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), (None, None, "embed")),
            (1, 1, cfg.hidden_size),
            jnp.float32,
        )
        x = jnp.concatenate(
            [jnp.broadcast_to(cls_token, (b, 1, cfg.hidden_size)).astype(cfg.dtype), x],
            axis=1,
        )
        pos_emb = self.param(
            "pos_embed",
            nn.with_logical_partitioning(
                nn.initializers.normal(0.02), (None, None, "embed")
            ),
            (1, cfg.num_patches + 1, cfg.hidden_size),
            jnp.float32,
        )
        x = x + pos_emb.astype(cfg.dtype)
        x = dropout_layer(cfg.drop_rate, "pos_drop", cfg.fast_dropout)(x, deterministic=deterministic)
        x = _constrain_act(x, cfg)

        # linearly-increasing stochastic depth (reference vit.py dpr rule)
        for i in range(cfg.num_layers):
            dp = cfg.drop_path_rate * i / max(cfg.num_layers - 1, 1)
            block = ViTBlock
            if cfg.use_recompute:
                block = nn.remat(ViTBlock, static_argnums=(2,))
            x = block(cfg, dp, name=f"block_{i}")(x, deterministic)

        x = _layer_norm(cfg, "final_norm")(x)
        x = x[:, 0]  # cls token
        if cfg.representation_size:
            x = _dense(cfg.representation_size, ("embed", None), "pre_logits",
                       dtype=cfg.dtype)(x)
            x = jnp.tanh(x)
        if cfg.num_classes == 0:  # backbone mode (MoCo etc.): pooled features
            return x
        logits = _dense(cfg.num_classes, ("embed", None), "head",
                        dtype=jnp.float32)(x.astype(jnp.float32))
        return logits


class VisionTower(nn.Module):
    """A vision tower served (module docstring). ``patches`` ``[P, patch x
    patch x channels]`` of ONE image, in MERGE ORDER (:func:`image_patches`:
    the four patches of a row side by side), the first ``grid[0] x
    grid[1]`` of them real; ``grid`` int32 ``[2]``: the image's rows and
    columns of PATCHES (traced: one program a bucket ``P``). Returns ``[P /
    merge^2, out_size]``, the first ``grid[0] x grid[1] / merge^2`` of them
    the image's rows in raster order."""

    cfg: ViTConfig
    out_size: int
    merge: int = 2

    @nn.compact
    def __call__(self, patches, grid):
        cfg, m = self.cfg, self.merge
        side = cfg.image_size // cfg.patch_size
        rows, cols = grid[0], grid[1]
        with jax.named_scope("vit_embed"):
            z = _dense(cfg.hidden_size, (None, "embed"), "patch_embed",
                       dtype=cfg.dtype)(patches.astype(cfg.dtype))
            table = self.param(
                "pos_embed", nn.with_logical_partitioning(
                    nn.initializers.normal(0.02), (None, None, "embed")),
                (side, side, cfg.hidden_size), jnp.float32)
            # patch i of the merge order: its block, then its place in it
            i = jnp.arange(z.shape[0], dtype=jnp.int32)
            block, k = i // (m * m), i % (m * m)
            per_row = jnp.maximum(cols // m, 1)
            r = (block // per_row) * m + k // m
            c = (block % per_row) * m + k % m
            z = z + interpolated(table, r, c, rows, cols).astype(cfg.dtype)
        lens = (rows * cols)[None]
        for n in range(cfg.num_layers):
            z = ViTBlock(cfg, name=f"block_{n}")(z[None], kv_lens=lens)[0]
        with jax.named_scope("vit_project"):
            z = _layer_norm(cfg, "post_norm")(z)
            z = _layer_norm(cfg, "merge_norm")(z)
            z = z.reshape(z.shape[0] // (m * m), m * m * cfg.hidden_size)
            z = _dense(z.shape[-1], ("embed", "mlp"), "project_in",
                       dtype=cfg.dtype)(z)
            return _dense(self.out_size, ("mlp", "embed"), "project_out",
                          dtype=cfg.dtype)(nn.gelu(z, approximate=False))


def interpolated(table, r, c, rows, cols):
    """The position table ``[side, side, h]`` read bilinearly at the centres
    of patches ``(r, c)`` ``[P]`` of a ``rows x cols`` grid (half-pixel
    centres, edges clamped: ``align_corners=False``), float32 ``[P, h]``."""
    side = table.shape[0]

    def along(at, n):
        y = jnp.clip((at.astype(jnp.float32) + 0.5) * side
                     / n.astype(jnp.float32) - 0.5, 0.0, side - 1.0)
        lo = jnp.floor(y).astype(jnp.int32)
        return lo, jnp.minimum(lo + 1, side - 1), (y - lo)[:, None]

    y0, y1, wy = along(r, rows)
    x0, x1, wx = along(c, cols)
    top = table[y0, x0] * (1.0 - wx) + table[y0, x1] * wx
    low = table[y1, x0] * (1.0 - wx) + table[y1, x1] * wx
    return top * (1.0 - wy) + low * wy


def output_grid(shape, patch: int, merge: int = 2):
    """``(h, w)``, the rows and columns of output rows an image of ``shape``
    ``[H, W, C]`` makes (what :func:`image_patches` cuts it into, by its
    shape alone); raises where it is no whole number of them."""
    height, width, _ = shape
    h, w = height // (patch * merge), width // (patch * merge)
    if (h * patch * merge, w * patch * merge) != (height, width) or not h * w:
        raise ValueError(f"an image of {height} x {width} pixels is no whole "
                         f"number of {patch * merge}-pixel rows and columns")
    return h, w


def image_patches(image, patch: int, merge: int = 2):
    """``image`` ``[H, W, C]`` (numpy; ``H`` and ``W`` whole ``patch x
    merge``s) as its patches in MERGE ORDER ``[patches, patch x patch x
    C]``: the ``merge x merge`` patches of output row ``(r, c)`` follow one
    another, rows in raster order; a patch is its pixels in ``(y, x,
    channel)`` order."""
    h, w = output_grid(image.shape, patch, merge)
    tiles = image.reshape(h, merge, patch, w, merge, patch, -1)
    return tiles.transpose(0, 3, 1, 4, 2, 5, 6).reshape(
        h * w * merge * merge, -1)


def tower_of(cfg) -> Optional[VisionTower]:
    """The tower a ``GPTConfig``'s ``vision`` group describes (None without
    one), its rows as wide as the language model."""
    group = cfg.vision_fields
    if not group:
        return None
    return VisionTower(ViTConfig(
        image_size=group["grid"] * group["patch_size"],
        patch_size=group["patch_size"], hidden_size=group["hidden_size"],
        num_layers=group["num_layers"],
        num_attention_heads=group["num_heads"],
        mlp_hidden_size=group["intermediate_size"], norm_eps=1e-6,
        attn_q_block=1024, use_flash_attention=False, num_classes=0,
        dtype=cfg.dtype), out_size=cfg.hidden_size, merge=group["merge"])


def build_vision_model(name: str, **overrides) -> ViT:
    """Model-zoo factory (reference vision_model/factory.py)."""
    if name not in VIT_PRESETS:
        raise ValueError(f"unknown vision model {name!r}; have {sorted(VIT_PRESETS)}")
    kw = {**VIT_PRESETS[name], **overrides}
    return ViT(ViTConfig(**kw))
