"""Convert a HuggingFace ViT checkpoint into a fleetx-tpu export artifact.

Completes the warm-start trio (GPT-2 -> GPT, BERT -> ERNIE, ViT -> ViT):
any local ``transformers`` ViT checkpoint becomes servable / finetunable
here.

    python tools/convert_hf_vit.py --hf-dir /ckpts/vit-base --output ./vit_artifact

Layout mapping (HF Linear [out, in] transposed; Conv2d [out, in, kh, kw]
-> flax [kh, kw, in, out]):
  embeddings.patch_embeddings.projection -> patch_embed
  embeddings.cls_token / position_embeddings -> cls_token / pos_embed
  encoder.layer.i.layernorm_before/after -> norm1 / norm2
  encoder.layer.i.attention.attention.{query,key,value} -> qkv_proj
       [h, nh, 3*hd], per-head q|k|v packing
  encoder.layer.i.attention.output.dense -> out_proj [nh, hd, h]
  encoder.layer.i.{intermediate,output}.dense -> fc1 / fc2
  layernorm -> final_norm; classifier (when present) -> head
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from tools.hf_convert_common import linear_t, pack_qkv

from fleetx_tpu.utils.log import logger


def convert_state_dict(sd, n_layer: int, n_head: int, num_classes: int):
    """HF ViT(ForImageClassification) state dict -> fleetx-tpu ViT tree."""
    pk = "vit." if any(k.startswith("vit.") for k in sd) else ""
    h = sd[pk + "embeddings.cls_token"].shape[-1]
    hd = h // n_head

    lin_t = lambda name: linear_t(sd, name)  # noqa: E731

    tree = {
        "patch_embed": {
            "kernel": sd[pk + "embeddings.patch_embeddings.projection.weight"]
            .transpose(2, 3, 1, 0).astype(np.float32),
            "bias": sd[pk + "embeddings.patch_embeddings.projection.bias"],
        },
        "cls_token": sd[pk + "embeddings.cls_token"].astype(np.float32),
        "pos_embed": sd[pk + "embeddings.position_embeddings"].astype(np.float32),
        "final_norm": {"scale": sd[pk + "layernorm.weight"],
                       "bias": sd[pk + "layernorm.bias"]},
    }
    for i in range(n_layer):
        pre = pk + f"encoder.layer.{i}."
        qkv_kernel, qkv_bias = pack_qkv(
            sd, pre + "attention.attention.", n_head, hd
        )
        ow, ob = lin_t(pre + "attention.output.dense")
        f1w, f1b = lin_t(pre + "intermediate.dense")
        f2w, f2b = lin_t(pre + "output.dense")
        tree[f"block_{i}"] = {
            "norm1": {"scale": sd[pre + "layernorm_before.weight"],
                      "bias": sd[pre + "layernorm_before.bias"]},
            "qkv_proj": {"kernel": qkv_kernel, "bias": qkv_bias},
            "out_proj": {"kernel": ow.reshape(n_head, hd, h), "bias": ob},
            "norm2": {"scale": sd[pre + "layernorm_after.weight"],
                      "bias": sd[pre + "layernorm_after.bias"]},
            "fc1": {"kernel": f1w, "bias": f1b},
            "fc2": {"kernel": f2w, "bias": f2b},
        }
    if "classifier.weight" in sd and sd["classifier.weight"].shape[0] == num_classes:
        hw, hb = lin_t("classifier")
        tree["head"] = {"kernel": hw, "bias": hb}
    else:  # backbone-only checkpoint: fresh head
        rng = np.random.RandomState(0)
        tree["head"] = {
            "kernel": (rng.randn(h, num_classes) * 0.02).astype(np.float32),
            "bias": np.zeros((num_classes,), np.float32),
        }
    return {k: _f32(v) for k, v in tree.items()}


def _f32(x):
    import jax

    return jax.tree.map(lambda a: np.asarray(a, np.float32), x)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hf-dir", required=True)
    ap.add_argument("--output", required=True)
    ap.add_argument("--num-classes", type=int, default=1000)
    args = ap.parse_args()

    from transformers import AutoConfig, AutoModel

    hf_cfg = AutoConfig.from_pretrained(args.hf_dir, local_files_only=True)
    try:  # keep the classifier head when the checkpoint carries one
        from transformers import AutoModelForImageClassification

        model = AutoModelForImageClassification.from_pretrained(
            args.hf_dir, local_files_only=True
        )
    except Exception:
        model = AutoModel.from_pretrained(args.hf_dir, local_files_only=True)
    sd = {k: v.numpy() for k, v in model.state_dict().items()}
    tree = convert_state_dict(
        sd, hf_cfg.num_hidden_layers, hf_cfg.num_attention_heads,
        args.num_classes,
    )

    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import AttrDict, process_configs
    from fleetx_tpu.utils.export import export_inference_model

    cfg = AttrDict(
        Global=AttrDict(seed=0, local_batch_size=1, micro_batch_size=1),
        Model=AttrDict(
            module="GeneralClsModule",
            image_size=hf_cfg.image_size,
            patch_size=hf_cfg.patch_size,
            num_classes=args.num_classes,
            hidden_size=hf_cfg.hidden_size,
            num_layers=hf_cfg.num_hidden_layers,
            num_attention_heads=hf_cfg.num_attention_heads,
            mlp_ratio=hf_cfg.intermediate_size / hf_cfg.hidden_size,
            drop_rate=0.0,
            attn_drop_rate=0.0,
            drop_path_rate=0.0,
            hidden_act="gelu",  # HF ViT uses erf gelu
        ),
        Distributed=AttrDict(dp_degree=None, mp_degree=1, pp_degree=1),
    )
    process_configs(cfg, nranks=1)
    module = build_module(cfg)
    export_inference_model(module, tree, args.output)
    logger.info("converted %s -> %s", args.hf_dir, args.output)


if __name__ == "__main__":
    main()
