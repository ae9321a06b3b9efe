"""Convert a HuggingFace BERT checkpoint into a fleetx-tpu ERNIE artifact.

The ERNIE encoder is architecture-compatible with BERT (post-LN blocks,
learned position + token-type embeddings, tanh pooler), so any local HF
BERT checkpoint becomes a warm start for the ERNIE family:

    python tools/convert_hf_bert.py --hf-dir /ckpts/bert-base --output ./bert_artifact

Layout mapping (HF Linear weights are [out, in] — transposed on the way):
  embeddings.{word,position,token_type}_embeddings -> same-name tables
  embeddings.LayerNorm                             -> embed_norm
  encoder.layer.i.attention.self.{query,key,value} -> qkv_proj
       [h, nh, 3*hd]: per-head packing, q|k|v along the last axis
  encoder.layer.i.attention.output.dense           -> out_proj [nh, hd, h]
  encoder.layer.i.attention.output.LayerNorm       -> norm1
  encoder.layer.i.{intermediate,output}.dense      -> linear1 / linear2
  encoder.layer.i.output.LayerNorm                 -> norm2
  pooler.dense                                     -> pooler
Per-layer trees stack into the scan layout [num_layers, ...]; the MLM/SOP
heads keep fresh init (BertModel checkpoints carry no heads).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from tools.hf_convert_common import linear_t, pack_qkv

from fleetx_tpu.utils.log import logger


def convert_state_dict(sd, n_layer: int, n_head: int):
    """HF BertModel state dict (numpy) -> fleetx-tpu ErnieModel param tree."""
    h = sd["embeddings.word_embeddings.weight"].shape[1]
    hd = h // n_head
    lin_t = lambda name: linear_t(sd, name)  # noqa: E731

    layers = []
    for i in range(n_layer):
        pre = f"encoder.layer.{i}."
        qkv_kernel, qkv_bias = pack_qkv(sd, pre + "attention.self.", n_head, hd)
        ow, ob = lin_t(pre + "attention.output.dense")
        l1w, l1b = lin_t(pre + "intermediate.dense")
        l2w, l2b = lin_t(pre + "output.dense")
        layers.append({
            "attn": {
                "qkv_proj": {"kernel": qkv_kernel, "bias": qkv_bias},
                "out_proj": {"kernel": ow.reshape(n_head, hd, h), "bias": ob},
            },
            "norm1": {"scale": sd[pre + "attention.output.LayerNorm.weight"],
                      "bias": sd[pre + "attention.output.LayerNorm.bias"]},
            "linear1": {"kernel": l1w, "bias": l1b},
            "linear2": {"kernel": l2w, "bias": l2b},
            "norm2": {"scale": sd[pre + "output.LayerNorm.weight"],
                      "bias": sd[pre + "output.LayerNorm.bias"]},
        })
    import jax

    stacked = jax.tree.map(lambda *xs: np.stack(xs).astype(np.float32), *layers)
    pw, pb = lin_t("pooler.dense")
    return {
        "word_embeddings": sd["embeddings.word_embeddings.weight"].astype(np.float32),
        "position_embeddings": sd["embeddings.position_embeddings.weight"].astype(np.float32),
        "token_type_embeddings": sd["embeddings.token_type_embeddings.weight"].astype(np.float32),
        "embed_norm": {"scale": sd["embeddings.LayerNorm.weight"],
                       "bias": sd["embeddings.LayerNorm.bias"]},
        "layers": {"layer": stacked},
        "pooler": {"kernel": pw, "bias": pb},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hf-dir", required=True)
    ap.add_argument("--output", required=True)
    args = ap.parse_args()

    import jax
    from transformers import BertConfig, BertModel

    hf_cfg = BertConfig.from_pretrained(args.hf_dir, local_files_only=True)
    model = BertModel.from_pretrained(
        args.hf_dir, local_files_only=True, add_pooling_layer=True
    )
    sd = {k: v.numpy() for k, v in model.state_dict().items()}
    ernie_tree = convert_state_dict(
        sd, hf_cfg.num_hidden_layers, hf_cfg.num_attention_heads
    )

    from fleetx_tpu.core.engine import _unbox
    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import AttrDict, process_configs
    from fleetx_tpu.utils.export import export_inference_model

    cfg = AttrDict(
        Global=AttrDict(seed=0, local_batch_size=1, micro_batch_size=1),
        Model=AttrDict(
            module="ErnieModule",
            vocab_size=hf_cfg.vocab_size,
            hidden_size=hf_cfg.hidden_size,
            num_layers=hf_cfg.num_hidden_layers,
            num_attention_heads=hf_cfg.num_attention_heads,
            ffn_hidden_size=hf_cfg.intermediate_size,
            max_position_embeddings=hf_cfg.max_position_embeddings,
            type_vocab_size=hf_cfg.type_vocab_size,
            hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0,
            hidden_act="gelu",  # HF BERT uses erf gelu
        ),
        Distributed=AttrDict(dp_degree=None, mp_degree=1, pp_degree=1),
    )
    process_configs(cfg, nranks=1)
    module = build_module(cfg)
    # heads (MLM transform/decoder, SOP) have no BertModel counterpart:
    # init fresh and graft the converted backbone in
    batch = {"input_ids": np.zeros((1, 8), np.int32),
             "masked_positions": np.zeros((1, 2), np.int32)}
    variables = module.init_params(jax.random.PRNGKey(0), batch)
    params = _unbox(variables["params"] if "params" in variables else variables)
    params = jax.tree.map(np.asarray, params)
    params["ernie"] = ernie_tree
    export_inference_model(module, params, args.output)
    logger.info(
        "converted %s (%d layers, %d heads) -> %s",
        args.hf_dir, hf_cfg.num_hidden_layers, hf_cfg.num_attention_heads,
        args.output,
    )


if __name__ == "__main__":
    main()
