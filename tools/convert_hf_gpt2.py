"""Convert a HuggingFace GPT-2 checkpoint into a fleetx-tpu export artifact.

Migration path for users switching from the reference (whose released GPT
checkpoints are re-exports of GPT-2-family weights): point this at any
local ``transformers`` GPT-2 directory and the output artifact loads
through the standard pretrained/serving machinery (InferenceEngine,
``Model.pretrained`` finetune loading).

    python tools/convert_hf_gpt2.py --hf-dir /ckpts/gpt2 --output ./gpt2_artifact

Layout mapping (HF GPT2 Conv1D keeps [in, out] orientation):
  wte/wpe                  -> gpt/word_embeddings, gpt/position_embeddings
  h.i.ln_1, ln_2, ln_f     -> norm1 / norm2 / final_norm (scale, bias)
  h.i.attn.c_attn [h, 3h]  -> qkv_proj kernel [h, nh, 3*hd] — HF packs
                              q|k|v each across ALL heads; ours packs per
                              head, so split thirds then concat per head
  h.i.attn.c_proj [h, h]   -> out_proj kernel [nh, hd, h]
  h.i.mlp.c_fc / c_proj    -> up_proj [h, 4h] / down_proj [4h, h]
Per-layer trees stack into scan layout [num_layers, ...].
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from fleetx_tpu.utils.log import logger


def convert_state_dict(sd, n_layer: int, n_head: int, pad_vocab_to: int = 0):
    """HF GPT-2 state dict (numpy arrays) -> fleetx-tpu 'gpt' param subtree."""
    h = sd["wte.weight"].shape[1]
    hd = h // n_head

    def qkv(w):  # [h, 3h] -> [h, nh, 3*hd]
        q, k, v = np.split(w, 3, axis=-1)
        parts = [x.reshape(x.shape[:-1] + (n_head, hd)) for x in (q, k, v)]
        return np.concatenate(parts, axis=-1)

    layers = []
    for i in range(n_layer):
        pre = f"h.{i}."
        layers.append({
            "norm1": {"scale": sd[pre + "ln_1.weight"], "bias": sd[pre + "ln_1.bias"]},
            "norm2": {"scale": sd[pre + "ln_2.weight"], "bias": sd[pre + "ln_2.bias"]},
            "attn": {
                "qkv_proj": {
                    "kernel": qkv(sd[pre + "attn.c_attn.weight"]),
                    "bias": qkv(sd[pre + "attn.c_attn.bias"][None])[0],
                },
                "out_proj": {
                    "kernel": sd[pre + "attn.c_proj.weight"].reshape(n_head, hd, h),
                    "bias": sd[pre + "attn.c_proj.bias"],
                },
            },
            "mlp": {
                "up_proj": {"kernel": sd[pre + "mlp.c_fc.weight"],
                            "bias": sd[pre + "mlp.c_fc.bias"]},
                "down_proj": {"kernel": sd[pre + "mlp.c_proj.weight"],
                              "bias": sd[pre + "mlp.c_proj.bias"]},
            },
        })
    # scan layout: stack each leaf over the layer axis
    import jax

    stacked = jax.tree.map(lambda *xs: np.stack(xs).astype(np.float32), *layers)

    wte = sd["wte.weight"].astype(np.float32)
    if pad_vocab_to and pad_vocab_to > wte.shape[0]:
        pad = np.zeros((pad_vocab_to - wte.shape[0], wte.shape[1]), np.float32)
        wte = np.concatenate([wte, pad], axis=0)
    return {
        "word_embeddings": wte,
        "position_embeddings": sd["wpe.weight"].astype(np.float32),
        "layers": {"layer": stacked},
        "final_norm": {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hf-dir", required=True,
                    help="local transformers GPT-2 checkpoint directory")
    ap.add_argument("--output", required=True, help="export artifact dir")
    ap.add_argument("--pad-vocab-multiple", type=int, default=0,
                    help="pad vocab to a multiple (e.g. 128) for TPU tiling")
    ap.add_argument("--quantize", choices=["int8"], default=None,
                    help="store weight-only int8 params in the artifact")
    args = ap.parse_args()

    from transformers import GPT2Config, GPT2LMHeadModel

    hf_cfg = GPT2Config.from_pretrained(args.hf_dir, local_files_only=True)
    model = GPT2LMHeadModel.from_pretrained(args.hf_dir, local_files_only=True)
    sd = {
        k.removeprefix("transformer."): v.numpy()
        for k, v in model.state_dict().items()
    }
    vocab = hf_cfg.vocab_size
    if args.pad_vocab_multiple:
        m = args.pad_vocab_multiple
        vocab = (vocab + m - 1) // m * m

    gpt_tree = convert_state_dict(
        sd, hf_cfg.n_layer, hf_cfg.n_head,
        pad_vocab_to=vocab if args.pad_vocab_multiple else 0,
    )

    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import AttrDict, process_configs
    from fleetx_tpu.utils.export import export_inference_model

    cfg = AttrDict(
        Global=AttrDict(seed=0, local_batch_size=1, micro_batch_size=1),
        Model=AttrDict(
            module="GPTModule",
            vocab_size=vocab,
            hidden_size=hf_cfg.n_embd,
            num_layers=hf_cfg.n_layer,
            num_attention_heads=hf_cfg.n_head,
            ffn_hidden_size=4 * hf_cfg.n_embd,
            max_position_embeddings=hf_cfg.n_positions,
            hidden_dropout_prob=0.0,
            attention_probs_dropout_prob=0.0,
            fuse_attn_qkv=True,
        ),
        Distributed=AttrDict(dp_degree=None, mp_degree=1, pp_degree=1),
    )
    process_configs(cfg, nranks=1)
    module = build_module(cfg)
    export_inference_model(module, {"gpt": gpt_tree}, args.output,
                           quantize=args.quantize)
    logger.info(
        "converted %s (%d layers, %d heads, vocab %d) -> %s",
        args.hf_dir, hf_cfg.n_layer, hf_cfg.n_head, vocab, args.output,
    )


if __name__ == "__main__":
    main()
