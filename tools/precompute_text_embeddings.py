"""Precompute text-encoder embeddings for Imagen training/serving.

The reference embeds T5/DeBERTa captions in-process every step
(/root/reference/ppfleetx/models/multimodal_model/imagen/utils.py, 431 LoC:
t5_encode_text / deberta encoding with HF transformers). TPU-first stance:
the text encoder is frozen, so run it ONCE offline and mmap the results —
the diffusion train step then feeds pure tensors and the TPU never waits on
a host-side encoder. This tool produces the ``{prefix}_embeds.npy`` [N,L,D]
+ ``{prefix}_mask.npy`` [N,L] pair TextImageDataset mmaps
(fleetx_tpu/data/multimodal_dataset.py).

    python tools/precompute_text_embeddings.py --input captions.jsonl \
        --output-prefix /data/imagen/train --encoder hf:t5-small

Encoders:
  hf:<name-or-path>  locally cached HuggingFace encoder via transformers
                     (torch CPU; ``local_files_only`` — zero-egress hosts
                     must pass a downloaded path)
  hash               deterministic hash-based token embeddings (no model
                     weights needed): each whitespace token maps to a fixed
                     unit vector seeded by its hash. Keeps the full data
                     pipeline + benchmarks runnable on air-gapped machines;
                     swap in a real encoder for quality runs.
"""

import argparse
import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from fleetx_tpu.utils.log import logger


def _read_captions(path):
    caps = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            if line.startswith("{"):
                doc = json.loads(line)
                caps.append(doc.get("text") or doc.get("caption") or "")
            else:
                caps.append(line)
    return caps


def _hash_vec(token: str, dim: int) -> np.ndarray:
    seed = int.from_bytes(hashlib.sha256(token.encode()).digest()[:8], "little")
    rng = np.random.RandomState(seed % (2**32))
    v = rng.randn(dim).astype(np.float32)
    return v / (np.linalg.norm(v) + 1e-6)


def encode_hash(captions, max_len: int, dim: int):
    n = len(captions)
    embeds = np.zeros((n, max_len, dim), np.float16)
    mask = np.zeros((n, max_len), np.uint8)
    cache = {}
    for i, cap in enumerate(captions):
        toks = cap.lower().split()[:max_len]
        for j, t in enumerate(toks):
            if t not in cache:
                cache[t] = _hash_vec(t, dim)
            embeds[i, j] = cache[t]
        mask[i, : len(toks)] = 1
    return embeds, mask


def encode_hf(captions, model_name: str, max_len: int, batch_size: int = 32):
    import torch
    from transformers import AutoModel, AutoTokenizer

    tok = AutoTokenizer.from_pretrained(model_name, local_files_only=True)
    model = AutoModel.from_pretrained(model_name, local_files_only=True)
    if hasattr(model, "encoder") and hasattr(model, "decoder"):
        model = model.encoder  # T5-style: conditioning uses the encoder only
    model.eval()
    outs, masks = [], []
    with torch.no_grad():
        for i in range(0, len(captions), batch_size):
            batch = tok(
                captions[i : i + batch_size],
                padding="max_length",
                truncation=True,
                max_length=max_len,
                return_tensors="pt",
            )
            h = model(**batch).last_hidden_state  # [b, L, D]
            m = batch["attention_mask"]
            outs.append((h * m[..., None]).numpy().astype(np.float16))
            masks.append(m.numpy().astype(np.uint8))
            logger.info("encoded %d/%d", min(i + batch_size, len(captions)), len(captions))
    return np.concatenate(outs), np.concatenate(masks)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--input", required=True,
                    help="captions: .jsonl with text/caption keys, or plain "
                         "text one caption per line")
    ap.add_argument("--output-prefix", required=True)
    ap.add_argument("--encoder", default="hash",
                    help="'hash' or 'hf:<model-name-or-local-path>'")
    ap.add_argument("--max-text-len", type=int, default=64)
    ap.add_argument("--cond-dim", type=int, default=512,
                    help="embedding dim for the hash encoder (hf encoders "
                         "use the model's hidden size)")
    ap.add_argument("--batch-size", type=int, default=32)
    args = ap.parse_args()

    captions = _read_captions(args.input)
    if not captions:
        raise SystemExit(f"no captions found in {args.input}")
    logger.info("%d captions from %s", len(captions), args.input)

    if args.encoder == "hash":
        embeds, mask = encode_hash(captions, args.max_text_len, args.cond_dim)
    elif args.encoder.startswith("hf:"):
        embeds, mask = encode_hf(
            captions, args.encoder[3:], args.max_text_len, args.batch_size
        )
    else:
        raise SystemExit(f"unknown encoder {args.encoder!r}")

    os.makedirs(os.path.dirname(os.path.abspath(args.output_prefix)), exist_ok=True)
    np.save(args.output_prefix + "_embeds.npy", embeds)
    np.save(args.output_prefix + "_mask.npy", mask)
    logger.info(
        "wrote %s_embeds.npy %s + %s_mask.npy %s",
        args.output_prefix, embeds.shape, args.output_prefix, mask.shape,
    )


if __name__ == "__main__":
    main()
