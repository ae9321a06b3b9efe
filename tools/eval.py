"""Offline evaluation entry point (reference /root/reference/tools/eval.py +
GPTEvalModule, language_module.py:586-703).

Two modes:
- ``Offline_Eval`` present: WikiText perplexity (overlapping windows) or
  LAMBADA last-word cloze accuracy (``cloze_eval: True``) over
  ``eval_path`` — raw text / jsonl (needs ``vocab_dir``) or pre-tokenized
  ``.npy``.
- otherwise: mean CE loss over the config's Data.Eval loader.

``Offline_Eval.weight_dtype: int8`` (or ``-o
Offline_Eval.weight_dtype=int8``) scores the weight-only-PTQ model the
quantized serving path deploys: params round-trip through the exact
``quantize_tree_int8`` → ``dequantize_tree_int8`` pair the serving
engines use, so the reported ppl/acc IS the served int8 model's quality
— the eval half of the docs/QUANTIZATION.md tolerance contract (the
token-level half is tests/serving_parity.py). KV-cache quantization has
no teacher-forced analogue (no decode cache is read here); its quality
is covered by the token-parity budget.
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from fleetx_tpu.core.engine import Trainer
from fleetx_tpu.data import build_dataloader
from fleetx_tpu.models import build_module
from fleetx_tpu.parallel.env import init_dist_env
from fleetx_tpu.utils.compile_cache import enable_compile_cache
from fleetx_tpu.utils.config import get_config, parse_args
from fleetx_tpu.utils.log import logger


def _batched(dataset, batch_size):
    """Stack dict samples into fixed-size batches (last partial dropped —
    matches reference eval batching, but loudly)."""
    batch = []
    for i in range(len(dataset)):
        batch.append(dataset[i])
        if len(batch) == batch_size:
            yield {k: np.stack([s[k] for s in batch]) for k in batch[0]}
            batch = []
    if batch:
        logger.warning(
            "dropping final partial eval batch of %d samples (< batch_size=%d)",
            len(batch), batch_size,
        )


def _load_tokens(oe):
    path = oe["eval_path"]
    if path.endswith(".npy"):
        return np.load(path).astype(np.int64)
    from fleetx_tpu.data.tokenizers.gpt_tokenizer import GPTTokenizer

    tok = GPTTokenizer.from_pretrained(oe.get("vocab_dir") or "./vocab")
    with open(path, encoding="utf-8") as f:
        return np.asarray(tok.encode(f.read()), np.int64)


def _lambada_pairs(oe):
    """jsonl {"text": ...}; target = last whitespace word (reference
    Lambada_Eval_Dataset tokenization split)."""
    from fleetx_tpu.data.tokenizers.gpt_tokenizer import GPTTokenizer

    tok = GPTTokenizer.from_pretrained(oe.get("vocab_dir") or "./vocab")
    contexts, targets = [], []
    with open(oe["eval_path"], encoding="utf-8") as f:
        for line in f:
            if not line.strip():
                continue
            text = json.loads(line)["text"]
            ctx, _, last = text.rpartition(" ")
            contexts.append(tok.encode(ctx))
            targets.append(tok.encode(" " + last))
    return contexts, targets


def offline_eval(cfg):
    from fleetx_tpu.data.gpt_dataset import LMEvalDataset, LambadaEvalDataset

    oe = cfg.Offline_Eval
    seq_len = oe.get("max_seq_len") or 1024
    batch_size = oe.get("batch_size") or 8
    module = build_module(cfg)

    if oe.get("cloze_eval"):
        contexts, targets = _lambada_pairs(oe)
        ds = LambadaEvalDataset(contexts, targets, seq_len, pad_id=0)
    else:
        ds = LMEvalDataset(
            _load_tokens(oe), seq_len, pad_id=0,
            overlapping_eval=oe.get("overlapping_eval"),
        )

    trainer = Trainer(cfg, module, mode="eval")
    try:
        first = next(_batched(ds, batch_size))
    except StopIteration:
        raise SystemExit(
            f"offline eval dataset has {len(ds)} samples — fewer than one "
            f"batch of {batch_size}; lower Offline_Eval.batch_size"
        ) from None
    trainer.init_state(first)
    if (cfg.Engine.save_load or {}).get("ckpt_dir"):
        if not trainer.load():
            raise SystemExit(
                "eval: no restorable checkpoint under ckpt_dir "
                f"{cfg.Engine.save_load.ckpt_dir!r} — evaluating unrestored "
                "params would report a meaningless loss")
    from fleetx_tpu.ops.quant import (
        dequantize_tree_int8,
        resolve_serving_dtype,
        serving_weight_params,
    )

    try:
        weight_dtype = resolve_serving_dtype(
            oe.get("weight_dtype"), None, label="Offline_Eval.weight_dtype")
    except ValueError as e:
        raise SystemExit(str(e)) from None
    params = trainer.state.params
    if weight_dtype == "int8":
        # the serving path's weight-only PTQ, applied verbatim: this eval
        # measures the model ServingEngine/InferenceEngine actually run
        params = dequantize_tree_int8(
            serving_weight_params(params, weight_dtype))
        logger.info("offline eval: weight-only int8 PTQ applied "
                    "(docs/QUANTIZATION.md)")
    result = module.evaluate_dataset(params, _batched(ds, batch_size))
    logger.info("offline eval (%s%s): %s", module.eval_type,
                " int8" if weight_dtype == "int8" else "", result)
    return result


def main():
    args = parse_args()
    init_dist_env()
    enable_compile_cache()
    cfg = get_config(args.config, overrides=args.override, show=False)
    if cfg.get("Offline_Eval"):
        offline_eval(cfg)
        return
    module = build_module(cfg)
    loader = build_dataloader(cfg, "Eval")
    trainer = Trainer(cfg, module, mode="eval")
    first = next(iter(loader))
    trainer.init_state(first)
    if (cfg.Engine.save_load or {}).get("ckpt_dir"):
        if not trainer.load():
            raise SystemExit(
                "eval: no restorable checkpoint under ckpt_dir "
                f"{cfg.Engine.save_load.ckpt_dir!r} — evaluating unrestored "
                "params would report a meaningless loss")
    loss = trainer.evaluate(loader)
    logger.info("eval loss: %s", loss)


if __name__ == "__main__":
    main()
