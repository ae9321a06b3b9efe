"""Serve an exported model (reference /root/reference/tools/inference.py ->
EagerEngine.inference -> InferenceEngine).

    python tools/inference.py --export-dir ./exported --prompt "Hi there"
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from fleetx_tpu.core.inference_engine import InferenceEngine
from fleetx_tpu.utils.compile_cache import enable_compile_cache
from fleetx_tpu.utils.log import logger


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--export-dir", default=None)
    ap.add_argument("-c", "--config", default=None,
                    help="inference yaml with Inference.model_dir "
                         "(reference inference_gpt_*.yaml surface)")
    ap.add_argument("-o", "--override", action="append", default=[])
    ap.add_argument("--prompt", default=None, help="text (needs vocab) or "
                    "comma-separated token ids")
    ap.add_argument("--vocab-dir", default=None)
    ap.add_argument("--max-length", type=int, default=None)
    ap.add_argument("--decode-strategy", default=None,
                    help="greedy | sampling | beam_search (overrides export)")
    ap.add_argument("--num-beams", type=int, default=None)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--top-p", type=float, default=None)
    ap.add_argument("--temperature", type=float, default=None)
    args = ap.parse_args()

    export_dir = args.export_dir
    if export_dir is None and args.config:
        # parse + overrides only: serving must not run the training-topology
        # validation (the serving host's device count is unrelated)
        from fleetx_tpu.utils.config import override_config, parse_config

        cfg = parse_config(args.config)
        override_config(cfg, args.override)
        export_dir = (cfg.get("Inference") or {}).get("model_dir")
    if not export_dir:
        ap.error("--export-dir or -c config with Inference.model_dir required")

    enable_compile_cache()
    engine = InferenceEngine(export_dir)
    if args.prompt is None:
        logger.info("no --prompt; running a smoke forward")
        feed = {
            k: np.zeros(v.shape, v.dtype) for k, v in engine.input_spec.items()
        }
        logits = engine.predict(feed)
        logger.info("forward OK, logits shape %s", logits.shape)
        return

    if all(p.strip().isdigit() for p in args.prompt.split(",")):
        ids = np.asarray([[int(p) for p in args.prompt.split(",")]], np.int32)
        tok = None
    else:
        from fleetx_tpu.data.tokenizers.gpt_tokenizer import GPTTokenizer

        tok = GPTTokenizer.from_pretrained(args.vocab_dir or "./vocab")
        ids = np.asarray([tok.encode(args.prompt)], np.int32)
    kw = {}
    if args.max_length:
        kw["max_length"] = args.max_length
    for name in ("decode_strategy", "num_beams", "top_k", "top_p", "temperature"):
        val = getattr(args, name)
        if val is not None:
            kw[name] = val
    out = np.asarray(engine.generate(ids, **kw))
    gen = out[0][ids.shape[1]:]
    eos = np.nonzero(gen == engine.eos_token_id)[0]
    if eos.size:  # trim EOS + the post-EOS pad fill (matches tasks/gpt driver)
        gen = gen[: eos[0]]
    logger.info("generated ids: %s", np.concatenate([ids[0], gen]).tolist())
    if tok is not None:
        logger.info("text: %s", tok.decode(np.concatenate([ids[0], gen])))


if __name__ == "__main__":
    main()
