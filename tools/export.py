"""Export a trained module to the inference artifact
(reference /root/reference/tools/export.py -> EagerEngine.export).

    python tools/export.py -c configs/nlp/gpt/generation_gpt_345M_single_card.yaml \
        -o Engine.save_load.ckpt_dir=./output -o Engine.save_load.output_dir=./exported
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np

from fleetx_tpu.core.engine import Trainer
from fleetx_tpu.models import build_module
from fleetx_tpu.parallel.env import init_dist_env
from fleetx_tpu.utils.compile_cache import enable_compile_cache
from fleetx_tpu.utils.config import get_config, parse_args
from fleetx_tpu.utils.export import export_inference_model
from fleetx_tpu.utils.log import logger


def main():
    args = parse_args()
    init_dist_env()
    enable_compile_cache()
    cfg = get_config(args.config, overrides=args.override, show=False)
    module = build_module(cfg)
    trainer = Trainer(cfg, module, mode="export")

    spec = module.input_spec()
    sample = {
        k: np.zeros(v.shape, v.dtype) for k, v in spec.items()
    }
    trainer.init_state(sample)
    if (cfg.Engine.save_load or {}).get("ckpt_dir"):
        if not trainer.load():
            # exporting whatever init_state left (random/pretrained) would
            # silently ship untrained weights with exit code 0
            raise SystemExit(
                "export: no restorable checkpoint under ckpt_dir "
                f"{cfg.Engine.save_load.ckpt_dir!r} (corrupt ones are "
                "quarantined); refusing to export unrestored params")
    out = (cfg.Engine.save_load or {}).get("output_dir") or "./exported"
    # QAT configs export int8 weights (reference quantized export,
    # eager_engine.py:734-745); serving dequantizes transparently
    quantize = "int8" if (cfg.get("Quantization") or {}).get("enable") else None
    export_inference_model(
        module, trainer.state.params, out, input_spec=spec, quantize=quantize
    )
    logger.info("export done: %s%s", out, " (int8 weights)" if quantize else "")


if __name__ == "__main__":
    main()
