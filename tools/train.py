"""Pretraining entry point (reference /root/reference/tools/train.py:44-72).

    python tools/train.py -c configs/nlp/gpt/pretrain_gpt_345M_single_card.yaml \
        -o Engine.max_steps=1000
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from fleetx_tpu.core.engine import Trainer
from fleetx_tpu.data import build_dataloader
from fleetx_tpu.models import build_module
from fleetx_tpu.parallel.env import init_dist_env
from fleetx_tpu.resilience.elastic import run_elastic
from fleetx_tpu.utils.compile_cache import enable_compile_cache
from fleetx_tpu.utils.config import get_config, parse_args
from fleetx_tpu.utils.log import advertise, logger
from fleetx_tpu.utils.xla_flags import apply_overlap_flags


def main():
    args = parse_args()
    # the comms/compute overlap flags must be in the environment before
    # anything touches a jax backend (utils/xla_flags.py)
    apply_overlap_flags()
    init_dist_env()
    enable_compile_cache()
    cfg = get_config(args.config, overrides=args.override, show=True)
    advertise()

    module = build_module(cfg)
    train_loader = build_dataloader(cfg, "Train")
    eval_loader = None
    if cfg.Data and cfg.Data.get("Eval") and cfg.Engine.eval_freq:
        eval_loader = build_dataloader(cfg, "Eval")

    trainer = Trainer(cfg, module)
    if (cfg.Engine.save_load or {}).get("ckpt_dir"):
        first = next(iter(train_loader))
        trainer.init_state(first)
        # a first launch (no checkpoint yet) trains from scratch; if
        # checkpoints exist but NONE restores, load() raises
        # CheckpointUnrestorable so an auto-restarting job dies loudly
        # instead of silently retraining from step 0
        trainer.load()
        train_loader.batch_sampler.consumed_samples = trainer.consumed_samples
    # elastic supervisor seam (resilience/elastic.py): a HostLossFault
    # mid-fit triggers emergency snapshot -> smaller mesh -> reshard-on-load
    # resume; with no fault plan active this is exactly trainer.fit()
    trainer = run_elastic(
        cfg, trainer, train_loader, eval_loader,
        make_loader=lambda c, consumed: build_dataloader(c, "Train"))
    logger.info("training done at step %d", int(trainer.state.step))


if __name__ == "__main__":
    main()
