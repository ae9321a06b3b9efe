"""Training through the ``tools/train.py`` path: ``get_config`` ->
``build_module`` -> ``build_dataloader`` -> ``Trainer`` -> ``run_elastic``
/ ``fit``, with the loader running, on a token file made from the seed.

The module's ``training_step_end`` hook (called once a step at
``logging_freq=1``, after the loss has been fetched, so each stamp ends in
a real wait on the device) stamps every step. The first ``warmup_steps``
steps (compilation and the first steady one) are set-up; the window opens
at the stamp that ends them and closes at the first stamp ``--seconds`` or
more later, so it holds whole steps only; the hook then lowers
``trainer.max_steps`` (a public attribute the loop reads every step).
Before ``fit``, the trainer's own evaluation step (dropout off) scores a
seeded sample of sequences and ``reference/gpt_f32.py`` scores the same
sample with the same parameters.
"""

from __future__ import annotations

import math
import os
import shutil
import time

import numpy as np

from perfbench import harness, traffic as traffic_gen
from perfbench.reference import gpt_f32

# The trainer's own evaluation step (dropout off, the loss path of the
# training step: flash attention, fused cross-entropy where the
# configuration has it) against the float32 reference on a seeded sample,
# chunk by chunk: the sample is cut into ``_CHUNKS`` equal runs of tokens
# (64 or 128 each) and the step scores each under a loss mask that keeps
# that run alone. One mean over the whole sample hides per-token errors,
# which largely cancel in it; the chunks do not, and a wrong mask, label or
# position moves single chunks by 1e-1 or more. The system computes in
# bf16. The chip read chunk rms errors of 0.79-1.18e-3 and largest chunk
# errors of 1.8-2.6e-3 in five runs of the two cells, so those two bounds
# sit at about twice the readings. The whole-sample error is the mean of
# the chunk errors and can only catch a bias they share; it scatters
# around 0 from seed to seed with a standard deviation of 1.1e-4 at
# GPT-345M (19 runs) and 2.3e-4 at dp2 x mp2 (12 runs, largest 4.3e-4), so
# its bound stays at four of the wider deviations: tighter, one run in a
# hundred would fail on noise (my chip runs, PR 22; PERF.md, Findings).
_CHUNKS = 32
REFERENCE_CHUNK_RMS_TOL = 2.5e-3
REFERENCE_CHUNK_MAX_TOL = 6e-3
REFERENCE_LOSS_TOL = 1e-3


def reference_agreement(system_loss, reference_token_losses, sample) -> dict:
    """``system_loss(batch)`` is the system's masked mean loss of a batch;
    ``reference_token_losses`` the reference's loss of every token of
    ``sample`` ``[rows, seq]``. Returns what was compared and
    ``reference_ok``."""
    rows, seq = sample["tokens"].shape
    chunk_of = (np.arange(rows * seq) * _CHUNKS // (rows * seq)).reshape(rows, seq)
    system, reference = [], []
    for c in range(_CHUNKS):
        mask = (chunk_of == c).astype(np.float32)
        system.append(system_loss(dict(sample, loss_mask=mask)))
        reference.append(float(np.asarray(reference_token_losses, np.float64)[
            chunk_of == c].mean()))
    err = np.abs(np.asarray(system) - np.asarray(reference))
    out = {"system_loss": float(np.mean(system)),
           "reference_loss": float(np.mean(reference)),
           "reference_abs_err": float(abs(np.mean(system) - np.mean(reference))),
           "reference_chunk_rms_err": float(np.sqrt((err ** 2).mean())),
           "reference_chunk_max_err": float(err.max()),
           "reference_chunk_tokens": rows * seq // _CHUNKS,
           "reference_tol": [REFERENCE_CHUNK_RMS_TOL, REFERENCE_CHUNK_MAX_TOL,
                             REFERENCE_LOSS_TOL]}
    out["reference_ok"] = bool(
        out["reference_chunk_rms_err"] <= REFERENCE_CHUNK_RMS_TOL
        and out["reference_chunk_max_err"] <= REFERENCE_CHUNK_MAX_TOL
        and out["reference_abs_err"] <= REFERENCE_LOSS_TOL)
    return out


def _token_file(cell, seed: int) -> str:
    """``{prefix}_ids.npy`` + ``{prefix}_idx.npz`` as GPTDataset reads
    them. The directory is emptied first: the dataset caches its index
    maps beside the prefix under a name that does not know the seed."""
    ids, lens = traffic_gen.token_documents(
        cell.traffic, seed, cell.config["model"]["vocab_size"])
    directory = os.path.join(harness.WORK, "data")
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    prefix = os.path.join(directory, "tokens")
    np.save(prefix + "_ids.npy", ids)
    np.savez(prefix + "_idx.npz", lens=lens)
    return prefix


def _overrides(cell, seed: int, prefix: str) -> list:
    layout, job = cell.deploy["layout"], cell.traffic
    local = job["global_batch"] // layout["dp"]
    out = [f"Model.{k}={v}" for k, v in cell.config["model"].items()]
    out += [
        f"Global.seed={seed}",
        f"Distributed.dp_degree={layout['dp']}",
        f"Distributed.mp_degree={layout['mp']}",
        f"Global.local_batch_size={local}",
        f"Global.micro_batch_size={local}",
        "Engine.max_steps=1000000000", "Engine.num_train_epochs=1000",
        "Engine.logging_freq=1", "Engine.eval_freq=0",
        "Engine.save_load.save_steps=1000000000",
        f"Engine.save_load.output_dir={os.path.join(harness.WORK, 'out')}",
        f"Data.Train.dataset.input_dir={prefix}",
        f"Data.Train.dataset.max_seq_len={job['seq_len']}",
    ]
    return out + list(cell.deploy.get("overrides", []))


def _sample_batch(prefix: str, rows: int, seq: int) -> dict:
    """``rows`` sequences cut from the head of the token file, in the
    loader's batch layout."""
    ids = np.load(prefix + "_ids.npy")[:rows * (seq + 1)]
    ids = ids.reshape(rows, seq + 1).astype(np.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:],
            "position_ids": np.broadcast_to(
                np.arange(seq, dtype=np.int32), (rows, seq)).copy(),
            "loss_mask": np.ones((rows, seq), np.float32)}


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    from fleetx_tpu.utils.xla_flags import apply_overlap_flags

    apply_overlap_flags()  # environment only; must precede the backend
    device = harness.own_the_chip(cell.chips, cell.tiny)

    import jax

    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.data import build_dataloader
    from fleetx_tpu.models import build_module
    from fleetx_tpu.ops.pallas.flash_attention import KERNEL_NAMES
    from fleetx_tpu.parallel.env import init_dist_env
    from fleetx_tpu.resilience.elastic import run_elastic
    from fleetx_tpu.utils.compile_cache import enable_compile_cache
    from fleetx_tpu.utils.config import get_config

    init_dist_env()
    enable_compile_cache()
    clock = harness.CompileClock()
    phases = {"import_s": time.perf_counter() - t_process}
    layout, job = cell.deploy["layout"], cell.traffic
    prefix = _token_file(cell, seed)
    cfg = get_config(os.path.join(harness.ROOT, cell.config["train_yaml"]),
                     nranks=layout["dp"] * layout["mp"],
                     overrides=_overrides(cell, seed, prefix))
    assert cfg.Global.global_batch_size == job["global_batch"], cfg.Global
    shutil.rmtree(os.path.join(harness.WORK, "out"), ignore_errors=True)
    module = build_module(cfg)
    loader = build_dataloader(cfg, "Train")
    trainer = Trainer(cfg, module)
    phases["data_and_trainer_s"] = time.perf_counter() - t_process

    # the reference check, outside the window: dropout off, same parameters
    sample = _sample_batch(prefix, 2 * layout["dp"], job["seq_len"])
    trainer.evaluate([sample])  # makes the state from the seed
    reference = reference_agreement(
        lambda batch: trainer.evaluate([batch]),
        jax.jit(gpt_f32.token_losses)(trainer.state.params, sample["tokens"],
                                      sample["labels"]), sample)

    phases["weights_and_reference_s"] = time.perf_counter() - t_process
    warmup = int(job["warmup_steps"])
    profiler = harness.ProfilerWindow(trace, job["trace_s"])
    stamps, losses, state = [], [], {"start": None, "end": None}
    log_line = module.training_step_end

    def record(log):  # once a step, after the loss was fetched
        now = time.perf_counter()
        stamps.append(now)
        losses.append(float(log["loss"]))
        if len(stamps) == warmup:
            state["start"] = now
            profiler.arm(now, seconds)
        elif len(stamps) > warmup and state["end"] is None:
            profiler.poll(now)
            if now - state["start"] >= seconds:
                profiler.close()
                state["end"] = now
                trainer.max_steps = len(stamps)
        log_line(log)

    module.training_step_end = record
    trainer = run_elastic(cfg, trainer, loader, None)
    start, end = state["start"], state["end"]
    steps = [t for t in stamps if start < t <= end]

    band = (math.log(cell.config["model"]["vocab_size"]) - 0.8,
            math.log(cell.config["model"]["vocab_size"]) + 1.2)
    hlo = trainer.compiled_text("train") or ""
    kernels = {name: harness.mosaic_calls(hlo, name) for name in KERNEL_NAMES}
    checks = {
        "losses_in_band": all(math.isfinite(x) and band[0] < x < band[1]
                              for x in losses),
        "loss_first_last": [losses[0], losses[-1]],
        "sentry_skips": int(trainer.sentry_skips),
        "mosaic_calls": kernels,
        "compiles_in_window": clock.inside(start, end),
        **reference,
        "steps_in_window": len(steps),
        "setup_done_at_s": phases,  # seconds since process start
        "step_s_p50": harness.percentile(np.diff([start] + steps), 50),
        **clock.report(),
    }
    correct = (checks["losses_in_band"] and checks["sentry_skips"] == 0
               and all(kernels.values())
               and checks["compiles_in_window"] == 0
               and reference["reference_ok"])
    return harness.Run(
        cell=cell, device=device, setup_s=start - t_process,
        window=(start, end), attempted=len(steps),
        failed=int(trainer.sentry_skips), correct=correct, checks=checks,
        samples={"step_end_s": steps,
                 "tokens_per_step": job["global_batch"] * job["seq_len"]},
        spans=harness.program_spans(start), counters={},
        traced=profiler.traced, trace=profiler.reduce() if trace else None,
        peaks=harness.device_peaks(device, cell.tiny))
