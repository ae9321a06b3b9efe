"""Does the reference check of the EvaByte cell refuse what has to come out
NOT correct? The cell's engine is built several times on the weights of one
seed and put through the cell's driver's ``reference_check`` (the reference
always reads the weights as made and the configuration as published):

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- ``bf16_residual``: the residual stream in bfloat16 (the nearest precision
  below the configuration's: ``residual_dtype`` left out);
- ``bf16_pool``: the two pooling softmaxes and their sums in bfloat16;
- ``mu_phi_swapped``: keys pooled by ``phi``, values by ``mu``;
- ``pooled_before_rotation``: a chunk program pools its keys as they are
  BEFORE the rotation (a tick pools what the cache holds, rotated);
- ``own_window_visible``: a query also attends the pooled rows of its OWN
  window's closed chunks (whole summary pages of them);
- ``window_slides``: a SLIDING window of ``W`` rows in the tumbling one's
  place, at the granularity the two classes allow (a summary page, ``C x
  page`` positions): the exact rows begin at the last multiple of that at or
  before ``p - W + 1``, the chunks before are seen pooled, and the window
  class slides (``WindowPagePool`` without ``tumbling``);
- ``unit_offset_left_out``: RMSNorm as ``x^ w``;
- ``padded_rows_pooled``: a chunk program counts its bucket's padded rows as
  rows, so it closes the prompt's open chunk over them, and the tick that
  writes that chunk's last row leaves the pooled row as it finds it;
- ``open_chunk_dropped``: the prompt's open chunk is never closed (the tick
  that writes its last row pools nothing).

Every engine but the first must read NOT ok. The faults are planted at the
seams of ``fleetx_tpu/models/gpt/eva.py`` and stay planted while the engine's
programs AND the check's (``Served``) are traced.

Then faults planted in the ENGINE'S OWN PROGRAMS ALONE (its chunk prefill and
its tick, traced with the fault planted; the check's programs, ``Served``,
traced without it), each put through the driver's ``engine_check`` on four
requests in flight, every lane decoding:

- ``engine_as_built``: must read ``engine_ok``;
- ``engine_ticks_close_nothing``: the engine's ticks never keep a pooled row
  (every chunk a tick should have closed holds what the pool held before);
- ``engine_own_window_visible``, ``engine_mu_phi_swapped``: as above, in the
  engine's programs only.

    python3 perfbench/probe_evabyte.py --seeds 7 8 [--tiny] [--only ...]

One JSON line per engine and seed; exit 1 if any reading is on the wrong
side. The limits in ``drivers/serve_closed_loop_eva.py`` are set between
these readings (PERF.md). The engines here have 4 lanes and pools to match:
the check runs one or two lanes.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness  # noqa: E402

WORKLOAD = "evabyte-l8-serve-bytedocs-longctx"
FAULTS = ("bf16_residual", "bf16_pool", "mu_phi_swapped",
          "pooled_before_rotation", "own_window_visible", "window_slides",
          "unit_offset_left_out", "padded_rows_pooled", "open_chunk_dropped")


@contextlib.contextmanager
def planted(name: str, cell, driver):
    """The fault ``name`` at the seams of ``models/gpt/eva.py`` (and, for a
    sliding window, of the cache manager) for the length of the block."""
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt import eva
    from fleetx_tpu.serving import cache_manager

    model = cell.config["model"]
    chunk, window = model["eva_chunk_size"], model["eva_window_size"]
    page = cell.deploy["page_size"]
    step = chunk * page          # positions a summary page stands for
    prompts = (driver.check_sizes(cell)[0], *driver.check_sizes(cell)[3])
    open_chunks = jnp.asarray([n // chunk for n in prompts if n % chunk])

    def tick_leaves_open(wpos, c):
        closes = wpos % c == c - 1
        return closes & ~jnp.isin(wpos // c, open_chunks)

    def slides(cfg, pos):
        return jnp.maximum(pos - window + 1, 0) // step * step

    class SlidingPool(cache_manager.WindowPagePool):
        def __init__(self, *args, tumbling=False, **kw):
            args = list(args)
            args[4] = window + step      # ``window``: what has to be held
            super().__init__(*args, **kw)

    changes = {
        "bf16_pool": [(eva, "_pool_dtype", lambda: jnp.bfloat16)],
        "mu_phi_swapped": [(eva, "_pool_vectors", lambda mu, phi: (phi, mu))],
        "pooled_before_rotation": [
            (eva, "_keys_to_pool", lambda rotated, raw: raw)],
        "own_window_visible": [
            (eva, "_summary_rows_visible",
             lambda cfg, pos: pos // step * page)],
        "window_slides": [
            (eva, "_window_start", slides),
            (eva, "_summary_rows_visible",
             lambda cfg, pos: slides(cfg, pos) // chunk),
            (eva, "_MORE_WINDOW_PAGES",
             (step + cell.deploy["prefill_chunk"]) // page),
            (cache_manager, "WindowPagePool", SlidingPool)],
        "unit_offset_left_out": [(eva, "_norm_gain", lambda w: w)],
        "padded_rows_pooled": [
            (eva, "_chunks_closed", lambda rows_true, rows, c: jnp.ones(
                (rows // c,), bool)),
            (eva, "_tick_closes", tick_leaves_open)],
        "open_chunk_dropped": [(eva, "_tick_closes", tick_leaves_open)],
        "ticks_close_nothing": [
            (eva, "_tick_closes", lambda wpos, c: jnp.zeros_like(wpos, bool))],
    }.get(name, [])
    before = [(module, attr, getattr(module, attr))
              for module, attr, _ in changes]
    for module, attr, value in changes:
        setattr(module, attr, value)
    try:
        yield
    finally:
        for module, attr, value in before:
            setattr(module, attr, value)


def readings(cell, driver, seed: int, only=None):
    """``(name, reference_check's dict)`` for every engine (``only``: for
    those named)."""
    model, variables = driver.build_model(cell, seed)
    for name in ("as_built", *FAULTS):
        if only and name not in only:
            continue
        served = model
        if name == "bf16_residual":
            served = model.clone(cfg=dataclasses.replace(
                model.cfg, residual_dtype=None))
        with planted(name, cell, driver):
            engine = driver.swa_driver.build_engine(cell, served, variables)
            try:
                driver.swa_driver.warm_up(engine, cell, seed)
                yield name, driver.reference_check(engine, variables, cell,
                                                   seed)
            finally:
                del engine
                gc.collect()


ENGINE_FAULTS = ("engine_as_built", "engine_ticks_close_nothing",
                 "engine_own_window_visible", "engine_mu_phi_swapped")


def in_flight(engine, cell, driver, seed: int) -> list:
    """One request a lane submitted and stepped until every one has decoded
    ``tokens`` tokens with every lane live; their ids."""
    import numpy as np

    lanes = cell.deploy["lanes"]
    if cell.tiny:
        prompts, tokens = (driver.check_sizes(cell)[3] * lanes)[:lanes], 24
    else:
        prompts, tokens = (6670, 4612, 3077, 2300)[:lanes], 160
    rng = np.random.default_rng([seed, 6])
    vocab = cell.config["model"]["vocab_size"]
    ids = [engine.submit(rng.integers(0, vocab, n, dtype=np.int32),
                         max_length=4 * tokens) for n in prompts]
    while min(len(engine.emitted_tokens(i) or ()) for i in ids) < tokens:
        engine.step()
    return ids


def engine_readings(cell, driver, seed: int, unit: float, only=None):
    """``(name, engine_check's dict)`` for every engine of ``ENGINE_FAULTS``
    (``only``: for those named)."""
    model, variables = driver.build_model(cell, seed)
    for name in ENGINE_FAULTS:
        if only and name not in only:
            continue
        fault = name[len("engine_"):]
        with planted(fault, cell, driver):
            engine = driver.swa_driver.build_engine(cell, model, variables)
            driver.swa_driver.warm_up(engine, cell, seed)   # traces them
            ids = in_flight(engine, cell, driver, seed)
        try:
            served = driver.Served(engine, driver.check_sizes(cell)[2])
            yield name, driver.engine_check(engine, served, ids, unit,
                                            driver.limits(cell))
        finally:
            del engine
            gc.collect()


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--only", nargs="*", default=None)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    cell = harness.load_cell(WORKLOAD, tiny=args.tiny)
    lanes = min(4, cell.deploy["lanes"])
    chunk = cell.config["model"]["eva_chunk_size"]
    cell.deploy.update(lanes=lanes,
                       pool_tokens=lanes * cell.deploy["cache_len"] // chunk)
    harness.own_the_chip(cell.chips, cell.tiny)

    import importlib

    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    driver = importlib.import_module(
        "perfbench.drivers." + cell.traffic["driver"])
    wrong = 0
    for seed in args.seeds:
        unit = 1.0
        for name, out in readings(cell, driver, seed, args.only):
            if name == "as_built":
                unit = out["reference_logit_std"]
            wrong += out["reference_ok"] != (name == "as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
        gc.collect()
        for name, out in engine_readings(cell, driver, seed, unit, args.only):
            wrong += out["engine_ok"] != (name == "engine_as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
