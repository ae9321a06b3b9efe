"""Does the reference check of the SmallThinker cell refuse what has to come
out NOT correct? The cell's engine is built several times on the weights of
one seed and put through the cell's driver's ``reference_check`` (the
reference always reads the weights as made and the configuration as
published):

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- ``bf16_router``: the router's product and softmax input in bfloat16;
- ``window_ignored``: every window layer attends its whole row (the served
  model's window is the cache length);
- ``full_layer_rotated``: the two full-attention layers rotate their
  queries and keys like the others (the served model's ``rope_layout`` all 1);
- ``as_built_l4`` / ``int8_experts_l4``: the first period alone (4 layers,
  reference and system both), as built and with the expert matrices the
  engine holds rounded to 8 bits with one scale per output column: a second
  copy of the experts of 8 layers does not fit beside the first (7.9 + 7.2
  GB), and a layer's reading does not depend on the depth.

Every engine but the first must read NOT ok.

Then faults planted in the ENGINE'S OWN PROGRAMS ALONE (its chunk prefill
and its tick, built from a model with the fault; the check's programs,
``Served``, from the model as configured), each put through the driver's
``engine_check`` on four requests in flight, every lane decoding:

- ``engine_as_built``: must read ``engine_ok``;
- ``engine_window_ignored``, ``engine_full_layer_rotated``: as above, in
  the engine's programs only;
- ``engine_stale_tables``: for the second half of the tokens the tick is
  handed the block tables of one moment (a version counter that does not move), so
  pages a lane is given later are never written.

    python3 perfbench/probe_smallthinker.py --seeds 7 8 [--tiny]

One JSON line per engine and seed; exit 1 if any reading is on the wrong
side. The limits in ``drivers/serve_closed_loop_swa.py`` are set between
these readings (PERF.md). The engines here have 4 lanes and pools to match:
the check runs one lane.
"""

from __future__ import annotations

import argparse
import copy
import dataclasses
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness, probe_precision  # noqa: E402

WORKLOAD = "smallthinker-l8-serve-longdoc-gen"


def first_period(cell):
    """``cell`` with its configuration cut to the first 4 layers."""
    cell = copy.copy(cell)
    cell.config = copy.deepcopy(cell.config)
    model = cell.config["model"]
    model.update(num_layers=4, rope_layout=model["rope_layout"][:4],
                 sliding_window_layout=model["sliding_window_layout"][:4])
    return cell


def readings(cell, driver, seed: int, only=None):
    """``(name, reference_check's dict)`` for every engine (``only``: for
    those named)."""
    def check(name, cell, model, held, variables):
        if only and name not in only:
            return name, None
        engine = driver.build_engine(cell, model, held)
        try:
            driver.warm_up(engine, cell, seed)
            return name, driver.reference_check(engine, variables, cell, seed)
        finally:
            del engine
            gc.collect()

    model, variables = driver.ref_driver.build_model(cell, seed)
    cfg = model.cfg
    yield check("as_built", cell, model, variables, variables)
    with probe_precision.router_in_bfloat16():
        yield check("bf16_router", cell, model.clone(), variables, variables)
    yield check("window_ignored", cell, model.clone(cfg=dataclasses.replace(
        cfg, sliding_window=cell.deploy["cache_len"])), variables, variables)
    yield check("full_layer_rotated", cell, model.clone(
        cfg=dataclasses.replace(cfg, rope_layout=(1,) * cfg.num_layers)),
        variables, variables)
    del model, variables
    gc.collect()
    cut = first_period(cell)
    model, variables = driver.ref_driver.build_model(cut, seed)
    yield check("as_built_l4", cut, model, variables, variables)
    yield check("int8_experts_l4", cut, model,
                probe_precision.int8_experts(variables)
                if not only or "int8_experts_l4" in only else None, variables)


def in_flight(engine, cell, driver, seed: int, stale: bool = False) -> list:
    """One request a lane submitted and stepped until every one has decoded
    ``tokens`` tokens with every lane live; their ids. ``stale``: see
    ``engine_stale_tables`` above."""
    import numpy as np

    lanes = cell.deploy["lanes"]
    if cell.tiny:
        prompts, tokens = (driver.check_sizes(cell)[3] * lanes)[:lanes], 16
    else:
        prompts, tokens = (6656, 4612, 3072, 772)[:lanes], 128
    rng = np.random.default_rng([seed, 6])
    vocab = cell.config["model"]["vocab_size"]
    ids = [engine.submit(rng.integers(1, vocab, n, dtype=np.int32),
                         max_length=4 * tokens) for n in prompts]

    def fewest():
        out = [engine.emitted_tokens(i) for i in ids]
        return min(len(t) for t in out)

    while fewest() < tokens // 2:
        engine.step()
    if stale:
        frozen = engine._device_tables()
        engine._device_tables = lambda: frozen
    while fewest() < tokens:
        engine.step()
    return ids


ENGINE_FAULTS = ("engine_as_built", "engine_window_ignored",
                 "engine_full_layer_rotated", "engine_stale_tables")


def engine_readings(cell, driver, seed: int, unit: float, only=None,
                    built=None):
    """``(name, engine_check's dict)`` for every engine of
    ``ENGINE_FAULTS`` (``only``: for those named); ``built``: ``(model,
    variables)`` where the caller has them."""
    model, variables = built or driver.ref_driver.build_model(cell, seed)
    cfg = model.cfg
    changes = {"engine_window_ignored":
               {"sliding_window": cell.deploy["cache_len"]},
               "engine_full_layer_rotated":
               {"rope_layout": (1,) * cfg.num_layers}}
    for name in ENGINE_FAULTS:
        if only and name not in only:
            continue
        changed = changes.get(name, {})
        engine = driver.build_engine(
            cell, model.clone(cfg=dataclasses.replace(cfg, **changed)),
            variables)
        try:
            right = engine.model.clone(cfg=dataclasses.replace(
                engine.model.cfg, **{k: getattr(cfg, k) for k in changed}))
            served = driver.Served(engine, driver.check_sizes(cell)[2],
                                   model=right)
            ids = in_flight(engine, cell, driver, seed,
                            stale=name == "engine_stale_tables")
            yield name, driver.engine_check(engine, served, ids, unit)
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
    cell.deploy.update(lanes=4, pool_tokens=4 * cell.deploy["cache_len"])
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
            if out is None:
                continue
            if name == "as_built":
                unit = out["reference_logit_std"]
            wrong += out["reference_ok"] != name.startswith("as_built")
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
