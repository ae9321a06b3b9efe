"""Does the check of the A.X-K1 cell refuse what has to come out NOT correct?
One engine is built on the weights of one seed; the cell's driver's
``reference_check`` then holds to the reference (which always reads the
weights as made and the configuration as published) the programs of
``Served`` traced with a fault planted:

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- ``w_uv_left_out``: the absorbed form (a decode tick) hands a head's share
  of ``P c_kv`` on without its product with ``W_UV``;
- ``key_unrotated``: the rotary key goes into the cache as projected;
- ``group_limit_off``: the router takes the 8 largest scores of all 192;
- ``shared_expert_dropped``: the expert layers add the routed part alone;
- ``unheld_pair_computed``: the pairs of the NEXT twelve experts are laid
  out and computed with the held experts' weights;
- ``bf16_router`` and ``bf16_scores``: the router's product in bfloat16 at
  default precision (``probe_precision.router_in_bfloat16``, as the other
  expert cells' probes plant it), and a chunk's attention scores
  accumulated in bfloat16: the nearest precision below the float32 that
  the configuration's arithmetic states for both.

Every reading but ``as_built`` must be NOT ok.

Then faults planted in the ENGINE'S OWN PROGRAMS ALONE (its chunks and its
tick, traced with the fault; the check's programs, ``Served``, without),
each put through the driver's ``engine_check`` on requests in flight, every
lane decoding:

- ``engine_as_built``: must read ``engine_ok``;
- ``engine_w_uv_left_out``: the TIMED TICK's absorbed form without ``W_UV``
  (the engine's chunks and ``Served`` as built);
- ``engine_stale_tables``: for the second half of the tokens the tick is
  handed the block tables of one moment, so pages a lane is given later are
  never written.

    python3 perfbench/probe_axk1.py --seeds 7 8 [--tiny] [--only ...]

One JSON line per reading and seed; exit 1 if any reading is on the wrong
side. The limits in ``drivers/serve_closed_loop_mla.py`` are set between
these readings (PERF.md). The engines here have the cell's 16 lanes (the
tick is checked at the timed lane count) and a pool of 4 lanes' rows.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness  # noqa: E402

WORKLOAD = "axk1-l6-serve-docqa-latent"
FAULTS = ("w_uv_left_out", "key_unrotated", "group_limit_off",
          "shared_expert_dropped", "unheld_pair_computed", "bf16_router",
          "bf16_scores")


@contextlib.contextmanager
def planted(fault: str):
    """While open, a model traced anew computes with ``fault`` planted in
    the seams of ``models/gpt/latent.py`` or ``parallel/moe_share.py``."""
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt import latent
    from fleetx_tpu.parallel import moe_share
    from perfbench import probe_precision

    if fault == "bf16_router":
        with probe_precision.router_in_bfloat16():
            yield
        return
    layout = moe_share.held_row_layout
    module, changed = {
        "w_uv_left_out": (latent, {"_through_w_uv": lambda out, w_uv: out[
            ..., :w_uv.shape[-1]]}),
        "key_unrotated": (latent, {"_rotated_key": lambda kr, rope: kr}),
        "bf16_scores": (latent, {"_SCORE_TYPE": jnp.bfloat16}),
        "group_limit_off": (moe_share, {
            "group_limited_topk": lambda scores, k, groups, kept:
                jax.lax.top_k(scores, k)[1]}),
        "shared_expert_dropped": (moe_share, {
            "_shared_expert": lambda t, gate, up, down: jnp.zeros_like(t)}),
        "unheld_pair_computed": (moe_share, {
            "held_row_layout": lambda idx, first, count, tm: layout(
                idx, first + count, count, tm)}),
    }[fault]
    real = {name: getattr(module, name) for name in changed}
    for name, value in changed.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in real.items():
            setattr(module, name, value)


def readings(cell, driver, seed: int, only=None):
    """``(name, reference_check's dict)`` for every reading (``only``: for
    those named; ``as_built`` always comes first, so that the engine's own
    programs, which register the document, are traced without a fault)."""
    model, variables = driver.ref_driver.build_model(cell, seed)
    engine = driver.build_engine(cell, model, variables)
    try:
        for name in ("as_built",) + FAULTS:
            if only and name != "as_built" and name not in only:
                continue
            context = (contextlib.nullcontext() if name == "as_built"
                       else planted(name))
            with context:  # ``Served``'s programs are traced in here
                yield name, driver.reference_check(
                    engine, variables, cell, seed, driver.Served(engine))
    finally:
        del engine, model, variables
        gc.collect()


ENGINE_FAULTS = ("engine_as_built", "engine_w_uv_left_out",
                 "engine_stale_tables")


def in_flight(engine, cell, driver, seed: int, stale: bool = False) -> None:
    """One request a lane, each a document of the check's size with a
    question of its own, stepped until every one has decoded twice the
    check's tail with every lane live. ``stale``: ``engine_stale_tables``."""
    import numpy as np

    doc, own, _, tail = driver.check_sizes(cell)
    # the lanes begin to decode a document's chunks apart: the first must
    # still be decoding when the last has its tokens
    longest = driver.traffic_gen.length_bounds(cell.traffic["output"])[1]
    rng = np.random.default_rng([seed, 6])
    vocab = cell.config["model"]["vocab_size"]
    ids = [engine.submit(rng.integers(
        1, vocab, doc + own // 8 * (i % 8 + 1), dtype=np.int32),
        max_length=longest) for i in range(cell.deploy["lanes"])]

    def fewest():
        return min(len(engine.emitted_tokens(i)) for i in ids)

    while fewest() < tail:
        engine.step()
    if stale:
        # a copy of its own: on the CPU the upload may alias the host table
        frozen = engine._device_tables().copy()
        engine._device_tables = lambda: frozen
    while fewest() < 2 * tail:
        engine.step()


def engine_readings(cell, driver, seed: int, unit: float, only=None):
    """``(name, engine_check's dict)`` for every engine of
    ``ENGINE_FAULTS`` (``only``: for those named)."""
    model, variables = driver.ref_driver.build_model(cell, seed)
    for name in ENGINE_FAULTS:
        if only and name not in only:
            continue
        context = (planted("w_uv_left_out")
                   if name == "engine_w_uv_left_out"
                   else contextlib.nullcontext())
        with context:  # the engine's programs are traced in here
            engine = driver.build_engine(cell, model.clone(), variables)
            in_flight(engine, cell, driver, seed,
                      stale=name == "engine_stale_tables")
        try:
            yield name, driver.engine_check(
                engine, driver.Served(engine), unit,
                driver.check_sizes(cell)[3])
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
    cell.deploy.update(pool_tokens=min(4, cell.deploy["lanes"])
                       * cell.deploy["cache_len"])
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
