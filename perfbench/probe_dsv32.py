"""Does the check of the DeepSeek-V3.2 cell refuse what has to come out NOT
correct? ``probe_axk1.py``'s scheme (one engine on the weights of one seed;
the cell's driver's ``reference_check`` holds to the reference the programs
of ``Served`` traced with a fault planted), with the faults of what this
configuration adds:

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- ``relu_left_out``: the heads' products are summed as they are;
- ``head_weights_left_out``: ``w_{t,j}`` is 1 for every head;
- ``index_key_unrotated``: the indexer's key goes into the cache as normed;
- ``tick_ignores_selection``: the TICK attends over every live row (dense
  absorbed attention); the chunks obey the selection;
- ``bias_in_weights``: the chosen experts' weights are taken from ``score +
  bias`` (the bias belongs in the groups' sums and the choice alone);
- ``unheld_pair_computed``: the pairs of the NEXT sixteen experts are laid
  out and computed with the held experts' weights;
- ``bf16_scores``: a chunk's attention scores accumulated in bfloat16, the
  nearest precision below the float32 the configuration's arithmetic states
  for them;
- ``bf16_index`` (a READING, not a fault): the indexer's products
  accumulated in bfloat16. On the chip it reads what ``as_built`` reads (``I``
  0.0266 both, the sets unmoved: the matmul accumulates in float32 whatever
  its output's type), so no limit can or should refuse it;
- on the CPU's tests besides (not worth a chip run: the chunk's own causal
  test stays, so it only wastes a query's choices on rows it cannot see):
  ``selects_from_unseen``, the top-k taken over the whole cache and not ``s
  <= t``.

Every reading but ``as_built`` must be NOT ok.

Then the ENGINE'S OWN PROGRAMS through the driver's ``engine_check`` on
requests in flight, every lane decoding: ``engine_as_built`` (must read
``engine_ok``), ``engine_tick_ignores_selection`` (the timed tick dense, the
engine's chunks and ``Served`` as built), ``engine_stale_tables``.

    python3 perfbench/probe_dsv32.py --seeds 7 8 [--tiny] [--only ...]

One JSON line per reading and seed; exit 1 if any reading is on the wrong
side. The limits in ``drivers/serve_closed_loop_dsa.py`` are set between
these readings (PERF.md). The engines here have the cell's lanes (the tick
is checked at the timed lane count) and a pool of 2 lanes' rows.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness, probe_axk1  # noqa: E402

WORKLOAD = "dsv32-l5-serve-longqa-sparse"
FAULTS = ("relu_left_out", "head_weights_left_out", "index_key_unrotated",
          "tick_ignores_selection", "bias_in_weights", "unheld_pair_computed",
          "bf16_scores")
CPU_FAULTS = ("selects_from_unseen",)
# read and printed, on either side of the limits: no fault by them
READINGS = ("bf16_index",)
ENGINE_FAULTS = ("engine_as_built", "engine_tick_ignores_selection",
                 "engine_stale_tables")


@contextlib.contextmanager
def planted(fault: str):
    """While open, a model traced anew computes with ``fault`` planted in
    the seams of ``models/gpt/latent.py`` or ``parallel/moe_share.py``."""
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt import latent
    from fleetx_tpu.parallel import moe_share

    if fault in ("unheld_pair_computed", "bf16_scores"):
        with probe_axk1.planted(fault):
            yield
        return

    def dense_tick(cfg, q_c, q_r, qi, w, pools, tables, end, scale):
        rows = tables.shape[1] * pools[0].shape[1]
        return (latent._decode(cfg, q_c, q_r, pools[0], pools[1], tables,
                               end, scale),
                jnp.zeros((q_c.shape[0], rows), jnp.float32),
                jnp.full((q_c.shape[0], 1), rows, jnp.int32))

    module, changed = {
        "relu_left_out": (latent, {"_index_act": lambda dots: dots}),
        "head_weights_left_out": (latent, {
            "_index_head_weights": lambda w: jnp.ones_like(w)}),
        "index_key_unrotated": (latent, {
            "_rotated_index_key": lambda ki, rope, rot: ki}),
        "selects_from_unseen": (latent, {
            "_visible": lambda seen: jnp.ones_like(seen)}),
        "tick_ignores_selection": (latent, {"_sparse_decode": dense_tick}),
        "bf16_index": (latent, {"_INDEX_TYPE": jnp.bfloat16}),
        "bias_in_weights": (moe_share, {
            "_weighed": lambda scores, ranked: ranked}),
    }[fault]
    real = {name: getattr(module, name) for name in changed}
    for name, value in changed.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in real.items():
            setattr(module, name, value)


def readings(cell, driver, seed: int, only=None,
             faults=FAULTS + READINGS):
    """``(name, reference_check's dict)`` for every reading (``only``: for
    those named; ``as_built`` always comes first, so that the engine's own
    programs, which register the document, are traced without a fault)."""
    model, variables = driver.ref_driver.build_model(cell, seed)
    engine = driver.build_engine(cell, model, variables)
    try:
        for name in ("as_built",) + tuple(faults):
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


def engine_readings(cell, driver, seed: int, unit: float, only=None):
    """``(name, engine_check's dict)`` for every engine of
    ``ENGINE_FAULTS`` (``only``: for those named)."""
    model, variables = driver.ref_driver.build_model(cell, seed)
    for name in ENGINE_FAULTS:
        if only and name not in only:
            continue
        context = (planted("tick_ignores_selection")
                   if name == "engine_tick_ignores_selection"
                   else contextlib.nullcontext())
        with context:  # the engine's programs are traced in here
            engine = driver.build_engine(cell, model.clone(), variables)
            probe_axk1.in_flight(engine, cell, driver, seed,
                                 stale=name == "engine_stale_tables")
        try:
            with driver.in_the_mla_drivers_place():
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
    parser.add_argument("--engines", action="store_true",
                        help="also the faults in the engine's own programs")
    args = parser.parse_args()
    cell = harness.load_cell(WORKLOAD, tiny=args.tiny)
    cell.deploy.update(pool_tokens=min(2, cell.deploy["lanes"])
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
            if name not in READINGS:
                wrong += out["reference_ok"] != (name == "as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
        gc.collect()
        if not args.engines:
            continue
        for name, out in engine_readings(cell, driver, seed, unit, args.only):
            wrong += out["engine_ok"] != (name == "engine_as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
