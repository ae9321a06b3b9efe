"""Does the check of the Solar-Open2 cell refuse what has to come out NOT
correct? One engine is built on the weights of one seed; the cell's driver's
``reference_check`` then holds to the reference (which always reads the
weights as made and the configuration as published) the programs of
``Served`` traced with a fault planted:

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- ``bf16_state``: the matrix state ``S`` rounded to bfloat16 wherever a
  kernel hands it back (the nearest precision below the float32 the
  configuration states for it);
- ``bf16_router``: the router's outputs rounded to bfloat16 before the
  sigmoid (``lax.reduce_precision``: the product itself in bfloat16 is
  "excess precision" that XLA keeps in float32 on the TPU, 1.5e-5 of a
  weight where as built reads 2.4e-7, my chip run, PR 56), so the scores and
  their sums are bfloat16's;
- ``beta_not_doubled``: ``kda_neg_eigval`` off (``beta`` in (0, 1));
- ``padded_rows_update``: a padded bucket's rows advance the state and enter
  the filter rows kept;
- ``state_zeroed``: every call begins from zero, whatever the lane holds;
- ``gate_left_out``: the GQA layers' output gate not applied.

    python3 perfbench/probe_solar2.py --seeds 7 8 [--tiny] [--only ...]

``--engines``: faults planted in the ENGINE'S OWN PROGRAMS ALONE (its chunks
and its tick, traced with the fault; the check's programs, ``Served``,
without), each put through the driver's ``engine_check`` on requests in
flight, every lane decoding: ``engine_as_built`` (must read ``engine_ok``),
``engine_state_zeroed`` and ``engine_padded_rows_update``.

One JSON line per reading and seed; exit 1 if any reading is on the wrong
side. The limits in ``drivers/serve_closed_loop_kda.py`` are set between
these readings (PERF.md section 6).

``--orders 1 .. 12 --decode-ms D --chunk-ms C`` replays
``simulate_closed_loop.py`` on the cell's stream for each ``order_seed`` at
the tick and chunk times a traced run read, with no device in the loop: the
traffic file pins the order whose rate lies nearest the median.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import statistics
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness  # noqa: E402

WORKLOAD = "solar2-l8-serve-docreason-mixed"
FAULTS = ("bf16_state", "bf16_router", "beta_not_doubled",
          "padded_rows_update", "state_zeroed", "gate_left_out")
# a fault that is another configuration: the fields ``Served``'s model gets
AS_CONFIGURED = {"beta_not_doubled": {"kda_neg_eigval": False}}
ENGINE_FAULTS = ("engine_as_built", "engine_state_zeroed",
                 "engine_padded_rows_update")


def _rounded(x):
    """``x`` rounded to bfloat16's precision (``lax.reduce_precision``: XLA
    drops a pair of converts as excess precision on the TPU)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _rounded_state() -> dict:
    """``ops/pallas/kda.py``'s two entry points handing the state back
    rounded to bfloat16."""
    from fleetx_tpu.ops.pallas import kda

    rounded, chunk, step = _rounded, kda.kda_chunk, kda.kda_step

    def kda_chunk(*args, **kwargs):
        o, s = chunk(*args, **kwargs)
        return o, rounded(s)

    def kda_step(state, layer, *args, **kwargs):
        o, state = step(state, layer, *args, **kwargs)
        return o, state.at[layer].set(rounded(state[layer]))

    return {"kda_chunk": kda_chunk, "kda_step": kda_step}


@contextlib.contextmanager
def planted(fault: str):
    """While open, a model traced anew computes with ``fault`` planted in
    ``models/gpt/mixed_stack.py``'s or ``hybrid.py``'s seams or
    ``ops/pallas/kda.py``'s entry points."""
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt import hybrid, mixed_stack
    from fleetx_tpu.ops.pallas import kda
    from fleetx_tpu.parallel import moe_share

    scored = moe_share._scored
    module, changed = {
        "bf16_state": (kda, _rounded_state()),
        "bf16_router": (moe_share, {
            "_scored": lambda logits, gate: scored(_rounded(logits), gate)}),
        "padded_rows_update": (mixed_stack, {"_state_rows": jnp.ones_like}),
        "state_zeroed": (mixed_stack, {
            "_begins": lambda wpos: jnp.ones_like(wpos, bool)}),
        "gate_left_out": (hybrid.HybridSelfAttention, {
            "_gate": lambda self, out, gate: out}),
    }.get(fault, (kda, {}))
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
    those named; ``as_built`` always comes first)."""
    model, variables = driver.ref_driver.build_model(cell, seed)
    engine = driver.longcat_driver.build_engine(cell, model, variables)
    try:
        for name in ("as_built",) + FAULTS:
            if only and name != "as_built" and name not in only:
                continue
            over = AS_CONFIGURED.get(name, {})
            with planted(name):  # ``Served``'s programs are traced in here
                served = driver.Served(engine, engine.model.clone(
                    cfg=dataclasses.replace(engine.model.cfg, **over)))
                yield name, driver.reference_check(
                    engine, variables, cell, seed, served)
            del served
            gc.collect()
    finally:
        del engine, model, variables
        gc.collect()


def in_flight(engine, cell, driver, seed: int) -> None:
    """One request a lane (prompts of one to three programs), stepped until
    every one has decoded ``2 x tail`` tokens with every lane live."""
    import numpy as np

    tail = driver.check_sizes(cell)[2]
    chunk, vocab = engine.prefill_chunk, cell.config["model"]["vocab_size"]
    rng = np.random.default_rng([seed, 9])
    for _ in range(cell.deploy["lanes"]):
        engine.submit(rng.integers(1, vocab, int(rng.integers(
            chunk // 2, 2 * chunk + chunk // 2)), dtype=np.int32),
            max_length=4 * tail)
    while (len(engine._active) < cell.deploy["lanes"] or min(
            len(r.tokens) for r in engine._active.values()) < 2 * tail):
        engine.step()
    engine.metrics.snapshot()          # (reads the tick in flight)


def engine_readings(cell, driver, seed: int, only=None):
    """``(name, engine_check's dict)`` for every reading of
    ``ENGINE_FAULTS``: an engine of its own each, its programs traced with
    the fault, ``Served`` as built."""
    for name in ENGINE_FAULTS:
        if only and name != "engine_as_built" and name not in only:
            continue
        model, variables = driver.ref_driver.build_model(cell, seed)
        engine = driver.longcat_driver.build_engine(cell, model, variables)
        del variables                 # (the engine holds the weights)
        with planted(name[len("engine_"):]):
            in_flight(engine, cell, driver, seed)
        served = driver.Served(engine, engine.model.clone())
        # (the logits' unit: the cell's reading, any seed's to 1%)
        out = driver.engine_check(engine, served, 1.28,
                                  driver.check_sizes(cell)[2])
        # the next engine does not fit beside this one
        del served, engine, model
        gc.collect()
        yield name, out


def order_rates(cell, orders, decode_ms: float, chunk_ms: float,
                seconds: float = 40.0) -> list:
    """``simulate_closed_loop.simulate`` of this cell's stream for every
    ``order_seed`` of ``orders``: its result dicts, ``order`` added."""
    from perfbench import simulate_closed_loop as simulator
    from perfbench.drivers import serve_closed_loop_kda

    theirs = simulator.traffic_gen
    simulator.traffic_gen = types.SimpleNamespace(
        client_stream=serve_closed_loop_kda.client_stream)
    try:
        return [{"order": order, **simulator.simulate(
            dataclasses.replace(cell, traffic={
                **cell.traffic, "order_seed": order,
                "clients": cell.traffic["closed_loop"]["clients"]}),
            0, seconds, decode_ms / 1e3, chunk_ms / 1e3)} for order in orders]
    finally:
        simulator.traffic_gen = theirs


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", type=int, nargs="*", default=[])
    parser.add_argument("--only", nargs="*", default=None)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--engines", action="store_true")
    parser.add_argument("--orders", type=int, nargs="*", default=[])
    parser.add_argument("--decode-ms", type=float, default=18.0)
    parser.add_argument("--chunk-ms", type=float, default=51.0)
    args = parser.parse_args()
    cell = harness.load_cell(WORKLOAD, tiny=args.tiny)
    if args.orders:
        rates = order_rates(cell, args.orders, args.decode_ms, args.chunk_ms)
        for out in rates:
            print(json.dumps(out), flush=True)
        each = [r["serve_tokens_per_s"] for r in rates]
        median = statistics.median(each)
        q1, _, q3 = (statistics.quantiles(each, n=4) if len(each) > 1
                     else (median,) * 3)
        print(json.dumps({
            "median": median, "spread": (q3 - q1) / median,
            "nearest_order": min(rates, key=lambda r: abs(
                r["serve_tokens_per_s"] - median))["order"]}))
    if not args.seeds:
        return 0
    # (the check takes one lane's rows; the tick keeps the timed lane count)
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
        for name, out in (() if args.engines else readings(
                cell, driver, seed, args.only)):
            wrong += out["reference_ok"] != (name == "as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
        for name, out in (engine_readings(cell, driver, seed, args.only)
                          if args.engines else ()):
            wrong += out["engine_ok"] != (name == "engine_as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
