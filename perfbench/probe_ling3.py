"""Does the check of the Ling-3.0-flash cell refuse what has to come out NOT
correct? One engine is built on the weights of one seed; the cell's driver's
``reference_check`` then holds to the reference (which always reads the
weights as made and the configuration as published) the programs of
``Served`` traced with a fault planted:

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- ``bf16_state``: the matrix state ``S`` rounded to bfloat16 wherever a
  kernel hands it back (the nearest precision below the float32 the
  configuration states for it);
- ``bf16_decay``: the log decay ``g`` rounded to bfloat16 where the two
  kernels take it (the rows the check is handed stay float32: the rule alone
  on the rows it really saw is what must refuse it);
- ``bf16_router``: the router's outputs rounded to bfloat16 before the
  sigmoid (``lax.reduce_precision``), so the scores, the groups' marks and
  the sums are bfloat16's;
- ``unnormed_rotary_key``: the shared rotary key cached and scored WITHOUT
  its norm (the other half of ``use_qk_norm`` in a latent layer);
- ``head_gate_left_out``: the latent layer's head-wise output gate not
  applied;
- ``unsafe_gate``: the decay's OTHER FORM, ``-exp(A_log) softplus(.)``
  unbounded (``kda_safe_gate`` off);
- ``padded_rows_update``: a padded bucket's rows advance the state and enter
  the filter rows kept;
- ``state_zeroed``: every call begins from zero, whatever the lane holds.

    python3 perfbench/probe_ling3.py --seeds 7 8 [--tiny] [--only ...]

``--engines``: faults planted in the ENGINE'S OWN PROGRAMS ALONE (its chunks
and its tick, traced with the fault; ``Served`` without), each put through
the driver's ``engine_check`` on requests in flight, every lane decoding:
``engine_as_built`` (must read ``engine_ok``), ``engine_state_zeroed`` and
``engine_padded_rows_update``. (An unnormed rotary key planted there reads
0.048 on the whole cached row where as built reads 0.033-0.044, my chip run,
PR 64: the engine check cannot tell it, the reference check's limit on the
key alone does, and the fault is planted in that part alone.)

One JSON line per reading and seed; exit 1 if any reading is on the wrong
side. The limits in ``drivers/serve_closed_loop_ling.py`` are set between
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
from perfbench.probe_solar2 import _rounded, _rounded_state  # noqa: E402

WORKLOAD = "ling3-l7-serve-reason-widebatch"
FAULTS = ("bf16_state", "bf16_decay", "bf16_router", "unnormed_rotary_key",
          "head_gate_left_out", "unsafe_gate", "padded_rows_update",
          "state_zeroed")
# a fault that is another configuration: the fields ``Served``'s model gets
AS_CONFIGURED = {"unsafe_gate": {"kda_safe_gate": False,
                                 "kda_lower_bound": 0.0}}
ENGINE_FAULTS = ("engine_as_built", "engine_state_zeroed",
                 "engine_padded_rows_update")


def _rounded_decay() -> dict:
    """``ops/pallas/kda.py``'s two entry points taking the log decay rounded
    to bfloat16 (``g`` is the fourth of a row's operands in both)."""
    from fleetx_tpu.ops.pallas import kda

    chunk, step = kda.kda_chunk, kda.kda_step

    def kda_chunk(q, k, v, g, *rest, **kwargs):
        return chunk(q, k, v, _rounded(g), *rest, **kwargs)

    def kda_step(state, layer, q, k, v, g, *rest, **kwargs):
        return step(state, layer, q, k, v, _rounded(g), *rest, **kwargs)

    return {"kda_chunk": kda_chunk, "kda_step": kda_step}


@contextlib.contextmanager
def planted(fault: str):
    """While open, a model traced anew computes with ``fault`` planted in
    ``models/gpt/mixed_stack.py``'s or ``latent.py``'s seams,
    ``parallel/moe_share.py``'s or ``ops/pallas/kda.py``'s entry points."""
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt import latent, mixed_stack
    from fleetx_tpu.ops.pallas import kda
    from fleetx_tpu.parallel import moe_share

    scored = moe_share._scored
    module, changed = {
        "bf16_state": (kda, _rounded_state()),
        "bf16_decay": (kda, _rounded_decay()),
        "bf16_router": (moe_share, {
            "_scored": lambda logits, gate: scored(_rounded(logits), gate)}),
        # (the norm is still called: its weight stays in the tree)
        "unnormed_rotary_key": (latent, {
            "_normed_rotary_key": lambda norm, key: (norm(key), key)[1]}),
        "head_gate_left_out": (latent, {
            "_head_gated": lambda out, gate: out}),
        "padded_rows_update": (mixed_stack, {"_state_rows": jnp.ones_like}),
        "state_zeroed": (mixed_stack, {
            "_begins": lambda wpos: jnp.ones_like(wpos, bool)}),
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
    every one has decoded ``2 x tail`` tokens with every lane live. The
    answers are long enough that the first lane admitted is still decoding
    when the last one has its tail (96 admissions of up to three programs
    are some 300 ticks: ``probe_solar2.in_flight``'s ``4 x tail`` tokens end
    the first lanes before the last begin, and its loop then never ends)."""
    import numpy as np

    tail, lanes = driver.check_sizes(cell)[2], cell.deploy["lanes"]
    chunk, vocab = engine.prefill_chunk, cell.config["model"]["vocab_size"]
    rng = np.random.default_rng([seed, 9])
    for _ in range(lanes):
        engine.submit(rng.integers(1, vocab, int(rng.integers(
            chunk // 2, 2 * chunk + chunk // 2)), dtype=np.int32),
            max_length=4 * tail + 8 * lanes)
    for _ in range(16 * (tail + lanes)):           # (never for ever)
        if len(engine._active) == lanes and min(
                len(r.tokens) for r in engine._active.values()) >= 2 * tail:
            break
        engine.step()
    else:
        raise RuntimeError("the lanes never decoded together")
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
        out = driver.engine_check(engine, served, 1.01,
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
    from perfbench.drivers import serve_closed_loop_ling

    theirs = simulator.traffic_gen
    simulator.traffic_gen = types.SimpleNamespace(
        client_stream=serve_closed_loop_ling.client_stream)
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
    parser.add_argument("--decode-ms", type=float, default=16.0)
    parser.add_argument("--chunk-ms", type=float, default=40.0)
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
    # (the check takes one lane's rows and the engines' requests are short; the
    # tick keeps the timed lane count)
    cell.deploy.update(pool_tokens=min(16, cell.deploy["lanes"])
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
