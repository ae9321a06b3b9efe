"""Does the check of the Jamba2 cell refuse what has to come out NOT correct?
One engine is built on the weights of one seed; the cell's driver's
``reference_check`` then holds to the reference (which always reads the
weights as made and the configuration as published) the programs of
``Served`` traced with a fault planted:

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- ``bf16_state``: the scan's state ``h`` rounded to bfloat16 wherever a call
  hands it back (between the chunks of a prefill and after every decode
  step): the nearest precision below the configuration's float32 state;
- ``inner_norms_left_out``: ``dt``'s input, ``B`` and ``C`` without their
  RMSNorms;
- ``d_skip_left_out``: ``y`` without ``D * u``;
- ``state_zeroed``: every call begins its lane's state from zero (a decode
  step that forgets the prefill; a chunk that forgets the last);
- ``state_stale``: a call of several rows leaves its last token out of the
  state it hands on (the state a position stale between prefill and decode
  and between chunks);
- ``padded_rows_update``: the rows of a padded call that are no tokens
  advance the state too.

Every reading but ``as_built`` must be NOT ok.

Then faults planted in the ENGINE'S OWN PROGRAMS ALONE (its prefill and its
tick, traced with the fault; the check's programs, ``Served``, without),
each put through the driver's ``engine_check`` on requests in flight, every
lane decoding:

- ``engine_as_built``: must read ``engine_ok``;
- ``engine_bf16_state``: the TICK's state rounded to bfloat16 (what the
  step kernel writes back, after every decode step of every lane; the
  engine's prefill and ``Served`` as built): the timed tick a precision
  below the configuration's, which the limit on the FIRST layer's state
  refuses (``FIRST_STATE_TOL``: nothing else differs there);
- ``engine_state_zeroed``: as above, in the engine's programs only;
- ``engine_stale_tables``: for the second half of the tokens the tick is
  handed the block tables of one moment, so pages a lane is given later are
  never written (the keys and values' part of the check).

    python3 perfbench/probe_jamba2.py --seeds 7 8 [--tiny] [--only ...]

One JSON line per reading and seed; exit 1 if any reading is on the wrong
side. The limits in ``drivers/serve_closed_loop_ssm.py`` are set between
these readings (PERF.md). The engines here have 8 lanes and pools to match.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness, serving  # noqa: E402

WORKLOAD = "jamba2-3b-serve-chat-peak"
FAULTS = ("bf16_state", "inner_norms_left_out", "d_skip_left_out",
          "state_zeroed", "state_stale", "padded_rows_update")
ENGINE_FAULTS = ("engine_as_built", "engine_bf16_state",
                 "engine_state_zeroed", "engine_stale_tables")
# the fault of ``planted`` that an engine of ``ENGINE_FAULTS`` is traced with
_ENGINE_PLANTS = {"engine_bf16_state": "bf16_step",
                  "engine_state_zeroed": "state_zeroed"}


def _rounded_state():
    """``{name: function}`` of ``ops/pallas/ssm_scan.py``'s two entry points
    handing the state back rounded to bfloat16."""
    import jax

    from fleetx_tpu.ops.pallas import ssm_scan

    def rounded(x):
        # (a pair of converts is "excess precision" that XLA may drop on
        # the TPU: the first chip reading of this fault was the as-built
        # one to every digit)
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    scan, step = ssm_scan.selective_scan, ssm_scan.selective_step

    def selective_scan(*args, **kwargs):
        y, h = scan(*args, **kwargs)
        return y, rounded(h)

    def selective_step(state, layer, *args, **kwargs):
        y, state = step(state, layer, *args, **kwargs)
        return y, state.at[layer].set(rounded(state[layer]))

    return {"selective_scan": selective_scan, "selective_step": selective_step}


@contextlib.contextmanager
def planted(fault: str):
    """While open, a model traced anew computes with ``fault`` (one of
    ``FAULTS``, or ``bf16_step``: the one-row update's share of
    ``bf16_state``) planted in ``models/gpt/mixed_stack.py``'s seams or
    ``ops/pallas/ssm_scan.py``'s entry points."""
    import jax.numpy as jnp
    from flax import linen as nn

    from fleetx_tpu.models.gpt import mixed_stack
    from fleetx_tpu.ops.pallas import ssm_scan

    class NoNorm(nn.Module):
        """The norm's weight declared (the tree keeps its leaves) and not
        applied."""

        @nn.compact
        def __call__(self, t):
            self.param("scale", nn.initializers.ones_init(), t.shape[-1:],
                       jnp.float32)
            return t

    def all_but_the_last(rows):
        if rows.shape[1] == 1:
            return rows
        count = rows.sum(axis=1, keepdims=True)
        return rows & (jnp.arange(rows.shape[1])[None, :] < count - 1)

    rounded = _rounded_state()
    module, changed = mixed_stack, {
        "bf16_state": rounded,
        "bf16_step": {"selective_step": rounded["selective_step"]},
        "inner_norms_left_out": {
            "_inner_norm": lambda cfg, name: NoNorm(name=name)},
        "d_skip_left_out": {"_gated": lambda y, u, z, skip: y * nn.silu(
            z.astype(jnp.float32))},
        "state_zeroed": {"_begins": lambda wpos: jnp.ones_like(wpos, bool)},
        "state_stale": {"_state_rows": all_but_the_last},
        "padded_rows_update": {"_state_rows": jnp.ones_like},
    }[fault]
    if fault.startswith("bf16_"):
        module = ssm_scan
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
    those named)."""
    model, variables = driver.ref_driver.build_model(cell, seed)
    engine = serving.build_engine(cell, model, variables)
    try:
        for name in ("as_built",) + FAULTS:
            if only and name not in only:
                continue
            context = (contextlib.nullcontext() if name == "as_built"
                       else planted(name))
            with context:  # ``Served``'s programs are traced in here
                yield name, driver.reference_check(
                    engine, variables, cell, seed, driver.Served(engine))
    finally:
        del engine, model, variables
        gc.collect()


def in_flight(engine, cell, driver, seed: int, stale: bool = False) -> None:
    """One request a lane, stepped until every one has decoded ``2 x tail``
    tokens with every lane live. ``stale``: see ``engine_stale_tables``."""
    import numpy as np

    from perfbench import traffic as traffic_gen

    tail = driver.check_sizes(cell)[4]
    tokens = 2 * tail
    rng = np.random.default_rng([seed, 6])
    vocab = cell.config["model"]["vocab_size"]
    lo, hi = traffic_gen.length_bounds(cell.traffic["tenants"][0]["prompt"])
    step = engine.prefill_bucket
    ids = [engine.submit(rng.integers(
        1, vocab, min(lo + step * i, hi), dtype=np.int32),
        max_length=2 * tokens) for i in range(cell.deploy["lanes"])]

    def fewest():
        return min(len(engine.emitted_tokens(i)) for i in ids)

    while fewest() < tokens // 2:
        engine.step()
    if stale:
        # a copy of its own: on the CPU the upload may alias the host table
        frozen = engine._device_tables().copy()
        engine._device_tables = lambda: frozen
    while fewest() < tokens:
        engine.step()
    engine._settle("other")  # the tick in flight: the host's count is whole


def engine_readings(cell, driver, seed: int, unit: float, only=None):
    """``(name, engine_check's dict)`` for every engine of
    ``ENGINE_FAULTS`` (``only``: for those named)."""
    model, variables = driver.ref_driver.build_model(cell, seed)
    _, chunk, _, _, tail = driver.check_sizes(cell)
    for name in ENGINE_FAULTS:
        if only and name not in only:
            continue
        context = (planted(_ENGINE_PLANTS[name]) if name in _ENGINE_PLANTS
                   else contextlib.nullcontext())
        with context:  # the engine's programs are traced in here
            engine = serving.build_engine(cell, model.clone(), variables)
            in_flight(engine, cell, driver, seed,
                      stale=name == "engine_stale_tables")
        try:
            yield name, driver.engine_check(engine, driver.Served(engine),
                                            unit, tail, chunk)
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
    lanes = min(8, cell.deploy["lanes"])
    cell.deploy.update(lanes=lanes, pool_tokens=lanes * cell.deploy["cache_len"])
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
