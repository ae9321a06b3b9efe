"""Does the check of the LFM2 cell refuse what has to come out NOT correct?
One engine is built on the weights of one seed; the cell's driver's
``reference_check`` then holds to the reference (which always reads the
weights as made and the configuration as published) the programs of
``Served`` built from a model with a fault planted:

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- ``state_zeroed``: every prefill call starts its convolution layers from
  zeros (a prefix hit that does not resume the state; a chunk that does not
  carry it);
- ``state_stale``: every call reads the state one position too early;
- ``bias_left_out``, ``softmax_for_sigmoid``, ``not_normalised``: the gate
  without its selection bias, with a softmax over all experts, with the
  chosen scores as they are;
- ``bf16_router``: the router's product in bfloat16;
- ``qk_norm_left_out``: read and NOT judged: with norm weights of 1 and
  projections drawn at 0.02 the per-head norm is all but the identity, and
  the logits read inside the as-built range (the CPU tests, with norm
  weights off 1, refuse it);
- ``as_built_l6`` / ``int8_experts_l6``: the first 6 layers alone (both dense
  layers and one period; reference and system both), as built and with the
  expert matrices the engine holds rounded to 8 bits with one scale per
  output column: a second copy of the experts of 14 layers does not fit
  beside the first, and a layer's reading does not depend on the depth.

Every reading but the two ``as_built`` must be NOT ok.

Then faults planted in the ENGINE'S OWN PROGRAMS ALONE (its prefill and its
tick, built from a model with the fault; the check's programs, ``Served``,
from the model as configured), each put through the driver's
``engine_check`` on four requests in flight that were admitted on a prefix
hit, every lane decoding:

- ``engine_as_built``: must read ``engine_ok``;
- ``engine_state_zeroed``: as above, in the engine's programs only; read and
  NOT judged (sixty positions after the hit the rows compared have
  forgotten it: ``reference_check`` and the CPU tests refuse that fault);
- ``engine_stale_tables``: for the second half of the tokens the tick is
  handed the block tables of one moment, so pages a lane is given later are
  never written.

    python3 perfbench/probe_lfm2.py --seeds 7 8 [--tiny] [--only ...]

One JSON line per reading and seed; exit 1 if any reading is on the wrong
side. The limits in ``drivers/serve_closed_loop_lfm2.py`` are set between
these readings (PERF.md). The engines here have 4 lanes and pools to match.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import dataclasses
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness, probe_precision  # noqa: E402

WORKLOAD = "lfm2-l14-serve-agent-prefix"
NOT_JUDGED = ("qk_norm_left_out", "engine_state_zeroed")
CUT = 6


def first_layers(cell):
    """``cell`` with its configuration cut to the first ``CUT`` layers."""
    cell = copy.copy(cell)
    cell.config = copy.deepcopy(cell.config)
    model = cell.config["model"]
    model.update(num_layers=CUT, layer_types=model["layer_types"][:CUT])
    return cell


def int8_experts(params):
    """``params`` with every expert matrix rounded to int8, one scale per
    output column, and cast back: the values an int8 engine would multiply
    by."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rounded(x):
        def one(w):  # a layer at a time: no float32 copy of the stack
            wide = w.astype(jnp.float32)
            scale = jnp.abs(wide).max(axis=-2, keepdims=True) / 127.0
            return (jnp.round(wide / scale) * scale).astype(w.dtype)
        return jax.lax.map(one, x)

    return jax.tree_util.tree_map_with_path(
        lambda path, x: rounded(x) if x.ndim == 4
        and "experts" in jax.tree_util.keystr(path) else x, params)


@contextlib.contextmanager
def state_read(fault: str):
    """While open, a model traced anew reads its convolution state wrongly:
    ``zeroed`` in every call of more than one row, ``stale`` one position
    too early in every call."""
    from fleetx_tpu.models.gpt import mixed_stack

    real = mixed_stack._read_state

    def stale(cfg, pool, tables, wpos, index):
        return real(cfg, pool, tables, wpos - 1, index)

    mixed_stack._read_state = (stale if fault == "stale"
                               else _zero_in_prefill(real))
    try:
        yield
    finally:
        mixed_stack._read_state = real


def _zero_in_prefill(real):
    """``_read_state`` that gives zeros to a one-lane call (a prefill: the
    tick has every lane) and the state to the others."""
    def read(cfg, pool, tables, wpos, index):
        held = real(cfg, pool, tables, wpos, index)
        return held * 0 if wpos.shape[0] == 1 else held
    return read


def leaves_of(model, params):
    """``params`` without the leaves ``model`` does not declare (a gate
    without its bias, an attention without its norms)."""
    import flax
    import jax
    import numpy as np

    declared = flax.core.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))))["params"]

    def keep(have, like):
        return ({k: keep(have[k], v) for k, v in like.items()}
                if isinstance(like, dict) else have)

    return keep(params, declared)


GATE_FAULTS = {
    "bias_left_out": {"use_expert_bias": False},
    "softmax_for_sigmoid": {"gate": "softmax_topk", "use_expert_bias": False,
                            "expert_bias_init_std": 0.0},
    "not_normalised": {"norm_topk_prob": False},
    "qk_norm_left_out": {"qk_norm": False, "qk_norm_scope": "projection"},
}


def readings(cell, driver, seed: int, only=None):
    """``(name, reference_check's dict)`` for every reading (``only``: for
    those named)."""
    def wanted(name):
        return not only or name in only

    model, variables = driver.ref_driver.build_model(cell, seed)
    engine = driver.build_engine(cell, model, variables)
    driver.warm_up(engine, cell, seed)

    def check(name, context=contextlib.nullcontext(), **changed):
        with context:
            faulty = engine.model.clone(
                cfg=dataclasses.replace(engine.model.cfg, **changed))
            served = driver.Served(engine, model=faulty, params=leaves_of(
                faulty, engine.params) if changed else None)
            return name, driver.reference_check(engine, variables, cell, seed,
                                                served)

    try:
        if wanted("as_built"):
            yield check("as_built")
        for fault in ("zeroed", "stale"):
            if wanted("state_" + fault):
                yield check("state_" + fault, state_read(fault))
        for name, changed in GATE_FAULTS.items():
            if wanted(name):
                yield check(name, **changed)
        if wanted("bf16_router"):
            yield check("bf16_router", probe_precision.router_in_bfloat16())
    finally:
        del engine, model, variables
        gc.collect()
    if not (wanted("as_built_l6") or wanted("int8_experts_l6")):
        return
    cut = first_layers(cell)
    model, variables = driver.ref_driver.build_model(cut, seed)
    engine = driver.build_engine(cut, model, variables)
    driver.warm_up(engine, cut, seed)
    if wanted("as_built_l6"):
        yield "as_built_l6", driver.reference_check(engine, variables, cut,
                                                    seed)
    if wanted("int8_experts_l6"):
        served = driver.Served(engine, params=int8_experts(engine.params))
        yield "int8_experts_l6", driver.reference_check(
            engine, variables, cut, seed, served)
    del engine, model, variables
    gc.collect()


def in_flight(engine, cell, driver, seed: int, stale: bool = False) -> list:
    """One request a lane, each admitted on a prefix another request has
    registered, stepped until every one has decoded ``tokens`` tokens with
    every lane live; their ids. ``stale``: see ``engine_stale_tables``."""
    import numpy as np

    lanes = cell.deploy["lanes"]
    prefix, own, _, _, tail = driver.check_sizes(cell)
    tokens = 2 * tail
    rng = np.random.default_rng([seed, 6])
    vocab = cell.config["model"]["vocab_size"]
    shared = rng.integers(1, vocab, prefix, dtype=np.int32)

    def prompt(n):
        return np.concatenate([shared, rng.integers(1, vocab, n,
                                                    dtype=np.int32)])

    engine.submit(prompt(own // 4), max_length=2)
    engine.drain()
    ids = [engine.submit(prompt(own // 8 * (i + 1)), max_length=2 * tokens)
           for i in range(lanes)]

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
    return ids


ENGINE_FAULTS = ("engine_as_built", "engine_state_zeroed",
                 "engine_stale_tables")


def engine_readings(cell, driver, seed: int, unit: float, only=None):
    """``(name, engine_check's dict)`` for every engine of
    ``ENGINE_FAULTS`` (``only``: for those named)."""
    model, variables = driver.ref_driver.build_model(cell, seed)
    for name in ENGINE_FAULTS:
        if only and name not in only:
            continue
        context = (state_read("zeroed") if name == "engine_state_zeroed"
                   else contextlib.nullcontext())
        with context:  # the engine's programs are traced in here
            engine = driver.build_engine(cell, model.clone(), variables)
            ids = in_flight(engine, cell, driver, seed,
                            stale=name == "engine_stale_tables")
        try:
            served = driver.Served(engine)
            yield name, driver.engine_check(engine, served, ids, unit,
                                            driver.check_sizes(cell)[4])
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
            if name == "as_built":
                unit = out["reference_logit_std"]
            if name not in NOT_JUDGED:
                wrong += out["reference_ok"] != name.startswith("as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
        gc.collect()
        for name, out in engine_readings(cell, driver, seed, unit, args.only):
            if name not in NOT_JUDGED:
                wrong += out["engine_ok"] != (name == "engine_as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
