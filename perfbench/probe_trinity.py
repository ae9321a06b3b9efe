"""Does the check of the Trinity cell refuse what has to come out NOT correct?
One engine is built on the weights of one seed; the cell's driver's
``reference_check`` then holds to the reference (which always reads the
weights as made and the configuration as published) the programs of
``Served`` traced with a fault planted:

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- in the attention: ``gate_left_out``; ``gate_from_unnormed_input`` (the
  gate's projection reads the residual stream, not its norm);
  ``qk_norm_left_out``; ``full_layer_rotated``; ``window_layers_unrotated``;
  ``window_4095`` and ``window_4097`` (a window off by one row, either way:
  the edge check's to refuse, in any precision);
- in the stack: ``post_norm_left_out``; ``norms_exchanged`` (every attention
  layer's pre- and post-norm weights change places); ``embedding_unscaled``;
- in the expert layers: ``route_scale_left_out``; ``bias_in_the_weights``
  (the chosen experts weighed by score + bias); ``unheld_pair_computed`` (the
  pairs of the NEXT 32 experts laid out and computed with the held experts'
  weights); ``shared_expert_twice``; ``bf16_router`` (the router's product
  in bfloat16 at default precision: the nearest precision below the float32
  the configuration's arithmetic states for it);
- ``answers_gate_left_out``: the ENGINE'S OWN programs traced without the
  gate and ``Served`` as built: the tokens the engine answers with stand far
  under the reference's best (the token limit's to refuse);
- ``as_built_l2`` / ``int8_experts_l2``: the first two layers alone (the
  dense layer and one expert layer, reference and system both), as built
  and with the expert matrices the engine holds rounded to 8 bits with one
  scale per output column: a second copy of the experts of four layers
  does not fit beside the first (7.2 GB twice), and a layer's reading does
  not depend on the depth.

Every reading but the ``as_built`` ones must be NOT ok.

Then faults planted in the ENGINE'S OWN PROGRAMS ALONE (its chunks and its
tick, traced with the fault; the check's programs, ``Served``, without),
each put through the driver's ``engine_check`` on requests in flight, every
lane decoding:

- ``engine_as_built``: must read ``engine_ok``;
- ``engine_gate_left_out``: the timed programs without the output gate;
- ``engine_stale_tables``: for the second half of the tokens the tick is
  handed the block tables of one moment, so a window page a lane is given
  later is never written and a released one goes on being read: a stale
  window page.

    python3 perfbench/probe_trinity.py --seeds 7 8 [--tiny] [--only ...]

One JSON line per reading and seed; exit 1 if any reading is on the wrong
side. The limits in ``drivers/serve_closed_loop_swa_share.py`` are set
between these readings (PERF.md). The engines here have the cell's 12 lanes
(the tick is checked at the timed lane count) and a pool of 4 lanes' rows.
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

from perfbench import harness  # noqa: E402

WORKLOAD = "trinity-l5-serve-mixed-longshort"
# a fault that is another configuration: the fields ``Served``'s model gets
CONFIGURED = {
    "qk_norm_left_out": lambda cfg: {"qk_norm": False,
                                     "qk_norm_scope": "projection"},
    "full_layer_rotated": lambda cfg: {"rope_layout": (1,) * cfg.num_layers},
    "window_layers_unrotated": lambda cfg: {
        "rope_layout": (0,) * cfg.num_layers},
    "window_4095": lambda cfg: {"sliding_window": cfg.sliding_window - 1},
    "window_4097": lambda cfg: {"sliding_window": cfg.sliding_window + 1},
    "post_norm_left_out": lambda cfg: {"sandwich_norm": False},
    "embedding_unscaled": lambda cfg: {"embedding_multiplier": 1.0},
    "route_scale_left_out": lambda cfg: {"routed_scaling_factor": 1.0},
}
SEAMS = ("gate_left_out", "gate_from_unnormed_input", "bias_in_the_weights",
         "unheld_pair_computed", "shared_expert_twice", "bf16_router")
FAULTS = (*CONFIGURED, *SEAMS, "norms_exchanged")


@contextlib.contextmanager
def planted(fault: str):
    """While open, a model traced anew computes with ``fault`` (one of
    ``SEAMS``) planted in the seams of ``models/gpt/hybrid.py`` or
    ``parallel/moe_share.py``."""
    from fleetx_tpu.models.gpt import hybrid, mixed_stack
    from fleetx_tpu.parallel import moe_share
    from perfbench import probe_precision

    if fault == "bf16_router":
        with probe_precision.router_in_bfloat16():
            yield
        return
    layout, shared, norm = (moe_share.held_row_layout,
                            moe_share._shared_expert, mixed_stack._norm)
    seen = []   # what the norm before the operator read, layer by layer

    class Recorded:
        """The stack's pre-norm, which notes its input: the stream."""

        def __init__(self, cfg):
            self.norm = norm(cfg)

        def __getattr__(self, name):
            return getattr(self.norm, name)

        def apply(self, variables, value):
            seen.append(value)
            return self.norm.apply(variables, value)

    module, changed = {
        "gate_left_out": (hybrid.HybridSelfAttention, {
            "_gate": lambda self, out, gate: out}),
        # the attention's norm is the last applied before its projections
        "gate_from_unnormed_input": (hybrid, {
            "_gate_reads": lambda x: seen[-1] if seen else x}),
        "bias_in_the_weights": (moe_share, {
            "_weighed": lambda scores, ranked: ranked}),
        "unheld_pair_computed": (moe_share, {
            "held_row_layout": lambda idx, first, count, tm: layout(
                idx, first + count, count, tm)}),
        "shared_expert_twice": (moe_share, {
            "_shared_expert": lambda *args: 2 * shared(*args)}),
    }[fault]
    real = {name: getattr(module, name) for name in changed}
    for name, value in changed.items():
        setattr(module, name, value)
    if fault == "gate_from_unnormed_input":
        mixed_stack._norm = Recorded
    try:
        yield
    finally:
        mixed_stack._norm = norm
        for name, value in real.items():
            setattr(module, name, value)


def norms_exchanged(params):
    """``params`` with every attention layer's pre-norm and post-norm
    weights in each other's place."""
    params = copy.copy(params)
    gpt = params["gpt"] = dict(params["gpt"])
    layers = gpt["layers"] = dict(gpt["layers"])
    kind = layers["attention"] = dict(layers["attention"])
    kind["norm"], kind["post_norm"] = kind["post_norm"], kind["norm"]
    return params


def int8_experts(variables):
    """``variables`` with every routed expert's matrices (the leaves
    ``w_gate`` / ``w_up`` / ``w_down`` ``[layers, held, in, out]``) rounded
    to int8, one scale per output column, and cast back."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rounded(x):
        def one(w):
            wide = w.astype(jnp.float32)
            scale = jnp.abs(wide).max(axis=-2, keepdims=True) / 127.0
            return (jnp.round(wide / scale) * scale).astype(w.dtype)
        return jax.lax.map(one, x)

    return jax.tree_util.tree_map_with_path(
        lambda path, x: rounded(x) if getattr(path[-1], "key", "") in (
            "w_gate", "w_up", "w_down") else x, variables)


def first_layers(cell, layers: int = 2):
    """``cell`` with its configuration cut to the first ``layers``."""
    cell = copy.copy(cell)
    cell.config = copy.deepcopy(cell.config)
    model = cell.config["model"]
    model.update(num_layers=layers, **{
        name: model[name][:layers] for name in (
            "layer_types", "rope_layout", "sliding_window_layout")})
    return cell


def _configured(engine, fault: str):
    """The engine's model with ``fault`` of ``CONFIGURED`` as its
    configuration (the engine's own otherwise), and the engine's weights
    without the leaves that model has no place for (a part left out leaves
    its weights out: flax refuses a tree with more)."""
    import jax
    import numpy as np

    cfg = engine.model.cfg
    changed = CONFIGURED.get(fault, lambda cfg: {})(cfg)
    model = engine.model.clone(cfg=dataclasses.replace(cfg, **changed))
    if not changed:
        return model, engine.params
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]

    def keep(have, want):
        return {k: keep(have[k], w) if isinstance(w, dict) else have[k]
                for k, w in want.items()}

    return model, keep(engine.params, want)


def _planted_served(driver, engine, tail: int, model, params):
    """``Served`` whose programs are ``model``'s on ``params`` (the engine's
    own programs keep the engine's)."""
    class Planted(driver.Served):
        def _call(self, *args, **kwargs):
            held, engine.params = engine.params, params
            try:
                return super()._call(*args, **kwargs)
            finally:
                engine.params = held

    return Planted(engine, tail, model=model)


def readings(cell, driver, seed: int, only=None):
    """``(name, reference_check's dict)`` for every reading (``only``: for
    those named; ``as_built`` always comes first, so that the engine's own
    programs are traced without a fault)."""
    def wanted(name):
        return not only or name in only

    model, variables = driver.build_model(cell, seed)
    engine = driver.build_engine(cell, model, variables)
    try:
        driver.warm_up(engine, cell, seed)
        for name in ("as_built",) + FAULTS:
            if name != "as_built" and not wanted(name):
                continue
            context = planted(name) if name in SEAMS else (
                contextlib.nullcontext())
            wrong, params = _configured(engine, name)
            if name == "norms_exchanged":
                params = norms_exchanged(params)
            with context:  # ``Served``'s programs are traced in here
                yield name, driver.reference_check(
                    engine, variables, cell, seed, _planted_served(
                        driver, engine, driver.check_sizes(cell)[2], wrong,
                        params))
    finally:  # (the loop's last weights among them)
        wrong = params = None
        del engine, model, variables
        gc.collect()
    if wanted("answers_gate_left_out"):
        # the ENGINE'S programs without the gate, ``Served`` as built: the
        # engine's own answers are the token limit's to refuse
        model, variables = driver.build_model(cell, seed)
        with planted("gate_left_out"):
            engine = driver.build_engine(cell, model.clone(), variables)
            driver.warm_up(engine, cell, seed)
        try:
            yield "answers_gate_left_out", driver.reference_check(
                engine, variables, cell, seed)
        finally:
            del engine, model, variables
            gc.collect()
    if not (wanted("as_built_l2") or wanted("int8_experts_l2")):
        return
    cut = first_layers(cell)
    model, variables = driver.build_model(cut, seed)
    for name in ("as_built_l2", "int8_experts_l2"):
        if not wanted(name):
            continue
        engine = driver.build_engine(
            cut, model, variables if name == "as_built_l2"
            else int8_experts(variables))
        try:
            driver.warm_up(engine, cut, seed)
            yield name, driver.reference_check(engine, variables, cut, seed)
        finally:
            del engine
            gc.collect()


ENGINE_FAULTS = ("engine_as_built", "engine_gate_left_out",
                 "engine_stale_tables")


def in_flight(engine, cell, driver, seed: int, stale: bool = False) -> list:
    """One request a lane, each longer than a chunk and two of them past the
    window, stepped until every one has decoded the check's tail with every
    lane live (a budget of twice that, the traffic's longest output at the
    published sizes); their ids. ``stale``: ``engine_stale_tables``."""
    import numpy as np

    _, _, tail, prompts, _ = driver.check_sizes(cell)
    lanes = cell.deploy["lanes"]
    rng = np.random.default_rng([seed, 6])
    vocab = cell.config["model"]["vocab_size"]
    chunk = engine.prefill_chunk
    lengths = [prompts[i] if i < 2 else chunk + chunk // 8 * (i % 8 + 1)
               for i in range(lanes)]
    ids = [engine.submit(rng.integers(1, vocab, n, dtype=np.int32),
                         max_length=2 * tail) for n in lengths]

    def fewest():
        return min(len(engine.emitted_tokens(i)) for i in ids)

    while fewest() < tail // 2:
        engine.step()
    if stale:
        # a copy of its own: on the CPU the upload may alias the host table
        frozen = engine._device_tables().copy()
        engine._device_tables = lambda: frozen
    while fewest() < tail:
        engine.step()
    return ids


def engine_readings(cell, driver, seed: int, unit: float, only=None):
    """``(name, engine_check's dict)`` for every engine of
    ``ENGINE_FAULTS`` (``only``: for those named)."""
    model, variables = driver.build_model(cell, seed)
    for name in ENGINE_FAULTS:
        if only and name not in only:
            continue
        context = (planted("gate_left_out")
                   if name == "engine_gate_left_out"
                   else contextlib.nullcontext())
        with context:  # the engine's programs are traced in here
            engine = driver.build_engine(cell, model.clone(), variables)
            ids = in_flight(engine, cell, driver, seed,
                            stale=name == "engine_stale_tables")
        try:
            yield name, driver.engine_check(
                engine, driver.Served(engine, driver.check_sizes(cell)[2]),
                ids, unit)
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
