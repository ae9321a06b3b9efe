"""Does the check of the LongCat-Flash cell refuse what has to come out NOT
correct? One engine is built on the weights of one seed; the cell's driver's
``reference_check`` then holds to the reference (which always reads the
weights as made and the configuration as published) the programs of
``Served`` traced with a fault planted, one at a time:

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- ``zero_experts_left_out``: a chosen zero-compute expert gives nothing;
- ``shortcut_lands_a_half_early``: the expert layer's output joins the
  stream after the dense MLP of the half it LEAVES at, not the next one's;
- ``s_kv_left_out``: the compressed keys/values go into the cache unscaled
  (``mla_scale_kv_lora`` off in the model traced, the weights the same);
- ``bias_in_the_weights``: a chosen expert is weighed by its score PLUS the
  selection bias;
- ``weights_renormalised``: the chosen weights are divided by their sum
  (``norm_topk_prob`` on in the model traced);
- ``bf16_softmax_scores``: the router's softmax computed in bfloat16, the
  nearest precision below the float32 the configuration states for it.

Every reading but ``as_built`` must be NOT ok.

    python3 perfbench/probe_longcat.py --seeds 7 8 [--tiny] [--only ...]

One JSON line per reading and seed; exit 1 if any reading is on the wrong
side. The limits in ``drivers/serve_closed_loop_longcat.py`` are set between
these readings (PERF.md). The engines here have the cell's lanes (the
tick is checked at the timed lane count) and a pool of 4 lanes' rows.

    python3 perfbench/probe_longcat.py --orders 1 2 .. --decode-ms D --chunk-ms C

replays ``simulate_closed_loop.py`` (no device: which lengths fall into the
window and nothing else) over this cell's stream for each ``order_seed``
named, at the tick and chunk times a traced run read: one JSON line an order,
then their median and the order whose rate lies nearest it, which the traffic
file pins.
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

WORKLOAD = "longcat-l4-serve-rollout-skewed"
FAULTS = ("zero_experts_left_out", "shortcut_lands_a_half_early",
          "s_kv_left_out", "bias_in_the_weights", "weights_renormalised",
          "bf16_softmax_scores")
# the faults that are another configuration of the model traced
AS_CONFIGURED = {"s_kv_left_out": {"mla_scale_kv_lora": False},
                 "weights_renormalised": {"norm_topk_prob": True}}


@contextlib.contextmanager
def planted(fault: str):
    """While open, a model traced anew computes with ``fault`` planted in
    the seams of ``models/gpt/mixed_stack.py`` or ``parallel/moe_share.py``
    (the faults of ``AS_CONFIGURED`` are the caller's: :func:`faulty`)."""
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt import mixed_stack
    from fleetx_tpu.parallel import moe_share

    scored = moe_share._scored
    module, changed = {
        "zero_experts_left_out": (moe_share, {
            "_zero_experts": lambda tokens, weights, zero: jnp.zeros(
                tokens.shape, jnp.float32)}),
        "shortcut_lands_a_half_early": (mixed_stack, {
            "_landing": lambda leaving, shortcut: jnp.where(
                leaving, shortcut, jnp.zeros_like(shortcut))}),
        "bias_in_the_weights": (moe_share, {
            "_weighed": lambda scores, ranked: ranked}),
        "bf16_softmax_scores": (moe_share, {
            "_scored": lambda logits, gate: jax.nn.softmax(
                logits.astype(jnp.bfloat16), axis=-1).astype(jnp.float32)
            if gate == "softmax_bias_topk" else scored(logits, gate)}),
    }.get(fault, (None, {}))
    real = {name: getattr(module, name) for name in changed}
    for name, value in changed.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in real.items():
            setattr(module, name, value)


def faulty(model, fault: str):
    """The model ``Served`` traces for ``fault``: the engine's own, or for a
    fault of ``AS_CONFIGURED`` its clone under the other configuration."""
    over = AS_CONFIGURED.get(fault)
    return model.clone(cfg=dataclasses.replace(model.cfg, **over)) \
        if over else model.clone()


def readings(cell, driver, seed: int, only=None):
    """``(name, reference_check's dict)`` for every reading (``only``: for
    those named; ``as_built`` always comes first)."""
    model, variables = driver.ref_driver.build_model(cell, seed)
    engine = driver.build_engine(cell, model, variables)
    try:
        for name in ("as_built",) + FAULTS:
            if only and name != "as_built" and name not in only:
                continue
            with planted(name):  # ``Served``'s programs are traced in here
                served = driver.Served(
                    engine, None if name == "as_built"
                    else faulty(engine.model, name))
                yield name, driver.reference_check(
                    engine, variables, cell, seed, served)
            del served
            gc.collect()
    finally:
        del engine, model, variables
        gc.collect()


def order_rates(cell, orders, decode_ms: float, chunk_ms: float,
                seconds: float = 40.0) -> list:
    """``simulate_closed_loop.simulate`` of this cell's stream for every
    ``order_seed`` of ``orders``: its result dicts, ``order`` added."""
    from perfbench import simulate_closed_loop as simulator
    from perfbench.drivers import rollout_stream

    theirs = simulator.traffic_gen
    simulator.traffic_gen = types.SimpleNamespace(
        client_stream=rollout_stream.client_stream)
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
    parser.add_argument("--orders", type=int, nargs="*", default=[])
    parser.add_argument("--decode-ms", type=float, default=12.0)
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
        for name, out in readings(cell, driver, seed, args.only):
            wrong += out["reference_ok"] != (name == "as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
