"""Does the reference check of a serving cell refuse the next precision
down? The cell's engine is built three times on the weights of one seed and
put through the cell's driver's ``reference_check`` (the reference always
reads the weights as made):

- ``as_built``: the system as the cell runs it; must read ``reference_ok``;
- ``int8_experts``: the expert matrices the engine holds rounded to 8 bits
  with one scale per output column (what weight-only int8 keeps; handed
  over in the weights' own type again); must read NOT ok;
- ``bf16_router``: the router's product and softmax input in bfloat16;
  must read NOT ok.

    python3 perfbench/probe_precision.py --workload olmoe-l8-serve-gen-batch \\
        --seeds 7 8 9 [--tiny]

One JSON line per engine and seed; exit 1 if any reading is on the wrong
side. The limits in the driver are set between these readings (PERF.md).
A second copy of the experts sits beside the weights, so the engine here
has 4 lanes and a pool to match: the check runs one lane.
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


def int8_experts(variables):
    """``variables`` with every expert matrix (a leaf under ``moe_mlp``
    with an expert axis) rounded to int8, one scale per output column, and
    cast back: the values an int8 engine would multiply by."""
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
        and "moe_mlp" in jax.tree_util.keystr(path) else x, variables)


def router_dense_in_bfloat16(real):
    """``real`` (flax's ``DenseGeneral``) with the module named ``router``
    computed in bfloat16 at default precision."""
    import jax.numpy as jnp

    def dense(*args, **kw):
        if kw.get("name") == "router":
            kw.update(dtype=jnp.bfloat16, precision=None)
        return real(*args, **kw)

    return dense


@contextlib.contextmanager
def router_in_bfloat16():
    """While open, a model traced anew computes its router in bfloat16."""
    from fleetx_tpu.parallel import moe

    real = moe.nn.DenseGeneral
    moe.nn.DenseGeneral = router_dense_in_bfloat16(real)
    try:
        yield
    finally:
        moe.nn.DenseGeneral = real


def readings(cell, driver, seed: int):
    """``(name, reference_check's dict)`` for the three engines."""
    model, variables = driver.build_model(cell, seed)

    def check(model, held):
        engine = serving.build_engine(cell, model, held)
        try:
            return driver.reference_check(engine, variables, cell, seed)
        finally:
            del engine
            gc.collect()

    yield "as_built", check(model, variables)
    yield "int8_experts", check(model, int8_experts(variables))
    with router_in_bfloat16():
        yield "bf16_router", check(model.clone(), variables)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    cell = harness.load_cell(args.workload, tiny=args.tiny)
    cell.deploy.update(lanes=4, pool_tokens=4 * cell.deploy["cache_len"])
    harness.own_the_chip(cell.chips, cell.tiny)

    import importlib

    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    driver = importlib.import_module(
        "perfbench.drivers." + cell.traffic["driver"])
    wrong = 0
    for seed in args.seeds:
        for name, out in readings(cell, driver, seed):
            wrong += out["reference_ok"] != (name == "as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
