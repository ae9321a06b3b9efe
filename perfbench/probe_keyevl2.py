"""The second reading of every limit of ``serve_closed_loop_vl.py``, at the
published widths on the chip (``--tiny``: the rehearsal's sizes anywhere),
as ``probe_dsv32.py`` takes DeepSeek-V3.2's. Two kinds of fault:

- in the programs of the check's own (``Served``: traced anew inside
  :func:`planted`), read by the cell's reference check. The engine's own
  programs are traced as built (they register the document):

      python3 perfbench/probe_keyevl2.py --seeds 7 [--only bf16_index ...]

  prints one JSON line a reading: ``as_built`` first, then every fault of
  :data:`FAULTS` (each must come out ``reference_ok: false``; the first is
  the nearest precision below the one the configuration states) and every
  reading of :data:`READINGS` (printed, on either side of the limits).
- in what the ENGINE'S OWN tick and chunk programs are handed, which
  ``Served`` never runs (it passes positions and the stage directly): the
  lane install's eleventh int, the three position rows a chunk parses, the
  place the tower's rows wait at (:func:`engine_planted`), read by the
  cell's ``engine_check`` itself:

      python3 perfbench/probe_keyevl2.py --seeds 7 --engine

  prints the reference check as built (``Served`` held to the reference),
  then ``engine_check`` as built and under every fault of
  :data:`ENGINE_FAULTS` (each must come out ``engine_ok: false``).

- ``--long``: what the fixed session (6,144 rows: 7 key blocks) does not
  reach, read once at the published widths: a 512-row chunk's selection by
  threshold over ``[512, 33,792]`` scores against ``lax.top_k``'s sets, and
  the kernel ``fleetx_gqa_sparse_prefill`` against its plain twin
  (``hybrid.grouped_attention`` under the same mask) with the chunk's last
  row in key block 8, 16 and 33 (:func:`long_readings`). No limit reads it.

Exit code 1 where a fault passes or the engine as built does not.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench import harness  # noqa: E402

WORKLOAD = "keyevl2-l6-serve-pagesqa-sparse"
# the nearest precision below the float32 the configuration states for the
# router comes first: ``LAYER_WEIGHT_TOL`` is what refuses it
FAULTS = ("bf16_router", "relu_left_out", "head_weights_left_out",
          "index_key_unrotated", "one_axis_positions",
          "position_table_left_out")
# (the indexer's products accumulated in bfloat16: inside every limit on the
# chip, PERF.md section 6)
READINGS = ("bf16_index",)
# what the engine's own programs are handed (``engine_check``'s to refuse)
ENGINE_FAULTS = ("rope_delta_not_installed", "chunk_positions_of_one_axis",
                 "stage_a_page_off")


@contextlib.contextmanager
def planted(fault: str):
    """While open, a model traced anew computes with ``fault`` planted in
    the seams of ``models/gpt/hybrid.py``, ``indexer.py``, ``block_fields.py``
    or ``models/vision/vit.py``."""
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt import hybrid, indexer, mixed_stack
    from fleetx_tpu.models.vision import vit

    def one_axis(rope, section):  # every pair from the time axis
        return tuple(t[0] if t.ndim == 4 else t for t in rope)

    if fault == "bf16_router":
        from perfbench import probe_precision

        with probe_precision.router_in_bfloat16():
            yield
        return
    module, changed = {
        "relu_left_out": (indexer, {"_index_act": lambda dots: dots}),
        "head_weights_left_out": (indexer, {
            "_index_head_weights": lambda w: jnp.ones_like(w)}),
        "index_key_unrotated": (hybrid, {
            "_rotated_index_key": lambda ki, rope: ki}),
        "one_axis_positions": (mixed_stack, {"fold_mrope": one_axis}),
        "position_table_left_out": (vit, {
            "interpolated": lambda table, r, c, rows, cols: jnp.zeros(
                (r.shape[0], table.shape[-1]), jnp.float32)}),
        "bf16_index": (indexer, {"_INDEX_TYPE": jnp.bfloat16}),
    }[fault]
    real = {name: getattr(module, name) for name in changed}
    for name, value in changed.items():
        setattr(module, name, value)
    try:
        yield
    finally:
        for name, value in real.items():
            setattr(module, name, value)


@contextlib.contextmanager
def engine_planted(engine, fault: str):
    """While open, ``engine``'s OWN programs are handed what ``fault`` says,
    planted on the host's side of the operand (the engine's code and its
    compiled programs are as built; ``Served`` is out of reach: each hook
    sits on a method the engine alone calls):

    - ``rope_delta_not_installed``: the lane install packs 0 for its
      eleventh int, so a tick's position is its row;
    - ``chunk_positions_of_one_axis``: the three position rows a chunk
      program parses hold the row's index, three times;
    - ``stage_a_page_off``: the chunk program slices the stage a page
      before the place the tower's rows wait at (it is handed the stage
      rolled by a page: staging the rows elsewhere would leave an earlier
      request's rows of the same document where the slice falls)."""
    import jax.numpy as jnp

    from fleetx_tpu.serving import rows_in

    page = engine.page_size
    install, args, guarded = (engine._install_lane, engine._prefill_args,
                              engine._guarded_prefill)

    def install_without(req, **kw):
        kept, req.rope_delta = req.rope_delta, 0
        try:
            return install(req, **kw)
        finally:
            req.rope_delta = kept

    def args_of_one_axis(*a, **kw):
        real = rows_in.row_positions
        rows_in.row_positions = lambda req, start, n: np.broadcast_to(
            start + np.arange(n), (3, n))
        try:
            return args(*a, **kw)
        finally:
            rows_in.row_positions = real

    def prefill_a_page_off(req, fn, operands, **kw):
        return guarded(req, fn, operands[:-1] + (
            jnp.roll(operands[-1], page, axis=0),), **kw)

    name, hook = {
        "rope_delta_not_installed": ("_install_lane", install_without),
        "chunk_positions_of_one_axis": ("_prefill_args", args_of_one_axis),
        "stage_a_page_off": ("_guarded_prefill", prefill_a_page_off),
    }[fault]
    setattr(engine, name, hook)
    try:
        yield
    finally:
        delattr(engine, name)


def engine_readings(cell, driver, seed: int, only=None):
    """``(name, dict)``: the reference check as built (it holds ``Served``,
    what ``engine_check`` compares with, to the reference, and gives the
    logits' unit), then ``engine_check`` as built and under every fault of
    :data:`ENGINE_FAULTS` (``only``: those named)."""
    model, variables = driver.build_model(cell, seed)
    engine = driver.build_engine(cell, model, variables)
    try:
        served = driver.Served(engine)
        reference = driver.reference_check(engine, variables, cell, seed,
                                           served)
        yield "reference_as_built", reference
        unit = reference["reference_logit_std"]
        for name in ("as_built",) + ENGINE_FAULTS:
            if only and name != "as_built" and name not in only:
                continue
            context = (contextlib.nullcontext() if name == "as_built"
                       else engine_planted(engine, name))
            with context:
                yield name, driver.engine_check(engine, served, unit, cell,
                                                seed)
    finally:
        del engine, model, variables
        gc.collect()


def long_readings(cell, seed: int, ends=(8192, 16384, 33792)):
    """One layer's chunk attention under the indexer on ONE lane of
    ``cache_len`` rows drawn from ``seed`` (queries and keys of unit rms a
    head, as the q/k norms leave them), the chunk ending at each of
    ``ends``: rows whose set by threshold is not ``lax.top_k``'s, and the
    kernel's output against its plain twin's."""
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt import hybrid, indexer
    from fleetx_tpu.ops.pallas import prefill_gqa

    model, deploy = cell.config["model"], cell.deploy
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d, top = model["head_size"], min(model["index_topk"],
                                     deploy["cache_len"])
    ni, di = model["index_n_heads"], model["index_head_dim"]
    s, t = deploy["prefill_chunk"], deploy["cache_len"]
    dtype = jnp.dtype(cell.config["compute_dtype"])
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)

    def drawn(key, *shape):
        return jax.random.normal(key, shape, jnp.float32)

    q = drawn(keys[0], s, heads, d).astype(dtype)
    k = drawn(keys[1], t, kv * d).astype(dtype)
    v = drawn(keys[2], t, kv * d).astype(dtype)
    qi = drawn(keys[3], s, ni, di).astype(dtype)
    ki = drawn(keys[4], t, di).astype(dtype)
    w = drawn(keys[5], s, ni) * (ni * di) ** -0.5

    @jax.jit
    def one(start):
        scores = indexer._chunk_index_scores(qi, w, ki, start)
        seen = (jnp.arange(t)[None, :] <= start + jnp.arange(s)[:, None])
        mask = indexer.select_rows(scores, seen, top)
        best = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), top)[1]
        theirs = jnp.zeros((s, t + 1), bool).at[
            jnp.arange(s)[:, None],
            jnp.where(jnp.arange(top)[None, :] < seen.sum(-1, keepdims=True),
                      best, t)].set(True)[:, :-1]
        got = prefill_gqa.gqa_sparse_prefill(q, k, v, mask, start)
        want = hybrid.grouped_attention(q[None], k[None], v[None],
                                        mask[None, None])[0]
        err = (got.astype(jnp.float32) - want.astype(jnp.float32)) ** 2
        return ((mask != theirs).any(-1).sum(), mask.sum(-1).min(),
                jnp.sqrt(err.mean() / (want.astype(jnp.float32) ** 2).mean()),
                jnp.sqrt(err.max()))

    for end in ends:
        differ, least, rel, worst = one(jnp.int32(end - s))
        yield {"chunk_ends_at": end,
               "key_blocks_walked": -(-end // prefill_gqa.BLOCK_ROWS),
               "rows_whose_set_is_not_top_k": int(differ),
               "least_rows_selected": int(least),
               "kernel_twin_rel_rms_err": float(rel),
               "kernel_twin_max_abs_err": float(worst)}


def readings(cell, driver, seed: int, only=None, faults=FAULTS + READINGS):
    """``(name, reference_check's dict)`` for every reading (``only``: for
    those named; ``as_built`` always comes first, so that the engine's own
    programs, which register the document, are traced without a fault). A
    fault in the tower is planted in the ENGINE'S tower programs too (the
    check takes those): they are minted anew for it."""
    model, variables = driver.build_model(cell, seed)
    engine = driver.build_engine(cell, model, variables)
    try:
        for name in ("as_built",) + tuple(faults):
            if only and name != "as_built" and name not in only:
                continue
            context = (contextlib.nullcontext() if name == "as_built"
                       else planted(name))
            with context:  # ``Served``'s programs are traced in here
                kept = dict(engine._tower._jits)
                if name == "position_table_left_out":
                    engine._tower._jits.clear()
                try:
                    yield name, driver.reference_check(
                        engine, variables, cell, seed, driver.Served(engine))
                finally:
                    engine._tower._jits.clear()
                    engine._tower._jits.update(kept)
    finally:
        del engine, model, variables
        gc.collect()


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--only", nargs="*", default=None)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--engine", action="store_true",
                        help="the faults of ENGINE_FAULTS through engine_check")
    parser.add_argument("--long", action="store_true",
                        help="a chunk's selection and kernel at long contexts")
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
        if args.long:
            for out in long_readings(cell, seed):
                print(json.dumps({"seed": seed, "long": True, **out}),
                      flush=True)
            continue
        if args.engine:
            for name, out in engine_readings(cell, driver, seed, args.only):
                ok = out["reference_ok" if name == "reference_as_built"
                         else "engine_ok"]
                wrong += ok != name.endswith("as_built")
                print(json.dumps({"seed": seed, "engine_check": name, **out}),
                      flush=True)
            continue
        for name, out in readings(cell, driver, seed, args.only):
            if name not in READINGS:
                wrong += out["reference_ok"] != (name == "as_built")
            print(json.dumps({"seed": seed, "engine": name, **out}),
                  flush=True)
        gc.collect()
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
