"""Serving, closed loop, for a configuration whose lanes keep their state in
TWO HOMES of different kinds at once (Ling-3.0-flash): a DELTA RULE'S matrix
state once a lane (six KDA layers: ``[heads, d, d]`` float32, 2 MB a layer
at the published widths) AND the latents of one latent attention (MLA) layer
in the page pool under the lane's pages (1,152 B of values a token), over an
expert layer that holds exactly ONE ROUTER GROUP. The loop, the engine, the
warm-up and ``Served`` are ``serve_closed_loop_kda.py``'s AS THEY ARE (so
every reader of a closed-loop cell reads the run), with

- a stream of its own (:func:`client_stream`: 15 requests in 16 reasoning
  tasks, 1 in 16 with a document; lengths from the traffic file's
  ``order_seed`` and the client, ids uniform over the slice from ``--seed``);
- the latent pool's rows where that file reads keys and values
  (:func:`lane_rows`: ``[c_kv | k_r]``, the zero columns the rotary key's
  leaf is padded with left out);
- the same check in the same two steps, judged by THIS file's limits.

Before the window (:func:`reference_check`): a prompt of ``CHECK_PROMPT``
tokens prefilled in chunk programs of the engine's own shapes and order
(four chunks of 512, then 192 tokens in a program of 256 rows, whose padded
rows must leave BOTH homes alone) and ``CHECK_DECODE`` ticks over EVERY lane
in order, against ``perfbench/reference/ling3_f32.py``'s full forward of the
same tokens from position 0: the logits at the last call's positions and at
every tick; what the latent layer cached there; ``S`` and the filter rows of
each KDA layer after the prefill and after the last tick; each expert layer
on the input it really saw; the delta rule alone on the float32 rows its
kernels were really handed (``rule_check``: where a lower precision of the
state OR OF THE DECAY shows, which the bfloat16 of seven layers hides from
every comparison above).

After the window (:func:`engine_check`): what the ENGINE'S OWN chunk and
tick programs left in lanes in flight (``S``, filter rows, latents, tokens)
against ``Served`` on the same sequences.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import types
from typing import Iterator

import numpy as np

from perfbench import harness
from perfbench.drivers import serve_closed_loop_kda as kda_driver
from perfbench.drivers import serve_closed_loop_longcat as longcat_driver
from perfbench.drivers import serve_closed_loop_mla as mla_driver
from perfbench.drivers import serve_closed_loop_ref as ref_driver
from perfbench.drivers import serve_closed_loop_ssm as ssm_driver
from perfbench.drivers.docqa_stream import _lengths
from perfbench.drivers.serve_closed_loop_kda import (
    Served,
    check_sizes,
    lane_state,
    rule_check,
)
from perfbench.drivers.serve_closed_loop_ssm import _rel_rms, _rms
from perfbench.drivers.serve_closed_loop_swa_share import LAYER_OUTPUT_TOL
from perfbench.traffic import Request

# How far the system may stand from the float32 reference. Limits from two
# readings each on the chip at the published widths (my chip runs, PR 64,
# ``chiprun_out/pr64``; PERF.md section 6; ``perfbench/probe_ling3.py`` takes
# both): the largest reading of the engine as built over its seeds (eight
# readings of part 1: the probe's seeds 7 and 8 and six runs of the cell,
# three at 128 lanes and three at 96; they agree to 2-4%), and the smallest
# reading of what has to come out NOT correct. A fault is refused by one of
# the limits and not by each. Logit errors are in units of the reference's
# logit deviation (1.011). The reference sums over the SYSTEM'S experts at
# every position (``given``).
#
# 1. Against the reference (``reference_check``), a prompt of 2,240 tokens in
# four chunks of 512 and 192 tokens in a program of 256 rows, then 32 ticks
# over every lane:
# - ``RULE_TOL``, what refuses A LOWER PRECISION OF THE STATE OR THE DECAY:
#   the delta rule alone on the float32 rows the kernels were really handed
#   (``kda_driver.rule_check``), ``o`` and the last ``S``, the worst layer,
#   both sides float32 on the same numbers: as built 2.98e-7 to 3.06e-7 and
#   3.45e-7 to 3.56e-7 (the order of two sums); THE LOG DECAY ROUNDED TO
#   BFLOAT16 where the kernels take it 8.97e-5 / 9.35e-5 and 1.11e-4 /
#   1.21e-4 (seeds 7 and 8), ``S`` ROUNDED TO BFLOAT16 wherever a kernel
#   hands it back 2.32e-3 / 2.31e-3 and 8.97e-3 / 8.94e-3: either passes
#   every limit below (logits 0.0291-0.0302, state 0.0442-0.0468: the
#   bfloat16 of seven layers hides both); also padded rows advancing the
#   state 0.084-0.221, the state zeroed 1.2-23. The limit is 5.6e-6, the
#   geometric middle of 3.56e-7 and 8.97e-5: 16 times each way.
# - ``REFERENCE_RMS_TOL``, the logits at the last call's 192 positions and
#   at the 32 ticks, over all of them and over either part alone: as built
#   0.0290-0.0300; THE HEAD-WISE GATE LEFT OUT 0.0715-0.0746 (one latent
#   layer of seven, its gate near a half at a seeded weight), padded rows
#   0.652-0.664 over the ticks, the decay's other form 1.28-1.30, the state
#   zeroed 1.23-1.43. The limit is 0.046, the geometric middle of 0.0300 and
#   0.0715: 1.5 times each way, of a reading that moved by 3% over eight.
# - ``REFERENCE_MAX_TOL``, the largest error over 4.4 million logits: as
#   built 0.153-0.167; the gate left out 0.389-0.424 (the rms limit's to
#   refuse), the smallest other fault 5.21. The limit is 0.93, the geometric
#   middle of 0.167 and 5.21.
# - ``REFERENCE_ROWS_TOL``, what the latent layer cached at those positions,
#   the whole row ``[c_kv | k_r]`` and the rotary key's 64 values alone, the
#   larger: as built 0.0198-0.0205 (the key alone 0.0194-0.0203); AN
#   UNNORMED ROTARY KEY 0.0924 / 0.0948 on the key alone and NOTHING ELSE
#   (logits 0.0289-0.0295: at the initializer's 0.02 over 2,560 inputs the
#   key's rms is 1.01 unnormed, so the norm moves a row by the spread of its
#   own rms, and the softmax barely notices); padded rows 0.226, zeroed 1.2.
#   The limit is 0.043, the geometric middle of 0.0205 and 0.0924: 2.1 times
#   each way.
# - ``REFERENCE_STATE_TOL`` and ``REFERENCE_CONV_TOL``, ``S`` and the filter
#   rows of each KDA layer after the prefill and after the last tick, the
#   worst layer (the sixth: 0.004 in the first, growing a layer): as built
#   0.0444-0.0460 and 0.0240-0.0254; the gate left out 0.101-0.103 and
#   0.0622-0.0636 (the two layers behind the latent one), padded rows 0.437
#   and 1.41, the other decay 3.6 and 1.27, zeroed 24 and 2.0. The limits are
#   0.068 and 0.040, the geometric middles: 1.5 and 1.6 times each way.
# - ``LAYER_WEIGHT_TOL``, the weights a share's layer applied against the
#   reference router's for the same experts on the input it really saw: as
#   built 2.98e-7 to 3.58e-7; THE ROUTER'S OUTPUTS ROUNDED TO BFLOAT16
#   1.09e-3, with 41-45 of 1,344 layer-positions choosing an expert beside
#   the reference's eight inside the groups that stay. The limit is 2e-5, the
#   geometric middle: 55 times each way; an expert beside the reference's by
#   more than that limit of a score: none is allowed.
# - ``LAYER_OUTPUT_TOL`` is ``serve_closed_loop_swa_share.py``'s (0.0041: the
#   same layer, whose second reading is that file's): as built here
#   0.00307-0.00309 on every seed.
#
# 2. The ENGINE'S OWN PROGRAMS (its chunks with the head on one row, its tick
# over every lane with one tick in flight) against the check's (``Served``,
# held to the reference by 1.) on 8 of the requests in flight when the window
# closes (``engine_check``); the two sides run the same arithmetic through
# programs of other shapes, so bfloat16 rounds otherwise and an expert
# changes hands at a near-tie, and the readings grow with the depth. The
# second reading is a fault planted in the engine's programs ALONE
# (``probe_ling3.py --engines``:
# 96 lanes, 96 + 8 x 96 tokens out, every lane decoding; as built there
# 0.073, 0.058, 0.038 and 0.020):
# - ``ENGINE_STATE_TOL``, the matrix state ``S`` a lane holds, the worst
#   layer (the sixth; 0.001 in the first): as built 0.073-0.104 over ten
#   readings (nine runs of the cell and the probe's); padded rows advancing
#   the state in the engine's last bucket 0.763, the state zeroed at every
#   call 1.00. The limit is 0.28, the geometric middle of 0.104 and 0.763:
#   2.7 times each way.
# - ``ENGINE_CONV_TOL``, the filter rows it holds: as built 0.057-0.091;
#   padded rows 0.553, zeroed 1.17. The limit is 0.22, the geometric middle
#   of 0.091 and 0.553.
# - ``ENGINE_ROWS_TOL``, the latents at its last 64 positions in the latent
#   layer (which stands behind four KDA layers): as built 0.033-0.044; padded
#   rows 0.513, zeroed 1.41. The limit is 0.15, the geometric middle of 0.044
#   and 0.513. AN UNNORMED ROTARY KEY planted in the engine's programs alone
#   reads 0.048 here (the key is 64 of a row's 576 values) and 0.076, 0.064,
#   0.024 on the other three: this part cannot tell it from as built, and
#   ``REFERENCE_ROWS_TOL`` on the key alone is what refuses it (the engine and
#   ``Served`` trace the same model code: a key unnormed in one is unnormed
#   in the other).
# - ``ENGINE_TOKEN_TOL``, how far the tokens it returned stand below
#   ``Served``'s best, rms in the logits' unit: as built 0.020-0.038 (9-16%
#   of the tokens are not ``Served``'s best, by at most 0.34: the best logit
#   leads the second by 0.16-0.19 at the median); padded rows 0.796, zeroed
#   4.31. The limit is 0.17, the geometric middle of 0.038 and 0.796: 4.5
#   times each way.
REFERENCE_MAX_TOL = 0.93
REFERENCE_RMS_TOL = 0.046
REFERENCE_ROWS_TOL = 0.043
REFERENCE_STATE_TOL = 0.068
REFERENCE_CONV_TOL = 0.040
RULE_TOL = 5.6e-6
LAYER_WEIGHT_TOL = 2e-5
ENGINE_STATE_TOL = 0.28
ENGINE_CONV_TOL = 0.22
ENGINE_ROWS_TOL = 0.15
ENGINE_TOKEN_TOL = 0.17


def carries_document(traffic: dict, client: int, index: int) -> bool:
    """Whether request ``index`` of ``client`` carries a document: one in
    ``document.every``, staggered over the clients so that every round of
    them holds its share, from an offset drawn from ``order_seed``."""
    every = int(traffic["document"]["every"])
    offset = int(np.random.default_rng(
        [int(traffic["order_seed"]), 2]).integers(every))
    return (client + index + offset) % every == 0


def client_stream(traffic: dict, seed: int, client: int,
                  vocab: int) -> Iterator[Request]:
    """The endless request sequence of one client: reasoning tasks, and one
    request in ``document.every`` with a document (:func:`carries_document`).
    The LENGTHS of either kind's prompts and of the outputs hold the
    quantiles of their distributions once in every block of ``block``, in an
    order drawn from the traffic file's ``order_seed`` and the client alone;
    the IDS are drawn uniformly from ``[1, vocab)`` by ``--seed`` and the
    client. No request shares a prefix."""
    block = int(traffic.get("block", 4))
    order = np.random.default_rng([int(traffic["order_seed"]), 1, client])
    ids = np.random.default_rng([seed, 1, client])
    prompts = _lengths(order, traffic["prompt"], block)
    documents = _lengths(order, traffic["document"]["prompt"], block)
    outputs = _lengths(order, traffic["output"], block)
    index = 0
    while True:
        document = carries_document(traffic, client, index)
        yield Request(index, 0.0, "document" if document else "reason",
                      ids.integers(1, vocab, int(next(
                          documents if document else prompts)),
                          dtype=np.int32), int(next(outputs)))
        index += 1


def lane_rows(engine, lane: int, lo: int, hi: int) -> np.ndarray:
    """The latents the engine's pool holds for ``lane`` at positions ``[lo,
    hi)`` of every LATENT layer, read through the manager's HOST table:
    ``[latent layers, 1, hi - lo, c_kv + k_r]`` float32 (the zero columns
    the rotary key's leaf is padded with left out)."""
    cfg, manager = engine.model.cfg, engine.cache_manager
    pos = np.arange(lo, hi)
    layers = cfg.layer_types.count("latent_attention")
    page = (manager.pool.tables[lane][pos // manager.page_size][None, :]
            + np.arange(layers)[:, None] * manager.num_pages)
    leaves = ssm_driver._leaves(engine)
    return np.concatenate([
        np.asarray(leaves[name][page, pos % manager.page_size],
                   np.float32)[..., :width]
        for name, width in (("cached_key", cfg.kv_lora_rank),
                            ("cached_value", cfg.qk_rope_head_dim))],
        axis=-1)[:, None]


@contextlib.contextmanager
def _latent_rows():
    """While open, the two files this one builds on read the LATENT pool
    where they read keys and values (``lane_rows``), and the delta rule's
    state as ``serve_closed_loop_kda.py`` lays it out."""
    def flat(engine, lane):  # (``ssm_driver`` takes [layers, rows, width])
        state, conv = lane_state(engine, lane)
        return state.reshape(len(state), -1, state.shape[-1]), conv

    held = (kda_driver.lane_rows, ssm_driver.lane_rows, ssm_driver.lane_state)
    kda_driver.lane_rows = ssm_driver.lane_rows = lane_rows
    ssm_driver.lane_state = flat
    try:
        yield
    finally:
        (kda_driver.lane_rows, ssm_driver.lane_rows,
         ssm_driver.lane_state) = held


def layer_check(mine: dict, variables, cell, chosen) -> dict:
    """``serve_closed_loop_mla.layer_check`` (the weights a share's layer
    applied against the reference router's for the same experts on the same
    input, every expert it chose among the reference's ``k`` highest of score
    + bias INSIDE THE GROUPS THAT STAY, its output against the reference's
    sum over the held ones of them plus the shared expert), judged by this
    file's limits."""
    out = mla_driver.layer_check(mine, variables, cell, chosen)
    out["layer_tol"] = [LAYER_WEIGHT_TOL, LAYER_OUTPUT_TOL]
    out["layers_ok"] = bool(
        out["layer_weight_max_rel_err"] <= LAYER_WEIGHT_TOL
        and not out["layer_experts_beside_reference"]
        and out["layer_output_rel_rms_err"] <= LAYER_OUTPUT_TOL)
    return out


def reference_check(engine, variables, cell, seed: int,
                    served: Served = None) -> dict:
    """The engine against the configuration's float32 reference, which reads
    the weights as made (``variables``), outside the window: module
    docstring, ``correct``."""
    import jax

    served = served or Served(engine)
    prompt, decode, _ = check_sizes(cell)
    tokens = np.random.default_rng([seed, 4]).integers(
        1, cell.config["model"]["vocab_size"], prompt + decode,
        dtype=np.int32)
    with _latent_rows():
        mine = served.sequence_parts(tokens, prompt)
    engine.cache_manager.pool.check_invariants()
    own = mine["own"]
    # the reference sums over the SYSTEM'S experts at EVERY position (two
    # ranks lie a rounding apart at many positions, and a matrix state
    # integrates every position before it; ``layer_check`` holds the choice
    # to the router's at the positions compared)
    theirs = jax.jit(
        ref_driver.reference_module(cell).configured(cell.config["model"]),
        static_argnames=("tail", "with_parts", "states_at"))(
        variables["params"], tokens, tail=own + decode, with_parts=True,
        given=mine["all_experts"], states_at=(prompt, prompt + decode))
    theirs = {k: np.asarray(v) for k, v in theirs.items()}
    reference = theirs["logits"]
    unit = float(reference.std())
    err = np.abs(mine["logits"] - reference)
    rows_err = _rel_rms(theirs["kv"], mine["kv"], (2, 3))     # [layers, 1]
    # the rotary key alone: 64 of a row's 576 values
    rope = engine.model.cfg.qk_rope_head_dim
    key_err = _rel_rms(theirs["kv"][..., -rope:], mine["kv"][..., -rope:],
                       (2, 3))
    state_err, conv_err = (np.stack([
        _rel_rms(theirs[name][:, i], mine[key][part], (1, 2, 3)[:n])
        for i, key in enumerate(("state_prefill", "state_end"))])
        for name, part, n in (("state", 0, 3), ("rows", 1, 2)))
    out = {"reference_logit_std": unit,
           "reference_positions_checked": int(err.shape[0]),
           "reference_max_abs_err": float(err.max()),
           "reference_rms_err": _rms(err),
           "reference_prefill_rms_err": _rms(err[:own]),
           "reference_decode_rms_err": _rms(err[own:]),
           "reference_rows_rel_rms_err": float(
               max(rows_err.max(), key_err.max())),
           "reference_rotary_key_rel_rms_err": float(key_err.max()),
           "reference_state_rel_rms_err": float(state_err.max()),
           "reference_state_rel_rms_err_by_layer": [
               float(e) for e in state_err.max(0)],
           "reference_conv_rel_rms_err": float(conv_err.max()),
           "reference_tol_in_std": [REFERENCE_MAX_TOL, REFERENCE_RMS_TOL],
           "reference_rows_state_conv_tol": [
               REFERENCE_ROWS_TOL, REFERENCE_STATE_TOL, REFERENCE_CONV_TOL]}
    positions = mine["experts"].shape[1]
    layers = layer_check(mine, variables, cell,
                         theirs["chosen"][:, -positions:])
    out.update(layers)
    rule = rule_check(mine, cell)
    rule.update(rule_tol=RULE_TOL, rule_ok=bool(max(
        rule["rule_output_rel_rms_err"],
        rule["rule_state_rel_rms_err"]) <= RULE_TOL))
    out.update(rule)
    out["reference_ok"] = bool(
        layers["layers_ok"] and out["rule_ok"]
        and out["reference_rows_rel_rms_err"] <= REFERENCE_ROWS_TOL
        and out["reference_state_rel_rms_err"] <= REFERENCE_STATE_TOL
        and out["reference_conv_rel_rms_err"] <= REFERENCE_CONV_TOL
        and out["reference_max_abs_err"] <= REFERENCE_MAX_TOL * unit
        and max(out["reference_rms_err"], out["reference_prefill_rms_err"],
                out["reference_decode_rms_err"]) <= REFERENCE_RMS_TOL * unit)
    return out


def engine_check(engine, served: Served, unit: float, tail: int) -> dict:
    """``serve_closed_loop_ssm.engine_check`` as it is (the state, the filter
    rows, the pool's rows and the tokens the ENGINE'S OWN programs left in
    lanes in flight against ``Served`` on the same sequences), reading this
    family's leaves (:func:`_latent_rows`) and judged by this file's limits
    (no layer's state is bit for bit the check's: the two sides' programs
    have other shapes; ``rule_check`` is what holds the state's precision)."""
    with _latent_rows():
        out = ssm_driver.engine_check(engine, served, unit, tail,
                                      engine.prefill_chunk)
    if "engine_state_max_rel_rms_err" not in out:
        return out                              # nothing in flight: not ok
    out.pop("engine_first_state_rel_rms_err")
    out["engine_tol"] = [ENGINE_STATE_TOL, ENGINE_CONV_TOL, ENGINE_ROWS_TOL,
                         ENGINE_TOKEN_TOL]
    out["engine_ok"] = bool(
        out["engine_tokens_served_checked"]
        and out["engine_state_max_rel_rms_err"] <= ENGINE_STATE_TOL
        and out["engine_conv_max_rel_rms_err"] <= ENGINE_CONV_TOL
        and out["engine_rows_max_rel_rms_err"] <= ENGINE_ROWS_TOL
        and out["engine_token_served_rms_deficit"] <= ENGINE_TOKEN_TOL * unit)
    return out


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    """``serve_closed_loop_ref.run`` with ``serve_closed_loop_kda.py``'s
    set-up, this file's stream and checks in the place of its own, then the
    engine check on what the window left in flight."""
    held = {}

    def set_up(cell, seed, t_process):
        device = harness.own_the_chip(cell.chips, cell.tiny)

        from fleetx_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        clock = harness.CompileClock()
        phases = {"import_s": time.perf_counter() - t_process}
        peak = {}  # the device's peak so far, after each phase of the set-up

        def done(phase):
            phases[phase + "_s"] = time.perf_counter() - t_process
            peak[phase] = harness.memory_peak_bytes(cell.chips) / 1e9

        model, variables = ref_driver.build_model(cell, seed)
        engine = longcat_driver.build_engine(cell, model, variables)
        done("weights_and_engine")
        buckets = longcat_driver.warm_up(engine, cell, seed)
        done("warm_up")
        served = Served(engine)
        reference = reference_check(engine, variables, cell, seed, served)
        done("reference")
        held.update(engine=engine, served=served, reference=reference,
                    peak=peak)
        return device, clock, engine, reference, buckets, phases

    # the loop reads ``clients`` at the traffic's top level and takes its
    # streams from ``traffic.client_stream``
    loop_cell = dataclasses.replace(cell, traffic={
        **cell.traffic, "clients": cell.traffic["closed_loop"]["clients"]})
    theirs = ref_driver.set_up, ref_driver.traffic_gen
    ref_driver.set_up = set_up
    ref_driver.traffic_gen = types.SimpleNamespace(client_stream=client_stream)
    try:
        out = ref_driver.run(loop_cell, seed, seconds, trace, t_process)
    finally:
        ref_driver.set_up, ref_driver.traffic_gen = theirs
    out.cell = cell
    harness.log("state and routing counters " + str({
        k: v for k, v in out.counters.items()
        if k.startswith(("state_", "kv_page_", "kda_", "latent_", "moe_"))}))
    engine, checks = held["engine"], out.checks
    checks["memory_peak_gb_after"] = dict(
        held["peak"], window=harness.memory_peak_bytes(cell.chips) / 1e9)
    # everything the window is read from is taken; now what the engine's
    # programs left in flight, which ends those requests
    checks.update(engine_check(
        engine, held["served"], held["reference"]["reference_logit_std"],
        check_sizes(cell)[2]))
    from fleetx_tpu.ops.pallas.kda import STEP_KERNEL_NAME
    from fleetx_tpu.ops.pallas.mla_decode import KERNEL_NAME

    text = engine.compiled_decode().as_text()
    checks["kda_step_mosaic_calls"] = harness.mosaic_calls(
        text, STEP_KERNEL_NAME)
    checks["mla_decode_mosaic_calls"] = harness.mosaic_calls(
        text, KERNEL_NAME)
    # (the loop's own ``correct`` asks for the grouped decode kernel, which
    # no layer of this stack runs: decided anew, as
    # ``serve_closed_loop_mla.py`` decides it, by BOTH homes' kernels)
    checks["mosaic_calls"] = min(checks["kda_step_mosaic_calls"],
                                 checks["mla_decode_mosaic_calls"])
    checks["correct"] = bool(
        not checks["wrong_results"] and not checks["refused"]
        and not checks["engine_recoveries"] and not checks["poison_retired"]
        and not any(checks["fault_events"].values())
        and (checks["mosaic_calls"] > 0 or cell.tiny)
        and checks["compiles_in_window"] == 0
        and checks["reference_ok"] and checks["engine_ok"])
    out.correct = checks["correct"]
    return out
