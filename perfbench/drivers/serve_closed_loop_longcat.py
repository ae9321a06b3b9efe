"""Serving, closed loop, for a configuration whose double layers are
SHORTCUT-CONNECTED (LongCat-Flash: two latent attentions, two dense MLPs and
one expert layer that leaves at the first half and lands after the second),
whose router is a softmax over routed AND zero-compute experts, and whose
clients send short prompts skewed by topic and read long answers:
``serve_closed_loop_mla.py``'s loop, set-up, ``Served`` (chunk programs of
the check's own and a step SHAPED AS THE ENGINE'S TICK, every lane in order)
and engine check AS THEY ARE (imported; that file is not edited), with

- an engine of its own (:func:`build_engine`: the prefix cache OFF, no
  request shares a prefix), a warm-up of its own (:func:`warm_up`: the
  buckets a prompt of THIS traffic can reach) and the stream
  ``rollout_stream.client_stream``;
- a reference check of its own (:func:`reference_check`), because the check
  is COLD (no trie: nothing to hit) and the expert layer has a part the MLA
  driver's ``layer_check`` does not know: a prompt of ``CHECK_PROMPT`` tokens
  is prefilled in chunk programs (256 rows, then whole chunks that read the
  latents of those before them back from the pool) and decoded ``CHECK_DECODE`` steps
  through the cache; compared with ``perfbench/reference/longcat_f32.py``'s
  full forward of the same tokens: the logits at the prompt's last
  ``CHECK_OWN`` positions and at every decode step; what every attention
  HALF cached there (``c_kv`` as scaled and ``k_r`` apart); and every
  expert layer ON THE INPUT IT REALLY SAW (:func:`layer_check`): the
  weights it applied against the reference router's raw scores times the
  scaling for the same experts, whether each expert it chose is among the
  12 the reference ranks highest UNDER THE BIAS, and its output against the
  reference's sum over the held ones of the same experts plus its chosen
  zero-compute experts' weights times the input.

The limits: two readings each on the chip at the published widths (my chip
runs, PR 53; ``perfbench/probe_longcat.py`` takes both; PERF.md section 6).
"""

from __future__ import annotations

import contextlib
import types

import numpy as np

from perfbench import harness, traffic as traffic_gen
from perfbench.drivers import rollout_stream
from perfbench.drivers import serve_closed_loop_lfm2 as lfm2_driver
from perfbench.drivers import serve_closed_loop_mla as mla_driver
from perfbench.drivers import serve_closed_loop_ref as ref_driver
from perfbench.drivers.serve_closed_loop_mla import (  # noqa: F401
    ENGINE_ROWS_TOL,
    ENGINE_TOKEN_TOL,
    Served,
    _rms,
    engine_check,
)

CHECK_PROMPT, CHECK_OWN, CHECK_DECODE = 2304, 192, 32

# Limits from two readings each on the chip at the published widths (my chip
# runs, PR 53, ``chiprun_out/pr53``: the cell's own check on ten seeds,
# 5300000101-102 and 5300000201-207, and ``perfbench/probe_longcat.py`` on
# seeds 7 and 8; PERF.md section 6): the
# largest reading of the engine as built over its seeds, and the smallest
# reading of what has to come out NOT correct. A fault is refused by one of
# the limits and not by each. Logit errors are in units of the reference's
# logit deviation (1.57). The reference sums over the SYSTEM'S experts at the
# positions compared (``given``): the twelfth and thirteenth of 768 softmax
# scores lie a rounding apart, so the bfloat16 of the layers before hands an
# expert over at 171 of 224 positions.
# - ``REFERENCE_RMS_TOL``, the logits at the prompt's last 192 positions and
#   through 32 ticks, over all of them and over either part alone: as built
#   0.0366-0.0372 (bfloat16 weights and activations through EIGHT attentions,
#   eight dense MLPs and four expert layers, the latents scaled by 3.46 and
#   the queries by 2, so the softmax is seven times sharper than A.X-K1's,
#   whose six layers read 0.0162); the zero-compute experts left out 0.250-
#   0.267, the shortcut landing a half early 0.310-0.325, weights
#   renormalised 0.485-0.502, ``s_kv`` left out 1.33. The limit is 0.099, the
#   geometric middle of 0.0372 and 0.264. (The bias in the weights and a
#   bfloat16 softmax read 0.0366-0.0372, as built: the weight limit's.)
# - ``REFERENCE_MAX_TOL``, the largest error: as built 0.195-0.215; the
#   smallest fault that moves it 2.20. The limit is 0.69, the geometric
#   middle (as built 0.193-0.216 over the twelve readings).
# - ``REFERENCE_ROWS_TOL``, what every attention half cached at the
#   positions compared against what the reference's halves would, rms of the
#   difference over the rms of the rows, ``c_kv`` (as scaled) and ``k_r``
#   apart, the worst half: as built 0.0344-0.0351 for either; the
#   zero-compute experts left out 0.242-0.246, the shortcut a half early
#   0.303-0.309, ``s_kv`` left out 3.48 for ``c_kv`` (1.33 for ``k_r``). The
#   limit is 0.092, the geometric middle of 0.0351 and 0.242.
# - ``LAYER_WEIGHT_TOL``, the weights a layer applied against the reference
#   router's raw scores x 6 for the same experts, largest relative error: as
#   built 4.2e-7 to 4.8e-7 (both float32 at ``highest``); THE SOFTMAX IN
#   BFLOAT16 0.0190 / 0.0191 (the nearest precision below the float32 the
#   configuration states for it: refused by this limit, by the experts it
#   then chooses beside the reference's, 39 / 43 of 896 layer-positions, and
#   by the output's), the bias in the weights 0.125 / 0.141, renormalised 5.0.
#   The limit is 1e-4, the geometric middle of 4.8e-7 and 0.019. An expert it
#   chose counts as BESIDE the reference's when the reference ranks it, under
#   the bias, below its own twelfth by more than that limit of a mean score:
#   as built 0 of 896; none is allowed.
# - ``LAYER_OUTPUT_TOL``, a layer's output against the reference's sum over
#   the held ones of the same experts plus the chosen zero-compute experts'
#   weights times the input, rms over the layer's rms, the worst layer: as
#   built 0.00172-0.00182; the softmax in bfloat16 0.0051 / 0.0053, the bias
#   in the weights 0.0074 / 0.0076, the zero-compute experts left out 0.987,
#   renormalised 2.65. The limit is 0.0030, the geometric middle of 0.00182
#   and 0.0051.
# - the engine's own programs: A.X-K1's limits as they are
#   (``ENGINE_ROWS_TOL`` 0.19, ``ENGINE_TOKEN_TOL`` 0.27): as built 0.0264-
#   0.0274 (0 in the first half, growing a half) and 0.0052-0.0127.
REFERENCE_MAX_TOL = 0.69
REFERENCE_RMS_TOL = 0.099
REFERENCE_ROWS_TOL = 0.092
LAYER_WEIGHT_TOL = 1e-4
LAYER_OUTPUT_TOL = 0.0030


def check_sizes(cell) -> tuple:
    """``(prompt, own part, decode steps, engine tail)`` of the check: the
    constants above at the published sizes; a rehearsal's scale with its
    chunk (a prompt of a chunk and a quarter: two programs)."""
    if not cell.tiny:
        return CHECK_PROMPT, CHECK_OWN, CHECK_DECODE, mla_driver.ENGINE_TAIL
    chunk = cell.deploy["prefill_chunk"]
    return chunk + chunk // 4, chunk // 4, 4, 2


def build_engine(cell, model, variables):
    """``serve_closed_loop_mla.build_engine`` with the prefix cache OFF."""
    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.serving import ServingEngine

    deploy = cell.deploy
    page = deploy["page_size"]
    return ServingEngine(
        model, variables, slots=deploy["lanes"], cache_len=deploy["cache_len"],
        gen_cfg=GenerationConfig(
            decode_strategy="greedy", eos_token_id=-1, pad_token_id=0,
            max_length=traffic_gen.length_bounds(cell.traffic["output"])[1]),
        page_size=page, num_pages=deploy["pool_tokens"] // page + 1,
        prefill_chunk=deploy["prefill_chunk"],
        prefill_bucket=deploy["prefill_bucket"], prefix_cache=False)


def warm_up(engine, cell, seed: int) -> list:
    """One request for every prefill program a prompt can reach: the
    chunk-sized one and one for each bucket of a last chunk, as
    ``serve_closed_loop_swa.warm_up`` sends them (a chunk and a bucket
    together), or, where no prompt is longer than a chunk, a bucket alone;
    each followed by decode ticks. Returns the bucket lengths."""
    vocab = cell.config["model"]["vocab_size"]
    chunk, step = engine.prefill_chunk, engine.prefill_bucket
    longest = traffic_gen.length_bounds(cell.traffic["prompt"])[1]
    buckets = sorted({min(-(-n // step) * step, chunk)
                      for n in range(1, min(chunk, longest) + 1)})
    rng = np.random.default_rng([seed, 3])
    for bucket in buckets:
        engine.submit(rng.integers(
            1, vocab, bucket + (chunk if longest > chunk else 0),
            dtype=np.int32), max_length=2)
        engine.drain()
    return buckets


def layer_check(mine: dict, variables, cell, chosen) -> dict:
    """Every expert layer of the engine's model against the reference's
    layer ON THE INPUT THE SYSTEM'S LAYER REALLY SAW (module docstring).
    ``chosen`` is the reference's choice in its OWN forward: where the
    system's differs, the rounding of the layers before has moved the input
    (counted, not judged)."""
    model = cell.config["model"]
    picked = mine["experts"].astype(np.int32)
    sums, scores, ranked = (np.asarray(x) for x in ref_driver.reference_module(
        cell).configured_layers(model)(
            variables["params"], mine["input"], picked))
    k = picked.shape[-1]
    theirs = np.take_along_axis(scores, picked, -1)   # [layers, positions, k]
    weights = theirs * float(model.get("routed_scaling_factor", 1.0))
    if model.get("norm_topk_prob"):
        weights = weights / (theirs.sum(-1, keepdims=True) + 1e-20)
    weight_err = float(np.abs(mine["weights"] / weights - 1.0).max())
    # beside: ranked UNDER THE BIAS below the reference's k-th by more than
    # the weight limit of a score (the bias may make a rank negative, so the
    # margin is absolute, in the scores' unit)
    kth = np.sort(ranked, -1)[..., -k][..., None]
    margin = LAYER_WEIGHT_TOL * np.abs(scores).mean()
    beside = (np.take_along_axis(ranked, picked, -1) < kth - margin).any(-1)
    err = np.sqrt(((mine["output"] - sums) ** 2).mean((1, 2)))
    unit = np.sqrt((sums ** 2).mean((1, 2)))         # per layer
    same = (np.sort(picked, -1) == np.sort(chosen, -1)).all(-1)
    first, held = int(model.get("first_expert_held", 0)), model["num_experts"]
    zero = picked >= int(model["num_routed_experts"])
    out = {"layer_positions_checked": int(beside.size),
           "layer_weight_max_rel_err": weight_err,
           "layer_experts_beside_reference": int(beside.sum()),
           "layer_output_rel_rms_err": float((err / unit).max()),
           "layer_output_rel_rms_err_by_layer": [
               float(e) for e in err / unit],
           "layer_tol": [LAYER_WEIGHT_TOL, LAYER_OUTPUT_TOL],
           "layer_pairs_here_share": float(
               ((picked >= first) & (picked < first + held)).mean()),
           "layer_zero_pairs_share": float(zero.mean()),
           "layer_routed_pairs_a_token_min_max": [
               int((~zero).sum(-1).min()), int((~zero).sum(-1).max())],
           "experts_positions_checked": int(same.shape[1]),
           "experts_differ_positions": int((~same.all(0)).sum()),
           "experts_differ_layer_positions": int((~same).sum())}
    out["layers_ok"] = bool(
        weight_err <= LAYER_WEIGHT_TOL and not beside.any()
        and out["layer_output_rel_rms_err"] <= LAYER_OUTPUT_TOL)
    return out


def reference_check(engine, variables, cell, seed: int,
                    served: Served = None) -> dict:
    """The engine against the configuration's float32 reference, which reads
    the weights as made (``variables``), outside the window: module
    docstring, ``correct``."""
    served = served or Served(engine)
    logits = ref_driver.reference_module(cell).configured(
        cell.config["model"])           # a part a program: not to be jitted
    prompt, own, decode, _ = check_sizes(cell)
    vocab = cell.config["model"]["vocab_size"]
    tokens = np.random.default_rng([seed, 4]).integers(
        1, vocab, prompt + decode, dtype=np.int32)
    mine = served.sequence(tokens, prompt, own)
    engine.cache_manager.pool.check_invariants()
    # the reference sums over the SYSTEM'S experts at the positions compared
    # (``layer_check`` holds that choice to the router's); the system's
    # logits at position i predict token i + 1
    reference, chosen, _, latents = (np.asarray(x) for x in logits(
        variables["params"], tokens, tail=own + decode, with_experts=True,
        with_latents=True, given=mine["experts"].astype(np.int32)))
    unit = float(reference.std())
    err = np.abs(mine["logits"] - reference)
    # the compressed vector and the rotary key apart (the key is 64 of 576
    # columns: a joint rms would hide it), the worst half
    c = engine.model.cfg.kv_lora_rank
    ckv_err, kr_err = (float(lfm2_driver._rel_rms(
        latents[..., part], mine["rows"][..., part], (1, 2)).max())
        for part in (slice(None, c), slice(c, None)))
    out = {"reference_logit_std": unit,
           "reference_positions_checked": int(err.shape[0]),
           "cold_matched_tokens": mine["matched"],
           "reference_max_abs_err": float(err.max()),
           "reference_rms_err": _rms(err),
           "reference_prefill_rms_err": _rms(err[:own]),
           "reference_decode_rms_err": _rms(err[own:]),
           "reference_ckv_rel_rms_err": ckv_err,
           "reference_kr_rel_rms_err": kr_err,
           "reference_tol_in_std": [REFERENCE_MAX_TOL, REFERENCE_RMS_TOL],
           "reference_rows_tol": REFERENCE_ROWS_TOL}
    positions = mine["experts"].shape[1]
    layers = layer_check(mine, variables, cell, chosen[:, -positions:])
    out.update(layers)
    out["reference_ok"] = bool(
        layers["layers_ok"] and mine["matched"] == 0
        and max(ckv_err, kr_err) <= REFERENCE_ROWS_TOL
        and out["reference_max_abs_err"] <= REFERENCE_MAX_TOL * unit
        and max(out["reference_rms_err"], out["reference_prefill_rms_err"],
                out["reference_decode_rms_err"]) <= REFERENCE_RMS_TOL * unit)
    return out


@contextlib.contextmanager
def in_the_mla_drivers_place():
    """While open, ``serve_closed_loop_mla``'s ``run`` and ``engine_check``
    (which name their module's own) find this file's engine, warm-up, check
    and sizes, and this cell's stream in ``docqa_stream``'s place."""
    mine = {"build_engine": build_engine, "check_sizes": check_sizes,
            "reference_check": reference_check,
            "docqa_stream": rollout_stream,
            "swa_driver": types.SimpleNamespace(warm_up=warm_up)}
    theirs = {name: getattr(mla_driver, name) for name in mine}
    for name, value in mine.items():
        setattr(mla_driver, name, value)
    try:
        yield
    finally:
        for name, value in theirs.items():
            setattr(mla_driver, name, value)


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    """``serve_closed_loop_mla.run`` as it is (the loop, the set-up's order,
    the engine check after the window, ``correct`` with the latent kernel
    counted), with this file's parts in the place of its own."""
    with in_the_mla_drivers_place():
        out = mla_driver.run(cell, seed, seconds, trace, t_process)
    harness.log("zero-compute counters " + str({
        k: v for k, v in out.counters.items()
        if "zero" in k or "routed_pairs" in k}))
    return out
