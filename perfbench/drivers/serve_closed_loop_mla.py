"""Serving, closed loop, for a configuration whose lanes keep LATENTS in the
page pool (latent attention: one compressed vector and one rotary key a
token and layer, no head axis) beside an expert layer that holds a share of
the routed experts, and whose clients ask several questions of one long
document: the loop of ``serve_closed_loop_ref.py`` AS IT IS (:func:`run`
calls it, with this file's set-up and ``docqa_stream.client_stream`` in the
place of its own), so the same ``harness.Run`` and ``samples`` keys and
every reader of a closed-loop cell reads it.

What differs is ``correct``, decided in two steps on what the timed path
produces at the timed sizes:

- before the window (:func:`reference_check`): a document of ``CHECK_DOC``
  tokens is registered in the trie BY THE ENGINE ITSELF (a request through
  ``submit``: its timed chunk programs write the latent pages); then a
  prompt of that document plus ``CHECK_OWN`` tokens is admitted ON THE HIT,
  so that its prefill starts behind the matched latent pages and
  re-expands them, and decoded for ``CHECK_DECODE`` steps; and again COLD
  (the trie off: four chunks, each reading the latents of those before it
  back from the pool). The programs are the check's own (:class:`Served`:
  the engine's return tokens only); its decode step is SHAPED AS THE
  ENGINE'S TICK, one row of every lane with the check's lane alone
  decoding, so the reference holds the absorbed form and the kernel
  ``fleetx_mla_decode_paged`` at the timed lane count. Compared: the
  logits at the prompt's last ``CHECK_OWN`` positions and at every decode
  step against the reference's full forward of the same tokens
  (``perfbench/reference/axk1_f32.py``: materialised, no cache, the same
  share); and every expert layer ON THE INPUT IT REALLY SAW
  (:func:`layer_check`): the experts it chose against the reference's
  group-limited choice, its weights, and its output against the reference's
  sum over the held ones of them plus the shared expert.
- after the window (:func:`engine_check`): what the ENGINE'S OWN tick and
  chunk programs left in the lanes in flight when the window closed, every
  lane live (the latents at a lane's last positions, written by the
  16-lane tick) and the tokens they returned, against ``Served`` on the
  same sequences from position 0.

The kernel the tick must hold is ``fleetx_mla_decode_paged``, where
``serving.serving_checks`` counts ``fleetx_decode_paged`` (which this model
has no use for): :func:`run` counts this one and decides ``correct`` anew
from the same parts.

From ``serve_closed_loop_ref.py`` as it is: ``run`` (the loop),
``build_model`` (which makes an older program say at once, before any
compile, that it cannot run the configuration) and ``reference_module``;
from ``serve_closed_loop_lfm2.py``: ``Served``'s chunk programs,
``trie_off``; from ``serve_closed_loop_swa.py``: ``warm_up``.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import types

import numpy as np

from perfbench import harness, serving, traffic as traffic_gen
from perfbench.drivers import docqa_stream
from perfbench.drivers import serve_closed_loop_lfm2 as lfm2_driver
from perfbench.drivers import serve_closed_loop_ref as ref_driver
from perfbench.drivers import serve_closed_loop_swa as swa_driver

CHECK_DOC, CHECK_OWN, CHECK_DECODE = 1536, 192, 32
ENGINE_LANES, ENGINE_TAIL = 4, 64

# How far the system may stand from the float32 reference. Limits from two
# readings each on the chip at the published widths (my chip runs, PR 40,
# ``chiprun_out/pr40``: twelve readings of the engine as built on twelve
# seeds, the probe's faults on seeds 7, 8 and 9; PERF.md section 6;
# ``perfbench/probe_axk1.py`` takes both): the largest reading of the engine
# as built over its seeds, and the smallest reading of what has to come out
# NOT correct. A fault is refused by one of the limits and not by each.
#
# 1. The logits of the whole model, on a trie hit (1,536 tokens matched) and
# cold, at the prompt's last 192 positions and through 32 ticks, in units of
# the standard deviation of the reference's logits (1.693). The reference
# sums over the SYSTEM'S experts at the positions compared (``given``): the
# eighth and ninth sigmoid scores of random weights lie a rounding apart, so
# the bfloat16 of the layers before hands an expert over at 116-130 of 224
# positions, and an expert exchanged moves the logits by more than any
# arithmetic does (0.9 of the unit at the first reading, taken without
# ``given``). The hit and the cold prefill read THE SAME to every digit.
# - ``REFERENCE_RMS_TOL``, over all positions and over the decode steps
#   alone: as built 0.0159-0.0162 on twelve seeds (bfloat16 weights and
#   activations through six layers; the readings agree to 1% because 4.6
#   million logits of iid weights average themselves); a CHUNK'S SCORES
#   ACCUMULATED IN BFLOAT16 0.0194 / 0.0197 (the nearest precision below the
#   float32 the configuration states for them; 0.0194 on a third seed); an unheld expert's pair
#   computed 0.216, the rotary key unrotated 0.60, the shared expert dropped
#   0.63, ``W_UV`` left out 1.41. The limit is 0.0178, the geometric middle of
#   0.0162 and 0.0194: 10% of room either way, ten times the spread of either
#   side's readings.
# - ``REFERENCE_MAX_TOL``, the largest error: as built 0.081-0.089; bfloat16
#   scores 0.108-0.116, an unheld pair 1.52, the shared expert 3.1, the key
#   6.7, ``W_UV`` 6.7. The limit is 0.30: 3.4 times the one, a fifth of the
#   smallest fault that is not a precision (the rms limit has the precision).
# - ``REFERENCE_ROWS_TOL``, the latents the lane's pages hold at the
#   positions compared against what the reference's layers would cache, rms
#   of the difference over the rms of the rows, the compressed vector and the
#   rotary key apart, the worst layer: as built 0.0153-0.0157 for either;
#   the key unrotated 1.38 (the logits miss it at small widths:
#   tests/perfbench/test_perfbench_axk1.py), ``W_UV`` left out 0.53, an
#   unheld pair 0.19 (bfloat16 scores 0.0186-0.0190: the rms limit's). The
#   limit is 0.05: 3.2 times the one, a quarter of the smallest fault.
# 2. Every expert layer on the input it really saw:
# - ``LAYER_WEIGHT_TOL``: the weights it applied against the reference
#   router's for the same experts: as built 2.98e-7 on every one of twelve
#   readings (both float32 at ``highest``); the router's product in
#   bfloat16 at default precision (``probe_precision.router_in_bfloat16``)
#   1.36e-5 / 1.55e-5 / 1.32e-5 (the chip's compiler keeps excess precision through
#   the planted converts: LFM2's router read 0.009 under the same plant).
#   The limit is 2e-6, the geometric middle: 6.7 times either way. An expert
#   it chose counts as BESIDE the reference's when the reference ranks it,
#   inside the groups that stay, under its own eighth by more than that
#   limit: as built 0 of 1,120 layer-positions; the group limit off 974 /
#   991 (a choice in a group that does not stay, where the reference's rank
#   is -inf), a bfloat16 router 1 / 1 / 0; none is allowed.
# - ``LAYER_OUTPUT_TOL``: its output against the reference's sum over the
#   held ones of the same experts plus the shared expert, rms over the
#   layer's rms, the worst layer: as built 0.00308-0.00311 (the accepted
#   expert cells read 0.0029: the same kernels); an unheld expert's pair
#   computed 0.313 / 0.323, the shared expert dropped 0.979. The limit is
#   0.012: 3.9 times the one, 26 times under the other.
# 3. The ENGINE'S OWN PROGRAMS against the check's, on 4 of the lanes in
# flight when the window closes (the two sides differ in the shapes of their
# programs, so where the router's scores tie they choose other experts and
# the rows after it move: the readings grow with the depth):
# - ``ENGINE_ROWS_TOL``, the latents at a lane's last 64 positions (written
#   by the timed 16-lane tick), rms of the difference over the rms of the
#   rows, the worst lane and layer: as built 0.023-0.037 on ten seeds (0 in
#   the first layer, 0.008 in the second); the TICK'S absorbed form without
#   ``W_UV`` 1.41, block tables that stopped following the allocator 0.99.
#   The limit is 0.19, the geometric middle.
# - ``ENGINE_TOKEN_TOL``, how far the tokens it returned stand below
#   ``Served``'s best, rms in the logits' unit: as built 0.003-0.022 (245-249
#   of 256 tokens are ``Served``'s best); stale tables 3.5, no ``W_UV`` 4.0.
#   The limit is 0.27, the geometric middle.
REFERENCE_MAX_TOL = 0.30
REFERENCE_RMS_TOL = 0.0178
REFERENCE_ROWS_TOL = 0.05
LAYER_WEIGHT_TOL = 2e-6
LAYER_OUTPUT_TOL = 0.012
ENGINE_ROWS_TOL = 0.19
ENGINE_TOKEN_TOL = 0.27


def check_sizes(cell) -> tuple:
    """``(document, own part, decode steps, engine tail)`` of the check: the
    constants above at the published sizes; a rehearsal's scale with its
    chunk."""
    if not cell.tiny:
        return CHECK_DOC, CHECK_OWN, CHECK_DECODE, ENGINE_TAIL
    chunk = cell.deploy["prefill_chunk"]
    return 2 * chunk, chunk // 2, 4, 2


def build_engine(cell, model, variables):
    """The engine as ``serving.build_engine`` builds it, with chunked
    prefill and the prefix cache ON."""
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
        prefill_bucket=deploy["prefill_bucket"], prefix_cache=True)


class Served(lfm2_driver.Served):
    """``serve_closed_loop_lfm2.Served`` (a chunk that only writes, a chunk
    that also gives the logits and routing of its last ``tail`` tokens; on
    ``engine.params``, in a lane of ``engine.cache_manager``, so that the
    trie matches, shares and registers latent pages exactly as for a
    request), with the decode step SHAPED AS THE ENGINE'S TICK: one row of
    EVERY lane in order, the check's lane the only one decoding, so it runs
    the absorbed form and the paged latent kernel at the timed lane count.
    ``model`` is the engine's unless a probe plants a fault."""

    def __init__(self, engine, model=None, params=None):
        import jax
        import jax.numpy as jnp

        super().__init__(engine, model, params)
        model = model or engine.model
        donate = (1,) if jax.default_backend() == "tpu" else ()

        @functools.partial(jax.jit, donate_argnums=donate)
        def tick(params, cache, token, at, lane, tables):
            # as ``ServingEngine._decode_fn`` hands the model a tick: a lane
            # that is not decoding writes at the lane's last row, which its
            # table sends to the trash page
            active = jnp.arange(tables.shape[0]) == lane
            logits, mut = model.apply(
                {"params": engine._dequant_params(params), "cache": cache},
                jnp.where(active, token, 0)[:, None],
                jnp.where(active, at, 0)[:, None], active[:, None],
                decode=True, block_tables=tables,
                cache_positions=jnp.where(active, at, engine.cache_len - 1),
                mutable=["cache", "routing"])
            # one leaf [expert layers, lanes, 1, width] of each name
            sown = {jax.tree_util.keystr(path[-2:-1]).strip("[']"):
                    jax.lax.dynamic_index_in_dim(leaf, lane, 1, False)
                    for path, leaf in jax.tree_util.tree_flatten_with_path(
                        mut["routing"])[0]}
            return mut["cache"], logits[lane].astype(jnp.float32), sown

        self._tick = tick

    def step(self, lane: int, token: int):
        import jax.numpy as jnp

        manager = self.engine.cache_manager
        if not manager.ensure_page(lane):
            raise RuntimeError("the pool ran dry in the check")
        # every other lane's table is zeroed for the step: the check runs
        # while no request is in flight, and a freed lane's table is zeros
        tables = np.zeros_like(manager.tables)
        tables[lane] = manager.tables[lane]
        manager.cache, logits, sown = self._tick(
            self.engine.params if self.params is None else self.params,
            manager.cache, jnp.asarray(token, jnp.int32),
            jnp.asarray(manager.lengths[lane], jnp.int32),
            jnp.asarray(lane, jnp.int32), jnp.asarray(tables))
        manager.lengths[lane] += 1
        return np.asarray(logits), sown

    def sequence(self, tokens, prompt_len: int, tail: int) -> dict:
        """``serve_closed_loop_lfm2.Served.sequence`` (the first
        ``prompt_len`` of ``tokens`` admitted, the trie matching what it
        holds of them, and prefilled from the match's end; the rest decoded a
        tick each), with ``rows``: the latents the lane's pages hold at the
        positions compared ``[layers, positions, c_kv + k_r]``, read before
        the lane is freed."""
        manager = self.engine.cache_manager
        cfg = self.engine.model.cfg
        lane, matched = manager.alloc(-1, tokens[:prompt_len])
        try:
            logits, sown = self.prefill(lane, tokens[:prompt_len], matched,
                                        tail)
            out = [np.asarray(logits)]
            routing = {k: [np.asarray(v, np.float32)] for k, v in sown.items()}
            for token in tokens[prompt_len:]:
                logits, sown = self.step(lane, int(token))
                out.append(logits)
                for k, v in sown.items():
                    routing[k].append(np.asarray(v, np.float32))
            compared = tail + len(tokens) - prompt_len
            rows = lane_rows(self.engine, lane, len(tokens) - compared,
                             len(tokens))
        finally:
            manager.free(lane)
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim   # (the leaf's padding)
        return {"matched": int(matched), "logits": np.concatenate(out),
                "rows": rows[..., :width],
                **{k: np.concatenate(v, axis=1) for k, v in routing.items()}}


def lane_rows(engine, lane: int, lo: int, hi: int) -> np.ndarray:
    """The latents the engine's pool holds for ``lane`` at positions ``[lo,
    hi)`` of every layer, read through the manager's HOST table: ``[layers,
    hi - lo, c_kv + k_r]`` float32."""
    cfg, manager = engine.model.cfg, engine.cache_manager
    pos = np.arange(lo, hi)
    page = (manager.lane_tables(lane)[pos // manager.page_size][None, :]
            + np.arange(cfg.num_layers)[:, None] * manager.num_pages)
    pools = lfm2_driver._pools(engine)
    return np.concatenate([
        np.asarray(pools[name][page, pos % manager.page_size], np.float32)
        for name in ("cached_key", "cached_value")], axis=-1)


def _rms(x) -> float:
    return float(np.sqrt((np.asarray(x, np.float64) ** 2).mean()))


def layer_check(mine: dict, variables, cell, chosen) -> dict:
    """Every expert layer of the engine's model against the reference's
    layer ON THE INPUT THE SYSTEM'S LAYER REALLY SAW: the weights it applied
    against the reference router's for the same experts on the same input;
    whether each expert it chose is among the ``k`` the reference ranks
    highest INSIDE THE GROUPS THAT STAY (a tie inside the weight limit is no
    fault); and its output against the reference's sum over the held ones
    of the same experts plus the shared expert. ``chosen`` is the
    reference's choice in its OWN forward: where the system's differs, the
    rounding of the layers before has moved the input (counted, not
    judged)."""
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
    kth = np.sort(ranked, -1)[..., -k][..., None]
    beside = (np.take_along_axis(ranked, picked, -1)
              < kth * (1.0 - LAYER_WEIGHT_TOL)).any(-1)
    err = np.sqrt(((mine["output"] - sums) ** 2).mean((1, 2)))
    unit = np.sqrt((sums ** 2).mean((1, 2)))         # per layer
    same = (np.sort(picked, -1) == np.sort(chosen, -1)).all(-1)
    first, held = int(model.get("first_expert_held", 0)), model["num_experts"]
    out = {"layer_positions_checked": int(beside.size),
           "layer_weight_max_rel_err": weight_err,
           "layer_experts_beside_reference": int(beside.sum()),
           "layer_output_rel_rms_err": float((err / unit).max()),
           "layer_tol": [LAYER_WEIGHT_TOL, LAYER_OUTPUT_TOL],
           "layer_pairs_here_share": float(
               ((picked >= first) & (picked < first + held)).mean()),
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
        cell.config["model"])           # a layer a program: not to be jitted
    doc, own, decode, _ = check_sizes(cell)
    vocab = cell.config["model"]["vocab_size"]
    rng = np.random.default_rng([seed, 4])
    tokens = rng.integers(1, vocab, doc + own + decode, dtype=np.int32)
    # another question registers the document, through the engine itself
    other = np.concatenate([tokens[:doc], rng.integers(
        1, vocab, engine.page_size * 2, dtype=np.int32)])
    engine.submit(other, max_length=2)
    engine.drain()

    def compared(mine):
        # the reference sums over the SYSTEM'S experts at the positions
        # compared (``layer_check`` holds that choice to the router's); the
        # system's logits at position i predict token i + 1: the own part's
        # positions and the decode steps are the sequence's last
        reference, chosen, _, latents = logits(
            variables["params"], tokens, tail=own + decode,
            with_experts=True, with_latents=True,
            given=mine["experts"].astype(np.int32))
        return np.asarray(reference), np.asarray(chosen), np.asarray(latents)

    def latent_err(mine, theirs):
        # the compressed vector and the rotary key apart (the key is 64 of
        # 576 columns: a joint rms would hide it), the worst layer
        c = engine.model.cfg.kv_lora_rank
        return [float(lfm2_driver._rel_rms(
            theirs[..., part], mine["rows"][..., part], (1, 2)).max())
            for part in (slice(None, c), slice(c, None))]

    hit = served.sequence(tokens, doc + own, own)
    reference, chosen, latents = compared(hit)
    unit = float(reference.std())
    with lfm2_driver.trie_off(engine.cache_manager.pool):
        cold = served.sequence(tokens, doc + own, own)
    engine.cache_manager.pool.check_invariants()
    err = np.abs(hit["logits"] - reference)
    rows_err = latent_err(hit, latents)
    # (a cold prefill writes the rows a hit resumes, so the two choose alike
    # wherever nothing is wrong: the reference is then the same)
    if not np.array_equal(cold["experts"], hit["experts"]):
        reference, _, latents = compared(cold)
    cold_err = np.abs(cold["logits"] - reference)
    ckv_err, kr_err = np.maximum(rows_err, latent_err(cold, latents)).tolist()
    out = {"reference_logit_std": unit,
           "reference_positions_checked": int(err.shape[0]),
           "hit_matched_tokens": hit["matched"],
           "cold_matched_tokens": cold["matched"],
           "reference_max_abs_err": float(err.max()),
           "reference_rms_err": _rms(err),
           "reference_decode_rms_err": _rms(err[own:]),
           "reference_cold_max_abs_err": float(cold_err.max()),
           "reference_cold_rms_err": _rms(cold_err),
           "reference_cold_decode_rms_err": _rms(cold_err[own:]),
           "hit_cold_logit_rms_diff": _rms(hit["logits"] - cold["logits"]),
           "reference_ckv_rel_rms_err": ckv_err,
           "reference_kr_rel_rms_err": kr_err,
           "reference_tol_in_std": [REFERENCE_MAX_TOL, REFERENCE_RMS_TOL],
           "reference_rows_tol": REFERENCE_ROWS_TOL}
    positions = hit["experts"].shape[1]
    layers = layer_check(hit, variables, cell, chosen[:, -positions:])
    out.update(layers)
    out["reference_ok"] = bool(
        layers["layers_ok"]
        and hit["matched"] == doc and cold["matched"] == 0
        and max(ckv_err, kr_err) <= REFERENCE_ROWS_TOL
        and max(out["reference_max_abs_err"],
                out["reference_cold_max_abs_err"]) <= REFERENCE_MAX_TOL * unit
        and max(out["reference_rms_err"], out["reference_decode_rms_err"],
                out["reference_cold_rms_err"],
                out["reference_cold_decode_rms_err"])
        <= REFERENCE_RMS_TOL * unit)
    return out


def engine_check(engine, served: Served, unit: float, tail: int) -> dict:
    """The ENGINE'S OWN PROGRAMS against the checked ones (``Served``, which
    ``reference_check`` holds to the reference), on the requests in flight
    when the window closed: what the timed tick and chunk programs produced
    with every lane live. For ``ENGINE_LANES`` decoding lanes, those with
    the fewest tokens out and those with the most (more than ``tail`` out,
    so that the tick wrote the rows compared): the latents at the lane's
    last ``tail`` positions in every layer and the tokens it returned
    there, against ``Served``'s forward of the same sequence from position
    0 in a lane of the same pool (cold: it shares no page with the engine's
    lane). The engine's rows are read first; then the requests in flight
    are cancelled, which frees the lanes the check needs."""
    t0 = time.perf_counter()
    manager = engine.cache_manager
    live = sorted(((lane, req) for lane, req in engine._active.items()
                   if len(req.tokens) > tail), key=lambda kv: len(kv[1].tokens))
    few = min(ENGINE_LANES // 2, len(live))
    many = min(ENGINE_LANES - few, len(live) - few)
    held = []
    for lane, req in live[:few] + live[len(live) - many:]:
        tokens = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
        n = int(manager.lengths[lane])        # rows [0, n) hold tokens[:n]
        if n != len(tokens) - 1:
            raise RuntimeError(f"lane {lane} holds {n} rows for "
                               f"{len(tokens)} tokens")
        held.append((tokens, len(req.prompt),
                     lane_rows(engine, lane, n - tail, n)))
    out = {"engine_lanes_live": len(engine._active),
           "engine_lanes_checked": len(held)}
    for req in list(engine._active.values()) + list(
            getattr(engine, "_prefilling", {}).values()):
        engine.cancel(req.id)
    if not held:
        # no lane had decoded past the tail when the window closed (a
        # rehearsal can end so): nothing was checked, so not correct
        out["engine_ok"] = False
        return out
    rows_err, deficits, margins = [], [], []
    for tokens, prompt_len, rows in held:
        n = len(tokens) - 1
        with lfm2_driver.trie_off(manager.pool):
            lane, _ = manager.alloc(-1, tokens[:n])
        try:
            logits, _ = served.prefill(lane, tokens[:n], 0, tail)
            rows_err.append(lfm2_driver._rel_rms(
                rows, lane_rows(engine, lane, n - tail, n), (1, 2)))
        finally:
            manager.free(lane)
        # position i predicts token i + 1; the engine chose those from the
        # prompt's last position on
        chosen = np.arange(n - tail, n) >= prompt_len - 1
        deficit, margin = served.deficits(logits, tokens[n - tail + 1:])
        deficits.append(deficit[chosen])
        margins.append(margin[chosen])
    manager.pool.check_invariants()
    rows_err = np.asarray(rows_err).reshape(len(held), -1)   # [lanes, layers]
    deficits, margins = np.concatenate(deficits), np.concatenate(margins)
    out.update({
        "engine_rows_checked": int(tail * len(held)),
        "engine_rows_max_rel_rms_err": float(rows_err.max()),
        "engine_rows_rel_rms_err_by_layer": [
            float(e) for e in rows_err.max(0)],
        "engine_tokens_served_checked": int(deficits.size),
        "engine_tokens_served_best": int((deficits == 0).sum()),
        "engine_token_served_max_deficit": float(deficits.max()),
        "engine_token_served_rms_deficit": _rms(deficits),
        "served_margin_p50": float(np.median(margins)),
        "engine_tol": [ENGINE_ROWS_TOL, ENGINE_TOKEN_TOL],
        "engine_check_s": time.perf_counter() - t0})
    out["engine_ok"] = bool(
        deficits.size
        and out["engine_rows_max_rel_rms_err"] <= ENGINE_ROWS_TOL
        and out["engine_token_served_rms_deficit"] <= ENGINE_TOKEN_TOL * unit)
    return out


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    """``serve_closed_loop_ref.run`` with this file's set-up and stream in
    the place of its own, then the engine check on what the window left in
    flight, and ``correct`` decided anew with the latent kernel counted."""
    from fleetx_tpu.ops.pallas.mla_decode import KERNEL_NAME

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
        engine = build_engine(cell, model, variables)
        done("weights_and_engine")
        buckets = swa_driver.warm_up(engine, cell, seed)
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
    ref_driver.traffic_gen = types.SimpleNamespace(
        client_stream=docqa_stream.client_stream)
    try:
        out = ref_driver.run(loop_cell, seed, seconds, trace, t_process)
    finally:
        ref_driver.set_up, ref_driver.traffic_gen = theirs
    out.cell = cell
    harness.log("latent and routing counters " + str({
        k: v for k, v in out.counters.items()
        if k.startswith(("latent_", "moe_", "prefill_tokens_", "prefix_"))}))
    engine, checks = held["engine"], out.checks
    checks["memory_peak_gb_after"] = dict(
        held["peak"], window=harness.memory_peak_bytes(cell.chips) / 1e9)
    # everything the window is read from is taken; now what the engine's
    # programs left in flight, which ends those requests
    checks.update(engine_check(
        engine, held["served"], held["reference"]["reference_logit_std"],
        check_sizes(cell)[3]))
    checks["mosaic_calls"] = harness.mosaic_calls(
        engine.compiled_decode().as_text(), KERNEL_NAME)
    checks["correct"] = bool(
        not checks["wrong_results"] and not checks["refused"]
        and not checks["engine_recoveries"] and not checks["poison_retired"]
        and not any(checks["fault_events"].values())
        and (checks["mosaic_calls"] > 0 or cell.tiny)
        and checks["compiles_in_window"] == 0
        and checks["reference_ok"] and checks["engine_ok"])
    out.correct = checks["correct"]
    return out
