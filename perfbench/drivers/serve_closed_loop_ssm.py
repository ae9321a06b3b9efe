"""Serving, closed loop, for a configuration whose lanes keep a selective
scan's state ONCE A LANE, outside the page pool that holds the keys and
values of its few attention layers: the loop of ``serve_closed_loop_ref.py``
AS IT IS (:func:`run` calls it, with this file's set-up in the place of its
own), so the same ``harness.Run`` and ``samples`` keys and every reader of a
closed-loop cell reads it.

What differs is ``correct``, decided in two steps on what the timed path
produces at the timed sizes:

- before the window (:func:`reference_check`): a prompt of ``CHECK_PROMPT``
  tokens admitted into the engine's OWN pool and lane state ONE-SHOT, and
  again IN CHUNKS of ``CHECK_CHUNK`` rows (the first holds the remainder,
  128 tokens in 320 rows: its padded rows must leave the state alone; every
  later chunk begins from what the lane holds), each then decoded for
  ``CHECK_DECODE`` steps through the lane's state, by programs of the
  check's own (:class:`Served`: the engine's return tokens only; its step
  is SHAPED AS THE ENGINE'S TICK, one row of every lane, so the reference
  holds the step kernel and the paged decode kernel at the timed lane
  count); the logits at the last ``CHECK_TAIL`` prompt positions and at
  every decode step against the reference's forward of the same tokens from
  position 0 (``perfbench/reference/jamba2_f32.py``, which upcasts the
  engine's bfloat16 values a layer at a time); and the state ``h`` the two
  runs leave in the first layer against each other, where only the state's
  own arithmetic can differ.
- after the window (:func:`engine_check`): what the ENGINE'S OWN tick and
  prefill programs left in the lanes in flight when the window closed, all
  256 live (the scan's state ``h`` and the filter's rows of every
  selective-scan layer, the keys and values of a lane's last positions) and
  the tokens they returned, against ``Served`` on the same sequences. The
  reference holds ``Served``; ``Served`` holds the timed programs, in the
  first layer bit for bit (the reference itself cannot: bfloat16 weights and
  activations stand between it and any program at 0.046 of the logits'
  deviation, above what a lower precision of the state adds).

From ``serve_closed_loop_ref.py`` as it is: ``run`` (the loop),
``build_model`` (which makes an older program say at once, before any
compile, that it cannot run the configuration) and ``reference_module``;
from ``serving.py``: ``build_engine``, ``warm_up``, and through the loop
``Clients``, ``serving_checks``, ``counters``.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from perfbench import harness, serving
from perfbench.drivers import serve_closed_loop_ref as ref_driver

CHECK_PROMPT, CHECK_CHUNK, CHECK_DECODE, CHECK_TAIL = 768, 320, 64, 256
ENGINE_LANES, ENGINE_TAIL = 8, 64

# How far the system may stand from the float32 reference. Limits from two
# readings each on the chip at the published widths (my chip runs, PR 38,
# second round, ``chiprun_out/r2``: the check as it stands, ``Served``'s
# step shaped as the tick; PERF.md section 6; ``perfbench/probe_jamba2.py``
# takes both): the largest reading of the engine as built over its seeds
# (nine: the probe's seeds 7 and 8 and seven runs of the cell), and the
# smallest reading of what has to come out NOT correct. A fault is refused
# by one of the limits and not by each.
#
# 1. The logits of the whole model after a one-shot prefill of 768 tokens,
# after a chunked prefill of the same prompt (128 tokens in a first call of
# 320 rows, then two whole ones) and through 64 decode steps through the
# lane's state (``Served``: the steps are ticks over every lane), at the
# prompt's last 256 positions and every decode step, in units of the
# standard deviation of the reference's logits (1.012 here):
# - ``REFERENCE_RMS_TOL``, over all 320 positions and over the decode steps
#   alone, one-shot and chunked: as built 0.0445-0.0466 and 0.0443-0.0474
#   (bfloat16 through 28 layers); padded rows updating the state 0.190
#   chunked (0.136 over its decode steps); the state a position stale 0.222
#   (0.474); the state zeroed between calls 0.631 (1.41); the inner norms
#   left out 1.14; ``D * u`` left out 1.41. The limit is 0.09: twice the
#   largest as built, half the smallest fault.
# - ``REFERENCE_MAX_TOL``: the largest error over 21 million logits, as
#   built 0.25-0.29; padded rows 2.56, every other fault 6.1-8.0. The limit
#   is 0.85, the geometric middle: 2.9 times each way.
# - ``FIRST_STATE_TOL``, what refuses a LOWER PRECISION OF THE STATE: the
#   state ``h`` the one-shot and the chunked run leave in the lane in the
#   FIRST layer, rms of their difference over the rms of the value
#   (:func:`first_state`). Every program is handed the same rows there, the
#   embedding's, so the two differ by the state's own arithmetic alone: as
#   built 0.0 in all nine readings, bit for bit (float32's own rounding
#   would read 1e-7); ``h`` rounded to bfloat16 wherever a call hands it
#   back 2.99e-4 and 3.74e-4 (seeds 7 and 8: the two runs round at other
#   positions of the prompt and decay towards each other over the 64 steps
#   they share); also stale 0.0020-0.0042, padded rows 0.033-0.079. The
#   limit is 1e-5: a hundred times float32's rounding, a thirtieth of the
#   smallest fault. Against the REFERENCE that fault reads 0.0458-0.0477,
#   inside the as-built range: the rounding of 28 bfloat16 layers hides it.
# - ``CHUNK_DIFF_TOL``, rms between the one-shot and the chunked logits:
#   the second line against that fault, at a smaller distance: as built
#   0.0177-0.0179 in all nine readings (the two run the same arithmetic
#   through programs of other shapes); ``h`` in bfloat16 0.0405-0.0412.
#   Also stale 0.052-0.065, padded rows 0.19-0.31, zeroed 0.26-0.32. The
#   limit is 0.027, the geometric middle: 1.5 times each way, of a reading
#   that moved by 1% over nine seeds.
# They do NOT tell apart what leaves both runs alike and is small beside
# bfloat16's own rounding; no such fault is planted.
#
# 2. The ENGINE'S OWN PROGRAMS (the timed one-shot prefill, padded to its
# bucket, and the 256-lane tick) against the check's (``Served``: in chunks,
# one lane; held to the reference by 1.), on 8 of the requests in flight when
# the window closes (:func:`engine_check`); rms of the difference over the
# rms of the value, the worst lane and layer. The two sides differ in the
# shapes of their programs, so bfloat16 rounds otherwise and the readings
# grow with the depth. The second reading is a fault planted in the engine's
# programs ALONE (``probe_jamba2.py``: 8 lanes, 128 tokens out, which reads
# higher as built than the cell's 256 lanes do):
# - ``FIRST_STATE_TOL`` again, on ``h`` in the first layer, where the tick's
#   step kernel over N steps and ``Served``'s scan kernel over the same
#   tokens are handed the same rows: as built 0.0 in all nine readings (in
#   the cell the first SEVEN layers read 0.0: up to the first attention
#   layer nothing rounds otherwise); the TICK's state alone rounded to
#   bfloat16 (``engine_bf16_state``) 0.0072-0.0074; zeroed 1.0. Of the
#   limits below that fault passes every one (``h`` over all layers 0.076-
#   0.078, rows 0.044, tokens 0.019): this one refuses it, 700 times over.
# - ``ENGINE_STATE_TOL``, the scan's state ``h`` the lane holds, the worst
#   layer: as built 0.027-0.032 in the cell, 0.058-0.060 in the probe; the
#   state zeroed at every tick 1.11; stale block tables 0.142. The limit is
#   0.25, the geometric middle of 0.060 and 1.11.
# - ``ENGINE_CONV_TOL``, the filter's rows it holds: as built 0.018-0.019
#   (0.041-0.043); zeroed 1.17. The limit is 0.22.
# - ``ENGINE_ROWS_TOL``, keys and values at its last 64 positions in the two
#   attention layers: as built 0.0145-0.0148 (0.035); stale tables (pages a
#   lane is given later never written) 0.99; zeroed 1.40. The limit is 0.19.
# - ``ENGINE_TOKEN_TOL``, how far the tokens it returned stand below
#   ``Served``'s best, rms in the logits' unit: as built 0.0028-0.0066
#   (0.012-0.016; 2-11% of the tokens are not ``Served``'s best, by at most
#   0.12: the best logit leads the second by 0.13-0.17 at the median);
#   zeroed 4.30. The limit is 0.26. Stale tables read 0.019 here: the rows'
#   limit refuses them.
REFERENCE_MAX_TOL = 0.85
REFERENCE_RMS_TOL = 0.09
CHUNK_DIFF_TOL = 0.027
ENGINE_STATE_TOL = 0.25
ENGINE_CONV_TOL = 0.22
ENGINE_ROWS_TOL = 0.19
ENGINE_TOKEN_TOL = 0.26
FIRST_STATE_TOL = 1e-5


def check_sizes(cell) -> tuple:
    """``(prompt, chunk, decode steps, tail, engine tail)`` of the check:
    the constants above at the published sizes; a rehearsal's scale with
    its prefill bucket."""
    if not cell.tiny:
        return CHECK_PROMPT, CHECK_CHUNK, CHECK_DECODE, CHECK_TAIL, ENGINE_TAIL
    bucket = cell.deploy["prefill_bucket"]
    return 2 * bucket + bucket // 4, bucket, 4, bucket, 8


class Served:
    """What the model computes through the ENGINE'S pool and lane state, by
    programs of the check's own (the engine's return tokens only): a call
    that only writes, a call that also gives the logits of its last ``tail``
    tokens, a step; on ``engine.params`` through its dequantisation seam, in
    a lane of ``engine.cache_manager`` claimed and freed by the caller. A
    call has ``rows`` rows, the first ``count`` of them tokens (the model is
    told which). The step is SHAPED AS THE ENGINE'S TICK: one row of EVERY
    lane in order, the check's lane the only one decoding, so it runs the
    model's tick branch (the step kernel that updates the lanes' leaf in
    place, the paged decode kernel) at the timed lane count, and the
    reference holds THAT. ``model`` is the engine's unless a probe plants a
    fault."""

    def __init__(self, engine, model=None):
        import jax
        import jax.numpy as jnp

        self.engine = engine
        model = model or engine.model
        donate = (1,) if jax.default_backend() == "tpu" else ()

        @functools.partial(jax.jit, donate_argnums=donate,
                           static_argnames=("tail",))
        def forward(params, cache, ids, at, count, table, tail=0):
            rows = jnp.arange(ids.shape[0], dtype=jnp.int32)
            logits, mut = model.apply(
                {"params": engine._dequant_params(params), "cache": cache},
                ids[None], (at + rows)[None], (rows < count)[None],
                decode=True, cache_positions=at[None],
                block_tables=table[None], mutable=["cache"])
            if not tail:
                return mut["cache"], None
            return mut["cache"], jax.lax.dynamic_slice_in_dim(
                logits[0], count - tail, tail, 0).astype(jnp.float32)

        @functools.partial(jax.jit, donate_argnums=donate)
        def tick(params, cache, token, at, lane, tables):
            # as ``ServingEngine._decode_fn`` hands the model a tick: a lane
            # that is not decoding writes at the lane's last row, which its
            # zeroed table sends to the trash page
            active = jnp.arange(tables.shape[0]) == lane
            logits, mut = model.apply(
                {"params": engine._dequant_params(params), "cache": cache},
                jnp.where(active, token, 0)[:, None],
                jnp.where(active, at, 0)[:, None], active[:, None],
                decode=True, block_tables=tables,
                cache_positions=jnp.where(active, at, engine.cache_len - 1),
                mutable=["cache"])
            return mut["cache"], logits[lane].astype(jnp.float32)

        @jax.jit
        def rate(logits, tokens):
            """How far each of ``tokens`` stands below the best logit of its
            row, and the best above the second."""
            top = jax.lax.top_k(logits, 2)[0]
            at = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
            return top[:, 0] - at, top[:, 0] - top[:, 1]

        self._forward, self._tick, self._rate = forward, tick, rate

    def _call(self, lane: int, ids, at: int, rows: int, tail: int = 0):
        import jax.numpy as jnp

        manager = self.engine.cache_manager
        padded = np.zeros(rows, np.int32)
        padded[:len(ids)] = ids
        manager.cache, logits = self._forward(
            self.engine.params, manager.cache, jnp.asarray(padded),
            jnp.asarray(at, jnp.int32), jnp.asarray(len(ids), jnp.int32),
            jnp.asarray(manager.lane_tables(lane)), tail=tail)
        return logits

    def prefill(self, lane: int, tokens, tail: int, chunk: int = 0):
        """``tokens`` written from position 0 on: in ONE call of as many
        rows (``chunk`` 0), or in calls of ``chunk`` rows, a first one with
        the remainder (padded), then whole ones. Returns the logits of the
        last ``tail`` positions (on the device)."""
        n = len(tokens)
        if not chunk:
            return self._call(lane, tokens, 0, n, tail)
        first = n % chunk or min(chunk, n)
        starts = [0] + list(range(first, n, chunk))
        if min(first if len(starts) == 1 else chunk, n) < tail:
            raise ValueError(f"{n} tokens in chunks of {chunk} give no tail "
                             f"of {tail}")
        for at in starts:
            out = self._call(lane, tokens[at:at + (chunk if at else first)],
                             at, chunk, tail if at == starts[-1] else 0)
        return out

    def step(self, lane: int, token: int):
        """One decode step at the lane's next position, as a tick over every
        lane with ``lane`` alone decoding: its logits ``[1, vocab]`` (host)."""
        import jax.numpy as jnp

        manager = self.engine.cache_manager
        if not manager.ensure_page(lane):
            raise RuntimeError("the pool ran dry in the check")
        manager.cache, logits = self._tick(
            self.engine.params, manager.cache, jnp.asarray(token, jnp.int32),
            jnp.asarray(manager.lengths[lane], jnp.int32),
            jnp.asarray(lane, jnp.int32), jnp.asarray(manager.tables))
        manager.lengths[lane] += 1
        return np.asarray(logits)

    def deficits(self, logits, tokens) -> tuple:
        """``(deficit, margin)`` of ``tokens`` under ``logits``, one row
        each: :func:`rate` on the device (the rows stay there)."""
        import jax.numpy as jnp

        deficit, margin = self._rate(logits, jnp.asarray(tokens, jnp.int32))
        return np.asarray(deficit), np.asarray(margin)

    def sequence(self, tokens, prompt_len: int, tail: int,
                 chunk: int = 0) -> tuple:
        """The first ``prompt_len`` of ``tokens`` admitted and prefilled
        (:meth:`prefill`), the rest decoded one step each: the logits of
        the last ``tail`` prompt positions, then of every decode step; and
        the scan's state the lane holds at the end in the FIRST
        selective-scan layer (:func:`first_state`)."""
        manager = self.engine.cache_manager
        lane, _ = manager.alloc(-1, tokens[:prompt_len])
        try:
            out = [np.asarray(self.prefill(lane, tokens[:prompt_len], tail,
                                           chunk))]
            out += [self.step(lane, int(token))
                    for token in tokens[prompt_len:]]
            state = first_state(self.engine, lane)
        finally:
            manager.free(lane)
        return np.concatenate(out), state


def _leaves(engine) -> dict:
    import jax

    return {path[-1].key: leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(
                engine.cache_manager.cache)[0]}


def lane_rows(engine, lane: int, lo: int, hi: int) -> np.ndarray:
    """The keys and values the engine's pool holds for ``lane`` at positions
    ``[lo, hi)`` of every ATTENTION layer, read through the manager's HOST
    table: ``[attention layers, 2, hi - lo, kv_heads * head]`` float32."""
    cfg, manager = engine.model.cfg, engine.cache_manager
    pos = np.arange(lo, hi)
    layers = cfg.layer_types.count("full_attention")
    page = (manager.pool.tables[lane][pos // manager.page_size][None, :]
            + np.arange(layers)[:, None] * manager.num_pages)
    leaves = _leaves(engine)
    return np.stack([np.asarray(leaves[name][page, pos % manager.page_size],
                                np.float32)
                     for name in ("cached_key", "cached_value")], axis=1)


def lane_state(engine, lane: int) -> tuple:
    """What ``lane`` holds outside the pool: ``(h, filter rows)`` of every
    selective-scan layer, ``[layers, d_state, inner]`` and ``[layers,
    d_conv - 1, inner]`` float32."""
    leaves = _leaves(engine)
    conv = np.asarray(leaves["ssm_conv"][:, lane], np.float32)
    return (np.asarray(leaves["ssm_state"][:, lane], np.float32),
            conv.reshape(*conv.shape[:-1], -1, leaves["ssm_state"].shape[-1]))


def first_state(engine, lane: int) -> np.ndarray:
    """``h`` ``[d_state, inner]`` that ``lane`` holds in the FIRST
    selective-scan layer. Where that is the model's first layer (here it
    is) every program is handed the same rows there, the embedding's, so two
    runs of one sequence differ in it by the state's own arithmetic alone,
    with none of the rounding that bfloat16 activations gather through the
    layers above: the place where a lower precision of the state shows."""
    return np.asarray(_leaves(engine)["ssm_state"][0, lane], np.float32)


def _rel_rms(theirs, mine, axes) -> np.ndarray:
    return np.sqrt(((theirs - mine) ** 2).mean(axes)
                   / np.maximum((mine ** 2).mean(axes), 1e-30))


def _rms(x) -> float:
    return float(np.sqrt((np.asarray(x, np.float64) ** 2).mean()))


def engine_check(engine, served: Served, unit: float, tail: int,
                 chunk: int) -> dict:
    """The ENGINE'S OWN PROGRAMS against the checked ones (``Served``, which
    ``reference_check`` holds to the reference), on the requests in flight
    when the window closed: what the timed tick and prefill programs
    produced with every lane live. For ``ENGINE_LANES`` decoding lanes,
    those with the fewest tokens out and those with the most: the scan's
    state and the filter's rows the lane holds in every selective-scan
    layer, the keys and values at the lane's last ``tail`` positions in
    every attention layer, and the tokens it returned at those positions,
    against ``Served``'s forward of the same sequence from position 0 in
    chunks of ``chunk``. The engine's leaves are read first; then the
    requests in flight are cancelled, which frees the lanes the check
    needs."""
    t0 = time.perf_counter()
    manager = engine.cache_manager
    live = sorted(((lane, req) for lane, req in engine._active.items()
                   if manager.lengths[lane] > tail),
                  key=lambda kv: len(kv[1].tokens))
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
                     lane_rows(engine, lane, n - tail, n),
                     *lane_state(engine, lane)))
    out = {"engine_lanes_live": len(engine._active),
           "engine_lanes_checked": len(held)}
    for req in list(engine._active.values()):
        engine.cancel(req.id)
    if not held:
        # no lane was decoding past the tail when the window closed (a
        # rehearsal can end so): nothing was checked, so the run is not
        # correct
        out["engine_ok"] = False
        return out
    errs = {"rows": [], "state": [], "conv": []}
    deficits, margins = [], []
    for tokens, prompt_len, rows, state, conv in held:
        n = len(tokens) - 1
        lane, _ = manager.alloc(-1, tokens[:n])
        try:
            logits = served.prefill(lane, tokens[:n], tail, chunk)
            mine_state, mine_conv = lane_state(engine, lane)
            errs["rows"].append(_rel_rms(
                rows, lane_rows(engine, lane, n - tail, n), (1, 2, 3)))
            errs["state"].append(_rel_rms(state, mine_state, (1, 2)))
            errs["conv"].append(_rel_rms(conv, mine_conv, (1, 2)))
        finally:
            manager.free(lane)
        # position i predicts token i + 1; the engine chose those from the
        # prompt's last position on
        chosen = np.arange(n - tail, n) >= prompt_len - 1
        deficit, margin = served.deficits(logits, tokens[n - tail + 1:])
        deficits.append(deficit[chosen])
        margins.append(margin[chosen])
    manager.pool.check_invariants()
    deficits, margins = np.concatenate(deficits), np.concatenate(margins)
    for name, err in errs.items():
        err = np.asarray(err).reshape(len(held), -1)         # [lanes, layers]
        out[f"engine_{name}_max_rel_rms_err"] = float(err.max())
        out[f"engine_{name}_rel_rms_err_by_layer"] = [
            float(e) for e in err.max(0)]
    # (the leaf's first layer: ``first_state``'s)
    out["engine_first_state_rel_rms_err"] = out[
        "engine_state_rel_rms_err_by_layer"][0]
    out.update({
        "engine_tokens_served_checked": int(deficits.size),
        "engine_tokens_served_best": int((deficits == 0).sum()),
        "engine_token_served_max_deficit": float(
            deficits.max() if deficits.size else 0.0),
        "engine_token_served_rms_deficit": _rms(deficits)
        if deficits.size else 0.0,
        "served_margin_p50": float(np.median(margins))
        if margins.size else 0.0,
        "engine_tol": [ENGINE_STATE_TOL, ENGINE_CONV_TOL, ENGINE_ROWS_TOL,
                       ENGINE_TOKEN_TOL, FIRST_STATE_TOL],
        "engine_check_s": time.perf_counter() - t0})
    out["engine_ok"] = bool(
        deficits.size
        and out["engine_state_max_rel_rms_err"] <= ENGINE_STATE_TOL
        and out["engine_first_state_rel_rms_err"] <= FIRST_STATE_TOL
        and out["engine_conv_max_rel_rms_err"] <= ENGINE_CONV_TOL
        and out["engine_rows_max_rel_rms_err"] <= ENGINE_ROWS_TOL
        and out["engine_token_served_rms_deficit"] <= ENGINE_TOKEN_TOL * unit)
    return out


def reference_check(engine, variables, cell, seed: int,
                    served: Served = None) -> dict:
    """The engine against the configuration's float32 reference, which reads
    the weights as made (``variables``), outside the window: module
    docstring, ``correct``."""
    import jax

    served = served or Served(engine)
    prompt, chunk, decode, tail, _ = check_sizes(cell)
    tokens = np.random.default_rng([seed, 4]).integers(
        1, cell.config["model"]["vocab_size"], prompt + decode,
        dtype=np.int32)
    # position i predicts token i + 1: the prompt's last ``tail`` positions
    # and the decode steps are the sequence's last
    reference = np.asarray(jax.jit(
        ref_driver.reference_module(cell).configured(cell.config["model"]),
        static_argnames=("tail",))(variables["params"], tokens,
                                   tail=tail + decode))
    unit = float(reference.std())
    whole, whole_state = served.sequence(tokens, prompt, tail)
    chunked, chunked_state = served.sequence(tokens, prompt, tail, chunk)
    engine.cache_manager.pool.check_invariants()
    err, chunk_err = np.abs(whole - reference), np.abs(chunked - reference)
    out = {"reference_logit_std": unit,
           "reference_positions_checked": int(err.shape[0]),
           "reference_max_abs_err": float(err.max()),
           "reference_rms_err": _rms(err),
           "reference_decode_rms_err": _rms(err[tail:]),
           "reference_chunked_max_abs_err": float(chunk_err.max()),
           "reference_chunked_rms_err": _rms(chunk_err),
           "reference_chunked_decode_rms_err": _rms(chunk_err[tail:]),
           "whole_chunked_logit_rms_diff": _rms(whole - chunked),
           "whole_chunked_first_state_rel_rms_diff": float(_rel_rms(
               chunked_state, whole_state, (0, 1))),
           "reference_tol_in_std": [REFERENCE_MAX_TOL, REFERENCE_RMS_TOL,
                                    CHUNK_DIFF_TOL],
           "first_state_tol": FIRST_STATE_TOL}
    out["reference_ok"] = bool(
        max(out["reference_max_abs_err"],
            out["reference_chunked_max_abs_err"]) <= REFERENCE_MAX_TOL * unit
        and max(out["reference_rms_err"], out["reference_decode_rms_err"],
                out["reference_chunked_rms_err"],
                out["reference_chunked_decode_rms_err"])
        <= REFERENCE_RMS_TOL * unit
        and out["whole_chunked_logit_rms_diff"] <= CHUNK_DIFF_TOL * unit
        and out["whole_chunked_first_state_rel_rms_diff"] <= FIRST_STATE_TOL)
    return out


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    """``serve_closed_loop_ref.run`` with this file's set-up in the place of
    its own, then the engine check on what the window left in flight."""
    held = {}

    def set_up(cell, seed, t_process):
        device = harness.own_the_chip(cell.chips, cell.tiny)

        from fleetx_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        clock = harness.CompileClock()
        phases = {"import_s": time.perf_counter() - t_process}
        model, variables = ref_driver.build_model(cell, seed)
        engine = serving.build_engine(cell, model, variables)
        phases["weights_and_engine_s"] = time.perf_counter() - t_process
        buckets = serving.warm_up(engine, cell, seed)
        phases["warm_up_s"] = time.perf_counter() - t_process
        served = Served(engine)
        reference = reference_check(engine, variables, cell, seed, served)
        phases["reference_s"] = time.perf_counter() - t_process
        held.update(engine=engine, served=served, reference=reference)
        return device, clock, engine, reference, buckets, phases

    theirs, ref_driver.set_up = ref_driver.set_up, set_up
    try:
        out = ref_driver.run(cell, seed, seconds, trace, t_process)
    finally:
        ref_driver.set_up = theirs
    harness.log("state counters " + str({
        k: v for k, v in out.counters.items()
        if k.startswith(("state_", "kv_page_", "ssm_"))}))
    # everything the window is read from is taken; now what the engine's
    # programs left in flight, which ends those requests
    _, chunk, _, _, tail = check_sizes(cell)
    out.checks.update(engine_check(
        held["engine"], held["served"],
        held["reference"]["reference_logit_std"], tail, chunk))
    out.checks["correct"] = out.checks["correct"] and out.checks["engine_ok"]
    out.correct = out.checks["correct"]
    return out
