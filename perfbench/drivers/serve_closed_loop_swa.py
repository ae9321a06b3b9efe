"""Serving, closed loop, for a configuration with window-attention layers
over a page pool of two classes: the loop of ``serve_closed_loop_ref.py``
(``clients`` callers, each sending its next request when its last one has
returned; the first round is warm-up; tokens count if delivered inside the
window) with the same ``harness.Run`` and ``samples`` keys, so that every
reader of a closed-loop cell reads it.

What differs from ``serve_closed_loop_ref.py`` (which fixes OLMoE's limits,
an 84-token check and one input for router and experts):

- the engine is built with CHUNKED prefill (the cell's ``prefill_chunk``)
  and no prefix cache;
- the warm-up is one request a chunk and four tokens long: with chunked
  prefill every prompt runs the chunk-sized program and one program for its
  last chunk's bucket, whatever its length;
- ``correct`` compares what the timed path produces at the timed sizes, in
  two steps. Before the window (:func:`reference_check`): a sequence of
  ``CHECK_PROMPT`` tokens (2,048 positions beyond the window, so the window
  class has released and re-allocated pages) prefilled in chunks and then
  decoded through the engine's OWN pool, allocators and weights by programs
  of the check's own (:class:`Served`: the engine's return tokens only),
  its logits at the last ``CHECK_TAIL`` prompt positions and at every
  decode step against the reference's forward of the same tokens; each
  expert layer on the router input and expert input it really saw; the
  engine's own answers through ``submit`` and ``step``, one of them past
  the window, rated by the reference. After the window
  (:func:`engine_check`): what the ENGINE'S OWN tick and chunk programs
  wrote into the pool and returned for the requests in flight when the
  window closed, every lane live, against ``Served`` on the same
  sequences. The reference holds ``Served``; ``Served`` holds the timed
  programs.

From ``serve_closed_loop_ref.py`` as it is: ``build_model`` (which makes an
older program say at once, before any compile, that it cannot run the
configuration) and ``reference_module``; from ``serving.py``: ``Clients``,
``serving_checks``, ``counters``.
"""

from __future__ import annotations

import functools
import json
import time

import numpy as np

from perfbench import harness, serving, traffic as traffic_gen
from perfbench.drivers import serve_closed_loop_ref as ref_driver

CHECK_PROMPT, CHECK_DECODE, CHECK_TAIL = 6144, 32, 256
ANSWER_PROMPTS, ANSWER_TOKENS = (4612, 772), 128
ENGINE_LANES = 8      # lanes of the window's end held to the checked programs

# How far the system may stand from the float32 reference. Limits from two
# readings each on the chip at the published widths (my chip runs, PR 31;
# PERF.md section 6; ``perfbench/probe_smallthinker.py`` takes both): the
# largest reading of the engine as built over its seeds, and the smallest
# reading of what has to come out NOT correct.
#
# 1. Every expert layer against the reference's layer ON THE ROUTER INPUT
# AND THE EXPERT INPUT IT REALLY SAW (:func:`layer_check`), as OLMoE's: this
# is what refuses a lower precision in the expert layer.
# - ``LAYER_WEIGHT_TOL``, the routing weights the layer applied against the
#   reference router's for the same experts, largest relative error: as
#   built 1.8e-6 to 3.0e-6 over eight seeds (both sides float32 at ``highest``); a router
#   computed in bfloat16 0.0252-0.0293. The limit is 1e-4: fifty times the
#   one and more, a two-hundred-and-fiftieth of the other. An expert the layer chose
#   counts as beside the reference's when the reference's router rates it
#   under its own sixth by more than that limit (a tie inside the limit is
#   no fault): as built 0 of 4,352 layer-positions, a bfloat16 router
#   450-463; none is allowed.
# - ``LAYER_OUTPUT_TOL``, the layer's output against the reference's sum
#   over the same experts, rms over the layer's rms, the worst layer: as
#   built 0.00288-0.00292 (three bfloat16 roundings: the activation,
#   the kernel's output, the sum); experts rounded to int8 with a scale per
#   column 0.01486-0.01512 (read on the first period alone, 4 layers: a
#   second copy of eight layers' experts does not fit, and a layer's
#   reading does not depend on the depth; as built there 0.00290). The
#   limit is 0.0066, the geometric middle: 2.3 times each way.
#
# 2. The logits of the whole model at positions beyond the window, after
# chunked prefill and through decode (``Served``), and the tokens the engine
# returned before the window, in units of the standard deviation of the reference's logits (1.01 here).
# The reference sums over the SYSTEM'S experts at the positions compared
# (``given``): the router reads the raw residual stream here, whose common
# part is large, so its logits lie close and at a fifth of the positions
# (103-105 of 544) the rounding of the layers before hands an expert over;
# an expert exchanged moves the logits by more (largest error 0.34-0.40,
# rms 0.015-0.020 without ``given``) than any arithmetic does. These limits
# refuse a wrong attention pattern, position or page:
# - ``REFERENCE_RMS_TOL``: as built 0.0056-0.0061 of the unit over eight
#   seeds (prompt tail and decode steps together; the decode steps alone
#   0.0049-0.0054); a full layer rotated 0.151-0.156, a window
#   layer attending its whole row 0.224-0.229 (a bfloat16 router 0.075).
#   The limit is 0.02 for both: 3.3 times the largest as built, a seventh
#   of the smallest wrong pattern.
# - ``REFERENCE_MAX_TOL``: the largest error over 44 million logits, as
#   built 0.032-0.036 of the unit; a rotated full layer 0.87-0.90, an
#   ignored window 1.13-1.27. The limit is 0.16, as the other serve cells
#   have it: 4.5 times the one, a fifth of the other.
# - ``REFERENCE_TOKEN_TOL``: how far the tokens the ENGINE returned for the
#   two seeded requests of the check (through ``submit`` and ``step``, 128
#   tokens each, one past the window) stand below the reference's best at
#   their positions, rms over the 256; the reference sums over the experts
#   ``Served`` chose on the same sequence. As built 0-0.0130 over twelve
#   seeds (0 to 17 tokens of 256 are not the reference's best, the largest
#   deficit 0.175: an expert exchanged between the engine's program and
#   the check's). A rotated full layer 0.036 and 0.239; a bfloat16 router
#   and an ignored window 0.098 and 0.044 on one seed and 0.0 on the other
#   (no token moved there). The limit is 0.03, the loosest of the set, 2.3
#   times the largest as built: it refuses an engine whose tokens have come
#   apart; the logits limits above are what refuses each wrong pattern.
# They do NOT tell a lower precision apart (int8 experts read rms 0.0085-
# 0.0089 where the engine as built read 0.0048-0.0050): the layer limits
# do.
#
# 3. The ENGINE'S OWN PROGRAMS (the timed chunk prefill and 24-lane tick)
# against the check's (``Served``, held to the reference by 2.), on 8 of
# the requests in flight when the window closes (:func:`engine_check`). The
# second readings are faults planted in the engine's programs ALONE
# (``probe_smallthinker.py`` ``engine_*``, five seeds, 4 lanes in flight):
# the window ignored, a full layer rotated, block tables that stopped
# following the allocator.
# - ``ENGINE_ROWS_TOL``: the keys and values the engine wrote at a lane's
#   last 256 positions against ``Served``'s, rms of the difference over the
#   rms of the rows, the worst lane and layer. As built 0.047-0.123 over
#   eighteen readings (0 in the first layer, 0.007 in the second, the most
#   in the third: the second layer's router is the first to read a stream
#   that two programs round differently, and where it hands an expert
#   over the row after it moves; bfloat16 alone would read under 0.01).
#   The window ignored 0.39-0.47 (from the third layer on), a rotated full
#   layer 0.98-1.01, stale tables 0.67-0.69. The limit is 0.22, the
#   geometric middle: 1.8 times each way.
# - ``ENGINE_TOKEN_TOL``: how far the tokens the engine returned at those
#   positions (790-1,150 of them a run) stand below ``Served``'s best, rms
#   in the logits' unit. As built 0.0019-0.0157 over fourteen readings (1.3
#   to 6.9% of the tokens are not ``Served``'s best, the largest deficit
#   0.04-0.19; the best logit leads the second by 0.08-0.42 at the median);
#   the window ignored 0.129-0.278, a rotated full layer 0.183-0.212. The
#   limit is 0.045, the geometric middle of 0.0157 and 0.129: 2.9 times
#   each way. Stale tables read 0.020-0.053, under or on this limit: they are the
#   rows' to refuse (0.67 against 0.22), as a fault is refused by one of
#   the limits and not by each. The largest single deficit tells nothing
#   apart (0.19 as built, 0.23 with stale tables) and is reported only.
REFERENCE_MAX_TOL = 0.16
REFERENCE_RMS_TOL = 0.02
REFERENCE_TOKEN_TOL = 0.03
ENGINE_ROWS_TOL = 0.22
ENGINE_TOKEN_TOL = 0.045
LAYER_WEIGHT_TOL = 1e-4
LAYER_OUTPUT_TOL = 0.0066


def check_sizes(cell) -> tuple:
    """``(prompt, decode steps, tail, answer prompts, answer tokens)`` of
    the check: the constants above at the published sizes; a rehearsal's
    scale with its window and chunk (past the window by half of it, a whole
    number of chunks)."""
    if not cell.tiny:
        return (CHECK_PROMPT, CHECK_DECODE, CHECK_TAIL, ANSWER_PROMPTS,
                ANSWER_TOKENS)
    window = cell.config["model"]["sliding_window"]
    chunk = cell.deploy["prefill_chunk"]
    prompt = -(-(window + window // 2) // chunk) * chunk
    return prompt, 4, chunk // 2, (window + chunk + 4, chunk + 4), chunk // 2


def build_engine(cell, model, variables):
    """The engine as ``serving.build_engine`` builds it, with chunked
    prefill (from which it sizes the window class) and no prefix cache (the
    family refuses one: a prefix's window pages are released behind the
    window)."""
    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.serving import ServingEngine

    deploy = cell.deploy
    page = deploy["page_size"]
    max_new = max(traffic_gen.length_bounds(t["output"])[1]
                  for t in cell.traffic["tenants"])
    return ServingEngine(
        model, variables, slots=deploy["lanes"], cache_len=deploy["cache_len"],
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=max_new),
        page_size=page, num_pages=deploy["pool_tokens"] // page + 1,
        prefill_chunk=deploy["prefill_chunk"],
        prefill_bucket=deploy["prefill_bucket"], prefix_cache=False)


def warm_up(engine, cell, seed: int) -> list:
    """One request for every prefill program a prompt can reach: the
    chunk-sized one and one for each bucket of a last chunk; each followed
    by decode ticks. Returns the bucket lengths."""
    vocab = cell.config["model"]["vocab_size"]
    chunk, step = engine.prefill_chunk, engine.prefill_bucket
    buckets = sorted({min(-(-n // step) * step, chunk)
                      for n in range(1, chunk + 1)})
    rng = np.random.default_rng([seed, 3])
    for bucket in buckets:
        engine.submit(rng.integers(1, vocab, chunk + bucket, dtype=np.int32),
                      max_length=2)
        engine.drain()
    return buckets


def engine_answers(engine, cell, seed: int) -> list:
    """``(prompt, tokens)`` of seeded requests served by the ENGINE ITSELF,
    together, through ``submit`` and ``step``: its chunked prefill and tick,
    both block tables, both allocators. The first prompt is longer than the
    window plus a chunk, so its window pages were released and re-used."""
    vocab = cell.config["model"]["vocab_size"]
    rng = np.random.default_rng([seed, 5])
    *_, prompts, tokens = check_sizes(cell)
    ids = [engine.submit(rng.integers(1, vocab, n, dtype=np.int32),
                         max_length=tokens) for n in prompts]
    results = engine.drain()
    return [(np.asarray(results[i].prompt), np.asarray(results[i].tokens))
            for i in ids]


class Served:
    """What the model computes through the ENGINE'S pool, by programs of
    the check's own (the engine's return tokens only, so logits and routing
    need them: a chunk that only writes, a chunk that also gives the logits
    of its last ``tail`` positions and its routing, a step): on
    ``engine.params`` through its dequantisation seam, in a lane of
    ``engine.cache_manager`` claimed and freed by the caller, so that both
    classes of page are allocated, released behind the window and re-used
    exactly as for a request. ``model`` is ``engine.model`` unless a probe
    plants a fault in the engine's programs alone."""

    def __init__(self, engine, tail: int, model=None):
        import jax
        import jax.numpy as jnp

        self.engine, self.tail = engine, tail
        model = model or engine.model
        donate = (1,) if jax.default_backend() == "tpu" else ()

        @functools.partial(jax.jit, donate_argnums=donate,
                           static_argnames=("tail",))
        def forward(params, cache, ids, at, table, tail=0):
            """Writes ``ids`` at positions ``at`` on; ``tail`` > 0: also the
            logits of the last ``tail`` of them and the routing of all."""
            pos = at + jnp.arange(ids.shape[0], dtype=jnp.int32)
            logits, mut = model.apply(
                {"params": engine._dequant_params(params), "cache": cache},
                ids[None], pos[None], None, decode=True,
                cache_positions=at[None],
                block_tables=jnp.expand_dims(table, -2),
                mutable=["cache"] + (["routing"] if tail else []))
            if not tail:
                return mut["cache"], None, None
            # under the layer scan one leaf [layers, 1, s, width] of each name
            sown = {jax.tree_util.keystr(path[-2:-1]).strip("[']"): leaf[:, 0]
                    for path, leaf in jax.tree_util.tree_flatten_with_path(
                        mut["routing"])[0]}
            return mut["cache"], logits[0, -tail:].astype(jnp.float32), sown

        @jax.jit
        def rate(logits, tokens):
            """How far each of ``tokens`` stands below the best logit of its
            row, and the best above the second."""
            top = jax.lax.top_k(logits, 2)[0]
            at = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
            return top[:, 0] - at, top[:, 0] - top[:, 1]

        self._forward, self._rate = forward, rate

    def _call(self, lane: int, ids, at: int, tail: int = 0):
        import jax.numpy as jnp

        manager = self.engine.cache_manager
        manager.cache, logits, sown = self._forward(
            self.engine.params, manager.cache, jnp.asarray(ids, jnp.int32),
            jnp.asarray(at, jnp.int32),
            jnp.asarray(manager.lane_tables(lane)), tail=tail)
        return logits, sown

    def prefill(self, lane: int, tokens):
        """``tokens`` written from position 0 in whole chunks of
        ``engine.prefill_chunk``, so that every length takes the same two
        programs: where the length is no whole number of chunks the first
        two chunks overlap (the second writes again what the first wrote
        there). Returns the logits of the last ``tail`` positions (on the
        device) and the routing of the last chunk's positions."""
        chunk, n = self.engine.prefill_chunk, len(tokens)
        if n < max(chunk, self.tail):
            raise ValueError(f"{n} tokens are less than a chunk or the tail")
        starts = ([0] if n % chunk else []) + list(range(n % chunk, n, chunk))
        for at in starts:
            if not self.engine.cache_manager.prepare_span(lane, at, chunk):
                raise RuntimeError("the window class ran dry in the check")
            out = self._call(lane, tokens[at:at + chunk], at,
                             self.tail if at == starts[-1] else 0)
        return out

    def step(self, lane: int, token: int):
        """One decode step at the lane's next position: its logits (host)
        and routing."""
        manager = self.engine.cache_manager
        if not manager.ensure_page(lane):
            raise RuntimeError("a page class ran dry in the check")
        logits, sown = self._call(lane, [token], int(manager.lengths[lane]), 1)
        manager.lengths[lane] += 1
        return np.asarray(logits), sown

    def deficits(self, logits, tokens) -> tuple:
        """``(deficit, margin)`` of ``tokens`` under ``logits``, one row
        each: :func:`rate` on the device (the rows stay there)."""
        import jax.numpy as jnp

        deficit, margin = self._rate(logits, jnp.asarray(tokens, jnp.int32))
        return np.asarray(deficit), np.asarray(margin)

    def sequence(self, tokens, prompt_len: int) -> dict:
        """The first ``prompt_len`` of ``tokens`` prefilled, the rest
        decoded one step each: ``logits`` (the last ``tail`` prompt
        positions, then every decode step) and what the expert layers saw,
        chose and gave at the last chunk's positions and the decode steps:
        ``input``, ``router_input``, ``output`` ``[layers, positions,
        hidden]``, ``experts``, ``weights`` ``[layers, positions, k]``."""
        manager = self.engine.cache_manager
        lane, _ = manager.alloc(-1, tokens[:prompt_len])
        try:
            logits, sown = self.prefill(lane, tokens[:prompt_len])
            out = [np.asarray(logits)]
            routing = {k: [np.asarray(v, np.float32)] for k, v in sown.items()}
            for token in tokens[prompt_len:]:
                logits, sown = self.step(lane, int(token))
                out.append(logits)
                for k, v in sown.items():
                    routing[k].append(np.asarray(v, np.float32))
        finally:
            manager.free(lane)
        return {"logits": np.concatenate(out),
                **{k: np.concatenate(v, axis=1) for k, v in routing.items()}}


def lane_rows(engine, lane: int, lo: int, hi: int) -> np.ndarray:
    """The keys and values the engine's pool holds for ``lane`` at positions
    ``[lo, hi)`` of every layer, read through the manager's HOST tables of
    the layer's class: ``[layers, 2, hi - lo, kv_heads * head]`` float32."""
    import jax

    from fleetx_tpu.models.gpt.hybrid import layer_bases

    cfg, manager = engine.model.cfg, engine.cache_manager
    pos = np.arange(lo, hi)
    tables = manager.lane_tables(lane)[np.asarray(cfg.window_layers, int)]
    page = tables[:, pos // manager.page_size] + layer_bases(cfg)[:, None]
    pools = {path[-1].key: leaf for path, leaf in
             jax.tree_util.tree_flatten_with_path(manager.cache)[0]}
    return np.stack([np.asarray(pools[name][page, pos % manager.page_size],
                                np.float32)
                     for name in ("cached_key", "cached_value")], axis=1)


def engine_check(engine, served: Served, in_flight, unit: float) -> dict:
    """The ENGINE'S OWN PROGRAMS against the checked ones (``Served``, which
    ``reference_check`` holds to the reference), on the requests in flight
    when the window closed: what the timed tick and chunk programs produced
    with every lane live. For ``ENGINE_LANES`` decoding lanes, those with
    the fewest tokens out and those with the most: the keys and values the
    engine wrote at the lane's last ``tail`` positions in every layer (a
    row of layer l holds the stream every layer before it left at that
    position: the table, pages and window each of them read through), and
    the tokens it returned at those positions, against ``Served``'s forward
    of the same sequence in a lane of the same pool. The engine's rows are
    read first; then the requests ``in_flight`` are cancelled, which frees
    the lanes the check needs."""
    t0 = time.perf_counter()
    manager = engine.cache_manager
    live = sorted(engine._active.items(), key=lambda kv: len(kv[1].tokens))
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
                     lane_rows(engine, lane, n - served.tail, n)))
    out = {"engine_lanes_live": len(live), "engine_lanes_checked": len(held)}
    for rid in list(in_flight):
        engine.cancel(rid)
    if not held:
        # no lane was decoding when the window closed (a rehearsal of three
        # lanes can end so): nothing was checked, so the run is not correct
        out["engine_ok"] = False
        return out
    rows_err, deficits, margins = [], [], []
    for tokens, prompt_len, theirs in held:
        n = len(tokens) - 1
        lane, _ = manager.alloc(-1, tokens[:n])
        try:
            logits, _ = served.prefill(lane, tokens[:n])
            mine = lane_rows(engine, lane, n - served.tail, n)
        finally:
            manager.free(lane)
        rows_err.append(np.sqrt(((theirs - mine) ** 2).mean((1, 2, 3))
                                / (mine ** 2).mean((1, 2, 3))))
        # position i predicts token i + 1; the engine chose those from the
        # prompt's last position on
        chosen = np.arange(n - served.tail, n) >= prompt_len - 1
        deficit, margin = served.deficits(logits, tokens[n - served.tail + 1:])
        deficits.append(deficit[chosen])
        margins.append(margin[chosen])
    manager.pool.check_invariants()
    manager.window_pool.check_invariants()
    rows_err = np.asarray(rows_err).reshape(len(held), -1)   # [lanes, layers]
    deficits, margins = np.concatenate(deficits), np.concatenate(margins)
    out.update({
        "engine_rows_checked": int(served.tail * len(held)),
        "engine_rows_max_rel_rms_err": float(rows_err.max()),
        "engine_rows_rel_rms_err_by_layer": [
            float(e) for e in rows_err.max(0)],
        "engine_tokens_served_checked": int(deficits.size),
        "engine_tokens_served_best": int((deficits == 0).sum()),
        "engine_token_served_max_deficit": float(deficits.max()),
        "engine_token_served_rms_deficit": float(
            np.sqrt((deficits ** 2).mean())),
        "served_margin_p50": float(np.median(margins)),
        "engine_tol": [ENGINE_ROWS_TOL, ENGINE_TOKEN_TOL],
        "engine_check_s": time.perf_counter() - t0})
    out["engine_ok"] = bool(
        held and deficits.size
        and out["engine_rows_max_rel_rms_err"] <= ENGINE_ROWS_TOL
        and out["engine_token_served_rms_deficit"] <= ENGINE_TOKEN_TOL * unit)
    return out


def layer_check(mine: dict, variables, cell, chosen) -> dict:
    """Every expert layer of the engine's model against the reference's
    layer ON THE INPUTS THE SYSTEM'S LAYER REALLY SAW (``served``): the
    routing weights it applied against the reference router's for the same
    experts on the same router input, whether each expert it chose is among
    the reference's ``k`` most probable (a tie inside the weight limit is
    no fault), and its output against the reference's sum over the same
    experts on the same expert input. ``chosen`` is the reference's choice
    in its OWN forward ``[layers, positions, k]``: where the system's
    differs, the rounding of the layers before has moved the input
    (counted, not judged)."""
    import jax

    picked = mine["experts"].astype(np.int32)
    sums, probs = jax.jit(
        ref_driver.reference_module(cell).configured_layers(
            cell.config["model"]))(
        variables["params"], mine["router_input"], mine["input"], picked)
    sums, probs = np.asarray(sums), np.asarray(probs)
    k = picked.shape[-1]
    theirs = np.take_along_axis(probs, picked, -1)   # [layers, positions, k]
    weights = theirs / theirs.sum(-1, keepdims=True)
    weight_err = float(np.abs(mine["weights"] / weights - 1.0).max())
    kth = np.sort(probs, -1)[..., -k][..., None]
    beside = (theirs < kth * (1.0 - LAYER_WEIGHT_TOL)).any(-1)
    err = np.sqrt(((mine["output"] - sums) ** 2).mean((1, 2)))
    unit = np.sqrt((sums ** 2).mean((1, 2)))         # per layer
    same = (np.sort(picked, -1) == np.sort(chosen, -1)).all(-1)
    out = {"layer_positions_checked": int(beside.size),
           "layer_weight_max_rel_err": weight_err,
           "layer_experts_beside_reference": int(beside.sum()),
           "layer_output_rel_rms_err": float((err / unit).max()),
           "layer_tol": [LAYER_WEIGHT_TOL, LAYER_OUTPUT_TOL],
           "experts_positions_checked": int(same.shape[1]),
           "experts_differ_positions": int((~same.all(0)).sum()),
           "experts_differ_layer_positions": int((~same).sum())}
    out["layers_ok"] = bool(
        weight_err <= LAYER_WEIGHT_TOL and not beside.any()
        and out["layer_output_rel_rms_err"] <= LAYER_OUTPUT_TOL)
    return out


def reference_check(engine, variables, cell, seed: int,
                    served: Served = None) -> dict:
    """The engine against the configuration's float32 reference, which
    reads the weights as made (``variables``), outside the window: module
    docstring, ``correct``."""
    import jax

    served = served or Served(engine, check_sizes(cell)[2])
    logits = jax.jit(
        ref_driver.reference_module(cell).configured(cell.config["model"]),
        static_argnames=("tail", "with_experts"))  # ``given`` is traced
    prompt, decode, tail, _, answer_tokens = check_sizes(cell)
    manager = engine.cache_manager
    recycled = manager.window_pool.recycled

    # the engine's own answers, rated by the checked programs and by the
    # reference on the experts those chose
    by_served, by_reference = [], []
    answers = engine_answers(engine, cell, seed)
    for asked, got in answers:
        tokens = np.concatenate([asked, got])
        lane, _ = manager.alloc(-1, tokens[:-1])
        try:
            mine, sown = served.prefill(lane, tokens[:-1])
        finally:
            manager.free(lane)
        by_served.append(served.deficits(mine, tokens[-tail:])[0][-len(got):])
        rated = np.asarray(logits(
            variables["params"], tokens[:-1], tail=len(got),
            given=np.asarray(sown["experts"], np.int32)))
        by_reference.append(rated.max(-1) - rated[np.arange(len(got)), got])
    by_served, by_reference = map(np.concatenate, (by_served, by_reference))
    complete = all(len(t) == answer_tokens for _, t in answers)

    tokens = np.random.default_rng([seed, 4]).integers(
        1, cell.config["model"]["vocab_size"], prompt + decode,
        dtype=np.int32)
    mine = served.sequence(tokens, prompt)
    # the reference sums over the SYSTEM'S experts at the positions
    # compared (``layer_check`` holds that choice to the router): an expert
    # exchanged at a near-tie moves the logits more than any rounding
    reference, chosen, _ = logits(
        variables["params"], tokens, tail=tail + decode, with_experts=True,
        given=mine["experts"].astype(np.int32))
    # the system's logits at position i predict token i + 1: the prompt's
    # last ``tail`` positions and the decode steps are the sequence's last
    # ``tail + decode``
    reference = np.asarray(reference)
    err, unit = np.abs(mine["logits"] - reference), float(reference.std())
    manager.pool.check_invariants()
    manager.window_pool.check_invariants()
    out = {"reference_logit_std": unit,
           "reference_positions_checked": int(err.shape[0]),
           "reference_max_abs_err": float(err.max()),
           "reference_decode_max_abs_err": float(err[tail:].max()),
           "reference_rms_err": float(np.sqrt((err ** 2).mean())),
           "reference_decode_rms_err": float(
               np.sqrt((err[tail:] ** 2).mean())),
           "engine_tokens_checked": int(by_reference.size),
           "engine_tokens_reference_best": int((by_reference == 0).sum()),
           "engine_token_max_deficit": float(by_reference.max()),
           "engine_token_rms_deficit": float(
               np.sqrt((by_reference ** 2).mean())),
           "answer_token_served_max_deficit": float(by_served.max()),
           "answer_token_served_rms_deficit": float(
               np.sqrt((by_served ** 2).mean())),
           "window_pages_recycled_in_check": int(
               manager.window_pool.recycled - recycled),
           "reference_tol_in_std": [REFERENCE_MAX_TOL, REFERENCE_RMS_TOL,
                                    REFERENCE_TOKEN_TOL]}
    positions = mine["experts"].shape[1]
    layers = layer_check(mine, variables, cell,
                         np.asarray(chosen)[:, -positions:])
    out.update(layers)
    out["reference_ok"] = bool(
        complete and layers["layers_ok"]
        and out["window_pages_recycled_in_check"] > 0
        and out["reference_max_abs_err"] <= REFERENCE_MAX_TOL * unit
        and out["reference_rms_err"] <= REFERENCE_RMS_TOL * unit
        and out["reference_decode_rms_err"] <= REFERENCE_RMS_TOL * unit
        and out["engine_token_rms_deficit"] <= REFERENCE_TOKEN_TOL * unit)
    return out


def set_up(cell, seed: int, t_process: float):
    """``serve_closed_loop_ref.set_up`` with this file's engine, warm-up
    and reference check."""
    device = harness.own_the_chip(cell.chips, cell.tiny)

    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = harness.CompileClock()
    phases = {"import_s": time.perf_counter() - t_process}
    model, variables = ref_driver.build_model(cell, seed)
    engine = build_engine(cell, model, variables)
    phases["weights_and_engine_s"] = time.perf_counter() - t_process
    buckets = warm_up(engine, cell, seed)
    phases["warm_up_s"] = time.perf_counter() - t_process
    served = Served(engine, check_sizes(cell)[2])
    reference = reference_check(engine, variables, cell, seed, served)
    phases["reference_s"] = time.perf_counter() - t_process
    return device, clock, engine, served, reference, buckets, phases


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    device, clock, engine, served, reference, buckets, phases = set_up(
        cell, seed, t_process)
    job = cell.traffic
    vocab = cell.config["model"]["vocab_size"]
    streams = [traffic_gen.client_stream(job, seed, c, vocab)
               for c in range(job["clients"])]
    harness.log(f"{len(streams)} clients; prefill chunk "
                f"{engine.prefill_chunk}, warmed buckets {buckets}")
    clients = serving.Clients(engine)
    profiler = harness.ProfilerWindow(trace, job["trace_s"])
    holding = {}                      # client -> its open record
    first_round = set()
    start = end = None
    live = []
    while True:
        now = time.perf_counter()
        for c, stream in enumerate(streams):
            rec = holding.get(c)
            if rec is None or (rec["id"] not in clients.open):
                holding[c] = clients.submit(next(stream), now, client=c)
                if rec is None and holding[c]["id"] is not None:
                    first_round.add(holding[c]["id"])
        if start is None and not (first_round & clients.open):
            start, end = now, now + seconds
            profiler.arm(start, seconds)
        elif start is not None:
            if now >= end:
                profiler.close()
                break
            profiler.poll(now)
        engine.step()
        live.append((time.perf_counter(), clients.live_tokens))

    inside = [r for r in clients.records.values()
              if start <= r["submit_s"] <= end]
    checks = serving.serving_checks(engine, clients, clock, (start, end),
                                    reference, buckets, phases)
    done = [r for r in inside if r["id"] not in clients.open]
    ttft = [(r["stamps"][0] - r["submit_s"]) * 1e3 for r in inside
            if r["stamps"]]
    samples = {
        "token_s": clients.token_s,
        "gaps": clients.gaps(start, end),
        "closed_ttft_ms": ttft,
        "live_tokens": live,
        "lanes": cell.deploy["lanes"],
        "requests_done": len(done),
        "prompt_tokens_done": sum(len(r["request"].prompt) for r in done),
    }
    harness.log(f"requests submitted in the window {len(inside)}, returned "
                f"{len(done)} ({len(done) / seconds:.2f}/s); prompt tokens "
                f"prefilled/s {samples['prompt_tokens_done'] / seconds:.0f}; "
                f"closed-loop ttft ms p50 {harness.percentile(ttft, 50)}")
    counters = serving.counters(engine)
    harness.log("routing and pool counters " + json.dumps(
        {k: v for k, v in counters.items()
         if k.startswith(("moe_", "pages_in_use_", "window_pages_",
                          "admits_refused_"))}))
    spans = harness.program_spans(start)
    reduced = profiler.reduce() if trace else None
    # everything the window is read from is taken; now what the engine's
    # programs left in flight, which ends those requests
    checks.update(engine_check(engine, served, clients.open,
                               reference["reference_logit_std"]))
    checks["correct"] = checks["correct"] and checks["engine_ok"]
    return harness.Run(
        cell=cell, device=device, setup_s=start - t_process,
        window=(start, end), attempted=len(inside),
        failed=len(clients.refused) + checks["wrong_results"],
        correct=checks["correct"], checks=checks, samples=samples,
        spans=spans, counters=counters, traced=profiler.traced, trace=reduced,
        peaks=harness.device_peaks(device, cell.tiny))
