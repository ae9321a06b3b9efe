"""Serving, closed loop, for a configuration whose lanes keep two kinds of
state in one page pool (keys and values in its attention layers, the gated
short convolution's last inputs in tail pages of its other layers) and whose
requests share long prefixes: the loop of ``serve_closed_loop_ref.py``
(``clients`` callers, each sending its next request when its last one has
returned; the first round is warm-up and registers the tenants' prefixes;
tokens count if delivered inside the window) with the same ``harness.Run``
and ``samples`` keys, so that every reader of a closed-loop cell reads it.

What differs from ``serve_closed_loop_swa.py`` (window layers, no prefix
cache):

- the engine is built with the prefix cache ON and chunked prefill for the
  cold prompts of the warm-up;
- ``correct`` compares what the timed path produces at the timed sizes, in
  two steps. Before the window (:func:`reference_check`): a prompt of
  ``CHECK_PREFIX + CHECK_OWN`` tokens whose first ``CHECK_PREFIX`` another
  request has registered through the engine's ``submit``, admitted into the
  engine's OWN pool so that its prefill STARTS from the matched pages (keys
  and values) and their tails (the convolution state), then decoded for
  ``CHECK_DECODE`` steps, by programs of the check's own (:class:`Served`:
  the engine's return tokens only); its logits at every position after the
  match's end (the first 16 apart: where a wrong state shows) and at every
  decode step against the reference's forward of the same tokens from
  position 0; the same prompt admitted COLD in chunks, to the same limits;
  each expert layer on the input it really saw, the choice held to the
  router's scores plus bias. After the window (:func:`engine_check`): what
  the ENGINE'S OWN tick and prefill programs wrote into the pool (rows of K
  and V, the convolution state of the lanes) and returned for the requests
  in flight when the window closed, every lane live, against ``Served`` on
  the same sequences. The reference holds ``Served``; ``Served`` holds the
  timed programs.

From ``serve_closed_loop_ref.py`` as it is: ``build_model`` (which makes an
older program say at once, before any compile, that it cannot run the
configuration) and ``reference_module``; from ``serve_closed_loop_swa.py``:
``warm_up``; from ``serving.py``: ``Clients``, ``serving_checks``,
``counters``.
"""

from __future__ import annotations

import contextlib
import functools
import json
import time

import numpy as np

from perfbench import harness, serving, traffic as traffic_gen
from perfbench.drivers import serve_closed_loop_ref as ref_driver
from perfbench.drivers import serve_closed_loop_swa as swa_driver

CHECK_PREFIX, CHECK_OWN, CHECK_DECODE, CHECK_FIRST = 3072, 512, 64, 16
ENGINE_LANES, ENGINE_TAIL = 8, 64

# How far the system may stand from the float32 reference. Limits from two
# readings each on the chip at the published widths (my chip runs, PR 33;
# PERF.md section 6; ``perfbench/probe_lfm2.py`` takes both): the largest
# reading of the engine as built over its seeds, and the smallest reading of
# what has to come out NOT correct. A fault is refused by one of the limits
# and not by each.
#
# 1. Every expert layer against the reference's layer ON THE INPUT IT REALLY
# SAW (:func:`layer_check`): what refuses a lower precision in the expert
# layer and a wrong gate.
# - ``LAYER_WEIGHT_TOL``, the routing weights the layer applied against the
#   reference router's for the same experts, largest relative error: as
#   built 0 to 3.6e-7 over eight readings (both sides float32 at ``highest``); a router computed
#   in bfloat16 0.0090, a softmax for the sigmoid 1.87, weights not
#   normalised 2.71. The limit is 1e-4. An expert the layer chose counts as
#   beside the reference's when the reference ranks it (score + bias) under
#   its own fourth by more than that limit (a tie inside the limit is no
#   fault): as built 0 of 6,912 layer-positions; the bias left out of the
#   choice 2,465, a softmax 2,443, a bfloat16 router 225; none is allowed.
# - ``LAYER_OUTPUT_TOL``, the layer's output against the reference's sum
#   over the same experts, rms over the layer's rms, the worst layer: as
#   built 0.00287-0.00288 (three bfloat16 roundings: the activation, the
#   kernel's output, the sum); experts rounded to int8 with a scale per
#   column 0.01507 (read on the first 6 layers alone: a second copy of
#   twelve layers' experts does not fit, and a layer's reading does not
#   depend on the depth; as built there 0.00287). The limit is 0.0066, the
#   geometric middle, as the two accepted expert cells have it (the same
#   kernels, the same readings).
#
# 2. The logits of the whole model after a prefill that STARTED from a
# prefix hit (3,072 tokens matched), after a cold chunked prefill of the same
# prompt, and through 64 decode steps (``Served``), in units of the standard
# deviation of the reference's logits (0.905 here). The reference sums over
# the SYSTEM'S experts at the positions compared (``given``): sigmoid scores
# of random weights lie 0.03 apart at the fourth place, so the rounding of
# the layers before hands an expert over at 317-341 of 576 positions, and an
# expert exchanged moves the logits by more than any arithmetic does. The
# hit and the cold prefill read THE SAME to every digit (the state and the
# rows a hit resumes are those a cold prefill writes). These limits refuse a
# wrong state, position or page:
# - ``REFERENCE_RMS_TOL``: as built 0.0235-0.0243 over all 576 positions and
#   0.0232-0.0240 over the decode steps, eight readings (bfloat16 through 11 convolution layers
#   whose products B * u and C * c each round; the first 6 layers alone read
#   0.0154); the state zeroed where a prefill starts 0.406 (1.19 over the
#   decode steps), read a position stale 0.427, a softmax for the sigmoid
#   0.178, weights not normalised 0.778. The limit is 0.07: 2.9 times the
#   largest as built, 2.5 times under the smallest wrong gate, 5.8 under the
#   smallest wrong state.
# - ``REFERENCE_FIRST_TOL``, rms over the first 16 positions after the
#   match's end alone, where a wrong state at the hit shows at full size: as
#   built 0.024-0.045; zeroed 0.514, stale 0.547. The limit is 0.15, the
#   geometric middle: 3.4 times each way.
# - ``REFERENCE_MAX_TOL``: the largest error over 38 million logits swings
#   between seeds, as built 0.14-0.62 of the unit; a wrong state 6.3, a
#   softmax 1.19. The limit is 2.0: 3.2 times the one, a third of the other
#   (the softmax is the layer limits' to refuse).
# They do NOT tell a lower precision apart (int8 experts read rms 0.0161
# where as built read 0.0154; a bfloat16 router 0.0244 where 0.0243), nor a
# bias left out (0.0243: the reference sums over the system's choice): the
# layer limits do. And QK-norm left out reads 0.0253, inside the as-built
# range: with norm weights of 1 and projections drawn at 0.02 a head's
# values already have an rms of 0.9 +- 0.08, so the norm is all but the
# identity at these weights; tests/test_lfm2_serving.py, where the norm
# weights are moved off 1, refuses it at 30 times its tolerance.
#
# 3. The ENGINE'S OWN PROGRAMS (the timed prefill, admitted on a prefix hit
# and padded to its bucket, and the 48-lane tick) against the check's
# (``Served``: cold, in chunks, one lane; held to the reference by 2.), on 8
# of the requests in flight when the window closes (:func:`engine_check`).
# The two sides differ in the shapes of their programs, so where the
# router's scores tie they choose other experts and the rows after it move:
# the readings grow with the depth and are far above bfloat16's own. The
# second reading is a fault planted in the engine's programs ALONE
# (``probe_lfm2.py`` ``engine_stale_tables``: block tables that stopped
# following the allocator):
# - ``ENGINE_ROWS_TOL``: the keys and values the engine wrote at a lane's
#   last 64 positions against ``Served``'s, rms of the difference over the
#   rms of the rows, the worst lane and attention layer: as built
#   0.112-0.142 over eight readings (0.011 in the first attention layer,
#   0.063 in the second); stale tables 1.0. The limit is 0.35: 2.5 times
#   the one, a third of the other.
# - ``ENGINE_STATE_TOL``: the same for the convolution state the lane holds
#   (its last two positions, the worst of the eleven layers): as built
#   0.209-0.352 (0 in the first layer, growing to the last); stale tables
#   1.0. The limit is 0.6, the geometric middle: 1.7 times each way.
# - ``ENGINE_TOKEN_TOL``: how far the tokens the engine returned at those
#   positions (256-281 of them a run) stand below ``Served``'s best, rms in
#   the logits' unit: as built 0.099-0.196 (a quarter of the tokens are not
#   ``Served``'s best: the best logit leads the second by 0.15 at the
#   median, less than an exchanged expert moves it); stale tables 3.77. The
#   limit is 0.8, near the geometric middle: 4 times each way.
# A state zeroed at the hit in the engine's programs alone reads inside
# these limits (rows 0.117, state 0.305, tokens 0.155): sixty positions
# after the hit the filter's two taps have forgotten it. That fault is 2.'s
# to refuse, in ``Served``, and tests/test_lfm2_serving.py's, bit for bit.
REFERENCE_MAX_TOL = 2.0
REFERENCE_RMS_TOL = 0.07
REFERENCE_FIRST_TOL = 0.15
ENGINE_ROWS_TOL = 0.35
ENGINE_STATE_TOL = 0.6
ENGINE_TOKEN_TOL = 0.8
LAYER_WEIGHT_TOL = 1e-4
LAYER_OUTPUT_TOL = 0.0066


def check_sizes(cell) -> tuple:
    """``(prefix, own part, decode steps, first, engine tail)`` of the
    check: the constants above at the published sizes; a rehearsal's scale
    with its chunk (a prefix of two chunks, an own part of one)."""
    if not cell.tiny:
        return CHECK_PREFIX, CHECK_OWN, CHECK_DECODE, CHECK_FIRST, ENGINE_TAIL
    chunk = cell.deploy["prefill_chunk"]
    return 2 * chunk, chunk, 4, 4, 8


def build_engine(cell, model, variables):
    """The engine as ``serving.build_engine`` builds it, with chunked
    prefill and the prefix cache ON."""
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
        prefill_bucket=deploy["prefill_bucket"], prefix_cache=True)


warm_up = swa_driver.warm_up


@contextlib.contextmanager
def trie_off(pool):
    """Inside, ``pool`` matches and registers no prefix: an admission is
    cold whatever the trie holds."""
    was, pool.prefix_cache = pool.prefix_cache, False
    try:
        yield
    finally:
        pool.prefix_cache = was


class Served:
    """What the model computes through the ENGINE'S pool, by programs of the
    check's own (the engine's return tokens only, so logits and routing need
    them): a chunk that only writes, a chunk that also gives the logits of
    its last ``tail`` tokens and its routing, a step; on ``engine.params``
    through its dequantisation seam, in a lane of ``engine.cache_manager``
    claimed and freed by the caller, so that the trie matches, shares and
    registers pages exactly as for a request and a prefill starts where the
    match ends. Every chunk has ``engine.prefill_chunk`` rows, the first
    ``count`` of them tokens (the model is told which). ``model`` and
    ``params`` are the engine's unless a probe plants a fault."""

    def __init__(self, engine, model=None, params=None):
        import jax
        import jax.numpy as jnp

        self.engine, self.params = engine, params
        model = model or engine.model
        donate = (1,) if jax.default_backend() == "tpu" else ()

        @functools.partial(jax.jit, donate_argnums=donate,
                           static_argnames=("tail",))
        def forward(params, cache, ids, at, count, table, tail=0):
            """Writes the first ``count`` of ``ids`` at positions ``at`` on;
            ``tail`` > 0: also the logits of the last ``tail`` of them and
            the routing at those."""
            rows = jnp.arange(ids.shape[0], dtype=jnp.int32)
            logits, mut = model.apply(
                {"params": engine._dequant_params(params), "cache": cache},
                ids[None], (at + rows)[None], (rows < count)[None],
                decode=True, cache_positions=at[None],
                block_tables=table[None],
                mutable=["cache"] + (["routing"] if tail else []))
            if not tail:
                return mut["cache"], None, None

            def last(x):  # [rows, ...] -> its last ``tail`` tokens
                return jax.lax.dynamic_slice_in_dim(x, count - tail, tail, 0)

            # one leaf [expert layers, 1, rows, width] of each name
            sown = {jax.tree_util.keystr(path[-2:-1]).strip("[']"):
                    jax.vmap(last)(leaf[:, 0])
                    for path, leaf in jax.tree_util.tree_flatten_with_path(
                        mut["routing"])[0]}
            return mut["cache"], last(logits[0]).astype(jnp.float32), sown

        @jax.jit
        def rate(logits, tokens):
            """How far each of ``tokens`` stands below the best logit of its
            row, and the best above the second."""
            top = jax.lax.top_k(logits, 2)[0]
            at = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
            return top[:, 0] - at, top[:, 0] - top[:, 1]

        self._forward, self._rate = forward, rate

    def _call(self, lane: int, ids, at: int, rows: int, tail: int = 0):
        import jax.numpy as jnp

        manager = self.engine.cache_manager
        padded = np.zeros(rows, np.int32)
        padded[:len(ids)] = ids
        manager.cache, logits, sown = self._forward(
            self.engine.params if self.params is None else self.params,
            manager.cache, jnp.asarray(padded),
            jnp.asarray(at, jnp.int32), jnp.asarray(len(ids), jnp.int32),
            jnp.asarray(manager.lane_tables(lane)), tail=tail)
        return logits, sown

    def prefill(self, lane: int, tokens, start: int, tail: int):
        """``tokens[start:]`` written at positions ``start`` on (what lies
        before is in the lane's matched pages), in chunks of
        ``engine.prefill_chunk`` rows: a first chunk with the remainder,
        then whole ones, so that the last is whole where there is more than
        one. Returns the logits of the last ``tail`` positions (on the
        device) and the routing there."""
        chunk, n = self.engine.prefill_chunk, len(tokens)
        first = (n - start) % chunk or min(chunk, n - start)
        starts = [start] + list(range(start + first, n, chunk))
        if min(first if len(starts) == 1 else chunk, n - start) < tail:
            raise ValueError(f"{n - start} tokens from {start} on give no "
                             f"tail of {tail}")
        for at in starts:
            out = self._call(lane, tokens[at:at + (first if at == start
                                                   else chunk)], at, chunk,
                             tail if at == starts[-1] else 0)
        return out

    def step(self, lane: int, token: int):
        """One decode step at the lane's next position: its logits (host)
        and routing."""
        manager = self.engine.cache_manager
        if not manager.ensure_page(lane):
            raise RuntimeError("the pool ran dry in the check")
        logits, sown = self._call(lane, [token], int(manager.lengths[lane]),
                                  1, 1)
        manager.lengths[lane] += 1
        return np.asarray(logits), sown

    def deficits(self, logits, tokens) -> tuple:
        """``(deficit, margin)`` of ``tokens`` under ``logits``, one row
        each: :func:`rate` on the device (the rows stay there)."""
        import jax.numpy as jnp

        deficit, margin = self._rate(logits, jnp.asarray(tokens, jnp.int32))
        return np.asarray(deficit), np.asarray(margin)

    def sequence(self, tokens, prompt_len: int, tail: int) -> dict:
        """The first ``prompt_len`` of ``tokens`` admitted (the trie
        matching what it holds of them) and prefilled from the match's end,
        the rest decoded one step each: ``matched`` tokens, ``logits`` (the
        last ``tail`` prompt positions, then every decode step) and what the
        expert layers saw, chose and gave there: ``input``, ``output``
        ``[layers, positions, hidden]``, ``experts``, ``weights`` ``[layers,
        positions, k]``."""
        manager = self.engine.cache_manager
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
        finally:
            manager.free(lane)
        return {"matched": int(matched), "logits": np.concatenate(out),
                **{k: np.concatenate(v, axis=1) for k, v in routing.items()}}


def _pools(engine) -> dict:
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
    page = (manager.lane_tables(lane)[pos // manager.page_size][None, :]
            + np.arange(layers)[:, None] * manager.num_pages)
    pools = _pools(engine)
    return np.stack([np.asarray(pools[name][page, pos % manager.page_size],
                                np.float32)
                     for name in ("cached_key", "cached_value")], axis=1)


def lane_state(engine, lane: int, length: int) -> np.ndarray:
    """The convolution state ``lane`` holds after ``length`` positions, of
    every convolution layer, read through the manager's HOST table: the
    operator's input at the last ``conv_L_cache - 1`` positions, ``[conv
    layers, rows, hidden]`` float32."""
    cfg, manager = engine.model.cfg, engine.cache_manager
    rows = cfg.conv_L_cache - 1
    pos = np.arange(length - rows, length)
    layers = cfg.layer_types.count("conv")
    page = (manager.lane_tables(lane)[pos // manager.page_size][None, :]
            + np.arange(layers)[:, None] * manager.num_pages)
    return np.asarray(_pools(engine)["conv_state"][page, pos % rows],
                      np.float32)


def _rel_rms(theirs, mine, axes) -> np.ndarray:
    return np.sqrt(((theirs - mine) ** 2).mean(axes) / (mine ** 2).mean(axes))


def engine_check(engine, served: Served, in_flight, unit: float,
                 tail: int) -> dict:
    """The ENGINE'S OWN PROGRAMS against the checked ones (``Served``, which
    ``reference_check`` holds to the reference), on the requests in flight
    when the window closed: what the timed tick and prefill programs
    produced with every lane live, each admitted on a prefix hit. For
    ``ENGINE_LANES`` decoding lanes, those with the fewest tokens out and
    those with the most: the keys and values the engine wrote at the lane's
    last ``tail`` positions in every attention layer, the convolution state
    the lane holds in every convolution layer, and the tokens it returned at
    those positions, against ``Served``'s forward of the same sequence from
    position 0 in a lane of the same pool (cold: it shares no page with the
    engine's lane). The engine's rows are read first; then the requests ``in_flight`` are cancelled, which
    frees the lanes the check needs."""
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
                     lane_rows(engine, lane, n - tail, n),
                     lane_state(engine, lane, n)))
    out = {"engine_lanes_live": len(live), "engine_lanes_checked": len(held)}
    for rid in list(in_flight):
        engine.cancel(rid)
    if not held:
        # no lane was decoding when the window closed (a rehearsal of three
        # lanes can end so): nothing was checked, so the run is not correct
        out["engine_ok"] = False
        return out
    rows_err, state_err, deficits, margins = [], [], [], []
    for tokens, prompt_len, rows, state in held:
        n = len(tokens) - 1
        # cold: the engine's lane started from a prefix hit, ``Served``'s
        # computes every position itself (and shares no page with it)
        with trie_off(manager.pool):
            lane, _ = manager.alloc(-1, tokens[:n])
        try:
            logits, _ = served.prefill(lane, tokens[:n], 0, tail)
            rows_err.append(_rel_rms(rows, lane_rows(engine, lane, n - tail,
                                                     n), (1, 2, 3)))
            state_err.append(_rel_rms(state, lane_state(engine, lane, n),
                                      (1, 2)))
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
    state_err = np.asarray(state_err).reshape(len(held), -1)
    deficits, margins = np.concatenate(deficits), np.concatenate(margins)
    out.update({
        "engine_rows_checked": int(tail * len(held)),
        "engine_rows_max_rel_rms_err": float(rows_err.max()),
        "engine_rows_rel_rms_err_by_layer": [
            float(e) for e in rows_err.max(0)],
        "engine_state_max_rel_rms_err": float(state_err.max()),
        "engine_state_rel_rms_err_by_layer": [
            float(e) for e in state_err.max(0)],
        "engine_tokens_served_checked": int(deficits.size),
        "engine_tokens_served_best": int((deficits == 0).sum()),
        "engine_token_served_max_deficit": float(deficits.max()),
        "engine_token_served_rms_deficit": float(
            np.sqrt((deficits ** 2).mean())),
        "served_margin_p50": float(np.median(margins)),
        "engine_tol": [ENGINE_ROWS_TOL, ENGINE_STATE_TOL, ENGINE_TOKEN_TOL],
        "engine_check_s": time.perf_counter() - t0})
    out["engine_ok"] = bool(
        held and deficits.size
        and out["engine_rows_max_rel_rms_err"] <= ENGINE_ROWS_TOL
        and out["engine_state_max_rel_rms_err"] <= ENGINE_STATE_TOL
        and out["engine_token_served_rms_deficit"] <= ENGINE_TOKEN_TOL * unit)
    return out


def layer_check(mine: dict, variables, cell, chosen) -> dict:
    """Every expert layer of the engine's model against the reference's
    layer ON THE INPUT THE SYSTEM'S LAYER REALLY SAW (``served``): the
    routing weights it applied against the reference router's for the same
    experts on the same input, whether each expert it chose is among the
    ``k`` the reference ranks highest by score plus bias (a tie inside the
    weight limit is no fault), and its output against the reference's sum
    over the same experts. ``chosen`` is the reference's choice in its OWN
    forward ``[layers, positions, k]``: where the system's differs, the
    rounding of the layers before has moved the input (counted, not
    judged)."""
    import jax

    picked = mine["experts"].astype(np.int32)
    sums, scores, ranked = (np.asarray(x) for x in jax.jit(
        ref_driver.reference_module(cell).configured_layers(
            cell.config["model"]))(variables["params"], mine["input"], picked))
    k = picked.shape[-1]
    theirs = np.take_along_axis(scores, picked, -1)   # [layers, positions, k]
    scaling = float(cell.config["model"].get("routed_scaling_factor", 1.0))
    weights = theirs / (theirs.sum(-1, keepdims=True) + 1e-6) * scaling
    weight_err = float(np.abs(mine["weights"] / weights - 1.0).max())
    kth = np.sort(ranked, -1)[..., -k][..., None]
    beside = (np.take_along_axis(ranked, picked, -1)
              < kth * (1.0 - LAYER_WEIGHT_TOL)).any(-1)
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
    """The engine against the configuration's float32 reference, which reads
    the weights as made (``variables``), outside the window: module
    docstring, ``correct``."""
    import jax

    served = served or Served(engine)
    logits = jax.jit(
        ref_driver.reference_module(cell).configured(cell.config["model"]),
        static_argnames=("tail", "with_experts"))  # ``given`` is traced
    prefix, own, decode, first, _ = check_sizes(cell)
    vocab = cell.config["model"]["vocab_size"]
    rng = np.random.default_rng([seed, 4])
    tokens = rng.integers(1, vocab, prefix + own + decode, dtype=np.int32)
    # another request registers the prefix, through the engine itself
    other = np.concatenate([tokens[:prefix], rng.integers(
        1, vocab, engine.page_size * 2, dtype=np.int32)])
    engine.submit(other, max_length=2)
    engine.drain()

    def compared(mine):
        # the reference sums over the SYSTEM'S experts at the positions
        # compared (``layer_check`` holds that choice to the router)
        reference, chosen, _ = logits(
            variables["params"], tokens, tail=own + decode,
            with_experts=True, given=mine["experts"].astype(np.int32))
        # the system's logits at position i predict token i + 1: the own
        # part's positions and the decode steps are the sequence's last
        reference = np.asarray(reference)
        return np.abs(mine["logits"] - reference), float(
            reference.std()), np.asarray(chosen)

    def rms(x):
        return float(np.sqrt((x ** 2).mean()))

    hit = served.sequence(tokens, prefix + own, own)
    err, unit, chosen = compared(hit)
    with trie_off(engine.cache_manager.pool):
        cold = served.sequence(tokens, prefix + own, own)
    cold_err = compared(cold)[0]
    engine.cache_manager.pool.check_invariants()
    out = {"reference_logit_std": unit,
           "reference_positions_checked": int(err.shape[0]),
           "hit_matched_tokens": hit["matched"],
           "cold_matched_tokens": cold["matched"],
           "reference_max_abs_err": float(err.max()),
           "reference_rms_err": rms(err),
           "reference_first_rms_err": rms(err[:first]),
           "reference_decode_rms_err": rms(err[own:]),
           "reference_cold_max_abs_err": float(cold_err.max()),
           "reference_cold_rms_err": rms(cold_err),
           "reference_cold_decode_rms_err": rms(cold_err[own:]),
           "hit_cold_logit_rms_diff": rms(hit["logits"] - cold["logits"]),
           "reference_tol_in_std": [REFERENCE_MAX_TOL, REFERENCE_RMS_TOL,
                                    REFERENCE_FIRST_TOL]}
    positions = hit["experts"].shape[1]
    layers = layer_check(hit, variables, cell, chosen[:, -positions:])
    out.update(layers)
    out["reference_ok"] = bool(
        layers["layers_ok"]
        and hit["matched"] == prefix and cold["matched"] == 0
        and max(out["reference_max_abs_err"],
                out["reference_cold_max_abs_err"]) <= REFERENCE_MAX_TOL * unit
        and max(out["reference_rms_err"], out["reference_decode_rms_err"],
                out["reference_cold_rms_err"],
                out["reference_cold_decode_rms_err"])
        <= REFERENCE_RMS_TOL * unit
        and out["reference_first_rms_err"] <= REFERENCE_FIRST_TOL * unit)
    return out


def set_up(cell, seed: int, t_process: float):
    """``serve_closed_loop_ref.set_up`` with this file's engine, warm-up and
    reference check."""
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
    served = Served(engine)
    reference = reference_check(engine, variables, cell, seed, served)
    phases["reference_s"] = time.perf_counter() - t_process
    return device, clock, engine, served, reference, buckets, phases


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    device, clock, engine, served, reference, buckets, phases = set_up(
        cell, seed, t_process)
    job = cell.traffic
    vocab = cell.config["model"]["vocab_size"]
    streams = [traffic_gen.client_stream(job, seed, c, vocab)
               for c in range(job["closed_loop"]["clients"])]
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
                f"admitted/s {samples['prompt_tokens_done'] / seconds:.0f}; "
                f"closed-loop ttft ms p50 {harness.percentile(ttft, 50)}")
    counters = serving.counters(engine)
    harness.log("routing, prefix and state counters " + json.dumps(
        {k: v for k, v in counters.items()
         if k.startswith(("moe_", "state_", "kv_page_", "prefill_tokens_",
                          "prefix_"))}))
    spans = harness.program_spans(start)
    reduced = profiler.reduce() if trace else None
    # everything the window is read from is taken; now what the engine's
    # programs left in flight, which ends those requests
    checks.update(engine_check(engine, served, clients.open,
                               reference["reference_logit_std"],
                               check_sizes(cell)[4]))
    checks["correct"] = checks["correct"] and checks["engine_ok"]
    return harness.Run(
        cell=cell, device=device, setup_s=start - t_process,
        window=(start, end), attempted=len(inside),
        failed=len(clients.refused) + checks["wrong_results"],
        correct=checks["correct"], checks=checks, samples=samples,
        spans=spans, counters=counters, traced=profiler.traced, trace=reduced,
        peaks=harness.device_peaks(device, cell.tiny))
