"""Serving, closed loop, for a configuration with EVA attention over a page
pool of two classes in every layer (a tumbling window of exact rows beside
one pooled row a chunk: ``fleetx_tpu/models/gpt/eva.py``): the loop of
``serve_closed_loop_swa.py`` (``clients`` callers, each sending its next
request when its last one has returned; the first round is warm-up; tokens
count if delivered inside the window), with the same ``harness.Run`` and
``samples`` keys, so that every reader of a closed-loop cell reads it.

From ``serve_closed_loop_swa.py`` as it is: ``build_engine`` (chunked
prefill, no prefix cache; ``pool_tokens`` counts the SUMMARY class's rows
here, the engine sizes the window class itself) and ``warm_up``; from
``serve_closed_loop_ref.py``: ``build_model`` (which makes an older program
say at once, before any compile, that it cannot run the configuration) and
``reference_module``; from ``serving.py``: ``Clients``, ``serving_checks``,
``counters``.

``correct`` compares what the timed path produces at the timed sizes, in two
steps. Before the window (:func:`reference_check`): a sequence of
``CHECK_PROMPT`` bytes (two window boundaries behind it, a prompt that leaves
a chunk open) prefilled in chunks and then decoded for ``CHECK_DECODE``
steps, ACROSS a window's boundary, through the engine's OWN pool, allocators
and weights by programs of the check's own (:class:`Served`: the engine
returns tokens only), the logits of ALL EIGHT prediction heads at the last
``CHECK_TAIL`` prompt positions and at every decode step against the float32
reference's forward of the same bytes; and the engine's own answers through
``submit`` and ``step``, one of them past a boundary, rated by the reference.
After the window (:func:`engine_check`): what the ENGINE'S OWN tick and chunk
programs wrote into BOTH classes and returned for requests in flight when the
window closed, every lane live, against ``Served`` on the same sequences. The
reference holds ``Served``; ``Served`` holds the timed programs.
"""

from __future__ import annotations

import functools
import json
import time

import numpy as np

from perfbench import harness, serving, traffic as traffic_gen
from perfbench.drivers import serve_closed_loop_ref as ref_driver
from perfbench.drivers import serve_closed_loop_swa as swa_driver

CHECK_PROMPT, CHECK_DECODE, CHECK_TAIL = 4090, 40, 128
ANSWER_PROMPTS, ANSWER_TOKENS = (2300, 715), 96
ENGINE_LANES = 4      # lanes of the window's end held to the checked programs
ENGINE_CHUNKS = 32    # pooled rows a lane read back, and 16 exact rows each
NORM_WEIGHT_STD = 0.1

# How far the system may stand from the float32 reference, in units of the
# standard deviation of the reference's logits (0.82 here). Limits from two
# readings each on the chip at the published widths (my chip runs, PR 66;
# PERF.md section 6; ``perfbench/probe_evabyte.py`` takes both): the largest
# reading of the engine as built over its seeds, and the smallest reading of
# what has to come out NOT correct.
#
# 1. The logits of ALL EIGHT heads at the prompt's last 128 positions and at
# the 40 decode steps (``Served``), 430,080 logits.
# - ``REFERENCE_RMS_TOL``: rms error over all of them. As built 0.02039-
#   0.02099 over thirty seeds (eight layers of bfloat16 projections under a
#   float32 stream; the statistic is an average over 430,080 values: the
#   first session's runs read 0.02044-0.02093, the second's seventeen
#   0.02039-0.02099). The NEAREST PRECISION BELOW
#   the configuration's, the residual stream in bfloat16
#   (``bf16_residual``), reads 0.02303 and 0.02311 on two seeds: at 8 of 32
#   layers the stream's rounding adds an independent 0.0100 to 0.0207, no
#   more. The limit is 0.0218: 3.9% above the largest as built (the runs'
#   standard deviation is 0.5%), 5.3% under the stream in bfloat16. Every
#   wrong pattern reads far above: the query's own window's chunks attended
#   0.0355, the open chunk never closed 0.0274, padded rows pooled 0.0404,
#   ``mu`` and ``phi`` swapped 0.435, keys pooled before the rotation 0.437,
#   a sliding window 0.941, the unit offset left out 1.006.
# - ``REFERENCE_DECODE_RMS_TOL``: the same over the decode steps alone
#   (102,400 logits: noisier, so looser). As built 0.0203-0.0219; the open
#   chunk never closed 0.0422-0.0425, padded rows pooled 0.0741. The limit
#   is 0.03.
# - ``REFERENCE_MAX_TOL``: the largest error of any logit. As built
#   0.097-0.124 (five standard deviations of a logit's error, as the largest
#   of 430,080 is); the own window's chunks attended 0.188-0.214, the open
#   chunk never closed 0.244-0.252. The limit is 0.16, as the other serve
#   cells have it: 1.3 times the one, 0.85 of the other.
# - ``REFERENCE_TOKEN_TOL``: how far the tokens the ENGINE returned for the
#   two seeded requests (through ``submit`` and ``step``, 96 tokens each, one
#   past a window's boundary) stand below the reference's best (head 0), rms
#   over the 192. As built 0.0021-0.0088 (4 to 13 tokens of 192 are not the
#   reference's best, the largest deficit 0.092: near-ties); ``mu`` and
#   ``phi`` swapped 0.115, a sliding window 2.03. The limit is 0.03, as the
#   window cells have it: 3 times the one, a quarter of the other. It refuses
#   an engine whose tokens have come apart; the limits above refuse each
#   wrong pattern.
#
# 2. The pooled rows the check's lane holds in its summary pages, all 258
# chunks the sequence closed (255 by chunk programs, the prompt's open one and
# two more by ticks), against the reference's ``k~`` and ``v~``.
# - ``POOLED_ROW_TOL``: the worst row of any layer, rms of the difference
#   over the rms of the reference's row. As built 0.0265-0.0295 (a row of the
#   eighth layer carries seven layers' rounding); a chunk nobody closed 1.0
#   (``open_chunk_dropped``), one pooled over padded rows 1.33. The limit is
#   0.17, the geometric middle: six times each way.
# - ``POOLED_FIRST_LAYER_TOL``: all rows of the FIRST layer together (its
#   keys and values are one projection of the embedding: 2.1 million values,
#   and the rounding of no layer before), which is what tells the pooling's
#   own precision. As built 0.003793-0.003818 over 28 seeds; the two
#   pooling softmaxes and sums in bfloat16 (``bf16_pool``, whose logits read
#   as built's: 0.0207-0.0210) 0.004570 and 0.004575. The limit is 0.00417,
#   the geometric middle: 9.5% each way, against a spread over seeds of half
#   a percent.
#
# 3. The ENGINE'S OWN PROGRAMS (the timed chunk prefill and 24-lane tick)
# against the check's (``Served``, held to the reference by 1. and 2.), on 4
# of the requests in flight when the window closes (:func:`engine_check`).
# The second readings are faults planted in the engine's programs ALONE
# (``probe_evabyte.py`` ``engine_*``).
# - ``ENGINE_ROWS_TOL``: the pooled rows of a lane's last 32 closed chunks
#   and the exact rows of its window's last chunks, against ``Served``'s of
#   the same sequence, rms of the difference over the rms of the rows, the
#   worst lane, class and layer. As built 0.0107-0.0153 (0 in the first
#   layer, growing a layer: two programs round the same stream differently;
#   standard deviation 0.00025 over nine runs of the cell); the own window's
#   chunks attended by the engine's programs 0.0362 and 0.0372, ticks that
#   close no chunk 0.620, ``mu`` and ``phi`` swapped 0.971. The limit is
#   0.024, the geometric middle of 0.0153 and 0.0372: 1.5 times each way.
# - ``ENGINE_TOKEN_TOL``: how far the tokens the engine returned at those
#   positions stand below ``Served``'s best, rms in the logits' unit. As
#   built 0.0004-0.0044 (3 to 15 of 176-512 tokens are not ``Served``'s
#   best); ``mu`` and ``phi`` swapped in the engine's programs 0.127 (156
#   of 512 tokens are not ``Served``'s best). The limit is 0.03: 7 times
#   the largest as built, a quarter of the other.
REFERENCE_RMS_TOL, REFERENCE_DECODE_RMS_TOL = 0.0218, 0.03
REFERENCE_MAX_TOL, REFERENCE_TOKEN_TOL = 0.16, 0.03
POOLED_ROW_TOL, POOLED_FIRST_LAYER_TOL = 0.17, 0.00417
ENGINE_ROWS_TOL, ENGINE_TOKEN_TOL = 0.024, 0.03
# a rehearsal computes in float32 on the CPU, where system and reference
# differ only in the order of their sums (1e-6 is read): one limit for all
TINY_TOL = 2e-4


def limits(cell) -> dict:
    """The limits of ``correct``, by the names the checks give them."""
    if cell.tiny:
        return dict.fromkeys(("rms", "decode_rms", "max", "token", "row",
                              "first_layer", "engine_rows", "engine_token"),
                             TINY_TOL)
    return {"rms": REFERENCE_RMS_TOL, "decode_rms": REFERENCE_DECODE_RMS_TOL,
            "max": REFERENCE_MAX_TOL,
            "token": REFERENCE_TOKEN_TOL, "row": POOLED_ROW_TOL,
            "first_layer": POOLED_FIRST_LAYER_TOL,
            "engine_rows": ENGINE_ROWS_TOL, "engine_token": ENGINE_TOKEN_TOL}


def check_sizes(cell) -> tuple:
    """``(prompt, decode steps, tail, answers' prompts, answers' tokens)``
    of the check; a rehearsal's from its window and chunk."""
    if not cell.tiny:
        return (CHECK_PROMPT, CHECK_DECODE, CHECK_TAIL, ANSWER_PROMPTS,
                ANSWER_TOKENS)
    window = cell.config["model"]["eva_window_size"]
    chunk = cell.deploy["prefill_chunk"]
    # two boundaries behind the prompt, an open chunk, a third crossed
    return (3 * window - 5, 12, chunk // 2,
            (window + chunk + 5, chunk + 3), chunk // 2)


def build_model(cell, seed: int):
    """``serve_closed_loop_ref.build_model``, then the norm weights drawn OFF
    ZERO (normal, ``NORM_WEIGHT_STD``, from the seed): the norm is ``x^ (1 +
    w)`` and the initialiser's ``w = 0`` would hide an offset left out."""
    import jax
    import jax.numpy as jnp

    model, variables = ref_driver.build_model(cell, seed)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(variables)
    key = jax.random.PRNGKey(seed ^ 0x5EED)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        if getattr(path[-1], "key", "") == "scale":
            leaf = (NORM_WEIGHT_STD * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, jnp.float32)
            ).astype(leaf.dtype)
        out.append(leaf)
    return model, jax.tree_util.tree_unflatten(treedef, out)


def engine_answers(engine, cell, seed: int) -> list:
    """``(prompt, tokens)`` of seeded requests served by the ENGINE ITSELF,
    together, through ``submit`` and ``step``: its chunked prefill and tick,
    both block tables, both allocators. The first prompt lies past a
    window's boundary, so its window pages were given back whole."""
    vocab = cell.config["model"]["vocab_size"]
    rng = np.random.default_rng([seed, 5])
    *_, prompts, tokens = check_sizes(cell)
    ids = [engine.submit(rng.integers(0, vocab, n, dtype=np.int32),
                         max_length=tokens) for n in prompts]
    results = engine.drain()
    return [(np.asarray(results[i].prompt), np.asarray(results[i].tokens))
            for i in ids]


class Served:
    """What the model computes through the ENGINE'S pool, by programs of the
    check's own (the engine returns tokens only, so logits need them): a
    chunk program a bucket, which also gives the logits of ALL heads at its
    last ``tail`` true rows, and a step; on ``engine.params`` in a lane of
    ``engine.cache_manager`` claimed and freed by the caller, so that both
    classes of page are allocated, closed and tumbled exactly as for a
    request. Chunks start at multiples of the engine's prefill chunk and the
    last is padded to its bucket, as the engine's are. ``model`` is
    ``engine.model`` unless a probe plants a fault."""

    def __init__(self, engine, tail: int, model=None):
        import jax
        import jax.numpy as jnp

        self.engine, self.tail = engine, tail
        model = model or engine.model
        donate = (1,) if jax.default_backend() == "tpu" else ()

        @functools.partial(jax.jit, donate_argnums=donate,
                           static_argnames=("tail",))
        def forward(params, cache, ids, true_len, at, table, tail=0):
            """Writes ``ids`` (``true_len`` of them tokens) at positions
            ``at`` on; ``tail`` > 0: also the logits of the ``tail`` rows
            that end at the last true one."""
            rows = jnp.arange(ids.shape[0], dtype=jnp.int32)
            logits, mut = model.apply(
                {"params": engine._dequant_params(params), "cache": cache},
                ids[None], (at + rows)[None], (rows < true_len)[None],
                decode=True, cache_positions=at[None],
                block_tables=jnp.expand_dims(table, -2), mutable=["cache"])
            if not tail:
                return mut["cache"], None
            return mut["cache"], jax.lax.dynamic_slice_in_dim(
                logits[0].astype(jnp.float32),
                jnp.maximum(true_len - tail, 0), tail)

        @jax.jit
        def rate(logits, tokens):
            """How far each of ``tokens`` stands below the best logit of its
            row, and the best above the second."""
            top = jax.lax.top_k(logits, 2)[0]
            at = jnp.take_along_axis(logits, tokens[:, None], 1)[:, 0]
            return top[:, 0] - at, top[:, 0] - top[:, 1]

        self._forward, self._rate = forward, rate

    def _call(self, lane: int, ids, true_len: int, at: int, tail: int = 0):
        import jax.numpy as jnp

        manager = self.engine.cache_manager
        manager.cache, logits = self._forward(
            self.engine.params, manager.cache, jnp.asarray(ids, jnp.int32),
            jnp.asarray(true_len, jnp.int32), jnp.asarray(at, jnp.int32),
            jnp.asarray(manager.lane_tables(lane)), tail=tail)
        return logits

    def prefill(self, lane: int, tokens):
        """``tokens`` written from position 0 in the engine's chunks. Returns
        the logits (on the device) of the last ``min(tail, rows of the last
        chunk)`` positions, and how many those are."""
        engine, n = self.engine, len(tokens)
        chunk = engine.prefill_chunk
        for at in range(0, n, chunk):
            part = np.asarray(tokens[at:at + chunk], np.int32)
            last = at + chunk >= n
            if not engine.cache_manager.prepare_span(lane, at, len(part)):
                raise RuntimeError("the window class ran dry in the check")
            padded = np.zeros(engine._bucket_rows(len(part), at), np.int32)
            padded[:len(part)] = part
            tail = min(self.tail, len(padded)) if last else 0
            out = self._call(lane, padded, len(part), at, tail)
        kept = min(tail, len(part))
        return out[:kept], kept

    def step(self, lane: int, token: int):
        """One decode step at the lane's next position: its logits, every
        head's (host)."""
        manager = self.engine.cache_manager
        if not manager.ensure_page(lane):
            raise RuntimeError("a page class ran dry in the check")
        logits = self._call(lane, [token], 1, int(manager.lengths[lane]), 1)
        manager.lengths[lane] += 1
        return np.asarray(logits)

    def deficits(self, logits, tokens) -> tuple:
        """``(deficit, margin)`` of ``tokens`` under head 0 of ``logits``,
        one row each."""
        import jax.numpy as jnp

        vocab = self.engine.model.cfg.vocab_size
        deficit, margin = self._rate(logits[:, :vocab],
                                     jnp.asarray(tokens, jnp.int32))
        return np.asarray(deficit), np.asarray(margin)

    def sequence(self, tokens, prompt_len: int) -> tuple:
        """The first ``prompt_len`` of ``tokens`` prefilled, the rest decoded
        one step each: the logits of the last ``tail`` prompt positions, then
        of every decode step; and the pooled rows the lane's summary pages
        then hold, of every chunk the sequence closed ``[layers, 2, chunks,
        width]`` (:func:`pooled_rows`)."""
        manager = self.engine.cache_manager
        lane, _ = manager.alloc(-1, tokens[:prompt_len])
        try:
            logits, kept = self.prefill(lane, tokens[:prompt_len])
            if kept != self.tail:
                raise ValueError(f"the last chunk holds {kept} rows of the "
                                 f"tail's {self.tail}")
            out = [np.asarray(logits)]
            for token in tokens[prompt_len:]:
                out.append(self.step(lane, int(token)))
            chunk = self.engine.model.cfg.eva_chunk_size
            pooled = pooled_rows(self.engine, lane,
                                 np.arange(len(tokens) // chunk))
        finally:
            manager.free(lane)
        return np.concatenate(out), pooled


def _read_rows(engine, page, offset) -> np.ndarray:
    """The keys and values at ``[page, offset]`` (the flat pool's own page
    numbers, ``[layers, rows]``): ``[layers, 2, rows, width]`` float32."""
    import jax

    pools = {path[-1].key: leaf for path, leaf in
             jax.tree_util.tree_flatten_with_path(
                 engine.cache_manager.cache)[0]}
    return np.stack([np.asarray(pools[name][page, offset], np.float32)
                     for name in ("cached_key", "cached_value")], axis=1)


def pooled_rows(engine, lane: int, chunks) -> np.ndarray:
    """The pooled rows ``lane``'s summary pages hold for ``chunks``, in every
    layer, read through the manager's HOST table of the class."""
    from fleetx_tpu.models.gpt.hybrid import layer_bases

    manager = engine.cache_manager
    ps = manager.page_size
    summary = manager.lane_tables(lane)[0]
    return _read_rows(
        engine, summary[chunks // ps] + layer_bases(engine.model.cfg)[:, None],
        chunks % ps)


def lane_rows(engine, lane: int, n: int) -> tuple:
    """What the engine's pool holds for ``lane`` with ``n`` rows written, in
    every layer, read through the manager's HOST tables of both classes:
    the pooled rows of its last ``ENGINE_CHUNKS`` closed chunks and the exact
    rows of its current window's last chunks ``[layers, 2, rows, width]``
    float32 each. The exact rows are gathered at ONE shape whatever ``n``
    (the ``ENGINE_CHUNKS`` chunks behind ``n``; what lies before the window's
    first row, whose pages went back at the boundary, is cut off on the
    host): a gather compiles once a shape, after the window."""
    from fleetx_tpu.models.gpt.hybrid import layer_bases

    cfg, manager = engine.model.cfg, engine.cache_manager
    ps, chunk = manager.page_size, cfg.eva_chunk_size
    window = manager.lane_tables(lane)[1]
    span = ENGINE_CHUNKS * chunk
    first = max(n // cfg.eva_window_size * cfg.eva_window_size, n - span)
    pos = np.maximum(np.arange(n - span, n), 0)
    exact = _read_rows(engine, window[pos // ps] + layer_bases(cfg)[:, None]
                       + cfg.decode_num_pages, pos % ps)
    return (pooled_rows(engine, lane, np.arange(
        max(n // chunk - ENGINE_CHUNKS, 0), n // chunk)),
        exact[:, :, span - (n - first):])


def engine_check(engine, served: Served, in_flight, unit: float,
                 tol: dict) -> dict:
    """The ENGINE'S OWN PROGRAMS against the checked ones (``Served``, which
    ``reference_check`` holds to the reference), on requests in flight when
    the window closed: for ``ENGINE_LANES`` decoding lanes (those with the
    fewest tokens out and those with the most) the pooled and exact rows the
    engine's tick and chunk programs left in both classes, and the tokens it
    returned, against ``Served``'s forward of the same sequence in a lane of
    the same pool. The engine's rows are read first; then the requests
    ``in_flight`` are cancelled, which frees the lanes the check needs."""
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
        held.append((tokens, len(req.prompt), lane_rows(engine, lane, n)))
    out = {"engine_lanes_live": len(live), "engine_lanes_checked": len(held)}
    for rid in list(in_flight):
        engine.cancel(rid)
    if not held:
        out["engine_ok"] = False
        return out
    errs, deficits, margins = [], [], []
    for tokens, prompt_len, theirs in held:
        n = len(tokens) - 1
        lane, _ = manager.alloc(-1, tokens[:n])
        try:
            logits, kept = served.prefill(lane, tokens[:n])
            mine = lane_rows(engine, lane, n)
        finally:
            manager.free(lane)
        for a, b in zip(theirs, mine):
            if b.size:
                errs.append(np.sqrt(((a - b) ** 2).mean((1, 2, 3))
                                    / (b ** 2).mean((1, 2, 3))))
        # position i predicts token i + 1; the engine chose those from the
        # prompt's last position on
        chosen = np.arange(n - kept, n) >= prompt_len - 1
        deficit, margin = served.deficits(logits, tokens[n - kept + 1:])
        deficits.append(deficit[chosen])
        margins.append(margin[chosen])
    manager.pool.check_invariants()
    manager.window_pool.check_invariants()
    errs = np.asarray(errs)                          # [lanes x classes, layers]
    deficits, margins = np.concatenate(deficits), np.concatenate(margins)
    out.update({
        "engine_rows_max_rel_rms_err": float(errs.max()),
        "engine_rows_rel_rms_err_by_layer": [float(e) for e in errs.max(0)],
        "engine_tokens_served_checked": int(deficits.size),
        "engine_tokens_served_best": int((deficits == 0).sum()),
        "engine_token_served_max_deficit": float(
            deficits.max() if deficits.size else 0.0),
        "engine_token_served_rms_deficit": float(
            np.sqrt((deficits ** 2).mean()) if deficits.size else 0.0),
        "served_margin_p50": float(
            np.median(margins) if margins.size else 0.0),
        "engine_tol": [tol["engine_rows"], tol["engine_token"]],
        "engine_check_s": time.perf_counter() - t0})
    out["engine_ok"] = bool(
        deficits.size
        and out["engine_rows_max_rel_rms_err"] <= tol["engine_rows"]
        and out["engine_token_served_rms_deficit"]
        <= tol["engine_token"] * unit)
    return out


def reference_check(engine, variables, cell, seed: int,
                    served: Served = None) -> dict:
    """The engine against the configuration's float32 reference, which reads
    the weights as made (``variables``): module docstring, ``correct``."""
    import jax

    prompt, decode, tail, _, answer_tokens = check_sizes(cell)
    served, tol = served or Served(engine, tail), limits(cell)
    vocab = cell.config["model"]["vocab_size"]
    logits = jax.jit(
        ref_driver.reference_module(cell).configured(cell.config["model"]),
        static_argnames=("tail", "with_pooled"))
    manager = engine.cache_manager
    before = manager.class_counters()

    # the engine's own answers, rated by the reference (head 0)
    by_reference = []
    answers = engine_answers(engine, cell, seed)
    for asked, got in answers:
        tokens = np.concatenate([asked, got])
        rated = np.asarray(logits(variables["params"], tokens[:-1],
                                  tail=len(got)))[:, :vocab]
        by_reference.append(rated.max(-1) - rated[np.arange(len(got)), got])
    by_reference = np.concatenate(by_reference)
    complete = all(len(t) == answer_tokens for _, t in answers)

    tokens = np.random.default_rng([seed, 4]).integers(
        0, vocab, prompt + decode, dtype=np.int32)
    mine, pooled = served.sequence(tokens, prompt)
    # the system's logits at position i predict token i + 1: the prompt's
    # last ``tail`` positions and the decode steps are the sequence's last
    # ``tail + decode``
    reference, theirs = logits(variables["params"], tokens,
                               tail=tail + decode, with_pooled=True)
    reference, theirs = np.asarray(reference), np.asarray(theirs)
    err, unit = np.abs(mine - reference), float(reference.std())
    # the pooled rows the lane's summary pages hold against the reference's,
    # every chunk the sequence closed (prefill's, the prompt's open one, the
    # ticks'): rms of the difference over the rms of the reference's rows
    row_err = np.sqrt(((pooled - theirs) ** 2).mean(-1)
                      / (theirs ** 2).mean(-1))      # [layers, 2, chunks]
    first = np.sqrt(((pooled[0] - theirs[0]) ** 2).mean()
                    / (theirs[0] ** 2).mean())
    by_head = np.sqrt((err ** 2).reshape(len(err), -1, vocab).mean((0, 2)))
    manager.pool.check_invariants()
    manager.window_pool.check_invariants()
    after = manager.class_counters()
    out = {"reference_logit_std": unit,
           "reference_positions_checked": int(err.shape[0]),
           "reference_heads_checked": int(by_head.size),
           "reference_max_abs_err": float(err.max()),
           "reference_decode_max_abs_err": float(err[tail:].max()),
           "reference_rms_err": float(np.sqrt((err ** 2).mean())),
           "reference_decode_rms_err": float(
               np.sqrt((err[tail:] ** 2).mean())),
           "reference_rms_err_by_head": [float(e) for e in by_head],
           "engine_tokens_checked": int(by_reference.size),
           "engine_tokens_reference_best": int((by_reference == 0).sum()),
           "engine_token_max_deficit": float(by_reference.max()),
           "engine_token_rms_deficit": float(
               np.sqrt((by_reference ** 2).mean())),
           "pooled_rows_checked": int(row_err.shape[-1]),
           "pooled_row_max_rel_err": float(row_err.max()),
           "pooled_first_layer_rel_rms_err": float(first),
           "pooled_tol": [tol["row"], tol["first_layer"]],
           "windows_tumbled_in_check": int(
               after["eva_windows_tumbled"] - before["eva_windows_tumbled"]),
           "reference_tol_in_std": [tol["max"], tol["rms"],
                                    tol["decode_rms"], tol["token"]]}
    out["reference_ok"] = bool(
        complete and out["windows_tumbled_in_check"] > 0
        and out["pooled_row_max_rel_err"] <= tol["row"]
        and out["pooled_first_layer_rel_rms_err"] <= tol["first_layer"]
        and out["reference_max_abs_err"] <= tol["max"] * unit
        and out["reference_rms_err"] <= tol["rms"] * unit
        and out["reference_decode_rms_err"] <= tol["decode_rms"] * unit
        and out["engine_token_rms_deficit"] <= tol["token"] * unit)
    return out


def set_up(cell, seed: int, t_process: float):
    """``serve_closed_loop_swa.set_up`` with this file's model, ``Served``
    and reference check."""
    device = harness.own_the_chip(cell.chips, cell.tiny)

    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = harness.CompileClock()
    phases = {"import_s": time.perf_counter() - t_process}
    model, variables = build_model(cell, seed)
    engine = swa_driver.build_engine(cell, model, variables)
    phases["weights_and_engine_s"] = time.perf_counter() - t_process
    buckets = swa_driver.warm_up(engine, cell, seed)
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
                f"{len(done)} ({len(done) / seconds:.2f}/s); prompt bytes "
                f"prefilled/s {samples['prompt_tokens_done'] / seconds:.0f}; "
                f"closed-loop ttft ms p50 {harness.percentile(ttft, 50)}")
    counters = serving.counters(engine)
    harness.log("pool counters " + json.dumps(
        {k: v for k, v in counters.items()
         if k.startswith(("eva_", "pages_in_use_", "usable_pages_",
                          "window_pages_", "admits_refused_"))}))
    spans = harness.program_spans(start)
    reduced = profiler.reduce() if trace else None
    # everything the window is read from is taken; now what the engine's
    # programs left in flight, which ends those requests
    checks.update(engine_check(engine, served, clients.open,
                               reference["reference_logit_std"], limits(cell)))
    # (a rehearsal runs no kernel: their presence is waived off the chip)
    checks["correct"] = bool(
        not checks["wrong_results"] and not checks["refused"]
        and not checks["engine_recoveries"] and not checks["poison_retired"]
        and not any(checks["fault_events"].values())
        and (checks["mosaic_calls"] > 0 or cell.tiny)
        and checks["compiles_in_window"] == 0
        and checks["reference_ok"] and checks["engine_ok"])
    return harness.Run(
        cell=cell, device=device, setup_s=start - t_process,
        window=(start, end), attempted=len(inside),
        failed=len(clients.refused) + checks["wrong_results"],
        correct=checks["correct"], checks=checks, samples=samples,
        spans=spans, counters=counters, traced=profiler.traced, trace=reduced,
        peaks=harness.device_peaks(device, cell.tiny))
