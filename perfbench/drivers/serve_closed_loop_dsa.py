"""Serving, closed loop, for a configuration whose latent attention runs
UNDER A LEARNED INDEXER (DeepSeek-V3.2: every query scores the index keys of
its lane's rows, the pool's third leaf, and attends over the 2,048 best):
``serve_closed_loop_mla.py``'s loop, set-up, engine, stream and engine check
AS THEY ARE (imported; that file is not edited), with a reference check of
its own, because two things differ and must:

- **The check's document is longer than ``index_topk``.** At the MLA
  driver's 1,536 rows every row is selected and the indexer could be absent
  without the check noticing. Here a document of ``CHECK_DOC`` = 6,144
  tokens is registered in the trie BY THE ENGINE ITSELF, then a prompt of it
  plus ``CHECK_OWN`` tokens is prefilled COLD (the trie off) and decoded
  ``CHECK_DECODE`` steps, and again ON THE HIT (the three leaves of the
  matched pages resumed): 2,048 of 6,144-6,368 rows are kept at every
  position compared.
- **A discrete choice sits before the softmax**, as a router sits before
  the experts. Two rows whose index scores lie a rounding apart change
  places between bfloat16 and float32, and a row exchanged moves the logits
  by more than any arithmetic does; and an expert or a row exchanged at ANY
  earlier position moves the keys every later query scores. So the
  reference follows the system's experts and sets at EVERY position (the
  cold run, which prefills every row itself, has them all), and, as
  ``given`` does for the experts: (a) the index scores ``I`` against the
  reference's (at the decode steps: a tick sows them, a chunk would sow 103
  MB a layer); (b) the selected sets:
  the share of the reference's ``S_t`` the system also chose, and every row
  chosen BESIDE the reference's must score, in the reference, within
  ``SET_SCORE_TOL`` of its ``k``-th; (c) the logits against the reference
  ATTENDING OVER THE SYSTEM'S SETS at the positions compared (tight), and
  against the reference's own sets (looser: what the exchanged rows cost);
  (d) the cached rows, ``c_kv``, ``k_r`` and ``kI`` apart; (e) the expert
  layers as ``serve_closed_loop_mla.layer_check`` holds them (the
  reference's router has the selection bias).

After the window the engine's own programs against ``Served``
(``serve_closed_loop_mla.engine_check``, its limits too), the rows read with
the third leaf beside the two (:func:`in_the_mla_drivers_place`).

The limits: two readings each on the chip at the published widths (my chip
runs, PR 46; ``perfbench/probe_dsv32.py`` takes both; PERF.md section 6).
"""

from __future__ import annotations

import contextlib

import numpy as np

from perfbench import harness
from perfbench.drivers import serve_closed_loop_lfm2 as lfm2_driver
from perfbench.drivers import serve_closed_loop_mla as mla_driver
from perfbench.drivers import serve_closed_loop_ref as ref_driver
from perfbench.drivers.serve_closed_loop_mla import (  # noqa: F401
    ENGINE_ROWS_TOL,
    ENGINE_TOKEN_TOL,
    LAYER_OUTPUT_TOL,
    LAYER_WEIGHT_TOL,
    _rms,
    build_engine,
    engine_check,
    layer_check,
    traffic_gen,
)

CHECK_DOC, CHECK_OWN, CHECK_DECODE = 6144, 192, 32

# Limits from two readings each on the chip at the published widths (my chip
# runs, PR 46: the cell on seeds 3000000013, 4600000101-106 and, on the final
# tree, 4600000401-407, the probe on seeds 7 and 8, ``chiprun_out/pr46``;
# PERF.md section 6): the largest reading of the engine as built over its
# seeds, and the smallest reading of
# what has to come out NOT correct. A fault is refused by one of the limits
# and not by each. Logit errors are in units of the reference's logit
# deviation (1.694). The reference follows the system's experts and sets at
# EVERY position (module docstring): following them at the positions
# compared alone read ``I`` 0.091 and a row chosen 1.16 units under the
# reference's k-th (my first chip run), the keys of exchanged positions'
# rows, not the indexer's arithmetic.
# - ``REFERENCE_RMS_TOL``, the logits against the reference attending over
#   the SYSTEM'S sets, hit and cold, all positions and the decode steps
#   alone: as built 0.0155-0.0159 on sixteen seeds (bfloat16 weights and
#   activations through five layers; A.X-K1's six read 0.0159-0.0162); a
#   CHUNK'S ATTENTION SCORES ACCUMULATED IN BFLOAT16 0.0214 / 0.0217 (the
#   nearest precision below the float32 the configuration states for them:
#   refused by this limit alone); the tick ignoring the selection 1.34 at
#   the decode steps; an unheld pair 0.18-0.19. The limit is 0.0183, the
#   geometric middle of 0.0156 and 0.0214 (15% over the largest reading).
# - ``REFERENCE_MAX_TOL``, the largest error: as built 0.077-0.096; bfloat16
#   scores 0.115-0.125, an unheld pair 1.4-1.5, the dense tick 6.7. The limit
#   is 0.30 (A.X-K1's: the rms limit has the precision).
# - ``OWN_SETS_RMS_TOL``, the same logits against the reference's OWN sets:
#   as built 0.107-0.124, SEVEN TIMES the reading over the system's sets,
#   because 1% of a query's 2,048 rows change places with rows that score
#   alike (below) and the softmax of random weights is flat; a selection
#   that is not the indexer's 0.91 (ReLU left out), 1.12 (keys unrotated),
#   1.28 (head weights left out), the dense tick 0.44. The limit is 0.23,
#   the geometric middle of 0.124 and 0.44; no fault is its alone.
# - ``INDEX_TOL``, ``I`` at the decode steps over the rows each query sees,
#   rms of the difference over the rms of the reference's, the worst layer:
#   as built 0.0245-0.0291 (0.005 in the first layer, whose input is exact:
#   the sum over 64 heads of either sign cancels, so 1.5% on the cached keys
#   is 2.7% on ``I``); an unheld pair 0.29-0.30, the keys unrotated 0.76,
#   ReLU left out 1.01, the head weights left out 252. The limit is 0.09,
#   the geometric middle of 0.028 and 0.29. The indexer's products
#   accumulated in bfloat16 read 0.0266 where as built reads 0.0266: the
#   chip's matmul accumulates in float32 whatever the output's type, the
#   sets do not move, and so it is a reading of the probe and no fault.
# - ``SET_SHARE_TOL``, the least share of a reference set ``S_t`` the system
#   also chose, over layers and the 224 positions: as built 0.979-0.982
#   (mean 0.990: 20 of 2,048 rows exchanged); an unheld pair 0.77, ReLU left
#   out 0.71, the keys unrotated 0.51, the head weights 0.18. The limit is
#   0.88, the geometric middle of 0.979 and 0.77 rounded up.
# - ``SET_SCORE_TOL``, how far under the reference's k-th score a row chosen
#   beside the reference's set scores there, in units of the rms of that
#   query's scores, the worst row: as built 0.097-0.127 (the 99th percentile
#   0.082-0.089); an unheld pair 1.42-1.90, ReLU left out 2.4, the keys
#   unrotated 4.0. The limit is 0.42, the geometric middle of 0.127 and 1.42.
#   And every set has ``min(index_topk, rows seen)`` rows: the dense tick
#   sows none.
# - ``REFERENCE_ROWS_TOL``, the cached rows, ``c_kv``, ``k_r`` and ``kI``
#   apart, the worst layer: as built 0.0149-0.0160 for each; THE INDEX KEY
#   UNROTATED 0.92 for ``kI`` with the other two as built (refused by this
#   limit and the selection's); bfloat16 scores 0.021. The limit is 0.05
#   (A.X-K1's).
# - the expert layers on the input they really saw, with A.X-K1's limits
#   (``serve_closed_loop_mla.LAYER_WEIGHT_TOL`` 2e-6, ``LAYER_OUTPUT_TOL``
#   0.012): as built 2.98e-7 / 3.58e-7 and 0.0031; THE BIAS USED IN THE
#   WEIGHTS 0.148 / 0.189 on the weights (its output 0.0085-0.0142: the
#   weight limit's alone); an unheld pair's output 0.318-0.322.
# - the engine's own programs: A.X-K1's limits (``ENGINE_ROWS_TOL`` 0.19,
#   ``ENGINE_TOKEN_TOL`` 0.27), the rows of all three leaves: as built
#   0.013-0.035 and 0.000-0.039 (tick dense: 1.13 and 4.8; stale tables: 0.99
#   and 6.2).
REFERENCE_MAX_TOL = 0.30
REFERENCE_RMS_TOL = 0.0183
OWN_SETS_RMS_TOL = 0.23
INDEX_TOL = 0.09
SET_SHARE_TOL = 0.88
SET_SCORE_TOL = 0.42
REFERENCE_ROWS_TOL = 0.05


def check_sizes(cell) -> tuple:
    """``(document, own part, decode steps, engine tail)``; a rehearsal's
    scale with its chunk, the document still several ``index_topk`` long."""
    if not cell.tiny:
        return CHECK_DOC, CHECK_OWN, CHECK_DECODE, mla_driver.ENGINE_TAIL
    chunk = cell.deploy["prefill_chunk"]
    return 4 * chunk, chunk // 2, 4, 2


def lane_rows(engine, lane: int, lo: int, hi: int) -> np.ndarray:
    """``serve_closed_loop_mla.lane_rows`` with the third leaf: ``[layers,
    hi - lo, c_kv + k_r leaf + kI]`` float32."""
    cfg, manager = engine.model.cfg, engine.cache_manager
    pos = np.arange(lo, hi)
    page = (manager.lane_tables(lane)[pos // manager.page_size][None, :]
            + np.arange(cfg.num_layers)[:, None] * manager.num_pages)
    pools = lfm2_driver._pools(engine)
    return np.concatenate([
        np.asarray(pools[name][page, pos % manager.page_size], np.float32)
        for name in ("cached_key", "cached_value", "cached_index")], axis=-1)


class Served(mla_driver.Served):
    """``serve_closed_loop_mla.Served`` (chunk programs of the check's own
    and a step shaped as the engine's tick), which keeps EVERY position's
    choices: the experts each expert layer chose and the rows each layer's
    queries attended over, from the first row it prefilled itself on
    (``experts_all`` ``[expert layers, positions, k]``, ``sets_all``
    ``[layers, positions, sequence]``): the reference follows them, so that
    what is compared is the arithmetic and not what a choice exchanged
    earlier in the sequence does to every row after it. At the positions
    compared also ``index_sets`` and, from the ticks, ``index_scores``, and
    the rows of all three leaves."""

    def prefill_keeping(self, lane: int, tokens, start: int, tail: int):
        """``serve_closed_loop_lfm2.Served.prefill``, every chunk giving the
        routing of ALL its rows (kept in ``self.choices``; ``prefill``
        itself stays the light one the engine check uses)."""
        chunk, n = self.engine.prefill_chunk, len(tokens)
        first = (n - start) % chunk or min(chunk, n - start)
        starts = [start] + list(range(start + first, n, chunk))
        if min(first if len(starts) == 1 else chunk, n - start) < tail:
            raise ValueError(f"{n - start} tokens from {start} on give no "
                             f"tail of {tail}")
        self.choices = {"experts": [], "index_sets": []}
        for at in starts:
            ids = tokens[at:at + (first if at == start else chunk)]
            logits, sown = self._call(lane, ids, at, chunk, len(ids))
            self._keep(sown)
        return logits[-tail:], {k: v[:, -tail:] for k, v in sown.items()}

    def _keep(self, sown) -> None:
        self.choices["experts"].append(np.asarray(sown["experts"], np.int32))
        self.choices["index_sets"].append(
            np.asarray(sown["index_sets"][..., :self.columns]))

    def sequence(self, tokens, prompt_len: int, tail: int) -> dict:
        manager = self.engine.cache_manager
        cfg = self.engine.model.cfg
        self.columns = len(tokens)
        lane, matched = manager.alloc(-1, tokens[:prompt_len])
        try:
            logits, sown = self.prefill_keeping(
                lane, tokens[:prompt_len], matched, tail)
            out = [np.asarray(logits)]
            routing = {k: [np.asarray(v, np.float32)] for k, v in sown.items()}
            for token in tokens[prompt_len:]:
                logits, sown = self.step(lane, int(token))
                out.append(logits)
                self._keep(sown)
                for k, v in sown.items():
                    routing.setdefault(k, []).append(
                        np.asarray(v, np.float32))
            compared = tail + len(tokens) - prompt_len
            rows = lane_rows(self.engine, lane, len(tokens) - compared,
                             len(tokens))
        finally:
            manager.free(lane)
        c, leaf = cfg.kv_lora_rank, rows.shape[-1] - cfg.index_head_dim
        return {"matched": int(matched), "logits": np.concatenate(out),
                # (without the rotary leaf's padding)
                "rows": np.concatenate([rows[..., :c + cfg.qk_rope_head_dim],
                                        rows[..., leaf:]], -1),
                "experts_all": np.concatenate(self.choices["experts"], 1),
                "sets_all": np.concatenate(self.choices["index_sets"], 1),
                **{k: np.concatenate(v, axis=1) for k, v in routing.items()}}


def selection_check(mine: dict, theirs: dict, n: int, top: int) -> dict:
    """The indexer at the positions compared (the last of ``n``): the
    system's sets and, at the decode steps (a tick sows its scores, a chunk
    its sets alone), its ``I`` (``mine``) against the reference's
    (``theirs``), layer for layer."""
    positions = mine["index_sets"].shape[1]
    seen = (np.arange(n)[None, :]
            <= np.arange(n - positions, n)[:, None])[None]
    ref = np.asarray(theirs["index"], np.float32)
    sets = (mine["index_sets"][..., :n] > 0) & seen
    ref_sets = np.asarray(theirs["sets"], bool)
    steps = mine["index_scores"].shape[1]
    index = np.where(seen[:, -steps:], mine["index_scores"][..., :n], 0.0)
    err = np.sqrt(((index - ref[:, -steps:]) ** 2).sum((1, 2))
                  / (ref[:, -steps:] ** 2).sum((1, 2)))
    shared = (sets & ref_sets).sum(-1) / ref_sets.sum(-1)
    # a row chosen beside the reference's: how far under the reference's
    # k-th score it stands there, in units of that query's scores' rms
    kth = np.where(ref_sets, ref, np.inf).min(-1)
    unit = np.sqrt((ref ** 2).sum(-1) / seen.sum(-1))
    under = np.where(sets & ~ref_sets, kth[..., None] - ref, -np.inf).max(-1)
    return {"index_steps_checked": int(steps),
            "index_rel_rms_err": float(err.max()),
            "index_rel_rms_err_by_layer": [float(e) for e in err],
            "sets_positions_checked": int(positions),
            "sets_rows_attended_mean": float(sets.sum(-1).mean()),
            "sets_sizes_right": bool(
                (sets.sum(-1) == np.minimum(seen.sum(-1), top)).all()),
            "sets_shared_min": float(shared.min()),
            "sets_shared_mean": float(shared.mean()),
            "sets_same_positions": int((shared == 1.0).sum()),
            "sets_beside_max_under_kth": float((under / unit).max()),
            "sets_beside_p99_under_kth": float(np.percentile(
                (under / unit)[np.isfinite(under)], 99))
            if np.isfinite(under).any() else 0.0,
            "selection_tol": [INDEX_TOL, SET_SHARE_TOL, SET_SCORE_TOL]}


def reference_check(engine, variables, cell, seed: int,
                    served: Served = None) -> dict:
    """The engine against the configuration's float32 reference, which reads
    the weights as made (``variables``), outside the window: module
    docstring."""
    served = served or Served(engine)
    model = cell.config["model"]
    logits = ref_driver.reference_module(cell).configured(model)
    doc, own, decode, _ = check_sizes(cell)
    vocab, top = model["vocab_size"], model["index_topk"]
    rng = np.random.default_rng([seed, 4])
    tokens = rng.integers(1, vocab, doc + own + decode, dtype=np.int32)
    n, tail = len(tokens), own + decode
    # another question registers the document, through the engine itself
    other = np.concatenate([tokens[:doc], rng.integers(
        1, vocab, engine.page_size * 2, dtype=np.int32)])
    engine.submit(other, max_length=2)
    engine.drain()

    def reference(mine, own_sets: bool):
        # the reference follows the SYSTEM'S experts, and unless
        # ``own_sets`` the system's sets, at EVERY position (module
        # docstring); what it returns stays its own choice
        out = logits(
            variables["params"], tokens, tail=tail, with_all=True,
            given=mine["experts_all"],
            given_sets=None if own_sets else mine["sets_all"])
        return {k: np.asarray(v) for k, v in out.items()}

    def rows_err(mine, theirs):
        # each leaf apart (the rotary key is 64 of 704 columns: a joint rms
        # would hide it), the worst layer
        cfg = engine.model.cfg
        c, r = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        return [float(lfm2_driver._rel_rms(
            theirs["rows"][..., part], mine["rows"][..., part], (1, 2)).max())
            for part in (slice(None, c), slice(c, c + r), slice(c + r, None))]

    # cold first: it prefills every row itself, so it has every position's
    # choices; a hit resumes the rows the ENGINE'S programs wrote
    with lfm2_driver.trie_off(engine.cache_manager.pool):
        cold = served.sequence(tokens, doc + own, own)
    under = reference(cold, own_sets=False)     # over the system's sets
    unit = float(under["logits"].std())
    hit = served.sequence(tokens, doc + own, own)
    engine.cache_manager.pool.check_invariants()
    cold_err = np.abs(cold["logits"] - under["logits"])
    out = selection_check(cold, under, n, top)
    leaves = rows_err(cold, under)
    # (a hit resumes what a cold prefill of the same programs wrote, so the
    # two choose alike wherever nothing is wrong: the reference is the same)
    same = all(np.array_equal(cold[k], hit[k])
               for k in ("experts", "index_sets"))
    under_hit = under
    if not same:  # the document's choices are the cold run's either way
        at = hit["matched"]
        under_hit = reference({k: np.concatenate([cold[k][:, :at], hit[k]], 1)
                               for k in ("experts_all", "sets_all")},
                              own_sets=False)
    err = np.abs(hit["logits"] - under_hit["logits"])
    leaves = np.maximum(leaves, rows_err(hit, under_hit)).tolist()
    own_err = np.abs(cold["logits"]
                     - reference(cold, own_sets=True)["logits"])
    out.update({
        "reference_logit_std": unit,
        "reference_positions_checked": int(err.shape[0]),
        "hit_matched_tokens": hit["matched"],
        "cold_matched_tokens": cold["matched"],
        "hit_cold_same_choices": bool(same),
        "reference_max_abs_err": float(err.max()),
        "reference_rms_err": _rms(err),
        "reference_decode_rms_err": _rms(err[own:]),
        "reference_cold_max_abs_err": float(cold_err.max()),
        "reference_cold_rms_err": _rms(cold_err),
        "reference_cold_decode_rms_err": _rms(cold_err[own:]),
        "reference_own_sets_rms_err": _rms(own_err),
        "reference_own_sets_max_abs_err": float(own_err.max()),
        "hit_cold_logit_rms_diff": _rms(hit["logits"] - cold["logits"]),
        "reference_ckv_rel_rms_err": leaves[0],
        "reference_kr_rel_rms_err": leaves[1],
        "reference_ki_rel_rms_err": leaves[2],
        "reference_tol_in_std": [REFERENCE_MAX_TOL, REFERENCE_RMS_TOL,
                                 OWN_SETS_RMS_TOL],
        "reference_rows_tol": REFERENCE_ROWS_TOL})
    positions = hit["experts"].shape[1]
    layers = layer_check(hit, variables, cell,
                         under["experts"][:, -positions:])
    out.update(layers)
    out["selection_ok"] = bool(
        out["sets_sizes_right"] and out["index_rel_rms_err"] <= INDEX_TOL
        and out["sets_shared_min"] >= SET_SHARE_TOL
        and out["sets_beside_max_under_kth"] <= SET_SCORE_TOL)
    out["reference_ok"] = bool(
        layers["layers_ok"] and out["selection_ok"]
        and hit["matched"] == doc and cold["matched"] == 0
        and max(leaves) <= REFERENCE_ROWS_TOL
        and max(out["reference_max_abs_err"],
                out["reference_cold_max_abs_err"]) <= REFERENCE_MAX_TOL * unit
        and max(out["reference_rms_err"], out["reference_decode_rms_err"],
                out["reference_cold_rms_err"],
                out["reference_cold_decode_rms_err"])
        <= REFERENCE_RMS_TOL * unit
        and out["reference_own_sets_rms_err"] <= OWN_SETS_RMS_TOL * unit)
    return out


@contextlib.contextmanager
def in_the_mla_drivers_place():
    """While open, ``serve_closed_loop_mla``'s ``run`` and ``engine_check``
    (which name their module's own) find this file's check, its ``Served``,
    its sizes and a lane's rows with the third leaf."""
    names = ("Served", "check_sizes", "lane_rows", "reference_check")
    theirs = {name: getattr(mla_driver, name) for name in names}
    for name in names:
        setattr(mla_driver, name, globals()[name])
    try:
        yield
    finally:
        for name, value in theirs.items():
            setattr(mla_driver, name, value)


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    """``serve_closed_loop_mla.run`` as it is (the loop, the set-up, the
    engine check after the window, ``correct`` with the latent kernel
    counted), with this file's check in the place of its own."""
    with in_the_mla_drivers_place():
        out = mla_driver.run(cell, seed, seconds, trace, t_process)
    harness.log("index counters " + str({
        k: v for k, v in out.counters.items()
        if k.startswith(("index_", "rows_selected"))}))
    return out
