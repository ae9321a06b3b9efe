"""Serving, closed loop, for a configuration whose lanes keep a DELTA RULE'S
matrix state once a lane (KDA linear attention layers: ``[heads, d, d]``
float32 a layer, 4 MB at the published widths) beside the keys and values
of gated position-free GQA layers in the page pool, over an expert layer
that holds a share (Solar-Open2): the loop of ``serve_closed_loop_ref.py``
AS IT IS, so every reader of a closed-loop cell reads the run, with

- an engine and a warm-up as ``serve_closed_loop_longcat.py`` builds them
  (chunked prefill, the prefix cache OFF: refused for this state) and a
  stream of its own (:func:`client_stream`: lengths from the traffic file's
  ``order_seed`` and the client, ids uniform over the slice from ``--seed``);
- a check of its own, in two steps, on what the timed path produces at the
  timed sizes.

Before the window (:func:`reference_check`): a prompt of ``CHECK_PROMPT``
tokens is prefilled in chunk programs OF THE ENGINE'S OWN SHAPES AND ORDER
(whole chunks of ``prefill_chunk`` rows, each beginning from what the lane
holds, then the rest in a program of its length rounded up to the bucket:
192 tokens in 256 rows, whose padded rows must leave the state alone) and
decoded ``CHECK_DECODE`` steps, each a tick over EVERY lane in order
(:class:`Served`), against ``perfbench/reference/solar2_f32.py``'s full
forward of the same tokens from position 0:

- the logits at the last call's positions and at every tick;
- what each GQA layer cached there;
- the matrix state ``S`` and the filter rows of each KDA layer after the
  prefill and after the last tick;
- each expert layer ON THE INPUT IT REALLY SAW (``layer_check``: weights,
  choice under the bias, the held experts' sum + the shared expert);
- the DELTA RULE ALONE ON THE ROWS IT REALLY SAW (:func:`rule_check`): the
  float32 ``q, k, v, g, beta`` the system's kernels were handed in the last
  call and the ticks, from the state the lane held before them, through the
  reference's scan over tokens: its ``o`` and its last ``S`` against the
  kernels'. Both sides are float32 on the same numbers, so this is where a
  lower precision of the state shows, which the bfloat16 of eight layers
  hides from every comparison above.

After the window (:func:`engine_check`): what the ENGINE'S OWN chunk and
tick programs left in lanes in flight (``S``, filter rows, keys and values,
tokens) against ``Served`` on the same sequences
(``serve_closed_loop_ssm.engine_check`` as it is, judged by this file's
limits).
"""

from __future__ import annotations

import dataclasses
import functools
import time
import types
from typing import Iterator

import numpy as np

from perfbench import harness
from perfbench.drivers import serve_closed_loop_longcat as longcat_driver
from perfbench.drivers import serve_closed_loop_mla as mla_driver
from perfbench.drivers import serve_closed_loop_ref as ref_driver
from perfbench.drivers import serve_closed_loop_ssm as ssm_driver
from perfbench.drivers.docqa_stream import _lengths
from perfbench.drivers.serve_closed_loop_ssm import _rel_rms, _rms, lane_rows
from perfbench.drivers.serve_closed_loop_swa_share import LAYER_OUTPUT_TOL
from perfbench.traffic import Request

CHECK_PROMPT, CHECK_DECODE = 2240, 32      # 4 chunks of 512 + 192 in 256 rows

# How far the system may stand from the float32 reference. Limits from two
# readings each on the chip at the published widths (my chip runs, PR 56,
# ``chiprun_out/pr56``; PERF.md section 6; ``perfbench/probe_solar2.py``
# takes both): the largest reading of the engine as built over its seeds
# (twenty-two readings: the probe's 7 and 8 and the cell's runs at 48 and at
# 32 lanes, the two sets of six among them), and the
# smallest reading of what has to come out NOT correct. A fault is refused by
# one of the limits and not by each. Logit errors are in units of the
# reference's logit deviation (1.279-1.281). The reference sums over the
# SYSTEM'S experts at every position (``given``): 450-500 of 1,792
# layer-positions hand an expert over to the rounding of the layers before
# (the eighth and ninth of 320 sigmoid scores lie a rounding apart).
#
# 1. Against the reference (``reference_check``), a prompt of 2,240 tokens in
# four chunks of 512 and 192 tokens in a program of 256 rows, then 32 ticks
# over every lane:
# - ``RULE_TOL``, what refuses A LOWER PRECISION OF THE STATE: the delta
#   rule alone on the float32 rows the kernels were really handed in the
#   last call and the ticks, from the state the lane held before them
#   (``rule_check``), rms of the difference over the rms of the value, ``o``
#   and the last ``S``, the worst layer: as built 2.55e-7 to 2.66e-7 and
#   1.85e-7 to 1.98e-7 (both sides float32: the order of two sums); ``S``
#   ROUNDED TO BFLOAT16 wherever a kernel hands it back 1.59e-3 / 1.63e-3 and
#   6.69e-3 / 6.56e-3 (seeds 7 and 8), which every limit below passes
#   (logits 0.0518-0.0535, state 0.0773-0.0786: inside the as-built range, the
#   bfloat16 of eight layers hides it); also padded rows advancing the state
#   0.167 and 0.336, the state zeroed at every call 0.416 and 3.98. The
#   limit is 2e-5, the geometric middle of 2.66e-7 and 1.59e-3: 77 times
#   each way.
# - ``REFERENCE_RMS_TOL``, the logits at the last call's 192 positions and
#   at the 32 ticks, over all of them and over either part alone: as built
#   0.0508-0.0540 (bfloat16 weights and activations through two gated
#   attentions, six delta-rule operators whose state integrates 2,240 rows,
#   and eight expert layers); ``beta`` not doubled 0.587, padded rows
#   advancing the state 0.933 over the ticks (0.0515 over the last call's
#   own rows, which come before the padding), the state zeroed 0.607, the
#   GQA gate left out 1.16. The limit is 0.17, the geometric middle of
#   0.0540 and 0.587: 3.3 times each way.
# - ``REFERENCE_MAX_TOL``, the largest error over 5.5 million logits: as
#   built 0.260-0.295; the smallest fault that moves it 3.23. The limit is
#   0.95, the geometric middle.
# - ``REFERENCE_ROWS_TOL``, what each GQA layer cached at those positions,
#   keys and values apart, the worst layer: as built 0.0316-0.0325; padded
#   rows 0.293, ``beta`` not doubled 0.407, the state zeroed 0.625, the gate
#   left out 1.08. The limit is 0.098, the geometric middle of 0.0325 and
#   0.293.
# - ``REFERENCE_STATE_TOL`` and ``REFERENCE_CONV_TOL``, the matrix state
#   ``S`` and the filter rows of each KDA layer after the prefill and after
#   the last tick against the reference's, the worst layer (the sixth: 0.017
#   in the first, growing a layer): as built 0.0732-0.0787 and
#   0.0443-0.0479; ``beta`` not doubled 1.30 and 0.548, padded rows 1.32 and
#   1.40, the gate left out 1.33 and 1.16, zeroed 6.75 and 1.91. The limits
#   are 0.32 and 0.16, the geometric middles.
# - ``LAYER_WEIGHT_TOL``, the weights a share's layer applied against the
#   reference router's for the same experts on the input it really saw: as
#   built 2.4e-7 to 3.6e-7 (both float32 at ``highest``); THE ROUTER'S
#   OUTPUTS ROUNDED TO BFLOAT16 1.04e-3, with 69 of 1,792 layer-positions
#   choosing an expert beside the reference's eight (the product alone in
#   bfloat16 reads 1.5e-5 and none beside: XLA keeps that rounding in
#   float32 on the TPU, which is why the plant rounds with
#   ``lax.reduce_precision``). The limit is 2e-5, the geometric middle of
#   3.6e-7 and 1.04e-3: 55 times each way; an expert beside the reference's
#   by more than that limit of a score: none is allowed.
# - ``LAYER_OUTPUT_TOL`` is ``serve_closed_loop_swa_share.py``'s (0.0041:
#   the same layer, ``parallel/moe_share.py``'s sigmoid gate over a held
#   share beside a shared expert, whose second reading is that file's): as
#   built here 0.00314-0.00315 on every seed (three bfloat16 roundings), as
#   there 0.00306-0.00308.
#
# 2. The ENGINE'S OWN PROGRAMS (its chunks with the head on one row, its
# tick over every lane with one tick in flight) against the check's
# (``Served``, held to the reference by 1.) on 8 of the requests in flight
# when the window closes (``engine_check``); the two sides run the same
# arithmetic through programs of other shapes, so bfloat16 rounds otherwise
# and an expert changes hands at a near-tie, and the readings grow with the
# depth (no layer's state is bit for bit the check's: the first KDA layer
# stands behind a GQA layer; ``RULE_TOL`` is what holds the precision of the
# state). The second reading is a fault planted in the engine's programs
# ALONE (``probe_solar2.py --engines``: 32 lanes, 256 tokens out; as built
# there 0.130, 0.103, 0.0545 and 0.020):
# - ``ENGINE_STATE_TOL``, the matrix state ``S`` a lane holds, the worst
#   layer (the sixth; 0.01-0.02 in the first): as built 0.0705-0.150 over
#   twenty readings; padded rows advancing the state in the engine's last
#   bucket 0.566, the state zeroed at every call 1.02. The limit is 0.29,
#   the geometric middle of 0.150 and 0.566: 1.9 times each way.
# - ``ENGINE_CONV_TOL``, the filter rows it holds: as built 0.042-0.107;
#   zeroed 1.11 (padded rows 0.254: the state's and the tokens' to refuse).
#   The limit is 0.34, the geometric middle of 0.107 and 1.11.
# - ``ENGINE_ROWS_TOL``, keys and values at its last 64 positions in the two
#   GQA layers (0.0 in the first: nothing stands before it): as built
#   0.030-0.067; zeroed 1.22 (padded rows 0.240). The limit is 0.29, the
#   geometric middle of 0.067 and 1.22.
# - ``ENGINE_TOKEN_TOL``, how far the tokens it returned stand below
#   ``Served``'s best, rms in the logits' unit: as built 0.015-0.038 (9-14%
#   of the tokens are not ``Served``'s best, by at most 0.36: the best logit
#   leads the second by 0.16-0.18 at the median); padded rows 0.360, zeroed
#   3.57. The limit is 0.12, the geometric middle of 0.038 and 0.360: 3.1
#   times each way.
REFERENCE_MAX_TOL = 0.95
REFERENCE_RMS_TOL = 0.17
REFERENCE_ROWS_TOL = 0.098
REFERENCE_STATE_TOL = 0.32
REFERENCE_CONV_TOL = 0.16
RULE_TOL = 2e-5
LAYER_WEIGHT_TOL = 2e-5
ENGINE_STATE_TOL = 0.29
ENGINE_CONV_TOL = 0.34
ENGINE_ROWS_TOL = 0.29
ENGINE_TOKEN_TOL = 0.12


def client_stream(traffic: dict, seed: int, client: int,
                  vocab: int) -> Iterator[Request]:
    """The endless request sequence of one client: prompt and output LENGTHS
    hold the quantiles of their distributions once in every block of
    ``block`` (``traffic.stratified_lengths``), in an order drawn from the
    traffic file's ``order_seed`` and the client alone (some 100 requests
    fill a window, and which lengths fall into it is not the seed's to
    choose); the IDS are drawn uniformly from ``[1, vocab)`` by ``--seed``
    and the client. No request shares a prefix."""
    block = int(traffic.get("block", 4))
    order = np.random.default_rng([int(traffic["order_seed"]), 1, client])
    ids = np.random.default_rng([seed, 1, client])
    prompts = _lengths(order, traffic["prompt"], block)
    outputs = _lengths(order, traffic["output"], block)
    index = 0
    while True:
        yield Request(index, 0.0, "doc", ids.integers(
            1, vocab, int(next(prompts)), dtype=np.int32), int(next(outputs)))
        index += 1


def check_sizes(cell) -> tuple:
    """``(prompt, decode steps, engine tail)`` of the check: the constants
    above at the published sizes; a rehearsal's scale with its chunk (a
    chunk, then a bucket and a half: a padded last program)."""
    if not cell.tiny:
        return CHECK_PROMPT, CHECK_DECODE, ssm_driver.ENGINE_TAIL
    chunk, bucket = cell.deploy["prefill_chunk"], cell.deploy["prefill_bucket"]
    return chunk + bucket + bucket // 2, 4, 8


def lane_state(engine, lane) -> tuple:
    """What ``lane`` holds outside the pool: ``(S, filter rows)`` of every
    KDA layer, ``[layers, heads, d_k, d_v]`` (the reference's layout: the
    leaf holds ``[d_k, heads, d_v]``) and ``[layers, taps - 1, 3 x heads x
    d]`` float32."""
    leaves, cfg = ssm_driver._leaves(engine), engine.model.cfg
    conv = np.asarray(leaves["kda_conv"][:, lane], np.float32)
    state = np.asarray(leaves["kda_state"][:, lane], np.float32)
    return (np.moveaxis(state, -2, -3),
            conv.reshape(*conv.shape[:-1], cfg.kda_conv_size - 1, -1))


class Served(ssm_driver.Served):
    """``serve_closed_loop_ssm.Served`` (programs of the check's own through
    the ENGINE'S pool and lane state; the step SHAPED AS THE ENGINE'S TICK,
    one row of every lane in order) with the prefill IN THE ENGINE'S ORDER
    (whole chunks, then the rest padded to its bucket) and, for the
    reference check, calls that also give every row's logits and what the
    expert layers and the delta rule saw, chose and gave (the model's
    ``routing`` collection)."""

    def __init__(self, engine, model=None):
        import jax
        import jax.numpy as jnp

        super().__init__(engine, model)
        model = model or engine.model
        donate = (1,) if jax.default_backend() == "tpu" else ()

        def sown_of(mut, lane=None):
            # under the layer scan one leaf [layers, b, s, ...] of each name
            return {jax.tree_util.keystr(path[-2:-1]).strip("[']"): (
                leaf[:, 0] if lane is None else leaf[:, lane])
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                    mut["routing"])[0]}

        @functools.partial(jax.jit, donate_argnums=donate,
                           static_argnames=("parts",))
        def forward(params, cache, ids, at, count, table, parts=True):
            """``parts``: every row's logits and the whole routing
            collection; else the experts chosen alone (the rest of the
            collection is never computed)."""
            rows = jnp.arange(ids.shape[0], dtype=jnp.int32)
            logits, mut = model.apply(
                {"params": engine._dequant_params(params), "cache": cache},
                ids[None], (at + rows)[None], (rows < count)[None],
                decode=True, cache_positions=at[None],
                block_tables=table[None], mutable=["cache", "routing"])
            sown = sown_of(mut)
            if not parts:
                return mut["cache"], None, {"experts": sown["experts"]}
            return mut["cache"], logits[0].astype(jnp.float32), sown

        @functools.partial(jax.jit, donate_argnums=donate)
        def tick(params, cache, token, at, lane, tables):
            active = jnp.arange(tables.shape[0]) == lane
            logits, mut = model.apply(
                {"params": engine._dequant_params(params), "cache": cache},
                jnp.where(active, token, 0)[:, None],
                jnp.where(active, at, 0)[:, None], active[:, None],
                decode=True, block_tables=tables,
                cache_positions=jnp.where(active, at, engine.cache_len - 1),
                mutable=["cache", "routing"])
            return (mut["cache"], logits[lane].astype(jnp.float32),
                    sown_of(mut, lane))

        self._forward_parts, self._tick_parts = forward, tick

    def calls(self, n: int, tail: int = 0) -> list:
        """``(start, tokens, rows)`` of the programs the ENGINE prefills
        ``n`` tokens with: whole chunks (none without chunked prefill), then
        the rest in a program of its length rounded up to the bucket. Where
        the rest is shorter than
        ``tail`` (the last call's logits are wanted that far back) the short
        piece goes BEFORE the last whole chunk: the same two program shapes,
        padded rows in mid-sequence, which the next call overwrites."""
        chunk, bucket = self.engine.prefill_chunk, self.engine.prefill_bucket
        whole, rest = divmod(n - 1, chunk) if chunk else (0, n - 1)
        rest += 1
        sizes = [chunk] * whole + [rest]
        if rest < tail:
            if not whole:
                raise ValueError(f"{n} tokens give no tail of {tail}")
            sizes = [chunk] * (whole - 1) + [rest, chunk]
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        return [(int(at), size, -(-size // bucket) * bucket)
                for at, size in zip(starts, sizes)]

    def prefill(self, lane: int, tokens, tail: int, chunk: int = 0):
        """``tokens`` written from position 0 on by :meth:`calls`'s programs
        (``chunk`` is the engine's: taken and not used). Returns the logits
        of the last ``tail`` positions (on the device)."""
        del chunk
        calls = self.calls(len(tokens), tail)
        for at, count, rows in calls:
            out = self._call(lane, tokens[at:at + count], at, rows,
                             tail if at == calls[-1][0] else 0)
        return out

    def sequence_parts(self, tokens, prompt_len: int) -> dict:
        """The first ``prompt_len`` of ``tokens`` prefilled
        (:meth:`calls`), the rest decoded one tick each. Over the LAST
        call's ``own`` tokens and the ticks: ``logits``; the routing
        collection's leaves ``[layers, positions, ...]`` (``input``,
        ``experts``, ``weights``, ``output``; ``kda_q`` .. ``kda_o``); ``kv``
        ``[GQA layers, 2, positions, width]`` as cached. And what the lane
        held of every KDA layer ``(S, filter rows)`` before the last call
        (``state_before``), after the prefill (``state_prefill``) and after
        the last tick (``state_end``); and ``all_experts`` ``[layers,
        len(tokens), k]``, the experts chosen at EVERY position."""
        import jax.numpy as jnp

        engine = self.engine
        manager = engine.cache_manager
        lane, _ = manager.alloc(-1, tokens[:prompt_len])

        def call(at, count, rows, parts):
            padded = np.zeros(rows, np.int32)
            padded[:count] = tokens[at:at + count]
            manager.cache, logits, sown = self._forward_parts(
                engine.params, manager.cache, jnp.asarray(padded),
                jnp.asarray(at, jnp.int32), jnp.asarray(count, jnp.int32),
                jnp.asarray(manager.lane_tables(lane)), parts=parts)
            return logits, sown

        try:
            calls = self.calls(prompt_len)
            earlier = [np.asarray(call(*each, False)[1]["experts"],
                                  np.int32)[:, :each[1]]
                       for each in calls[:-1]]
            at, own, rows = calls[-1]
            before = lane_state(engine, lane)
            logits, sown = call(at, own, rows, True)
            out = [np.asarray(logits[:own])]
            parts = {k: [np.asarray(v[:, :own], np.float32)]
                     for k, v in sown.items()}
            after = lane_state(engine, lane)
            for token in tokens[prompt_len:]:
                if not manager.ensure_page(lane):
                    raise RuntimeError("the pool ran dry in the check")
                manager.cache, logits, sown = self._tick_parts(
                    engine.params, manager.cache,
                    jnp.asarray(token, jnp.int32),
                    jnp.asarray(manager.lengths[lane], jnp.int32),
                    jnp.asarray(lane, jnp.int32), jnp.asarray(manager.tables))
                manager.lengths[lane] += 1
                out.append(np.asarray(logits))
                for k, v in sown.items():
                    parts[k].append(np.asarray(v, np.float32))
            end = lane_state(engine, lane)
            kv = lane_rows(engine, lane, at, len(tokens))
        finally:
            manager.free(lane)
        parts = {k: np.concatenate(v, axis=1) for k, v in parts.items()}
        return {"logits": np.concatenate(out), "own": own, "kv": kv,
                "state_before": before, "state_prefill": after,
                "state_end": end, **parts,
                "all_experts": np.concatenate(
                    earlier + [parts["experts"].astype(np.int32)], axis=1)}


def layer_check(mine: dict, variables, cell, chosen) -> dict:
    """``serve_closed_loop_mla.layer_check`` (the weights a share's layer
    applied against the reference router's for the same experts on the same
    input, every expert it chose among the reference's ``k`` highest of
    score + bias, its output against the reference's sum over the held ones
    of them plus the shared expert), judged by this file's limits."""
    out = mla_driver.layer_check(mine, variables, cell, chosen)
    out["layer_tol"] = [LAYER_WEIGHT_TOL, LAYER_OUTPUT_TOL]
    out["layers_ok"] = bool(
        out["layer_weight_max_rel_err"] <= LAYER_WEIGHT_TOL
        and not out["layer_experts_beside_reference"]
        and out["layer_output_rel_rms_err"] <= LAYER_OUTPUT_TOL)
    return out


def rule_check(mine: dict, cell) -> dict:
    """The delta rule alone on the rows it really saw (module docstring):
    per KDA layer, the reference's scan over tokens of the ``q, k, v, g,
    beta`` the system's kernels were handed in the last call and the ticks,
    from the state the lane held before them; rms of the difference over
    the rms of the value, ``o`` and the last ``S``, the worst layer."""
    import jax

    rule = jax.jit(ref_driver.reference_module(cell).delta_rule)
    kinds = cell.config["model"]["layer_types"]
    places = [i for i, t in enumerate(kinds) if t == "kda"]
    o_err, s_err = [], []
    for at, layer in enumerate(places):
        o, state = rule(*(mine["kda_" + n][layer] for n in (
            "q", "k", "v", "g", "beta")), mine["state_before"][0][at])
        o_err.append(float(_rel_rms(np.asarray(o), mine["kda_o"][layer],
                                    (0, 1, 2))))
        s_err.append(float(_rel_rms(np.asarray(state),
                                    mine["state_end"][0][at], (0, 1, 2))))
    return {"rule_output_rel_rms_err": max(o_err),
            "rule_state_rel_rms_err": max(s_err),
            "rule_state_rel_rms_err_by_layer": s_err,
            "rule_beta_max": float(mine["kda_beta"].max()),
            "rule_log_decay_min": float(mine["kda_g"].min()),
            "rule_tol": RULE_TOL,
            "rule_ok": bool(max(o_err + s_err) <= RULE_TOL)}


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
    mine = served.sequence_parts(tokens, prompt)
    engine.cache_manager.pool.check_invariants()
    own = mine["own"]
    # the reference sums over the SYSTEM'S experts at EVERY position (the
    # eighth and ninth of 320 sigmoid scores lie a rounding apart at most
    # positions, and a matrix state integrates every position before it;
    # ``layer_check`` holds the choice to the router's at the positions
    # compared)
    theirs = jax.jit(
        ref_driver.reference_module(cell).configured(cell.config["model"]),
        static_argnames=("tail", "with_parts", "states_at"))(
        variables["params"], tokens, tail=own + decode, with_parts=True,
        given=mine["all_experts"], states_at=(prompt, prompt + decode))
    theirs = {k: np.asarray(v) for k, v in theirs.items()}
    reference = theirs["logits"]
    unit = float(reference.std())
    err = np.abs(mine["logits"] - reference)
    rows_err = _rel_rms(theirs["kv"], mine["kv"], (2, 3))     # [layers, 2]
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
           "reference_rows_rel_rms_err": float(rows_err.max()),
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
    out.update(rule_check(mine, cell))
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
    """``serve_closed_loop_ssm.engine_check`` as it is (the state, the
    filter rows, the keys and values and the tokens the ENGINE'S OWN
    programs left in lanes in flight against ``Served`` on the same
    sequences), reading this kind's leaves and judged by this file's limits
    (the first KDA layer stands behind a GQA layer, so no layer's state is
    bit for bit the check's: ``rule_check`` is what holds the state's
    precision)."""
    def flat(engine, lane):  # (that check takes [layers, rows, width])
        state, conv = lane_state(engine, lane)
        return state.reshape(len(state), -1, state.shape[-1]), conv

    theirs, ssm_driver.lane_state = ssm_driver.lane_state, flat
    try:
        out = ssm_driver.engine_check(engine, served, unit, tail,
                                      engine.prefill_chunk)
    finally:
        ssm_driver.lane_state = theirs
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
    """``serve_closed_loop_ref.run`` with this file's set-up and stream in
    the place of its own, then the engine check on what the window left in
    flight."""
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
        if k.startswith(("state_", "kv_page_", "kda_", "moe_"))}))
    engine, checks = held["engine"], out.checks
    checks["memory_peak_gb_after"] = dict(
        held["peak"], window=harness.memory_peak_bytes(cell.chips) / 1e9)
    # everything the window is read from is taken; now what the engine's
    # programs left in flight, which ends those requests
    checks.update(engine_check(
        engine, held["served"], held["reference"]["reference_logit_std"],
        check_sizes(cell)[2]))
    from fleetx_tpu.ops.pallas.kda import STEP_KERNEL_NAME

    checks["kda_step_mosaic_calls"] = harness.mosaic_calls(
        engine.compiled_decode().as_text(), STEP_KERNEL_NAME)
    checks["correct"] = bool(
        checks["correct"] and checks["engine_ok"]
        and (checks["kda_step_mosaic_calls"] > 0 or cell.tiny))
    out.correct = checks["correct"]
    return out
