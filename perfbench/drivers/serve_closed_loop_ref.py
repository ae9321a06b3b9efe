"""Serving, closed loop, for a configuration that names its own reference
and weight type: the loop of ``serve_closed_loop.py`` (``clients`` callers,
each sending its next request when its last one has returned; the first
round is warm-up and the window opens when the last of it has returned;
tokens count if delivered inside the window), returning the same
``harness.Run`` with the same ``samples`` keys, so that every reader of a
closed-loop cell reads it.

What differs is the set-up, which ``perfbench/serving.py`` writes for
float32 GPT weights and ``gpt_f32``. Here the configuration file names the
weights' type (``weight_dtype``: made AND cast inside one jitted call, so
no float32 copy of the tree ever sits on the device) and the reference
module (``reference``: ``perfbench/reference/<name>.py`` with
``configured(model) -> logits function``). The pieces of
``perfbench/serving.py`` that know no model are used as they are:
``build_engine`` (which hands the cell's ``prefill_bucket`` on),
``Clients``, ``warm_up``, ``engine_answers``, ``served_logits``,
``serving_checks``, ``counters``.

``correct`` is decided as ``serving_checks`` decides it; its reference
part is :func:`reference_check` below.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import sys
import time

import numpy as np

from perfbench import harness, serving, traffic as traffic_gen

# How far the system may stand from the float32 reference. Two comparisons
# decide, each with limits set from two readings on the chip at the
# published widths (my chip runs, PR 27; PERF.md section 6): the largest
# reading of the engine as built over its seeds, and the smallest reading of
# the next precision down, which has to come out NOT correct
# (``perfbench/probe_precision.py`` takes both).
#
# 1. Every expert layer against the reference's layer ON THE INPUT IT
# REALLY SAW (:func:`layer_check`). This is what refuses a lower precision
# in the layer the cell is named for; the logits cannot, because at these
# weights the experts carry a tenth of the residual stream and bfloat16
# activations through eight layers cost more than int8 experts do.
# - ``LAYER_WEIGHT_TOL``, the routing weights the layer applied against the
#   reference router's probabilities for the same experts, largest relative
#   error: as built 4.8e-7 to 1.2e-6 over ten seeds (both sides float32 at
#   ``highest``); a router computed in bfloat16 0.0114-0.0124. The limit is
#   1e-4, a hundred times each way. An expert the layer chose counts as
#   beside the reference's when the reference's router rates it under its
#   own eighth by more than that limit (a tie inside the limit is no
#   fault): as built 0 of 672 layer-positions, bfloat16 router 11-18; none
#   is allowed.
# - ``LAYER_OUTPUT_TOL``, the layer's output against the reference's sum
#   over the same experts, rms over the layer's rms, the worst layer: as
#   built 0.00288-0.00292 (three bfloat16 roundings: the activation, the
#   kernel's output, the sum); experts rounded to int8 with a scale per
#   column 0.01529-0.01546 (a bfloat16 router: 0.00493-0.00534). The limit
#   is 0.0066, the geometric middle: 2.3 times each way.
#
# 2. The logits of the whole model and the tokens the engine returned, in
# units of the standard deviation of the reference's logits (0.90-0.91
# here; the unit makes the limits hold at other widths), as
# ``perfbench/serving.py`` judges the GPT cells. These refuse what is wrong
# between the layers or in a few places only (a stale page, a wrong
# position or block table, keys rotated after the cache write, QK-norm left
# out: each stands 30 times outside a float32 tolerance in
# tests/test_olmoe_serving.py). Engine as built over ten seeds: largest
# logit error 0.053-0.080, rms 0.0078-0.0090, a returned token at most
# 0.017 below the reference's best. The largest error over 4 million logits
# swings by half between seeds, so its limit and the token's are twice the
# largest reading; the rms limit is 1.8 times its largest. They do NOT tell
# a lower precision apart: int8 experts read rms 0.0079-0.0092 and a
# bfloat16 router 0.0078-0.0091 on the seeds where the engine as built read
# 0.0079-0.0090. Where two experts' router probabilities lie closer than
# the rounding of the layers before, the system's top 8 differ from the
# reference's own forward in one expert: ``experts_differ_positions``
# counts such positions and is printed, not judged (the chip read 18-32 of
# 84).
REFERENCE_MAX_TOL = 0.16
REFERENCE_RMS_TOL = 0.016
REFERENCE_TOKEN_TOL = 0.16
LAYER_WEIGHT_TOL = 1e-4
LAYER_OUTPUT_TOL = 0.0066


def build_model(cell, seed: int):
    """``(model, variables)``: the cell's configuration at its compute
    type, dropout off, the weights made from the seed and cast to the
    configuration's ``weight_dtype`` inside ONE jitted call."""
    import flax
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    sizes = dict(cell.config["model"])
    missing = sorted(set(sizes) - {f.name for f in dataclasses.fields(GPTConfig)})
    if missing:  # an older program: say so at once, before any compile
        sys.exit(f"perfbench: this program's GPTConfig has no {missing}: it "
                 f"cannot run configuration {cell.config['name']!r}")
    sizes.update(hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
                 fuse_attn_qkv=True,
                 use_flash_attention=cell.deploy.get("use_flash_attention", True),
                 dtype=cell.config["compute_dtype"])
    model = GPTForPretraining(GPTConfig.from_model_config(sizes))
    held = jnp.dtype(cell.config["weight_dtype"])

    @jax.jit
    def make(key):
        variables = flax.core.meta.unbox(
            model.init(key, np.zeros((1, 8), np.int32)))
        return jax.tree.map(lambda x: x.astype(held), variables)

    return model, make(jax.random.PRNGKey(seed))


def reference_module(cell):
    """The configuration's reference, ``perfbench/reference/<reference>.py``:
    ``configured(model)`` gives ``logits(params, tokens,
    with_experts=False)`` and ``configured_layers(model)`` the expert layers
    alone."""
    return importlib.import_module(
        "perfbench.reference." + cell.config["reference"])


def served_routing(engine, tokens):
    """What the expert layers of the ENGINE'S model saw, chose and gave for
    ``tokens``: ``{"input", "output": [layers, len(tokens), hidden],
    "experts", "weights": [layers, len(tokens), k]}``. Prefill of the first
    ``serving._PROMPT`` then one decode step for each of the rest through a
    small paged cache, as ``serving.served_logits`` runs it, on
    ``engine.params``, asking the model for its ``routing`` collection.
    None for a model that has none."""
    import jax
    import jax.numpy as jnp

    prompt = serving._PROMPT
    page = engine.page_size
    bucket = -(-prompt // engine.prefill_bucket) * engine.prefill_bucket
    rows = -(-(bucket + len(tokens) - prompt) // page)
    model = engine.model.clone(cfg=dataclasses.replace(
        engine.model.cfg, decode_cache_len=rows * page,
        decode_num_pages=rows + 1))
    table = jnp.arange(1, rows + 1, dtype=jnp.int32)[None]  # page 0 is trash

    @jax.jit
    def forward(params, cache, ids, at):
        pos = at + jnp.arange(ids.shape[0], dtype=jnp.int32)
        _, mut = model.apply(
            {"params": params, "cache": cache}, ids[None], pos[None], None,
            decode=True, cache_positions=at[None], block_tables=table,
            mutable=["cache", "routing"])
        # under the layer scan one leaf [layers, 1, s, width] of each name
        sown = {jax.tree_util.keystr(path[-2:-1]).strip("[']"): leaf[:, 0]
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                    mut.get("routing", {}))[0]}
        return mut["cache"], sown

    padded = np.zeros(bucket, np.int32)
    padded[:prompt] = tokens[:prompt]
    cache, sown = forward(engine.params, engine.executor.bind(
        model).init_cache(1), jnp.asarray(padded), jnp.asarray(0, jnp.int32))
    if not sown:
        return None
    out = {name: [np.asarray(leaf[:, :prompt], np.float32)]
           for name, leaf in sown.items()}
    for i in range(prompt, len(tokens)):
        cache, sown = forward(engine.params, cache,
                              jnp.asarray(tokens[i:i + 1]),
                              jnp.asarray(i, jnp.int32))
        for name, leaf in sown.items():
            out[name].append(np.asarray(leaf, np.float32))
    return {name: np.concatenate(parts, axis=1) for name, parts in out.items()}


def layer_check(engine, variables, cell, tokens, chosen) -> dict:
    """Every expert layer of the engine's model against the reference's
    layer ON THE INPUT THE SYSTEM'S LAYER REALLY SAW (prefill, then decode
    through the paged cache): the routing weights it applied against the
    reference router's probabilities for the same experts, whether each
    expert it chose is among the reference's ``k`` most probable for that
    input (a tie inside the weight limit is no fault), and its output
    against the reference's sum over the same experts. ``chosen`` is the
    reference's choice in its OWN forward ``[layers, positions, k]``: where
    the system's differs, the rounding of the layers before has moved the
    input (counted, not judged). Empty for a model without experts."""
    import jax

    mine = served_routing(engine, tokens)
    if not mine:
        return {}
    picked = mine["experts"].astype(np.int32)
    sums, probs = jax.jit(
        reference_module(cell).configured_layers(cell.config["model"]))(
        variables["params"], mine["input"], picked)
    sums, probs = np.asarray(sums), np.asarray(probs)
    k = picked.shape[-1]
    theirs = np.take_along_axis(probs, picked, -1)   # [layers, positions, k]
    weight_err = float(np.abs(mine["weights"] / theirs - 1.0).max())
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


def reference_check(engine, variables, cell, seed: int) -> dict:
    """``serving.reference_check`` against the configuration's own
    reference, which reads the weights as made (``variables``): the tokens
    the engine itself returns for seeded requests through ``submit`` and
    ``step`` (how far the reference rates each below its own best), the
    logits of its executor on its weights (prefill, then decode through the
    paged cache), and every expert layer on the input it really saw
    (:func:`layer_check`)."""
    import jax

    logits = jax.jit(reference_module(cell).configured(cell.config["model"]),
                     static_argnames=("with_experts",))
    answers = serving.engine_answers(engine, cell, seed)
    width = max(len(p) + len(t) for p, t in answers)
    rows = np.zeros((len(answers), width), np.int32)  # right-padded: causal
    for row, (prompt, tokens) in zip(rows, answers):
        row[:len(prompt) + len(tokens)] = np.concatenate([prompt, tokens])
    rated = np.asarray(logits(variables["params"], rows))
    deficits = np.concatenate([
        (at := row[len(p) - 1:len(p) - 1 + len(t)]).max(-1)
        - at[np.arange(len(t)), t] for row, (p, t) in zip(rated, answers)])
    complete = all(len(t) == serving._ANSWER_TOKENS for _, t in answers)

    tokens = np.random.default_rng([seed, 4]).integers(
        1, cell.config["model"]["vocab_size"],
        serving._PROMPT + serving._DECODE, dtype=np.int32)
    system = serving.served_logits(engine, tokens)
    reference, chosen, _ = logits(variables["params"], tokens[None],
                                  with_experts=True)
    reference = np.asarray(reference[0])
    err, unit = np.abs(system - reference), float(reference.std())
    out = {"reference_logit_std": unit,
           "reference_max_abs_err": float(err.max()),
           "reference_decode_max_abs_err": float(err[serving._PROMPT:].max()),
           "reference_rms_err": float(np.sqrt((err ** 2).mean())),
           "engine_tokens_checked": int(deficits.size),
           "engine_tokens_reference_best": int((deficits == 0).sum()),
           "engine_token_max_deficit": float(deficits.max()),
           "reference_tol_in_std": [REFERENCE_MAX_TOL, REFERENCE_RMS_TOL,
                                    REFERENCE_TOKEN_TOL]}
    layers = layer_check(engine, variables, cell, tokens,
                         np.asarray(chosen)[:, 0])
    out.update(layers)
    out["reference_ok"] = bool(
        complete and layers.get("layers_ok", True)
        and out["reference_max_abs_err"] <= REFERENCE_MAX_TOL * unit
        and out["reference_rms_err"] <= REFERENCE_RMS_TOL * unit
        and out["engine_token_max_deficit"] <= REFERENCE_TOKEN_TOL * unit)
    return out


def set_up(cell, seed: int, t_process: float):
    """``serving.set_up`` with this file's weights, engine and reference."""
    device = harness.own_the_chip(cell.chips, cell.tiny)

    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = harness.CompileClock()
    phases = {"import_s": time.perf_counter() - t_process}
    model, variables = build_model(cell, seed)
    engine = serving.build_engine(cell, model, variables)
    phases["weights_and_engine_s"] = time.perf_counter() - t_process
    buckets = serving.warm_up(engine, cell, seed)
    phases["warm_up_s"] = time.perf_counter() - t_process
    reference = reference_check(engine, variables, cell, seed)
    phases["reference_s"] = time.perf_counter() - t_process
    return device, clock, engine, reference, buckets, phases


def run(cell, seed: int, seconds: float, trace: bool, t_process: float):
    device, clock, engine, reference, buckets, phases = set_up(
        cell, seed, t_process)
    job = cell.traffic
    vocab = cell.config["model"]["vocab_size"]
    streams = [traffic_gen.client_stream(job, seed, c, vocab)
               for c in range(job["clients"])]
    harness.log(f"{len(streams)} clients; warmed {len(buckets)} prefill "
                f"buckets {buckets[0]}-{buckets[-1]}")
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
                f"closed-loop ttft ms p50 {harness.percentile(ttft, 50):.1f}")
    counters = serving.counters(engine)
    harness.log("routing counters " + json.dumps(
        {k: v for k, v in counters.items() if k.startswith("moe_")}))
    return harness.Run(
        cell=cell, device=device, setup_s=start - t_process,
        window=(start, end), attempted=len(inside),
        failed=len(clients.refused) + checks["wrong_results"],
        correct=checks["correct"], checks=checks, samples=samples,
        spans=harness.program_spans(start), counters=counters,
        traced=profiler.traced, trace=profiler.reduce() if trace else None,
        peaks=harness.device_peaks(device, cell.tiny))
