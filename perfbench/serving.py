"""What the serving drivers share: the engine built as
``tools/bench_serving.py`` and ``chip_smoke.py`` build it, the warm-up of
every prefill bucket the traffic's length range can reach, the client-side
clock on ``on_token``, the reference check and the checks behind
``correct``."""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from perfbench import harness, traffic as traffic_gen
from perfbench.reference import gpt_f32

# How far the system may stand from the float32 reference, in units of
# the standard deviation of the reference's logits (0.9 at GPT-1.3B, where
# the tolerances were set; the unit makes them hold at other widths).
# The system computes in bf16: 8 bits of mantissa through 24 layers. The
# chip runs of PR 22 read a largest logit error of 0.059-0.068 (0.075 of
# the standard deviation) over 4.2 million logits and an rms of
# 0.0118-0.0123 (0.0136) (PERF.md, Findings), so the bounds sit at about
# twice that: a path with fewer bits (int8 weights or cache, fp8) at least
# doubles the rms, which is the steadier of the two. A token the ENGINE
# returned may stand below the reference's best logit at its position by
# two such errors (one on each of the two logits), and no more (the chip
# read 0-0.032): greedy decoding through a stale page, a wrong block table
# or a wrong position picks tokens the reference rates far lower.
REFERENCE_MAX_TOL = 0.16
REFERENCE_RMS_TOL = 0.027
REFERENCE_TOKEN_TOL = 0.16
_PROMPT, _DECODE = 80, 4        # the logits check: prefill, then decode steps
_ANSWERS, _ANSWER_TOKENS = 4, 16  # the engine check: requests, tokens of each


def build_model(cell, seed: int):
    """``(model, variables)``: the cell's configuration at bf16 compute,
    dropout off, weights made on the device from the seed in one jitted
    call."""
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    sizes = dict(cell.config["model"])
    sizes.update(hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    model = GPTForPretraining(GPTConfig(
        **sizes, fuse_attn_qkv=True,
        use_flash_attention=cell.deploy.get("use_flash_attention", True),
        dtype=jnp.dtype(cell.config["compute_dtype"])))
    return model, jax.jit(model.init)(
        jax.random.PRNGKey(seed), np.zeros((1, 8), np.int32))


def build_engine(cell, model, variables):
    """The engine as ``tools/bench_serving.py`` and ``chip_smoke.py`` build
    it: paged cache, greedy, EOS off, sizes from the cell's file (a cell
    without ``prefill_bucket`` keeps the engine's default)."""
    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.serving import ServingEngine

    deploy = cell.deploy
    max_new = max(traffic_gen.length_bounds(t["output"])[1]
                  for t in cell.traffic["tenants"])
    return ServingEngine(
        model, variables, slots=deploy["lanes"], cache_len=deploy["cache_len"],
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=max_new),
        paged=True, page_size=deploy["page_size"],
        num_pages=deploy["pool_tokens"] // deploy["page_size"] + 1,
        prefill_bucket=deploy.get("prefill_bucket"))


def warm_up(engine, cell, seed: int) -> list:
    """One request for every prefill bucket the traffic's LENGTH RANGE can
    reach (not only the lengths this seed drew, so every seed compiles the
    same set), each followed by a decode tick. Returns the bucket lengths."""
    vocab = cell.config["model"]["vocab_size"]
    step = engine.prefill_bucket
    buckets = set()
    for tenant in cell.traffic["tenants"]:
        lo, hi = traffic_gen.length_bounds(tenant["prompt"])
        lo = max(lo, tenant.get("shared_prefix_len", 0) + 1)
        buckets.update((-(-n // step) * step, min(-(-n // step) * step, hi))
                       for n in range(lo, hi + 1))
    rng = np.random.default_rng([seed, 3])
    for _, length in sorted(buckets):
        engine.submit(rng.integers(1, vocab, length, dtype=np.int32),
                      max_length=2)
        engine.drain()
    return sorted(b for b, _ in buckets)


def engine_answers(engine, cell, seed: int) -> list:
    """``(prompt, tokens)`` of a few seeded requests served by the ENGINE
    ITSELF, together, through ``submit`` and ``step``: its compiled prefill
    and tick, its block tables and page pool, the weights as it holds
    them. The prompts are of the traffic's shortest lengths, so they use
    prefill programs the warm-up has compiled anyway."""
    vocab = cell.config["model"]["vocab_size"]
    lo, hi = min(traffic_gen.length_bounds(t["prompt"])
                 for t in cell.traffic["tenants"])
    rng = np.random.default_rng([seed, 5])
    ids = [engine.submit(rng.integers(1, vocab, min(lo + 7 * i, hi),
                                      dtype=np.int32),
                         max_length=_ANSWER_TOKENS) for i in range(_ANSWERS)]
    results = engine.drain()
    return [(np.asarray(results[i].prompt), np.asarray(results[i].tokens))
            for i in ids]


def token_deficits(answers: list, variables) -> np.ndarray:
    """For every token of ``answers``, how far the float32 reference rates
    it below its own best token at that position (0 where they agree), the
    reference reading the engine's own tokens before it."""
    import jax

    width = max(len(p) + len(t) for p, t in answers)
    rows = np.zeros((len(answers), width), np.int32)  # right-padded: causal
    for row, (prompt, tokens) in zip(rows, answers):
        row[:len(prompt) + len(tokens)] = np.concatenate([prompt, tokens])
    reference = np.asarray(jax.jit(gpt_f32.logits)(variables["params"], rows))
    out = []
    for row, (prompt, tokens) in zip(reference, answers):
        at = row[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
        out.append(at.max(-1) - at[np.arange(len(tokens)), tokens])
    return np.concatenate(out)


def served_logits(engine, tokens) -> np.ndarray:
    """Logits of ``tokens`` as the engine computes them: prefill of the
    first ``_PROMPT`` then one decode step for each of the rest through a
    (small) paged cache, by the engine's own executor on the weights as the
    engine holds them (``engine.params``: int8 or bf16 where it serves
    those, passed through its own dequantisation seam inside the jit, as
    its prefill and tick do). The engine's programs return tokens only, so
    the logits need a program of their own."""
    import jax
    import jax.numpy as jnp

    page = engine.page_size
    bucket = -(-_PROMPT // engine.prefill_bucket) * engine.prefill_bucket
    rows = -(-(bucket + _DECODE) // page)
    executor = engine.executor.bind(engine.model.clone(cfg=dataclasses.replace(
        engine.model.cfg, decode_cache_len=rows * page,
        decode_num_pages=rows + 1)))
    table = jnp.arange(1, rows + 1, dtype=jnp.int32)[None]  # page 0 is trash

    @jax.jit
    def prefill(params, cache, ids):
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)[None]
        return executor.forward(
            engine._dequant_params(params), cache, ids[None], pos,
            cache_positions=jnp.zeros((1,), jnp.int32), block_tables=table)

    @jax.jit
    def decode(params, cache, tok, at):
        return executor.forward(
            engine._dequant_params(params), cache, tok[None, None],
            at[None, None], cache_positions=at[None], block_tables=table)

    padded = np.zeros(bucket, np.int32)
    padded[:_PROMPT] = tokens[:_PROMPT]
    logits, cache = prefill(engine.params, executor.init_cache(1),
                            jnp.asarray(padded))
    out = [np.asarray(logits[0, :_PROMPT], np.float32)]
    for i in range(_PROMPT, len(tokens)):
        logits, cache = decode(engine.params, cache, jnp.asarray(tokens[i]),
                               jnp.asarray(i, jnp.int32))
        out.append(np.asarray(logits[0, -1:], np.float32))
    return np.concatenate(out)


def reference_check(engine, variables, cell, seed: int) -> dict:
    """The engine against the float32 reference, which reads the weights
    as made (``variables``), outside the window. Two parts: the tokens the
    engine itself returns for seeded requests (``engine_answers``), and the
    logits of its executor on its weights (``served_logits``)."""
    import jax

    answers = engine_answers(engine, cell, seed)
    deficits = token_deficits(answers, variables)
    complete = all(len(t) == _ANSWER_TOKENS for _, t in answers)
    tokens = np.random.default_rng([seed, 4]).integers(
        1, cell.config["model"]["vocab_size"], _PROMPT + _DECODE,
        dtype=np.int32)
    system = served_logits(engine, tokens)
    reference = np.asarray(
        jax.jit(gpt_f32.logits)(variables["params"], tokens[None])[0])
    err, unit = np.abs(system - reference), float(reference.std())
    out = {"reference_logit_std": unit,
           "reference_max_abs_err": float(err.max()),
           "reference_decode_max_abs_err": float(err[_PROMPT:].max()),
           "reference_rms_err": float(np.sqrt((err ** 2).mean())),
           "engine_tokens_checked": int(deficits.size),
           "engine_tokens_reference_best": int((deficits == 0).sum()),
           "engine_token_max_deficit": float(deficits.max()),
           "reference_tol_in_std": [REFERENCE_MAX_TOL, REFERENCE_RMS_TOL,
                                    REFERENCE_TOKEN_TOL]}
    out["reference_ok"] = bool(
        complete and out["reference_max_abs_err"] <= REFERENCE_MAX_TOL * unit
        and out["reference_rms_err"] <= REFERENCE_RMS_TOL * unit
        and out["engine_token_max_deficit"] <= REFERENCE_TOKEN_TOL * unit)
    return out


def set_up(cell, seed: int, t_process: float):
    """Everything before the first request, the same for every serving
    driver: the chip, the compile cache and clock, the engine, the
    reference check and the warm-up. Returns ``(device, clock, engine,
    reference, buckets, phases)``; ``phases`` holds the seconds since the
    process started at which each part was done."""
    device = harness.own_the_chip(cell.chips, cell.tiny)

    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = harness.CompileClock()
    phases = {"import_s": time.perf_counter() - t_process}
    model, variables = build_model(cell, seed)
    engine = build_engine(cell, model, variables)
    phases["weights_and_engine_s"] = time.perf_counter() - t_process
    buckets = warm_up(engine, cell, seed)
    phases["warm_up_s"] = time.perf_counter() - t_process
    reference = reference_check(engine, variables, cell, seed)
    phases["reference_s"] = time.perf_counter() - t_process
    return device, clock, engine, reference, buckets, phases


class Clients:
    """The client side of the engine: submits, and stamps every
    ``on_token`` with this process's ``perf_counter``."""

    def __init__(self, engine):
        from fleetx_tpu.serving.engine import QueueFull, ShuttingDown

        self.engine = engine
        self._refused = (QueueFull, ShuttingDown)
        self.records = {}       # request id -> record
        self.refused = []       # records the engine refused at submit
        self.open = set()       # ids submitted and not finished
        self.token_s = []       # stamp of every delivered token
        self.live_tokens = 0    # cached positions of the open requests
        self.finished = []      # ids in order of finishing

    def submit(self, request, due_s: float, **extra) -> dict:
        rec = {"request": request, "due_s": due_s, "stamps": [],
               "submit_s": time.perf_counter(), "id": None, **extra}
        try:
            rid = self.engine.submit(request.prompt,
                                     max_length=request.max_new_tokens,
                                     on_token=self._on_token)
        except self._refused:
            self.refused.append(rec)
            return rec
        rec["id"] = rid
        self.records[rid] = rec
        self.open.add(rid)
        return rec

    def _on_token(self, rid, _token, finished):
        now = time.perf_counter()
        rec = self.records[rid]
        if not rec["stamps"]:
            self.live_tokens += len(rec["request"].prompt)
        rec["stamps"].append(now)
        self.token_s.append(now)
        self.live_tokens += 1
        if finished:
            self.open.discard(rid)
            self.finished.append(rid)
            self.live_tokens -= len(rec["request"].prompt) + len(rec["stamps"])

    def gaps(self, start: float, end: float) -> list:
        """``(end of the gap, milliseconds)`` of every gap between
        consecutive tokens of one request that ended inside
        ``[start, end]``."""
        return [(b, (b - a) * 1e3) for rec in self.records.values()
                for a, b in zip(rec["stamps"][:-1], rec["stamps"][1:])
                if start <= b <= end]


def serving_checks(engine, clients: Clients, clock, window, reference,
                   buckets, phases) -> dict:
    """What ``correct`` is decided from, for either serving driver."""
    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.ops.pallas.decode_attention import PAGED_KERNEL_NAME

    wrong = []
    for rid in clients.finished:
        result = engine.take_result(rid)
        budget = clients.records[rid]["request"].max_new_tokens
        if (result is None or result.finish_reason != "max_length"
                or len(result.tokens) != budget):
            wrong.append(rid)
    snap = engine.metrics.snapshot()
    events = get_event_log().counts()
    faults = {kind: int(events.get(kind, 0)) for kind in (
        "fault_injected", "engine_recovery", "tick_fault", "poison_retired",
        "cache_full")}
    checks = {
        "finished": len(clients.finished), "wrong_results": len(wrong),
        "refused": len(clients.refused),
        "engine_recoveries": int(snap["engine_recoveries"]),
        "poison_retired": int(snap["poison_retired"]),
        "fault_events": faults,
        "mosaic_calls": harness.mosaic_calls(
            engine.compiled_decode().as_text(), PAGED_KERNEL_NAME),
        "compiles_in_window": clock.inside(*window),
        "prefill_buckets": len(buckets),
        "setup_done_at_s": phases,  # seconds since process start
        **reference, **clock.report(),
    }
    checks["correct"] = (
        not wrong and not checks["refused"]
        and not checks["engine_recoveries"] and not checks["poison_retired"]
        and not any(faults.values()) and checks["mosaic_calls"] > 0
        and checks["compiles_in_window"] == 0
        and reference["reference_ok"])
    return checks


def counters(engine) -> dict:
    """The engine's own counts after the window (numbers only)."""
    return {k: v for k, v in engine.metrics.snapshot().items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}
