"""Serving-mode bench: static vs continuous batching, mixed-length load.

The quantitative case for `fleetx_tpu/serving/`: one fixed workload of
mixed prompt lengths AND mixed requested decode lengths, run two ways —

- **static**: requests grouped into padded batches of `slots` in arrival
  order, each batch one blocking `generate()` call running to the batch
  max; early-finishing rows burn decode steps as dead padding and tokens
  only surface when the whole batch returns (classic InferenceEngine
  serving).
- **continuous**: the same requests through `ServingEngine` — admitted
  into free slots the tick one opens, retired individually, every decode
  step full of live rows.

Both modes decode greedily with EOS disabled, so they emit byte-identical
tokens per request (asserted, `detail.parity`) and the comparison is pure
scheduling: useful-tokens/s, TTFT, queue depth, slot occupancy.

A third record exercises the PAGED cache's shared-prefix reuse: every
request carries the same system prompt, run twice through one paged
engine — the first pass populates the prefix trie (and compiles), the
timed pass hits it — reporting prefix hit rate, prefill tokens saved,
page occupancy, and fresh pages/request next to the usual TTFT and
tokens/s (byte parity between cold-trie and warm-trie passes asserted).

A fourth record (`faulted`) prices the crash-safety machinery: the same
continuous workload with a decode-tick failure injected mid-run, so the
engine rolls the tick back and replay-recovers (docs/RESILIENCE.md). It
reports tokens/s next to the clean run (`recovery_overhead_frac`), the
recovery count, and tick p50/p99 — resilience cost in the perf
trajectory, with byte parity vs the clean run asserted.

A fifth record (`int8`) runs the QUANTIZED serving path
(docs/QUANTIZATION.md): the same continuous workload through an engine
with int8 KV cache + int8 weight-only params. It reports tokens/s next
to the bf16 run (`speedup_vs_bf16`), the measured cache/param HBM bytes,
the XLA cost-model bytes one decode tick moves per lane for BOTH
precisions (`decode_bytes_per_token*` — the bandwidth claim, from
`Compiled.cost_analysis()`), and asserts the tolerance-parity contract:
every request's stream must share at least 75% of its leading tokens
with the bf16 run (`parity` + `parity_prefix_frac_min`; byte parity is
deliberately NOT required — that is the bf16 contract).

A sixth record (`chunked`) prices CHUNKED PREFILL (docs/SERVING.md): a
long-prompt mixed workload run with and without
`FLEETX_SERVING_PREFILL_CHUNK`, reporting decode TPOT p50/p99 (inter-
token gaps observed through `on_token` callbacks — the latency long
arriving prompts hold hostage) both ways with byte parity asserted, plus
the engine's `prefill_stall_ms` percentiles: with chunking on, no tick
stalls decode longer than ~one chunk-sized prefill call. Its
`detail.spill` sub-report runs an OVERSUBSCRIBED shared-prefix workload
(hot prefix set > device page pool) with the host-DRAM spill tier on vs
off: without it LRU eviction destroys every warm prefix (hit rate
collapses on revisit), with it spilled pages revive from host DRAM and
the hit rate holds — byte parity asserted, spill/revive/byte counters
reported.

A seventh record (`spec`) prices SPECULATIVE DECODING (docs/SERVING.md):
a repetitive motif workload (the template/code-edit shape where n-gram /
prompt-lookup drafting shines) run through a baseline engine and a
`FLEETX_SERVING_SPEC=1` engine at the default k — greedy byte parity
ASSERTED, mean tokens-per-tick > 1 asserted, tokens/s speedup vs
baseline, acceptance rate, and baseline-vs-spec TTFT reported, plus a
`detail.k_sweep` over `FLEETX_SERVING_SPEC_K` ∈ {2, 4, 8} (each swept k
byte-identical too).

An eighth record (`mesh`) prices MESH-SHARDED SERVING (docs/SERVING.md
"Mesh-sharded serving"): the same continuous workload through an engine
whose params and KV cache shard over a TP(mp2) mesh — byte parity vs the
single-device run ASSERTED, per-device `fleetx_serving_kv_cache_bytes`
(~half the single-device engine's), tokens/s, TTFT, and the mesh shape
in `detail.mesh`. Skipped (no record) below 2 devices or when the heads
don't divide.

A ninth record (`router_slo`) banks the MULTI-REPLICA SLO goodput story
(docs/SERVING.md "Multi-replica router", ROADMAP item 5): a seeded
deterministic trace (Poisson arrivals, two tenants — one sharing a
system prefix — `serving/workload.py`) replays against a
`ServingRouter` over N warmed replicas twice: AT saturation (the fleet
keeps up; every request completes — asserted) and PAST saturation
(arrivals several times the fleet's capacity against a bounded router
queue + queue TTL; the router degrades gracefully — rejects/timeouts
shed load, the survivors complete, nothing is lost or duplicated —
asserted). `value` is the at-saturation goodput fraction; `detail`
carries both passes' full scores (goodput, TTFT/TPOT p50/p99,
finish-reason mix, per-tenant goodput) and the seeded workload hashes,
so a regression gate can compare like against like.

`BENCH_SERVING_PAGE_SIZES=16,32,64` appends a page-size sweep record
(`page_sweep`): the continuous workload re-run per page size so a TPU
window can pick a DMA-tuned default over the correctness-tuned 16
(ROADMAP item 1 follow-up); per-size tokens/s + TTFT ride
`detail.sweep`, `value` is the best size's tokens/s.

`--http` (or `http_record()` in-process) banks the separate
`gpt_345m_serving_http` record instead: the continuous workload served
through the deployable front door (replica RPC servers + router-over-
RPC + OpenAI-compatible SSE API, the `tools/serve.py` shape) with
byte parity vs the in-process engine asserted — the record's delta
against the in-process pass IS the HTTP/RPC serving tax.

Standalone:  python tools/bench_serving.py [--http]
In-process:  from tools.bench_serving import serving_records
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

# BENCH_SERVING_TINY=1 shrinks everything for CPU smoke tests of the
# harness itself (schema + scheduler liveness, not perf)
_TINY = os.environ.get("BENCH_SERVING_TINY") == "1"
VOCAB = 128 if _TINY else 50304
N_REQUESTS = 8 if _TINY else 32
SLOTS = 3 if _TINY else 8
PROMPT_RANGE = (3, 9) if _TINY else (32, 192)
GEN_RANGE = (3, 9) if _TINY else (16, 160)
# shared-prefix mode: the "system prompt" every request carries
PREFIX_LEN = 8 if _TINY else 128


def _model():
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    max_pos = PROMPT_RANGE[1] + GEN_RANGE[1]
    max_pos += -max_pos % 8
    cfg = GPTConfig(
        vocab_size=VOCAB,
        hidden_size=64 if _TINY else 1024,
        num_layers=2 if _TINY else 24,
        num_attention_heads=4 if _TINY else 16,
        ffn_hidden_size=128 if _TINY else 4096,
        max_position_embeddings=max_pos,
        hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0,
        fuse_attn_qkv=True,
        use_flash_attention=True,  # flash-decode on TPU; dense on CPU
        dtype=jnp.float32 if _TINY else jnp.bfloat16,
    )
    return GPTForPretraining(cfg)


def _workload(n: int):
    """Deterministic mixed-length request list: (prompt, max_new)."""
    rng = np.random.RandomState(0)
    out = []
    for _ in range(n):
        plen = rng.randint(PROMPT_RANGE[0], PROMPT_RANGE[1] + 1)
        gen = rng.randint(GEN_RANGE[0], GEN_RANGE[1] + 1)
        out.append((rng.randint(0, VOCAB, plen).astype(np.int32), int(gen)))
    return out


def _shared_prefix_workload(n: int):
    """Every request = the SAME system prompt + a short unique tail: the
    prefix-trie's target shape (a thousand chat users, one template)."""
    rng = np.random.RandomState(1)
    prefix = rng.randint(0, VOCAB, PREFIX_LEN).astype(np.int32)
    tail_max = max(PROMPT_RANGE[1] - PREFIX_LEN, 1)
    out = []
    for _ in range(n):
        tail = rng.randint(1, tail_max + 1)
        gen = rng.randint(GEN_RANGE[0], GEN_RANGE[1] + 1)
        prompt = np.concatenate(
            [prefix, rng.randint(0, VOCAB, tail).astype(np.int32)])
        out.append((prompt, int(gen)))
    return out


def _chunked_workload(n: int):
    """Long-prompt mixed load: alternating near-max prompts and short
    ones, so long arrivals keep landing while earlier requests decode —
    the TPOT-hostage shape chunked prefill exists for."""
    rng = np.random.RandomState(2)
    long_len = PROMPT_RANGE[1]
    short_len = max(PROMPT_RANGE[0], 3)
    out = []
    for i in range(n):
        plen = long_len if i % 2 == 0 else short_len
        gen = rng.randint(GEN_RANGE[0], GEN_RANGE[1] + 1)
        out.append((rng.randint(0, VOCAB, plen).astype(np.int32), int(gen)))
    return out


def _repetitive_workload(n: int):
    """Motif-tiled prompts decoding EOS-free to the max length: the
    repetitive/template shape where prompt-lookup (n-gram) drafting
    shines — the continuation keeps re-appearing verbatim in the
    request's own prompt + generated history."""
    rng = np.random.RandomState(6)
    motif_len = 4 if _TINY else 16
    out = []
    for _ in range(n):
        motif = rng.randint(0, VOCAB, motif_len).astype(np.int32)
        reps = -(-PROMPT_RANGE[1] // motif_len)
        prompt = np.tile(motif, reps)[:PROMPT_RANGE[1]].astype(np.int32)
        out.append((prompt, int(GEN_RANGE[1])))
    return out


def _run_continuous_tpot(engine, workload):
    """_run_continuous with per-token host timestamps: returns (tokens,
    detail) where detail carries decode TPOT percentiles — the
    inter-token gap every active stream observes, the number a long
    arriving prompt's prefill inflates."""
    from fleetx_tpu.serving.metrics import ServingMetrics

    engine.metrics = ServingMetrics(engine.slots)
    engine._publish_quant_metrics()
    stamps = {}

    def on_token(rid, tok, finished):
        stamps.setdefault(rid, []).append(time.perf_counter())

    t0 = time.perf_counter()
    rids = [engine.submit(p, max_length=g, on_token=on_token)
            for p, g in workload]
    res = engine.drain()
    elapsed = time.perf_counter() - t0
    gaps = []
    for ts in stamps.values():
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    arr = np.asarray(gaps, np.float64) * 1e3
    snap = engine.metrics.snapshot()
    detail = {
        "requests": len(workload),
        "slots": engine.slots,
        "useful_tokens": sum(g for _, g in workload),
        "elapsed_s": round(elapsed, 3),
        "queue_depth_mean": round(snap["queue_depth_mean"], 2),
        "slot_occupancy_mean": round(snap["slot_occupancy_mean"], 3),
        "ttft_ms_mean": round(snap["ttft_ms_mean"], 2),
        "ttft_ms_p50": round(snap["ttft_ms_p50"], 2),
        "ttft_ms_p95": round(snap["ttft_ms_p95"], 2),
        "tpot_ms_p50": round(float(np.percentile(arr, 50)), 2),
        "tpot_ms_p99": round(float(np.percentile(arr, 99)), 2),
        "tpot_ms_max": round(float(arr.max()), 2),
        "prefill_chunks": snap["prefill_chunks"],
        "prefill_stall_ms_p50": (
            None if snap["prefill_stall_ms_p50"] is None
            else round(snap["prefill_stall_ms_p50"], 2)),
        "prefill_stall_ms_p99": (
            None if snap["prefill_stall_ms_p99"] is None
            else round(snap["prefill_stall_ms_p99"], 2)),
        "prefill_stall_ms_max": (
            None if snap["prefill_stall_ms_max"] is None
            else round(snap["prefill_stall_ms_max"], 2)),
    }
    return [np.asarray(res[r].tokens) for r in rids], detail


def _spill_report(model, variables, gen_cfg, slots):
    """The host-tier sub-benchmark: an oversubscribed shared-prefix
    workload (hot prefix set exceeds the device page pool, every revisit
    finds its warm pages evicted) run with the spill tier OFF then ON —
    same submissions, byte parity asserted. OFF collapses the prefix hit
    rate; ON sustains it out of host DRAM."""
    from fleetx_tpu.serving import ServingEngine

    page_size = 8 if _TINY else 16
    cache_len = model.cfg.max_position_embeddings
    cache_len += -cache_len % page_size
    lane_pages = cache_len // page_size
    # the smallest legal pool — one full lane + the trash page — so the
    # hot prefix set cannot stay device-resident across revisits: the
    # device tier is oversubscribed by construction
    num_pages = lane_pages + 1
    n_prefixes = 3  # > what the pool can park warm, even at TINY sizes
    rounds = 2
    rng = np.random.RandomState(4)
    prefixes = [rng.randint(0, VOCAB, PREFIX_LEN).astype(np.int32)
                for _ in range(n_prefixes)]
    tail_max = max(PROMPT_RANGE[1] - PREFIX_LEN, 1)
    reqs = []
    for i in range(rounds * n_prefixes):
        tail = rng.randint(1, tail_max + 1)
        prompt = np.concatenate(
            [prefixes[i % n_prefixes],
             rng.randint(0, VOCAB, tail).astype(np.int32)])
        reqs.append((prompt, int(rng.randint(GEN_RANGE[0],
                                             GEN_RANGE[1] + 1))))

    def run(host_bytes):
        eng = ServingEngine(
            model, variables, slots=slots, cache_len=cache_len,
            gen_cfg=gen_cfg, paged=True, page_size=page_size,
            num_pages=num_pages, prefill_bucket=8 if _TINY else 32,
            host_cache_bytes=host_bytes)
        toks = []
        for prompt, gen in reqs:  # sequential: each revisit sees the
            rid = eng.submit(prompt, max_length=gen)  # pool at rest
            toks.append(np.asarray(eng.drain()[rid].tokens))
        eng.cache_manager.pool.check_invariants()
        return eng.metrics.snapshot(), toks

    off_snap, off_toks = run(0)
    on_snap, on_toks = run(1 << 30)
    assert all(np.array_equal(a, b) for a, b in zip(off_toks, on_toks)), (
        "host-tier revival broke byte parity vs cold prefill")
    assert on_snap["host_revived_pages"] > 0, (
        "spill workload never revived a page (pool not oversubscribed?)")
    return {
        "prefixes": n_prefixes,
        "rounds": rounds,
        "pages_total": num_pages - 1,
        "parity": True,
        "prefix_hit_rate_host_off": round(off_snap["prefix_hit_rate"], 3),
        "prefix_hit_rate_host_on": round(on_snap["prefix_hit_rate"], 3),
        "prefill_tokens_saved_host_off": off_snap["prefill_tokens_saved"],
        "prefill_tokens_saved_host_on": on_snap["prefill_tokens_saved"],
        "host_spilled_pages": on_snap["host_spilled_pages"],
        "host_revived_pages": on_snap["host_revived_pages"],
        "host_evicted_pages": on_snap["host_evicted_pages"],
        "host_cache_bytes": on_snap["host_cache_bytes"],
    }


def _router_slo_report(model, variables, gen_cfg, slots):
    """The multi-replica SLO goodput record (module docstring): one
    seeded two-tenant trace replayed against a ServingRouter over N
    warmed replicas AT saturation (everything completes — asserted) and
    PAST it (bounded queue + TTL shed gracefully, survivors complete —
    asserted). Wall-clock-free determinism lives in the trace hash; the
    scores are this host's latency truth."""
    import jax

    from fleetx_tpu.serving import (
        ServingEngine,
        ServingRouter,
        TenantSpec,
        WorkloadSpec,
        generate_trace,
        run_trace,
        score_goodput,
        trace_hash,
    )

    n_replicas = 2 if _TINY else 3
    n_requests = 8 if _TINY else 24
    prompt_rng = (3, 8) if _TINY else (32, 128)
    gen_rng = (3, 6) if _TINY else (16, 64)
    prefix = 4 if _TINY else PREFIX_LEN

    def tenants(ttft_s, tpot_ms):
        return (
            TenantSpec("chat", weight=2.0, prompt_len=prompt_rng,
                       gen_len=gen_rng, ttft_deadline_s=ttft_s,
                       tpot_deadline_ms=tpot_ms),
            TenantSpec("template", weight=1.0, prompt_len=prompt_rng,
                       gen_len=gen_rng, shared_prefix_len=prefix,
                       ttft_deadline_s=ttft_s, tpot_deadline_ms=tpot_ms),
        )

    at_rate = 50.0 if _TINY else 10.0
    at_spec = WorkloadSpec(
        seed=17, n_requests=n_requests, arrival_rate=at_rate,
        vocab=model.cfg.vocab_size, tenants=tenants(60.0, 5000.0),
        burst_every_s=0.5, burst_len_s=0.1, burst_factor=3.0)
    # past saturation: the whole burst arrives inside one scheduler
    # window (rate x200 => sub-ms inter-arrivals) against a router queue
    # bounded BELOW the burst, so shedding is structural, not a host-
    # speed coin flip — the record's claim is the degradation SHAPE
    past_spec = WorkloadSpec(
        seed=18, n_requests=n_requests, arrival_rate=at_rate * 200,
        vocab=model.cfg.vocab_size, tenants=tenants(60.0, 5000.0))
    at_trace, past_trace = generate_trace(at_spec), generate_trace(past_spec)

    replicas = [
        ServingEngine(model, variables, slots=slots,
                      cache_len=model.cfg.max_position_embeddings,
                      gen_cfg=gen_cfg, prefill_bucket=8 if _TINY else 32)
        for _ in range(n_replicas)
    ]
    # warmup pass: replay the at-trace once untimed so prefill-bucket /
    # decode compiles don't masquerade as TTFT in the scored passes
    run_trace(ServingRouter(replicas), at_trace)

    at_router = ServingRouter(replicas)
    at_score = score_goodput(run_trace(at_router, at_trace))
    assert at_score["requests"] == n_requests, at_score
    assert at_score["completed_frac"] == 1.0, (
        f"at-saturation pass lost requests: {at_score}")

    past_router = ServingRouter(
        replicas, max_queue=max(2, n_replicas),
        queue_ttl_s=1.0 if _TINY else 5.0)
    past_score = score_goodput(run_trace(past_router, past_trace))
    assert past_score["requests"] == n_requests, past_score
    assert past_score["shed_frac"] > 0, (
        f"past-saturation pass never shed (not saturated?): {past_score}")
    assert past_score["completed_frac"] > 0, (
        f"past-saturation pass collapsed (nothing completed): {past_score}")
    assert set(past_score["finish_reasons"]) <= {
        "eos", "max_length", "timeout", "rejected", "cache_full"}, (
        f"uncontrolled degradation past saturation: {past_score}")

    at_snap = at_router.metrics.snapshot()
    return {
        "requests": n_requests,
        "n_replicas": n_replicas,
        "replica_slots": slots,
        "workload_hash_at": trace_hash(at_trace),
        "workload_hash_past": trace_hash(past_trace),
        "at": at_score,
        "past": past_score,
        "at_arrival_rate": at_rate,
        "past_arrival_rate": past_spec.arrival_rate,
        "dispatched": at_snap["dispatched"],
        "affinity_hits": at_snap["affinity_hits"],
        "replica_deaths": at_snap["replica_deaths"],
        "device": getattr(jax.devices()[0], "device_kind", "?"),
    }


def _qos_autoscale_subpass(model, variables, gen_cfg, slots):
    """The closed-loop scale-up leg of the router_qos record: segment 1
    (a shared-template trace) warms ONE replica's prefix trie and pool
    pressure spills the template to the fleet's shared DiskPageStore;
    segment 2 floods the single replica, the FleetAutoscaler spawns a
    second engine on the same store and pre-warms it from
    ``router.hot_prefixes()`` BEFORE it takes traffic — asserted: the
    scale-up happened and the new replica prefix-HIT on its first trace
    segment (non-zero ``prefix_hits``), i.e. the pre-warm was real."""
    import shutil
    import tempfile

    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.serving import (
        FleetAutoscaler,
        ServingEngine,
        ServingRouter,
        TenantSpec,
        WorkloadSpec,
        generate_trace,
        run_trace,
    )

    page = 8 if _TINY else 16
    prefix_len = 2 * page if _TINY else 4 * page
    plo, phi = (prefix_len + 1, prefix_len + 2) if _TINY else (
        prefix_len + 1, prefix_len + 32)
    gen_rng = (3, 4) if _TINY else (8, 16)
    pages_a = 8 if _TINY else 16        # tight: filler traffic must evict
    pages_b = 24 if _TINY else 64
    d = tempfile.mkdtemp(prefix="fleetx-qos-scale-")
    try:
        def mk(num_pages):
            # small max_queue matters: an unbounded engine queue would
            # swallow every affinity-pinned dispatch on replica 0, so the
            # pre-warmed newcomer would never see template traffic —
            # QueueFull overflow is what routes work onto it
            return ServingEngine(
                model, variables, slots=slots,
                cache_len=model.cfg.max_position_embeddings,
                gen_cfg=gen_cfg, page_size=page, num_pages=num_pages,
                disk_cache_dir=d, disk_cache_bytes=1 << 22,
                max_queue=2, prefill_bucket=8 if _TINY else 32)

        def seg_spec(seed_unused, n, rate):
            # one seed for BOTH segments: generate_trace draws shared
            # prefixes first, so the template bytes are identical
            return WorkloadSpec(
                seed=31, n_requests=n, arrival_rate=rate,
                vocab=model.cfg.vocab_size,
                tenants=(TenantSpec("template", prompt_len=(plo, phi),
                                    gen_len=gen_rng,
                                    shared_prefix_len=prefix_len),))

        eng_a = mk(pages_a)
        router = ServingRouter([eng_a], probe_every=1)
        seg1 = generate_trace(seg_spec(0, 4 if _TINY else 8, 1000.0))
        run_trace(router, seg1)  # warms trie + router hot-prefix ledger
        # deterministic pool pressure: distinct prompts evict the parked
        # template pages, spilling them to the shared disk store
        vocab = model.cfg.vocab_size
        flen = phi
        for base in (3, 5):
            p = ((np.arange(flen, dtype=np.int64) * base + base)
                 % (vocab - 1) + 1).astype(np.int32)
            eng_a.submit(p, max_length=gen_rng[0])
        eng_a.drain(max_ticks=2000)

        spawned = []

        def spawn():
            e = mk(pages_b)
            spawned.append(e)
            return e

        scaler = FleetAutoscaler(
            router, spawn, min_replicas=1, max_replicas=2,
            high_queue_tokens=2.0, low_queue_tokens=0.5,
            eval_every=1, up_after=2, down_after=10 ** 6, prewarm=True)

        class _Scaled:
            # run_trace drives step(); the scaler rides every tick
            def submit(self, prompt, **kw):
                return router.submit(prompt, **kw)

            def step(self):
                router.step()
                scaler.step()

            def cancel(self, rid):
                return router.cancel(rid)

            def take_result(self, rid):
                return router.take_result(rid)

        seg2 = generate_trace(seg_spec(0, 12 if _TINY else 24, 1000.0))
        outcomes = run_trace(_Scaled(), seg2)
        assert scaler.scale_ups >= 1, "flooded replica never scaled up"
        assert spawned, "scale-up reported but nothing spawned"
        new_hits = int(spawned[0].metrics.prefix_hits)
        assert new_hits > 0, (
            "pre-warmed replica never prefix-hit on its first segment — "
            "the DiskPageStore pre-warm did not take")
        completed = sum(o.finish_reason in ("eos", "max_length")
                        for o in outcomes)
        assert completed == len(seg2), (
            f"scale-up segment lost requests: {completed}/{len(seg2)}")
        ups = get_event_log().find("autoscale_up")
        prewarmed = int(ups[-1].attrs.get("prewarmed_tokens", 0)) if ups \
            else 0
        return {
            "scale_ups": int(scaler.scale_ups),
            "prewarmed_tokens": prewarmed,
            "new_replica_prefix_hits": new_hits,
            "segment1_requests": len(seg1),
            "segment2_requests": len(seg2),
            "segment2_completed": completed,
            "shared_prefix_len": prefix_len,
            "page_size": page,
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _router_qos_report(model, variables, gen_cfg, slots):
    """The per-tenant QoS record (docs/SERVING.md "Per-tenant QoS &
    autoscaling"): ONE seeded heavy-tailed (azure_llm) trace at 2× the
    fleet's measured saturation throughput, two thirds of it a flooding
    tenant, replayed twice over the same warmed replicas — once with
    FIFO dispatch, once with DRR lanes + priority preemption. The gates:
    the well-behaved tenants' TTFT p99 under DRR is strictly below
    FIFO's on the SAME trace, their goodput at the derived SLO is
    strictly above, and their token streams are byte-identical to an
    UNCONTENDED replay (the flood never changed a byte — zero-loss
    preemption included). ``detail.autoscale`` banks the closed-loop
    scale-up + DiskPageStore pre-warm leg."""
    import jax

    from fleetx_tpu.serving import (
        ServingEngine,
        ServingRouter,
        TenantPolicy,
        TenantSpec,
        WorkloadSpec,
        generate_trace,
        run_trace,
        score_goodput,
        trace_hash,
    )

    n_replicas = 2
    n_well = 8 if _TINY else 24
    n_total = 3 * n_well
    prompt_rng = (3, 8) if _TINY else (32, 128)
    gen_rng = (3, 6) if _TINY else (16, 64)
    well = ("paid", "free")

    def tenant_specs(with_flood):
        out = [
            TenantSpec("paid", weight=1.0, prompt_len=prompt_rng,
                       gen_len=gen_rng),
            TenantSpec("free", weight=1.0, prompt_len=prompt_rng,
                       gen_len=gen_rng),
        ]
        if with_flood:
            out.append(TenantSpec("flood", weight=4.0,
                                  prompt_len=prompt_rng, gen_len=gen_rng))
        return tuple(out)

    # the tenant contracts: paid outranks (and may preempt), the flood
    # lane is bounded so its backlog sheds onto ITSELF (lane-scoped
    # QueueFull), never onto the well-behaved lanes
    policies = {
        "paid": TenantPolicy(weight=4.0, priority=1),
        "free": TenantPolicy(weight=2.0),
        "flood": TenantPolicy(weight=1.0, max_queue=max(4, slots)),
    }

    replicas = [
        ServingEngine(model, variables, slots=slots,
                      cache_len=model.cfg.max_position_embeddings,
                      gen_cfg=gen_cfg, prefill_bucket=8 if _TINY else 32)
        for _ in range(n_replicas)
    ]

    def mk_router(mode):
        return ServingRouter(replicas, tenants=policies, dispatch=mode,
                             preempt=(mode == "drr"), preempt_risk_frac=0.0)

    class _Target:
        """submit shim: paid requests carry a (generous) deadline —
        what arms the deadline-at-risk preemption path."""

        supports_tenants = True

        def __init__(self, r):
            self.r = r

        def submit(self, prompt, *, tenant=None, **kw):
            if tenant == "paid":
                kw["deadline_s"] = 120.0
            return self.r.submit(prompt, tenant=tenant, **kw)

        def step(self):
            self.r.step()

        def cancel(self, rid):
            return self.r.cancel(rid)

        def take_result(self, rid):
            return self.r.take_result(rid)

    # ---- calibrate saturation: near-simultaneous arrivals => elapsed is
    # pure service time and n/elapsed is the fleet's throughput ceiling
    calib_spec = WorkloadSpec(
        seed=23, n_requests=n_well, arrival_rate=1000.0,
        vocab=model.cfg.vocab_size, tenants=tenant_specs(False),
        distribution="azure_llm")
    calib = generate_trace(calib_spec)
    run_trace(_Target(mk_router("drr")), calib)  # compile warmup
    t0 = time.perf_counter()
    run_trace(_Target(mk_router("drr")), calib)
    capacity_rps = n_well / (time.perf_counter() - t0)

    # ---- the contended trace: heavy-tailed arrivals at 2× saturation,
    # flood weighted to ~2/3 of them — the misbehaving-tenant shape
    spec = WorkloadSpec(
        seed=29, n_requests=n_total, arrival_rate=2.0 * capacity_rps,
        vocab=model.cfg.vocab_size, tenants=tenant_specs(True),
        distribution="azure_llm")
    trace = generate_trace(spec)
    well_trace = [r for r in trace if r.tenant in well]
    assert len(well_trace) >= max(4, n_well // 2), (
        f"seeded mix starved the well-behaved tenants: {len(well_trace)}")

    # uncontended reference: the SAME well-behaved requests (same bytes,
    # same arrival offsets) with the flood deleted — the parity source
    unc = run_trace(_Target(mk_router("drr")), well_trace,
                    keep_tokens=True)

    fifo = run_trace(_Target(mk_router("fifo")), trace)
    drr_router = mk_router("drr")
    drr = run_trace(_Target(drr_router), trace, keep_tokens=True)

    def well_of(outcomes):
        return [o for o in outcomes if o.tenant in well]

    # byte parity: every well-behaved stream under DRR+flood+preemption
    # is identical to its uncontended run (and all of them completed)
    unc_by_idx = {o.index: o for o in unc}
    for o in well_of(drr):
        ref = unc_by_idx[o.index]
        assert o.finish_reason in ("eos", "max_length"), (
            f"DRR shed well-behaved request {o.index}: {o.finish_reason}")
        assert ref.finish_reason in ("eos", "max_length"), (
            f"uncontended run shed request {o.index}: {ref.finish_reason}")
        assert o.tokens == ref.tokens, (
            f"request {o.index} ({o.tenant}) diverged under contention")

    # latency isolation, the raw perf claim: DRR keeps the well-behaved
    # TTFT tail below FIFO's on the same trace
    def ttft_p99_ms(outcomes):
        return _pct_ms([o.ttft_s for o in outcomes], 99)

    def _pct_ms(vals, q):
        vals = [v * 1e3 for v in vals if v is not None]
        return float(np.percentile(np.asarray(vals, np.float64), q)) \
            if vals else None

    fifo_p99 = ttft_p99_ms(well_of(fifo))
    drr_p99 = ttft_p99_ms(well_of(drr))
    unc_p99 = ttft_p99_ms(well_of(unc))
    assert fifo_p99 is not None and drr_p99 is not None
    assert drr_p99 < fifo_p99, (
        f"DRR did not isolate the well-behaved tail: DRR p99 {drr_p99:.1f}"
        f"ms >= FIFO p99 {fifo_p99:.1f}ms")

    # goodput at a derived SLO between the two tails: the threshold a
    # well-behaved user could actually be sold given this fleet
    ttft_dl_s = float(np.sqrt(drr_p99 * fifo_p99)) / 1e3

    def rescore(outcomes):
        for o in outcomes:
            if o.tenant in well:
                o.ttft_deadline_s = ttft_dl_s
        return score_goodput(outcomes)

    def well_goodput(outcomes):
        ws = well_of(outcomes)
        return round(sum(o.good for o in ws) / len(ws), 4)

    fifo_score, drr_score = rescore(fifo), rescore(drr)
    unc_score = rescore(unc)
    gw_fifo, gw_drr = well_goodput(fifo), well_goodput(drr)
    assert gw_drr > gw_fifo, (
        f"DRR goodput not above FIFO at the derived SLO: "
        f"{gw_drr} <= {gw_fifo}")

    drr_snap = drr_router.metrics.snapshot()
    per_tenant = {}
    for t in ("paid", "free", "flood"):
        per_tenant[t] = {
            "fifo_ttft_ms_p99": _pct_ms(
                [o.ttft_s for o in fifo if o.tenant == t], 99),
            "drr_ttft_ms_p99": _pct_ms(
                [o.ttft_s for o in drr if o.tenant == t], 99),
            "drr_tpot_ms_p99": _pct_ms(
                [o.tpot_ms / 1e3 for o in drr
                 if o.tenant == t and o.tpot_ms is not None], 99),
        }

    return {
        "requests": n_total,
        "well_requests": len(well_trace),
        "n_replicas": n_replicas,
        "replica_slots": slots,
        "distribution": spec.distribution,
        "capacity_rps": round(capacity_rps, 2),
        "arrival_rate": round(spec.arrival_rate, 2),
        "saturation_x": 2.0,
        "workload_hash": trace_hash(trace),
        "ttft_deadline_ms": round(ttft_dl_s * 1e3, 1),
        "goodput_well_fifo": gw_fifo,
        "goodput_well_drr": gw_drr,
        "ttft_ms_p99_well_fifo": round(fifo_p99, 1),
        "ttft_ms_p99_well_drr": round(drr_p99, 1),
        "ttft_ms_p99_well_uncontended": (
            round(unc_p99, 1) if unc_p99 is not None else None),
        "preempted": drr_snap["preempted"],
        "parity_well_behaved": True,  # asserted above
        "per_tenant": per_tenant,
        "fifo": fifo_score,
        "drr": drr_score,
        "uncontended": unc_score,
        "autoscale": _qos_autoscale_subpass(model, variables, gen_cfg,
                                            slots),
        "device": getattr(jax.devices()[0], "device_kind", "?"),
    }


def _hetero_report(model, variables, gen_cfg, slots, workload, ref_toks):
    """The heterogeneous-fleet record (docs/SERVING.md "Heterogeneous
    fleet"): the continuous GPT workload plus an equal embedding
    workload through ONE model-aware router — a GPT replica and a
    KV-free ViT embedding replica in the same fleet. The gates: GPT
    stays byte-identical to its single-engine run (``ref_toks``) under
    mixed traffic (model-aware dispatch never crosses families),
    embeddings are deterministic (same image → same bits), and every
    request of both families gets exactly one terminal result. The
    detail carries per-model TTFT/throughput."""
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.vision.vit import ViT, ViTConfig
    from fleetx_tpu.serving import (
        EmbeddingEngine,
        ServingEngine,
        ServingRouter,
        decode_floats,
        encode_floats,
    )

    vcfg = ViTConfig(
        image_size=8 if _TINY else 32,
        patch_size=4 if _TINY else 8,
        in_channels=3, num_classes=0,
        hidden_size=32 if _TINY else 192,
        num_layers=2 if _TINY else 4,
        num_attention_heads=2 if _TINY else 3,
        drop_rate=0.0, attn_drop_rate=0.0,
        dtype=jnp.float32 if _TINY else jnp.bfloat16,
        use_flash_attention=False)
    vit = ViT(vcfg)
    shape = (vcfg.image_size, vcfg.image_size, vcfg.in_channels)
    vit_vars = jax.jit(vit.init)(jax.random.PRNGKey(1),
                                 np.zeros((1,) + shape, np.float32))
    rng = np.random.RandomState(7)
    images = [rng.rand(*shape).astype(np.float32)
              for _ in range(len(workload))]

    gpt_eng = ServingEngine(model, variables, slots=slots,
                            cache_len=model.cfg.max_position_embeddings,
                            gen_cfg=gen_cfg,
                            prefill_bucket=8 if _TINY else 32)
    emb_eng = EmbeddingEngine(vit, vit_vars, slots=slots)

    def run():
        router = ServingRouter([gpt_eng, emb_eng])
        t0 = time.perf_counter()
        rids = []  # (family, rid)
        for (prompt, gen), img in zip(workload, images):
            rids.append(("gpt", router.submit(
                prompt, max_length=gen, model="gpt")))
            rids.append(("vit", router.submit(
                encode_floats(img), model="vit")))
        res = router.drain()
        return rids, res, time.perf_counter() - t0

    run()  # compile warmup (both families)
    rids, res, elapsed = run()
    assert len(res) == len(rids), (
        f"exactly-one-result broke: {len(res)} results for "
        f"{len(rids)} requests")
    gpt_res = [res[r] for fam, r in rids if fam == "gpt"]
    vit_res = [res[r] for fam, r in rids if fam == "vit"]
    parity = all(np.array_equal(np.asarray(r.tokens), ref)
                 for r, ref in zip(gpt_res, ref_toks))
    assert parity, ("mixed embedding traffic changed GPT decode bytes — "
                    "model-aware dispatch leaked across families")
    assert all(r.finish_reason == "complete" for r in vit_res), (
        [r.finish_reason for r in vit_res])
    dim = decode_floats(vit_res[0].tokens).size
    # determinism gate: re-embedding the first image reproduces its bits
    rid2 = emb_eng.submit(encode_floats(images[0]))
    redo = emb_eng.drain()[rid2]
    assert np.array_equal(redo.tokens, vit_res[0].tokens), (
        "re-embedding the same image changed bits")

    def ttfts(results):
        ms = sorted(r.ttft_s * 1000 for r in results)
        return (round(ms[len(ms) // 2], 2),
                round(ms[min(int(len(ms) * 0.95), len(ms) - 1)], 2))

    g50, g95 = ttfts(gpt_res)
    v50, v95 = ttfts(vit_res)
    useful = sum(g for _, g in workload)
    emb_snap = emb_eng.metrics.snapshot()
    return {
        "requests": len(rids),
        "slots": slots,
        "useful_tokens": useful,
        "elapsed_s": round(elapsed, 3),
        "parity": parity,
        "per_model": {
            "gpt": {"requests": len(gpt_res),
                    "tokens_per_s": round(useful / elapsed, 1),
                    "ttft_ms_p50": g50, "ttft_ms_p95": g95},
            "vit": {"requests": len(vit_res),
                    "vectors_per_s": round(len(vit_res) / elapsed, 1),
                    "embedding_dim": int(dim),
                    "ttft_ms_p50": v50, "ttft_ms_p95": v95},
        },
        "embed_obs_snapshot": emb_snap,
        "device": getattr(jax.devices()[0], "device_kind", "?"),
    }


def _disagg_report(model, variables, gen_cfg, slots):
    """Phase-disaggregated serving record (docs/SERVING.md
    "Disaggregated prefill/decode"): the mixed workload behind a
    phase-aware router over 1 prefill + 1 decode replica vs the SAME
    workload over 2 colocated replicas — byte parity asserted, TTFT/
    TPOT p99 both ways (disaggregation is an isolation story: arriving
    prefills stop stealing decode ticks), the pages/bytes actually
    shipped over the wire, and a ``disk_tier`` sub-pass where a second
    FRESH replica sharing one content-addressed DiskPageStore sustains
    the prefix hit rate across the replica boundary."""
    import tempfile

    import jax

    from fleetx_tpu.serving import ServingEngine, ServingRouter
    from fleetx_tpu.serving.workload import (
        disagg_spec,
        generate_trace,
        trace_hash,
    )

    n_requests = 8 if _TINY else 16
    # the mixed long-prompt/short-decode trace from serving/workload.py
    # (the disaggregation-favoring shape), skewed within the bench's
    # global ranges so prompt+decode still fits max_position_embeddings
    trace = generate_trace(disagg_spec(
        n_requests, vocab=VOCAB,
        prompt_len=((PROMPT_RANGE[0] + PROMPT_RANGE[1]) // 2,
                    PROMPT_RANGE[1]),
        gen_len=(GEN_RANGE[0], max(GEN_RANGE[0], GEN_RANGE[1] // 2))))
    workload = [(t.prompt, t.max_new_tokens) for t in trace]
    page_size = 8 if _TINY else 16
    cache_len = model.cfg.max_position_embeddings
    cache_len += -cache_len % page_size

    def make(role=None, **kw):
        return ServingEngine(model, variables, slots=slots,
                             cache_len=cache_len, gen_cfg=gen_cfg,
                             paged=True, page_size=page_size,
                             prefill_bucket=8 if _TINY else 32,
                             prefill_chunk=page_size, role=role, **kw)

    def run(replicas):
        # untimed warmup over the same replicas (router_slo idiom), then
        # the timed pass on a fresh router — compiles never bill as TTFT
        warm = ServingRouter(replicas)
        for p, g in workload:
            warm.submit(p, max_length=g)
        warm.drain(max_ticks=50_000)
        router = ServingRouter(replicas)
        stamps, subs = {}, {}

        def on_token(rid, tok, fin):
            stamps.setdefault(rid, []).append(time.perf_counter())

        t0 = time.perf_counter()
        rids = []
        for p, g in workload:
            r = router.submit(p, max_length=g, on_token=on_token)
            subs[r] = time.perf_counter()
            rids.append(r)
        res = router.drain(max_ticks=50_000)
        elapsed = time.perf_counter() - t0
        assert len(res) == len(rids), "disagg bench lost requests"
        gaps, ttfts = [], []
        for r in rids:
            ts = stamps[r]
            ttfts.append(ts[0] - subs[r])
            gaps += [b - a for a, b in zip(ts, ts[1:])]
        garr = np.asarray(gaps, np.float64) * 1e3
        tarr = np.asarray(ttfts, np.float64) * 1e3
        stats = {
            "elapsed_s": round(elapsed, 3),
            "ttft_ms_p50": round(float(np.percentile(tarr, 50)), 2),
            "ttft_ms_p99": round(float(np.percentile(tarr, 99)), 2),
            "tpot_ms_p50": round(float(np.percentile(garr, 50)), 2),
            "tpot_ms_p99": round(float(np.percentile(garr, 99)), 2),
        }
        return [np.asarray(res[r].tokens) for r in rids], stats

    colo_toks, colo_stats = run([make(), make()])
    pre, dec = make(role="prefill"), make(role="decode")
    dis_toks, dis_stats = run([pre, dec])
    assert all(np.array_equal(a, b) for a, b in zip(colo_toks, dis_toks)), (
        "disaggregated serving broke greedy byte parity vs colocated")
    # lifetime wire counters over warmup + timed pass: the warm pass
    # ships every prompt's pages but the decode trie already owns most
    # of them (the shipped-admission only revives BEYOND the shared
    # prefix), so revived <= shipped is the steady-state shape
    pages_shipped = pre.metrics.kv_pages_shipped
    bytes_shipped = pre.metrics.kv_bytes_shipped
    assert pages_shipped > 0, "disagg pass never shipped a page"
    assert 0 < dec.metrics.kv_pages_revived_remote <= pages_shipped, (
        "shipped pages were not revived on the decode replica")

    # disk-tier sub-pass: the _spill_report oversubscription shape (hot
    # prefix set > device pool) but the store is a SHARED disk dir and
    # the second run is a FRESH replica — its pool, trie, and host DRAM
    # all start cold, so every revive it gets crossed the replica
    # boundary through the content-addressed files
    lane_pages = cache_len // page_size
    num_pages = lane_pages + 1
    n_prefixes, rounds = 3, 2
    rng = np.random.RandomState(5)
    prefixes = [rng.randint(0, VOCAB, PREFIX_LEN).astype(np.int32)
                for _ in range(n_prefixes)]
    tail_max = max(PROMPT_RANGE[1] - PREFIX_LEN, 1)
    reqs = []
    for i in range(rounds * n_prefixes):
        prompt = np.concatenate(
            [prefixes[i % n_prefixes],
             rng.randint(0, VOCAB, rng.randint(1, tail_max + 1))
             .astype(np.int32)])
        reqs.append((prompt, int(rng.randint(GEN_RANGE[0],
                                             GEN_RANGE[1] + 1))))

    def run_disk(disk_dir):
        eng = ServingEngine(
            model, variables, slots=slots, cache_len=cache_len,
            gen_cfg=gen_cfg, paged=True, page_size=page_size,
            num_pages=num_pages, prefill_bucket=8 if _TINY else 32,
            host_cache_bytes=0, disk_cache_dir=disk_dir,
            disk_cache_bytes=1 << 30 if disk_dir else 0)
        toks = []
        for prompt, gen in reqs:  # sequential: pool at rest per visit
            rid = eng.submit(prompt, max_length=gen)
            toks.append(np.asarray(eng.drain()[rid].tokens))
        eng.cache_manager.pool.check_invariants()
        return eng.metrics.snapshot(), toks

    off_snap, off_toks = run_disk("")
    with tempfile.TemporaryDirectory() as d:
        a_snap, a_toks = run_disk(d)   # cold store: fills the disk tier
        b_snap, b_toks = run_disk(d)   # fresh replica, same dir
    assert all(np.array_equal(x, y) for x, y in zip(off_toks, a_toks)), (
        "disk-tier revival broke byte parity vs cold prefill")
    assert all(np.array_equal(x, y) for x, y in zip(off_toks, b_toks)), (
        "cross-replica disk revival broke byte parity")
    # the cross-replica claim: replica B starts with a COLD pool, trie
    # and host DRAM, so every disk hit it serves revived a page some
    # other replica prefilled — and its prefix hit rate holds where the
    # store-less run collapses
    assert b_snap["disk_cache_hits"] > 0, (
        "second replica never revived a page from the shared disk tier")
    assert (b_snap["prefix_hit_rate"] > off_snap["prefix_hit_rate"]), (
        "shared disk tier failed to sustain the prefix hit rate "
        f"cross-replica: {b_snap['prefix_hit_rate']} vs disk-off "
        f"{off_snap['prefix_hit_rate']}")
    disk_tier = {
        "prefixes": n_prefixes,
        "rounds": rounds,
        "parity": True,
        "prefix_hit_rate_disk_off": round(off_snap["prefix_hit_rate"], 3),
        "prefix_hit_rate_first_replica": round(a_snap["prefix_hit_rate"], 3),
        "prefix_hit_rate_fresh_replica": round(b_snap["prefix_hit_rate"], 3),
        "prefill_tokens_saved_fresh_replica": b_snap["prefill_tokens_saved"],
        "fresh_replica_disk_hits": b_snap["disk_cache_hits"],
        "fresh_replica_disk_misses": b_snap["disk_cache_misses"],
        "disk_cache_bytes": a_snap["disk_cache_bytes"],
    }
    useful = sum(g for _, g in workload)
    return {
        "requests": n_requests,
        "workload_hash": trace_hash(trace),
        "n_prefill": 1,
        "n_decode": 1,
        "replica_slots": slots,
        "parity": True,
        "useful_tokens": useful,
        "elapsed_s": dis_stats["elapsed_s"],
        "colocated": colo_stats,
        "disagg": dis_stats,
        "kv_pages_shipped": pages_shipped,
        "kv_bytes_shipped": bytes_shipped,
        "kv_pages_revived_remote": dec.metrics.kv_pages_revived_remote,
        "disk_tier": disk_tier,
        "device": getattr(jax.devices()[0], "device_kind", "?"),
    }


def _decode_bytes_per_token(engine):
    """XLA cost-model bytes one jitted decode tick accesses, per decode
    lane (= per token at full occupancy) — the HBM-bandwidth claim the
    int8 record makes, measured on the COMPILED step, not estimated.
    None when the backend's cost analysis has no byte accounting."""
    try:
        cost = engine.compiled_decode().cost_analysis()
        if not cost or cost.get("bytes accessed") is None:
            return None
        return round(float(cost["bytes accessed"]) / engine.slots, 1)
    except Exception:  # cost model is best-effort, never fails the bench
        return None


def _ttft_stats(ttfts_s):
    arr = np.asarray(ttfts_s, np.float64) * 1e3
    return {
        "ttft_ms_mean": round(float(arr.mean()), 2),
        "ttft_ms_p50": round(float(np.percentile(arr, 50)), 2),
        "ttft_ms_p95": round(float(np.percentile(arr, 95)), 2),
    }


def _run_static(model, variables, workload, slots, jit_cache):
    """Padded batches of ``slots`` in arrival order, each one blocking
    generate() call; returns (per-request tokens, detail). ``jit_cache``
    persists the per-batch-shape compiled calls across warmup/timed
    passes (one-shot serving pays one compile per (batch, prompt, gen)
    shape — that cost is the warmup's, not the steady state's)."""
    import functools

    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.generation import GenerationConfig, generate

    results = [None] * len(workload)
    ttfts = [0.0] * len(workload)
    generated_total = 0
    depth_samples = []
    t0 = time.perf_counter()
    for start in range(0, len(workload), slots):
        batch = workload[start:start + slots]
        pmax = max(len(p) for p, _ in batch)
        gmax = max(g for _, g in batch)
        ids = np.zeros((len(batch), pmax), np.int32)
        mask = np.zeros((len(batch), pmax), np.int32)
        for i, (p, _) in enumerate(batch):
            ids[i, pmax - len(p):] = p  # left-pad to the batch max
            mask[i, pmax - len(p):] = 1
        key = (len(batch), pmax, gmax)
        if key not in jit_cache:
            cfg = GenerationConfig(max_length=gmax, min_length=gmax,
                                   decode_strategy="greedy", eos_token_id=-1,
                                   pad_token_id=0)
            jit_cache[key] = jax.jit(functools.partial(
                generate, model, gen_cfg=cfg))
        out = np.asarray(jax.device_get(jit_cache[key](
            variables, input_ids=jnp.asarray(ids),
            attention_mask=jnp.asarray(mask))))
        done_t = time.perf_counter()
        generated_total += len(batch) * gmax
        # tokens surface only when the whole batch returns
        for i, (p, g) in enumerate(batch):
            results[start + i] = out[i, pmax:pmax + g]
            ttfts[start + i] = done_t - t0
        depth_samples.append(len(workload) - (start + len(batch)))
    elapsed = time.perf_counter() - t0
    useful = sum(g for _, g in workload)
    detail = {
        "requests": len(workload),
        "slots": slots,
        "useful_tokens": useful,
        "generated_tokens": generated_total,
        "dead_token_frac": round(1.0 - useful / generated_total, 3),
        "elapsed_s": round(elapsed, 3),
        "queue_depth_mean": round(float(np.mean(depth_samples)), 2),
        "queue_depth_peak": int(max(depth_samples) + slots),
        "slot_occupancy_mean": round(useful / generated_total, 3),
        **_ttft_stats(ttfts),
    }
    return results, elapsed, detail


def _run_continuous(engine, workload):
    """All requests submitted up front; drain; engine metrics carry the
    queue/occupancy/TTFT story."""
    from fleetx_tpu.serving.metrics import ServingMetrics

    engine.metrics = ServingMetrics(engine.slots)  # fresh gauges per run
    engine._publish_quant_metrics()  # fresh gauges need the precision info
    t0 = time.perf_counter()
    rids = [engine.submit(p, max_length=g) for p, g in workload]
    res = engine.drain()
    elapsed = time.perf_counter() - t0
    snap = engine.metrics.snapshot()
    results = [np.asarray(res[r].tokens) for r in rids]
    useful = sum(g for _, g in workload)
    detail = {
        "requests": len(workload),
        "slots": engine.slots,
        "useful_tokens": useful,
        "generated_tokens": snap["tokens_generated"],
        "dead_token_frac": 0.0,  # every decoded row belongs to a live request
        "elapsed_s": round(elapsed, 3),
        "ticks": snap["ticks"],
        "queue_depth_mean": round(snap["queue_depth_mean"], 2),
        "queue_depth_peak": snap["queue_depth_peak"],
        "slot_occupancy_mean": round(snap["slot_occupancy_mean"], 3),
        "ttft_ms_mean": round(snap["ttft_ms_mean"], 2),
        "ttft_ms_p50": round(snap["ttft_ms_p50"], 2),
        "ttft_ms_p95": round(snap["ttft_ms_p95"], 2),
    }
    # full metric context rides the record (docs/OBSERVABILITY.md): the
    # summary fields above are the headline, obs_snapshot is everything
    # the engine's registry instruments saw this pass
    detail["obs_snapshot"] = snap
    if getattr(engine, "paged", False):
        detail.update({
            "prefix_hit_rate": round(snap["prefix_hit_rate"], 3),
            "prefill_tokens_saved": snap["prefill_tokens_saved"],
            "prefill_tokens_saved_frac": round(
                snap["prefill_tokens_saved_frac"], 3),
            "page_occupancy_mean": round(snap["page_occupancy_mean"], 3),
            "page_occupancy_peak": round(snap["page_occupancy_peak"], 3),
            "pages_per_request_mean": (
                None if snap["pages_per_request_mean"] is None
                else round(snap["pages_per_request_mean"], 2)),
            "pages_total": snap["pages_total"],
        })
    return results, elapsed, detail


def serving_records(n_requests: int = N_REQUESTS, slots: int = SLOTS):
    """One JSON-able record per serving mode (static, continuous,
    shared_prefix), plus byte-parity assertions between them. Each mode
    gets an untimed warmup pass so compile time doesn't masquerade as
    scheduling cost; the shared-prefix warmup doubles as the trie-cold
    pass, so its timed pass reports the warm steady state a production
    template workload sees."""
    import jax

    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.serving import ServingEngine

    model = _model()
    workload = _workload(n_requests)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        np.zeros((1, PROMPT_RANGE[1]), np.int32),
    )
    gen_cfg = GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                               pad_token_id=0,
                               max_length=GEN_RANGE[1])
    engine = ServingEngine(model, variables, slots=slots,
                           cache_len=model.cfg.max_position_embeddings,
                           gen_cfg=gen_cfg,
                           prefill_bucket=8 if _TINY else 32)

    static_jits = {}
    _run_static(model, variables, workload, slots, static_jits)  # warmup
    static_toks, _, static_detail = _run_static(model, variables, workload,
                                                slots, static_jits)
    _run_continuous(engine, workload)  # compile warmup
    cont_toks, _, cont_detail = _run_continuous(engine, workload)

    parity = all(
        np.array_equal(a, b) for a, b in zip(static_toks, cont_toks)
    )
    cont_detail["parity"] = parity

    # faulted mode: same workload, one injected decode-tick failure ->
    # transactional rollback + replay recovery mid-run; the delta vs the
    # clean continuous record IS the price of a recovery
    from fleetx_tpu.resilience.faults import faults

    faulted_engine = ServingEngine(model, variables, slots=slots,
                                   cache_len=model.cfg.max_position_embeddings,
                                   gen_cfg=gen_cfg,
                                   prefill_bucket=8 if _TINY else 32)
    _run_continuous(faulted_engine, workload)  # compile warmup
    # fail a tick mid-run: the workload takes >= useful/slots decode ticks,
    # so 1/4 of that is comfortably inside the timed pass
    fault_tick = faulted_engine._fault_ticks + max(
        sum(g for _, g in workload) // slots // 4, 1)
    faults.configure(tick_raise=str(fault_tick))
    try:
        fault_toks, _, fault_detail = _run_continuous(faulted_engine, workload)
    finally:
        faults.reset()
    snap = faulted_engine.metrics.snapshot()
    assert snap["engine_recoveries"] == 1, (
        f"faulted bench expected exactly 1 recovery, got "
        f"{snap['engine_recoveries']}")
    # the recovery must not cost a single byte of output
    fault_detail["parity"] = all(
        np.array_equal(a, b) for a, b in zip(cont_toks, fault_toks))
    fault_detail["engine_recoveries"] = snap["engine_recoveries"]
    fault_detail["poison_retired"] = snap["poison_retired"]
    fault_detail["tick_ms_p50"] = (None if snap["tick_ms_p50"] is None
                                   else round(snap["tick_ms_p50"], 2))
    fault_detail["tick_ms_p99"] = (None if snap["tick_ms_p99"] is None
                                   else round(snap["tick_ms_p99"], 2))
    clean_tps = cont_detail["useful_tokens"] / cont_detail["elapsed_s"]
    fault_tps = fault_detail["useful_tokens"] / fault_detail["elapsed_s"]
    fault_detail["recovery_overhead_frac"] = round(
        max(1.0 - fault_tps / clean_tps, 0.0), 3)

    # int8 mode: the full quantized serving path (int8 KV + int8 weights)
    # on the same workload; the comparison vs the bf16 continuous record
    # is the precision lever's price/win sheet (docs/QUANTIZATION.md)
    int8_engine = ServingEngine(model, variables, slots=slots,
                                cache_len=model.cfg.max_position_embeddings,
                                gen_cfg=gen_cfg,
                                prefill_bucket=8 if _TINY else 32,
                                kv_dtype="int8", weight_dtype="int8")
    _run_continuous(int8_engine, workload)  # compile warmup
    int8_toks, _, int8_detail = _run_continuous(int8_engine, workload)
    # tolerance parity, not byte parity: every stream must share its
    # leading tokens with the bf16 run up to the documented budget —
    # ops/quant owns BOTH the number and the measure (length mismatch =
    # outright fail), so this gate cannot drift from the test harness's;
    # byte-identity is the bf16 records' gate
    from fleetx_tpu.ops.quant import QUANT_PREFIX_BUDGET, quant_parity_frac

    need = 1.0 - QUANT_PREFIX_BUDGET
    fracs = [quant_parity_frac(a, b) for a, b in zip(int8_toks, cont_toks)]
    int8_detail["parity_prefix_frac_min"] = round(min(fracs), 3)
    int8_detail["parity"] = min(fracs) >= need
    assert int8_detail["parity"], (
        f"int8 serving diverged from bf16 beyond the tolerance contract: "
        f"min leading-token agreement {min(fracs):.3f} < {need}")
    snap = int8_engine.metrics.snapshot()
    bf16_snap = cont_detail["obs_snapshot"]
    int8_detail.update({
        "kv_dtype": snap["kv_dtype"],
        "weight_dtype": snap["weight_dtype"],
        "kv_bytes_per_token": snap["kv_bytes_per_token"],
        "kv_bytes_per_token_bf16": bf16_snap["kv_bytes_per_token"],
        "kv_cache_bytes": snap["kv_cache_bytes"],
        "kv_cache_bytes_bf16": bf16_snap["kv_cache_bytes"],
        "weight_bytes": snap["weight_bytes"],
        "weight_bytes_bf16": bf16_snap["weight_bytes"],
        # XLA cost-model bytes per decode lane per tick, both precisions:
        # the bandwidth-bound-path claim, from the compiled step itself
        "decode_bytes_per_token_int8": _decode_bytes_per_token(int8_engine),
        "decode_bytes_per_token_bf16": _decode_bytes_per_token(engine),
    })
    int8_tps = int8_detail["useful_tokens"] / int8_detail["elapsed_s"]
    int8_detail["speedup_vs_bf16"] = round(int8_tps / clean_tps, 3)

    # chunked mode: long-prompt mixed workload with vs without chunked
    # prefill — the TPOT p50/p99 delta is the decode-stall story, byte
    # parity proves chunking only reschedules WHEN prompts ingest
    ck_workload = _chunked_workload(n_requests)
    chunk = 4 if _TINY else max(PROMPT_RANGE[1] // 4, 32)

    def chunked_engine(prefill_chunk):
        return ServingEngine(model, variables, slots=slots,
                             cache_len=model.cfg.max_position_embeddings,
                             gen_cfg=gen_cfg,
                             prefill_bucket=8 if _TINY else 32,
                             prefill_chunk=prefill_chunk)

    base_eng = chunked_engine(0)
    if not _TINY:  # TINY only schema-checks: compile time in the TPOT
        _run_continuous(base_eng, ck_workload)  # numbers is acceptable
    base_toks, base_detail = _run_continuous_tpot(base_eng, ck_workload)
    ck_eng = chunked_engine(chunk)
    if not _TINY:
        _run_continuous(ck_eng, ck_workload)  # compile warmup
    ck_toks, ck_detail = _run_continuous_tpot(ck_eng, ck_workload)
    # chunking must not move a single byte of any stream
    ck_detail["parity"] = all(
        np.array_equal(a, b) for a, b in zip(base_toks, ck_toks))
    assert ck_detail["parity"], "chunked prefill broke greedy byte parity"
    assert ck_detail["prefill_chunks"] > 0, (
        "chunked bench never ran a chunk (prompts shorter than the chunk?)")
    ck_detail["prefill_chunk"] = chunk
    ck_detail["unchunked"] = {
        k: base_detail[k]
        for k in ("tpot_ms_p50", "tpot_ms_p99", "tpot_ms_max",
                  "ttft_ms_p50", "ttft_ms_p95", "prefill_stall_ms_p99",
                  "prefill_stall_ms_max", "elapsed_s")}
    # the headline claim: with chunking, the WORST decode stall a tick
    # can suffer is ~one chunk-sized prefill, not a whole-prompt one
    # (ratio < 1 on any host once prompts outgrow the chunk; noise can
    # blur it at TINY sizes, so the record reports rather than asserts)
    ck_detail["tpot_p99_ratio_vs_unchunked"] = round(
        ck_detail["tpot_ms_p99"] / max(base_detail["tpot_ms_p99"], 1e-9), 3)
    ck_detail["spill"] = _spill_report(model, variables, gen_cfg, slots)
    ck_detail["dead_token_frac"] = 0.0
    ck_detail["generated_tokens"] = ck_detail["useful_tokens"]

    # speculative mode: draft-k-verify-once ticks (docs/SERVING.md) on a
    # repetitive workload the n-gram proposer can actually draft for —
    # byte parity vs the non-speculative engine asserted at every k, the
    # tokens-per-tick multiplier and acceptance rate are the story, and
    # TTFT rides along to show admission latency is untouched (drafting
    # only changes the decode tick)
    rep_workload = _repetitive_workload(n_requests)

    def _spec_engine(spec, k):
        return ServingEngine(model, variables, slots=slots,
                             cache_len=model.cfg.max_position_embeddings,
                             gen_cfg=gen_cfg,
                             prefill_bucket=8 if _TINY else 32,
                             spec=spec, spec_k=k)

    spec_base_eng = _spec_engine(False, 4)
    if not _TINY:  # TINY only schema-checks; compile time in the
        _run_continuous(spec_base_eng, rep_workload)  # speedup is OK there
    sb_toks, _, sb_detail = _run_continuous(spec_base_eng, rep_workload)
    sb_tps = sb_detail["useful_tokens"] / sb_detail["elapsed_s"]
    k_sweep = []
    spec_detail = None
    for kk in (2, 4, 8):
        eng = _spec_engine(True, kk)
        if not _TINY:
            _run_continuous(eng, rep_workload)  # compile warmup
        toks, _, d = _run_continuous(eng, rep_workload)
        assert all(np.array_equal(a, b) for a, b in zip(sb_toks, toks)), (
            f"speculative decoding (k={kk}) broke greedy byte parity")
        snap = d["obs_snapshot"]
        tps = d["useful_tokens"] / d["elapsed_s"]
        k_sweep.append({
            "k": kk,
            "tokens_per_s": round(tps, 1),
            "speedup_vs_baseline": round(tps / sb_tps, 3),
            "acceptance_rate": round(snap["spec_acceptance_rate"], 3),
            "tokens_per_tick_mean": (
                None if snap["spec_tokens_per_tick_mean"] is None
                else round(snap["spec_tokens_per_tick_mean"], 2)),
            "ttft_ms_p50": d["ttft_ms_p50"],
        })
        if kk == 4:  # the record's headline run: the default k
            spec_detail = d
            spec_detail.update({
                "parity": True,
                "spec_k": kk,
                "proposer": "ngram",
                "speedup_vs_baseline": round(tps / sb_tps, 3),
                "acceptance_rate": round(snap["spec_acceptance_rate"], 3),
                "spec_proposed_tokens": snap["spec_proposed_tokens"],
                "spec_accepted_tokens": snap["spec_accepted_tokens"],
                "tokens_per_tick_mean": round(
                    snap["spec_tokens_per_tick_mean"], 2),
                "ttft_ms_p50_baseline": sb_detail["ttft_ms_p50"],
                "elapsed_s_baseline": sb_detail["elapsed_s"],
            })
    assert spec_detail["tokens_per_tick_mean"] > 1, (
        "speculative ticks averaged <= 1 token per request per tick — "
        f"the draft path gained nothing ({spec_detail})")
    spec_detail["k_sweep"] = k_sweep

    # mesh mode (docs/SERVING.md "Mesh-sharded serving"): the continuous
    # workload on a TP(mp2) mesh — byte parity vs single-device asserted,
    # per-device KV bytes ~halve; skipped below 2 devices (the record is
    # the point where a model outgrowing one chip keeps serving)
    mesh_detail = None
    n_heads = model.cfg.num_attention_heads
    if jax.device_count() >= 2 and n_heads % 2 == 0:
        from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(mp=2), jax.devices()[:2])
        mesh_engine = ServingEngine(model, variables, slots=slots,
                                    cache_len=model.cfg.max_position_embeddings,
                                    gen_cfg=gen_cfg,
                                    prefill_bucket=8 if _TINY else 32,
                                    mesh=mesh)
        if not _TINY:
            _run_continuous(mesh_engine, workload)  # compile warmup
        mesh_toks, _, mesh_detail = _run_continuous(mesh_engine, workload)
        # sharding is a layout, never math: not one byte may move
        mesh_detail["parity"] = all(
            np.array_equal(a, b) for a, b in zip(cont_toks, mesh_toks))
        assert mesh_detail["parity"], (
            "mesh-sharded serving broke greedy byte parity vs the "
            "single-device engine")
        snap = mesh_engine.metrics.snapshot()
        single_snap = cont_detail["obs_snapshot"]
        mesh_detail.update({
            "mesh": {a: int(s) for a, s in mesh.shape.items() if s > 1}
                    or {"mp": 1},
            "mesh_devices": snap["mesh_devices"],
            # PER-DEVICE cache bytes: the capacity math that lets a
            # model too big (or too slow) for one chip serve from a mesh
            "kv_cache_bytes_per_device": snap["kv_cache_bytes"],
            "kv_cache_bytes_single_device": single_snap["kv_cache_bytes"],
            "weight_bytes_per_device": snap["weight_bytes"],
            "weight_bytes_single_device": single_snap["weight_bytes"],
        })
        mesh_tps = mesh_detail["useful_tokens"] / mesh_detail["elapsed_s"]
        mesh_detail["speedup_vs_single_device"] = round(
            mesh_tps / clean_tps, 3)

    # shared-prefix mode: paged engine, trie-cold warmup then warm timing
    sp_workload = _shared_prefix_workload(n_requests)
    sp_engine = ServingEngine(model, variables, slots=slots,
                              cache_len=model.cfg.max_position_embeddings,
                              gen_cfg=gen_cfg, paged=True,
                              # tiny prompts need tiny pages or the 8-token
                              # system prompt never fills a shareable page
                              page_size=8 if _TINY else None,
                              prefill_bucket=8 if _TINY else 32)
    cold_toks, _, _ = _run_continuous(sp_engine, sp_workload)
    sp_toks, _, sp_detail = _run_continuous(sp_engine, sp_workload)
    # trie reuse must not change a single byte of any request's tokens
    sp_detail["parity"] = all(
        np.array_equal(a, b) for a, b in zip(cold_toks, sp_toks)
    )
    sp_detail["prefix_len"] = PREFIX_LEN

    device = getattr(jax.devices()[0], "device_kind", "?")
    modes = [("static", static_detail),
             ("continuous", cont_detail),
             ("shared_prefix", sp_detail),
             ("faulted", fault_detail),
             ("int8", int8_detail),
             ("chunked", ck_detail),
             ("spec", spec_detail)]
    if mesh_detail is not None:
        modes.append(("mesh", mesh_detail))

    # page-size sweep (ROADMAP item 1 follow-up): opt-in via
    # BENCH_SERVING_PAGE_SIZES so a TPU window can pick a DMA-tuned
    # default; each size re-runs the continuous workload byte-identically
    sweep_env = os.environ.get("BENCH_SERVING_PAGE_SIZES", "")
    if sweep_env.strip():
        sweep, per_size_detail = [], {}
        for ps in (int(s) for s in sweep_env.split(",") if s.strip()):
            eng = ServingEngine(model, variables, slots=slots,
                                cache_len=model.cfg.max_position_embeddings,
                                gen_cfg=gen_cfg, paged=True, page_size=ps,
                                prefill_bucket=8 if _TINY else 32)
            _run_continuous(eng, workload)  # compile warmup
            toks, _, d = _run_continuous(eng, workload)
            assert all(np.array_equal(a, b)
                       for a, b in zip(toks, cont_toks)), (
                f"page_size={ps} broke greedy byte parity")
            per_size_detail[ps] = d
            sweep.append({
                "page_size": ps,
                "tokens_per_s": round(d["useful_tokens"] / d["elapsed_s"], 1),
                "ttft_ms_p50": d["ttft_ms_p50"],
                "ttft_ms_p95": d["ttft_ms_p95"],
                "page_occupancy_mean": d.get("page_occupancy_mean"),
            })
        best = max(sweep, key=lambda r: r["tokens_per_s"])
        # the record's standard fields come from the winning size's timed
        # pass; the full per-size table rides detail.sweep
        sweep_detail = per_size_detail[best["page_size"]]
        sweep_detail["sweep"] = sweep
        sweep_detail["best_page_size"] = best["page_size"]
        sweep_detail["parity"] = True  # asserted per size above
        modes.append(("page_sweep", sweep_detail))

    records = []
    for mode, detail in modes:
        detail["device"] = device
        records.append({
            "metric": f"gpt_345m_serving_{mode}",
            "value": round(detail["useful_tokens"] / detail["elapsed_s"], 1),
            "unit": "tokens/s",
            "vs_baseline": None,  # reference serves static batches only
            "detail": detail,
        })

    # multi-replica SLO goodput record (docs/SERVING.md "Multi-replica
    # router"): its headline is a FRACTION, not tokens/s — the router's
    # regression gate is "the fleet still meets its SLOs at saturation
    # and degrades gracefully past it"
    router_detail = _router_slo_report(model, variables, gen_cfg, slots)
    records.append({
        "metric": "gpt_345m_serving_router_slo",
        "value": router_detail["at"]["goodput"],
        "unit": "goodput_frac",
        "vs_baseline": None,
        "detail": router_detail,
    })

    # phase-disaggregated record (docs/SERVING.md "Disaggregated
    # prefill/decode"): 1 prefill + 1 decode replica vs 2 colocated on
    # the same workload — byte parity, the TTFT/TPOT trade both ways,
    # the shipped-KV wire counters, and the shared-disk tier sub-pass
    disagg_detail = _disagg_report(model, variables, gen_cfg, slots)
    records.append({
        "metric": "gpt_345m_serving_disagg",
        "value": round(disagg_detail["useful_tokens"]
                       / disagg_detail["elapsed_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "detail": disagg_detail,
    })

    # heterogeneous-fleet record (docs/SERVING.md "Heterogeneous
    # fleet"): mixed GPT + embedding traffic through one model-aware
    # router; the headline is GPT decode throughput under mixed load,
    # per-model TTFT/throughput ride the detail
    hetero_detail = _hetero_report(model, variables, gen_cfg, slots,
                                   workload, cont_toks)
    records.append({
        "metric": "gpt_345m_serving_hetero",
        "value": round(hetero_detail["useful_tokens"]
                       / hetero_detail["elapsed_s"], 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "detail": hetero_detail,
    })

    # per-tenant QoS record (docs/SERVING.md "Per-tenant QoS &
    # autoscaling"): DRR vs FIFO goodput for the well-behaved tenants at
    # 2× saturation with one flooding tenant — byte parity vs an
    # uncontended replay and the autoscale pre-warm leg asserted inside
    qos_detail = _router_qos_report(model, variables, gen_cfg, slots)
    records.append({
        "metric": "gpt_345m_serving_router_qos",
        "value": qos_detail["goodput_well_drr"],
        "unit": "goodput_frac",
        "vs_baseline": None,
        "detail": qos_detail,
    })
    return records


def http_record(n_requests: int = N_REQUESTS, slots: int = SLOTS,
                replicas: int = 2):
    """The ``gpt_345m_serving_http`` record: the continuous workload
    served through the DEPLOYABLE front door — per-replica RPC servers,
    a router over :class:`ReplicaClient` proxies, and the OpenAI-
    compatible SSE API on top (the ``tools/serve.py`` fleet shape, all
    in-process threads here so the record is hermetic) — with byte
    parity vs the in-process engine ASSERTED per request. ``detail``
    carries both sides' TTFT and tokens/s; the delta is the HTTP/RPC
    serving tax. Note the fleet runs ``replicas × slots`` lanes vs the
    baseline's ``slots``, so tokens/s is the fleet-shape number, not an
    apples-to-apples single-engine overhead."""
    import concurrent.futures
    import urllib.request

    import jax

    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.serving import ServingEngine
    from fleetx_tpu.serving.api.replica_client import ReplicaClient
    from fleetx_tpu.serving.api.replica_server import ReplicaServer
    from fleetx_tpu.serving.api.server import ApiServer
    from fleetx_tpu.serving.router import ServingRouter

    model = _model()
    workload = _workload(n_requests)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        np.zeros((1, PROMPT_RANGE[1]), np.int32),
    )
    gen_cfg = GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                               pad_token_id=0, max_length=GEN_RANGE[1])

    def make_engine():
        return ServingEngine(model, variables, slots=slots,
                             cache_len=model.cfg.max_position_embeddings,
                             gen_cfg=gen_cfg,
                             prefill_bucket=8 if _TINY else 32)

    # in-process reference: the parity source and the overhead baseline
    engine = make_engine()
    _run_continuous(engine, workload)  # compile warmup
    base_toks, base_elapsed, base_detail = _run_continuous(engine, workload)

    servers = [ReplicaServer(make_engine()).start() for _ in range(replicas)]
    api = None
    try:
        clients = [ReplicaClient(s.url, connect_wait_s=10)
                   for s in servers]
        api = ApiServer(ServingRouter(clients),
                        model_id="fleetx-bench").start()

        def one(item):
            i, (prompt, gen) = item
            req = urllib.request.Request(
                api.url + "/v1/completions",
                json.dumps({"prompt": [int(t) for t in prompt],
                            "max_tokens": int(gen),
                            "stream": True}).encode(),
                {"Content-Type": "application/json"})
            t_submit = time.perf_counter()
            ttft, toks = None, []
            with urllib.request.urlopen(req, timeout=600) as resp:
                for line in resp:
                    line = line.decode().strip()
                    if (not line.startswith("data: ")
                            or line[6:] == "[DONE]"):
                        continue
                    chunk = json.loads(line[6:])
                    if "token" in chunk:
                        if ttft is None:
                            ttft = time.perf_counter() - t_submit
                        toks.append(chunk["token"])
            return i, toks, ttft

        def sweep():
            out = [None] * len(workload)
            ttfts = [0.0] * len(workload)
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=len(workload)) as pool:
                for i, toks, ttft in pool.map(one, enumerate(workload)):
                    out[i] = toks
                    ttfts[i] = ttft if ttft is not None else 0.0
            return out, time.perf_counter() - t0, ttfts

        sweep()  # warmup: compiles every replica engine's decode path
        http_toks, elapsed, ttfts = sweep()
    finally:
        if api is not None:
            api.stop()
        for s in servers:
            s.stop()

    parity = all(
        np.array_equal(np.asarray(a, np.int32), np.asarray(b, np.int32))
        for a, b in zip(base_toks, http_toks))
    assert parity, ("HTTP-served tokens diverged from the in-process "
                    "engine — the front door corrupted a stream")
    useful = sum(g for _, g in workload)
    detail = {
        "requests": len(workload),
        "slots": slots,
        "replicas": replicas,
        "useful_tokens": useful,
        "elapsed_s": round(elapsed, 3),
        "parity": parity,
        **_ttft_stats(ttfts),
        "inproc_tokens_per_s": round(useful / base_elapsed, 1),
        "inproc_ttft_ms_p50": base_detail["ttft_ms_p50"],
        "inproc_elapsed_s": round(base_elapsed, 3),
    }
    return {
        "metric": "gpt_345m_serving_http",
        "value": round(useful / elapsed, 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "detail": detail,
    }


def http_qos_record(slots: int = SLOTS, replicas: int = 2):
    """The ``gpt_345m_serving_router_qos_http`` record: the same
    multi-tenant bursty (azure_llm) trace the in-process QoS record
    uses, replayed through the deployable front door — replica RPC
    servers, a DRR router over :class:`ReplicaClient` proxies, and the
    OpenAI API forwarding each request's ``X-Fleetx-Tenant`` header into
    ``submit(tenant=...)``. Asserted: every well-behaved stream over
    HTTP is byte-identical to the in-process DRR replay of the same
    trace (tenant threading survives the wire), all well-behaved
    requests complete on both sides, any shed lands on the flood lane
    alone (its bounded lane → HTTP 429), and the scraped
    ``fleetx_api_*`` families carry the tenant label end-to-end."""
    import concurrent.futures
    import urllib.error
    import urllib.request

    import jax

    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.obs import get_registry
    from fleetx_tpu.serving import (
        RequestOutcome,
        ServingEngine,
        ServingRouter,
        TenantPolicy,
        TenantSpec,
        WorkloadSpec,
        generate_trace,
        run_trace,
        score_goodput,
        trace_hash,
    )
    from fleetx_tpu.serving.api.replica_client import ReplicaClient
    from fleetx_tpu.serving.api.replica_server import ReplicaServer
    from fleetx_tpu.serving.api.server import ApiServer

    model = _model()
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0),
        np.zeros((1, PROMPT_RANGE[1]), np.int32),
    )
    gen_cfg = GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                               pad_token_id=0, max_length=GEN_RANGE[1])

    n_well = 6 if _TINY else 16
    n_total = 2 * n_well
    prompt_rng = (3, 8) if _TINY else (32, 96)
    gen_rng = (3, 6) if _TINY else (8, 32)
    rate = 50.0 if _TINY else 20.0
    well = ("paid", "free")
    policies = {
        "paid": TenantPolicy(weight=4.0, priority=1, preempt=False),
        "free": TenantPolicy(weight=2.0),
        "flood": TenantPolicy(weight=1.0, max_queue=2),
    }
    spec = WorkloadSpec(
        seed=37, n_requests=n_total, arrival_rate=rate,
        vocab=model.cfg.vocab_size, distribution="azure_llm",
        tenants=(
            TenantSpec("paid", weight=1.0, prompt_len=prompt_rng,
                       gen_len=gen_rng),
            TenantSpec("free", weight=1.0, prompt_len=prompt_rng,
                       gen_len=gen_rng),
            TenantSpec("flood", weight=2.0, prompt_len=prompt_rng,
                       gen_len=gen_rng),
        ))
    trace = generate_trace(spec)

    def make_engine():
        return ServingEngine(model, variables, slots=slots,
                             cache_len=model.cfg.max_position_embeddings,
                             gen_cfg=gen_cfg,
                             prefill_bucket=8 if _TINY else 32)

    # in-process DRR reference on its own engines: the parity source
    ref_engines = [make_engine() for _ in range(replicas)]

    def ref_router():
        return ServingRouter(ref_engines, tenants=policies,
                             dispatch="drr", preempt=False)

    run_trace(ref_router(), trace)  # compile warmup
    ref = run_trace(ref_router(), trace, keep_tokens=True)
    ref_by_idx = {o.index: o for o in ref}

    servers = [ReplicaServer(make_engine()).start() for _ in range(replicas)]
    api = None
    try:
        clients = [ReplicaClient(s.url, connect_wait_s=10) for s in servers]
        api = ApiServer(ServingRouter(clients, tenants=policies,
                                      dispatch="drr", preempt=False),
                        model_id="fleetx-qos").start()

        def one(tr, t0):
            delay = tr.arrival_s - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            req = urllib.request.Request(
                api.url + "/v1/completions",
                json.dumps({"prompt": [int(t) for t in tr.prompt],
                            "max_tokens": int(tr.max_new_tokens),
                            "stream": True}).encode(),
                {"Content-Type": "application/json",
                 "X-Fleetx-Tenant": tr.tenant})
            t_submit = time.perf_counter()
            times, toks = [], []
            try:
                with urllib.request.urlopen(req, timeout=600) as resp:
                    for line in resp:
                        line = line.decode().strip()
                        if (not line.startswith("data: ")
                                or line[6:] == "[DONE]"):
                            continue
                        chunk = json.loads(line[6:])
                        if "token" in chunk:
                            times.append(time.perf_counter())
                            toks.append(int(chunk["token"]))
            except urllib.error.HTTPError as e:
                e.read()
                return RequestOutcome(index=tr.index, tenant=tr.tenant,
                                      finish_reason="rejected"), None
            done = len(toks) == tr.max_new_tokens
            tpot = ((times[-1] - times[0]) / (len(times) - 1) * 1e3
                    if len(times) >= 2 else None)
            return RequestOutcome(
                index=tr.index, tenant=tr.tenant,
                finish_reason="max_length" if done else "error",
                n_tokens=len(toks),
                ttft_s=(times[0] - t_submit) if times else None,
                tpot_ms=tpot), tuple(toks)

        def sweep():
            t0 = time.perf_counter()
            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=len(trace)) as pool:
                return list(pool.map(lambda tr: one(tr, t0), trace))

        sweep()  # warmup: compiles every replica engine's decode path
        t0 = time.perf_counter()
        results = sweep()
        elapsed = time.perf_counter() - t0
    finally:
        if api is not None:
            api.stop()
        for s in servers:
            s.stop()

    http_outcomes = [o for o, _ in results]
    toks_by_idx = {o.index: t for o, t in results}
    for o in http_outcomes:
        if o.tenant not in well:
            continue
        assert o.finish_reason == "max_length", (
            f"well-behaved request {o.index} did not complete over "
            f"HTTP: {o.finish_reason}")
        ro = ref_by_idx[o.index]
        assert ro.finish_reason in ("eos", "max_length"), (
            f"in-process reference shed request {o.index}")
        assert toks_by_idx[o.index] == ro.tokens, (
            f"request {o.index} ({o.tenant}) diverged between HTTP "
            f"and in-process")
    shed = [o for o in http_outcomes if o.finish_reason == "rejected"]
    assert all(o.tenant == "flood" for o in shed), (
        "shed leaked outside the flood lane: "
        f"{sorted({o.tenant for o in shed})}")
    scrape = get_registry().prometheus_text()
    tenant_labeled = ('tenant="flood"' in scrape
                      and 'tenant="paid"' in scrape)
    assert tenant_labeled, "fleetx_api_* families lost the tenant label"

    http_score = score_goodput(http_outcomes)
    ref_score = score_goodput(ref)
    well_http = [o for o in http_outcomes if o.tenant in well]
    value = round(sum(o.good for o in well_http) / len(well_http), 4)
    return {
        "metric": "gpt_345m_serving_router_qos_http",
        "value": value,
        "unit": "goodput_frac",
        "vs_baseline": None,
        "detail": {
            "requests": n_total,
            "replicas": replicas,
            "slots": slots,
            "arrival_rate": rate,
            "distribution": spec.distribution,
            "workload_hash": trace_hash(trace),
            "elapsed_s": round(elapsed, 3),
            "parity_well_behaved": True,  # asserted above
            "shed_tenants": sorted({o.tenant for o in shed}),
            "api_tenant_labels": tenant_labeled,
            "http": http_score,
            "inproc": ref_score,
            "device": getattr(jax.devices()[0], "device_kind", "?"),
        },
    }


if __name__ == "__main__":
    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--http" in sys.argv[1:]:
        print(json.dumps(http_record()))
        print(json.dumps(http_qos_record()))
    else:
        for rec in serving_records():
            print(json.dumps(rec))
