"""Multi-replica router chaos suite: dispatch, health rotate-out,
zero-token-loss failover, graceful degradation, and the workload/goodput
substrate (docs/SERVING.md "Multi-replica router").

Everything here runs on CPU in seconds and carries the ``chaos`` marker —
INSIDE tier-1 by design, like the engine's crash-safety suite: a router
that loses or duplicates a request under replica failure is as broken as
an engine that emits wrong tokens. The load-bearing assertions are the
EXACTLY-ONE-RESULT conservation invariant and greedy byte parity of
migrated requests against a replica that never died."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from serving_parity import sharing_programs

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.obs import get_event_log
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.serving import (
    QueueFull,
    ServingEngine,
    ServingRouter,
    TenantSpec,
    WorkloadSpec,
    generate_trace,
    score_goodput,
    trace_hash,
)
from fleetx_tpu.serving.workload import RequestOutcome, run_trace

pytestmark = pytest.mark.chaos

PROMPTS = [np.asarray([1, 2, 3], np.int32),
           np.asarray([4, 5, 6, 7, 8], np.int32),
           np.asarray([9, 10], np.int32),
           np.asarray([11, 12, 13], np.int32)]


@pytest.fixture(scope="module")
def tiny():
    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return model, params


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    get_event_log().clear()
    yield
    faults.reset()


GEN = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                      pad_token_id=60, max_length=8)


@sharing_programs
def _engine(tiny, **kw):
    model, params = tiny
    gen_cfg = kw.pop("gen_cfg", GEN)
    return ServingEngine(model, params, slots=kw.pop("slots", 2),
                         cache_len=kw.pop("cache_len", 32),
                         gen_cfg=gen_cfg, prefill_bucket=4, page_size=8,
                         **kw)


_CLEAN = {}


def _clean_stream(tiny, prompt, max_length=8):
    """Reference greedy tokens for one prompt from a never-faulted
    engine, memoized by prompt bytes (batch composition never changes
    greedy tokens — the staggered-parity suites prove that)."""
    key = (prompt.tobytes(), max_length)
    if key not in _CLEAN:
        eng = _engine(tiny, slots=1)
        rid = eng.submit(prompt, max_length=max_length)
        _CLEAN[key] = np.asarray(eng.drain()[rid].tokens)
    return _CLEAN[key]


# ------------------------------------------------------------- failover


@pytest.mark.slow  # 18.1s (PR 17 tier-1 budget audit): the same
# zero-token-loss migration contract stays tier-1 via
# test_serving_api.py::test_rpc_router_byte_parity_and_migration (the
# identical router dead-replica path driven by a real replica-server
# death, asserting byte parity + callback-stream conservation +
# exactly-one-result + replica_dead/request_migrated events); the
# FLEETX_FAULT_REPLICA_KILL injector itself stays covered by the
# chaos_check router_kill scenario and the slow conservation churn.
def test_replica_kill_failover_byte_parity(tiny):
    """THE chaos gate (ISSUE 15): a replica killed mid-burst on a
    3-replica router — every request reaches exactly one terminal
    result, migrated streams are byte-identical to a never-killed
    replica, the callback stream has no lost or duplicated tokens, and
    replica_dead + request_migrated events are banked."""
    streams = {}

    def cb(rid, tok, fin):
        streams.setdefault(rid, []).append(int(tok))

    faults.configure(replica_kill="1:3")
    try:
        router = ServingRouter([_engine(tiny) for _ in range(3)],
                               probe_every=1)
        rids = [router.submit(p, max_length=8, on_token=cb)
                for p in PROMPTS]
        res = router.drain(max_ticks=400)
    finally:
        faults.reset()
    assert len(res) == len(PROMPTS)
    assert get_event_log().find("fault_injected", fault="replica_kill")
    for i, rid in enumerate(rids):
        want = _clean_stream(tiny, PROMPTS[i])
        assert res[rid].finish_reason == "max_length"
        np.testing.assert_array_equal(np.asarray(res[rid].tokens), want,
                                      err_msg=f"request {rid} diverged")
        assert streams[rid] == list(want), (
            f"request {rid} callback stream lost/duplicated tokens")
    ev = get_event_log()
    assert ev.find("replica_dead", replica=1)
    assert ev.find("request_migrated")
    m = router.metrics.snapshot()
    assert m["replica_deaths"] == 1 and m["migrated"] >= 1
    assert router.replica_states[1] == "dead"


@pytest.mark.slow  # 10.0s (PR 19 tier-1 budget audit): the rotate-out/
# escalation half stays tier-1 via test_probe_escalation_marks_dead_and_
# migrates (same probe_flap injector, byte parity on the survivor); the
# flap-REJOIN half (replica_back, never dead) stays tier-1 via
# test_router_qos.py::test_preemption_churn_conservation, whose seed-1
# leg flaps replica 0 mid-churn and asserts replica_back with no death
def test_probe_flap_rotates_out_and_back_never_dead(tiny):
    """A health probe lying for fewer than FLEETX_ROUTER_PROBE_MAX
    probes costs a rotation round-trip (replica_out then replica_back),
    never a replica — and every request still finishes normally."""
    faults.configure(probe_flap="1:2")
    try:
        router = ServingRouter([_engine(tiny), _engine(tiny)],
                               probe_every=1, probe_max_failures=4,
                               probe_backoff_ticks=1)
        rids = [router.submit(p, max_length=8) for p in PROMPTS]
        res = router.drain(max_ticks=400)
    finally:
        faults.reset()
    assert len(res) == len(PROMPTS)
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(res[rid].tokens), _clean_stream(tiny, PROMPTS[i]))
    ev = get_event_log()
    assert ev.find("replica_out", replica=1)
    assert ev.find("replica_back", replica=1)
    assert not ev.find("replica_dead")
    assert router.replica_states == ["ok", "ok"]


def test_probe_escalation_marks_dead_and_migrates(tiny):
    """A probe that keeps failing past the bounded-backoff budget marks
    the replica DEAD exactly once; its hedged-away requests finish
    byte-identically on the survivor."""
    faults.configure(probe_flap="0:50")  # lies far past probe_max
    try:
        router = ServingRouter([_engine(tiny), _engine(tiny)],
                               probe_every=1, probe_max_failures=3,
                               probe_backoff_ticks=1)
        rids = [router.submit(p, max_length=8) for p in PROMPTS]
        res = router.drain(max_ticks=400)
    finally:
        faults.reset()
    assert len(res) == len(PROMPTS)
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(res[rid].tokens), _clean_stream(tiny, PROMPTS[i]))
    ev = get_event_log()
    assert len(ev.find("replica_dead", replica=0)) == 1
    assert router.replica_states[0] == "dead"
    assert router.metrics.snapshot()["probe_failures"] >= 3


@pytest.mark.slow  # 39.9s (PR 16 tier-1 budget audit): the combined
# churn is the belt-and-braces superset — each failure mode it mixes
# keeps its own focused tier-1 gate (kill-failover parity, flap
# rotate-out-and-back, probe escalation, bounded queue + deadline
# shed), and the chaos CLI router scenarios drive the same mix e2e
def test_conservation_under_kill_flap_and_saturation_churn(tiny):
    """THE conservation churn test (ISSUE 15 satellite): random bursts
    over a bounded router queue while replicas are killed and probes
    flap — every accepted request reaches EXACTLY ONE terminal result,
    no stream loses or duplicates a token (callback transcript equals
    the final token list), and every normally-finished stream is
    byte-identical to a never-killed replica."""
    rng = np.random.RandomState(3)
    streams = {}

    def cb(rid, tok, fin):
        streams.setdefault(rid, []).append(int(tok))

    faults.configure(replica_kill="0:6,2:11", probe_flap="1:2")
    try:
        router = ServingRouter([_engine(tiny) for _ in range(3)],
                               probe_every=1, probe_max_failures=3,
                               probe_backoff_ticks=1, max_queue=6)
        accepted, rejected = [], 0
        prompts = {}
        for wave in range(4):
            for _ in range(5):
                p = rng.randint(1, 61, rng.randint(2, 7)).astype(np.int32)
                kw = {}
                if rng.rand() < 0.15:
                    kw["deadline_s"] = 1e-6  # guaranteed shed: saturation
                try:
                    rid = router.submit(p, max_length=8, on_token=cb, **kw)
                except QueueFull:
                    rejected += 1
                    continue
                accepted.append(rid)
                prompts[rid] = p
            for _ in range(3):
                router.step()
        res = router.drain(max_ticks=600)
    finally:
        faults.reset()
    # exactly one terminal result per accepted request, none invented
    assert sorted(res) == sorted(accepted)
    assert rejected > 0, "churn never saturated the bounded queue"
    reasons = {rid: r.finish_reason for rid, r in res.items()}
    assert set(reasons.values()) <= {"max_length", "timeout"}, reasons
    for rid, r in res.items():
        toks = list(np.asarray(r.tokens))
        # the callback transcript IS the result — nothing lost or duped
        assert streams.get(rid, []) == toks, (
            f"request {rid} stream {streams.get(rid)} != result {toks}")
        if r.finish_reason == "max_length":
            np.testing.assert_array_equal(
                np.asarray(r.tokens), _clean_stream(tiny, prompts[rid]),
                err_msg=f"request {rid} diverged from clean replica")
    m = router.metrics.snapshot()
    assert m["replica_deaths"] == 2, m
    assert m["migrated"] >= 1
    assert router.replica_states.count("dead") == 2


def test_suspect_turning_draining_cancels_hedged_copies(tiny):
    """Regression (post-review): a SUSPECT whose next probe says
    'draining' (SIGTERM arrived during the suspicion) is ticked again —
    its hedged-away stale copies must be cancelled FIRST, or they would
    decode alongside the migrated copies and double-deliver tokens."""
    e0, e1 = _engine(tiny), _engine(tiny)
    router = ServingRouter([e0, e1], probe_every=1, probe_max_failures=4,
                           probe_backoff_ticks=1, hedge=True)
    streams = {}

    def cb(rid, tok, fin):
        streams.setdefault(rid, []).append(int(tok))

    rids = [router.submit(p, max_length=8, on_token=cb) for p in PROMPTS]
    router.step()  # dispatch spreads over both replicas
    assert any(r.replica == 0 for r in router._requests.values())
    lies = {"n": 1}
    orig = e0.health

    def flaky_health():
        if lies["n"]:
            lies["n"] -= 1
            return {"state": "dead", "queue_depth": 0, "active": 0}
        return orig()

    e0.health = flaky_health
    router.step()  # probe lies -> suspect, hedge migrates its requests
    assert router.replica_states[0] == "suspect"
    e0.request_shutdown(grace_s=30.0)  # SIGTERM while suspect
    router.step()  # honest probe now says draining -> stale must die
    assert router.replica_states[0] == "draining"
    res = router.drain(max_ticks=400)
    assert sorted(res) == sorted(rids)
    for i, rid in enumerate(rids):
        want = _clean_stream(tiny, PROMPTS[i])
        np.testing.assert_array_equal(np.asarray(res[rid].tokens), want)
        assert streams[rid] == list(want), (
            f"request {rid} stream double-delivered: {streams[rid]}")
    # the draining engine holds no zombie copies of migrated requests
    assert not e0._active and not len(e0.scheduler)
    assert not get_event_log().find("replica_dead")


def test_queue_waits_while_only_replica_is_suspect(tiny):
    """Regression (post-review): with the ONLY replica suspect, dispatch
    must leave the queue waiting (no candidates is a normal state, not a
    crash), and the request completes once the flap clears."""
    faults.configure(probe_flap="0:2")
    try:
        router = ServingRouter([_engine(tiny)], probe_every=1,
                               probe_max_failures=4, probe_backoff_ticks=1)
        rid = router.submit(PROMPTS[0], max_length=8)
        for _ in range(3):  # steps while the lone replica is out
            router.step()
        res = router.drain(max_ticks=300)
    finally:
        faults.reset()
    np.testing.assert_array_equal(np.asarray(res[rid].tokens),
                                  _clean_stream(tiny, PROMPTS[0]))
    assert not get_event_log().find("replica_dead")


def test_all_replicas_dead_strands_loudly(tiny):
    """Total fleet loss must terminate drain() with every request at a
    terminal result (finish_reason='error') and a router_stranded
    event — never a hang."""
    faults.configure(replica_kill="0:2")
    try:
        router = ServingRouter([_engine(tiny)], probe_every=1)
        rids = [router.submit(p, max_length=8) for p in PROMPTS]
        res = router.drain(max_ticks=100)
    finally:
        faults.reset()
    assert sorted(res) == sorted(rids)
    assert all(r.finish_reason == "error" for r in res.values())
    assert get_event_log().find("router_stranded")


# ----------------------------------------------- admit-with-history seam


def test_submit_with_history_continues_byte_identically(tiny):
    """The engine's admit-with-history seam: a request submitted with
    the first k tokens as history finishes with the SAME full stream as
    an uninterrupted run, and on_token fires only for the new tokens."""
    prompt = PROMPTS[1]
    want = _clean_stream(tiny, prompt)
    assert len(want) == 8
    eng = _engine(tiny)
    got = []
    rid = eng.submit(prompt, max_length=8, history=want[:3],
                     on_token=lambda r, t, f: got.append(int(t)))
    res = eng.drain()[rid]
    np.testing.assert_array_equal(np.asarray(res.tokens), want)
    assert got == list(want[3:]), "history tokens must not re-emit"
    assert res.finish_reason == "max_length"


def test_submit_with_history_sampling_rng_position_exact(tiny):
    """Sampling continuation: the same rng key + k history tokens must
    resume the SAME stream (one split per emitted token — the replay
    reconstruction), so failover is RNG-position-exact, not just
    greedy-exact."""
    gen = GenerationConfig(decode_strategy="sampling", temperature=0.9,
                           top_k=8, top_p=0.9, eos_token_id=10**6,
                           pad_token_id=60, max_length=8)
    prompt = PROMPTS[0]
    eng = _engine(tiny, gen_cfg=gen)
    rid = eng.submit(prompt, max_length=8, seed=123)
    want = np.asarray(eng.drain()[rid].tokens)
    eng2 = _engine(tiny, gen_cfg=gen)
    rid2 = eng2.submit(prompt, max_length=8, seed=123, history=want[:4])
    got = np.asarray(eng2.drain()[rid2].tokens)
    np.testing.assert_array_equal(got, want)


def test_submit_with_terminal_history_raises(tiny):
    """Migrating a finished request is a caller bug: history at the
    max_length budget, or ending in EOS, raises at submit."""
    eng = _engine(tiny)
    with pytest.raises(ValueError, match="terminal"):
        eng.submit(PROMPTS[0], max_length=4, history=[5, 6, 7, 8])
    with pytest.raises(ValueError, match="EOS"):
        eng.submit(PROMPTS[0], max_length=8, eos_token_id=7,
                   history=[5, 7])


# ------------------------------------------------------------- dispatch


def test_least_loaded_dispatch_and_prefix_affinity(tiny):
    """Placement: concurrent requests spread to the least-loaded
    replica; a prompt sharing a previously-routed full-page prefix pins
    back to the replica whose warm trie owns it, even when another
    replica is idle; affinity falls back when the owner dies."""
    router = ServingRouter([_engine(tiny), _engine(tiny)], probe_every=1)
    prefix = np.arange(1, 9, dtype=np.int32)  # exactly one 8-token page
    pa = np.concatenate([prefix, np.asarray([20, 21], np.int32)])
    ra = router.submit(pa, max_length=8)
    router.step()  # dispatches to replica 0 (tie-break by index)
    assert router._requests[ra].replica == 0
    # while replica 0 is busy, a fresh unrelated prompt goes to 1
    rb = router.submit(PROMPTS[0], max_length=8)
    router.step()
    assert router._requests[rb].replica == 1
    router.drain(max_ticks=300)
    # replica 0 now idle again and owns the prefix pages: an affinity
    # prompt returns there even though both are idle (and would also if
    # 0 were busier — the pin is the point)
    pc = np.concatenate([prefix, np.asarray([30, 31, 32], np.int32)])
    rc = router.submit(pc, max_length=8)
    router.step()
    assert router._requests[rc].replica == 0
    assert router.metrics.snapshot()["affinity_hits"] >= 1
    router.drain(max_ticks=300)
    # owner dies -> the pin drops, the same prefix falls back to 1
    faults.configure(replica_kill="0:%d" % (router._ticks + 1))
    try:
        rd = router.submit(pc, max_length=8)
        router.step()
    finally:
        faults.reset()
    res = router.drain(max_ticks=300)
    # the request finished on the survivor byte-identically and the
    # dead owner's pin is gone (fallback re-recorded it on replica 1)
    assert res[rd].finish_reason == "max_length"
    np.testing.assert_array_equal(np.asarray(res[rd].tokens),
                                  _clean_stream(tiny, pc))
    assert router.replica_states[0] == "dead"
    assert all(v != 0 for v in router._affinity_map.values())


def test_router_bounded_queue_and_deadline_shed(tiny):
    """Graceful degradation: the bounded router queue rejects the
    overflow with QueueFull, expired queued requests shed as timeout,
    every accepted request still reaches exactly one terminal result,
    and the router serves normally afterwards."""
    router = ServingRouter([_engine(tiny)], max_queue=4)
    accepted, rejected = [], 0
    for i in range(10):
        kw = {"deadline_s": 1e-6} if i == 3 else {}
        try:
            accepted.append(
                router.submit(PROMPTS[i % 4], max_length=8, **kw))
        except QueueFull:
            rejected += 1
    res = router.drain(max_ticks=300)
    assert rejected > 0
    assert sorted(res) == sorted(accepted)
    reasons = [res[r].finish_reason for r in accepted]
    assert "timeout" in reasons
    assert all(x in ("max_length", "timeout") for x in reasons)
    rid = router.submit(PROMPTS[0], max_length=8)
    after = router.drain(max_ticks=200)
    np.testing.assert_array_equal(np.asarray(after[rid].tokens),
                                  _clean_stream(tiny, PROMPTS[0]))


def test_router_shutdown_returns_every_request(tiny):
    """Router-level graceful drain: shutdown() finalizes EVERY request
    (dispatched ones finish or retire under the engine grace window,
    queued ones return 'shutdown'), and later submits reject."""
    from fleetx_tpu.serving import ShuttingDown

    router = ServingRouter([_engine(tiny)], max_queue=0)
    rids = [router.submit(p, max_length=8) for p in PROMPTS * 2]
    router.step()  # dispatch a first wave
    res = router.shutdown(grace_s=30.0)
    assert sorted(res) == sorted(rids)
    assert all(r.finish_reason in ("max_length", "eos", "shutdown")
               for r in res.values())
    with pytest.raises(ShuttingDown):
        router.submit(PROMPTS[0])


def test_queue_ttl_measures_waiting_not_lifetime(tiny):
    """Regression (post-review): the router queue TTL is THIS queue
    residency, not total request age — a migrated request that already
    ran past the TTL must not be shed the instant it re-queues (the
    total-lifetime budget is deadline_s)."""
    router = ServingRouter([_engine(tiny)], queue_ttl_s=5.0)
    rid = router.submit(PROMPTS[0], max_length=8)
    req = router._requests[rid]
    now = router._now()
    # simulate a request that decoded for 20s elsewhere and just
    # re-queued: old submit_time, fresh queue residency
    req.submit_time = now - 20.0
    req.queued_since = now
    assert router._shed_expired(now + 0.1) == 0
    assert req.state == "queued"
    # a genuinely stale queue residency DOES shed...
    req.queued_since = now - 6.0
    assert router._shed_expired(now) == 1
    assert router.result(rid).finish_reason == "timeout"
    # ...and deadline_s still measures total lifetime
    rid2 = router.submit(PROMPTS[0], max_length=8, deadline_s=10.0)
    req2 = router._requests[rid2]
    req2.submit_time = router._now() - 11.0
    req2.queued_since = router._now()
    assert router._shed_expired(router._now()) == 1
    assert router.result(rid2).finish_reason == "timeout"


def test_heterogeneous_fleet_refusal_tries_next_replica(tiny):
    """Regression (post-review): one replica refusing a migrated
    request (history exceeds ITS smaller budget) must not kill it —
    dispatch excludes the refuser and the roomier survivor admits it.
    A request EVERY replica refuses still errors exactly once."""
    small = _engine(tiny, cache_len=16)
    big = _engine(tiny)  # cache_len=32
    router = ServingRouter([small, big])
    rid = router.submit(PROMPTS[0], max_length=28)  # 3 + 28 <= 32 only
    req = router._requests[rid]
    req.tokens = [int(t) % 61 for t in range(14)]  # migrated history:
    router.step()                # 14 >= small's clamped budget of 13
    assert req.state == "dispatched" and req.replica == 1, (
        req.state, req.replica)
    router.drain(max_ticks=300)
    # universal refusal: a single small replica errors it, loudly
    router2 = ServingRouter([_engine(tiny, cache_len=16)])
    rid2 = router2.submit(PROMPTS[0], max_length=14)
    req2 = router2._requests[rid2]
    req2.tokens = [5] * 14
    router2.step()
    assert router2.result(rid2).finish_reason == "error"


def test_raising_health_between_probes_does_not_crash_step(tiny):
    """Regression (post-review): an engine whose health() starts
    raising BETWEEN probes (probe_every > 1) scores infinitely loaded
    in dispatch instead of crashing the router step; the next probe
    rotates it out properly."""
    e0, e1 = _engine(tiny), _engine(tiny)
    router = ServingRouter([e0, e1], probe_every=5, probe_max_failures=2,
                           probe_backoff_ticks=1)
    router.step()  # healthy first probe

    def raising_health():
        raise RuntimeError("health endpoint wedged")

    e0.health = raising_health
    rids = [router.submit(p, max_length=8) for p in PROMPTS]
    res = router.drain(max_ticks=400)  # must not raise
    assert sorted(res) == sorted(rids)
    for i, rid in enumerate(rids):
        np.testing.assert_array_equal(
            np.asarray(res[rid].tokens), _clean_stream(tiny, PROMPTS[i]))
    assert router.replica_states[0] == "dead"  # probes escalated it


# --------------------------------------------- workload generator/scorer


def test_workload_trace_is_seed_deterministic():
    """Same spec -> byte-identical trace (hash equal); different seed ->
    different trace. Bursty windows pin to the shared-prefix tenant and
    its requests actually share the prefix."""
    spec = WorkloadSpec(
        seed=5, n_requests=40, arrival_rate=50.0, vocab=61,
        tenants=(TenantSpec("chat", weight=2.0, prompt_len=(3, 8),
                            gen_len=(2, 5)),
                 TenantSpec("tmpl", weight=1.0, prompt_len=(10, 14),
                            gen_len=(2, 5), shared_prefix_len=8)),
        burst_every_s=0.2, burst_len_s=0.08, burst_factor=5.0)
    t1, t2 = generate_trace(spec), generate_trace(spec)
    assert trace_hash(t1) == trace_hash(t2)
    assert all(np.array_equal(a.prompt, b.prompt)
               for a, b in zip(t1, t2))
    other = WorkloadSpec(**{**spec.__dict__, "seed": 6})
    assert trace_hash(generate_trace(other)) != trace_hash(t1)
    assert [r.arrival_s for r in t1] == sorted(r.arrival_s for r in t1)
    tenants = {r.tenant for r in t1}
    assert tenants == {"chat", "tmpl"}
    tmpl = [r for r in t1 if r.tenant == "tmpl"]
    assert all(np.array_equal(r.prompt[:8], tmpl[0].prompt[:8])
               for r in tmpl), "shared prefix not shared"
    # inside a burst window, arrivals pin to the shared-prefix tenant
    in_burst = [r for r in t1
                if (r.arrival_s % spec.burst_every_s) < spec.burst_len_s]
    assert in_burst and all(r.tenant == "tmpl" for r in in_burst)


def test_goodput_scorer_math():
    """score_goodput: goodput divides GOOD (normal finish + every SLO
    met) by ALL submitted; sheds and SLO misses both charge it."""
    outs = [
        RequestOutcome(index=0, tenant="a", finish_reason="eos",
                       n_tokens=5, ttft_s=0.1, tpot_ms=10.0,
                       ttft_deadline_s=1.0, tpot_deadline_ms=100.0),
        RequestOutcome(index=1, tenant="a", finish_reason="max_length",
                       n_tokens=4, ttft_s=2.0, tpot_ms=10.0,
                       ttft_deadline_s=1.0),               # late TTFT
        RequestOutcome(index=2, tenant="b", finish_reason="rejected",
                       ttft_deadline_s=1.0),               # shed
        RequestOutcome(index=3, tenant="b", finish_reason="timeout",
                       n_tokens=0, ttft_deadline_s=1.0),   # shed
    ]
    s = score_goodput(outs)
    assert s["requests"] == 4
    assert s["good"] == 1 and s["goodput"] == 0.25
    assert s["met_ttft_frac"] == 0.25
    assert s["completed_frac"] == 0.5
    assert s["shed_frac"] == 0.5
    assert s["finish_reasons"] == {"eos": 1, "max_length": 1,
                                   "rejected": 1, "timeout": 1}
    assert s["goodput_per_tenant"] == {"a": 0.5, "b": 0.0}
    assert s["tokens_total"] == 9
    with pytest.raises(ValueError):
        score_goodput([])


class _StubTarget:
    """Host-only serving stub for run_trace mechanics (no jax): each
    step() emits one token per live request through its callback and
    finishes it after ``finish_after`` tokens; cancel() retires."""

    def __init__(self, finish_after=3, step_sleep=0.0):
        import time as _t

        self._t = _t
        self.finish_after = finish_after
        self.step_sleep = step_sleep
        self._next = 0
        self._live = {}
        self._results = {}

    def submit(self, prompt, *, max_length, on_token):
        rid = self._next
        self._next += 1
        self._live[rid] = {"cb": on_token, "n": 0,
                           "prompt": np.asarray(prompt)}
        return rid

    def step(self):
        if self.step_sleep:
            self._t.sleep(self.step_sleep)
        from fleetx_tpu.serving import ServingResult

        for rid, rec in list(self._live.items()):
            rec["n"] += 1
            done = rec["n"] >= self.finish_after
            rec["cb"](rid, rec["n"], done)
            if done:
                self._results[rid] = ServingResult(
                    id=rid, prompt=rec["prompt"],
                    tokens=np.arange(rec["n"], dtype=np.int32),
                    finish_reason="max_length", ttft_s=0.0, latency_s=0.0)
                del self._live[rid]

    def cancel(self, rid):
        from fleetx_tpu.serving import ServingResult

        rec = self._live.pop(rid, None)
        if rec is None:
            return False
        self._results[rid] = ServingResult(
            id=rid, prompt=rec["prompt"],
            tokens=np.arange(rec["n"], dtype=np.int32),
            finish_reason="cancelled", ttft_s=0.0, latency_s=0.0)
        return True

    def take_result(self, rid):
        return self._results.pop(rid, None)


def test_run_trace_abandonment_cancels():
    """An abandoning tenant's request is actively cancelled past its
    patience and scored as not-good; patient requests complete."""
    spec = WorkloadSpec(
        seed=1, n_requests=6, arrival_rate=500.0, vocab=61,
        tenants=(TenantSpec("impatient", prompt_len=(2, 4), gen_len=(2, 4),
                            abandon_s=0.02),))
    trace = generate_trace(spec)
    # a stub whose requests would take ~50 steps x 5ms >> 20ms patience
    outs = run_trace(_StubTarget(finish_after=50, step_sleep=0.005), trace)
    assert len(outs) == 6
    assert all(o.finish_reason == "cancelled" for o in outs)
    assert score_goodput(outs)["goodput"] == 0.0
    # patient run: same trace, fast finishes
    spec2 = WorkloadSpec(**{**spec.__dict__, "tenants": (
        TenantSpec("patient", prompt_len=(2, 4), gen_len=(2, 4)),)})
    outs = run_trace(_StubTarget(finish_after=2), generate_trace(spec2))
    assert all(o.finish_reason == "max_length" for o in outs)
    assert score_goodput(outs)["goodput"] == 1.0
