"""Per-tenant QoS dispatch suite: DRR lanes, admission budgets,
priority preemption, prefix pre-warm, and the closed autoscaling loop
(docs/SERVING.md "Per-tenant QoS & autoscaling").

Everything runs on CPU with the tiny deterministic GPT and carries the
``chaos`` marker — INSIDE tier-1 like the router chaos suite: the
load-bearing assertions are (1) lane isolation — a flooding tenant
sheds ITS OWN requests, never another lane's, (2) the exactly-one-
result conservation invariant surviving preemption churn with zero
token loss, and (3) byte parity of preempted/pre-warmed streams
against a never-contended engine."""

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
    FleetAutoscaler,
    QueueFull,
    ServingEngine,
    ServingRouter,
    TenantPolicy,
)

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
    """Reference greedy tokens from a never-contended engine, memoized
    by prompt bytes (batch composition never changes greedy tokens)."""
    key = (prompt.tobytes(), max_length)
    if key not in _CLEAN:
        eng = _engine(tiny, slots=1)
        rid = eng.submit(prompt, max_length=max_length)
        _CLEAN[key] = np.asarray(eng.drain()[rid].tokens)
    return _CLEAN[key]


# ------------------------------------------------------- lane admission


def test_lane_scoped_queue_full_isolates_flooder(tiny):
    """A tenant at its own max_queue sheds ITS OWN submits — the other
    lanes (and the fleet bound) never see the flood."""
    router = ServingRouter(
        [_engine(tiny, slots=1, max_queue=1)],
        tenants={"flood": TenantPolicy(max_queue=2)})
    flood_rids = [router.submit(PROMPTS[0], max_length=8, tenant="flood")
                  for _ in range(2)]
    with pytest.raises(QueueFull) as ei:
        router.submit(PROMPTS[0], max_length=8, tenant="flood")
    assert "flood" in str(ei.value)  # the refusal names the lane
    # the well-behaved lane still admits freely
    good = router.submit(PROMPTS[1], max_length=8)
    res = router.drain(max_ticks=300)
    assert set(res) == set(flood_rids) | {good}
    snap = router.metrics.snapshot()
    assert snap["per_tenant"]["flood"]["shed"] == 1
    assert snap["per_tenant"].get("default", {}).get("shed", 0) == 0


def test_tenant_rate_and_token_budget(tiny):
    """rate_rps bounds admits/second, token_budget bounds cost-tokens
    (prompt + decode budget)/second — both per lane, both refilling
    with the router clock."""
    router = ServingRouter(
        [_engine(tiny, slots=2)],
        tenants={"metered": TenantPolicy(rate_rps=2.0),
                 "budgeted": TenantPolicy(token_budget=16.0)})
    t = [100.0]
    router._now = lambda: t[0]
    a = router.submit(PROMPTS[0], max_length=8, tenant="metered")
    b = router.submit(PROMPTS[0], max_length=8, tenant="metered")
    with pytest.raises(QueueFull) as ei:
        router.submit(PROMPTS[0], max_length=8, tenant="metered")
    assert "metered" in str(ei.value)
    # cost = 3 prompt + 8 decode = 11 <= 16; the second submit busts it
    c = router.submit(PROMPTS[0], max_length=8, tenant="budgeted")
    with pytest.raises(QueueFull):
        router.submit(PROMPTS[0], max_length=8, tenant="budgeted")
    t[0] += 1.0  # one second on: both buckets refill
    d = router.submit(PROMPTS[1], max_length=8, tenant="metered")
    e = router.submit(PROMPTS[1], max_length=8, tenant="budgeted")
    res = router.drain(max_ticks=300)
    assert set(res) == {a, b, c, d, e}
    for rid in (a, b, c, d, e):
        assert res[rid].finish_reason == "max_length"


# ----------------------------------------------------------- DRR order


def test_drr_single_lane_matches_fifo(tiny):
    """With only the default lane, DRR degenerates to the legacy FIFO:
    same dispatch order, byte-identical results."""
    outs = {}
    for mode in ("fifo", "drr"):
        router = ServingRouter([_engine(tiny, slots=2)], dispatch=mode)
        rids = [router.submit(p, max_length=8) for p in PROMPTS]
        res = router.drain(max_ticks=300)
        outs[mode] = [list(res[r].tokens) for r in rids]
    assert outs["drr"] == outs["fifo"]
    for toks, p in zip(outs["drr"], PROMPTS):
        np.testing.assert_array_equal(toks, _clean_stream(tiny, p))


def test_drr_weighted_share_and_flood_isolation(tiny):
    """Weighted-fair dispatch under saturation: a heavy lane gets a
    proportionally larger dispatch share, and a flooding lane's backlog
    never blocks the other lanes' heads (per-lane blocking only)."""
    router = ServingRouter(
        [_engine(tiny, slots=2, max_queue=2)],
        tenants={"heavy": TenantPolicy(weight=4.0),
                 "light": TenantPolicy(weight=1.0)},
        drr_quantum=16)
    heavy = [router.submit(PROMPTS[i % 4], max_length=8, tenant="heavy")
             for i in range(6)]
    light = [router.submit(PROMPTS[i % 4], max_length=8, tenant="light")
             for i in range(6)]
    router.step()
    snap = router.metrics.snapshot()["per_tenant"]
    # the first dispatch wave favors the heavy lane (4:1 deficit growth)
    assert (snap["heavy"]["dispatched"]
            >= snap.get("light", {}).get("dispatched", 0))
    res = router.drain(max_ticks=600)
    assert set(res) == set(heavy) | set(light)  # nobody starves forever
    for rid in heavy + light:
        assert res[rid].finish_reason == "max_length"


# ---------------------------------------------------------- preemption


def test_priority_preemption_zero_loss(tiny):
    """THE preemption gate: a deadline-at-risk paid request evicts a
    best-effort in-flight request when the fleet is full; the victim
    re-queues at its lane head, finishes later, and its final stream is
    byte-identical to an uncontended run — zero tokens lost, exactly
    one result each, preemption observable in metrics + events."""
    streams = {}

    def cb(rid, tok, fin):
        streams.setdefault(rid, []).append(int(tok))

    router = ServingRouter(
        [_engine(tiny, slots=1, max_queue=1)],
        tenants={"paid": TenantPolicy(priority=1)},
        deadline_s=60.0, preempt_risk_frac=0.0)
    free1 = router.submit(PROMPTS[0], max_length=8, on_token=cb)
    router.step()   # free1 into the only slot
    free2 = router.submit(PROMPTS[1], max_length=8, on_token=cb)
    router.step()   # free2 into the engine queue (fills max_queue)
    paid = router.submit(PROMPTS[2], max_length=8, on_token=cb,
                         tenant="paid")
    router.step()   # paid can't place -> preempts the cheapest victim
    snap = router.metrics.snapshot()
    assert snap["preempted"] == 1
    assert snap["per_tenant"]["default"]["preempted"] == 1
    ev = get_event_log().find("request_preempted", by_tenant="paid")
    assert ev
    victim = ev[0].attrs["request"]
    assert victim in (free1, free2)
    assert router._requests[victim].preemptions == 1
    res = router.drain(max_ticks=400)
    assert set(res) == {free1, free2, paid}
    for rid, p in zip((free1, free2, paid), PROMPTS[:3]):
        want = _clean_stream(tiny, p)
        assert res[rid].finish_reason == "max_length"
        np.testing.assert_array_equal(np.asarray(res[rid].tokens), want,
                                      err_msg=f"request {rid} diverged")
        assert streams[rid] == list(want), (
            f"request {rid} stream lost/duplicated tokens")


def test_preemption_churn_conservation(tiny):
    """Property-style invariant sweep: random interleavings of
    submit/cancel under preemption pressure, with a replica killed
    mid-churn — every request reaches EXACTLY one terminal result,
    normally-finished streams are byte-identical to clean runs, and no
    callback stream ever loses, duplicates, or reorders a token."""
    for seed in (0, 1):
        faults.reset()
        get_event_log().clear()
        rng = np.random.default_rng(seed)
        # seed 1 additionally flaps replica 0's health probe mid-churn:
        # it must rotate out and BACK without ever being marked dead
        flap = {"probe_flap": "0:2"} if seed else {}
        faults.configure(replica_kill=f"1:{6 + seed}", **flap)
        try:
            router = ServingRouter(
                [_engine(tiny, slots=1, max_queue=1) for _ in range(2)],
                tenants={"paid": TenantPolicy(priority=1)},
                probe_every=1, probe_max_failures=4,
                probe_backoff_ticks=1, deadline_s=120.0,
                preempt_risk_frac=0.0)
            streams = {}

            def cb(rid, tok, fin, streams=streams):
                streams.setdefault(rid, []).append(int(tok))

            submitted, prompts, cancelled = [], {}, set()
            for _ in range(40):
                op = int(rng.integers(0, 4))
                if op <= 1 and len(submitted) < 10:
                    p = np.asarray(
                        rng.integers(1, 60, int(rng.integers(2, 6))),
                        np.int32)
                    tn = "paid" if int(rng.integers(0, 2)) else "default"
                    try:
                        rid = router.submit(p, max_length=8, on_token=cb,
                                            tenant=tn)
                    except QueueFull:
                        continue
                    submitted.append(rid)
                    prompts[rid] = p
                elif op == 2 and submitted and int(rng.integers(0, 5)) == 0:
                    victim = int(rng.choice(submitted))
                    if router.cancel(victim):
                        cancelled.add(victim)
                router.step()
            res = router.drain(max_ticks=600)
        finally:
            faults.reset()
        assert set(res) == set(submitted), "lost or duplicated a result"
        for rid in submitted:
            got = list(np.asarray(res[rid].tokens))
            want = list(_clean_stream(tiny, prompts[rid]))
            if res[rid].finish_reason == "max_length":
                assert got == want, f"request {rid} diverged (seed {seed})"
                assert streams.get(rid, []) == want, (
                    f"request {rid} stream corrupt (seed {seed})")
            else:
                # cancelled/timed out: whatever was delivered is a clean
                # prefix, never reordered or duplicated
                assert got == want[:len(got)], (
                    f"request {rid} partial diverged (seed {seed})")
        ev = get_event_log()
        assert ev.find("replica_dead"), "the kill never landed"
        if seed:
            # the flap-rejoin contract (tier-1 home; the standalone
            # probe-flap test in test_router.py is slow-marked)
            assert ev.find("replica_back", replica=0)
            assert not ev.find("replica_dead", replica=0)


# ----------------------------------------------- pre-warm + autoscaler


def test_prewarm_revives_shared_disk_prefix(tiny, tmp_path):
    """A fresh engine sharing the fleet's DiskPageStore pre-warms a hot
    prefix into its device trie before taking traffic: prewarm() > 0,
    the first real request prefix-hits, and its tokens stay
    byte-identical to an uncontended engine."""
    shared = np.asarray(list(range(1, 25)), np.int32)   # 3 full pages
    disk = dict(disk_cache_dir=str(tmp_path), disk_cache_bytes=1 << 20)
    a = _engine(tiny, slots=2, num_pages=8, **disk)
    rid = a.submit(shared, max_length=4)
    a.drain(max_ticks=200)
    # pool pressure evicts the warm prefix -> spills it to the shared disk
    for lo in (30, 36):
        a.submit(np.asarray(list(range(lo, lo + 24)), np.int32),
                 max_length=4)
    a.drain(max_ticks=200)

    b = _engine(tiny, slots=2, num_pages=8, **disk)
    warmed = b.prewarm(shared)
    assert warmed >= 8, f"prewarm revived only {warmed} tokens"
    rid_b = b.submit(shared, max_length=4)
    res = b.drain(max_ticks=200)[rid_b]
    assert b.metrics.prefix_hits > 0, "first request missed the warm trie"
    want = _clean_stream(tiny, shared, max_length=4)
    np.testing.assert_array_equal(np.asarray(res.tokens), want)


def test_autoscaler_scale_up_prewarms_and_scale_down_drains(tiny):
    """The closed loop end to end (in-process): sustained backlog spawns
    a replica through spawn_fn (pre-warmed from the router's hot
    prefixes), the fleet absorbs the queue, and a sustained lull drains
    and removes a replica — never below min_replicas."""
    router = ServingRouter([_engine(tiny, slots=1, max_queue=1)],
                           probe_every=1)
    spawned = []

    def spawn():
        e = _engine(tiny, slots=2)
        spawned.append(e)
        return e

    scaler = FleetAutoscaler(
        router, spawn, min_replicas=1, max_replicas=2,
        high_queue_tokens=2.0, low_queue_tokens=1.0,
        eval_every=1, up_after=2, down_after=3, grace_s=5.0)
    rids = [router.submit(p, max_length=8) for p in PROMPTS * 2]
    for _ in range(60):
        router.step()
        scaler.step()
        if scaler.scale_ups:
            break
    assert scaler.scale_ups == 1 and len(spawned) == 1
    assert len(router._replicas) == 2
    ev = get_event_log().find("autoscale_up", replica=1)
    assert ev
    # the fleet (old + spawned) finishes everything exactly once
    done = {}
    for _ in range(400):
        router.step()
        scaler.step()
        for rid in rids:
            if rid not in done:
                r = router.take_result(rid)
                if r is not None:
                    done[rid] = r
        if len(done) == len(rids):
            break
    assert len(done) == len(rids)
    assert sum(1 for r in done.values()
               if r.finish_reason == "max_length") == len(rids)
    # idle lull: the loop drains one replica back out, then holds at min
    for _ in range(200):
        router.step()
        scaler.step()
        if scaler.scale_downs and not scaler._draining:
            break
    assert scaler.scale_downs == 1
    assert router.replica_states.count("dead") == 1
    assert router.replica_states.count("ok") == 1
