"""Crash-safe serving chaos suite: transactional ticks, replay recovery,
poison quarantine, hung-tick watchdog, graceful drain.

Everything here runs on CPU in seconds and carries the ``chaos`` marker —
INSIDE tier-1 by design, like tests/test_resilience.py: a serving engine
that loses tokens under faults is as broken as one that emits wrong ones.
The load-bearing assertions are byte-parity ones: after any injected
fault (tick raise, poison request, hung tick, device reset), every
SURVIVING request's token stream must equal the unfaulted run's, for
prompts that share nothing and for prompts that share refcounted prefix
pages, with PagePool invariants intact.
"""

import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from serving_parity import assert_token_parity, sharing_programs

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.serving import (
    RecoveryExhausted,
    ServingEngine,
    ShuttingDown,
)

pytestmark = pytest.mark.chaos

PROMPTS = [np.asarray([1, 2, 3], np.int32),
           np.asarray([4, 5, 6, 7, 8], np.int32),
           np.asarray([9, 10], np.int32),
           np.asarray([11, 12, 13], np.int32)]
# the same tails behind a two-page common prefix (page 8): admission and
# replay go through alloc() with shared > 0 and _register_prefix, so
# refcounted pages ride the fault
SHARED = [np.concatenate([np.arange(20, 36, dtype=np.int32), p])
          for p in PROMPTS]
WORKLOADS = pytest.mark.parametrize(
    "prompts", [PROMPTS, SHARED], ids=["paged", "paged-shared-prefix"])


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
    yield
    faults.reset()


@sharing_programs
def _engine(tiny, **kw):
    model, params = tiny
    gen_cfg = kw.pop("gen_cfg", None) or GenerationConfig(
        decode_strategy="greedy", eos_token_id=10**6, pad_token_id=60,
        max_length=8)
    return ServingEngine(model, params, slots=3, cache_len=32,
                         gen_cfg=gen_cfg, prefill_bucket=4, page_size=8,
                         **kw)


def _check_pool(eng):
    eng.cache_manager.pool.check_invariants()


def _run(tiny, prompts=PROMPTS, *, fault_kw=None, seeds=None, max_length=8,
         **ekw):
    """Submit ``prompts``, drain, return ({rid: tokens}, engine)."""
    if fault_kw:
        faults.configure(**fault_kw)
    try:
        eng = _engine(tiny, **ekw)
        rids = [eng.submit(p, max_length=max_length,
                           seed=None if seeds is None else seeds[i])
                for i, p in enumerate(prompts)]
        res = eng.drain()
    finally:
        faults.reset()
    _check_pool(eng)
    return {i: np.asarray(res[r].tokens) for i, r in enumerate(rids)}, eng


_CLEAN = {}


def _clean(tiny, prompts=PROMPTS):
    """Unfaulted-run token streams, computed once per workload (every
    parity test compares against the same greedy baseline; recomputing it
    per test would just re-pay engine compile time)."""
    key = id(prompts)
    if key not in _CLEAN:
        _CLEAN[key] = _run(tiny, prompts)[0]
    return _CLEAN[key]


@WORKLOADS
def test_tick_raise_rollback_and_replay_parity(tiny, prompts):
    """An injected decode-tick failure rolls the host bookkeeping back and
    replay recovery resumes byte-identically — surviving token streams
    equal the unfaulted run's, and replay re-populates the prefix trie
    (requests behind one system prompt share its pages again)."""
    clean = _clean(tiny, prompts)
    faulty, eng = _run(tiny, prompts, fault_kw=dict(tick_raise="1"))
    assert eng.metrics.engine_recoveries == 1
    assert eng.metrics.snapshot()["engine_recoveries"] == 1
    if prompts is SHARED:
        assert eng.metrics.snapshot()["prefix_hits"] >= 2
    for i in clean:
        assert_token_parity(clean[i], faulty[i])


@WORKLOADS
def test_manual_recover_is_byte_identical(tiny, prompts):
    """recover() mid-flight (the external-device-reset path) rebuilds the
    caches from prompt + emitted tokens and the finished streams are
    byte-identical to a run that never recovered."""
    clean = _clean(tiny, prompts)
    eng = _engine(tiny)
    rids = [eng.submit(p, max_length=8) for p in prompts]
    eng.step()
    eng.step()
    eng.recover()
    _check_pool(eng)
    if prompts is SHARED:
        # replay went through alloc(history) with shared > 0: every lane
        # replayed after the first holds the prefix's two refcounted pages
        shared = eng.cache_manager.pool.shared_counts
        assert sorted(int(shared[s]) for s in eng._active) == [0, 2, 2]
    res = eng.drain()
    _check_pool(eng)
    for i, r in enumerate(rids):
        assert_token_parity(clean[i], np.asarray(res[r].tokens))


@pytest.mark.slow  # 5.3s (PR 15 tier-1 budget audit): the one-split-
# per-emitted-token RNG reconstruction stays tier-1 via test_router.py
# test_submit_with_history_sampling_rng_position_exact (the same
# _replay seam, sampling byte-parity) and the spec rng gates
def test_sampling_replay_reconstructs_rng_stream(tiny):
    """Replay recovery reconstructs each sampling request's PRNG position
    (one split at admit, one per decode tick), so post-recovery draws
    continue the same stream — byte parity even under sampling."""
    gen = GenerationConfig(decode_strategy="sampling", temperature=0.9,
                           top_k=8, top_p=0.9, eos_token_id=10**6,
                           pad_token_id=60, max_length=8)
    clean, _ = _run(tiny, gen_cfg=gen, seeds=[100, 101, 102, 103])
    faulty, eng = _run(tiny, gen_cfg=gen, seeds=[100, 101, 102, 103],
                       fault_kw=dict(tick_raise="2"))
    assert eng.metrics.engine_recoveries == 1
    for i in clean:
        assert_token_parity(clean[i], faulty[i])


def test_failed_tick_leaves_pre_tick_state(tiny):
    """Transactional tick contract, observed directly: a tick that fails
    before recovery can help (poison present, first strike) must leave
    queue depth, results, and every request's token list exactly as they
    were before that tick."""
    faults.configure(tick_raise="1")
    try:
        eng = _engine(tiny)
        rids = [eng.submit(p, max_length=8) for p in PROMPTS]
        eng.step()  # tick 0: admits + first decode (fault tick counter 0)
        tokens_before = {r.id: list(r.tokens)
                         for r in eng._active.values()}
        results_before = set(eng._results)
        depth_before = eng.scheduler.queue_depth
        summary = eng.step()  # decode attempt 1 raises -> rollback+recover
        assert summary["recovered"]
        assert eng.scheduler.queue_depth == depth_before
        assert set(eng._results) == results_before
        for r in eng._active.values():
            assert list(r.tokens) == tokens_before[r.id]
        _check_pool(eng)
        res = eng.drain()
    finally:
        faults.reset()
    clean = _clean(tiny)
    for i, r in enumerate(rids):
        assert_token_parity(clean[i], np.asarray(res[r].tokens))


@WORKLOADS
def test_poison_request_bisection_neighbor_parity(tiny, prompts):
    """A request whose presence kills the decode step is isolated by
    bisection, retired finish_reason='error' WITH its partial tokens, and
    every neighbor finishes byte-identically to the unfaulted run."""
    clean = _clean(tiny, prompts)
    faults.configure(poison_request="1")
    try:
        eng = _engine(tiny)
        rids = [eng.submit(p, max_length=8) for p in prompts]
        res = eng.drain()
    finally:
        faults.reset()
    _check_pool(eng)
    poison = res[rids[1]]
    assert poison.finish_reason == "error"
    assert len(poison.tokens) >= 1  # partial output preserved
    assert eng.metrics.poison_retired == 1
    assert eng.metrics.snapshot()["poison_retired"] == 1
    for i in (0, 2, 3):
        assert_token_parity(clean[i],
                                      np.asarray(res[rids[i]].tokens))


def test_poison_prefill_quarantined_without_bisection(tiny):
    """A prefill that fails, survives a recovery, and fails again retires
    exactly the request being admitted — the culprit is known, so no
    bisection; the queue keeps serving afterwards."""
    faults.configure(prefill_raise="0+")
    try:
        eng = _engine(tiny)
        rid = eng.submit(PROMPTS[0], max_length=8)
        res = eng.drain(max_ticks=10)
    finally:
        faults.reset()
    assert res[rid].finish_reason == "error"
    assert len(res[rid].tokens) == 0
    _check_pool(eng)
    # engine healthy after the quarantine: a clean request still matches
    clean = _clean(tiny)
    rid2 = eng.submit(PROMPTS[0], max_length=8)
    res2 = eng.drain()
    assert_token_parity(clean[0], np.asarray(res2[rid2].tokens))


def test_hung_tick_watchdog_recovers(tiny):
    """A tick stuck past FLEETX_SERVING_TICK_TIMEOUT_S is abandoned by the
    watchdog (diagnostics banked) and recovery resumes byte-identically.
    The engine is warmed first — the timeout budget is for steady-state
    ticks, not cold XLA compiles."""
    clean = _clean(tiny)
    eng = _engine(tiny)
    eng.submit(np.asarray([50, 51], np.int32), max_length=3)
    eng.drain()  # warm the decode jit
    faults.configure(tick_hang=str(eng._fault_ticks + 1), tick_hang_s=2.0)
    try:
        eng.tick_timeout_s = 0.3
        rids = [eng.submit(p, max_length=8) for p in PROMPTS]
        res = eng.drain()
    finally:
        faults.reset()
    assert eng.hang_diagnostics is not None
    assert eng.hang_diagnostics["timeout_s"] == 0.3
    assert eng.metrics.engine_recoveries >= 1
    _check_pool(eng)
    for i, r in enumerate(rids):
        assert_token_parity(clean[i], np.asarray(res[r].tokens))


def test_recovery_exhausted_raises(tiny):
    """A fault that is not request-shaped (every tick raises, probes stay
    clean) burns the recovery budget and surfaces RecoveryExhausted."""
    faults.configure(tick_raise="0+")
    try:
        eng = _engine(tiny, max_recoveries=3)
        eng.submit(PROMPTS[0], max_length=8)
        with pytest.raises(RecoveryExhausted):
            eng.drain(max_ticks=20)
    finally:
        faults.reset()


@WORKLOADS
def test_shutdown_returns_partials_for_everything(tiny, prompts):
    """shutdown() under load: every in-flight request returns with its
    partial tokens and finish_reason='shutdown', queued ones return empty,
    new submits reject with ShuttingDown, drain_rejects counts them."""
    eng = _engine(tiny)
    rids = [eng.submit(p, max_length=50) for p in prompts]
    extra = eng.submit(np.asarray([20, 21], np.int32), max_length=50)
    eng.step()
    eng.step()
    res = eng.shutdown(grace_s=0.0)
    assert set(res) == set(rids + [extra])
    for r in rids + [extra]:
        assert res[r].finish_reason == "shutdown"
    assert sum(1 for r in rids if len(res[r].tokens)) >= 3  # partials kept
    with pytest.raises(ShuttingDown):
        eng.submit(PROMPTS[0])
    assert eng.metrics.drain_rejects == 1
    assert eng.metrics.snapshot()["drain_rejects"] == 1
    _check_pool(eng)
    # all lanes and pages released by the drain
    assert eng.cache_manager.active_count == 0


def test_shutdown_with_grace_finishes_short_requests(tiny):
    """Inside a generous grace window the drain FINISHES the work instead
    of truncating it: short requests end eos/max_length, not shutdown."""
    clean = _clean(tiny)
    eng = _engine(tiny)
    rids = [eng.submit(p, max_length=8) for p in PROMPTS]
    eng.step()
    res = eng.shutdown(grace_s=60.0)
    for i, r in enumerate(rids):
        assert res[r].finish_reason == "max_length"
        assert_token_parity(clean[i], np.asarray(res[r].tokens))


def test_sigterm_requests_drain(tiny):
    """SIGTERM → request_shutdown via the installed handler: admission
    stops, the running drain loop finishes in-flight work, partials come
    back. The handler chains and uninstall restores the previous one."""
    eng = _engine(tiny)
    prev = signal.getsignal(signal.SIGTERM)
    eng.install_sigterm_handler(grace_s=0.0)
    try:
        rids = [eng.submit(p, max_length=50) for p in PROMPTS[:3]]
        eng.step()
        import os

        os.kill(os.getpid(), signal.SIGTERM)  # delivered synchronously
        assert eng._shutting_down
        with pytest.raises(ShuttingDown):
            eng.submit(PROMPTS[0])
        res = eng.drain()
        for r in rids:
            assert res[r].finish_reason == "shutdown"
        assert any(len(res[r].tokens) for r in rids)
    finally:
        eng.uninstall_sigterm_handler()
    assert signal.getsignal(signal.SIGTERM) is prev


def test_tick_wallclock_metrics_present(tiny):
    """Per-tick wall-clock percentiles ride the snapshot so recovery cost
    is observable next to steady-state ticks."""
    _, eng = _run(tiny)
    snap = eng.metrics.snapshot()
    assert snap["tick_ms_p50"] is not None
    assert snap["tick_ms_p99"] >= snap["tick_ms_p50"]
    assert len(eng.metrics.tick_s) == snap["ticks"]
