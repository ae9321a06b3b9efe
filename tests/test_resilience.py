"""Chaos suite (ISSUE 5): deterministic fault injection against the
resilience layer.

Training: a NaN batch is skipped by the step sentry and the post-run
params are byte-identical to a run that never saw the bad batch; a wall
of anomalies aborts cleanly after FLEETX_SENTRY_MAX_SKIPS; a corrupted
latest checkpoint is quarantined and restore falls back to the prior
step; a failed checkpoint write and a raising/slow data stream degrade
gracefully. Serving: a full queue rejects, expired queue-TTL/deadline
requests retire with ``finish_reason="timeout"``, ``cancel()`` frees the
slot for the next admission, and a raising ``on_token`` callback leaves
concurrent requests' outputs byte-identical to an undisturbed run.

Everything runs on CPU in seconds and carries the ``chaos`` marker but
stays inside the tier-1 ``not slow`` selection: resilience regressions
fail the same gate as correctness regressions."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.core.engine import SentryAbort, Trainer
from fleetx_tpu.models import build_module
from fleetx_tpu.models.gpt.generation import GenerationConfig, generate
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.resilience.faults import (
    CkptFault,
    DataFault,
    FaultPlan,
    faults,
    raising_on_token,
)
from fleetx_tpu.serving import QueueFull, ServingEngine
from fleetx_tpu.serving.scheduler import FIFOScheduler, Request

REPO = __file__.rsplit("/tests/", 1)[0]
sys.path.insert(0, REPO)
# the chaos CLI driver owns the tiny-trainer rig (config yaml, synthetic
# batches, param flattening); the suite reuses it so the two can't drift
from tools.chaos_check import _batches, _cfg, _params  # noqa: E402

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _reset_faults():
    """Every chaos test starts and ends with an inert injector."""
    faults.reset()
    yield
    faults.reset()


# ------------------------------------------------------------ training side

def _tcfg(tmp_path, name="o", **over):
    """Tiny single-device trainer config (tools/chaos_check.py's rig)."""
    return _cfg(str(tmp_path), name, **over)


def _tbatches(cfg, n, seed=0):
    """Synthetic next-token LM batches (tools/chaos_check.py's rig)."""
    return _batches(cfg, n, seed=seed)


def _params_np(trainer):
    return _params(trainer)


def test_sentry_nan_step_skipped_params_byte_identical(tmp_path):
    """Acceptance (a): with FLEETX_FAULT_NAN_BATCH poisoning one batch the
    sentry skips that step — no params/opt/step/rng advance — so the final
    params are byte-identical to a run whose data stream never contained
    the bad batch; the batch still counts as consumed."""
    data = None
    cfg1 = _tcfg(tmp_path, "clean")
    m1 = build_module(cfg1)
    t1 = Trainer(cfg1, m1)
    data = _tbatches(cfg1, 5)
    t1.fit([data[0], data[1], data[3], data[4]])  # never sees data[2]
    assert int(t1.state.step) == 4 and t1.sentry_skips == 0

    faults.configure(nan_batch="2")  # poison the 3rd fetched batch
    cfg2 = _tcfg(tmp_path, "faulty")
    t2 = Trainer(cfg2, build_module(cfg2))
    t2.fit(data)
    assert int(t2.state.step) == 4
    assert t2.sentry_skips == 1
    assert faults.injected["nan"] == 1
    # the skipped batch was consumed from the stream (resume won't re-feed
    # it) even though no update was applied
    gbs = cfg2.Global.global_batch_size
    assert t2.consumed_samples == 5 * gbs
    assert t1.consumed_samples == 4 * gbs
    for a, b in zip(_params_np(t1), _params_np(t2)):
        np.testing.assert_array_equal(a, b)


def test_sentry_aborts_after_consecutive_skips(tmp_path, monkeypatch):
    """A poisoned stream skips FLEETX_SENTRY_MAX_SKIPS steps, checkpoints
    the last healthy state — REWRITING the same-step checkpoint so the
    advanced consumed_samples lands in meta (resume must not re-feed the
    poisoned batches and crash-loop) — then raises SentryAbort."""
    monkeypatch.setenv("FLEETX_SENTRY_MAX_SKIPS", "2")
    monkeypatch.setenv("FLEETX_FAULT_NAN_BATCH", "2+")
    faults.configure_from_env()
    cfg = _tcfg(tmp_path)
    cfg.Engine.save_load.save_steps = 2
    cfg.Engine.max_steps = 8
    t = Trainer(cfg, build_module(cfg))
    data = _tbatches(cfg, 8)
    with pytest.raises(SentryAbort, match="2 consecutive"):
        t.fit(data)
    assert t.sentry_skips == 2
    assert int(t.state.step) == 2  # two healthy updates, nothing poisoned
    gbs = cfg.Global.global_batch_size
    assert t.consumed_samples == 4 * gbs  # 2 applied + 2 skipped-but-consumed
    # the step-2 periodic save was rewritten by the abort save: a fresh
    # trainer must resume past the poisoned batches, not back into them
    t2 = Trainer(cfg, build_module(cfg))
    t2.init_state(data[0])
    assert int(t2.state.step) == 2
    assert t2.consumed_samples == 4 * gbs


def test_sentry_gnorm_spike_threshold(tmp_path, monkeypatch):
    """FLEETX_SENTRY_GNORM_MAX treats a finite-but-huge grad norm as an
    anomaly: with an absurdly low threshold every step is a 'spike'."""
    monkeypatch.setenv("FLEETX_SENTRY_GNORM_MAX", "1e-12")
    monkeypatch.setenv("FLEETX_SENTRY_MAX_SKIPS", "2")
    cfg = _tcfg(tmp_path)
    t = Trainer(cfg, build_module(cfg))
    with pytest.raises(SentryAbort):
        t.fit(_tbatches(cfg, 8))
    assert t.sentry_skips == 2 and int(t.state.step) == 0


@pytest.mark.slow  # ~6s; the quarantine-and-fall-back contract stays
# tier-1 via test_partial_state_truncation_quarantined below (mid-leaf
# truncation -> quarantine latest -> restore previous step); this one
# adds the whole-directory-garbage flavour of the same path
def test_checkpoint_fallback_quarantines_corrupt_latest(tmp_path):
    """Acceptance (b): a corrupted latest checkpoint (truncated state dir,
    as a kill between async save and finalize leaves) is quarantined and
    restore walks back to the prior step; training resumes from there."""
    import shutil

    cfg = _tcfg(tmp_path)
    cfg.Engine.save_load.save_steps = 2
    t1 = Trainer(cfg, build_module(cfg))
    data = _tbatches(cfg, 4)
    t1.fit(data)  # periodic saves at steps 2 and 4
    t1.wait_for_checkpoints()
    root = os.path.join(cfg.Engine.save_load.output_dir, "checkpoints")
    steps = sorted(int(n) for n in os.listdir(root) if n.isdigit())
    assert steps == [2, 4]
    # corrupt the newest checkpoint: drop its state payload
    state_dir = [os.path.join(root, "4", n) for n in os.listdir(
        os.path.join(root, "4")) if "state" in n]
    assert state_dir, os.listdir(os.path.join(root, "4"))
    shutil.rmtree(state_dir[0])

    t2 = Trainer(cfg, build_module(cfg))
    t2.init_state(data[0])  # resumable dir -> load() with fallback
    assert int(t2.state.step) == 2  # fell back past the corrupt step 4
    qdir = os.path.join(cfg.Engine.save_load.output_dir, "quarantine")
    assert any(n.isdigit() and int(n) == 4 for n in os.listdir(qdir))
    assert 4 not in t2._ckpt_manager().all_steps()

    # when EVERY checkpoint is corrupt, resume must die loudly — silently
    # retraining from scratch would bury the quarantined history
    from fleetx_tpu.core.engine import CheckpointUnrestorable

    for n in list(os.listdir(root)):
        if n.isdigit():
            for sub in os.listdir(os.path.join(root, n)):
                if "state" in sub:
                    shutil.rmtree(os.path.join(root, n, sub))
    t3 = Trainer(cfg, build_module(cfg))
    with pytest.raises(CheckpointUnrestorable, match="quarantined"):
        t3.init_state(data[0])


def test_checkpoint_write_failure_survived(tmp_path):
    """An injected checkpoint-write failure at the step-2 periodic save is
    logged and counted; training continues and the step-4 save lands."""
    faults.configure(ckpt_save_step="2")
    cfg = _tcfg(tmp_path)
    cfg.Engine.save_load.save_steps = 2
    cfg.Engine.max_steps = 5
    t = Trainer(cfg, build_module(cfg))
    t.fit(_tbatches(cfg, 5))
    assert faults.injected["ckpt"] == 1
    assert t.save_failures == 1
    assert int(t.state.step) == 5
    assert t._ckpt_manager().latest_step() == 4  # step-4 save succeeded
    with pytest.raises(CkptFault):
        # direct save() calls still surface the failure to the caller
        faults.configure(ckpt_save_step="5")
        t.save()


def test_raising_data_stream_banks_emergency_checkpoint(tmp_path):
    """A data iterator dying mid-epoch re-raises, but only after an
    emergency checkpoint banks the healthy progress (slow batches are
    survived with zero behavioral change on the way there)."""
    faults.configure(data_raise_batch="2", data_slow_batch="1",
                     data_slow_s=0.01)
    cfg = _tcfg(tmp_path)
    t = Trainer(cfg, build_module(cfg))
    with pytest.raises(DataFault):
        t.fit(_tbatches(cfg, 8))
    assert faults.injected["data_raise"] == 1
    assert faults.injected["data_slow"] == 1
    assert int(t.state.step) == 2  # two healthy steps before the fault
    assert t._ckpt_manager().latest_step() == 2  # banked before re-raise


def test_meta_advanced_rewrite_survives_ckpt_fault(tmp_path):
    """ISSUE 20 satellite: the meta-advanced rewrite of an existing step
    must never destroy the only copy. The old flow deleted the step
    directory BEFORE the replacement save, so a crash (here: an injected
    CkptFault landing on the rewrite) left nothing restorable; now the
    old directory is detached first and reattached on failure."""
    cfg = _tcfg(tmp_path)
    cfg.Engine.save_load.save_steps = 2
    cfg.Engine.max_steps = 2
    t = Trainer(cfg, build_module(cfg))
    data = _tbatches(cfg, 3)
    t.fit(data[:2])  # periodic save at step 2
    t.wait_for_checkpoints()
    gbs = cfg.Global.global_batch_size
    assert t.consumed_samples == 2 * gbs

    # advance meta with the step counter frozen (what a sentry skip does),
    # then let the rewrite save die on an injected fault
    t.consumed_samples += gbs
    faults.configure(ckpt_save_step="2")
    t._guarded_save(0)
    faults.reset()
    assert t.save_failures == 1

    # the original step-2 checkpoint must still be on disk and restorable
    # with the OLD meta (the rewrite never landed)
    assert t._ckpt_manager().all_steps() == [2]
    t2 = Trainer(cfg, build_module(cfg))
    t2.init_state(data[0])
    assert int(t2.state.step) == 2
    assert t2.consumed_samples == 2 * gbs
    assert not os.path.isdir(os.path.join(
        cfg.Engine.save_load.output_dir, "quarantine"))

    # with the fault cleared the retried rewrite lands the advanced meta
    t.save(epoch=0)
    t.wait_for_checkpoints()
    t3 = Trainer(cfg, build_module(cfg))
    t3.init_state(data[0])
    assert int(t3.state.step) == 2
    assert t3.consumed_samples == 3 * gbs
    # no backup debris left behind after the successful rewrite
    assert not os.path.isdir(os.path.join(
        cfg.Engine.save_load.output_dir, "rewrite", "2"))


def test_partial_state_truncation_quarantined(tmp_path):
    """ISSUE 20 satellite: a checkpoint whose ``state`` payload is
    truncated MID-LEAF (meta JSON intact — the shape a torn write or
    partial copy leaves, unlike the whole-subtree deletion covered
    above) must fail verified restore, be quarantined, and fall back to
    the prior step."""
    cfg = _tcfg(tmp_path)
    cfg.Engine.save_load.save_steps = 2
    t1 = Trainer(cfg, build_module(cfg))
    data = _tbatches(cfg, 4)
    t1.fit(data)  # periodic saves at steps 2 and 4
    t1.wait_for_checkpoints()
    root = os.path.join(cfg.Engine.save_load.output_dir, "checkpoints")

    # truncate the largest file under step 4's state subtree to half
    state_dirs = [os.path.join(root, "4", n)
                  for n in os.listdir(os.path.join(root, "4"))
                  if "state" in n]
    assert state_dirs
    victim, vsize = None, 0
    for d, _, files in os.walk(state_dirs[0]):
        for f in files:
            p = os.path.join(d, f)
            if os.path.getsize(p) > vsize:
                victim, vsize = p, os.path.getsize(p)
    assert victim is not None and vsize > 0
    with open(victim, "r+b") as f:
        f.truncate(vsize // 2)
    # meta stays intact
    assert any("meta" in n for n in os.listdir(os.path.join(root, "4")))

    t2 = Trainer(cfg, build_module(cfg))
    t2.init_state(data[0])
    assert int(t2.state.step) == 2  # fell back past the torn step 4
    qdir = os.path.join(cfg.Engine.save_load.output_dir, "quarantine")
    assert any(n.isdigit() and int(n) == 4 for n in os.listdir(qdir))
    assert 4 not in t2._ckpt_manager().all_steps()


# ------------------------------------------------------------- serving side

SCFG = GPTConfig(
    vocab_size=61,
    hidden_size=32,
    num_layers=2,
    num_attention_heads=2,
    ffn_hidden_size=64,
    max_position_embeddings=32,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype=jnp.float32,
    use_flash_attention=False,
)
SGREEDY = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                           pad_token_id=60)
GEN = 4  # every request decodes 4 tokens (one one-shot compile bucket)


@pytest.fixture(scope="module")
def serving_model():
    model = GPTForPretraining(SCFG)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return model, params


class FakeClock:
    """Manually-advanced clock installed as ``engine._now`` so TTL and
    deadline expiry are exact, not sleep-based."""

    def __init__(self):
        self.t = 0.0

    def advance(self, dt: float) -> None:
        """Move time forward by ``dt`` seconds."""
        self.t += dt

    def __call__(self) -> float:
        return self.t


@pytest.fixture(scope="module")
def bounded_engine(serving_model):
    """slots=1 + max_queue=2 + fake clock: the admission-control rig.
    Tests drain fully, so sharing one engine (and its compiled prefill/
    decode) across tests is safe; metrics asserts use deltas."""
    model, params = serving_model
    eng = ServingEngine(model, params, slots=1, cache_len=16,
                        gen_cfg=SGREEDY, prefill_bucket=4, max_queue=2)
    clock = FakeClock()
    eng._now = clock
    return eng, clock


@pytest.fixture(scope="module")
def multi_engine(serving_model):
    """slots=3, no limits: the callback-isolation rig."""
    model, params = serving_model
    return ServingEngine(model, params, slots=3, cache_len=16,
                         gen_cfg=SGREEDY, prefill_bucket=4)


def _one_shot(model, params, prompt, max_length=GEN):
    cfg = dataclasses.replace(SGREEDY, max_length=max_length)
    out = np.asarray(generate(model, params, jnp.asarray(prompt[None]),
                              cfg))[0]
    return out[len(prompt):]


def test_full_queue_rejects_not_grows(serving_model, bounded_engine):
    """Acceptance (c): with max_queue=2 the third waiting submit raises
    QueueFull (bounded, explicit backpressure) and the accepted requests
    still decode their exact one-shot tokens."""
    model, params = serving_model
    eng, _ = bounded_engine
    rej0 = eng.metrics.rejected
    pa = np.asarray([1, 2, 3], np.int32)
    pb = np.asarray([9, 8, 7], np.int32)
    ra = eng.submit(pa, max_length=GEN)
    rb = eng.submit(pb, max_length=GEN)
    with pytest.raises(QueueFull, match="admission queue is full"):
        eng.submit(np.asarray([5, 5, 5], np.int32), max_length=GEN)
    assert eng.metrics.rejected == rej0 + 1
    assert eng.scheduler.queue_depth == 2  # bounded: the reject didn't queue
    res = eng.drain()
    np.testing.assert_array_equal(res[ra].tokens, _one_shot(model, params, pa))
    np.testing.assert_array_equal(res[rb].tokens, _one_shot(model, params, pb))


def test_queue_ttl_expires_waiting_request(serving_model, bounded_engine):
    """A request whose queue-TTL passes while waiting for the single slot
    retires with finish_reason='timeout' and zero tokens; the slot holder
    is untouched."""
    model, params = serving_model
    eng, clock = bounded_engine
    t0 = eng.metrics.timeouts
    pa = np.asarray([4, 5, 6], np.int32)
    ra = eng.submit(pa, max_length=GEN)
    eng.step()  # ra takes the only slot
    rb = eng.submit(np.asarray([7, 7, 7], np.int32), max_length=GEN,
                    queue_ttl_s=5.0)
    clock.advance(10.0)
    eng.step()
    res = eng.drain()
    assert res[rb].finish_reason == "timeout"
    assert len(res[rb].tokens) == 0
    assert res[ra].finish_reason == "max_length"
    np.testing.assert_array_equal(res[ra].tokens, _one_shot(model, params, pa))
    assert eng.metrics.timeouts == t0 + 1
    assert eng.cache_manager.free_count == 1


def test_deadline_retires_in_flight_request(serving_model, bounded_engine):
    """A total deadline expiring mid-decode retires the request with its
    partial tokens and frees the slot for the next admission."""
    model, params = serving_model
    eng, clock = bounded_engine
    rc = eng.submit(np.asarray([2, 4, 6], np.int32), max_length=8,
                    deadline_s=5.0)
    eng.step()  # admitted: first token sampled at prefill
    clock.advance(10.0)
    eng.step()  # one decode tick, then the deadline sweep catches it
    res = eng.drain()
    assert res[rc].finish_reason == "timeout"
    assert 1 <= len(res[rc].tokens) < 8  # partial output preserved
    assert eng.cache_manager.free_count == 1
    # the freed slot admits the next request, which decodes exactly
    pd = np.asarray([3, 1, 4], np.int32)
    rd = eng.submit(pd, max_length=GEN)
    res = eng.drain()
    np.testing.assert_array_equal(res[rd].tokens, _one_shot(model, params, pd))


def test_cancel_frees_slot_immediately(serving_model, bounded_engine):
    """cancel() retires a queued or in-flight request on the spot: the
    slot is free before the next step and the next admission decodes
    byte-identically."""
    model, params = serving_model
    eng, _ = bounded_engine
    c0 = eng.metrics.cancels
    rd = eng.submit(np.asarray([8, 8, 8], np.int32), max_length=8)
    eng.step()  # rd holds the slot
    re_ = eng.submit(np.asarray([6, 6, 6], np.int32), max_length=GEN)
    assert eng.cancel(re_)  # still queued: no slot involved
    assert eng.cancel(rd)  # in flight: slot freed this instant
    assert eng.cache_manager.free_count == 1
    assert not eng.cancel(999)  # unknown id
    assert not eng.cancel(rd)  # already finished
    res = eng.drain()
    assert res[rd].finish_reason == "cancelled"
    assert res[re_].finish_reason == "cancelled"
    assert len(res[re_].tokens) == 0
    assert eng.metrics.cancels == c0 + 2
    pf = np.asarray([1, 3, 5], np.int32)
    rf = eng.submit(pf, max_length=GEN)
    res = eng.drain()
    np.testing.assert_array_equal(res[rf].tokens, _one_shot(model, params, pf))


def test_raising_on_token_leaves_neighbors_byte_identical(serving_model,
                                                          multi_engine):
    """Acceptance (c): a raising on_token callback retires ITS request
    with finish_reason='error' (partial tokens kept) while concurrent
    requests' outputs stay byte-identical to an undisturbed run."""
    model, params = serving_model
    eng = multi_engine
    e0 = eng.metrics.callback_errors
    pa = np.asarray([11, 12, 13], np.int32)
    pb = np.asarray([21, 22, 23], np.int32)
    pc = np.asarray([31, 32, 33], np.int32)
    seen_a, seen_b = [], []
    ra = eng.submit(pa, max_length=GEN,
                    on_token=lambda i, t, f: seen_a.append(t))
    rb = eng.submit(pb, max_length=GEN,
                    on_token=raising_on_token(after_tokens=2, record=seen_b))
    rc = eng.submit(pc, max_length=GEN)
    res = eng.drain()
    assert res[rb].finish_reason == "error"
    assert len(res[rb].tokens) == 2  # the raising token is kept
    assert len(seen_b) == 2
    for rid, p in ((ra, pa), (rc, pc)):
        assert res[rid].finish_reason == "max_length"
        np.testing.assert_array_equal(
            res[rid].tokens, _one_shot(model, params, p),
            err_msg=f"neighbor {rid} disturbed by the raising callback")
    assert seen_a == res[ra].tokens.tolist()  # a's stream saw every token
    assert eng.metrics.callback_errors == e0 + 1
    assert eng.cache_manager.free_count == 3


def test_raising_callback_on_first_token_retires_at_admit(serving_model,
                                                          multi_engine):
    """The prefill-time first token goes through the same firewall: a
    callback that raises immediately retires the request as 'error'
    without leaking its slot."""
    eng = multi_engine
    rid = eng.submit(np.asarray([7, 7, 7], np.int32), max_length=GEN,
                     on_token=raising_on_token(after_tokens=1))
    res = eng.drain()
    assert res[rid].finish_reason == "error"
    assert len(res[rid].tokens) == 1
    assert eng.cache_manager.free_count == 3


def test_generate_batch_survives_missing_result(serving_model, multi_engine,
                                                monkeypatch):
    """A request retiring without a result entry pads its row instead of
    KeyError-crashing the whole batch (serving/engine.py:311 regression)."""
    eng = multi_engine
    model, params = serving_model
    ids = np.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
    orig = eng.drain

    def drain_and_drop(*a, **kw):
        res = orig(*a, **kw)
        res.pop(min(res))  # simulate a result lost to concurrent retirement
        return res

    monkeypatch.setattr(eng, "drain", drain_and_drop)
    out = np.asarray(eng.generate_batch(
        ids, dataclasses.replace(SGREEDY, max_length=GEN)))
    assert out.shape == (2, 3 + GEN)
    pad = SGREEDY.pad_token_id
    np.testing.assert_array_equal(out[0, 3:], [pad] * GEN)  # dropped row
    np.testing.assert_array_equal(
        out[1, 3:], _one_shot(model, params, ids[1]))  # surviving row exact


# ------------------------------------------------- unit: plan/scheduler bits

def test_fault_selector_grammar():
    """Selector entries: exact ints, comma lists, and open 'N+' ranges."""
    faults.configure(nan_batch="1,3")
    assert 1 in faults._nan_sel and 3 in faults._nan_sel
    assert 0 not in faults._nan_sel and 2 not in faults._nan_sel
    faults.configure(nan_batch="2+")
    assert 1 not in faults._nan_sel
    assert all(i in faults._nan_sel for i in (2, 3, 100))


def test_fault_plan_from_env(monkeypatch):
    """FLEETX_FAULT_* env vars build the plan; none set -> inert (None)."""
    assert FaultPlan.from_env({}) is None
    monkeypatch.setenv("FLEETX_FAULT_DATA_SLOW_BATCH", "3")
    monkeypatch.setenv("FLEETX_FAULT_DATA_SLOW_S", "0.25")
    plan = FaultPlan.from_env(os.environ)
    assert plan.data_slow_batch == "3" and plan.data_slow_s == 0.25


def test_wrap_train_data_inert_passthrough():
    """With no plan the wrapper returns the iterable object unchanged —
    the zero-overhead guarantee for fault-free runs."""
    data = [1, 2, 3]
    assert faults.wrap_train_data(data) is data
    faults.configure(data_raise_batch="5")
    wrapped = faults.wrap_train_data(data)
    assert wrapped is not data and list(wrapped) == data


def _req(rid, submit_time=0.0, **kw):
    kw.setdefault("queue_ttl_s", 0.0)
    kw.setdefault("deadline_s", 0.0)
    return Request(id=rid, prompt=np.asarray([1], np.int32),
                   max_new_tokens=4, min_new_tokens=0, eos_token_id=-1,
                   greedy=True, temperature=1.0, top_k=0, top_p=1.0,
                   rng_key=None, submit_time=submit_time, **kw)


def test_scheduler_remove_and_pop_expired():
    """remove() pulls by id preserving order; pop_expired applies TTL and
    deadline while waiting, and is a no-op scan when nothing has limits."""
    s = FIFOScheduler()
    for r in (_req(0), _req(1), _req(2)):
        s.submit(r)
    assert s.pop_expired(now=1e9) == []  # no limits configured anywhere
    assert s.remove(1).id == 1
    assert s.remove(1) is None
    assert [r.id for r in s._queue] == [0, 2]
    s.submit(_req(3, submit_time=0.0, queue_ttl_s=5.0))
    s.submit(_req(4, submit_time=0.0, deadline_s=2.0))
    dead = s.pop_expired(now=3.0)
    assert [r.id for r in dead] == [4]  # past deadline; ttl=5 still alive
    dead = s.pop_expired(now=6.0)
    assert [r.id for r in dead] == [3]
    assert [r.id for r in s._queue] == [0, 2]
