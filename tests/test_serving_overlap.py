"""One decode tick in flight (``fleetx_tpu/serving/engine.py``, "Tick
order"): ``step()`` dispatches tick n and only then reads tick n-1.

The reference throughout is the SYNCHRONOUS engine, which is the same engine
with the watchdog armed (``tick_timeout_s`` > 0 reads every tick right after
its dispatch, through the same function): streams as the callbacks saw them,
finish reasons and results must be equal request by request, over the tiny
GPT / OLMoE / SmallThinker / LFM2 engines the suite already builds. Then what
the new order has to get right on its own: the span order and the two
counters, who a token belongs to when its request left between dispatch and
collection, a fault with a tick unread, a dry pool, and the row counts of the
``serving.decode`` span.

Section (g) is the same for an admission's FIRST token, which stays in flight
like the tick's: the lane install takes it on the device, and the host reads
it only after a later program was dispatched (the next admission's prefill,
or the step's tick); none is unread when ``step()`` returns.
"""

import functools

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_lfm2_serving as lfm2
import test_olmoe_serving as olmoe
import test_smallthinker_serving as smallthinker
from serving_parity import sharing_programs

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.obs import get_event_log
from fleetx_tpu.obs.tracing import get_recorder
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.serving import ServingEngine
from fleetx_tpu.serving import engine as engine_module
from fleetx_tpu.serving.inflight import FLUSH_CAUSES

SYNC = dict(tick_timeout_s=60.0)   # the watchdog armed: no tick in flight
GPT = dict(
    vocab_size=97, hidden_size=48, num_layers=2, num_attention_heads=4,
    ffn_hidden_size=96, max_position_embeddings=64, hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0, dtype=jnp.float32,
    use_flash_attention=False)


def _stirred(model):
    """Seeded weights whose layers decide the logits (at these widths the
    initializer's 0.02 leaves the head alone to decide, and every request
    then decodes the same token: a token handed to the wrong request would
    not show)."""
    v = flax.core.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))

    def stir(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), x.shape)
        if "expert_bias" in name or "conv_kernel" in name:
            return x
        return x * 8.0 if "layers" in name else x

    return jax.tree_util.tree_map_with_path(stir, v)


@pytest.fixture(scope="module")
def gpt():
    model = GPTForPretraining(GPTConfig(**GPT))
    return model, _stirred(model)


@sharing_programs
def gpt_engine(gpt, **kw):
    """Some sixty engines over two dozen settings: each setting is traced
    and compiled once (``sharing_programs``), and every engine still runs
    what an engine built with its setting builds."""
    model, variables = gpt
    kw = {"slots": 3, "cache_len": 32, "page_size": 8, "prefill_bucket": 4,
          **kw}
    return ServingEngine(
        model, variables, gen_cfg=GenerationConfig(
            decode_strategy="greedy", eos_token_id=-1, pad_token_id=96,
            max_length=8), **kw)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(autouse=True)
def _no_probe(monkeypatch):
    """The engine's sampled admission that is read at once goes by the wall
    clock; the cases here count reads, so only the probe's own case has it."""
    monkeypatch.setattr(engine_module, "_PROBE_PERIOD_S", float("inf"))


class Client:
    """Submits with a recording ``on_token`` and keeps what each request
    saw: ``streams[rid]`` is ``[(token, finished), ...]``."""

    def __init__(self, engine):
        self.engine, self.streams, self.ids = engine, {}, []

    def submit(self, prompt, on_token=None, **kw):
        def record(rid, tok, finished):
            self.streams.setdefault(rid, []).append((int(tok), finished))
            if on_token is not None:
                on_token(rid, tok, finished)

        rid = self.engine.submit(np.asarray(prompt, np.int32),
                                 on_token=record, **kw)
        self.ids.append(rid)
        return rid

    def outcome(self, results=None):
        """Per request, in order of submission: the stream, the result's
        tokens and its finish reason."""
        results = self.engine.drain() if results is None else results
        return [(self.streams.get(rid, []), list(results[rid].tokens),
                 results[rid].finish_reason) for rid in self.ids]


def _assert_stream_is_result(outcome):
    """Nothing emitted twice, nothing lost: what the callbacks saw is the
    result, and exactly the last callback said finished."""
    for stream, tokens, reason in outcome:
        assert [t for t, _ in stream] == tokens
        flags = [f for _, f in stream]
        assert not any(flags[:-1])
        if reason in ("eos", "max_length"):
            assert flags[-1]


# ------------------------------------------------- (a) equal to synchronous

def _gpt_mixed(engine, eos_for=None):
    """Mixed lengths on three lanes, more requests than lanes (a lane's
    next tenant), a request crossing a page boundary (page 8: 6 prompt
    tokens then 8 more) with a tick in flight, two sampled lanes with seeds
    beside greedy ones. ``eos_for``: ``(index, token)`` gives one request an
    EOS it meets mid-stream."""
    rng = np.random.default_rng(5)
    client = Client(engine)
    for i, (n, new) in enumerate(((6, 8), (3, 5), (11, 12), (2, 3), (9, 7),
                                  (5, 10))):
        kw = dict(max_length=new)
        if i in (1, 4):
            kw.update(decode_strategy="sampling", seed=11 + i,
                      temperature=0.9, top_k=20, top_p=0.9)
        if eos_for is not None and eos_for[0] == i:
            kw.update(eos_token_id=eos_for[1])
        client.submit(rng.integers(1, 96, n), **kw)
        if i == 2:
            engine.step()   # staggered: the rest arrive mid-flight
    return client.outcome()


@pytest.mark.parametrize("chunk", [0, 4], ids=["whole-prefill", "chunked"])
def test_gpt_streams_equal_the_synchronous_engines(gpt, chunk):
    got = _gpt_mixed(gpt_engine(gpt, prefill_chunk=chunk))
    want = _gpt_mixed(gpt_engine(gpt, prefill_chunk=chunk, **SYNC))
    assert got == want
    _assert_stream_is_result(got)
    assert {reason for _, _, reason in got} == {"max_length"}
    assert sorted(len(t) for _, t, _ in got) == [3, 5, 7, 8, 10, 12]


def test_an_eos_mid_stream_ends_the_request_and_frees_its_lane(gpt):
    """Only the token says EOS: the host holds the lane live for one more
    dispatch, the device carries it inactive, and its token of that tick is
    nobody's (not the next tenant's either)."""
    plain = _gpt_mixed(gpt_engine(gpt))
    # a token request 2 meets at its fourth position and not before
    tokens = plain[2][1]
    eos = next(t for i, t in enumerate(tokens) if i >= 3 and t not in tokens[:i])
    cut = tokens.index(eos) + 1
    got = _gpt_mixed(gpt_engine(gpt), eos_for=(2, eos))
    want = _gpt_mixed(gpt_engine(gpt, **SYNC), eos_for=(2, eos))
    assert got == want
    _assert_stream_is_result(got)
    assert got[2][2] == "eos" and got[2][1] == tokens[:cut] and cut < 12
    for i in (0, 1, 3, 4, 5):          # the others, the lane's next tenant too
        assert got[i] == plain[i]


def _family(module, variables, **kw):
    engine = module.engine_of(module.build(), variables, **kw)
    rng = np.random.default_rng(4)
    client = Client(engine)
    return engine, rng, client


@pytest.fixture(scope="module")
def olmoe_weights():
    return _stirred(olmoe.build())


def test_olmoe_streams_equal_the_synchronous_engines(olmoe_weights):
    def serve(**kw):
        engine, rng, client = _family(olmoe, olmoe_weights, **kw)
        for n, new in ((9, 8), (17, 3), (30, 6), (12, 8), (25, 5), (7, 8)):
            client.submit(rng.integers(1, 512, n), max_length=new)
        out = client.outcome()
        assert engine.cache_manager.pages_in_use == 0
        return out

    got, want = serve(), serve(**SYNC)
    assert got == want
    _assert_stream_is_result(got)


@pytest.fixture(scope="module")
def smallthinker_weights():
    return _stirred(smallthinker.build())


def test_smallthinker_recycles_window_pages_with_a_tick_in_flight(
        smallthinker_weights):
    """The window class lets go of pages behind the window every tick,
    reckoned from the position as dispatched."""
    def serve(**kw):
        engine, rng, client = _family(smallthinker, smallthinker_weights, **kw)
        for n, new in ((50, 24), (37, 9), (70, 20), (20, 30)):
            client.submit(rng.integers(1, 512, n), max_length=new)
        out = client.outcome()
        manager = engine.cache_manager
        assert manager.pool.pages_in_use == 0
        assert manager.window_pool.pages_in_use == 0
        manager.pool.check_invariants()
        manager.window_pool.check_invariants()
        return out, manager.window_pool.recycled

    (got, recycled), (want, recycled_sync) = serve(), serve(**SYNC)
    assert got == want
    _assert_stream_is_result(got)
    assert recycled == recycled_sync > 0


@pytest.fixture(scope="module")
def lfm2_weights():
    return _stirred(lfm2.build())


def test_lfm2_resumes_conv_state_from_a_prefix_hit(lfm2_weights):
    """Requests on a registered prefix resume the convolution state from the
    matched pages while a tick of their neighbours is in flight."""
    def serve(**kw):
        engine, rng, client = _family(lfm2, lfm2_weights, **kw)
        prefix = rng.integers(1, 512, 32)
        client.submit(np.concatenate([prefix, rng.integers(1, 512, 13)]),
                      max_length=8)
        results = engine.drain()
        for n, new in ((5, 8), (21, 4)):
            client.submit(np.concatenate([prefix, rng.integers(1, 512, n)]),
                          max_length=new)
            engine.step()
            engine.step()
        results.update(engine.drain())
        assert engine.cache_manager.pages_in_use == 0
        engine.cache_manager.pool.check_invariants()
        return (client.outcome(results),
                engine.metrics.snapshot()["state_snapshots_resumed"])

    (got, resumed), (want, resumed_sync) = serve(), serve(**SYNC)
    assert got == want
    _assert_stream_is_result(got)
    assert resumed == resumed_sync == 2


def test_drain_and_generate_batch_return_what_they_did(gpt):
    ids = np.random.default_rng(8).integers(1, 96, (3, 7), dtype=np.int32)
    got = np.asarray(gpt_engine(gpt).generate_batch(ids))
    want = np.asarray(gpt_engine(gpt, **SYNC).generate_batch(ids))
    assert (got == want).all() and got.shape == (3, 7 + 8)


# ------------------------------------------- (b) span order and the counters

def test_a_tick_is_dispatched_before_the_one_before_it_is_read(gpt):
    engine = gpt_engine(gpt)
    client = Client(engine)
    for n in (4, 6, 5):
        client.submit(np.arange(1, n + 1), max_length=8)
    get_recorder().clear()
    results = engine.drain()
    spans = get_recorder().spans()
    decodes = [s for s in spans if s.name == "serving.decode"]
    fetches = [s for s in spans if s.name == "serving.fetch"]
    # seven ticks decode tokens 2..8; the eighth step dispatches nothing
    # (every lane's last token is in flight) and reads the seventh
    assert len(decodes) == len(fetches) == 7
    assert [s.attrs["inflight"] for s in decodes] == [0] + [1] * 6
    for n in range(1, 7):
        # tick n's dispatch ends before the fetch that returns tick n-1,
        # which is back before tick n+1 is dispatched
        assert decodes[n].end_s <= fetches[n - 1].start_s
    for n in range(1, 6):
        assert fetches[n - 1].end_s <= decodes[n + 1].start_s
    assert all(s.attrs["batch"] == 3 for s in decodes + fetches)
    snap = engine.metrics.snapshot()
    assert snap["decode_ticks_overlapped"] == 6
    assert snap["decode_ticks_flushed"] == snap["decode_ticks_flushed_idle"] == 1
    assert (snap["decode_ticks_overlapped"] + snap["decode_ticks_flushed"]
            == len(decodes))
    assert engine._inflight is None
    _assert_stream_is_result(client.outcome(results))


@pytest.mark.parametrize("kw, cause", [(SYNC, "watchdog"),
                                       (dict(spec=True, spec_k=2), "spec")])
def test_an_engine_that_needs_the_tokens_reads_every_tick_at_once(gpt, kw, cause):
    """The watchdog blocks on the program by design and the speculative
    proposer reads the tokens on the host: every tick is flushed, under that
    one cause, and none is ever left in flight."""
    engine = gpt_engine(gpt, **kw)
    engine.submit(np.asarray([3, 1, 4, 1, 5], np.int32), max_length=8)
    get_recorder().clear()
    while engine._active or len(engine.scheduler):
        engine.step()
        assert engine._inflight is None
    snap = engine.metrics.snapshot()
    assert snap["decode_ticks_overlapped"] == 0
    flushed = {c: snap[f"decode_ticks_flushed_{c}"] for c in FLUSH_CAUSES}
    assert flushed.pop(cause) == snap["decode_ticks_flushed"]
    assert not any(flushed.values())
    plain = [s for s in get_recorder().spans() if s.name == "serving.decode"]
    assert snap["decode_ticks_flushed"] == len(plain)   # verify ticks are
    assert all(s.attrs["inflight"] == 0 for s in plain)  # not decode ticks


@pytest.mark.parametrize("read", ["emitted_tokens", "snapshot"])
def test_a_reader_of_exact_state_finds_the_host_where_it_always_was(gpt, read):
    """``emitted_tokens`` and a ``metrics.snapshot()`` that reads the device
    report exact state: the tick in flight is read first, and with none in
    flight a lane's position is its prompt plus its tokens less one, as
    between two steps of the synchronous engine (the benchmark's drivers
    check the engine's pages on that footing)."""
    engine = gpt_engine(gpt)
    client = Client(engine)
    rid = client.submit([7, 8, 9, 10], max_length=10)
    for _ in range(3):
        engine.step()
    lane = engine._active[0]
    assert engine._inflight is not None
    assert engine.cache_manager.lengths[0] == 4 + len(lane.tokens)
    if read == "emitted_tokens":
        assert engine.emitted_tokens(rid) == lane.tokens
    else:
        assert engine.metrics.snapshot()["tokens_generated"] == len(lane.tokens)
    assert engine._inflight is None and len(lane.tokens) == 4
    assert engine.cache_manager.lengths[0] == 4 + len(lane.tokens) - 1
    assert [t for t, _ in client.streams[rid]] == lane.tokens
    assert engine.metrics.snapshot(device=False)["decode_ticks_flushed_other"] == 1
    _assert_stream_is_result(client.outcome())


# ----------------------- (c) a request that leaves between dispatch and read

def _leaving(gpt, leave, **kw):
    """Two lanes, three requests (the third is the next tenant of whichever
    lane frees first); ``leave(engine, client, step)`` acts after each of
    the first steps. A clock that the test moves, so that a deadline falls
    at the same step in both engines."""
    engine = gpt_engine(gpt, slots=2, **kw)
    now = [0.0]
    engine._now = lambda: now[0]
    client = Client(engine)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, 96, n) for n in (5, 7, 4)]
    leave(engine, client, prompts, -1)
    for step in range(40):
        engine.step()
        now[0] += 1.0
        leave(engine, client, prompts, step)
        if not (engine._active or len(engine.scheduler) or engine._inflight):
            break
    return client.outcome(), engine


def _both(gpt, leave):
    (got, engine), (want, _) = _leaving(gpt, leave), _leaving(gpt, leave, **SYNC)
    assert got == want
    assert engine._inflight is None and not engine._active
    assert engine.cache_manager.pages_in_use == 0
    engine.cache_manager.pool.check_invariants()
    return got, engine


def test_cancel_keeps_the_token_in_flight_and_the_next_tenant_gets_none(gpt):
    def leave(engine, client, prompts, step):
        if step == -1:
            for p in prompts:
                client.submit(p, max_length=10)
        if step == 3:
            assert engine.cancel(client.ids[0])
            assert not engine.cancel(client.ids[0])
            # nothing of it is on its way any more
            assert engine._inflight is None

    got, engine = _both(gpt, leave)
    _assert_stream_is_result(got)
    stream, tokens, reason = got[0]
    assert reason == "cancelled" and len(tokens) == 5   # first token + 4 ticks
    assert [r for _, _, r in got[1:]] == ["max_length"] * 2
    assert engine.metrics.snapshot()["decode_ticks_flushed_other"] == 1


def test_a_deadline_keeps_the_token_in_flight(gpt):
    def leave(engine, client, prompts, step):
        if step == -1:
            client.submit(prompts[0], max_length=10, deadline_s=3.5)
            for p in prompts[1:]:
                client.submit(p, max_length=10)

    got, engine = _both(gpt, leave)
    _assert_stream_is_result(got)
    assert got[0][2] == "timeout" and 1 < len(got[0][1]) < 10
    assert [r for _, _, r in got[1:]] == ["max_length"] * 2
    snap = engine.metrics.snapshot()
    assert snap["decode_ticks_flushed_evict"] == 1 and snap["timeouts"] == 1


def test_a_raising_callback_retires_its_request_alone(gpt):
    """The callback raises on its request's third token, with the next tick
    already dispatched for that lane: the request leaves with three tokens,
    the unread tick's token for its lane is dropped, and the lane's next
    tenant starts clean."""
    def leave(engine, client, prompts, step):
        if step != -1:
            return
        seen = []

        def third(rid, tok, finished):
            seen.append(tok)
            if len(seen) == 3:
                raise RuntimeError("the client went away")

        client.submit(prompts[0], on_token=third, max_length=10)
        for p in prompts[1:]:
            client.submit(p, max_length=10)

    got, engine = _both(gpt, leave)
    _assert_stream_is_result(got)
    assert got[0][2] == "error" and len(got[0][1]) == 3
    assert [(len(t), r) for _, t, r in got[1:]] == [(10, "max_length")] * 2
    assert engine.metrics.snapshot()["callback_errors"] == 1


def test_the_tick_in_flight_never_reaches_the_lanes_next_tenant(gpt):
    """By hand: a request leaves, another takes its lane in the same step
    that reads the tick dispatched for the one that left."""
    engine = gpt_engine(gpt, slots=1)
    client = Client(engine)
    first = client.submit([5, 6, 7], max_length=10)
    for _ in range(3):
        engine.step()
    tick = engine._inflight
    assert tick is not None and tick.lanes[0].id == first
    # retired by a callback-style eviction: no collection before it acts
    engine._evict(tick.lanes[0], "error", engine._now())
    second = client.submit([9, 8, 7, 6], max_length=4)
    engine.step()     # admits into lane 0, dispatches, reads the old tick
    assert engine._active[0].id == second
    results = engine.drain()
    alone = Client(gpt_engine(gpt, slots=1))
    alone.submit([9, 8, 7, 6], max_length=4)
    assert list(results[second].tokens) == alone.outcome()[0][1]
    assert [t for t, _ in client.streams[second]] == list(results[second].tokens)
    assert len(client.streams[first]) == len(results[first].tokens) == 3


# -------------------------------------------- (d) a fault with a tick unread

def _served_with(gpt, arm):
    """Three requests; ``arm(engine)`` is called with a tick in flight."""
    engine = gpt_engine(gpt)
    warm = engine.submit(np.asarray([50, 51], np.int32), max_length=3)
    engine.drain()
    engine.take_result(warm)
    client = Client(engine)
    rng = np.random.default_rng(12)
    for n, new in ((5, 9), (8, 6), (3, 12)):
        client.submit(rng.integers(1, 96, n), max_length=new)
    for _ in range(3):
        engine.step()
    assert engine._inflight is not None
    arm(engine)
    out = client.outcome()
    assert engine.cache_manager.pages_in_use == 0
    engine.cache_manager.pool.check_invariants()
    return out, engine


def test_a_tick_fault_drops_the_unread_tick_and_replay_recomputes_it(gpt):
    clean, _ = _served_with(gpt, lambda engine: None)
    faulted, engine = _served_with(gpt, lambda engine: faults.configure(
        tick_raise=str(engine._fault_ticks)))
    assert faulted == clean
    _assert_stream_is_result(faulted)
    assert engine.metrics.engine_recoveries == 1
    assert get_event_log().find("tick_fault")


def test_a_hung_tick_with_one_unread_recovers_exactly(gpt):
    """The watchdog is armed with a tick in flight; the next dispatch hangs
    and is abandoned. Neither tick's tokens reached the host: both are
    computed again."""
    clean, _ = _served_with(gpt, lambda engine: None)

    def arm(engine):
        faults.configure(tick_hang=str(engine._fault_ticks), tick_hang_s=2.0)
        engine.tick_timeout_s = 0.3

    faulted, engine = _served_with(gpt, arm)
    assert faulted == clean
    _assert_stream_is_result(faulted)
    assert engine.hang_diagnostics["timeout_s"] == 0.3
    assert engine.metrics.engine_recoveries == 1


class _Unreadable:
    """A device array whose way back to the host fails."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("device lost")


@pytest.mark.parametrize("where", ["step", "cancel", "recover"])
def test_a_read_that_fails_is_a_failed_tick(gpt, where):
    """A device error of tick n surfaces when tick n is read: in the next
    step() (inside its transaction), or in a cancel or recover() from
    outside. Nothing of it was emitted; replay computes it again."""
    clean, _ = _served_with(gpt, lambda engine: None)

    def arm(engine):
        engine._inflight.tok = _Unreadable()
        if where == "cancel":
            rid = min(r.id for r in engine._active.values())
            assert engine.cancel(rid) is True
        elif where == "recover":
            engine.recover()
        assert (engine._inflight is None) == (where != "step")

    faulted, engine = _served_with(gpt, arm)
    if where == "cancel":
        # the cancelled request left with what it had; the others are exact
        assert faulted[0][2] == "cancelled" and faulted[1:] == clean[1:]
    else:
        assert faulted == clean
    _assert_stream_is_result(faulted)
    assert engine.metrics.engine_recoveries == 1


# ------------------------------------------------------------ (e) a dry pool

def _tight(gpt, prompts_and_budgets, **kw):
    """Two lanes over four usable pages of eight rows, no prefix cache."""
    engine = gpt_engine(gpt, slots=2, num_pages=5, prefix_cache=False, **kw)
    client = Client(engine)
    for n, new in prompts_and_budgets:
        client.submit(np.arange(1, n + 1), max_length=new)
    return client.outcome(), engine


def test_a_dry_pool_reads_the_tick_in_flight_before_it_decides(gpt):
    """Both requests fill the pool. The short one's last token is in flight
    when the long one needs its next page: the pool is dry until that tick
    is read, and then it is not. ``cache_full`` is decided after."""
    work = ((14, 3), (14, 12))
    got, engine = _tight(gpt, work)
    want, _ = _tight(gpt, work, **SYNC)
    assert got == want
    _assert_stream_is_result(got)
    assert [(len(t), r) for _, t, r in got] == [(3, "max_length"),
                                                (12, "max_length")]
    assert engine.metrics.snapshot()["decode_ticks_flushed_pool_dry"] == 1
    assert not get_event_log().find("cache_full")


def test_a_pool_still_dry_after_the_read_retires_as_the_synchronous_one(gpt):
    work = ((14, 14), (14, 14))
    got, engine = _tight(gpt, work)
    want, _ = _tight(gpt, work, **SYNC)
    assert got == want
    _assert_stream_is_result(got)
    assert sorted(r for _, _, r in got) == ["cache_full", "max_length"]
    assert engine.metrics.snapshot()["decode_ticks_flushed_pool_dry"] >= 1


# ----------------------------------- (f) rows of the dispatched program

def _decode_spans(serve, **kw):
    get_recorder().clear()
    serve(**kw)
    return [s.attrs for s in get_recorder().spans()
            if s.name == "serving.decode"]


def test_attn_rows_count_the_dispatched_programs_rows(lfm2_weights):
    """A request of 20 prompt tokens: its first tick writes position 20 and
    reads 21 rows, whatever has reached the host by then; a second of 9
    joins at the next tick (chunked prefill admits one a step) for four."""
    def serve(**kw):
        engine = lfm2.engine_of(lfm2.build(), lfm2_weights, **kw)
        engine.submit(np.arange(1, 21, dtype=np.int32), max_length=8)
        engine.submit(np.arange(3, 12, dtype=np.int32), max_length=5)
        engine.drain()

    got, want = _decode_spans(serve), _decode_spans(serve, **SYNC)
    rows = [a["attn_rows"] for a in got]
    assert rows == [21, 22 + 10, 23 + 11, 24 + 12, 25 + 13, 26, 27]
    assert rows == [a["attn_rows"] for a in want]
    assert [a["batch"] for a in got] == [1, 2, 2, 2, 2, 1, 1]


def test_window_rows_count_the_dispatched_programs_rows(smallthinker_weights):
    def serve(**kw):
        engine = smallthinker.engine_of(smallthinker.build(),
                                        smallthinker_weights, **kw)
        engine.submit(np.arange(1, 41, dtype=np.int32), max_length=6)
        engine.drain()

    got, want = _decode_spans(serve), _decode_spans(serve, **SYNC)
    assert [a["full_rows"] for a in got] == [41, 42, 43, 44, 45]
    assert {a["window_rows"] for a in got} == {smallthinker.WINDOW}
    assert [(a["full_rows"], a["window_rows"]) for a in got] == [
        (a["full_rows"], a["window_rows"]) for a in want]


# ------------------------------ (g) an admission's first token in flight

@pytest.fixture(scope="module")
def same(gpt):
    """``same(**kw)``: a GPT engine of this section."""
    return functools.partial(gpt_engine, gpt)


@pytest.fixture(scope="module")
def installer(same):
    """``install(**lane) -> (active, last_tok)`` of lane 1 after the
    engine's own install program ran on a fresh lane state."""
    engine = same()

    def install(tok, packed=-1, wanted=1, eos=-1, max_new=8, decoded=1):
        ints = np.asarray([1, packed, 5, decoded, wanted, eos, max_new, 0, 1,
                           0], np.int32)
        st = engine._admit_jit(engine._state, ints, np.int32(tok),
                               np.ones(2, np.float32), jax.random.PRNGKey(0))
        return bool(st["active"][1]), int(st["last_tok"][1])
    return install


@pytest.mark.parametrize("lane, active, last_tok", [
    (dict(tok=7), True, 7),                             # the common case
    (dict(tok=7, eos=7), False, 7),                     # its first token is EOS
    (dict(tok=7, eos=9), True, 7),
    (dict(tok=0, eos=-1), True, 0),                     # no EOS: 0 is a token
    (dict(tok=7, max_new=1), False, 7),                 # a budget of one
    (dict(tok=7, max_new=2), True, 7),
    (dict(tok=7, wanted=0), False, 7),                  # parked for export
    (dict(tok=7, wanted=0, eos=7), False, 7),
    (dict(tok=3, packed=11), True, 11),                 # a replay packs its own
    (dict(tok=3, packed=11, eos=11), False, 11),
    (dict(tok=11, packed=0, eos=11), True, 0),          # a packed 0 is a token
    (dict(tok=3, packed=11, decoded=4, max_new=4), False, 11),
    (dict(tok=3, packed=11, decoded=3, max_new=4), True, 11),
], ids=lambda v: "-".join(f"{k}{x}" for k, x in v.items())
    if isinstance(v, dict) else None)
def test_the_install_decides_the_lane_from_the_token_on_the_device(
        installer, lane, active, last_tok):
    """``active = wanted & ~(eos >= 0 & tok == eos) & (decoded < max_new)``
    over the token the prefill left on the device, or the one the host
    packed (a replay, a shipped admission): what the host decided when it
    read the token first."""
    assert installer(**lane) == (active, last_tok)


def test_pending_of_counts_the_unread_tick_and_the_unread_first_token():
    from fleetx_tpu.serving.inflight import (InflightFirstToken,
                                              InflightTick, pending_of)

    mine, other = object(), object()
    tick = InflightTick(tok=None, done=None, lanes={0: mine, 2: other},
                        program=9)
    first = InflightFirstToken(tok=None, req=mine, program=5, installed=6)
    assert pending_of(None, (), 0, mine) == 0
    assert pending_of(tick, (), 0, mine) == 1
    assert pending_of(tick, (), 1, mine) == 0     # another lane's token
    assert pending_of(tick, (), 2, mine) == 0     # the lane's other tenant
    assert pending_of(None, [first], 0, mine) == 1
    assert pending_of(tick, [first], 0, mine) == 2
    assert pending_of(tick, [first], 2, other) == 1


def _first_token_counts(engine):
    snap = engine.metrics.snapshot(device=False)   # reads no tick in flight
    return snap["first_tokens_overlapped"], snap["first_tokens_flushed"]


def test_a_first_token_is_read_after_a_later_program_was_dispatched(same):
    """Three admissions in one step: the device's line reads prefill A,
    install A, prefill B, install B, prefill C, install C, tick; A's token
    is read once B's prefill is dispatched, B's once C's is, C's behind the
    tick."""
    engine = same()
    client = Client(engine)
    for n in (4, 6, 5):
        client.submit(np.arange(1, n + 1), max_length=8)
    get_recorder().clear()
    engine.step()
    spans = get_recorder().spans()
    dispatches = sorted(
        (s for s in spans if "program" in s.attrs),
        key=lambda s: s.attrs["program"])
    assert [s.name for s in dispatches] == [
        "serving.prefill", "serving.install"] * 3 + ["serving.decode"]
    reads = [s for s in spans if s.name == "serving.first_token"]
    assert [s.attrs["request"] for s in reads] == client.ids
    for read in reads:
        prefill = next(s for s in dispatches
                       if s.attrs["program"] == read.attrs["reads"])
        install, later = dispatches[dispatches.index(prefill) + 1:][:2]
        assert (prefill.name, install.name) == ("serving.prefill",
                                                "serving.install")
        assert prefill.attrs["request"] == install.attrs["request"] \
            == read.attrs["request"]
        assert later.name in ("serving.prefill", "serving.decode")
        assert install.end_s <= later.start_s
        assert later.end_s <= read.start_s
        assert read.attrs["overlapped"] == 1
    assert _first_token_counts(engine) == (3, 0)
    assert not engine._first_tokens and engine._inflight is not None
    assert all(len(stream) == 1 for stream in client.streams.values())
    _assert_stream_is_result(client.outcome())


def _burst(engine, vocab):
    """Bursts of admissions (several a step, more requests than lanes),
    budgets of one token among them; every other request samples from its
    own seeded stream, the rest are greedy."""
    rng = np.random.default_rng(9)
    client = Client(engine)
    work = ((7, 6), (3, 1), (12, 9), (5, 4), (9, 1), (4, 7), (10, 3), (6, 5))
    for i, (n, new) in enumerate(work):
        kw = dict(max_length=new)
        if i % 2:
            kw.update(decode_strategy="sampling", seed=40 + i,
                      temperature=0.8, top_k=24, top_p=0.95)
        client.submit(rng.integers(1, vocab, n), **kw)
        if i in (4, 6):
            engine.step()
            assert not engine._first_tokens
    return client.outcome()


@pytest.mark.parametrize("family,kw", [
    ("gpt", {}), ("gpt", dict(prefill_chunk=4)), ("olmoe", {})],
    ids=["gpt", "gpt-chunked", "olmoe"])
def test_bursts_of_admissions_equal_the_synchronous_engines(
        same, olmoe_weights, family, kw):
    """Same programs, same sampler, same rng order: every stream is bitwise
    the synchronous order's, callbacks in each request's order, and the
    overlapping engine did overlap."""
    def serve(**more):
        if family == "gpt":
            engine, vocab = same(**kw, **more), 96
        else:
            engine, vocab = olmoe.engine_of(
                olmoe.build(), olmoe_weights, **more), 512
        out = _burst(engine, vocab)
        assert engine.cache_manager.pages_in_use == 0
        return out, _first_token_counts(engine)

    (got, (overlapped, flushed)), (want, sync) = serve(), serve(**SYNC)
    assert got == want
    _assert_stream_is_result(got)
    assert [len(t) for _, t, _ in got] == [6, 1, 9, 4, 1, 7, 3, 5]
    assert overlapped + flushed == 8 and overlapped >= 6
    assert sync == (0, 8)


@pytest.mark.parametrize("how", ["eos", "one_token"])
def test_a_first_token_that_ends_its_request_leaves_the_lane_inert(same,
                                                                   how):
    """The host learns that the first token was the last (EOS, or a budget
    of one) one dispatch late: the install left the device lane inactive,
    the tick already dispatched carries the slot and its token for it is
    nobody's, the pages are free at the read."""
    prompts = [np.arange(3, 9), np.arange(20, 27), np.arange(40, 44)]

    def serve(first_kw, **kw):
        engine = same(slots=2, **kw)
        client = Client(engine)
        client.submit(prompts[0], max_length=6)
        engine.step()
        client.submit(prompts[1], **first_kw)
        client.submit(prompts[2], max_length=5)
        return engine, client

    _, client = serve(dict(max_length=6))
    first = client.outcome()[1][1][0]
    ending = (dict(max_length=6, eos_token_id=first) if how == "eos"
              else dict(max_length=1))
    engine, client = serve(ending)
    pages_before = engine.cache_manager.pages_in_use
    engine.step()
    rid = client.ids[1]
    # it ended at the read, in the step that admitted it
    assert client.streams[rid] == [(first, True)]
    result = engine.result(rid)
    assert list(result.tokens) == [first]
    assert result.finish_reason == ("eos" if how == "eos" else "max_length")
    assert engine._active.keys() == {0} and not engine._first_tokens
    assert not np.asarray(engine._state["active"])[1]
    assert engine.cache_manager.pages_in_use == pages_before
    tick = engine._inflight
    if how == "eos":
        # only the token says EOS: the tick was dispatched for its lane too
        assert tick.lanes[1].id == rid
    else:
        # the host counts a budget itself: no tick for that lane
        assert set(tick.lanes) == {0}
    got = client.outcome()
    _, want_client = serve(ending, **SYNC)
    assert got == want_client.outcome()
    _assert_stream_is_result(got)
    assert [len(t) for _, t, _ in got] == [6, 1, 5]
    assert _first_token_counts(engine) == (3, 0)
    assert engine.cache_manager.pages_in_use == 0
    engine.cache_manager.pool.check_invariants()


@pytest.mark.parametrize("kw, cause", [(SYNC, "watchdog"),
                                       (dict(spec=True, spec_k=2), "spec")])
def test_an_engine_that_reads_every_tick_at_once_reads_first_tokens_at_once(
        same, kw, cause):
    """Every first token is read right after its install, before any later
    dispatch, under the engine's one cause."""
    engine = same(**kw)
    client = Client(engine)
    for n in (4, 6, 5):
        client.submit(np.arange(1, n + 1), max_length=4)
    get_recorder().clear()
    engine.step()
    spans = [s for s in get_recorder().spans()
             if s.name in ("serving.prefill", "serving.install",
                           "serving.first_token") and (
                 "program" in s.attrs or "reads" in s.attrs)]
    assert [s.name for s in spans] == [
        "serving.prefill", "serving.install", "serving.first_token"] * 3
    assert all(s.attrs["overlapped"] == 0 for s in spans[2::3])
    engine.drain()
    snap = engine.metrics.snapshot()
    assert snap["first_tokens_overlapped"] == 0
    assert snap["first_tokens_flushed"] == 3 \
        == snap[f"first_tokens_flushed_{cause}"]


@pytest.mark.parametrize("surfaces", ["at_its_read", "at_the_next_dispatch"])
@pytest.mark.parametrize("tick_lost", [False, True],
                         ids=["prefill", "tick_before_it"])
def test_a_first_token_read_that_fails_is_that_requests_failed_prefill(
        same, tick_lost, surfaces):
    """A device error of prefill A surfaces when A's token is read, with B
    already dispatched behind it, or already when B's prefill is dispatched:
    one strike for A either way, nothing of either was emitted, both go back
    to the queue's head in their order and are admitted again. Where the
    tick dispatched BEFORE A is unreadable too, it is that tick that
    failed."""
    def serve(arm):
        engine = same()
        client = Client(engine)
        rng = np.random.default_rng(21)
        client.submit(rng.integers(1, 96, 5), max_length=7)
        engine.step()
        engine.step()
        assert engine._inflight is not None
        for n, new in ((8, 5), (3, 6)):
            client.submit(rng.integers(1, 96, n), max_length=new)
        if arm:
            dispatch, armed = engine._dispatch_first_token, [True]

            def lose(req, *rest):
                dispatch(req, *rest)
                if armed and req.id == client.ids[1]:
                    armed.clear()
                    engine._first_tokens[-1].tok = _Unreadable()
                    if tick_lost:
                        engine._inflight.tok = _Unreadable()
                    if surfaces == "at_the_next_dispatch":
                        def lost(*args, **kw):
                            del engine._paged_prefill_call   # this once
                            raise RuntimeError("device lost")

                        engine._paged_prefill_call = lost

            engine._dispatch_first_token = lose
            summary = engine.step()
            assert summary["recovered"] and summary["admitted"] == 0
            # nothing of A or B was emitted, and they wait in their order
            assert client.ids[1] not in client.streams
            assert client.ids[2] not in client.streams
            assert [r.id for r in engine.scheduler.snapshot()] == \
                client.ids[1:]
            assert not engine._first_tokens and engine._inflight is None
            strikes = {} if tick_lost else {client.ids[1]: 1}
            assert engine._prefill_strikes == strikes
            fault = get_event_log().find("tick_fault")[-1].attrs
            assert fault["during_prefill"] == (not tick_lost)
            assert fault["request"] == (None if tick_lost else client.ids[1])
        out = client.outcome()
        assert engine.cache_manager.pages_in_use == 0
        engine.cache_manager.pool.check_invariants()
        return out, engine

    (clean, _), (faulted, engine) = serve(False), serve(True)
    assert faulted == clean
    _assert_stream_is_result(faulted)
    assert engine.metrics.engine_recoveries == 1
    assert not engine._prefill_strikes     # the second admission survived


def test_a_sampled_admission_is_read_at_once_inside_its_own_span(
        same, monkeypatch):
    """Once a period the first admission of a step holds its own wait, as
    every admission did before first tokens stayed in flight (what reads an
    admission's host time as the span less that wait needs some that do):
    the read lies inside ``serving.admit``, counts as flushed by ``probe``,
    and the streams are the synchronous engine's."""
    monkeypatch.setattr(engine_module, "_PROBE_PERIOD_S", 3600.0)

    def serve(due, **kw):
        engine = same(**kw)
        engine._probed_at -= 7200.0 if due else 0.0
        client = Client(engine)
        for n in (4, 6, 5):
            client.submit(np.arange(1, n + 1), max_length=4)
        get_recorder().clear()
        engine.step()
        spans = get_recorder().spans()
        out = client.outcome()
        return out, spans, client.ids, engine.metrics.snapshot()

    got, spans, ids, snap = serve(True)
    want, _, _, sync = serve(True, **SYNC)
    assert got == want
    reads = {s.attrs["request"]: s for s in spans
             if s.name == "serving.first_token"}
    admits = {s.attrs["request"]: s for s in spans
              if s.name == "serving.admit"}
    # the step's first admission holds its wait; the next is due a period
    # later, so the other two stay in flight
    first, *rest = ids
    assert reads[first].parent == "serving.admit"
    assert admits[first].start_s <= reads[first].start_s
    assert reads[first].end_s <= admits[first].end_s
    assert reads[first].attrs["overlapped"] == 0
    for rid in rest:
        assert reads[rid].parent == "serving.tick"
        assert reads[rid].attrs["overlapped"] == 1
    assert (snap["first_tokens_overlapped"], snap["first_tokens_flushed"],
            snap["first_tokens_flushed_probe"]) == (2, 1, 1)
    assert snap["decode_ticks_flushed_probe"] == 0
    assert sync["first_tokens_flushed_probe"] == 0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_no_first_token_is_unread_when_step_returns(same, seed):
    """Over a random script of arrivals (budgets of one token, sampled
    lanes and an EOS met wherever it falls among them): after every ``step()`` nothing is
    unread but the one tick, the streams are the synchronous engine's, and
    overlapped plus flushed first tokens are the admissions."""
    def serve(**kw):
        engine = same(slots=3, **kw)
        client = Client(engine)
        rng = np.random.default_rng(100 + seed)
        for _ in range(14):
            for _ in range(rng.integers(0, 4)):
                kw = dict(max_length=int(rng.integers(1, 7)))
                if rng.random() < 0.3:
                    kw.update(eos_token_id=int(rng.integers(1, 96)))
                if rng.random() < 0.3:
                    kw.update(decode_strategy="sampling",
                              seed=int(rng.integers(1, 999)), top_k=16)
                client.submit(rng.integers(1, 96, rng.integers(2, 12)), **kw)
            engine.step()
            assert not engine._first_tokens
            for slot, req in engine._active.items():
                assert req.tokens, (slot, req.id)
        out = client.outcome()
        assert not engine._first_tokens and engine._inflight is None
        return out, engine

    (got, engine), (want, _) = serve(), serve(**SYNC)
    assert got == want
    _assert_stream_is_result(got)
    snap = engine.metrics.snapshot()
    assert (snap["first_tokens_overlapped"] + snap["first_tokens_flushed"]
            == snap["admitted"] == len(got))
    assert snap["first_tokens_flushed"] == snap["first_tokens_flushed_idle"]
    assert engine.cache_manager.pages_in_use == 0
