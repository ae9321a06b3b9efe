"""Continuous-batching serving tests (ISSUE 3).

Core contract: for any admission pattern — mixed prompt lengths,
staggered submits, slot reuse after retirement — every request's greedy
tokens are byte-identical to a one-shot per-request ``generate()`` call.
Plus: per-request sampling overrides with independent RNG streams,
streaming callbacks, retirement/metrics bookkeeping, and the flash-decode
kernel (interpret mode) receiving per-slot live windows."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import (assert_token_parity, one_shot_tokens,
                            sharing_programs)

from fleetx_tpu.models.gpt.generation import GenerationConfig, generate
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.serving import ServingEngine, ServingMetrics

CFG = GPTConfig(
    vocab_size=97,
    hidden_size=48,
    num_layers=2,
    num_attention_heads=4,
    ffn_hidden_size=96,
    max_position_embeddings=64,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype=jnp.float32,
    use_flash_attention=False,
)
GREEDY = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                          pad_token_id=96)


@pytest.fixture(scope="module")
def model_and_params():
    model = GPTForPretraining(CFG)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return model, params


@sharing_programs
def _engine(model, params, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("cache_len", 32)
    kw.setdefault("gen_cfg", GREEDY)
    kw.setdefault("prefill_bucket", 4)
    return ServingEngine(model, params, **kw)


def _one_shot_tokens(model, params, prompt, max_length, eos=10**6):
    """Reference: per-request one-shot generate(), trimmed at EOS (the
    shared tests/serving_parity.py harness bound to this suite's GREEDY)."""
    return one_shot_tokens(model, params, prompt, max_length,
                           gen_cfg=GREEDY, eos=eos)


# --------------------------------------------------- the acceptance parity

@pytest.mark.slow  # 72.0s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_staggered_mixed_length_parity(model_and_params):
    """8 requests, mixed prompt AND decode lengths, staggered admission,
    slots=3 (forces queueing + slot reuse): every request's continuous-
    batching tokens must be byte-identical to its one-shot generate()."""
    model, params = model_and_params
    eng = _engine(model, params)
    rng = np.random.RandomState(7)
    plens = (3, 5, 4, 7, 6, 3, 8, 4)
    glens = (6, 4, 7, 3, 6, 5, 4, 6)
    prompts = [rng.randint(1, 97, (n,)).astype(np.int32) for n in plens]
    rids = {}
    for p, g in zip(prompts[:4], glens[:4]):
        rids[eng.submit(p, max_length=g)] = (p, g)
    for _ in range(3):  # requests 4.. arrive mid-flight
        eng.step()
    for p, g in zip(prompts[4:], glens[4:]):
        rids[eng.submit(p, max_length=g)] = (p, g)
    results = eng.drain()
    assert len(results) == 8
    for rid, (p, g) in rids.items():
        want = _one_shot_tokens(model, params, p, g)
        assert_token_parity(results[rid].tokens, want,
                            err_msg=f"request {rid}")
        assert results[rid].finish_reason == "max_length"
    snap = eng.metrics.snapshot()
    assert snap["retired"] == 8 and snap["submitted"] == 8
    assert snap["tokens_generated"] == sum(glens)
    assert snap["queue_depth_peak"] >= 1  # the stagger actually queued
    assert 0 < snap["slot_occupancy_mean"] <= 1


def test_eos_retirement_frees_slot_and_matches_one_shot(model_and_params):
    """A request retiring on EOS mid-flight must (a) emit exactly what
    one-shot generate() emits up to EOS and (b) hand its slot to the next
    queued request, which must decode its own exact tokens."""
    model, params = model_and_params
    p1 = np.asarray([1, 2, 3], np.int32)
    p2 = np.asarray([9, 8, 7, 6], np.int32)
    # probe greedy's actual emissions so the EOS really fires mid-decode
    probe = _one_shot_tokens(model, params, p1, 8)
    eos = int(probe[0])  # first decoded token — retires after 1 token
    eng = _engine(model, params, slots=1)
    r1 = eng.submit(p1, max_length=8, eos_token_id=eos)
    r2 = eng.submit(p2, max_length=5)  # queued behind r1's slot
    res = eng.drain()
    assert res[r1].finish_reason == "eos"
    assert_token_parity(
        res[r1].tokens, _one_shot_tokens(model, params, p1, 8, eos=eos))
    assert_token_parity(
        res[r2].tokens, _one_shot_tokens(model, params, p2, 5))
    assert eng.cache_manager.free_count == 1  # slot cycled back
    assert eng.metrics.snapshot()["finish_reasons"] == {
        "eos": 1, "max_length": 1}


@pytest.mark.slow  # 38.5s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_slot_reuse_many_requests_few_slots(model_and_params):
    """9 requests through 2 slots: every slot is reused multiple times and
    parity still holds for each tenant."""
    model, params = model_and_params
    eng = _engine(model, params, slots=2)
    rng = np.random.RandomState(3)
    reqs = {}
    for i in range(9):
        p = rng.randint(1, 97, (2 + i % 5,)).astype(np.int32)
        reqs[eng.submit(p, max_length=4)] = p
    res = eng.drain()
    for rid, p in reqs.items():
        assert_token_parity(res[rid].tokens,
                            _one_shot_tokens(model, params, p, 4))
    assert eng.metrics.snapshot()["retired"] == 9
    assert eng.cache_manager.free_count == 2


@pytest.mark.slow  # 21.6s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_flash_decode_per_slot_windows(model_and_params, monkeypatch):
    """Continuous batching over the Pallas flash-decode kernel (interpret
    mode): per-slot ``end`` windows through the kernel must reproduce the
    dense path's one-shot tokens byte-exactly."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    dense_model, params = model_and_params
    flash_model = GPTForPretraining(
        dataclasses.replace(CFG, use_flash_attention=True))
    eng = _engine(flash_model, params, prefill_bucket=8)
    rng = np.random.RandomState(5)
    reqs = {}
    for n in (3, 6, 4, 5):
        p = rng.randint(1, 97, (n,)).astype(np.int32)
        reqs[eng.submit(p, max_length=6)] = p
    res = eng.drain()
    for rid, p in reqs.items():
        assert_token_parity(res[rid].tokens,
                            _one_shot_tokens(dense_model, params, p, 6))


# ------------------------------------------------ per-request decode knobs

@pytest.mark.slow  # 10.2s baseline (PR 12 tier-1 budget audit): per-request
def test_per_request_rng_streams(model_and_params):
    # rng stream reconstruction stays tier-1 via test_serving_recovery's
    # test_sampling_replay_reconstructs_rng_stream
    """Identical sampling submissions draw from independent streams; an
    explicit seed pins a reproducible one; top_k=1 collapses to greedy."""
    model, params = model_and_params
    eng = _engine(model, params, slots=4, gen_cfg=dataclasses.replace(
        GREEDY, decode_strategy="sampling"))
    p = np.asarray([1, 2, 3], np.int32)
    a = eng.submit(p, max_length=8, min_length=8)
    b = eng.submit(p, max_length=8, min_length=8)
    c = eng.submit(p, max_length=8, min_length=8, seed=11)
    d = eng.submit(p, max_length=8, min_length=8, seed=11)
    e = eng.submit(p, max_length=8, top_k=1)
    res = eng.drain()
    assert not np.array_equal(res[a].tokens, res[b].tokens)
    np.testing.assert_array_equal(res[c].tokens, res[d].tokens)
    np.testing.assert_array_equal(
        res[e].tokens, _one_shot_tokens(model, params, p, 8))


@pytest.mark.slow  # 8.8s baseline (PR 12 tier-1 budget audit): per-request
def test_min_length_suppresses_eos_per_request(model_and_params):
    # override plumbing stays tier-1 via the other override/EOS gates
    """min_length counts decoded tokens per request: with min_length=3 the
    EOS greedy would emit at step 1 is banned until step 4."""
    model, params = model_and_params
    p = np.asarray([1, 2, 3], np.int32)
    eos = int(_one_shot_tokens(model, params, p, 6)[0])
    eng = _engine(model, params)
    rid = eng.submit(p, max_length=6, min_length=3, eos_token_id=eos)
    res = eng.drain()
    assert eos not in res[rid].tokens[:3].tolist()
    # one-shot with the same min_length must agree byte-for-byte
    cfg = dataclasses.replace(GREEDY, max_length=6, min_length=3,
                              eos_token_id=eos)
    want = np.asarray(generate(model, params, jnp.asarray(p[None]), cfg))[0]
    gen = want[3:].tolist()
    if eos in gen:
        gen = gen[:gen.index(eos) + 1]
    np.testing.assert_array_equal(res[rid].tokens, gen)


def test_streaming_callbacks_in_order(model_and_params):
    """on_token must stream every decoded token the tick it is produced,
    in order, with finished=True exactly on the last one."""
    model, params = model_and_params
    eng = _engine(model, params, slots=1)
    got = []
    p = np.asarray([4, 9, 2], np.int32)
    rid = eng.submit(p, max_length=5,
                     on_token=lambda i, t, fin: got.append((i, t, fin)))
    res = eng.drain()
    assert [t for _, t, _ in got] == res[rid].tokens.tolist()
    assert [i for i, _, _ in got] == [rid] * 5
    assert [fin for _, _, fin in got] == [False] * 4 + [True]


def test_request_overrides_validated(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params)
    with pytest.raises(ValueError, match="beam"):
        eng.submit(np.asarray([1, 2], np.int32),
                   decode_strategy="beam_search")
    with pytest.raises(ValueError, match="prompt_len"):
        eng.submit(np.arange(40, dtype=np.int32))  # >= cache_len 32
    with pytest.raises(ValueError, match="repetition_penalty"):
        ServingEngine(model, params, gen_cfg=dataclasses.replace(
            GREEDY, repetition_penalty=1.5))
    # oversized decode clamps (with a warning) instead of dying mid-flight
    rid = eng.submit(np.arange(20, dtype=np.int32), max_length=50)
    res = eng.drain()
    assert len(res[rid].tokens) == 12  # cache_len 32 - prompt 20


# ----------------------------------------------------- unit: manager/metrics

def test_slot_layout_is_refused(model_and_params):
    """The page pool is the engine's one layout: ``paged=False`` (the
    fixed per-slot cache, removed at PR 29) raises and names the PR;
    ``paged=True`` (what perfbench/serving.py passes) and no argument
    build the same engine, and ``engine.paged`` reads True, which is how
    the router tells it from a KV-free BatchingEngine."""
    model, params = model_and_params
    with pytest.raises(ValueError, match="PR 29"):
        _engine(model, params, paged=False)
    a, b = _engine(model, params), _engine(model, params, paged=True)
    assert a.paged is True and b.paged is True
    assert (a.num_pages, a.page_size, a.cache_len, a.prefix_cache) == (
        b.num_pages, b.page_size, b.cache_len, b.prefix_cache)
    assert type(a.cache_manager) is type(b.cache_manager)


def test_metrics_snapshot_shape():
    m = ServingMetrics(slots=4)
    m.record_submit()
    m.record_admit(0.01)
    m.record_first_token(0.02)
    m.record_tokens(3)
    m.record_retire(0.05, "eos")
    m.observe_tick(queue_depth=2, active_slots=3)
    s = m.snapshot()
    assert s["submitted"] == s["admitted"] == s["retired"] == 1
    assert s["tokens_generated"] == 3
    assert s["queue_depth_peak"] == 2
    assert s["slot_occupancy_mean"] == pytest.approx(0.75)
    assert s["ttft_ms_p50"] == pytest.approx(20.0)
    assert s["finish_reasons"] == {"eos": 1}
