"""Paged-serving heavyweights (split out of tests/test_paged_serving.py
by the PR 7 tier-1 budget audit — every test here is 50s+ on the
slow-host baseline, dominated by one-shot ``generate()`` reference
compiles).

Full-width versions of the tier-1 parity gates: 8-request staggered
mixed-length parity against one-shot ``generate()``, the paged flash-decode
kernel in interpret mode (including shared-prefix gather through the
trie's pages), the hot-vs-cold prefix-cache engine comparison, and the
per-request sampling/callback behaviors under paged storage. The compact
tier-1 versions in ``test_paged_serving.py`` keep per-commit coverage;
run this module (``-m slow``) for the exhaustive sweep.
"""

import dataclasses

import numpy as np
import pytest

from test_paged_serving import (  # sibling module (pytest rootdir import)
    CFG,
    GREEDY,
    _engine,
    _one_shot_tokens,
    model_and_params,  # noqa: F401  (fixture re-export)
)

from fleetx_tpu.models.gpt.model import GPTForPretraining

pytestmark = pytest.mark.slow


def test_paged_staggered_one_shot_parity_full(model_and_params):  # noqa: F811
    """8 requests, mixed prompt AND decode lengths, staggered admission,
    slots=3 (queueing + lane reuse): paged == one-shot, per request,
    byte-identical."""
    model, params = model_and_params
    rng = np.random.RandomState(7)
    plens = (3, 5, 4, 7, 6, 3, 8, 4)
    glens = (6, 4, 7, 3, 6, 5, 4, 6)
    prompts = [rng.randint(1, 97, (n,)).astype(np.int32) for n in plens]

    eng = _engine(model, params)
    rids = []
    for p, g in zip(prompts[:4], glens[:4]):
        rids.append(eng.submit(p, max_length=g))
    for _ in range(3):
        eng.step()
    for p, g in zip(prompts[4:], glens[4:]):
        rids.append(eng.submit(p, max_length=g))
    res = eng.drain()
    for i, (p, g) in enumerate(zip(prompts, glens)):
        np.testing.assert_array_equal(
            res[rids[i]].tokens, _one_shot_tokens(model, params, p, g),
            err_msg=f"paged vs one-shot, req {i}")
    assert eng.cache_manager.pages_in_use == 0
    assert eng.cache_manager.free_count == 3


def test_paged_flash_interpret_parity(model_and_params, monkeypatch):  # noqa: F811
    """Paged serving through the block-table flash-decode kernel
    (interpret mode) must reproduce the dense one-shot tokens, including
    a shared-prefix pair exercising gather-through-the-trie pages."""
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
        np.testing.assert_array_equal(
            res[rid].tokens, _one_shot_tokens(dense_model, params, p, 6))
    # shared prefix through the kernel: second request reuses page chains
    sysp = rng.randint(1, 97, (16,)).astype(np.int32)
    a = np.concatenate([sysp, rng.randint(1, 97, (3,))]).astype(np.int32)
    b = np.concatenate([sysp, rng.randint(1, 97, (4,))]).astype(np.int32)
    ra = eng.submit(a, max_length=5)
    eng.step()
    rb = eng.submit(b, max_length=5)
    res = eng.drain()
    np.testing.assert_array_equal(
        res[ra].tokens, _one_shot_tokens(dense_model, params, a, 5))
    np.testing.assert_array_equal(
        res[rb].tokens, _one_shot_tokens(dense_model, params, b, 5))
    assert eng.metrics.snapshot()["prefill_tokens_saved"] == 16


def test_prefix_reuse_hot_vs_cold_engines(model_and_params):  # noqa: F811
    """The measured A/B: the same shared-system-prompt workload through a
    prefix-cache engine vs a prefix-cache-OFF engine — byte-identical
    tokens, strictly less prefill and strictly lower page peak with the
    trie on."""
    model, params = model_and_params
    rng = np.random.RandomState(11)
    sysp = rng.randint(1, 97, (16,)).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.randint(1, 97, (2 + i,))])
               .astype(np.int32) for i in range(4)]

    def run(prefix_cache):
        eng = _engine(model, params, slots=4, prefix_cache=prefix_cache)
        rids = [eng.submit(p, max_length=4) for p in prompts]
        res = eng.drain()
        return eng.metrics.snapshot(), [res[r].tokens for r in rids]

    hot, hot_toks = run(True)
    cold, cold_toks = run(False)
    for i, p in enumerate(prompts):
        want = _one_shot_tokens(model, params, p, 4)
        np.testing.assert_array_equal(hot_toks[i], want, err_msg=f"req {i}")
        np.testing.assert_array_equal(cold_toks[i], want, err_msg=f"req {i}")
    assert hot["prefix_hits"] == 3 and hot["prefix_queries"] == 4
    assert hot["prefill_tokens_saved"] == 3 * 16
    assert cold["prefill_tokens_saved"] == 0
    assert hot["pages_per_request_mean"] < cold["pages_per_request_mean"]
    assert hot["page_occupancy_peak"] < cold["page_occupancy_peak"]
    assert hot["prefix_hit_rate"] == pytest.approx(0.75)


def test_paged_sampling_and_callbacks(model_and_params):  # noqa: F811
    """Per-request RNG streams and streaming callbacks behave identically
    under paged storage (seeded reproducibility, in-order callbacks)."""
    model, params = model_and_params
    eng = _engine(model, params, slots=4, gen_cfg=dataclasses.replace(
        GREEDY, decode_strategy="sampling"))
    p = np.asarray([1, 2, 3], np.int32)
    got = []
    a = eng.submit(p, max_length=8, min_length=8, seed=11)
    b = eng.submit(p, max_length=8, min_length=8, seed=11)
    c = eng.submit(p, max_length=5, top_k=1,
                   on_token=lambda i, t, fin: got.append((i, t, fin)))
    res = eng.drain()
    np.testing.assert_array_equal(res[a].tokens, res[b].tokens)
    np.testing.assert_array_equal(
        res[c].tokens, _one_shot_tokens(model, params, p, 5))
    assert [t for _, t, _ in got] == res[c].tokens.tolist()
    assert [fin for _, _, fin in got] == [False] * 4 + [True]
