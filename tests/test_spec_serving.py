"""Speculative decoding for the serving engine (ISSUE 13).

Acceptance gates for ``FLEETX_SERVING_SPEC=1`` (docs/SERVING.md
"Speculative decoding"):

- **Greedy byte parity** — a speculative engine's greedy streams are
  byte-identical to the non-speculative engine (and therefore to the
  one-shot ``generate()`` reference the serving suites already gate on)
  across bf16(f32) + int8 KV, dense + flash-interpret attention, and
  both proposers. Compact gates run tier-1; the full matrix is
  slow-marked.
- **Edge cases** — a draft can never overrun a request's token budget
  (k ≥ remaining), its lane/page capacity (cache-capacity edge — the
  PR 11 chunk-edge precedent), or run past an EOS emitted inside the
  draft.
- **Sampling** — speculative rejection preserves the target
  distribution: degenerate distributions (top_k=1) stay byte-identical
  through the sampling code path, and the spec-on second-token
  histogram over fixed seeds is statistically indistinguishable from
  spec-off (total-variation gate, deterministic by construction).
- **Crash safety** — a fault injected during a verify call rolls back
  the un-verified draft and replay recovery resumes byte-identically
  with speculation still enabled (the chaos contract
  ``tools/chaos_check.py serving_spec`` demonstrates end-to-end).
- **Proposer protocol units** — n-gram suffix matching and the
  draft-model lane lifecycle (catch-up, rewind, retire, reset) hold
  without an engine.
"""

import collections
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from serving_parity import (assert_token_parity, one_shot_tokens,
                            sharing_programs)

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.serving import DraftModelProposer, NgramProposer, ServingEngine

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
PROMPT_LENS = (3, 5, 4, 7)
MAX_NEW = 8


@pytest.fixture(scope="module")
def model_and_params():
    model = GPTForPretraining(CFG)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return model, params


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(7)
    return [rng.randint(1, 97, (n,)).astype(np.int32) for n in PROMPT_LENS]


@sharing_programs
def _engine(model, params, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("cache_len", 32)
    kw.setdefault("gen_cfg", GREEDY)
    kw.setdefault("prefill_bucket", 4)
    kw.setdefault("page_size", 8)
    return ServingEngine(model, params, **kw)


def _serve(model, params, prompts, max_length=MAX_NEW, submit_kw=None,
           **kw):
    eng = _engine(model, params, **kw)
    rids = [eng.submit(p, max_length=max_length, **(submit_kw or {}))
            for p in prompts]
    res = eng.drain()
    return eng, [np.asarray(res[r].tokens) for r in rids]


# ------------------------------------------------- tier-1 byte-parity gates

def test_spec_greedy_byte_parity(model_and_params, prompts):
    """THE gate: speculative greedy streams are byte-identical to the
    non-speculative engine, and the engine actually speculated (drafts
    proposed, some accepted, spec metrics live)."""
    model, params = model_and_params
    _, base = _serve(model, params, prompts)
    eng, spec = _serve(model, params, prompts, spec=True, spec_k=4)
    for i, (a, b) in enumerate(zip(base, spec)):
        assert_token_parity(b, a, err_msg=f"spec req {i}")
    snap = eng.metrics.snapshot()
    assert snap["spec_proposed_tokens"] > 0
    assert snap["spec_tokens_per_tick_mean"] is not None
    eng.cache_manager.pool.check_invariants()


@pytest.mark.slow  # ~13s; redundant composition — spec==non-spec is the
def test_spec_matches_one_shot_generate(model_and_params, prompts):
    # tier-1 gate above, and non-spec==one-shot is tier-1 across the
    # whole existing serving suite
    """Transitivity made explicit: the speculative engine reproduces the
    per-request one-shot ``generate()`` streams byte-exactly (the same
    reference every serving suite gates on)."""
    model, params = model_and_params
    _, spec = _serve(model, params, prompts, spec=True,
                     spec_k=4)
    for i, (p, got) in enumerate(zip(prompts, spec)):
        want = one_shot_tokens(model, params, p, MAX_NEW, gen_cfg=GREEDY)
        assert_token_parity(got, want, err_msg=f"spec vs one-shot req {i}")


def test_spec_off_is_default_and_inert(model_and_params, prompts):
    """``FLEETX_SERVING_SPEC`` defaults off: a default engine has no
    proposer/verify machinery constructed at all — the existing serving
    suites run exactly the pre-spec engine."""
    model, params = model_and_params
    eng = _engine(model, params)
    assert eng.spec is False and eng._proposer is None
    assert not hasattr(eng, "_verify_jit")


# ------------------------------------------------------------- edge cases

@pytest.mark.slow  # 5-6s (PR 19 tier-1 budget audit): the k-exceeds-
# budget clamp stays tier-1 via test_spec_near_dry_pool_matches_plain
# (budget determinism when the pool is nearly dry) and the paged greedy
# parity gate; the eos-inside-draft edge keeps its own tier-1 test below
def test_spec_draft_clamped_to_budget(model_and_params, prompts):
    """k ≥ remaining budget: 2-token requests under k=6 emit exactly 2
    tokens, byte-unchanged."""
    model, params = model_and_params
    _, base = _serve(model, params, prompts[:3], max_length=2)
    _, spec = _serve(model, params, prompts[:3], max_length=2,
                     spec=True, spec_k=6)
    for a, b in zip(base, spec):
        assert len(b) == 2
        assert_token_parity(b, a, err_msg="budget clamp")


def test_spec_eos_inside_draft_window(model_and_params, prompts):
    """EOS-inside-draft: emission stops exactly where the sequential
    engine stops (finish_reason included)."""
    model, params = model_and_params
    # pick greedy's own 3rd token as EOS so it fires INSIDE a
    # 6-token draft window; stream + finish_reason must match non-spec
    probe = one_shot_tokens(model, params, prompts[0], MAX_NEW,
                            gen_cfg=GREEDY)
    eos = int(probe[2])

    def run(spec):
        eng = _engine(model, params, spec=spec, spec_k=6)
        rid = eng.submit(prompts[0], max_length=MAX_NEW, eos_token_id=eos)
        return eng.drain()[rid]

    a, b = run(False), run(True)
    assert a.finish_reason == b.finish_reason == "eos"
    assert_token_parity(b.tokens, a.tokens, err_msg="eos-in-draft")
    assert int(b.tokens[-1]) == eos and eos not in b.tokens[:-1]


@pytest.mark.slow  # 15.6s baseline (PR 14 tier-1 budget audit): the
def test_spec_cache_capacity_edge(model_and_params):
    # capacity-clamp contract stays tier-1 via
    # test_spec_near_dry_pool_matches_plain (cache_full determinism
    # under a dry pool) + the spec greedy parity gates
    """The ISSUE small-fix regression (mirroring the PR 11 chunk-edge
    fix): a request decoding right up to cache capacity under a large k
    must neither overrun its lane/pages mid-verify nor change a byte —
    it retires exactly where the plain engine does."""
    model, params = model_and_params
    prompt = np.arange(1, 17, dtype=np.int32)  # 16 of cache_len 24

    def run(spec):
        eng = _engine(model, params, slots=1, cache_len=24, spec=spec,
                      spec_k=8)
        rid = eng.submit(prompt, max_length=50)  # clamps to 8
        res = eng.drain()[rid]
        eng.cache_manager.pool.check_invariants()
        return res

    a, b = run(False), run(True)
    assert len(a.tokens) == len(b.tokens) == 8
    assert_token_parity(b.tokens, a.tokens, err_msg="capacity edge")
    assert a.finish_reason == b.finish_reason


def test_spec_near_dry_pool_matches_plain(model_and_params):
    """Byte parity under POOL PRESSURE: with a pool sized so the plain
    workload only just fits, the speculative engine must make the exact
    same admission/cache_full decisions — pending-token pages allocate
    first (plain order) and rejected-draft pages return to the pool the
    same tick (trim), so a lane's transient draft window can never
    starve a neighbor."""
    model, params = model_and_params
    prompts = [np.arange(1, 8, dtype=np.int32),
               np.arange(10, 17, dtype=np.int32)]

    def run(spec):
        # 8 usable pages of 8 tokens = exactly 2 lanes x (7 prompt + 20
        # decode = 27 tokens -> 4 pages); zero slack for draft windows
        eng = _engine(model, params, slots=2, cache_len=32,
                      num_pages=9, prefix_cache=False, spec=spec,
                      spec_k=4)
        rids = [eng.submit(p, max_length=20) for p in prompts]
        res = eng.drain()
        eng.cache_manager.pool.check_invariants()
        return [res[r] for r in rids]

    base, spec = run(False), run(True)
    for i, (a, b) in enumerate(zip(base, spec)):
        assert a.finish_reason == b.finish_reason, (
            i, a.finish_reason, b.finish_reason)
        assert_token_parity(b.tokens, a.tokens,
                            err_msg=f"near-dry pool req {i}")


def test_spec_proposer_kwarg_implies_spec(model_and_params):
    """An explicit ``spec_proposer`` turns speculation on (the kwarg
    wins over the env); pairing it with ``spec=False`` is a config
    contradiction that must raise, not be silently ignored."""
    model, params = model_and_params
    eng = _engine(model, params, spec_proposer=NgramProposer())
    assert eng.spec is True and eng._proposer is not None
    with pytest.raises(ValueError, match="spec_proposer"):
        _engine(model, params, spec=False, spec_proposer=NgramProposer())


def test_spec_acceptance_on_repetitive_prompt(model_and_params):
    """Acceptance-rate sanity: on a motif-repeating prompt with a long
    EOS-free decode, the n-gram proposer must accept far more than
    nothing — the whole point of prompt-lookup drafting."""
    model, params = model_and_params
    motif = np.asarray([11, 23, 5, 42], np.int32)
    prompt = np.tile(motif, 3)
    eng = _engine(model, params, slots=1, spec=True, spec_k=4)
    rid = eng.submit(prompt, max_length=16)
    res = eng.drain()[rid]
    assert len(res.tokens) == 16
    snap = eng.metrics.snapshot()
    assert snap["spec_accepted_tokens"] > 0, snap
    assert snap["spec_tokens_per_tick_mean"] > 1, snap
    # parity still holds on this shape, of course
    assert_token_parity(
        res.tokens, one_shot_tokens(model, params, prompt, 16,
                                    gen_cfg=GREEDY),
        err_msg="repetitive prompt")


# ------------------------------------------------------------ crash safety

@pytest.mark.chaos
def test_spec_verify_fault_rolls_back_and_recovers(model_and_params,
                                                   prompts):
    """A fault during the verify device call: transactional rollback
    drops the un-verified draft (per-request spec counters included),
    replay recovery resumes byte-identically, speculation stays on."""
    model, params = model_and_params
    _, clean = _serve(model, params, prompts, spec=True,
                      spec_k=4)
    faults.configure(tick_raise="1")
    try:
        eng, faulty = _serve(model, params, prompts, spec=True,
                             spec_k=4)
    finally:
        faults.reset()
    assert eng.metrics.engine_recoveries == 1, eng.metrics.snapshot()
    for i, (a, b) in enumerate(zip(clean, faulty)):
        assert_token_parity(b, a, err_msg=f"post-recovery req {i}")
    eng.cache_manager.pool.check_invariants()
    snap = eng.metrics.snapshot()
    assert snap["spec_proposed_tokens"] > 0  # still speculating after


# ------------------------------------------------------- proposer units

def test_ngram_proposer_matching():
    """Prompt-lookup mechanics: longest trailing n-gram wins, the MOST
    RECENT earlier occurrence is used, proposals clip to the cap, and a
    history with no recurrence proposes nothing."""
    p = NgramProposer(max_n=3, min_n=1)

    def match(hist, cap):
        return p._match(np.asarray(hist, np.int64), cap).tolist()

    # trailing [1, 2] recurred at position 0 -> propose what followed: 3, 4
    assert match([1, 2, 3, 4, 1, 2], 2) == [3, 4]
    assert match([1, 2, 3, 4, 1, 2], 1) == [3]  # cap clips
    # most recent occurrence wins: trailing [9] last recurred before the 7
    assert match([9, 5, 9, 7, 9], 2) == [7, 9]
    # no recurrence at any n -> empty
    assert match([1, 2, 3, 4, 5], 4) == []
    # proposals come only for lanes with a match and a positive cap
    out = p.propose({0: (np.asarray([1, 2, 1]), 2),
                     1: (np.asarray([1, 2, 3]), 2),
                     2: (np.asarray([1, 2, 1]), 0)}, k=2)
    assert set(out) == {0} and out[0].tolist() == [2, 1]
    with pytest.raises(ValueError, match="min_n"):
        NgramProposer(max_n=2, min_n=3)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
def test_scatter_gather_slot_roundtrip(model_and_params, kv_dtype):
    """The draft model's private slot-layout cache (the one contiguous
    cache left in serving/): ``scatter_slot`` writes a one-row tree into
    lane ``s`` and ``_gather_slot`` reads it back, K/V leaves and (int8)
    their scale leaves alike; every other lane's rows and the
    ``cache_index`` scalars stay as they were. The draft-model suites
    that reach it end to end are slow."""
    from fleetx_tpu.models.gpt.generation import init_decode_cache
    from fleetx_tpu.serving.cache_manager import KV_LEAF_RANK
    from fleetx_tpu.serving.spec import _gather_slot, scatter_slot

    model, _ = model_and_params
    sized = model.clone(cfg=dataclasses.replace(
        model.cfg, decode_cache_len=16, decode_kv_dtype=kv_dtype))

    def filled(lanes, seed):
        leaves, tree = jax.tree.flatten(init_decode_cache(sized, lanes))
        rng = np.random.RandomState(seed)
        return jax.tree.unflatten(tree, [
            jnp.asarray(rng.randint(-100, 100, x.shape), x.dtype)
            for x in leaves])

    big, small = filled(3, 0), filled(1, 1)
    kinds = {str(x.dtype) for x in jax.tree.leaves(big)}
    assert ("int8" in kinds) == (kv_dtype == "int8"), kinds
    lane = jnp.asarray(1, jnp.int32)  # traced in the proposer's jit
    out = jax.jit(scatter_slot)(big, small, lane)
    back = jax.jit(_gather_slot)(out, lane)
    for o, b, s, r in zip(*map(jax.tree.leaves, (out, big, small, back))):
        if b.ndim < KV_LEAF_RANK:  # cache_index scalars: untouched
            np.testing.assert_array_equal(o, b)
            np.testing.assert_array_equal(r, b)
            continue
        np.testing.assert_array_equal(r, s)
        ax = b.ndim - KV_LEAF_RANK
        for other in (0, 2):
            np.testing.assert_array_equal(
                np.take(np.asarray(o), other, axis=ax),
                np.take(np.asarray(b), other, axis=ax))


@pytest.mark.slow  # 7.2s baseline (PR 14 tier-1 budget audit): the
def test_draft_model_proposer_lane_lifecycle(model_and_params):
    # self-draft proposer's end-to-end contract stays covered by the
    # slow matrix (ngram+self-draft parity); the n-gram
    # proposer units above remain tier-1
    """The draft proposer's lane protocol without an engine: catch-up
    prefill on first propose, drafts equal the model's own greedy
    continuation (self-draft -> perfect prediction), observe() rewinds
    past rejected tails, full acceptance leaves one catch-up token, and
    retire/reset zero the lane."""
    model, params = model_and_params
    prop = DraftModelProposer(model, params, prefill_bucket=4)
    prop.bind(slots=2, cache_len=32)
    hist = np.asarray([3, 1, 4, 1, 5], np.int64)
    out = prop.propose({0: (hist, 3)}, k=3)
    # self-draft == the model's own greedy continuation of hist
    want = one_shot_tokens(model, params, hist.astype(np.int32), 3,
                           gen_cfg=GREEDY)
    assert out[0].tolist() == want.tolist()
    assert prop.lengths[0] == len(hist) - 1  # KV for all but the feed token
    # verification accepted 1 of the 3 (plus correction): rewind to +1
    prop.observe(0, emitted=1)
    assert prop.lengths[0] == len(hist)
    # full acceptance: k written, emitted k+1 -> advance caps at k and
    # the next propose catch-up writes the missing token
    hist2 = np.concatenate([hist, [int(want[0]), 7]])
    out = prop.propose({0: (hist2, 3)}, k=3)
    assert len(out[0]) == 3
    prop.observe(0, emitted=4)
    assert prop.lengths[0] == len(hist2) - 1 + 3  # clamped to written k
    prop.on_retire(0)
    assert prop.lengths[0] == 0
    prop.reset()
    assert not prop._written and (prop.lengths == 0).all()


def test_spec_draft_env_resolution(model_and_params, monkeypatch):
    """``FLEETX_SERVING_SPEC_DRAFT`` resolves the proposer: unset ->
    n-gram, ``self`` -> a self-draft model, junk -> a clear error
    (construction only — the self-draft's acceptance-1.0 serving run is
    the slow matrix's job; its drafting math is the unit test above)."""
    model, params = model_and_params
    eng = _engine(model, params, spec=True)
    assert eng._proposer.name == "ngram"
    monkeypatch.setenv("FLEETX_SERVING_SPEC_DRAFT", "self")
    eng = _engine(model, params, spec=True)
    assert eng._proposer.name == "draft"
    monkeypatch.setenv("FLEETX_SERVING_SPEC_DRAFT", "nope")
    with pytest.raises(ValueError, match="SPEC_DRAFT"):
        _engine(model, params, spec=True)
    with pytest.raises(ValueError, match="spec_k"):
        _engine(model, params, spec=True, spec_k=0)


@pytest.mark.slow  # ~9s; the self-draft end-to-end serving contract —
def test_spec_self_draft_acceptance_one(model_and_params, prompts,
                                        monkeypatch):
    # its drafting math stays tier-1 via the lane-lifecycle unit, and
    # greedy parity via the ngram tier-1 gates
    """Self-drafting (draft model == serving model) accepts every draft
    by construction: acceptance rate 1.0 and one-shot byte parity."""
    model, params = model_and_params
    monkeypatch.setenv("FLEETX_SERVING_SPEC_DRAFT", "self")
    eng, toks = _serve(model, params, prompts[:2], spec=True,
                       spec_k=3)
    assert eng._proposer.name == "draft"
    snap = eng.metrics.snapshot()
    assert snap["spec_acceptance_rate"] == 1.0, snap
    for i, (p, got) in enumerate(zip(prompts, toks)):
        want = one_shot_tokens(model, params, p, MAX_NEW, gen_cfg=GREEDY)
        assert_token_parity(got, want, err_msg=f"self-draft req {i}")


# ------------------------------------------------------------ sampling path

@pytest.mark.slow  # 21.7s baseline (PR 14 tier-1 budget audit): the
def test_spec_sampling_topk1_byte_parity(model_and_params, prompts):
    # sampling-rejection path stays gated by the slow-tier fixed-seed
    # total-variation distribution test; greedy byte parity (the
    # deterministic contract) stays tier-1 via test_spec_greedy_byte_parity
    """top_k=1 sampling is a degenerate distribution: the speculative
    REJECTION path must reproduce it byte-exactly (accept prob 1 on the
    matching draft, residual never sampled) — gated through the shared
    parity harness like every other serving mode."""
    model, params = model_and_params
    kw = dict(submit_kw=dict(decode_strategy="sampling", top_k=1))
    _, base = _serve(model, params, prompts[:3], **kw)
    _, spec = _serve(model, params, prompts[:3], spec=True, spec_k=4, **kw)
    for i, (a, b) in enumerate(zip(base, spec)):
        assert_token_parity(b, a, err_msg=f"top_k=1 sampling req {i}")


@pytest.mark.slow  # ~35s: 2×96 three-token sampling requests; the
def test_spec_sampling_distribution_preserved(model_and_params):
    # sampling-path mechanics stay tier-1 via the top_k=1 byte gate
    """Distribution preservation, measured: over 96 fixed seeds the
    spec-on second-token histogram (top_k=4 restricts the support) must
    match spec-off within a total-variation budget calibrated above the
    same-distribution sampling noise. Deterministic — fixed seeds, no
    statistical flake."""
    model, params = model_and_params
    p = np.asarray([5, 9, 2], np.int32)
    cfg = GenerationConfig(decode_strategy="sampling", eos_token_id=10**6,
                           pad_token_id=96, temperature=1.0, top_k=4,
                           top_p=1.0)

    def second_tokens(spec):
        eng = _engine(model, params, slots=8, cache_len=16, gen_cfg=cfg,
                      spec=spec, spec_k=3)
        rids = [eng.submit(p, max_length=3, seed=1000 + i)
                for i in range(96)]
        res = eng.drain()
        return collections.Counter(int(res[r].tokens[1]) for r in rids)

    off, on = second_tokens(False), second_tokens(True)
    assert set(on) <= set(off) | set(on)  # same (top_k-restricted) support
    tv = 0.5 * sum(abs(off.get(t, 0) - on.get(t, 0))
                   for t in set(off) | set(on)) / 96
    assert tv < 0.25, (tv, off.most_common(5), on.most_common(5))


# ------------------------------------------------- slow: the parity matrix

@pytest.mark.slow  # full storage × precision × attention × proposer
def test_spec_parity_matrix(model_and_params, prompts, monkeypatch):
    # matrix; the compact bf16 gates above stay tier-1
    """Greedy parity across f32+int8-KV × dense+flash-
    interpret × ngram+self-draft: int8 configs must match THEIR OWN
    non-speculative int8 engine byte-exactly (speculation is a
    scheduling change — the quantization noise is deterministic and
    identical), flash configs their flash baselines."""
    model, params = model_and_params
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    flash_model = GPTForPretraining(
        dataclasses.replace(CFG, use_flash_attention=True))
    for use_flash in (False, True):
        m = flash_model if use_flash else model
        for kv in (None, "int8"):
            kw = dict(kv_dtype=kv) if kv else {}
            _, base = _serve(m, params, prompts, **kw)
            for proposer in ("ngram", "self"):
                if proposer == "self":
                    monkeypatch.setenv("FLEETX_SERVING_SPEC_DRAFT", "self")
                else:
                    monkeypatch.delenv("FLEETX_SERVING_SPEC_DRAFT",
                                       raising=False)
                _, spec = _serve(m, params, prompts, spec=True, spec_k=4,
                                 **kw)
                for i, (a, b) in enumerate(zip(base, spec)):
                    assert_token_parity(
                        b, a,
                        err_msg=f"flash={use_flash} kv={kv} "
                                f"proposer={proposer} req {i}")
