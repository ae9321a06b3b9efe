"""Paged KV cache + shared-prefix reuse tests (ISSUE 7).

Three layers:

- **PagePool host units**: property-style random-ops simulation against a
  reference mirror (no page leaked, no double-free, refcounts match the
  lanes' chains, copy-on-write never lets a write-target page be shared)
  plus exact small scenarios for trie match / revive / LRU eviction. No
  model, no device arrays.
- **Engine parity**: paged serving must emit byte-identical greedy tokens
  to one-shot ``generate()`` under
  staggered mixed-length load with lane reuse — on the dense path and
  through the paged flash-decode kernel (interpret mode).
- **The paged wins**: prefix reuse measurably cuts prefill tokens and
  page usage (ServingMetrics counters), admission is page-granular (a
  workload fitting the pool as LIVE tokens admits even when it would not
  fit as max-length slots), and a dry pool retires mid-flight requests as
  ``cache_full`` without leaking a single page.

This module keeps COMPACT versions of the engine gates so tier-1 stays
inside the harness budget; the full-width sweeps (8-request stagger,
flash-interpret kernel parity, hot-vs-cold prefix A/B, sampling
behaviors) live in ``test_paged_serving_slow.py`` (marker ``slow``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import (assert_token_parity, one_shot_tokens,
                            sharing_programs)

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.serving import (
    HostPageStore,
    PagedKVCacheManager,
    PagePool,
    ServingEngine,
)

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
    kw.setdefault("page_size", 8)
    return ServingEngine(model, params, **kw)


def _one_shot_tokens(model, params, prompt, max_length, eos=10**6):
    """tests/serving_parity.py reference bound to this suite's GREEDY."""
    return one_shot_tokens(model, params, prompt, max_length,
                           gen_cfg=GREEDY, eos=eos)


# ------------------------------------------------------- PagePool host units

def _check_pool_invariants(pool: PagePool, prompts: dict):
    """Conservation + refcount + copy-on-write invariants against the
    ``prompts`` mirror ({lane: token array} for lanes believed held)."""
    # trash page pinned, never handed out
    assert pool.ref[0] >= 1
    # conservation: every usable page is free, cached, or referenced
    in_use = int((pool.ref[1:] > 0).sum())
    assert in_use + pool.free_pages == pool.usable_pages
    # refcounts == how many lanes carry the page in their allocated chain
    counted = np.zeros(pool.num_pages, np.int64)
    for lane in range(pool.lanes):
        n = int(pool.alloc_counts[lane])
        for i in range(n):
            page = int(pool.tables[lane, i])
            assert page != 0, "allocated chain entry points at trash"
            counted[page] += 1
    np.testing.assert_array_equal(counted[1:], pool.ref[1:])
    # copy-on-write: any page this lane may WRITE (logical index at or
    # past its registerable full-prefix chunks) is exclusively owned
    for lane, toks in prompts.items():
        n_chunks = (len(toks) - 1) // pool.page_size
        for i in range(n_chunks, int(pool.alloc_counts[lane])):
            assert pool.ref[int(pool.tables[lane, i])] == 1, (
                f"write-target page of lane {lane} is shared")


def test_pagepool_random_ops_property():
    """Randomized alloc/register/grow/free churn (with prompt reuse so the
    trie actually shares) never leaks a page, never double-frees, never
    shares a write-target page — checked after EVERY operation."""
    rng = np.random.RandomState(0)
    pool = PagePool(num_pages=24, page_size=4, lanes=6, lane_pages=8)
    held = {}  # lane -> prompt tokens
    # a small prompt zoo => frequent prefix collisions
    zoo = [rng.randint(1, 9, (n,)).astype(np.int32)
           for n in (3, 5, 8, 9, 13, 17, 21)]
    for step in range(400):
        op = rng.randint(3)
        if op == 0 and len(held) < pool.lanes:
            lane = min(set(range(pool.lanes)) - set(held))
            toks = zoo[rng.randint(len(zoo))]
            if rng.randint(2):  # sometimes share, sometimes extend the zoo
                toks = np.concatenate(
                    [toks, rng.randint(1, 9, (rng.randint(1, 4),))]
                ).astype(np.int32)
            shared = pool.alloc(lane, toks)
            if shared is not None:
                assert shared % pool.page_size == 0
                assert shared <= len(toks) - 1  # last token always re-runs
                pool.register_prefix(lane, toks)
                held[lane] = toks
        elif op == 1 and held:
            lane = sorted(held)[rng.randint(len(held))]
            # grow one decode position past the current chain
            pos = int(pool.alloc_counts[lane]) * pool.page_size
            if pos < pool.lane_pages * pool.page_size:
                pool.ensure_page(lane, pos)
        elif op == 2 and held:
            lane = sorted(held)[rng.randint(len(held))]
            pool.free(lane)
            del held[lane]
        _check_pool_invariants(pool, held)
    for lane in sorted(held):
        pool.free(lane)
    _check_pool_invariants(pool, {})
    assert pool.pages_in_use == 0  # everything returned (cached or free)


class _RecordingStore(HostPageStore):
    """HostPageStore that journals puts so the churn below can assert a
    revived payload is EXACTLY what was spilled under that token path."""

    def __init__(self, capacity_bytes):
        super().__init__(capacity_bytes)
        self.journal = {}  # key -> last payload put

    def put(self, key, payload, nbytes):
        ok = super().put(key, payload, nbytes)
        if ok:
            self.journal[key] = payload
        return ok


def _host_pool(num_pages=16, page_size=4, lanes=5, lane_pages=8,
               capacity_bytes=10 * 64):
    """PagePool wired to a recording host store with dummy device
    callbacks: spill hands each page a unique payload token, revive
    journals what came back — no model, no backend, pure host."""
    state = {"serial": 0, "revived": []}
    store = _RecordingStore(capacity_bytes)

    def spill_fn(pages):
        out = []
        for p in pages:
            state["serial"] += 1
            out.append((("payload", p, state["serial"]), 64))
        return out

    def revive_fn(entries):
        state["revived"].extend(entries)

    pool = PagePool(num_pages, page_size, lanes, lane_pages,
                    host_store=store, spill_fn=spill_fn,
                    revive_fn=revive_fn)
    return pool, store, state


def test_pagepool_spill_revive_churn_property():
    """The spill/revive extension of the random-ops churn: a small pool
    + a byte-bounded host tier under alloc/register/grow/free pressure
    with heavy prompt reuse. After EVERY op ``check_invariants()`` must
    hold (conservation, refcounts, trie, host-store byte accounting),
    and every payload ``revive_fn`` receives must be the exact payload
    spilled under that page's token path — the pool can never hand a
    prompt someone else's KV."""
    rng = np.random.RandomState(42)
    pool, store, state = _host_pool()
    held = {}
    zoo = [rng.randint(1, 7, (n,)).astype(np.int32)
           for n in (5, 9, 13, 17, 21, 29)]
    for step in range(500):
        op = rng.randint(3)
        if op == 0 and len(held) < pool.lanes:
            lane = min(set(range(pool.lanes)) - set(held))
            toks = zoo[rng.randint(len(zoo))]
            if rng.randint(2):
                toks = np.concatenate(
                    [toks, rng.randint(1, 7, (rng.randint(1, 4),))]
                ).astype(np.int32)
            state["revived"].clear()
            shared = pool.alloc(lane, toks)
            if shared is not None:
                assert shared % pool.page_size == 0
                assert shared <= len(toks) - 1
                # every revived payload is the one spilled for that path
                for page, payload in state["revived"]:
                    node = pool._node_of_page[page]
                    key = pool._node_key(node)
                    assert store.journal.get(key) == payload, (
                        f"page {page} revived someone else's payload")
                pool.register_prefix(lane, toks)
                held[lane] = toks
        elif op == 1 and held:
            lane = sorted(held)[rng.randint(len(held))]
            pos = int(pool.alloc_counts[lane]) * pool.page_size
            if pos < pool.lane_pages * pool.page_size:
                pool.ensure_page(lane, pos)
        elif op == 2 and held:
            lane = sorted(held)[rng.randint(len(held))]
            pool.free(lane)
            del held[lane]
        pool.check_invariants()
        _check_pool_invariants(pool, held)
    assert store.spilled_pages > 0, "churn never exercised the spill path"
    assert store.revived_pages > 0, "churn never exercised the revive path"
    assert store.evicted_pages > 0, (
        "churn never pressured the host byte budget (capacity too big?)")
    for lane in sorted(held):
        pool.free(lane)
    pool.check_invariants()


def test_pagepool_spill_then_host_revive_exact():
    """Deterministic two-tier lifecycle: a registered prefix parks warm,
    pool pressure SPILLS it to the host store (free_pages unchanged — a
    spilled page is a freed page), and a matching re-alloc revives it as
    shared tokens (prefill skipped) with the journaled payload, drawing
    physical pages like a fresh claim."""
    pool, store, state = _host_pool(num_pages=5, page_size=4, lanes=3,
                                    lane_pages=4)
    a = np.arange(1, 10, dtype=np.int32)  # 2 full chunks + tail = 3 pages
    assert pool.alloc(0, a) == 0
    pool.register_prefix(0, a)
    pool.free(0)
    assert pool.cached_pages == 2 and len(store) == 0  # warm, not spilled
    b = np.arange(20, 33, dtype=np.int32)  # 13 tokens: 4 fresh pages
    assert pool.alloc(1, b) == 0  # drains the stack -> A's subtree spills
    assert len(store) == 2 and store.spilled_pages == 2
    assert pool.cached_pages == 0
    pool.check_invariants()
    pool.free(1)
    # no trie node survives for A, but the HOST match revives both chunks
    state["revived"].clear()
    assert pool.alloc(2, a) == 8
    assert len(state["revived"]) == 2
    assert store.revived_pages == 2
    # inclusive tier: the entries STAY after revival (a later fault that
    # destroys the device copy can revive them again)
    assert len(store) == 2
    for page, payload in state["revived"]:
        key = pool._node_key(pool._node_of_page[page])
        assert store.journal[key] == payload
    pool.check_invariants()
    # revived pages are real trie pages again: a third tenant shares them
    pool.register_prefix(2, a)
    assert pool.free_pages >= 0
    pool.free(2)
    assert pool.cached_pages == 2  # parked warm again, full circle


def test_host_store_byte_budget_rejects_and_evicts():
    """The budget is a hard bound: an entry bigger than the whole budget
    is rejected outright, and capacity pressure drops OLDEST entries
    first (LRU) with exact byte accounting throughout."""
    store = HostPageStore(128)
    assert not store.put(("a",), "huge", 200)  # > budget: rejected
    assert store.put(("a",), "pa", 64) and store.put(("b",), "pb", 64)
    assert store.nbytes == 128 and len(store) == 2
    assert store.get(("b",), ) == "pb"  # refreshes ("b",)'s LRU slot
    assert store.revived_pages == 1 and store.nbytes == 128
    assert store.put(("c",), "pc", 64)  # evicts ("a",) — now the oldest
    assert ("a",) not in store and ("b",) in store and ("c",) in store
    assert store.evicted_pages == 1 and store.nbytes == 128
    assert store.pop(("b",)) == "pb"  # explicit invalidation
    assert store.nbytes == 64 and store.revived_pages == 1
    store.check_invariants()


def test_host_store_payload_bytes_roundtrip():
    """ISSUE 15 satellite: the pickle-free ``to_bytes``/``from_bytes``
    wire format round-trips a spill payload BYTE-EXACTLY — K/V leaves,
    int8 value pages, their fp32 scale leaves, bf16 leaves, and the None
    slots of rank-<4 cache leaves — and corrupt input fails loudly. This
    is the page-ship primitive the cross-replica prefill/decode split
    serializes over the wire (ROADMAP item 2)."""
    import ml_dtypes

    rng = np.random.RandomState(9)
    payload = [
        rng.randn(8, 2, 64).astype(np.float32),          # K page
        None,                                            # cache_index slot
        rng.randint(-128, 128, (8, 2, 64)).astype(np.int8),  # int8 V page
        rng.randn(8, 2, 1).astype(np.float32),           # int8 scale leaf
        rng.randn(4, 2, 8).astype(ml_dtypes.bfloat16),   # bf16 page
    ]
    buf = HostPageStore.payload_to_bytes(payload)
    assert isinstance(buf, bytes) and buf[:4] == b"FXPG"
    back = HostPageStore.payload_from_bytes(buf)
    assert len(back) == len(payload)
    assert back[1] is None
    for want, got in zip(payload, back):
        if want is None:
            continue
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes(), "not byte-exact"
    # the round-trip of the round-trip is stable (canonical form)
    assert HostPageStore.payload_to_bytes(back) == buf
    # corruption fails loudly — always as ValueError, wherever the
    # truncation lands (mid-array, right after the header, or inside a
    # dtype name) — never revives garbage K/V
    with pytest.raises(ValueError):
        HostPageStore.payload_from_bytes(buf[:-5])
    with pytest.raises(ValueError):
        HostPageStore.payload_from_bytes(buf[:8])
    with pytest.raises(ValueError):
        HostPageStore.payload_from_bytes(buf[:12])
    with pytest.raises(ValueError):
        HostPageStore.payload_from_bytes(buf + b"xx")
    with pytest.raises(ValueError):
        HostPageStore.payload_from_bytes(b"NOPE" + buf[4:])
    # a REAL spilled payload (engine path) round-trips too: grab one via
    # the manager's spill_fn on a live paged cache
    from fleetx_tpu.serving import ServingEngine
    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    eng = ServingEngine(
        model, params, slots=1, cache_len=16, prefill_bucket=4,
        page_size=8,
        gen_cfg=GenerationConfig(decode_strategy="greedy",
                                 eos_token_id=10**6, pad_token_id=60,
                                 max_length=2))
    rid = eng.submit(np.arange(1, 10, dtype=np.int32), max_length=2)
    eng.drain()
    (real, nbytes), = eng.cache_manager._spill_pages([1])
    buf = HostPageStore.payload_to_bytes(real)
    back = HostPageStore.payload_from_bytes(buf)
    for want, got in zip(real, back):
        if want is None:
            assert got is None
        else:
            assert got.dtype == want.dtype
            assert got.tobytes() == want.tobytes()
    assert sum(a.nbytes for a in back if a is not None) == nbytes
    del rid


def test_pagepool_share_revive_evict_exact():
    """Deterministic lifecycle: two lanes share a 2-page prefix (refcount
    2), frees park registered pages in the warm cache, a third alloc
    revives them for free, and eviction reclaims LRU subtrees when the
    stack runs dry."""
    pool = PagePool(num_pages=8, page_size=4, lanes=3, lane_pages=4)
    prompt = np.arange(1, 10, dtype=np.int32)  # 9 tokens: 2 full chunks
    assert pool.alloc(0, prompt) == 0  # cold: nothing shared
    pool.register_prefix(0, prompt)
    assert pool.pages_in_use == 3  # 2 full + 1 partial(+first-token) page
    assert pool.alloc(1, prompt) == 8  # 2 chunks * 4 tokens shared
    pool.register_prefix(1, prompt)
    assert pool.pages_in_use == 4  # one fresh tail page, prefix shared
    shared_pages = [int(p) for p in pool.tables[0, :2]]
    assert [int(p) for p in pool.tables[1, :2]] == shared_pages
    assert all(pool.ref[p] == 2 for p in shared_pages)
    pool.free(0)
    assert all(pool.ref[p] == 1 for p in shared_pages)
    pool.free(1)
    # registered pages park warm (reclaimable but content intact)
    assert pool.pages_in_use == 0 and pool.cached_pages == 2
    assert pool.alloc(2, prompt) == 8  # revived from the warm cache
    assert [int(p) for p in pool.tables[2, :2]] == shared_pages
    pool.free(2)
    # drain the stack: eviction must reclaim the cached subtree
    grabbed = [pool._take_page() for _ in range(pool.usable_pages)]
    assert sorted(grabbed) == list(range(1, 8))
    assert pool.cached_pages == 0  # trie emptied by eviction


def test_can_admit_accounts_for_warm_cache_revival():
    """Regression: a trie match whose pages sit in the warm cache REVIVES
    them on alloc — they stop being reclaimable — so can_admit must count
    them against the pool or it green-lights an alloc that then fails
    (the engine pops the request first and would crash mid-admission)."""
    pool = PagePool(num_pages=5, page_size=8, lanes=3, lane_pages=4)
    a = np.arange(1, 10, dtype=np.int32)   # 9 tokens: 1 full chunk + tail
    assert pool.alloc(0, a) == 0
    pool.register_prefix(0, a)
    pool.free(0)                           # chunk parks warm, tail frees
    b = np.arange(20, 37, dtype=np.int32)  # 17 tokens: 3 fresh pages
    assert pool.alloc(1, b) == 0           # drains the free stack
    assert pool.free_pages == 1            # only A's warm page remains
    # re-admitting A needs its warm page revived PLUS one fresh page —
    # two draws from a pool of one
    assert pool.pages_needed(a) == 2
    assert not pool.can_admit(a)
    before = (pool.free_pages, pool.ref.copy())
    assert pool.alloc(2, a) is None        # and alloc agrees, cleanly
    assert pool.free_pages == before[0]
    np.testing.assert_array_equal(pool.ref, before[1])
    pool.free(1)
    assert pool.can_admit(a)
    assert pool.alloc(2, a) == 8           # warm prefix revived for free


def test_full_capacity_prompt_rejected_cleanly(model_and_params):
    """Regression: a prompt of exactly cache_len tokens needs lane_pages+1
    logical pages (the first sampled token's slot) — both manager and
    pool must raise BEFORE committing anything, not corrupt the pool."""
    model, _ = model_and_params
    sized = model.clone(cfg=dataclasses.replace(
        model.cfg, decode_cache_len=16, decode_num_pages=7,
        decode_page_size=8))
    mgr = PagedKVCacheManager(sized, slots=2, cache_len=16, num_pages=7,
                              page_size=8)
    with pytest.raises(ValueError, match="decode room"):
        mgr.alloc(1, np.arange(16, dtype=np.int32))
    assert mgr.free_count == 2 and mgr.pages_in_use == 0
    pool = PagePool(num_pages=6, page_size=4, lanes=2, lane_pages=4)
    before = pool.free_pages
    with pytest.raises(ValueError, match="logical pages"):
        pool.alloc(0, np.arange(1, 18, dtype=np.int32))  # 5 pages > 4
    assert pool.free_pages == before and pool.alloc_counts[0] == 0


def test_pagepool_alloc_failure_commits_nothing():
    pool = PagePool(num_pages=5, page_size=4, lanes=2, lane_pages=4)
    long = np.arange(1, 14, dtype=np.int32)  # needs 4 pages
    assert pool.alloc(0, long) == 0
    before = (pool.free_pages, pool.ref.copy())
    assert pool.alloc(1, long) is None  # 0 free: must not commit anything
    assert pool.free_pages == before[0]
    np.testing.assert_array_equal(pool.ref, before[1])
    pool.free(0)
    with pytest.raises(ValueError, match="double-freed"):
        pool.free(0)


def test_paged_manager_lane_lifecycle(model_and_params):
    model, _ = model_and_params
    sized = model.clone(cfg=dataclasses.replace(
        model.cfg, decode_cache_len=16, decode_num_pages=7,
        decode_page_size=8))
    mgr = PagedKVCacheManager(sized, slots=2, cache_len=16, num_pages=7,
                              page_size=8)
    assert mgr.free_count == 2 and mgr.active_count == 0
    p = np.arange(1, 6, dtype=np.int32)
    s0, sh0 = mgr.alloc(request_id=7, tokens=p)
    s1, sh1 = mgr.alloc(request_id=8, tokens=p)
    assert (s0, s1, sh0, sh1) == (0, 1, 0, 0)  # lowest lane first
    assert mgr.alloc(request_id=9, tokens=p) is None  # lanes full
    assert mgr.occupancy() == 1.0 and mgr.pages_in_use == 2
    mgr.free(s0)
    assert mgr.request_ids == [None, 8]
    assert mgr.alloc(request_id=9, tokens=p)[0] == 0  # lane reused
    mgr.free(0)
    with pytest.raises(ValueError, match="already free"):
        mgr.free(0)


# --------------------------------------------------------- parity contracts

@pytest.mark.slow  # 25.1s baseline (PR 12 tier-1 budget audit): one-shot
def test_paged_staggered_one_shot_parity(model_and_params):
    # byte parity stays tier-1 via test_serving.py's staggered gate,
    # test_chunked_serving's gate + test_serving_recovery's replay parity
    """The acceptance gate, compact: paged serving ==
    one-shot generate(), byte-identical greedy tokens, under mixed prompt
    lengths, staggered admission, and lane reuse (slots=2, 5 requests —
    the 8-request / mixed-decode-length sweep is in the slow sibling).
    Decode lengths are uniform so the one-shot references share compiled
    shapes; lane reuse still happens (5 requests through 2 lanes)."""
    model, params = model_and_params
    rng = np.random.RandomState(7)
    plens = (3, 5, 4, 5, 3)
    prompts = [rng.randint(1, 97, (n,)).astype(np.int32) for n in plens]

    eng = _engine(model, params, slots=2)
    rids = [eng.submit(p, max_length=4) for p in prompts[:3]]
    eng.step()  # requests 3.. arrive mid-flight
    rids += [eng.submit(p, max_length=4) for p in prompts[3:]]
    res = eng.drain()
    for i, p in enumerate(prompts):
        assert_token_parity(res[rids[i]].tokens,
                            _one_shot_tokens(model, params, p, 4),
                            err_msg=f"paged vs one-shot, req {i}")
    assert eng.cache_manager.pages_in_use == 0  # all chains returned
    assert eng.cache_manager.free_count == 2


# ------------------------------------------------------------ the paged wins

def test_prefix_reuse_cuts_prefill_and_pages(model_and_params):
    """N requests sharing a system prompt: the trie must cut prefill work
    and fresh pages, asserted against the no-reuse arithmetic via the
    ServingMetrics counters — tokens byte-identical to one-shot. (The
    measured hot-vs-cold engine A/B is in the slow sibling.)"""
    model, params = model_and_params
    rng = np.random.RandomState(11)
    sysp = rng.randint(1, 97, (16,)).astype(np.int32)
    prompts = [np.concatenate([sysp, rng.randint(1, 97, (2 + i,))])
               .astype(np.int32) for i in range(3)]
    eng = _engine(model, params, slots=3)
    rids = [eng.submit(p, max_length=4) for p in prompts]
    res = eng.drain()
    for i, p in enumerate(prompts):
        assert_token_parity(
            res[rids[i]].tokens, _one_shot_tokens(model, params, p, 4),
            err_msg=f"req {i}")
    snap = eng.metrics.snapshot()
    # 2 follow-ups each reuse the 2 full system-prompt pages (16 tokens)
    assert snap["prefix_hits"] == 2 and snap["prefix_queries"] == 3
    assert snap["prefill_tokens_saved"] == 2 * 16
    assert snap["prefill_tokens_saved_frac"] == pytest.approx(
        32 / sum(len(p) for p in prompts))
    # fresh pages: 3 for the cold request, 1 each for the two hits — vs
    # the no-reuse arithmetic of 3 pages per request (prompt 18-20 + the
    # first token's slot at page_size 8)
    assert snap["pages_per_request_mean"] == pytest.approx(5 / 3)
    assert snap["pages_per_request_mean"] < 3.0
    assert eng.cache_manager.pages_in_use == 0  # drained clean


@pytest.mark.slow  # ~10s (PR 13 tier-1 budget audit): the pages-not-slots
def test_page_granular_admission(model_and_params):
    # admission contract stays tier-1 via test_pool_exhaustion_retires_
    # cache_full (page-gated admission + starvation) and the shared-
    # prefix admission tests; the bench schema test asserts occupancy
    """Acceptance: a workload whose LIVE tokens fit the pool is admitted
    concurrently even though it could never fit as max-length slots (4
    requests x 2 pages = 8 pages vs 4 slots x 56-token worst case)."""
    model, params = model_and_params
    eng = _engine(model, params, slots=4, cache_len=56, num_pages=9,
                  prefill_bucket=8)
    assert eng.cache_manager.usable_pages == 8  # < slots * cache_len / page
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 97, (8,)).astype(np.int32) for _ in range(4)]
    rids = [eng.submit(p, max_length=7) for p in prompts]
    summary = eng.step()
    assert summary["admitted"] == 4  # all four live despite the tiny pool
    res = eng.drain()
    for rid, p in zip(rids, prompts):
        assert_token_parity(res[rid].tokens,
                            _one_shot_tokens(model, params, p, 7))
    assert eng.cache_manager.pages_in_use == 0


def test_pool_exhaustion_retires_cache_full(model_and_params):
    """A pool too small for every request's decode span retires the
    starved request with ``finish_reason="cache_full"`` and its partial
    tokens; neighbors finish normally and no page leaks."""
    model, params = model_and_params
    eng = _engine(model, params, slots=2, num_pages=5, prefill_bucket=4)
    r1 = eng.submit(np.arange(1, 8, dtype=np.int32), max_length=20)
    r2 = eng.submit(np.arange(10, 17, dtype=np.int32), max_length=20)
    res = eng.drain()
    reasons = {res[r].finish_reason for r in (r1, r2)}
    assert "cache_full" in reasons  # somebody was starved...
    assert "max_length" in reasons  # ...and the survivor ran to the end
    starved = r1 if res[r1].finish_reason == "cache_full" else r2
    assert 0 < len(res[starved].tokens) < 20  # partial output kept
    assert eng.cache_manager.pages_in_use == 0
    assert eng.cache_manager.pool.free_pages == 4


def test_prefill_calls_counted_by_whether_they_computed_a_token(
        model_and_params):
    """Every prefill call is counted once, by whether its caller read a
    token (head and sampler ran) or not: a chunked admission is headless
    chunks and one final chunk, an admission in one call one program with
    a token, a replay (here a request admitted with its history, through
    the replay seam) headless. ``snapshot()`` and ``/metrics`` agree."""
    from fleetx_tpu.obs import get_registry

    model, params = model_and_params
    eng = _engine(model, params, slots=2, prefill_chunk=8, prefix_cache=False)
    rng = np.random.RandomState(2)
    chunked, short, moved = (rng.randint(1, 97, (n,)).astype(np.int32)
                             for n in (19, 4, 6))

    def counts():
        snap = eng.metrics.snapshot()
        return (snap["prefill_token_calls"],
                snap["prefill_headless_calls"])

    assert counts() == (0, 0)
    eng.submit(chunked, max_length=2)       # chunks of 8, 8 and 3
    eng.drain()
    assert counts() == (1, 2)
    rid = eng.submit(short, max_length=3)   # under a chunk: one call
    tokens = list(eng.drain()[rid].tokens)
    assert counts() == (2, 2)
    full = eng.submit(moved, max_length=5)
    stream = list(eng.drain()[full].tokens)
    assert counts() == (3, 2)
    rid = eng.submit(moved, max_length=5, history=stream[:2])
    assert list(eng.drain()[rid].tokens) == stream
    assert counts() == (3, 3)               # a replay computes no token
    snap = eng.metrics.snapshot()
    assert sum(counts()) == (snap["prefill_page_writes"]
                             + snap["prefill_row_writes"])
    assert snap["prefill_chunks"] == 3 and len(tokens) == 3
    text = get_registry().prometheus_text()
    lab = f'engine="{eng.metrics.engine_label}"'
    assert f"fleetx_serving_prefill_token_calls_total{{{lab}}} 3" in text
    assert (f"fleetx_serving_prefill_headless_calls_total{{{lab}}} 3"
            in text)
