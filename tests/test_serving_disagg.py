"""Phase-disaggregated serving tests (ISSUE 16).

Three layers, cheapest first:

- **Wire format** (pure numpy): the crc32-trailed v2 payload encoding
  round-trips arbitrary leaf shapes/dtypes byte-exactly (bf16 and
  int8-values + fp32-scales included), any truncation or bit corruption
  raises ValueError (never revives garbage K/V), and pre-checksum v1
  blobs are rejected by version with an explicit error.
- **Stores** (pure host): ``DiskPageStore`` content addressing across
  instances sharing one directory (the cross-replica property), its
  byte-bounded mtime-LRU eviction, atomic writes, loud corruption; the
  ``TieredPageStore`` host-first read with disk-hit promotion.
- **Engine + router**: prefill-role park/export, decode-role
  ``submit(kv_payloads=...)`` revival — byte-identical to colocated on
  fp32 AND int8 KV — the phase-aware router end to end, every fallback
  rung (export fault, corrupt blob, dead prefill replica), and
  ``recover()`` on a decode replica holding shipped-admitted requests.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import sharing_programs

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.serving import (
    DiskPageStore,
    HostPageStore,
    ServingEngine,
    ServingRouter,
    TieredPageStore,
)

CFG = GPTConfig(
    vocab_size=61,
    hidden_size=32,
    num_layers=1,
    num_attention_heads=2,
    ffn_hidden_size=64,
    max_position_embeddings=64,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype=jnp.float32,
    use_flash_attention=False,
)
GREEDY = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                          pad_token_id=60, max_length=8)
PROMPTS = [np.asarray([1, 2, 3], np.int32),
           np.asarray([4, 5, 6, 7, 8], np.int32),
           np.asarray([9, 10], np.int32),
           np.asarray([11, 12, 13, 14, 15, 16, 17, 18, 19], np.int32)]


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
    kw.setdefault("prefill_chunk", 4)
    return ServingEngine(model, params, **kw)


def _drain_colocated(eng, prompts=PROMPTS, max_length=8):
    rids = [eng.submit(p, max_length=max_length) for p in prompts]
    res = eng.drain()
    return [list(res[r].tokens) for r in rids]


# ------------------------------------------------------------ wire format

def _random_payload(rng):
    """One spill payload shaped like real cache leaves: random ndim/
    shape/dtype per leaf, with the int8+scales pairing and None holes
    the quantized cache produces."""
    payload = []
    for _ in range(rng.randint(1, 5)):
        kind = rng.randint(0, 5)
        if kind == 0:
            payload.append(None)
            continue
        shape = tuple(int(s) for s in
                      rng.randint(1, 5, size=rng.randint(1, 5)))
        if kind == 1:
            payload.append(rng.randn(*shape).astype(np.float32))
        elif kind == 2:
            payload.append(
                rng.randn(*shape).astype(jnp.bfloat16.dtype))
        else:
            payload.append(
                rng.randint(-128, 128, size=shape).astype(np.int8))
            payload.append(rng.rand(*shape).astype(np.float32))  # scales
    return payload


def test_wire_roundtrip_fuzz_truncation_corruption():
    """Property test over 25 random payloads: byte-exact round-trip
    (dtype, shape, values), every truncation point raises, and a bit
    flip anywhere in the blob raises — the crc makes silent corruption
    structurally impossible."""
    rng = np.random.RandomState(0)
    for _ in range(25):
        payload = _random_payload(rng)
        blob = HostPageStore.payload_to_bytes(payload)
        back = HostPageStore.payload_from_bytes(blob)
        assert len(back) == len(payload)
        for a, b in zip(payload, back):
            if a is None:
                assert b is None
                continue
            assert b.dtype == np.asarray(a).dtype
            assert b.shape == np.asarray(a).shape
            assert np.asarray(a).tobytes() == b.tobytes()
        # truncation at a spread of cut points (incl. mid-header,
        # mid-entry, inside the crc trailer) must raise, never return
        for cut in {0, 3, 7, len(blob) // 2, len(blob) - 1}:
            with pytest.raises(ValueError):
                HostPageStore.payload_from_bytes(blob[:cut])
        # single-byte corruption anywhere: the crc check catches body
        # flips, the magic/version checks catch header flips
        for pos in rng.randint(0, len(blob), size=6):
            bad = bytearray(blob)
            bad[pos] ^= 0xFF
            with pytest.raises(ValueError):
                HostPageStore.payload_from_bytes(bytes(bad))


def test_wire_v1_rejected_by_version():
    """A pre-checksum v1 blob (version word = 1, no trailer) is refused
    with an error that names the version — not a crc false-positive and
    never a silent parse."""
    import struct

    blob = HostPageStore.payload_to_bytes([np.arange(4, dtype=np.float32)])
    v1 = bytearray(blob[:-4])             # strip the crc trailer
    v1[4:6] = struct.pack("<H", 1)        # rewrite the version word
    with pytest.raises(ValueError, match="version 1"):
        HostPageStore.payload_from_bytes(bytes(v1))
    with pytest.raises(ValueError, match="magic"):
        HostPageStore.payload_from_bytes(b"NOPE" + bytes(v1[4:]))


# ------------------------------------------------------------ disk store

def _payload(rng, n=2):
    return [rng.randn(2, 4).astype(np.float32) for _ in range(n)]


def test_disk_store_roundtrip_and_cross_instance(tmp_path):
    """Content addressing is the cross-replica property: an entry put
    by one store instance is readable by a FRESH instance pointed at
    the same directory, byte-exactly."""
    rng = np.random.RandomState(1)
    key = ((1, 2, 3), (4, 5, 6))
    payload = _payload(rng)
    a = DiskPageStore(str(tmp_path), 1 << 20)
    assert a.put(key, payload, 0)
    assert key in a
    b = DiskPageStore(str(tmp_path), 1 << 20)   # fresh "replica"
    got = b.get(key)
    assert all(np.array_equal(x, y) for x, y in zip(payload, got))
    assert b.revived_pages == 1 and b.hits == 1
    assert ((99, 99),) not in b
    assert b.misses == 1
    b.check_invariants()
    a.check_invariants()


def test_disk_store_lru_eviction_and_budget(tmp_path):
    """The byte budget holds by eviction of the LRU (mtime-ordered)
    files — and a just-written entry is never its own victim."""
    rng = np.random.RandomState(2)
    payload = _payload(rng)
    one = len(HostPageStore.payload_to_bytes(payload))
    store = DiskPageStore(str(tmp_path), int(one * 2.5))  # fits 2 files
    keys = [((i, i + 1),) for i in range(4)]
    for k in keys:
        assert store.put(k, payload, 0)
    assert store.evicted_pages == 2
    assert keys[-1] in store  # the newest write survived its own put
    assert store.nbytes <= store.capacity_bytes
    store.check_invariants()
    # an oversized entry is refused, not thrashed in
    tiny = DiskPageStore(str(tmp_path / "tiny"), 8)
    assert not tiny.put(keys[0], payload, 0)
    assert keys[0] not in tiny


def test_disk_store_corruption_and_atomicity(tmp_path):
    """A file corrupted at rest raises ValueError at get (crc), writes
    leave no temp litter behind, and the corrupt file SELF-HEALS: get
    unlinks it so the entry reads as absent afterwards instead of
    poisoning every later prompt that matches the prefix."""
    rng = np.random.RandomState(3)
    store = DiskPageStore(str(tmp_path), 1 << 20)
    key = ((7, 8),)
    store.put(key, _payload(rng), 0)
    files = [f for f in os.listdir(tmp_path) if f.endswith(".fxpg")]
    assert len(files) == 1, "atomic write left temp litter"
    path = os.path.join(tmp_path, files[0])
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(ValueError, match="crc32|corrupt"):
        store.get(key)
    assert not os.path.exists(path), "corrupt file not unlinked by get"
    assert key not in store  # self-healed: absent, not poisoned
    assert store.hits == 0 and store.revived_pages == 0
    # pop on a corrupt entry removes it too, surfacing plain KeyError
    store.put(key, _payload(rng), 0)
    raw = bytearray(open(path, "rb").read())
    raw[-1] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(KeyError):
        store.pop(key)
    assert not os.path.exists(path)


def test_disk_store_put_degrades_on_io_error(tmp_path):
    """A full or read-only shared cache dir must degrade the disk tier
    to nothing-stored (put returns False), never fault the caller:
    TieredPageStore.put runs inside PagePool eviction, where an escaped
    OSError would crash the serving tick into engine recovery."""
    rng = np.random.RandomState(5)
    store = DiskPageStore(str(tmp_path / "cache"), 1 << 20)
    payload = _payload(rng)
    os.rmdir(store.cache_dir)
    open(store.cache_dir, "w").close()  # any write under it now fails
    assert store.put(((1, 2),), payload, 0) is False
    assert store.spilled_pages == 0
    assert ((1, 2),) not in store
    store.check_invariants()
    # the tiered store keeps the page in DRAM when disk I/O fails
    tiered = TieredPageStore(HostPageStore(1 << 20), store)
    assert tiered.put(((1, 2),), payload, 64) is True
    got = tiered.get(((1, 2),))
    assert all(np.array_equal(x, y) for x, y in zip(payload, got))


def _disk_pool(store, num_pages=5, page_size=4, lanes=3, lane_pages=4):
    """PagePool spilling real wire-format payloads into a DiskPageStore,
    with a revive journal (mirrors test_paged_serving._host_pool but
    over the disk tier, so corruption/race behavior is end to end)."""
    from fleetx_tpu.serving import PagePool

    state = {"serial": 0, "revived": []}

    def spill_fn(pages):
        out = []
        for _ in pages:
            state["serial"] += 1
            arr = np.full((2, 3), float(state["serial"]), np.float32)
            out.append(([arr], arr.nbytes))
        return out

    def revive_fn(entries):
        state["revived"].extend(entries)

    pool = PagePool(num_pages, page_size, lanes, lane_pages,
                    host_store=store, spill_fn=spill_fn,
                    revive_fn=revive_fn)
    return pool, state


def _spill_prompt_to_disk(pool):
    """Drive the deterministic spill lifecycle: register prompt A, park
    it warm, pressure the pool so its two chunks spill to the disk
    store, and return (A, key of chunk 1, key of chunk 2)."""
    a = np.arange(1, 10, dtype=np.int32)     # 2 full chunks + tail
    assert pool.alloc(0, a) == 0
    pool.register_prefix(0, a)
    pool.free(0)
    b = np.arange(20, 33, dtype=np.int32)    # 4 fresh pages: spills A
    assert pool.alloc(1, b) == 0
    pool.free(1)
    chunks = pool._chunks(a)
    return a, (chunks[0],), (chunks[0], chunks[1])


def test_alloc_corrupt_disk_entry_reads_as_miss(tmp_path):
    """REGRESSION: a corrupt disk file under a matched prefix must NOT
    escape PagePool.alloc after trie refs are committed (that crashed
    the tick into engine recovery, and the un-unlinked file poison-
    quarantined every prompt sharing the prefix). The key — and every
    deeper key, unattendable without it — reads as a miss: alloc
    succeeds with the surviving shallower revive plus fresh prefill,
    and the bad file self-heals."""
    store = DiskPageStore(str(tmp_path), 1 << 20)
    pool, state = _disk_pool(store)
    a, k1, k2 = _spill_prompt_to_disk(pool)
    assert k1 in store and k2 in store
    raw = bytearray(open(store._path(k2), "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(store._path(k2), "wb").write(bytes(raw))
    state["revived"].clear()
    shared = pool.alloc(2, a)            # must not raise
    assert shared == 4, "chunk-1 revive should survive chunk-2 corruption"
    assert len(state["revived"]) == 1
    assert not os.path.exists(store._path(k2)), "corrupt file not healed"
    pool.check_invariants()
    # the lane is fully usable: the missed chunk re-prefills + registers
    pool.register_prefix(2, a)
    pool.free(2)
    pool.check_invariants()


def test_alloc_sibling_evicted_disk_entry_reads_as_miss(tmp_path):
    """REGRESSION: the shared-dir TOCTOU — a sibling replica evicts the
    file between _match_host's membership check and the read (KeyError
    from get) — degrades to a plain full-fresh-prefill alloc, not an
    exception out of the tick."""

    class _RacingStore(DiskPageStore):
        """Evicts ``vanish`` just before serving it — the sibling race,
        made deterministic."""
        vanish = None

        def get(self, key):
            if key == self.vanish:
                os.remove(self._path(key))
            return super().get(key)

    store = _RacingStore(str(tmp_path), 1 << 20)
    pool, state = _disk_pool(store)
    a, k1, k2 = _spill_prompt_to_disk(pool)
    store.vanish = k1                    # the FIRST matched key vanishes
    state["revived"].clear()
    shared = pool.alloc(2, a)            # must not raise
    assert shared == 0 and not state["revived"]  # full fresh prefill
    pool.check_invariants()
    pool.register_prefix(2, a)
    pool.free(2)
    pool.check_invariants()


def test_tiered_store_promotion(tmp_path):
    """TieredPageStore: write-through put, host-first get, and a
    disk-tier hit PROMOTES the entry back into host DRAM."""
    rng = np.random.RandomState(4)
    host = HostPageStore(1 << 20)
    disk = DiskPageStore(str(tmp_path), 1 << 20)
    tiered = TieredPageStore(host, disk)
    key = ((0, 1),)
    payload = _payload(rng)
    tiered.put(key, payload, 1024)
    assert key in host and key in disk          # write-through
    host.pop(key)                               # simulate DRAM eviction
    assert key in tiered                        # disk still has it
    got = tiered.get(key)
    assert all(np.array_equal(x, y) for x, y in zip(payload, got))
    assert key in host, "disk hit did not promote into the host tier"


# ----------------------------------------------------- engine handoff

@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_export_admit_parity(model_and_params, kv_dtype):
    """The tentpole contract at engine level: prefill-role park/export
    → decode-role submit(kv_payloads=...) revive → decode, with the
    payloads crossing as WIRE BYTES — byte-identical to one colocated
    engine, on fp32 and int8 (scale leaves ride the same payloads)."""
    model, params = model_and_params
    kw = {"kv_dtype": kv_dtype} if kv_dtype else {}
    clean = _drain_colocated(_engine(model, params, **kw))

    pre = _engine(model, params, role="prefill", **kw)
    dec = _engine(model, params, role="decode", **kw)
    results = {}
    handed = {}
    rids = [pre.submit(p, max_length=8) for p in PROMPTS]
    while len(results) < len(PROMPTS):
        pre.step()
        for erid in pre.prefilled_ready():
            blobs = pre.export_kv(erid)
            assert all(isinstance(b, bytes) for b in blobs)
            stub = pre.take_result(erid)
            assert stub.finish_reason == "prefilled"
            handed[erid] = dec.submit(
                PROMPTS[rids.index(erid)], max_length=8,
                history=list(stub.tokens), kv_payloads=blobs)
        dec.step()
        for erid, drid in list(handed.items()):
            res = dec.take_result(drid)
            if res is not None:
                results[erid] = list(res.tokens)
                del handed[erid]
    assert [results[r] for r in rids] == clean
    assert pre.metrics.kv_pages_shipped > 0
    assert dec.metrics.kv_pages_revived_remote > 0
    pre.cache_manager.pool.check_invariants()
    dec.cache_manager.pool.check_invariants()


def test_role_and_payload_validation(model_and_params):
    """The contract edges fail loudly at the right layer: bad role
    strings, payload count mismatch, payloads without history, and
    export_kv of a request that is not parked."""
    model, params = model_and_params
    with pytest.raises(ValueError, match="role"):
        _engine(model, params, role="decoder")

    eng = _engine(model, params)
    blob = HostPageStore.payload_to_bytes(
        [np.zeros((2, 8, 2, 16), np.float32)])
    with pytest.raises(ValueError, match="history"):
        eng.submit(PROMPTS[0], max_length=8, kv_payloads=[blob])
    with pytest.raises(ValueError, match="page"):
        # 3-token prompt needs 1 page; two payloads is a protocol bug
        eng.submit(PROMPTS[0], max_length=8, history=[1],
                   kv_payloads=[blob, blob])
    with pytest.raises(KeyError, match="not parked"):
        eng.export_kv(12345)


def test_decode_replica_recovers_shipped_admissions(model_and_params):
    """A decode replica whose tick faults AFTER shipped-KV admissions
    recovers through the replay path (the shipped pages died with the
    pool) and still finishes byte-identically — graceful degradation,
    documented in the engine docstring."""
    from fleetx_tpu.resilience.faults import faults

    model, params = model_and_params
    clean = _drain_colocated(_engine(model, params))

    def run_disagg():
        pre = _engine(model, params, role="prefill")
        dec = _engine(model, params, role="decode")
        results = {}
        handed = {}
        rids = [pre.submit(p, max_length=8) for p in PROMPTS]
        while len(results) < len(PROMPTS):
            pre.step()
            for erid in pre.prefilled_ready():
                blobs = pre.export_kv(erid)
                stub = pre.take_result(erid)
                handed[erid] = dec.submit(
                    PROMPTS[rids.index(erid)], max_length=8,
                    history=list(stub.tokens), kv_payloads=blobs)
            dec.step()
            for erid, drid in list(handed.items()):
                res = dec.take_result(drid)
                if res is not None:
                    results[erid] = list(res.tokens)
                    del handed[erid]
        return [results[r] for r in rids], dec

    faults.configure(tick_raise="2")
    try:
        got, dec = run_disagg()
    finally:
        faults.reset()
    assert dec.metrics.engine_recoveries == 1
    assert got == clean
    dec.cache_manager.pool.check_invariants()


# ----------------------------------------------------- router handoff

def _run_router(router, prompts=PROMPTS, max_length=8):
    rids = [router.submit(p, max_length=max_length) for p in prompts]
    res = router.drain(max_ticks=500)
    assert len(res) == len(rids), "requests lost or duplicated"
    return [list(res[r].tokens) for r in rids]


def test_router_disagg_parity_roles_and_health(model_and_params):
    """The router end to end: fresh prompts land on the prefill
    replica (priced by queue TOKENS), finished prefills hand off with
    their pages, decoding finishes on the decode replica — tokens
    byte-identical to a colocated fleet — and both phases surface
    role + queue_tokens through health()/healthz."""
    model, params = model_and_params
    clean = _run_router(ServingRouter(
        [_engine(model, params), _engine(model, params)], base_seed=3))

    pre = _engine(model, params, role="prefill")
    dec = _engine(model, params, role="decode")
    router = ServingRouter([pre, dec], base_seed=3)
    assert [r.role for r in router._replicas] == ["prefill", "decode"]
    got = _run_router(router)
    assert got == clean
    # the work split: every page decoded remotely, none decoded where
    # it was prefilled
    assert pre.metrics.kv_pages_shipped > 0
    assert dec.metrics.kv_pages_revived_remote == \
        pre.metrics.kv_pages_shipped
    h = pre.health()
    assert h["role"] == "prefill" and "queue_tokens" in h
    assert dec.health()["role"] == "decode"

    # the aggregated /healthz body carries the same placement signals
    from fleetx_tpu.obs.http import healthz_payload, register_health

    # (an engine an earlier test of this process shut down unregisters its
    # probe when it is collected: do that now, or its 503 is the aggregate's)
    import gc

    gc.collect()
    register_health("serving", pre.health)
    try:
        ok, body = healthz_payload()
    finally:
        register_health("serving", lambda: True)
    assert ok and "role" in body and "queue_tokens" in body
    # other module-scope engines keep their own probes registered, so
    # the AGGREGATE role may read "both"; the prefill replica's own
    # probe detail must carry its phase verbatim
    assert any(d.get("role") == "prefill" for d in body["detail"].values())


@pytest.mark.slow  # 14.4s (PR 18 tier-1 budget audit): three full
# router workloads back to back to walk every rung in one test. Each
# rung's contract stays tier-1 on its own: export-fault/crc fallback
# via test_export_admit_parity, the prefill-replica death + replay via
# test_decode_replica_recovers_shipped_admissions, disagg routing +
# health via test_router_disagg_parity_roles_and_health; the combined
# ladder also runs end-to-end in chaos_check's serving_disagg scenario.
def test_router_fallback_ladder(model_and_params):
    """Every rung degrades to replay, never to wrong bytes: an export
    fault mid-handoff, a blob corrupted in flight (caught by the wire
    crc at the decode replica's submit), and the prefill replica dying
    outright — all three produce byte-identical tokens and bank
    kv_ship_failed / migration evidence."""
    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.resilience.faults import faults

    model, params = model_and_params
    clean = _run_router(ServingRouter(
        [_engine(model, params), _engine(model, params)], base_seed=3))
    ev = get_event_log()

    def disagg_router(**kw):
        return ServingRouter([_engine(model, params, role="prefill"),
                              _engine(model, params, role="decode")],
                             base_seed=3, **kw)

    ev.clear()
    faults.configure(kv_ship_raise="1")
    try:
        assert _run_router(disagg_router()) == clean
    finally:
        faults.reset()
    fails = ev.find("kv_ship_failed")
    assert any(e.attrs.get("where") == "export" for e in fails)

    ev.clear()
    faults.configure(kv_ship_corrupt="0")
    try:
        assert _run_router(disagg_router()) == clean
    finally:
        faults.reset()
    fails = ev.find("kv_ship_failed")
    assert any(e.attrs.get("where") == "admit" for e in fails)

    ev.clear()
    faults.configure(replica_kill="0:3")
    try:
        assert _run_router(
            disagg_router(probe_max_failures=1)) == clean
    finally:
        faults.reset()
    assert ev.find("replica_dead", replica=0)


def test_router_all_roles_colocated_unchanged(model_and_params):
    """A fleet with no role-specialized replicas must behave exactly as
    before this feature: no handoffs, no shipped pages, same bytes."""
    model, params = model_and_params
    engines = [_engine(model, params), _engine(model, params)]
    router = ServingRouter(engines, base_seed=5)
    rids = [router.submit(p, max_length=8) for p in PROMPTS]
    handoffs = 0
    while any(router.result(r) is None for r in rids):
        handoffs += router.step().get("handoff", 0)
    assert handoffs == 0
    assert all(e.metrics.kv_pages_shipped == 0 for e in engines)
