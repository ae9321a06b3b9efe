"""Chunked prefill + host-DRAM KV spill tier tests (ISSUE 11).

Three layers:

- **Parity gates**: chunked prefill must emit BYTE-identical greedy
  tokens to the unchunked engine across bf16/int8
  KV, and the flash-interpret kernel path — and a request decoded from
  spill-REVIVED host pages must match its cold-prefilled run byte for
  byte (revived bytes are the spilled bytes).
- **Scheduler semantics**: chunks interleaved with decode, two a tick at
  most (active requests keep streaming one token per tick while a long
  prompt ingests; ``test_serving_second_chunk.py`` holds the budget), deadlines checked between chunks (an expired request
  stops burning prefill with nothing leaked), FIFO preserved.
- **Crash safety**: a fault mid-chunk (prefill raise or decode raise
  while a prompt is mid-ingestion) rolls back, recovery requeues the
  mid-prefill request at the head, and every token stream still matches
  the unfaulted run; the host tier survives the recovery and keeps
  reviving.

The PagePool/HostPageStore host-unit coverage (spill/revive churn under
``check_invariants()``) lives in ``test_paged_serving.py`` beside the
rest of the pool property tests.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from serving_parity import (assert_token_parity, one_shot_tokens,
                            sharing_programs)

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.serving import ServingEngine

GREEDY = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                          pad_token_id=60)


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


@pytest.fixture(scope="module")
def tiny_flash():
    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=True)  # interpret on CPU
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
def _engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("cache_len", 32)
    kw.setdefault("gen_cfg", GREEDY)
    kw.setdefault("prefill_bucket", 4)
    kw.setdefault("page_size", 8)
    return ServingEngine(model, params, **kw)


def _mixed_prompts(seed=7):
    rng = np.random.RandomState(seed)
    # long prompts (well past the chunk) mixed with short ones
    return [rng.randint(1, 61, (n,)).astype(np.int32)
            for n in (19, 4, 23, 9)]


def _run(eng, prompts, max_length=4):
    rids = [eng.submit(p, max_length=max_length) for p in prompts]
    res = eng.drain()
    return [np.asarray(res[r].tokens) for r in rids]


# ---------------------------------------------------------- parity gates

# tier-1 keeps ONE compact gate (bf16); the int8 variant re-proves the
# same contract in the full sweep (8-15s on the slow-host baseline;
# PR 11 tier-1 budget audit)
@pytest.mark.parametrize(
    "kv", ["bf16", pytest.param("int8", marks=pytest.mark.slow)])
def test_chunked_vs_unchunked_byte_parity(tiny, kv):
    """The acceptance gate: chunking only reschedules WHEN prompt tokens
    ingest, never what anything computes — byte-identical greedy streams
    at both KV precisions (int8 compares against
    its own unchunked run: same quantization, same bytes), and the
    one-shot reference pins the bf16 runs to ``generate()``."""
    model, params = tiny
    prompts = _mixed_prompts()
    kw = dict(kv_dtype=None if kv == "bf16" else "int8")
    want = _run(_engine(model, params, **kw), prompts)
    eng = _engine(model, params, prefill_chunk=6, **kw)
    got = _run(eng, prompts)
    for i, (a, b) in enumerate(zip(got, want)):
        assert_token_parity(a, b, err_msg=f"req {i} ({kv})")
    if kv == "bf16":
        # one-shot pin on the longest prompt only: unchunked-vs-one-shot
        # is already the paged suites' gate; each extra reference
        # is a fresh generate() compile the tier-1 budget pays for
        ref = one_shot_tokens(model, params, prompts[2], 4, gen_cfg=GREEDY)
        assert_token_parity(got[2], ref, err_msg="req 2 vs one-shot")
    # the long prompts actually ran chunked
    assert eng.metrics.prefill_chunks >= 2 * (19 // 6)
    assert not eng._prefilling and eng.cache_manager.free_count == 2


@pytest.mark.slow  # 6.7s baseline — tier-1 keeps the dense paged gate
def test_chunked_flash_interpret_parity(tiny_flash):
    """Chunked prefill through the paged flash-decode kernel (interpret
    mode on CPU): decode reads chunk-written pages through the same
    scalar-prefetched tables — byte parity with the unchunked engine."""
    model, params = tiny_flash
    prompts = _mixed_prompts(11)
    want = _run(_engine(model, params), prompts)
    got = _run(_engine(model, params, prefill_chunk=6), prompts)
    for i, (a, b) in enumerate(zip(got, want)):
        assert_token_parity(a, b, err_msg=f"req {i} (flash-interpret)")


def test_free_and_mid_prefill_lanes_change_no_token(tiny_flash, monkeypatch):
    """More lanes than requests, and one long prompt mid-prefill in chunks
    (its request fills the row, so the lane is inactive in those ticks and
    OWNS a real page at the last row) while two requests decode: the paged
    decode kernel (interpreted) is handed an empty window for the free
    lanes, the row for the mid-prefill one, and every request's greedy
    tokens are those of the same request served alone. Each
    ``serving.decode`` span counts the slots dispatched without a token as
    ``empty_lanes``."""
    from fleetx_tpu.obs.tracing import get_recorder

    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    model, params = tiny_flash
    rng = np.random.RandomState(17)
    prompts = [rng.randint(1, 61, (n,)).astype(np.int32) for n in (5, 9, 28)]
    news = (14, 11, 4)      # 28 + 4: the long request fills its row of 32

    def serve(eng, which):
        rids = [eng.submit(prompts[i], max_length=news[i]) for i in which]
        res = eng.drain()
        return [np.asarray(res[r].tokens) for r in rids]

    alone = _engine(model, params, slots=5, prefill_chunk=6)
    want = [serve(alone, [i])[0] for i in range(3)]

    eng = _engine(model, params, slots=5, prefill_chunk=6)
    rec = get_recorder()
    rec.clear()
    short = [eng.submit(prompts[i], max_length=news[i]) for i in (0, 1)]
    eng.step()
    long_rid = eng.submit(prompts[2], max_length=news[2])
    mid_prefill_ticks = 0
    while eng._prefilling or len(eng.scheduler):
        eng.step()
        if eng._prefilling:     # its pages were handed out at admission
            mid_prefill_ticks += int(eng.cache_manager.tables[:, -1].any())
    assert mid_prefill_ticks >= 2     # five chunks, two a step
    res = eng.drain()
    for rid, tokens in zip(short + [long_rid], want):
        assert_token_parity(np.asarray(res[rid].tokens), tokens,
                            err_msg=f"request {rid}")
    ticks = [s for s in rec.spans() if s.name == "serving.decode"]
    assert ticks and all(
        s.attrs["empty_lanes"] == 5 - s.attrs["batch"] >= 2 for s in ticks)


# stack -> GPTConfig fields: the one block of models/gpt/model.py, and the
# grouped-head stack of models/gpt/hybrid.py with one full and three window
# layers (a window of 16 rows) over two classes of page
_KERNEL_STEP_STACKS = {
    "gpt": dict(num_layers=2, num_attention_heads=2, ffn_hidden_size=64),
    "hybrid": dict(
        num_layers=4, num_attention_heads=8, num_key_value_heads=2,
        head_size=16, ffn_hidden_size=32, num_experts=8, gate="softmax_topk",
        top_k=2, norm_topk_prob=True, position_embedding="rope",
        rope_layout=(0, 1, 1, 1), sliding_window=16,
        sliding_window_layout=(0, 1, 1, 1), norm="rmsnorm", mlp_act="reglu",
        use_bias=False, tie_word_embeddings=False,
        router_input="block_input", expert_mode=True, family="smallthinker"),
}


@pytest.mark.parametrize("stack", list(_KERNEL_STEP_STACKS))
def test_decode_span_counts_the_kernels_grid_steps(monkeypatch, stack):
    """Each ``serving.decode`` span carries ``kernel_steps``: the grid steps
    of ``fleetx_decode_paged`` over all the layers' calls of a tick, from
    the function the kernel sizes its grid with
    (``decode_attention.paged_grid``). Rows of 1,024 in steps of 256 (the
    step of a full-width row here): a full layer's call walks 4 steps a
    lane, a window layer's (16 rows) 2, and the calls the tick traces have
    those grids."""
    import fleetx_tpu.ops.pallas.decode_attention as da
    from fleetx_tpu.obs.tracing import get_recorder

    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    monkeypatch.setattr(da, "_FULL_ROW_BYTES", 0)
    traced, real = [], da._paged_block_call

    def recording(q, pools, starts, ends, tables, pages, steps):
        traced.append((q.shape[0], steps))
        return real(q, pools, starts, ends, tables, pages, steps)

    monkeypatch.setattr(da, "_paged_block_call", recording)
    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, max_position_embeddings=1024,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=True,
        **_KERNEL_STEP_STACKS[stack])
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    eng = _engine.__wrapped__(model, params, slots=3, cache_len=1024,
                              prefill_chunk=8, prefill_bucket=8)
    rec = get_recorder()
    rec.clear()
    eng.submit(np.arange(1, 12, dtype=np.int32), max_length=3)
    eng.drain()
    windows = {"gpt": (None, None), "hybrid": (None, 16, 16, 16)}[stack]
    width = cfg.kv_heads * cfg.head_dim
    pool = [jax.ShapeDtypeStruct((1, 8, width), jnp.float32)] * 2
    grids = [da.paged_grid(pool, 1024 // 8, max_live=w)[1] for w in windows]
    assert grids == [{None: 4, 16: 2}[w] for w in windows]
    ticks = [s for s in rec.spans() if s.name == "serving.decode"]
    assert ticks and all(
        s.attrs["kernel_steps"] == 3 * sum(grids) for s in ticks)
    assert set(traced) == {(3, steps) for steps in grids}


def test_chunked_parity_at_cache_capacity_edge(tiny):
    """Regression (PR 11 review): a chunk whose PADDED bucket would
    cross ``cache_len`` must cap at the remaining span (prompt_len 31 in
    a 32-cache, final chunk at wpos 30 with a 4-row bucket): positions
    past the lane's last page must never clobber live prompt KV or flip
    the sampled token."""
    model, params = tiny
    prompt = np.random.RandomState(13).randint(
        1, 61, (31,)).astype(np.int32)  # cache_len - 1: the worst case
    want = _run(_engine(model, params, slots=1), [prompt], max_length=1)
    got = _run(_engine(model, params, slots=1, prefill_chunk=6), [prompt],
               max_length=1)
    assert_token_parity(got[0], want[0], err_msg="cache-edge chunk")


def test_chunk_at_or_above_prompt_is_one_call(tiny):
    """``prefill_chunk >= prompt`` must take the one-call path exactly —
    no ``prefilling`` state, no chunk calls, today's tick trace."""
    model, params = tiny
    eng = _engine(model, params, prefill_chunk=32)
    rid = eng.submit(np.arange(1, 9, dtype=np.int32), max_length=3)
    summary = eng.step()
    assert summary["admitted"] == 1 and summary["chunked"] == 0
    assert not eng._prefilling
    res = eng.drain()
    assert res[rid].finish_reason == "max_length"
    assert eng.metrics.prefill_chunks == 0


# --------------------------------------------------- scheduler semantics

def test_decode_streams_one_token_per_tick_during_chunked_prefill(tiny):
    """The decode-stall-free claim in deterministic form: while a long
    prompt ingests chunk by chunk, an already-active request receives
    exactly one token EVERY tick — no tick is swallowed whole by
    prefill."""
    model, params = tiny
    eng = _engine(model, params, prefill_chunk=6)
    short = eng.submit(np.asarray([1, 2, 3], np.int32), max_length=12)
    eng.step()  # short admitted + first token
    long_rid = eng.submit(np.arange(1, 24, dtype=np.int32), max_length=3)
    req = next(iter(eng._active.values()))
    assert req.id == short
    while eng._prefilling or len(eng.scheduler):
        before = len(req.tokens)
        summary = eng.step()
        assert len(req.tokens) == before + 1, (
            "active stream stalled during a prefill chunk")
        assert summary["chunked"] <= 2
    res = eng.drain()
    assert len(res[long_rid].tokens) == 3
    assert_token_parity(
        res[long_rid].tokens,
        one_shot_tokens(model, params, np.arange(1, 24, dtype=np.int32), 3,
                        gen_cfg=GREEDY))


def test_expired_request_stops_burning_chunks(tiny):
    """Deadline checked BETWEEN chunks: an expired mid-prefill request
    retires ``finish_reason="timeout"`` with zero tokens, its lane and
    pages free immediately, and the pool stays invariant-clean (no
    partial-chunk leak — nothing was registered in the trie)."""
    model, params = tiny
    clock = {"t": 0.0}
    eng = _engine(model, params, prefill_chunk=6)
    eng._now = lambda: clock["t"]
    rid = eng.submit(np.arange(1, 20, dtype=np.int32), max_length=4,
                     deadline_s=5.0)
    eng.step()  # admission + first chunk
    assert eng._prefilling and not eng._active
    clock["t"] += 10.0
    summary = eng.step()  # expired: no further chunk runs
    assert summary["chunked"] == 0 and rid in summary["timed_out"]
    res = eng.drain()
    assert res[rid].finish_reason == "timeout" and not len(res[rid].tokens)
    assert eng.cache_manager.free_count == 2
    assert eng.cache_manager.pages_in_use == 0
    eng.cache_manager.pool.check_invariants()
    # the freed lane is immediately reusable
    rid2 = eng.submit(np.asarray([5, 6, 7], np.int32), max_length=3)
    res = eng.drain()
    assert res[rid2].finish_reason == "max_length"


@pytest.mark.slow  # 3.1s baseline (PR 11 tier-1 budget: suite must fit 870s)
def test_fifo_preserved_behind_chunked_head(tiny):
    """A queued request must not overtake the mid-prefill head: arrival
    order in, first-token order out — a free lane behind the chunking
    head does NOT let later arrivals jump it."""
    model, params = tiny
    eng = _engine(model, params, prefill_chunk=6, slots=2)
    order = []

    def on_token(rid, tok, finished):
        if rid not in order:
            order.append(rid)

    long_rid = eng.submit(np.arange(1, 20, dtype=np.int32), max_length=3,
                          on_token=on_token)
    short_rid = eng.submit(np.asarray([1, 2], np.int32), max_length=3,
                           on_token=on_token)
    eng.drain()
    assert order == [long_rid, short_rid]


# ----------------------------------------------------------- crash safety

def test_fault_mid_chunk_recovers_byte_identically(tiny):
    """A prefill raise INSIDE a chunk rolls the tick back; recovery
    requeues the mid-prefill request at the head and the final streams
    are byte-identical to the unfaulted run (zero tokens had been
    emitted — the roll-back is total)."""
    model, params = tiny
    prompts = _mixed_prompts(3)
    clean = _run(_engine(model, params, prefill_chunk=6), prompts)
    eng = _engine(model, params, prefill_chunk=6)
    # attempt 1 is the SECOND prefill-shaped call: the first long
    # prompt's second chunk — squarely mid-ingestion
    faults.configure(prefill_raise="1")
    faulty = _run(eng, prompts)
    assert eng.metrics.engine_recoveries == 1
    for i, (a, b) in enumerate(zip(faulty, clean)):
        assert_token_parity(a, b, err_msg=f"req {i} after mid-chunk fault")
    eng.cache_manager.pool.check_invariants()
    # nobody was quarantined: one strike + clean retry is not poison
    assert eng.metrics.poison_retired == 0


@pytest.mark.slow  # 4.6s baseline — the prefill-raise variant stays tier-1
def test_decode_fault_during_prefilling_requeues_and_recovers(tiny):
    """A decode-tick raise while another prompt is mid-chunk: the active
    request replays, the mid-prefill one restarts from the queue head,
    both finish byte-identical to the clean run."""
    model, params = tiny
    prompts = [np.asarray([1, 2, 3], np.int32),
               np.arange(1, 20, dtype=np.int32)]
    clean = _run(_engine(model, params, prefill_chunk=6), prompts,
                 max_length=6)
    eng = _engine(model, params, prefill_chunk=6)
    faults.configure(tick_raise="2")  # a tick with one active + one chunking
    faulty = _run(eng, prompts, max_length=6)
    assert eng.metrics.engine_recoveries == 1
    for i, (a, b) in enumerate(zip(faulty, clean)):
        assert_token_parity(a, b, err_msg=f"req {i}")
    eng.cache_manager.pool.check_invariants()


# ------------------------------------------------------- host spill tier

def _spill_fixture_runs(model, params, host_bytes, n_prefixes=2, rounds=2):
    """Sequential single-tenant visits over ``n_prefixes`` distinct
    16-token system prompts through a 4-usable-page pool: every revisit
    finds its warm pages evicted (the hot set exceeds the device pool),
    so only the host tier can keep the prefix cache hitting."""
    rng = np.random.RandomState(5)
    prefixes = [rng.randint(1, 61, (16,)).astype(np.int32)
                for _ in range(n_prefixes)]
    tails = np.random.RandomState(6).randint(
        1, 61, (rounds * n_prefixes, 3)).astype(np.int32)
    eng = _engine(model, params, num_pages=5, host_cache_bytes=host_bytes)
    toks = []
    for i in range(rounds * n_prefixes):
        p = np.concatenate([prefixes[i % n_prefixes], tails[i]])
        rid = eng.submit(p, max_length=4)
        toks.append(eng.drain()[rid].tokens)
        eng.cache_manager.pool.check_invariants()
    return eng, toks


def test_cold_vs_spill_revived_byte_parity(tiny):
    """The two-level-cache acceptance gate: with the host tier on, an
    oversubscribed shared-prefix workload keeps hitting (pages revive
    from host DRAM) and every token stream is byte-identical to the
    host-off run that re-prefilled everything cold — revived bytes ARE
    the spilled bytes."""
    model, params = tiny
    eng_off, cold = _spill_fixture_runs(model, params, host_bytes=0)
    eng_on, warm = _spill_fixture_runs(model, params, host_bytes=1 << 20)
    for i, (a, b) in enumerate(zip(cold, warm)):
        assert_token_parity(a, b, err_msg=f"req {i} cold vs revived")
    s_off, s_on = eng_off.metrics.snapshot(), eng_on.metrics.snapshot()
    # host off: each revisit's warm pages were LRU-destroyed -> no reuse
    assert s_off["host_revived_pages"] == 0
    assert s_on["host_revived_pages"] > 0
    assert s_on["prefill_tokens_saved"] > s_off["prefill_tokens_saved"]
    assert s_on["prefix_hit_rate"] > s_off["prefix_hit_rate"]
    assert s_on["host_spilled_pages"] >= s_on["host_revived_pages"] > 0


@pytest.mark.slow  # 4.6s baseline — bf16 spill parity stays tier-1
def test_int8_pages_spill_with_scales(tiny):
    """Quantized pool: spilled payloads carry the int8 K/V pages AND
    their fp32 scale pages (every cache leaf), so revived decoding is
    byte-identical to the cold int8 run."""
    model, params = tiny

    def run(host_bytes):
        rng = np.random.RandomState(5)  # fresh per run: identical prompts
        sysp = rng.randint(1, 61, (16,)).astype(np.int32)
        other = rng.randint(1, 61, (16,)).astype(np.int32)
        eng = _engine(model, params, num_pages=5, kv_dtype="int8",
                      host_cache_bytes=host_bytes)
        toks = []
        for pre in (sysp, other, sysp):
            p = np.concatenate([pre, rng.randint(1, 61, (3,))])
            rid = eng.submit(p.astype(np.int32), max_length=4)
            toks.append(eng.drain()[rid].tokens)
        return eng, toks

    # identical submission streams (fresh RandomState both runs)
    eng_off, cold = run(0)
    eng_on, warm = run(1 << 20)
    for a, b in zip(cold, warm):
        assert_token_parity(a, b, err_msg="int8 cold vs revived")
    assert eng_on.metrics.snapshot()["host_revived_pages"] > 0
    eng_on.cache_manager.pool.check_invariants()


@pytest.mark.slow  # 3.6s baseline; the cold-vs-revived tier-1 gate and
# the chaos serving_spill scenario keep recovery-survival covered — this
# is the direct unit form
def test_host_store_survives_recovery(tiny):
    """Replay recovery rebuilds pool + trie from scratch but the host
    tier is content-addressed and engine-owned: entries spilled before
    the fault revive AFTER it, and a post-recovery revisit of the
    spilled prefix skips its prefill again."""
    model, params = tiny
    eng, _ = _spill_fixture_runs(model, params, host_bytes=1 << 20)
    before = eng.metrics.snapshot()
    assert before["host_cache_pages"] > 0
    store = eng._host_store
    eng.recover()
    assert eng._host_store is store  # the same store, re-threaded
    assert eng.cache_manager.pool.host_store is store
    rng = np.random.RandomState(5)
    sysp = rng.randint(1, 61, (16,)).astype(np.int32)
    p = np.concatenate([sysp, np.asarray([7, 8, 9], np.int32)])
    rid = eng.submit(p.astype(np.int32), max_length=4)
    res = eng.drain()
    after = eng.metrics.snapshot()
    assert after["host_revived_pages"] > before["host_revived_pages"]
    assert res[rid].finish_reason == "max_length"
    assert_token_parity(
        res[rid].tokens,
        one_shot_tokens(model, params, p.astype(np.int32), 4,
                        gen_cfg=GREEDY),
        err_msg="post-recovery revived decode")


# ----------------------------------- the head follows what the caller reads

# stack -> GPTConfig fields, one for each module that holds a stack: the
# block of models/gpt/model.py under the tied word table, hybrid.py's
# grouped heads with window layers (an ``lm_head`` of its own),
# mixed_stack.py's operators of two kinds (conv state beside the pages),
# latent.py's latent attention
_HEAD_STACKS = dict(
    _KERNEL_STEP_STACKS,
    mixed=dict(
        num_layers=3, num_attention_heads=8, num_key_value_heads=2,
        ffn_hidden_size=32, dense_ffn_hidden_size=96, num_dense_layers=1,
        layer_types=("conv", "full_attention", "conv"), conv_L_cache=3,
        num_experts=8, gate="sigmoid_topk", top_k=2, norm_topk_prob=True,
        routed_scaling_factor=1.0, use_expert_bias=True,
        position_embedding="rope", norm="rmsnorm", mlp_act="swiglu",
        use_bias=False, qk_norm=True, qk_norm_scope="head",
        tie_word_embeddings=True, expert_mode=True, family="lfm2"),
    latent=dict(
        num_layers=2, num_attention_heads=4, ffn_hidden_size=32,
        position_embedding="rope", norm="rmsnorm", mlp_act="swiglu",
        use_bias=False, tie_word_embeddings=False,
        layer_types=("latent_attention",) * 2, num_dense_layers=2,
        dense_ffn_hidden_size=96, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16))
_VOCAB, _BUCKET = 61, 8


def _stack_engine(stack, **kw):
    cfg = GPTConfig(
        vocab_size=_VOCAB, hidden_size=32, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False, **_HEAD_STACKS[stack])
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # (weights of its own every call: nothing to share programs with)
    return _engine.__wrapped__(model, params, cache_len=64,
                               prefill_chunk=_BUCKET, prefill_bucket=_BUCKET,
                               **kw)


def _vocab_wide(jaxpr):
    """``(primitive, shape)`` of every value a program computes whose last
    axis is the vocabulary, through every nested program (the layer scan,
    both sides of a conditional)."""
    found = []
    for eqn in jaxpr.eqns:
        found += [(eqn.primitive.name, tuple(v.aval.shape))
                  for v in eqn.outvars
                  if getattr(v.aval, "shape", ())[-1:] == (_VOCAB,)]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _vocab_wide(sub)
    return found


@pytest.mark.parametrize("stack", list(_HEAD_STACKS))
def test_prefill_program_applies_the_head_to_the_one_row_it_samples(stack):
    """The prefill program of a bucket of 8 rows holds ONE head product, of
    one row, and no value of ``rows x vocab`` elements anywhere: everything
    as wide as the vocabulary is one row (the logits, the sampler's
    passes over them). The reader reads what it is meant to: a program
    around the executor's default ``forward`` holds the ``[1, 8, vocab]``
    product the prefill program held before."""
    eng = _stack_engine(stack)
    cache = eng.cache_manager.cache
    ints = eng._prefill_ints((), _BUCKET, 0, eng.cache_manager.lane_tables(0))
    wide = _vocab_wide(jax.make_jaxpr(eng._make_paged_prefill(_BUCKET))(
        eng.params, cache, ints, eng._inert_floats,
        jax.random.PRNGKey(0)).jaxpr)
    assert [s for p, s in wide if p == "dot_general"] == [(1, 1, _VOCAB)]
    assert wide and all(int(np.prod(s)) == _VOCAB for _, s in wide), wide

    table = jnp.expand_dims(jnp.asarray(eng.cache_manager.lane_tables(0)), -2)
    every = _vocab_wide(jax.make_jaxpr(lambda params, cache: eng.executor.forward(
        params, cache, jnp.zeros((1, _BUCKET), jnp.int32),
        jnp.arange(_BUCKET)[None], eng._row_mask(jnp.ones((1, _BUCKET), bool)),
        cache_positions=jnp.zeros((1,), jnp.int32),
        block_tables=table))(eng.params, cache).jaxpr)
    assert ("dot_general", (1, _BUCKET, _VOCAB)) in every


def _first_token_state(eng, prompt, **sampling):
    """Admit ``prompt`` alone and stop when its first token is out: the
    token, the lane's rng carry, and every position's cache rows by leaf
    (``[layers, pages, page, width]`` read through the lane's table)."""
    rid = eng.submit(prompt, max_length=4, **sampling)
    while not eng.emitted_tokens(rid):
        eng.step()
    slot = next(iter(eng._active))
    table = eng.cache_manager.lane_tables(slot)
    rows = [np.asarray(leaf)[:, table].reshape(
        leaf.shape[0], -1, leaf.shape[-1])[:, :len(prompt)]
        for leaf in jax.tree.leaves(eng.cache_manager.cache)
        if leaf.ndim == 4]
    return (eng.emitted_tokens(rid)[0], np.asarray(eng._state["rng"][slot]),
            rows)


@pytest.mark.parametrize("sampling", [
    {}, dict(decode_strategy="sampling", temperature=0.9, top_k=5, seed=11)],
    ids=["greedy", "sampled"])
def test_headless_chunks_leave_the_one_call_prefills_state(tiny, sampling):
    """A prompt of 19 tokens in chunks of 8, 8 and 3, of which the first
    two want no token (their programs run neither head nor sampler),
    against the same prompt in one call: the same first token, the same
    rng carry (only the call that samples splits the request's stream)
    and the same rows in the lane's pages."""
    model, params = tiny
    prompt = np.random.RandomState(3).randint(1, 61, (19,)).astype(np.int32)
    one = _engine(model, params, slots=1)
    tok, rng, rows = _first_token_state(one, prompt, **sampling)
    chunked = _engine(model, params, slots=1, prefill_chunk=8)
    c_tok, c_rng, c_rows = _first_token_state(chunked, prompt, **sampling)
    snap = chunked.metrics.snapshot()
    assert (snap["prefill_headless_calls"],
            snap["prefill_token_calls"]) == (2, 1)
    assert one.metrics.snapshot()["prefill_headless_calls"] == 0
    assert c_tok == tok
    np.testing.assert_array_equal(c_rng, rng)
    assert rows and len(rows) == len(c_rows)
    for a, b in zip(c_rows, rows):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("stack", list(_HEAD_STACKS))
def test_a_call_that_wants_no_token_writes_the_same_cache(stack):
    """One program a bucket (the flag is an operand, not a shape): the
    same jitted prefill, called for no token and for one, leaves every
    cache leaf bit for bit the same and hands back the same rng carry."""
    eng = _stack_engine(stack)
    lane, _ = eng.cache_manager.alloc(0, np.arange(1, 8, dtype=np.int32))
    assert eng.cache_manager.prepare_span(lane, 0, 7)
    fn = eng._make_paged_prefill(_BUCKET)
    outs = [fn(eng.params, eng.cache_manager.cache, eng._prefill_ints(
        np.arange(1, 8), _BUCKET, 0, eng.cache_manager.lane_tables(lane),
        wants_token=wants), eng._inert_floats, jax.random.PRNGKey(4))
        for wants in (False, True)]
    (cache, tok, carry), (t_cache, _, t_carry) = outs
    assert int(tok) == 0
    np.testing.assert_array_equal(carry, t_carry)
    jax.tree.map(np.testing.assert_array_equal, cache, t_cache)
