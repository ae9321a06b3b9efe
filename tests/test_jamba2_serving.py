"""The Jamba stack (Mamba-1 selective-scan layers whose state is held once a
lane, outside the engine's page pool, beside position-free attention layers
of 4 query heads over ONE key head; a dense gated MLP in every layer)
through the normal path, against the plain float32 reference
``perfbench/reference/jamba2_f32.py``, at a tiny size on seeded weights: the
full forward; a one-shot prefill; a prefill IN CHUNKS (the first padded);
prefill then decoding token by token through the lane's state; a padded
bucket and an idle lane, which leave the state bit for bit; a lane admitted
anew after the tick in flight ran over it; the kernels in interpret mode
against the ``lax.scan``; the published configuration's parameter count
against the program's own model; what the engine refuses for this state.
Logits are compared, not tokens.

TOLERANCE. These tests compute in float32 on the CPU, where system and
reference differ only in the order of their sums: the distance read is some
1e-5 of the standard deviation of the reference's logits, and the limit is
2e-4. The bfloat16 limits of the chip are the benchmark driver's
(``perfbench/drivers/serve_closed_loop_ssm.py``), and the faults it has to
refuse are planted at this size in
``tests/perfbench/test_perfbench_jamba2.py``.
"""

import os

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import computed_once, sharing_programs, traced_apply

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.ops.pallas import ssm_scan, write_rows
from fleetx_tpu.serving import ServingEngine
from perfbench import flops_ssm, harness
from perfbench.drivers.serve_closed_loop_ssm import (FIRST_STATE_TOL, Served,
                                                    lane_state)
from perfbench.reference import jamba2_f32

TOL = 2e-4          # of the reference's logit standard deviation (docstring)
PAGE, CACHE_LEN, CHUNK = 8, 128, 16
TYPES = ("mamba", "mamba", "full_attention", "mamba", "mamba",
         "full_attention")
MODEL = dict(
    vocab_size=512, hidden_size=64, num_layers=6, num_attention_heads=4,
    num_key_value_heads=1, head_size=32, ffn_hidden_size=96,
    dense_ffn_hidden_size=96, num_dense_layers=6, layer_types=TYPES,
    mamba_expand=2, mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=8,
    max_position_embeddings=256, position_embedding="rope",
    rope_layout=(0,) * 6, norm="rmsnorm", norm_eps=1e-6, mlp_act="swiglu",
    use_bias=False, tie_word_embeddings=True)
SIZES = dict(MODEL, hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
             family="jamba2", use_flash_attention=False, dtype=jnp.float32)
reference = computed_once(jamba2_f32.configured(MODEL))
TOKENS = np.random.default_rng(0).integers(1, 512, (2, 56), dtype=np.int32)


def build(**changes):
    return GPTForPretraining(GPTConfig(**{**SIZES, **changes}))


@pytest.fixture(scope="module")
def variables():
    """Seeded weights. At width 64 with every matrix at the initializer's
    0.02 the head dominates and the layers decide nothing, so the layers'
    matrices are scaled up and every norm weight (the three inner ones of a
    mixer too) moved off 1, until both operators and all the norms decide
    the logits (a fault in any of them then shows)."""
    v = flax.core.meta.unbox(jax.jit(build().init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))

    def stir(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), x.shape)
        return x * 5.0 if "layers" in name and "kernel']" in name else x

    return jax.tree_util.tree_map_with_path(stir, v)


def distance(logits, expected) -> float:
    """Largest error in units of the reference's logit deviation."""
    expected = np.asarray(expected)
    return float(np.abs(np.asarray(logits) - expected).max() / expected.std())


served_of = sharing_programs(Served)


@sharing_programs
def engine_of(model, variables, **kw):
    kw = {"slots": 3, "page_size": PAGE, "prefill_bucket": 8,
          "cache_len": CACHE_LEN, **kw}
    return ServingEngine(
        model, variables,
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=8), **kw)


def test_full_forward_matches_the_reference(variables):
    logits = traced_apply(build(), variables, TOKENS)
    assert distance(logits, reference(variables["params"], TOKENS)) < TOL


def test_fused_projections_match_too(variables):
    model = build(fuse_attn_qkv=True)
    held = flax.core.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(1), np.zeros((1, 8), np.int32)))
    assert "qkv_proj" in held["params"]["gpt"]["layers"]["attention"]["op"]
    assert distance(traced_apply(model, held, TOKENS[:1]),
                    reference(held["params"], TOKENS[:1])) < TOL


@pytest.mark.parametrize("chunk", [0, CHUNK], ids=["one_shot", "chunked"])
def test_prefill_then_decode_through_the_lane_state(variables, chunk):
    """40 prompt tokens in one call of 40 rows, or in chunks of 16 (a first
    chunk of 8, padded: its padded rows must leave the state alone, and the
    next chunk carries on from what the lane holds), then 16 decode steps
    through the lane's state: every one of the 56 logit rows."""
    engine = engine_of(build(), variables)
    tokens = TOKENS[0]
    logits, _ = served_of(engine).sequence(tokens, 40, CHUNK, chunk)
    expected = reference(variables["params"], tokens)[40 - CHUNK:]
    assert distance(logits, expected) < TOL
    engine.cache_manager.pool.check_invariants()
    assert engine.cache_manager.pages_in_use == 0


def test_chunked_prefill_gives_what_one_shot_gives(variables):
    engine = engine_of(build(), variables)
    whole, whole_state = served_of(engine).sequence(TOKENS[1], 44, CHUNK)
    chunked, state = served_of(engine).sequence(TOKENS[1], 44, CHUNK, CHUNK)
    assert distance(chunked, whole) < TOL
    # the first layer is handed the same rows either way: its state differs
    # by float32's own rounding at most (the cell's limit holds it there)
    assert distance(state, whole_state) < FIRST_STATE_TOL


def _states(engine):
    return [np.asarray(x) for x in lane_state(engine, slice(None))]


def test_a_padded_bucket_leaves_the_state_bit_for_bit(variables):
    """21 tokens in a call of 32 rows, twice, with other tokens in the 11
    rows that are no tokens (one program, so the same sums in the same
    order): neither ``h`` nor the filter's rows can tell."""
    engine = engine_of(build(), variables)
    served, manager, held = served_of(engine), engine.cache_manager, []
    for padding in (TOKENS[0][21:32], TOKENS[1][21:32]):
        lane, _ = manager.alloc(-1, TOKENS[0][:21])
        ids = np.concatenate([TOKENS[0][:21], padding])
        manager.cache, _ = served._forward(
            engine.params, manager.cache, jnp.asarray(ids), jnp.int32(0),
            jnp.int32(21), jnp.asarray(manager.lane_tables(lane)))
        held.append([x[:, lane] for x in _states(engine)])
        manager.free(lane)
    for one, other in zip(*held):
        assert one.any()
        np.testing.assert_array_equal(one, other)
    # and it is the state of the 21 tokens: a call of 21 rows holds the same
    lane, _ = manager.alloc(-1, TOKENS[0][:21])
    served._call(lane, TOKENS[0][:21], 0, 21)
    np.testing.assert_allclose(_states(engine)[0][:, lane], held[0][0],
                               rtol=1e-4, atol=1e-5)
    manager.free(lane)


def test_an_idle_lane_keeps_its_state_bit_for_bit(variables):
    """Lane 0's request ends; lane 1 decodes on for 6 ticks, each of which
    runs over all three lanes: what lanes 0 and 2 hold does not change."""
    engine = engine_of(build(), variables)
    engine.submit(TOKENS[0][:20], max_length=2)
    engine.submit(TOKENS[1][:24], max_length=10)
    while engine._active.get(0) is not None or engine._inflight is None:
        engine.step()
    engine._settle("other")
    before = _states(engine)
    for _ in range(6):
        engine.step()
    engine._settle("other")
    after = _states(engine)
    assert before[0][:, 0].any()
    for was, now in zip(before, after):
        np.testing.assert_array_equal(was[:, [0, 2]], now[:, [0, 2]])
    assert not np.array_equal(before[0][:, 1], after[0][:, 1])
    engine.drain()


def test_a_lane_admitted_anew_after_the_tick_in_flight_ran_over_it(variables):
    """One lane. The first request ends on an end-of-sequence token, which
    only the token tells: the next tick is already dispatched over the lane
    when the host reads it. The request admitted into the lane afterwards
    gives the tokens and leaves the state a fresh engine gives."""
    def run(engine, first):
        if first is not None:
            engine.drain()
        rid = engine.submit(TOKENS[1][:24], max_length=6)
        out = engine.drain()[rid]
        return np.asarray(out.tokens), _states(engine)

    probe = engine_of(build(), variables, slots=1)
    rid = probe.submit(TOKENS[0][:20], max_length=8)
    told = np.asarray(probe.drain()[rid].tokens)
    eos = int(told[3])

    used = engine_of(build(), variables, slots=1)
    rid = used.submit(TOKENS[0][:20], max_length=8, eos_token_id=eos)
    ended = used.drain()[rid]
    assert ended.finish_reason == "eos" and len(ended.tokens) < 8
    assert used.metrics.snapshot()["decode_ticks_overlapped"] > 0
    tokens, state = run(used, ended)
    fresh_tokens, fresh_state = run(engine_of(build(), variables, slots=1),
                                    None)
    np.testing.assert_array_equal(tokens, fresh_tokens)
    for mine, theirs in zip(state, fresh_state):
        np.testing.assert_array_equal(mine, theirs)
    assert used.metrics.snapshot()["ssm_state_resets"] == 2


# ------------------------------------------------------------- the kernels

def _operands(batch, rows, width, n=16, seed=0):
    rng = np.random.RandomState(seed)
    u = jnp.asarray(rng.randn(batch, rows, width), jnp.float32)
    dt = jnp.asarray(np.abs(rng.randn(batch, rows, width)) * 0.1, jnp.float32)
    a = -jnp.exp(jnp.asarray(rng.randn(n, width) * 0.3, jnp.float32))
    b = jnp.asarray(rng.randn(batch, rows, n), jnp.float32)
    c = jnp.asarray(rng.randn(batch, rows, n), jnp.float32)
    h0 = jnp.asarray(rng.randn(batch, n, width), jnp.float32)
    return u, dt, a, b, c, h0


@pytest.mark.parametrize("batch,rows,width", [(1, 8, 128), (2, 48, 256),
                                              (1, 512, 640)])
def test_the_scan_kernel_matches_the_lax_scan(monkeypatch, batch, rows, width):
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    args = _operands(batch, rows, width)
    want_y, want_h = ssm_scan.selective_scan_plain(*args)
    got_y, got_h = jax.jit(ssm_scan.selective_scan)(*args)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_h, want_h, rtol=1e-5, atol=1e-5)
    # rows that are no tokens (dt = 0) leave the state as it was, bit for bit
    masked = (args[0], args[1].at[:, rows // 2:].set(0.0), *args[2:])
    half = tuple(t[:, :rows // 2] if t.ndim == 3 and t.shape[1] == rows else t
                 for t in args)
    if (rows // 2) % 8 == 0:
        np.testing.assert_array_equal(
            jax.jit(ssm_scan.selective_scan)(*masked)[1],
            jax.jit(ssm_scan.selective_scan)(*half)[1])
    # a skipped call hands the initial state back
    skipped = jax.jit(lambda *x: ssm_scan.selective_scan(
        *x, skip=jnp.bool_(True)))(*args)[1]
    np.testing.assert_array_equal(skipped, args[-1])


@pytest.mark.parametrize("lanes,width", [(4, 128), (16, 256), (8, 1280)])
def test_the_step_kernel_matches_the_lax_scan(monkeypatch, lanes, width):
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    u, dt, a, b, c, _ = _operands(lanes, 1, width, seed=1)
    rng = np.random.RandomState(2)
    state = jnp.asarray(rng.randn(3, lanes, 16, width), jnp.float32)
    fresh = jnp.asarray(rng.rand(lanes) < 0.3)
    idle = np.arange(lanes) % 4 == 1
    dt = jnp.where(idle[:, None, None], 0.0, dt)
    args = (state, jnp.int32(1), u[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], fresh)
    want_y, want = ssm_scan.selective_step_plain(*args)
    got_y, got = jax.jit(ssm_scan.selective_step)(*args)
    got, state = np.asarray(got), np.asarray(state)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the other layers, and the lanes that are not decoding, bit for bit
    np.testing.assert_array_equal(got[[0, 2]], state[[0, 2]])
    keeps = idle & ~np.asarray(fresh)
    np.testing.assert_array_equal(got[1][keeps], state[1][keeps])
    # ``skip`` False is the same call, and True hands the leaf back as it
    # was (the fresh lanes' states too): the kernel and the plain path
    step = jax.jit(ssm_scan.selective_step, static_argnames=("kernel",))
    for kernel in (True, False):
        for kept, as_ever in zip(
                step(*args, skip=jnp.bool_(False), kernel=kernel),
                step(*args, kernel=kernel)):
            # (on the CPU the fusions are XLA's to choose, the interpreted
            # kernel's too: an ulp)
            np.testing.assert_allclose(kept, as_ever, rtol=1e-4, atol=1e-6)
        np.testing.assert_array_equal(
            step(*args, skip=jnp.bool_(True), kernel=kernel)[1], state)


def test_the_engine_with_the_kernels_on_matches_the_reference(monkeypatch,
                                                              variables):
    """The three kernels (scan, step, paged decode over 4 query heads on one
    key head) in interpret mode under the engine's own prefill and tick."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    engine = engine_of(build(use_flash_attention=True), variables)
    logits, _ = served_of(engine).sequence(TOKENS[0][:48], 40, CHUNK, CHUNK)
    expected = reference(variables["params"], TOKENS[0][:48])[40 - CHUNK:]
    assert distance(logits, expected) < TOL


def _tpu_device():
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    except Exception as err:  # noqa: BLE001: no libtpu in this environment
        pytest.skip(f"no compile-only TPU topology here: {err}")


def test_the_compiled_tick_holds_no_copy_of_a_state_leaf(monkeypatch,
                                                         variables):
    """The tick compiled for a v5e (XLA:TPU and Mosaic, no chip): the
    donated ``ssm_state`` leaf is aliased to the program's output, and the
    program's temporaries are smaller than that one leaf, so no copy of it
    exists."""
    from jax.sharding import SingleDeviceSharding

    import fleetx_tpu.ops.pallas.decode_attention as da

    sharding = SingleDeviceSharding(_tpu_device())
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    monkeypatch.setattr(ssm_scan, "_interpret", lambda: False)
    monkeypatch.setattr(da, "_interpret", lambda: False)
    monkeypatch.setattr(write_rows, "_interpret", lambda: False)
    sizes = dict(hidden_size=256, num_attention_heads=2, head_size=128,
                 ffn_hidden_size=256, dense_ffn_hidden_size=256,
                 use_flash_attention=True, dtype=jnp.bfloat16)
    model = build(**sizes)
    held = jax.eval_shape(lambda: flax.core.meta.unbox(model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))))
    engine = engine_of(
        model, jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), held),
        slots=64, page_size=16, cache_len=128)

    def abstract(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    held = {path[-1].key: x for path, x in jax.tree_util.tree_flatten_with_path(
        engine.cache_manager.cache)[0]}
    leaf, pools = held["ssm_state"], [held["cached_key"], held["cached_value"]]
    tick = jax.jit(engine._decode_fn, static_argnums=(4,),
                   donate_argnums=(1, 2))
    compiled = tick.lower(
        abstract(engine.params), abstract(engine.cache_manager.cache),
        abstract(engine._state), abstract(jnp.asarray(
            engine.cache_manager.tables)), True).compile()
    text = compiled.as_text()
    assert ssm_scan.STEP_KERNEL_NAME in text
    # the key and value pools likewise: written by the kernel that branches
    # on the layer's kind, in place, and by no scatter
    assert write_rows.KERNEL_NAME in text and "scatter(" not in text
    memory = compiled.memory_analysis()
    kept = leaf.nbytes + sum(pool.nbytes for pool in pools)
    assert memory.alias_size_in_bytes >= kept
    assert memory.temp_size_in_bytes < min(leaf.nbytes, pools[0].nbytes), (
        memory.temp_size_in_bytes, leaf.nbytes, pools[0].nbytes)


@pytest.mark.parametrize("lanes,rows", [(256, 1), (1, 768)],
                         ids=["tick", "prefill"])
def test_the_writer_compiles_for_the_v5e_at_the_cells_shapes(monkeypatch,
                                                             lanes, rows):
    """``fleetx_write_rows`` at the served shapes (two bfloat16 pools of
    32,770 pages of 16 rows of 128; a tick's 256 lanes, a prefill's 768
    rows), Mosaic's own passes and all: the pools aliased, no temporary."""
    from jax.sharding import SingleDeviceSharding

    from fleetx_tpu.models.gpt import paged_write

    sharding = SingleDeviceSharding(_tpu_device())
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    monkeypatch.setattr(write_rows, "_interpret", lambda: False)

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = abstract((32770, 16, 128), jnp.bfloat16)
    new = abstract((lanes * rows, 128), jnp.bfloat16)
    compiled = jax.jit(
        lambda pools, news, tables, wpos, keep: paged_write.write_rows_or_skip(
            pools, news, tables, wpos, 1024, keep),
        donate_argnums=(0,)).lower(
            [pool, pool], [new, new], abstract((lanes, 64), jnp.int32),
            abstract((lanes,), jnp.int32), abstract((), jnp.bool_)).compile()
    assert write_rows.KERNEL_NAME in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * 32770 * 16 * 128 * 2
    assert memory.temp_size_in_bytes < 1 << 20


# ------------------------------------------- the configuration and the engine

def test_the_published_configuration_counts_3_029_337_472_parameters():
    sizes = dict(harness.load_json("perfbench/configs/jamba2-3b.json")["model"])
    model = GPTForPretraining(GPTConfig.from_model_config(
        dict(sizes, fuse_attn_qkv=True)))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    counts = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            flax.core.meta.unbox(shapes))[0]:
        kind = next((k.key for k in path if getattr(k, "key", "") in (
            "mamba", "attention", "dense")), "other")
        counts[kind] = counts.get(kind, 0) + int(np.prod(leaf.shape))
    assert counts["mamba"] == 26 * (41_241_792 + 2_560)
    assert counts["attention"] == 2 * (13_762_560 + 2_560)
    assert counts["dense"] == 28 * (62_914_560 + 2_560)
    assert counts["other"] == 167_772_160 + 2_560
    assert sum(counts.values()) == 3_029_337_472
    assert 26 * flops_ssm.lane_state_bytes(sizes) == 9_318_400


def test_the_lanes_state_is_counted_once_a_lane(variables):
    engine = engine_of(build(), variables, slots=3)
    engine.submit(TOKENS[0][:20], max_length=3)
    engine.drain()
    counters = engine.metrics.snapshot()
    lane = 4 * (16 * 128 * 4 + 3 * 128 * 4)     # float32 model: 4 B filter rows
    assert engine.cache_manager.lane_bytes == lane
    assert counters["state_bytes_lanes"] == 3 * lane
    assert counters["ssm_state_resets"] == 1
    assert engine.health()["state_bytes"] == {"kv": 0, "ssm": 3 * lane}
    assert engine.capabilities.state_kinds == ("kv", "ssm")
    # the once-a-lane home knows the kind and its leaves from the MODEL
    # (``cfg.lane_state``), under whose name the resets are counted
    assert build().cfg.lane_state == ("ssm", ("ssm_state", "ssm_conv"))
    assert engine.cache_manager.lane_state_kind == "ssm"
    # the lane's address leads its row of the table
    np.testing.assert_array_equal(engine.cache_manager.tables[:, 0],
                                  np.arange(3))
    assert engine.cache_manager.lane_tables(2)[0] == 2


@pytest.mark.parametrize("asked,cause", [
    ({"prefix_cache": True}, "prefix reuse.*selective-scan"),
    ({"role": "prefill"}, "prefill or decode role.*selective-scan"),
    ({"role": "decode"}, "prefill or decode role.*selective-scan"),
    ({"host_cache_bytes": 1 << 20}, "host or disk page tier.*selective-scan"),
    ({"spec": True}, "speculative decoding"),
])
def test_what_the_engine_refuses_for_lane_resident_state(variables, asked,
                                                         cause):
    with pytest.raises(ValueError, match=cause):
        engine_of(build(), variables, **asked)


def test_spans_carry_the_scans_rows_and_the_ticks_lanes(variables):
    from fleetx_tpu.obs.tracing import get_recorder

    engine = engine_of(build(), variables, prefill_chunk=CHUNK)
    get_recorder().clear()  # the ring is the process's: other tests' spans
    engine.submit(TOKENS[0][:13], max_length=4)   # one call, bucket 16
    engine.submit(TOKENS[1][:40], max_length=4)   # chunks of 16, 16, 8
    engine.drain()
    spans = get_recorder().spans()
    admits = [s.attrs["scan_rows"] for s in spans
              if s.name == "serving.admit" and "scan_rows" in s.attrs]
    chunks = [s.attrs["scan_rows"] for s in spans
              if s.name == "serving.prefill_chunk"]
    ticks = [s.attrs for s in spans if s.name == "serving.decode"]
    assert admits == [16] and chunks == [16, 16, 8]
    assert ticks and all(0 < t["state_lanes"] <= 2 and t["attn_rows"] > 0
                         for t in ticks[-3:])
    # the plan's constants on every program's span: the layers whose
    # key/value write lands and the layers whose state advances
    programs = [s.attrs for s in spans
                if s.name in ("serving.prefill", "serving.decode")]
    kinds = [t.endswith("attention") for t in TYPES]
    assert len(programs) > len(ticks) and all(
        (p["kv_write_layers"], p["state_layers"])
        == (sum(kinds), len(kinds) - sum(kinds)) for p in programs)


def test_a_stack_with_both_recurrent_kinds_is_refused():
    with pytest.raises(NotImplementedError, match="conv AND mamba"):
        build(layer_types=("conv", "mamba") + TYPES[2:])
    with pytest.raises(ValueError, match="mamba_dt_rank"):
        build(mamba_dt_rank=None)
    with pytest.raises(NotImplementedError, match="rope_layout"):
        build(rope_layout=(1, 0, 0, 0, 0, 0))
