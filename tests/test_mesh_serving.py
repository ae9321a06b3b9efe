"""Mesh-sharded serving gates (ISSUE 14).

The contract (docs/SERVING.md "Mesh-sharded serving"): a ``ServingEngine``
handed a TP/FSDP mesh shards params + the KV page pool (heads over
``mp``, int8 scale leaves included) and runs every jitted device call —
prefill, decode tick, spec verify, probe, replay — under the mesh, with
the flash-decode kernels invoked per-shard inside ``shard_map``. Host
bookkeeping is mesh-agnostic, so greedy token streams must be
BYTE-IDENTICAL to the single-device engine, per-device cache bytes must
divide by the mp extent, and ``recover()`` must rebuild sharded device
state from the same host truth.

Compact mp2 gates (paged parity + cache-bytes ÷2, flash-sharded-kernel
dispatch, replay recovery, sharding-spec units) are tier-1;
the wider matrix (int8, speculative, chunked, sampling, mp2 x fsdp2)
rides the slow tier per the ISSUE 14 budget audit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import sharing_programs

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh
from fleetx_tpu.serving import ServingEngine

CFG = GPTConfig(
    vocab_size=96,  # divides over mp2 — the vocab-parallel axis shards
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
                          pad_token_id=95)
PROMPTS = [np.asarray([1, 2, 3], np.int32),
           np.asarray([4, 5, 6, 7, 8], np.int32),
           np.asarray([9, 10], np.int32)]


@pytest.fixture(scope="module")
def model_and_params():
    model = GPTForPretraining(CFG)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return model, params


@pytest.fixture(scope="module")
def mp2(eight_devices):
    return build_mesh(MeshConfig(mp=2), eight_devices[:2])


@sharing_programs
def _engine(model, params, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("cache_len", 32)
    kw.setdefault("gen_cfg", GREEDY)
    kw.setdefault("prefill_bucket", 4)
    return ServingEngine(model, params, **kw)


def _run(engine, prompts=PROMPTS, max_length=5):
    rids = [engine.submit(p, max_length=max_length) for p in prompts]
    res = engine.drain()
    return [np.asarray(res[r].tokens) for r in rids]


def _assert_streams_equal(got, want, label):
    for i, (a, b) in enumerate(zip(want, got)):
        np.testing.assert_array_equal(
            b, a, err_msg=f"{label}: request {i} diverged on the mesh")


# ------------------------------------------------- tier-1 compact gates

def test_mesh_paged_parity_cache_bytes_and_gauge(model_and_params, mp2):
    """The headline gate: an mp2 paged engine emits byte-identical greedy
    streams to the single-device engine, and its measured PER-DEVICE
    cache bytes (cache_nbytes() AND the fleetx_serving_kv_cache_bytes
    gauge) are half the single-device engine's — the heads-over-mp shard
    is real, not cosmetic."""
    model, params = model_and_params
    single = _engine(model, params)
    want = _run(single)
    single_bytes = single.cache_manager.cache_nbytes()
    meshed = _engine(model, params, mesh=mp2)
    got = _run(meshed)
    _assert_streams_equal(got, want, "paged mp2")
    mesh_bytes = meshed.cache_manager.cache_nbytes()
    # K/V leaves split exactly in two; only the per-layer cache_index
    # scalars replicate, so the ratio sits a hair above 0.5
    assert 0.45 <= mesh_bytes / single_bytes <= 0.55, (
        f"per-device cache bytes {mesh_bytes} vs single {single_bytes}: "
        "heads-over-mp sharding did not halve the footprint")
    snap = meshed.metrics.snapshot()
    assert snap["kv_cache_bytes"] == mesh_bytes
    assert snap["mesh_devices"] == 2 and snap["mesh"] == "mp2"
    assert single.metrics.snapshot()["mesh_devices"] == 1


@pytest.mark.slow  # 14.5s (PR 16 tier-1 budget audit): meshed byte
# parity stays tier-1 via test_mesh_paged_parity_cache_bytes_and_gauge;
# the which-kernel-ran assertion rides with the other mesh-matrix
# variants behind the slow mark (chaos serving_mesh drives it e2e)
def test_mesh_flash_decode_takes_sharded_kernels(model_and_params, mp2,
                                                 monkeypatch):
    """The paged Pallas decode kernel (interpret mode) must actually run
    under the mesh: for a tileable mp2 decode the dense fallback is NOT
    taken — the kernel entry point is invoked with ``mesh=`` set (the
    shard_map path), the contiguous kernel never — and tokens still
    match the single-device flash engine byte-for-byte."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    _, params = model_and_params
    flash_model = GPTForPretraining(
        dataclasses.replace(CFG, use_flash_attention=True))

    import fleetx_tpu.ops.pallas.decode_attention as da

    calls = {"paged": [], "contig": []}
    orig_paged, orig_contig = (da.flash_decode_paged_attention,
                               da.flash_decode_attention)

    def wrap_paged(*a, **kw):
        calls["paged"].append(kw.get("mesh"))
        return orig_paged(*a, **kw)

    def wrap_contig(*a, **kw):
        calls["contig"].append(kw.get("mesh"))
        return orig_contig(*a, **kw)

    monkeypatch.setattr(da, "flash_decode_paged_attention", wrap_paged)
    monkeypatch.setattr(da, "flash_decode_attention", wrap_contig)

    want_paged = _run(_engine.__wrapped__(flash_model, params))
    assert calls["paged"] and all(m is None for m in calls["paged"])
    calls["paged"].clear()
    got_paged = _run(_engine.__wrapped__(flash_model, params, mesh=mp2))
    # the decode tick dispatched the PAGED kernel with the mesh — the
    # dense fallback was not taken, and the call went through shard_map
    assert calls["paged"], "mp2 decode never reached the paged flash kernel"
    assert any(m is mp2 for m in calls["paged"]), (
        "paged flash kernel ran bare under the mesh (GSPMD would "
        "replicate the head-sharded pool around it)")
    _assert_streams_equal(got_paged, want_paged, "flash paged mp2")
    assert not calls["contig"], "the engine holds pages, not slot rows"


def test_mesh_recover_rebuilds_sharded_state(model_and_params, mp2):
    """Replay recovery on a sharded engine: an injected decode-tick fault
    rolls back, recover() rebuilds the SHARDED cache/pool from host truth
    and re-prefills — streams stay byte-identical to the single-device
    engine and the rebuilt cache keeps its per-device footprint."""
    from fleetx_tpu.resilience.faults import faults

    model, params = model_and_params
    want = _run(_engine(model, params))
    faults.configure(tick_raise="1")
    try:
        eng = _engine(model, params, mesh=mp2)
        got = _run(eng)
    finally:
        faults.reset()
    assert eng.metrics.engine_recoveries == 1, eng.metrics.snapshot()
    _assert_streams_equal(got, want, "recovered mp2")
    eng.cache_manager.pool.check_invariants()
    # the REBUILT cache is still the per-device shard, not a gathered copy
    single_bytes = _engine(model, params).cache_manager.cache_nbytes()
    assert eng.cache_manager.cache_nbytes() < 0.55 * single_bytes


def test_mesh_validation_and_spec_units(model_and_params, eight_devices):
    """Construction contract + sharding-spec units: pp/cp meshes and
    non-dividing heads raise with a cause; serving_param_shardings drops
    axes that do not divide (prime vocab, keepdims-1 scale dims) instead
    of erroring, and quantized {_q8, _scale} leaves inherit the kernel's
    spec."""
    from jax.sharding import PartitionSpec as P

    from fleetx_tpu.ops.quant import quantize_tree_int8
    from fleetx_tpu.parallel.sharding import (
        make_rules,
        serving_param_shardings,
    )

    model, params = model_and_params
    pp_mesh = build_mesh(MeshConfig(pp=2), eight_devices[:2])
    with pytest.raises(ValueError, match="pp/cp"):
        _engine(model, params, mesh=pp_mesh)
    mp4 = build_mesh(MeshConfig(mp=4), eight_devices[:4])
    odd_model = GPTForPretraining(
        dataclasses.replace(CFG, num_attention_heads=6, hidden_size=48))
    with pytest.raises(ValueError, match="heads"):
        _engine(odd_model, params, mesh=mp4)

    # spec units: prime-vocab embedding replicates, heads shard, a
    # quantized kernel's _q8 keeps the spec and its _scale replicates
    prime_model = GPTForPretraining(dataclasses.replace(CFG, vocab_size=97))
    prime_params = jax.eval_shape(lambda: prime_model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32)))["params"]
    mesh = build_mesh(MeshConfig(mp=2), eight_devices[:2])
    q = quantize_tree_int8(jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.float32)
        if not hasattr(s, "unbox") else jnp.zeros(s.value.shape, jnp.float32),
        prime_params, is_leaf=lambda x: hasattr(x, "unbox")))
    sh = serving_param_shardings(prime_params, q, mesh, make_rules())
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(sh)[0]}
    emb = flat["gpt/word_embeddings/_q8"]
    assert emb.spec == P(None, None), emb.spec  # 97 % 2 != 0 -> dropped
    qkv = flat["gpt/layers/layer/attn/qkv_proj/kernel/_q8"]
    assert "mp" in str(qkv.spec)  # heads axis genuinely shards
    qkv_scale = flat["gpt/layers/layer/attn/qkv_proj/kernel/_scale"]
    assert all(e is None for e in qkv_scale.spec), qkv_scale.spec


@pytest.mark.slow  # 12.3s (PR 14 budget audit): parity is guard-neutral
def test_dp_mesh_one_shot_flash_guard(eight_devices, monkeypatch):
    # (both dispatch outcomes are byte-exact — this locks the perf
    # pathology guard); the serving-side sharded dispatch stays tier-1
    # via test_mesh_flash_decode_takes_sharded_kernels
    """One-shot generate() under a DATA-PARALLEL mesh keeps its cache
    batch-sharded over dp, so the flash kernel must either shard the
    batch axis along (batch divides dp: shard_map engages, parity holds)
    or fall back dense (batch does not divide: a shard_map that
    replicated the batch axis would all-gather the whole cache per
    step). Locks the post-review dp guard in decode_mesh_shardable."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    from flax import linen as nn

    import fleetx_tpu.ops.pallas.decode_attention as da
    from fleetx_tpu.models.gpt.generation import generate
    from fleetx_tpu.parallel.mesh import use_mesh
    from fleetx_tpu.parallel.sharding import make_rules

    flash_model = GPTForPretraining(
        dataclasses.replace(CFG, use_flash_attention=True))
    params = jax.jit(flash_model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    gcfg = dataclasses.replace(GREEDY, max_length=3, eos_token_id=-1)
    calls = []
    orig = da.flash_decode_attention

    def wrap(*a, **kw):
        calls.append(kw.get("mesh"))
        return orig(*a, **kw)

    monkeypatch.setattr(da, "flash_decode_attention", wrap)
    ids2 = np.asarray([[5, 6, 7], [11, 3, 8]], np.int32)  # 2 % dp2 == 0
    ids3 = np.asarray([[5, 6, 7], [11, 3, 8], [1, 2, 3]], np.int32)  # 3 % 2
    plain2 = np.asarray(generate(flash_model, params, jnp.asarray(ids2), gcfg))
    plain3 = np.asarray(generate(flash_model, params, jnp.asarray(ids3), gcfg))
    dp2 = build_mesh(MeshConfig(dp=2), eight_devices[:2])
    calls.clear()
    with use_mesh(dp2), nn.logical_axis_rules(make_rules()):
        out2 = np.asarray(generate(flash_model, params, jnp.asarray(ids2),
                                   gcfg))
    assert any(m is dp2 for m in calls), (
        "dividing batch under dp2 should take the sharded flash path")
    np.testing.assert_array_equal(out2, plain2)
    calls.clear()
    with use_mesh(dp2), nn.logical_axis_rules(make_rules()):
        out3 = np.asarray(generate(flash_model, params, jnp.asarray(ids3),
                                   gcfg))
    assert not any(m is not None for m in calls), (
        "non-dividing batch under dp2 must take the dense fallback — a "
        "replicated-batch shard_map would all-gather the dp-sharded cache")
    np.testing.assert_array_equal(out3, plain3)


# ------------------------------------------------------- slow matrix

@pytest.mark.slow  # ISSUE 14 budget audit: the compact mp2 gates above
def test_mesh_matrix_int8_spec_chunked(model_and_params, mp2):
    # keep the tier-1 contract; this is the wide config sweep
    """mp2 parity across the feature matrix: int8 KV+weights (meshed int8
    == single-device int8, scale leaves shard along their pages), the
    speculative engine (draft/verify under the mesh), and chunked prefill
    (multi-call cache writes through the sharded seam)."""
    model, params = model_and_params
    for kw in (
        dict(kv_dtype="int8", weight_dtype="int8"),
        dict(spec=True, spec_k=4),
        dict(prefill_chunk=3),
    ):
        want = _run(_engine(model, params, **kw))
        got = _run(_engine(model, params, mesh=mp2, **kw))
        _assert_streams_equal(got, want, f"mp2 {kw}")


@pytest.mark.slow  # ISSUE 14 budget audit
def test_mesh_mp2_fsdp2_and_sampling(model_and_params, eight_devices):
    """mp2 x fsdp2 (params additionally fsdp-sharded over embed) keeps
    byte parity, and SAMPLING requests draw identical streams on and off
    the mesh (the per-request rng path is mesh-invariant)."""
    model, params = model_and_params
    mesh4 = build_mesh(MeshConfig(fsdp=2, mp=2), eight_devices[:4])
    want = _run(_engine(model, params))
    got = _run(_engine(model, params, mesh=mesh4))
    _assert_streams_equal(got, want, "mp2xfsdp2")

    samp = dataclasses.replace(GREEDY, decode_strategy="sampling",
                               temperature=1.3, top_k=8)
    mesh2 = build_mesh(MeshConfig(mp=2), eight_devices[:2])

    def sample(engine):
        rids = [engine.submit(p, max_length=6, seed=11 + i)
                for i, p in enumerate(PROMPTS)]
        res = engine.drain()
        return [np.asarray(res[r].tokens) for r in rids]

    want_s = sample(_engine(model, params, gen_cfg=samp))
    got_s = sample(_engine(model, params, gen_cfg=samp, mesh=mesh2))
    _assert_streams_equal(got_s, want_s, "sampling mp2")
