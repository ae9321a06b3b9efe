"""A.X-K1's block on the CPU at a small size, seeded random weights: latent
attention (MLA) served through the page pool, cold and after a trie hit on
latent pages, against the plain float32 reference
(``perfbench/reference/axk1_f32.py``); the absorbed form against the
materialised one; the paged latent kernel against its plain twin; the
group-limited gate on a written-out case; an expert layer that holds a
share, whose shares ADD UP to the uncut layer; what the family refuses at
construction; and that nothing of it reaches the configurations that hold
every expert."""

import dataclasses

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import plain_greedy, sharing_programs, traced_apply

from fleetx_tpu.models.gpt import block_fields, latent
from fleetx_tpu.models.gpt.generation import (GenerationConfig,
                                              init_decode_cache)
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.ops.pallas import mla_decode
from fleetx_tpu.parallel import moe, moe_share
from perfbench.reference import axk1_f32

SIZES = dict(
    vocab_size=128, hidden_size=64, num_layers=3, num_attention_heads=4,
    ffn_hidden_size=32, max_position_embeddings=512,
    position_embedding="rope", norm="rmsnorm", norm_eps=1e-6,
    mlp_act="swiglu", use_bias=False, tie_word_embeddings=False,
    layer_types=["latent_attention"] * 3, num_dense_layers=1,
    dense_ffn_hidden_size=96, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_scaling_factor=32.0, rope_scaling_mscale=1.0,
    rope_scaling_mscale_all_dim=1.0, rope_scaling_original_max_position=64,
    num_experts=4, num_routed_experts=16, first_expert_held=4, top_k=4,
    gate="sigmoid_topk", n_group=4, topk_group=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, num_shared_experts=1,
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    dtype="float32", use_flash_attention=False)
TOL = 2e-5  # float32 against float32: 1.6e-7 read, of logits up to 0.63


def build(**over):
    cfg = GPTConfig.from_model_config({**SIZES, **over})
    model = GPTForPretraining(cfg)
    variables = flax.core.meta.unbox(jax.jit(lambda k: model.init(
        k, np.zeros((1, 8), np.int32)))(jax.random.PRNGKey(0)))
    return model, variables


@pytest.fixture(scope="module")
def built():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(1, 128, 48, dtype=np.int32)


@pytest.fixture(scope="module")
def reference(built, tokens):
    return np.asarray(axk1_f32.configured(SIZES)(built[1]["params"], tokens))


def paged(model, pages=13, page=8, cache_len=96):
    return model.clone(cfg=dataclasses.replace(
        model.cfg, decode_cache_len=cache_len, decode_num_pages=pages,
        decode_page_size=page))


def _forward(model, params, cache, ids, at, tables, rows=None):
    pos = at[:, None] + jnp.arange(ids.shape[1])[None]
    logits, mut = model.apply(
        {"params": params, "cache": cache}, ids, pos, rows, decode=True,
        cache_positions=at, block_tables=tables, mutable=["cache"])
    return logits, mut["cache"]


def traced_anew():
    """``_forward`` as one program a model and a shape of call, as the engine
    runs it (eagerly every primitive of every tick is a dispatch of its own).
    A test that patches what a trace reads takes one of its own: jax keeps a
    trace by the function's identity."""
    return jax.jit(lambda *args: _forward(*args), static_argnums=0)


forward = traced_anew()


def test_the_plain_forward_is_the_reference(built, tokens, reference):
    model, variables = built
    plain = traced_apply(model, variables, jnp.asarray(tokens[None]))[0]
    assert np.abs(np.asarray(plain) - reference).max() < TOL


def test_the_cache_holds_latents_alone_and_the_leaf_is_as_wide_as_held(built):
    cache = init_decode_cache(paged(built[0]), 2)
    shapes = {path[-1].key: leaf.shape for path, leaf in
              jax.tree_util.tree_flatten_with_path(cache)[0]}
    # three layers' pages flat; c_kv 32 a row, k_r 8 held in a 128-lane tile
    assert shapes == {"cached_key": (39, 8, 32), "cached_value": (39, 8, 128),
                      "moe_stats": (2, 24)}
    assert latent.rope_leaf_width(built[0].cfg) == 128
    assert built[0].cfg.state_kinds == ("latent",)


@pytest.mark.parametrize("chunks", [(32,), (16, 16), (8, 24)])
def test_chunked_prefill_then_absorbed_ticks_are_the_reference(
        built, tokens, reference, chunks):
    """A chunk attends over the latents read back from the pool and its own
    (materialised, in key blocks); a tick of two lanes, one idle, takes the
    absorbed form: both are the reference's full forward."""
    model, variables = built
    served = paged(model)
    cache = init_decode_cache(served, 2)
    table = jnp.arange(1, 13, dtype=jnp.int32)[None]
    out, at = [], 0
    for n in chunks:
        logits, cache = forward(served, variables["params"], cache,
                                jnp.asarray(tokens[None, at:at + n]),
                                jnp.asarray([at]), table)
        out.append(logits[0])
        at += n
    tables = jnp.concatenate([table, jnp.zeros_like(table)])
    for i in range(at, 48):
        logits, cache = forward(
            served, variables["params"], cache,
            jnp.asarray([[tokens[i]], [0]]), jnp.asarray([i, 95]), tables,
            jnp.asarray([[True], [False]]))
        out.append(logits[0])
        assert np.isfinite(np.asarray(logits)).all()   # the idle lane too
    assert np.abs(np.asarray(jnp.concatenate(out)) - reference).max() < TOL


def test_key_blocks_past_the_chunk_are_not_computed_and_nothing_changes(
        built, tokens, reference, monkeypatch):
    """With key blocks of 16 rows a chunk behind 32 rows runs three of the
    lane's six blocks: the same logits."""
    monkeypatch.setattr(latent, "KEY_BLOCK", 16)
    forward = traced_anew()
    model, variables = built
    served = paged(model)
    cache = init_decode_cache(served, 1)
    table = jnp.arange(1, 13, dtype=jnp.int32)[None]
    out = []
    for at in (0, 16, 32):
        logits, cache = forward(served, variables["params"], cache,
                                jnp.asarray(tokens[None, at:at + 16]),
                                jnp.asarray([at]), table)
        out.append(logits[0])
    assert np.abs(np.asarray(jnp.concatenate(out)) - reference).max() < TOL


@sharing_programs
def engine_of(model, variables, **kwargs):
    from fleetx_tpu.serving import ServingEngine

    defaults = dict(
        slots=3, cache_len=128, page_size=8, num_pages=3 * 16 + 1,
        prefill_chunk=32, prefill_bucket=16, prefix_cache=True,
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=6))
    return ServingEngine(model, variables, **{**defaults, **kwargs})


def test_the_engine_serves_it_cold_and_on_a_hit_on_latent_pages(built):
    """Through ``ServingEngine``: chunked prefill, the tick, and a second
    and third question that resume from the document's latent pages in the
    trie; greedy tokens are the plain model's."""
    model, variables = built
    engine = engine_of(model, variables)
    rng = np.random.default_rng(1)
    document = rng.integers(1, 128, 64, dtype=np.int32)

    saved = []
    for q in range(3):
        prompt = np.concatenate([document, rng.integers(
            1, 128, 10 + q, dtype=np.int32)])
        rid = engine.submit(prompt, max_length=6)
        result = engine.drain()[rid]
        assert [int(t) for t in result.tokens] == plain_greedy(
            model, variables, prompt, 6)
        saved.append(engine.metrics.snapshot()["prefill_tokens_saved"])
    assert saved == [0, 64, 128]
    snap = engine.metrics.snapshot()
    assert snap["latent_pages_in_use"] == 0 and snap["latent_pages_in_trie"] > 0
    # three layers x 8 rows x (32 + 128) columns x 4 bytes
    assert snap["latent_page_bytes"] == 3 * 8 * 160 * 4
    # the device counted the pairs laid out HERE: under all that were routed
    assert 0 < snap["moe_tick_pairs"] < snap["moe_tick_layer_calls"] * 3 * 4
    assert engine.capabilities.state_kinds == ("latent",)
    engine.cache_manager.pool.check_invariants()


def test_spans_carry_the_live_rows_and_all_the_pairs(built):
    import time

    from fleetx_tpu.obs.tracing import get_recorder

    began = time.perf_counter()
    engine = engine_of(*built)
    engine.submit(np.arange(1, 41, dtype=np.int32), max_length=3)
    engine.drain()
    spans = [s for s in get_recorder().spans() if s.start_s >= began]
    ticks = [s for s in spans if s.name == "serving.decode"
             and "latent_rows" in s.attrs]
    chunks = [s for s in spans if s.name == "serving.prefill_chunk"
              and "latent_rows" in s.attrs]
    assert ticks and chunks
    # a tick routes every lane's row: 3 lanes x top 4 x 2 expert layers
    assert ticks[-1].attrs["pairs"] == 3 * 4 * 2
    assert chunks[0].attrs["latent_rows"] == 32           # the first chunk
    assert chunks[0].attrs["pairs"] == 32 * 4 * 2
    assert chunks[1].attrs["latent_rows"] == 40


@pytest.mark.parametrize("kwargs, refused", [
    (dict(kv_dtype="int8"), "supports_int8_kv"),
    (dict(weight_dtype="int8"), "supports_int8_weights"),
    (dict(spec=True, spec_k=2), "supports_spec"),
    (dict(host_cache_bytes=1 << 20), "supports_host_spill"),
    (dict(role="prefill"), "supports_roles"),
])
def test_what_the_family_cannot_ride_is_refused_at_construction(
        built, kwargs, refused):
    with pytest.raises(ValueError, match="does not support") as err:
        engine_of(*built, **kwargs)
    assert refused in str(err.value), refused


def test_a_mesh_over_the_latent_is_refused_at_construction(built):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "mp"))
    with pytest.raises(ValueError, match="supports_mesh"):
        engine_of(*built, mesh=mesh)


# ------------------------------------------------------- the latent kernel

@pytest.mark.parametrize("ends", [(5, 0, 48), (17, 33, 1), (0, 0, 9),
                                  (48, 48, 48)])
@pytest.mark.parametrize("block_rows", [16, 24, 512])
def test_the_paged_latent_kernel_is_its_plain_twin(monkeypatch, ends,
                                                   block_rows):
    """Interpreted: blocks of 2, 3 and all of a lane's 6 pages; lanes
    without rows give zeros and start no copy."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    rng = np.random.default_rng(0)
    b, h, c, r, ps, npg, pages = 3, 8, 128, 128, 8, 6, 40
    q_c, q_r = (jnp.asarray(rng.normal(size=(b, h, w)), jnp.float32)
                for w in (c, r))
    ckv, kr = (jnp.asarray(rng.normal(size=(pages, ps, w)), jnp.float32)
               for w in (c, r))
    tables = jnp.asarray(rng.permutation(np.arange(1, pages))[:b * npg]
                         .reshape(b, npg), jnp.int32)
    end = jnp.asarray(ends, jnp.int32)
    want = mla_decode.mla_decode_reference(q_c, q_r, ckv, kr, tables=tables,
                                           end=end, scale=0.1)
    got = mla_decode.mla_decode_paged(q_c, q_r, ckv, kr, tables=tables,
                                      end=end, scale=0.1,
                                      block_rows=block_rows)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6
    for lane, n in enumerate(ends):
        if n == 0:
            assert not np.asarray(got[lane]).any()


def test_the_tick_through_the_kernel_is_the_tick_without_it(
        built, tokens, monkeypatch):
    """The model's absorbed tick with the Pallas kernel (interpreted) and
    with its plain twin."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    forward = traced_anew()
    model, variables = built
    out = {}
    for flash in (False, True):
        served = paged(model.clone(cfg=dataclasses.replace(
            model.cfg, use_flash_attention=flash)))
        cache = init_decode_cache(served, 2)
        table = jnp.arange(1, 13, dtype=jnp.int32)[None]
        _, cache = forward(served, variables["params"], cache,
                           jnp.asarray(tokens[None, :24]), jnp.asarray([0]),
                           table)
        tables = jnp.concatenate([table, jnp.zeros_like(table)])
        out[flash], _ = forward(
            served, variables["params"], cache,
            jnp.asarray([[tokens[24]], [0]]), jnp.asarray([24, 95]), tables,
            jnp.asarray([[True], [False]]))
    assert np.abs(np.asarray(out[True][0]) - np.asarray(out[False][0])
                  ).max() < TOL


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernel_compiles_for_the_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """16 lanes x 64 heads over the cell's pool: 6 x 38,401 pages of 16 rows
    of 512 + 128 columns (4.72 GB), through Mosaic's own passes."""
    monkeypatch.setattr(mla_decode, "_interpret", lambda: False)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pages = 6 * 38401
    compiled = jax.jit(lambda q_c, q_r, ckv, kr, tables, end:
                       mla_decode.mla_decode_paged(
                           q_c, q_r, ckv, kr, tables=tables, end=end,
                           scale=0.1)).lower(
        spec((16, 64, 512)), spec((16, 64, 128)), spec((pages, 16, 512)),
        spec((pages, 16, 128)), spec((16, 1600), jnp.int32),
        spec((16,), jnp.int32)).compile()
    assert mla_decode.KERNEL_NAME in compiled.as_text()
    assert compiled.memory_analysis().argument_size_in_bytes == pytest.approx(
        pages * 16 * 640 * 2, rel=1e-3)


def test_a_chunks_attention_compiled_for_the_v5e_holds_the_kernel_and_no_scores(
        one_chip, monkeypatch):
    """One layer's attention of the cell's 512-row chunk program at the
    published widths, the lane's 1,600 pages gathered from the cell's pool:
    with the kernels on it holds ``fleetx_mla_prefill`` and no float32
    scores of 64 heads nor an expanded key or value of a block; the plain
    twin holds both (so the text does tell)."""
    from fleetx_tpu.ops.pallas import mla_prefill
    from perfbench import harness

    monkeypatch.setattr(mla_prefill, "_interpret", lambda: False)
    cfg = GPTConfig.from_model_config(dict(harness.load_json(
        "perfbench/configs/axk1-ep16-l6.json")["model"]))

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(flash):
        on = dataclasses.replace(cfg, use_flash_attention=flash)

        def chunk(q, w_kvb, ckv_pool, kr_pool, table, start):
            return latent._prefill(
                on, q, w_kvb, ckv_pool[table].reshape(-1, 512),
                kr_pool[table].reshape(-1, 128), start, 0.1)

        pages = 6 * 38401
        return jax.jit(chunk).lower(
            spec((512, 64, 192)), spec((512, 64, 256)),
            spec((pages, 16, 512)), spec((pages, 16, 128)),
            spec((1600,), jnp.int32), spec((), jnp.int32)).compile().as_text()

    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    kernel, plain = attend(True), attend(False)
    assert mla_prefill.KERNEL_NAME in kernel
    assert mla_prefill.KERNEL_NAME not in plain
    for array in ("f32[64,512,1024]", "bf16[1024,64,256]"):
        assert array in plain and array not in kernel, array
    assert "f32[64,512," not in kernel


@pytest.mark.parametrize("rows, window_rows", [(512, 4624), (256, 4368)])
def test_a_grouped_chunks_attention_compiled_for_the_v5e_holds_no_scores(
        one_chip, monkeypatch, rows, window_rows):
    """One layer's attention of the long-document cell's 512-row and
    256-row chunk programs at SmallThinker's published widths (28 heads
    over 4 of 128, a window of 4,096), both kinds of layer behind the
    layer's own conditional, one lane's 800 pages gathered from the cell's
    pool of two classes: with the kernels on it holds ``fleetx_prefill_gqa``
    and no float32 scores of 28 heads over the lane's or the window's rows;
    the plain twin holds both (so the text does tell)."""
    from fleetx_tpu.models.gpt import hybrid
    from fleetx_tpu.ops.pallas import prefill_gqa
    from perfbench import harness

    monkeypatch.setattr(prefill_gqa, "_interpret", lambda: False)
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    cfg = dataclasses.replace(
        GPTConfig.from_model_config(dict(harness.load_json(
            "perfbench/configs/smallthinker-21b-a3b-l8.json")["model"])),
        dtype=jnp.bfloat16, decode_cache_len=12800, decode_page_size=16,
        decode_num_pages=24 * 800 + 1, decode_window_pages=24 * 289 + 1)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def attend(flash):
        layer = hybrid.HybridSelfAttention(
            dataclasses.replace(cfg, use_flash_attention=flash))
        out_proj = jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, rows, 2560), jnp.bfloat16),
            layer_index=0))["params"]["out_proj"]

        def chunk(out_proj, q, k_pool, v_pool, tables, start, index):
            return layer.apply(
                {"params": {"out_proj": out_proj},
                 "cache": {"cached_key": k_pool, "cached_value": v_pool,
                           "cache_index": jnp.int32(0)}},
                q, decode=True, cache_positions=start, block_tables=tables,
                layer_index=index, phase="attend", mutable=["cache"])[0]

        pool = (hybrid.total_pages(cfg), 16, 512)
        return jax.jit(chunk).lower(
            jax.tree.map(lambda x: spec(x.shape, x.dtype), out_proj),
            spec((1, rows, 28, 128)), spec(pool), spec(pool),
            spec((2, 1, 800), jnp.int32), spec((1,), jnp.int32),
            spec((), jnp.int32)).compile().as_text()

    kernel, plain = attend(True), attend(False)
    assert kernel.count(prefill_gqa.KERNEL_NAME) >= 2   # one call a kind
    assert prefill_gqa.KERNEL_NAME not in plain
    for array in (f"f32[1,4,7,{rows},12800]",
                  f"f32[1,4,7,{rows},{window_rows}]"):
        assert array in plain and array not in kernel, array
    assert f"f32[1,4,7,{rows}," not in kernel
    assert f"f32[28,{rows}," not in kernel


# ----------------------------------------------------------------- the gate

def test_the_group_limited_choice_on_a_written_out_case():
    """8 experts in 4 groups of 2, the 2 best groups stay, top 3. Group
    scores (sum of the two highest = both): g0 0.9 + 0.1 = 1.0, g1 0.6 +
    0.55 = 1.15, g2 0.8 + 0.3 = 1.1, g3 0.5 + 0.45 = 0.95: g1 and g2 stay,
    so 0.9 (expert 0, the largest of all) is out: experts 4, 2, 3."""
    scores = jnp.asarray([[0.9, 0.1, 0.6, 0.55, 0.8, 0.3, 0.5, 0.45]])
    assert moe_share.group_limited_topk(scores, 3, 4, 2).tolist() == [
        [4, 2, 3]]
    # every group stays: the plain top 3
    assert moe_share.group_limited_topk(scores, 3, 4, 4).tolist() == [
        [0, 4, 2]]
    assert moe_share.group_limited_topk(scores, 3, 1, 1).tolist() == [
        [0, 4, 2]]
    # and the reference's own routine says the same
    settings = dict(n_group=4, topk_group=2, top_k=3)
    chosen, ranked = axk1_f32._route(scores, settings)
    assert chosen.tolist() == [[4, 2, 3]]
    assert np.isinf(np.asarray(ranked)[0, [0, 1, 6, 7]]).all()


def layer_of(cls, cfg, x, params=None, **kwargs):
    layer = cls(cfg)
    if params is None:
        params = flax.core.meta.unbox(jax.jit(layer.init)(
            jax.random.PRNGKey(1), x))["params"]
    return traced_apply(layer, {"params": params}, x, **kwargs), params


def test_one_group_and_every_expert_held_is_todays_sigmoid_topk():
    """``SharedMoEMLP`` with ``n_group`` 1, every routed expert held and no
    shared expert against ``DroplessMoEMLP``'s ``sigmoid_topk`` on the same
    weights (the normaliser's 1e-20 against 1e-6 is under float32's
    rounding of sums near 2)."""
    cfg = GPTConfig.from_model_config({
        **SIZES, "num_experts": 8, "num_routed_experts": 8,
        "first_expert_held": 0, "n_group": 1, "topk_group": 1,
        "num_shared_experts": 0})
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64), jnp.float32)
    old, params = layer_of(moe.DroplessMoEMLP, cfg, x)
    new, _ = layer_of(moe_share.SharedMoEMLP, cfg, x, params)
    assert np.abs(np.asarray(new) - np.asarray(old)).max() < 1e-6


def test_the_shares_add_up_to_the_uncut_layer():
    """4 shares of 2 of 8 routed experts: the routed parts of all the
    shares, with the shared expert counted ONCE, are the uncut reference's
    layer; no share's program computes anything for an expert it lacks."""
    base = {**SIZES, "num_routed_experts": 8, "top_k": 3, "n_group": 4,
            "topk_group": 2, "num_shared_experts": 1}
    whole_cfg = GPTConfig.from_model_config(
        {**base, "num_experts": 8, "first_expert_held": 0})
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 21, 64), jnp.float32)
    whole, params = layer_of(moe_share.SharedMoEMLP, whole_cfg, x)
    no_shared = GPTConfig.from_model_config(
        {**base, "num_experts": 8, "first_expert_held": 0,
         "num_shared_experts": 0})
    routed_whole, _ = layer_of(moe_share.SharedMoEMLP, no_shared, x, params)
    shared_once = whole - routed_whole
    total = shared_once
    for first in (0, 2, 4, 6):
        cfg = GPTConfig.from_model_config(
            {**base, "num_experts": 2, "first_expert_held": first,
             "num_shared_experts": 0})
        share = {**params, **{k: params[k][first:first + 2]
                              for k in ("w_gate", "w_up", "w_down")}}
        part, _ = layer_of(moe_share.SharedMoEMLP, cfg, x, share)
        total = total + part
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 2e-6
    # against the plain reference's layer, uncut
    stack = jax.tree.map(lambda leaf: leaf[None], params)
    settings = axk1_f32._settings(
        {**base, "num_layers": 1, "first_expert_held": 0})
    want, chosen, _, _ = axk1_f32._experts(x[0], stack, 0, settings)
    assert np.abs(np.asarray(whole[0]) - np.asarray(want)).max() < 2e-6
    # each token's three experts lie in two groups of two
    assert (np.unique(np.asarray(chosen) // 2, axis=None).size <= 4
            and all(len(set(row // 2)) <= 2 for row in np.asarray(chosen)))


def test_a_share_lays_out_its_own_pairs_alone():
    idx = jnp.asarray([[0, 5, 9], [4, 5, 11], [1, 2, 3]])
    dest, src, sizes, tile_expert, num_tiles, held = (
        moe_share.held_row_layout(idx, 4, 4, 1))
    assert sizes.tolist() == [1, 2, 0, 0] and int(num_tiles) == 3
    assert held.tolist() == [[False, True, False], [True, True, False],
                             [False, False, False]]
    rows = 9
    assert [int(d) for d in dest if d < rows] == [1, 0, 2]
    assert src[:3].tolist() == [1, 0, 1]           # the tokens, by expert
    # padded to tiles of 16: expert 0's tile, expert 1's tile, a spare
    *_, tile_expert, num_tiles, _ = moe_share.held_row_layout(idx, 4, 4, 16)
    assert int(num_tiles) == 2 and tile_expert[:2].tolist() == [0, 1]


def test_a_call_with_no_held_pair_still_names_a_tile_and_adds_nothing(
        monkeypatch):
    """A tick of few lanes can route no pair to the held experts: the
    kernels' index maps name tile ``num_tiles - 1``, so the layout keeps
    one (on the chip a tile -1 read an expert index from outside the
    table and halted the core: my chip run, PR 40), and the layer's output
    is the shared expert's alone."""
    idx = jnp.asarray([[0, 1, 2], [12, 13, 3]])
    *_, sizes, tile_expert, num_tiles, held = moe_share.held_row_layout(
        idx, 4, 4, 16)
    assert int(num_tiles) == 1 and not sizes.any() and not held.any()
    assert 0 <= int(tile_expert[0]) < 4
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    base = {**SIZES, "num_routed_experts": 16, "num_experts": 4, "top_k": 3,
            "n_group": 1, "topk_group": 1, "use_flash_attention": True}
    cfg = GPTConfig.from_model_config({**base, "first_expert_held": 4})
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 1, 64), jnp.float32)
    layer = moe_share.SharedMoEMLP(cfg)
    params = flax.core.meta.unbox(jax.jit(layer.init)(
        jax.random.PRNGKey(1), x))["params"]
    # a router that sends every token to experts 0-2, none of them held
    router = np.zeros((64, 16), np.float32)
    router[:, :3] = 1.0
    params = {**params, "router": {"kernel": jnp.asarray(router)}}
    x = jnp.abs(x)  # (so that the three columns' scores are the largest)
    stack = tuple(params[k][None] for k in ("w_gate", "w_up", "w_down"))
    got, mut = traced_apply(
        layer,
        {"params": params, "cache": {"moe_stats": jnp.zeros(
            (1, moe_share.stats_words(cfg)), jnp.uint32)}},
        x, decode=True, layer_index=jnp.int32(0), expert_stack=stack,
        mutable=["cache"])
    # one tick call counted, no pair laid out here, no held expert read
    assert mut["cache"]["moe_stats"][0, :6:2].tolist() == [1, 0, 0]
    t = x.reshape(-1, 64)
    want = (jax.nn.silu(t @ params["shared_gate"]) * (
        t @ params["shared_up"])) @ params["shared_down"]
    assert np.abs(np.asarray(got).reshape(-1, 64) - np.asarray(want)).max() < 1e-6


def _pairs(kind, tokens, top_k, held, routed):
    """``[tokens, top_k]`` experts of ``routed``, of which ``[0, held)`` are
    held here: a share's proportions, no pair held, or groups that fill
    their tiles (16 pairs every held expert)."""
    rng = np.random.default_rng(tokens)
    if kind == "share":
        return np.stack([rng.permutation(routed)[:top_k]
                         for _ in range(tokens)])
    if kind == "none":
        return held + np.stack([rng.permutation(routed - held)[:top_k]
                                for _ in range(tokens)])
    return (np.arange(tokens * top_k) % held).reshape(tokens, top_k)


def _experts(rows, gate, up, down, tile_expert, num_tiles, layer, tm):
    """Both grouped matmuls of ``ops/pallas/moe_gmm.py`` over one layout."""
    from fleetx_tpu.ops.pallas import moe_gmm

    hidden = moe_gmm.grouped_gate_up(rows, gate, up, tile_expert, num_tiles,
                                     tm=tm, layer=layer)
    return moe_gmm.grouped_down(hidden, down, tile_expert, num_tiles, tm=tm,
                                layer=layer)


@pytest.mark.parametrize("kind, tokens, top_k, held, routed, tm", [
    ("share", 64, 4, 4, 64, 16),      # 20 tiles laid, 3-4 of them hold rows
    ("share", 40, 3, 2, 32, 16),
    ("none", 2, 3, 4, 16, 16),        # a tick of few lanes: ONE tile
    ("full", 32, 2, 4, 4, 16),        # every walked tile is full
    ("full", 64, 1, 2, 2, 32),
])
def test_the_kernels_walk_the_tiles_that_hold_rows_and_no_other(
        kind, tokens, top_k, held, routed, tm):
    """Both grouped matmuls (interpreted) against ``ragged_dot`` on the held
    rows, with every row of the INPUT past ``num_tiles`` tiles set to NaN:
    the bound of the grid's row axis is the traced ``num_tiles``, so no step
    reads them, and no held row shows one."""
    h, f = 128, 256
    rng = np.random.default_rng(7)
    idx = jnp.asarray(_pairs(kind, tokens, top_k, held, routed), jnp.int32)
    x = jnp.asarray(rng.normal(size=(tokens, h)), jnp.float32)
    gate, up, down = (jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
                      for s in ((2, held, h, f), (2, held, h, f),
                                (2, held, f, h)))
    dest, src, sizes, *_ = moe_share.held_row_layout(idx, 0, held, 1)
    rows = x[src]
    want = jax.lax.ragged_dot(
        jax.nn.silu(jax.lax.ragged_dot(rows, gate[1], sizes))
        * jax.lax.ragged_dot(rows, up[1], sizes), down[1], sizes)[dest]

    dest, src, sizes, tile_expert, num_tiles, here = (
        moe_share.held_row_layout(idx, 0, held, tm))
    walked = max(int((-(-np.asarray(sizes) // tm)).sum()), 1)
    laid = len(tile_expert) - 1
    assert int(num_tiles) == walked <= laid == -(-(
        tokens * top_k + held * (tm - 1)) // tm)
    assert {"share": walked * 4 <= laid, "none": walked == 1,
            "full": walked * tm == tokens * top_k}[kind]
    rows = x[src].at[walked * tm:].set(jnp.nan)

    def both(rows, tile_expert, num_tiles):
        return _experts(rows, gate, up, down, tile_expert, num_tiles,
                        jnp.int32(1), tm)

    calls = [e.params["grid_mapping"] for e in jax.make_jaxpr(both)(
        rows, tile_expert, num_tiles).jaxpr.eqns
        if e.primitive.name == "pallas_call"]
    assert [c.num_dynamic_grid_bounds for c in calls] == [1, 1]
    got = jax.jit(both)(rows, tile_expert, num_tiles)[dest]
    here = np.asarray(here).reshape(-1)
    assert here.sum() == np.asarray(sizes).sum()
    np.testing.assert_allclose(np.asarray(got)[here], np.asarray(want)[here],
                               rtol=1e-5, atol=1e-5)


def test_the_expert_kernels_compile_for_the_v5e_under_the_traced_bound(
        one_chip, monkeypatch):
    """A chunk of 512 at the published widths (12 experts of 7,168 x 2,048,
    269 tiles of 16 rows laid) through Mosaic's own passes: the traced
    scalar as the bound of the grid's row axis is the chip's compiler's to
    take or refuse, not the interpreter's."""
    from fleetx_tpu.ops.pallas import moe_gmm

    monkeypatch.setattr(moe_gmm, "_interpret", lambda: False)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(_experts, static_argnames="tm").lower(
        spec((269 * 16, 7168)), spec((2, 12, 7168, 2048)),
        spec((2, 12, 7168, 2048)), spec((2, 12, 2048, 7168)),
        spec((269,), jnp.int32), spec((), jnp.int32),
        spec((), jnp.int32), tm=16).compile().as_text()
    assert moe_gmm.GATE_UP_KERNEL_NAME in text
    assert moe_gmm.DOWN_KERNEL_NAME in text


@pytest.mark.parametrize("kind, shape", [("tick", (6, 1)),
                                         ("prefill", (1, 24))])
def test_the_counters_read_the_tiles_walked_and_the_tiles_laid(
        monkeypatch, kind, shape):
    """``moe_{tick,prefill}_tiles_walked`` is the bound of the kernels' grid
    over the tiles, ``sum(ceil(an expert's pairs / tm))`` and at least 1;
    ``_tiles_laid`` the static count of tiles that can hold rows. A call of
    one token a lane is a tick's, a longer one a prefill's."""
    from fleetx_tpu.serving.model_protocol import GPTExecutor

    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    cfg = GPTConfig.from_model_config({
        **SIZES, "num_routed_experts": 16, "num_experts": 4, "top_k": 3,
        "n_group": 1, "topk_group": 1, "use_flash_attention": True})
    layer = moe_share.SharedMoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (*shape, 64), jnp.float32)
    params = flax.core.meta.unbox(jax.jit(layer.init)(
        jax.random.PRNGKey(1), x))["params"]
    stack = tuple(params[k][None] for k in ("w_gate", "w_up", "w_down"))
    stats = jnp.zeros((2, moe_share.stats_words(cfg)), jnp.uint32)
    _, mut = traced_apply(
        layer, {"params": params, "cache": {"moe_stats": stats}}, x,
        decode=True, layer_index=jnp.int32(0), expert_stack=stack,
        mutable=["cache", "routing"])
    chose = np.asarray(mut["routing"]["experts"][0]).reshape(-1) - 4
    sizes = np.bincount(chose[(chose >= 0) & (chose < 4)], minlength=4)
    pairs, tm = chose.size, 16
    walked = max(int((-(-sizes // tm)).sum()), 1)
    laid = -(-(pairs + 4 * (tm - 1)) // tm)
    counters = GPTExecutor(GPTForPretraining(cfg)).counters(mut["cache"])
    other = "prefill" if kind == "tick" else "tick"
    assert counters[f"moe_{kind}_layer_calls"] == 1
    assert counters[f"moe_{kind}_pairs"] == sizes.sum() > 0
    assert counters[f"moe_{kind}_tiles_walked"] == walked
    assert counters[f"moe_{kind}_tiles_laid"] == laid > walked
    assert not any(counters[f"moe_{other}_{name}"] for name in (
        "layer_calls", "pairs", "tiles_walked", "tiles_laid"))


# ------------------------------------------------ what stays as it is today

@pytest.mark.parametrize("path, name", [
    ("perfbench/configs/olmoe-1b-7b-l8.json", "olmoe"),
    ("perfbench/configs/smallthinker-21b-a3b-l8.json", "smallthinker"),
    ("perfbench/configs/lfm2-8b-a1b-l14.json", "lfm2"),
])
def test_a_configuration_that_holds_every_expert_runs_todays_layer(path, name):
    """The three configurations with experts name no share: their stacks
    build ``DroplessMoEMLP`` and none of the new modules, so their lowered
    programs are what they were (a tiny model of each kind lowers to the
    same text with and without the new fields spelled out at their
    defaults)."""
    from perfbench import harness

    data = harness.with_tiny(harness.load_json(path), True)
    cfg = GPTConfig.from_model_config(
        {**data["model"], "dtype": "float32", "use_flash_attention": False})
    assert not cfg.expert_share and not cfg.latent
    assert cfg.experts_held == (0, cfg.num_experts)
    assert cfg.routed_experts == cfg.num_experts
    spelled = dataclasses.replace(
        cfg, num_routed_experts=None, first_expert_held=0, n_group=1,
        topk_group=1, num_shared_experts=0)
    ids = np.zeros((1, 8), np.int32)
    texts = []
    for each in (cfg, spelled):
        model = GPTForPretraining(each)
        variables = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), ids))
        texts.append(jax.jit(model.apply).lower(variables, ids).as_text())
    assert texts[0] == texts[1]
    if cfg.layer_types:
        from fleetx_tpu.models.gpt.mixed_stack import MixedStack

        stack = block_fields.stack_of(types_of(cfg))
        assert type(stack) is MixedStack
        assert type(stack._kinds()["experts"][0]) is moe.DroplessMoEMLP


def types_of(cfg):
    import types

    return types.SimpleNamespace(cfg=cfg, _decoder_stack=None)


# ------------------------------------------------------------- the refusals

@pytest.mark.parametrize("over, match", [
    (dict(layer_types=["latent_attention", "full_attention",
                       "latent_attention"]), "beside another operator"),
    (dict(kv_lora_rank=None), "kv_lora_rank"),
    (dict(qk_rope_head_dim=7), "even"),
    (dict(position_embedding="learned"), "rope"),
    (dict(num_key_value_heads=2), "no grouped heads"),
    (dict(first_expert_held=14), "experts held"),
    (dict(n_group=3), "n_group"),
    (dict(topk_group=5), "n_group"),
    (dict(index_topk=8), "index_topk"),   # (an indexer needs all three)
    (dict(gate="softmax_topk"), "sigmoid_topk"),
    (dict(rope_scaling_factor=0.5), "rope_scaling_factor"),
])
def test_the_configuration_refuses_what_nobody_wrote(over, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        GPTConfig.from_model_config({**SIZES, **over})


def test_latent_widths_without_the_layer_are_refused():
    sizes = {k: v for k, v in SIZES.items() if k not in (
        "num_routed_experts", "first_expert_held", "n_group", "topk_group",
        "num_shared_experts")}
    with pytest.raises(ValueError, match="without a latent_attention"):
        GPTConfig.from_model_config(
            {**sizes, "layer_types": ["full_attention"] * 3})


def test_yarn_frequencies_blend_between_the_two_ends():
    cfg = GPTConfig.from_model_config(
        {**SIZES, "qk_rope_head_dim": 64, "rope_scaling_original_max_position":
         4096})
    got = latent.yarn_frequencies(cfg)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # the fastest dimensions are kept, the slowest divided by the factor
    assert got[0] == pytest.approx(plain[0]) and got[-1] == pytest.approx(
        plain[-1] / 32)
    assert (np.diff(got) < 0).all()
    ours = axk1_f32._yarn_frequencies(axk1_f32._settings(
        {**SIZES, "qk_rope_head_dim": 64,
         "rope_scaling_original_max_position": 4096}))
    assert np.abs(ours - got).max() < 1e-7
    # m = 0.1 ln 32 + 1 = 1.3466; the softmax's scale takes its square
    assert latent.softmax_scale(cfg) == pytest.approx(
        (16 + 64) ** -0.5 * 1.3466 ** 2, rel=1e-4)
