"""Keye-VL-2.0's block on the CPU at a small size, seeded random weights:
grouped attention UNDER A LEARNED INDEXER (``models/gpt/hybrid.py``,
``indexer.py``) over the page pool's three leaves, rotary positions of three
axes, and rows that a vision tower makes of images (``models/vision/vit.py``,
``serving/rows_in.py``), served through ONE tiny ``ServingEngine``, against
the plain float32 reference (``perfbench/reference/keyevl2_f32.py``): the
LOGITS outside the cache, in chunks and ticks through the engine's pool,
text-only and with images, cold and on a trie hit that must skip the tower;
three equal axes against the one-axis table, bit for bit; ``rope_delta``
after images; the indexer's functions existing once; the chunk kernel under
a mask, interpreted, against its plain twin and, compiled for the v5e, at the
published widths; what a request with images is refused by name; and the
flags' absence leaving another model's programs what they were."""

import dataclasses

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt import block_fields, hybrid, indexer, latent
from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import (GPTConfig, GPTForPretraining,
                                         rope_tables)
from fleetx_tpu.models.vision.vit import tower_of
from fleetx_tpu.ops.pallas import prefill_gqa
from fleetx_tpu.serving import ServingEngine
from fleetx_tpu.serving import rows_in
from perfbench.drivers import serve_closed_loop_vl as driver
from perfbench.reference import keyevl2_f32

TOPK, TOKEN = 24, 511
SIZES = dict(
    family="keyevl2", vocab_size=512, hidden_size=64, num_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_size=16,
    ffn_hidden_size=32, layer_types=["full_attention"] * 3,
    max_position_embeddings=4096, hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0, position_embedding="rope",
    rope_theta=1e4, mrope_section=[2, 3, 3], qk_norm=True,
    qk_norm_scope="head", norm="rmsnorm", norm_eps=1e-6, mlp_act="swiglu",
    use_bias=False, tie_word_embeddings=False, num_experts=8,
    gate="softmax_topk", top_k=2, norm_topk_prob=True, index_n_heads=2,
    index_head_dim=8, index_topk=TOPK, index_rope_section=[1, 1, 2],
    vision=dict(hidden_size=32, num_layers=2, num_heads=4,
                intermediate_size=48, patch_size=2, grid=5, merge=2,
                image_token_id=TOKEN),
    use_flash_attention=False, dtype="float32")
TOL = 2e-5    # float32 against float32, logits up to 0.5


def tiny_model():
    """The model and its weights, the tower's under ``vision`` and every
    norm's scale moved off 1."""
    cfg = GPTConfig.from_model_config(SIZES)
    model = GPTForPretraining(cfg)
    variables = flax.core.meta.unbox(jax.jit(lambda k: model.init(
        k, np.zeros((1, 8), np.int32)))(jax.random.PRNGKey(0)))
    params = dict(variables["params"])
    params["vision"] = flax.core.meta.unbox(tower_of(cfg).init(
        jax.random.PRNGKey(1), jnp.zeros((16, 12)),
        jnp.asarray([4, 4])))["params"]
    leaves, shape = jax.tree_util.tree_flatten_with_path(params)
    rng = np.random.default_rng(2)
    params = jax.tree_util.tree_unflatten(shape, [
        leaf + 0.1 * rng.normal(size=leaf.shape).astype(np.float32)
        if path[-1].key == "scale" else leaf for path, leaf in leaves])
    return model, {"params": params}


@pytest.fixture(scope="module")
def built():
    """:func:`tiny_model` and ONE engine over it for the whole file."""
    model, variables = tiny_model()
    engine = ServingEngine(
        model, variables, slots=3, cache_len=256, page_size=8,
        num_pages=3 * 32 + 1, prefill_chunk=32, prefill_bucket=16,
        prefix_cache=True, gen_cfg=GenerationConfig(
            decode_strategy="greedy", eos_token_id=-1, pad_token_id=0,
            max_length=8))
    return model, variables, engine


def session(seed, grids=((2, 3), (4, 2), (3, 3)), caption=6, tail=40):
    """A prompt of captions, images and a tail of text (five times
    ``index_topk`` rows in all), then 6 ids to decode; and the images."""
    rng = np.random.default_rng(seed)
    parts, images = [], []
    for h, w in grids:
        parts += [rng.integers(1, 500, caption, dtype=np.int32),
                  np.full(h * w, TOKEN, np.int32)]
        images.append(rng.integers(0, 256, (h * 4, w * 4, 3), dtype=np.uint8))
    parts.append(rng.integers(1, 500, tail + 6, dtype=np.int32))
    return np.concatenate(parts), images


@pytest.fixture(scope="module")
def reference():
    return keyevl2_f32.configured(SIZES)


@pytest.fixture(scope="module")
def served(built):
    """The check's own programs over the engine's pool, traced once."""
    return driver.Served(built[2])


# ------------------------------------------------ against the reference

def test_the_plain_forward_is_the_reference_and_prunes(built, reference):
    model, variables, _ = built
    tokens = np.random.default_rng(0).integers(1, 500, 120, dtype=np.int32)
    want = {k: np.asarray(v) for k, v in reference(
        variables["params"], tokens, with_all=True).items() if v is not None}
    text_only = {k: v for k, v in variables["params"].items()
                 if k != "vision"}
    plain, mut = model.apply({"params": text_only}, tokens[None],
                             mutable=["routing"])
    assert np.abs(np.asarray(plain[0]) - want["logits"]).max() < TOL
    sown = {jax.tree_util.keystr(path[-2:-1]).strip("[']"): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                mut["routing"])[0]}
    assert (sown["index_sets"][:, 0] == want["sets"]).all()
    sizes = want["sets"].sum(-1)
    assert (sizes == np.minimum(np.arange(120) + 1, TOPK)[None]).all()


@pytest.mark.parametrize("images", [False, True], ids=["text", "images"])
def test_chunks_and_ticks_through_the_engines_pool_are_the_reference(
        built, reference, served, images):
    """Prefill in chunks (the tower's rows from the engine's stage, each
    row's own position) then ticks (``rope_delta``), through the cache of
    three leaves, against the reference's full forward: LOGITS."""
    _, variables, engine = built
    tokens, pictures = session(3) if images else (
        np.random.default_rng(4).integers(1, 500, 120, dtype=np.int32), [])
    n, tail = len(tokens), 30
    with driver.lfm2_driver.trie_off(engine.cache_manager.pool):
        mine = served.sequence(tokens, pictures, n - 6, tail)
    want = np.asarray(reference(variables["params"], tokens, images=pictures,
                                tail=tail + 6))
    assert mine["matched"] == 0 and mine["staged"] == len(pictures)
    assert np.abs(mine["logits"] - want).max() < TOL
    # the selection pruned: 24 of up to 120 rows at every position compared
    assert (mine["index_sets"].sum(-1) == TOPK).all()


def test_a_trie_hit_on_an_image_skips_the_tower_and_gives_the_cold_logits(
        built, reference, served):
    _, variables, engine = built
    tokens, pictures = session(5)
    n = len(tokens)
    before = engine.metrics.snapshot()
    # the engine itself registers the session (its tower and chunk programs)
    rid = engine.submit(tokens[:n - 6], images=pictures, max_length=3)
    cold_tokens = engine.drain()[rid].tokens
    middle = engine.metrics.snapshot()
    assert middle["images_encoded"] - before["images_encoded"] == 3
    rid = engine.submit(tokens[:n - 6], images=pictures, max_length=3)
    hit_tokens = engine.drain()[rid].tokens
    after = engine.metrics.snapshot()
    assert after["images_encoded"] == middle["images_encoded"]   # none
    assert after["images_skipped"] - middle["images_skipped"] == 3
    assert after["image_rows"] - middle["image_rows"] == 6 + 8 + 9
    assert list(hit_tokens) == list(cold_tokens)
    # and the LOGITS on the hit are the reference's (the cold run's)
    want = np.asarray(reference(variables["params"], tokens, images=pictures,
                                tail=7))
    # the engine's greedy token is the reference's argmax
    assert cold_tokens[0] == int(np.argmax(want[0]))
    # another question of the same session: the images' pages are matched,
    # the tower encodes nothing, and the logits are the reference's
    other = tokens.copy()
    other[-30:] = np.random.default_rng(8).integers(1, 500, 30)
    hit = served.sequence(other, pictures, n - 6, 16)
    assert 40 <= hit["matched"] <= n - 30 and hit["staged"] == 0
    want = np.asarray(reference(variables["params"], other, images=pictures,
                                tail=22))
    assert np.abs(hit["logits"] - want).max() < TOL


# ------------------------------------------------------------- positions

def test_three_equal_axes_are_the_one_axis_table_bit_for_bit(built):
    model, variables, _ = built
    pos = jnp.arange(40, dtype=jnp.int32)[None] + 7
    one = rope_tables(pos, 16, 1e4)
    three = block_fields.fold_mrope(
        rope_tables(jnp.broadcast_to(pos, (3, 1, 40)), 16, 1e4), (2, 3, 3))
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(one, three))
    assert block_fields.fold_mrope(one, (2, 3, 3)) is one
    # and so are the model's logits
    tokens = np.random.default_rng(6).integers(1, 500, (1, 40))
    params = {k: v for k, v in variables["params"].items() if k != "vision"}
    a = model.apply({"params": params}, tokens, pos)
    b = model.apply({"params": params}, tokens,
                    jnp.broadcast_to(pos, (3, 1, 40)))
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_rows_keys_positions_and_rope_delta_after_images():
    tokens, pictures = session(7, grids=((2, 3), (4, 2)), caption=2, tail=3)
    tokens = tokens[:-6]
    keys, positions, delta, records = rows_in.layout(
        tokens, pictures, SIZES["vision"])
    # 2 text, 6 rows of a 2 x 3 image, 2 text, 8 rows of a 4 x 2, 3 text
    assert positions[:, :2].tolist() == [[0, 1]] * 3
    assert positions[:, 2:8].tolist() == [[2] * 6, [2, 2, 2, 3, 3, 3],
                                          [2, 3, 4, 2, 3, 4]]
    assert positions[:, 8:10].tolist() == [[5, 6]] * 3     # 2 + max(2, 3)
    assert positions[0, 10:18].tolist() == [7] * 8
    assert positions[1, 10:18].tolist() == [7, 7, 8, 8, 9, 9, 10, 10]
    assert positions[:, 18:].tolist() == [[11, 12, 13]] * 3   # 7 + max(4, 2)
    assert delta == 14 - 21 and [r["start"] for r in records] == [2, 10]
    assert np.array_equal(positions, keyevl2_f32.positions_of(
        tokens, [(2, 3), (4, 2)], TOKEN))
    # a text row's key is its id, an image row's negative and its own
    marked = tokens == TOKEN
    assert np.array_equal(keys[~marked], tokens[~marked])
    assert (keys[marked] < 0).all() and len(set(keys[marked])) == 14
    again = rows_in.layout(tokens, pictures, SIZES["vision"])[0]
    assert np.array_equal(keys, again)
    pictures[1][0, 0, 0] ^= 1                     # one bit of one pixel
    moved = rows_in.layout(tokens, pictures, SIZES["vision"])[0]
    assert np.array_equal(moved[:10], keys[:10])
    assert (moved[10:18] != keys[10:18]).all()
    # a decoded row's position is its cache row plus rope_delta
    req = driver.types.SimpleNamespace(prompt_len=21, positions=positions,
                                       rope_delta=delta)
    assert rows_in.row_positions(req, 19, 4).tolist() == [[12, 13, 14, 15]] * 3


# ------------------------------------------------------------ the indexer

def test_the_indexers_functions_exist_once():
    """``latent.py`` exports the shared module's own objects (an import,
    not a copy), and its two wrappers hand its seams over."""
    for name in ("select_rows", "top_rows", "gather_rows", "_index_act",
                 "_index_head_weights", "_visible", "KEY_BLOCK"):
        assert getattr(latent, name) is getattr(indexer, name), name
    rng = np.random.default_rng(0)
    qi = jnp.asarray(rng.normal(size=(5, 2, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5, 2)), jnp.float32)
    ki = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    want = (np.maximum(np.einsum("shd,td->sht", qi, ki), 0)
            * np.asarray(w)[..., None]).sum(1)
    for module in (latent, indexer):
        assert np.allclose(module.index_scores(qi, w, ki), want, atol=1e-5)
    assert np.allclose(latent._chunk_index_scores(qi, w, ki, 11),
                       indexer._chunk_index_scores(qi, w, ki, 11))


# the tick of the tiny preset (perfbench/cells/keyevl2-l6-serve-pagesqa-
# sparse.json ``tiny``: 3 lanes of 256 rows, ``index_topk`` 24) and the
# cell's own, as ``hybrid.py`` calls it
@pytest.mark.parametrize("lanes,t,k", [(3, 256, 24), (5, 33792, 2048)])
def test_a_ticks_selection_holds_no_sort(lanes, t, k):
    from tests.test_dsv32_serving import sorting_primitives

    def call(scores, end):
        seen = jnp.arange(t, dtype=jnp.int32)[None, :] < end[:, None]
        return indexer.top_rows(scores, indexer._visible(seen), end,
                                min(k, t))

    jaxpr = jax.make_jaxpr(call)(
        jax.ShapeDtypeStruct((lanes, t), jnp.float32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32))
    assert sorting_primitives(jaxpr.jaxpr) == []
    chosen, count = jaxpr.out_avals
    assert (chosen.shape, chosen.dtype) == ((lanes, k), jnp.int32)
    assert count.shape == (lanes,)


def test_the_cache_has_three_leaves_under_one_table(built):
    engine = built[2]
    shapes = {path[-1].key: leaf.shape for path, leaf in
              jax.tree_util.tree_flatten_with_path(
                  engine.cache_manager.cache)[0]}
    assert shapes == {"cached_key": (291, 8, 32), "cached_value": (291, 8, 32),
                      "cached_index": (291, 8, 128), "moe_stats": (3, 24)}
    assert hybrid.index_leaf_width(engine.model.cfg) == 128
    assert engine.model.cfg.state_kinds == ("kv",) and engine.model.cfg.indexed
    assert engine.cache_manager.index_pool_bytes == 291 * 8 * 128 * 4
    caps = engine.capabilities
    assert caps.takes_rows and caps.mrope and not caps.supports_spec
    assert "rope_delta" in engine._state


@pytest.mark.parametrize("block", [1024, 32], ids=["one_block", "blocks_32"])
@pytest.mark.parametrize("start", [0, 40, 100])
def test_the_sparse_chunk_kernel_interpreted_is_its_plain_twin(start, block,
                                                               monkeypatch):
    """``block`` 32: the lane's 128 rows are four key blocks, of which the
    grid's dynamic bound walks one, two and four (the chunk's last row is
    15, 55, 115), each under its own block of the mask."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    monkeypatch.setattr(prefill_gqa, "BLOCK_ROWS", block)
    rng = np.random.default_rng(start)
    s, heads, kv, d, t = 16, 4, 2, 128, 128
    q = jnp.asarray(rng.normal(size=(s, heads, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(t, kv * d)), jnp.float32)
            for _ in range(2))
    seen = np.arange(t)[None, :] <= start + np.arange(s)[:, None]
    mask = jnp.asarray(seen & (rng.random((s, t)) < 0.4) | np.eye(
        s, t, start, dtype=bool))
    assert prefill_gqa.takes(1, s, d, 16)
    got = prefill_gqa.gqa_sparse_prefill(q, k, v, mask, jnp.int32(start))
    want = hybrid.grouped_attention(q[None], k[None], v[None],
                                    mask[None, None])[0]
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


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


def test_a_chunks_attention_compiled_for_the_v5e_holds_the_sparse_kernel(
        one_chip, monkeypatch):
    """One layer's attention of the cell's 512-row chunk program at the
    published widths (32 heads over 4 of 128, 16 index heads of 64, top
    2,048 of a lane's 33,792 rows) under the layer's own index: it holds
    ``fleetx_gqa_sparse_prefill`` and no float32 scores of 32 heads."""
    from perfbench import harness

    monkeypatch.setattr(prefill_gqa, "_interpret", lambda: False)
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    cfg = dataclasses.replace(
        GPTConfig.from_model_config(dict(harness.load_json(
            "perfbench/configs/keye-vl2-30b-l6.json")["model"])),
        dtype=jnp.bfloat16, use_flash_attention=True, decode_cache_len=33792,
        decode_page_size=16, decode_num_pages=16385)
    rows, pages = 512, 6 * 16385

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer = hybrid.HybridSelfAttention(cfg)
    out_proj = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 2048), jnp.bfloat16),
        layer_index=0, rope=(jnp.ones((1, 8, 64)),) * 2
        + (jnp.ones((1, 8, 32)),) * 2))["params"]["out_proj"]

    def chunk(out_proj, q, qi, w, k_pool, v_pool, ki_pool, tables, start,
              index):
        return layer.apply(
            {"params": {"out_proj": out_proj},
             "cache": {"cached_key": k_pool, "cached_value": v_pool,
                       "cached_index": ki_pool}},
            (q, qi, w), decode=True, cache_positions=start,
            block_tables=tables, layer_index=index, phase="attend")

    text = jax.jit(chunk).lower(
        jax.tree.map(lambda x: spec(x.shape, x.dtype), out_proj),
        spec((1, rows, 32, 128)), spec((1, rows, 16, 64)),
        spec((1, rows, 16), jnp.float32), spec((pages, 16, 512)),
        spec((pages, 16, 512)), spec((pages, 16, 128)),
        spec((1, 2112), jnp.int32), spec((1,), jnp.int32),
        spec((), jnp.int32)).compile().as_text()
    assert text.count(prefill_gqa.SPARSE_KERNEL_NAME) >= 1
    assert f"f32[32,{rows}," not in text and f"f32[4,8,{rows}," not in text


# ------------------------------------------------------------ what refuses

def test_what_a_request_with_images_is_refused_by_name(built):
    _, variables, engine = built
    tokens, pictures = session(9)
    prompt = tokens[:-6]
    for kwargs, name in (({"kv_payloads": [b""]}, "kv_payloads"),
                         ({"history": [5]}, "history")):
        with pytest.raises(ValueError, match=name):
            engine.submit(prompt, images=pictures, **kwargs)
    engine.role = "prefill"
    try:
        with pytest.raises(ValueError, match="role='prefill'"):
            engine.submit(prompt, images=pictures)
    finally:
        engine.role = "both"
    with pytest.raises(ValueError, match="no image is left"):
        engine.submit(prompt, images=pictures[:2])
    with pytest.raises(ValueError, match="3 images given"):
        engine.submit(prompt[:14], images=pictures)       # one run of 6
    with pytest.raises(ValueError, match="rows and the run"):
        engine.submit(prompt, images=[pictures[1]] + pictures[1:])
    with pytest.raises(ValueError, match="no image is left"):
        engine.submit(prompt)             # marked rows and no image at all
    with pytest.raises(ValueError, match="uint8"):
        engine.submit(prompt, images=[p.astype(np.float32)
                                      for p in pictures])
    assert engine.scheduler.queue_depth == 0
    # and what the family is refused at construction (a flat pool's pages
    # are neither spilled nor shipped; no test speculates)
    for kwargs, flag in ((dict(spec=True), "supports_spec"),
                         (dict(host_cache_bytes=1 << 20),
                          "supports_host_spill"),
                         (dict(role="prefill"), "supports_roles")):
        with pytest.raises(ValueError, match=flag):
            ServingEngine(built[0], variables, slots=2, cache_len=64,
                          page_size=8, prefill_chunk=32, **kwargs)
    for field, value in (("index_topk", 0), ("qk_norm_scope", "projection"),
                         ("mrope_section", [2, 3, 4]),
                         ("index_rope_section", None)):
        with pytest.raises((ValueError, NotImplementedError)):
            GPTConfig.from_model_config({**SIZES, field: value,
                                         **({"index_n_heads": 2}
                                            if field == "index_topk"
                                            else {})})
    bad = dict(SIZES["vision"], merge=3)
    with pytest.raises(ValueError, match="merge"):
        GPTConfig.from_model_config({**SIZES, "vision": bad})


# (roles, host spill, prefix cache) of every served family's configuration:
# the stack under a grouped indexer is refused the tiers and the roles (its
# flat pool's page, as they read it, is one layer's); every OTHER family
# has the flags the tree before this family gave it
FLAGS = {
    "axk1/serve_axk1_ep16_l6.yaml": (False, False, True),
    "dsv32/serve_dsv32_ep16_l5.yaml": (False, False, True),
    "jamba2/serve_jamba2_3b.yaml": (False, False, False),
    "keye/serve_keye_vl2_30b_l6.yaml": (False, False, True),
    "lfm2/serve_lfm2_8b_a1b_l14.yaml": (False, False, True),
    "longcat/serve_longcat_flash_ep32_l4.yaml": (False, False, True),
    "olmoe/serve_olmoe_1b_7b_l8.yaml": (True, True, True),
    "smallthinker/serve_smallthinker_21b_a3b_l8.yaml": (False, True, False),
    "solar/serve_solar_open2_ep16_l8.yaml": (False, False, False),
    "trinity/serve_trinity_large_ep8_l5.yaml": (False, True, False),
}


@pytest.mark.parametrize("rel", sorted(FLAGS))
def test_a_familys_tiers_and_roles_are_what_they_were(rel):
    import os

    from fleetx_tpu.serving.model_protocol import GPTExecutor
    from fleetx_tpu.utils.config import get_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = get_config(os.path.join(root, "configs", "nlp", rel), nranks=1)
    caps = GPTExecutor(GPTForPretraining(
        GPTConfig.from_model_config(dict(cfg.Model)))).capabilities
    assert (caps.supports_roles, caps.supports_host_spill,
            caps.supports_prefix_cache) == FLAGS[rel]
    assert caps.takes_rows == caps.mrope == rel.startswith("keye/")


def _gpt2_block():
    return GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_attention_heads=4, max_position_embeddings=64,
                     hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
                     use_flash_attention=False, dtype=jnp.float32), {}


def _dsv32_tiny():
    from perfbench import harness

    data = harness.with_tiny(harness.load_json(
        "perfbench/configs/dsv32-ep16-l5.json"), True)
    return GPTConfig.from_model_config(
        {**data["model"], "dtype": "float32",
         "use_flash_attention": False}), {"prefill_chunk": 32}


@pytest.mark.parametrize("family", [_gpt2_block, _dsv32_tiny],
                         ids=["gpt", "dsv32"])
def test_a_model_without_the_flags_sees_neither_operand(family):
    """A GPT-2 block's engine and a latent one's under its own indexer: no
    ``rope_delta`` in the state, a prefill program of five operands whose
    int32 operand holds no position rows, ``images=`` refused by the
    family's name; the programs' text is the same whether the flags are
    absent or explicitly false."""
    cfg, extra = family()
    model = GPTForPretraining(cfg)
    variables = flax.core.meta.unbox(model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))

    def programs(engine):
        fn = engine._make_paged_prefill(16)
        ints = engine._prefill_ints(np.arange(1, 9), 16, 0,
                                    engine.cache_manager.lane_tables(0))
        prefill = fn.lower(engine.params, engine.cache_manager.cache, ints,
                           engine._inert_floats,
                           jax.random.PRNGKey(0)).as_text()
        decode = engine._decode_jit.lower(
            engine.params, engine.cache_manager.cache, engine._state,
            engine._device_tables(), True).as_text()
        return prefill, decode

    def build():
        return ServingEngine(model, variables, slots=2, cache_len=32,
                             page_size=8, **extra)

    engine = build()
    assert not engine.capabilities.takes_rows and not engine.capabilities.mrope
    assert engine._tower is None and "rope_delta" not in engine._state
    with pytest.raises(ValueError, match="takes no images"):
        engine.submit(np.arange(1, 9), images=[])
    ints = engine._prefill_ints(np.arange(1, 9), 16, 0,
                                engine.cache_manager.lane_tables(0))
    assert ints.shape == (7 + 4 + 16,)        # no position rows behind ids
    first = programs(engine)
    assert "rope_delta" not in first[1]
    other = build()
    other.capabilities = dataclasses.replace(
        other.capabilities, takes_rows=False, mrope=False)
    assert programs(other) == first
