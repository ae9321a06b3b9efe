"""The SmallThinker block (grouped-query heads with a head size of their
own, one full-attention layer without positions then three rotary layers
with a window, ReGLU softmax top-k experts whose router reads the block's
input) through the normal path, against the plain float32 reference
``perfbench/reference/smallthinker_f32.py``, at a tiny size on seeded
weights: the full forward; prefill IN CHUNKS and then decoding token by
token through the engine's page pool of TWO CLASSES, past the (tiny) window
so that window pages are released and used again; through
``ServingEngine.submit`` / ``step``. Logits are compared, not tokens. And
the tolerance bites: eight wrong systems each turn the comparison false.

TOLERANCE. These tests compute in float32 on the CPU, where system and
reference differ only in the order of their sums (the grouped matmuls sum
one expert's rows, the reference every expert's; the cache path splits the
attention sum at the page and the chunk; the reference sums a block of
queries at a time): the distance read is 7e-6 of the standard deviation of
the reference's logits on the full forward and 6e-6 through the pool, and
the limit is 1e-4, some fifteen times that. The smallest of the eight
faults (a window off by one) reads 1.5, and the test asks of each at least
30 times the limit. The bfloat16 limits of the chip are the benchmark
driver's (``perfbench/drivers/serve_closed_loop_swa.py``).
"""

import dataclasses

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import computed_once, sharing_programs

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.obs.tracing import get_recorder
from fleetx_tpu.serving import ServingEngine
from perfbench.drivers.serve_closed_loop_swa import Served
from perfbench.reference import smallthinker_f32

TOL = 1e-4          # of the reference's logit standard deviation (docstring)
WINDOW, PAGE, CACHE_LEN, CHUNK = 16, 8, 128, 16
LAYOUT = (0, 1, 1, 1)
MODEL = dict(
    vocab_size=512, hidden_size=64, num_layers=4, num_attention_heads=8,
    num_key_value_heads=2, head_size=16, ffn_hidden_size=32,
    max_position_embeddings=256, num_experts=8, gate="softmax_topk", top_k=2,
    norm_topk_prob=True, position_embedding="rope", rope_theta=1.5e6,
    rope_layout=LAYOUT, sliding_window=WINDOW, sliding_window_layout=LAYOUT,
    norm="rmsnorm", norm_eps=1e-6, mlp_act="reglu", use_bias=False,
    tie_word_embeddings=False, router_input="block_input")
SIZES = dict(MODEL, hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
             expert_mode=True, family="smallthinker",
             use_flash_attention=False, dtype=jnp.float32)
reference = computed_once(smallthinker_f32.configured(MODEL))
TOKENS = np.random.default_rng(0).integers(1, 512, (2, 56), dtype=np.int32)


def build(**changes):
    return GPTForPretraining(GPTConfig(**{**SIZES, **changes}))


def seeded(model):
    """Seeded weights. At width 64 with every weight at the initializer's
    0.02 the head dominates and the layers decide nothing, so the layers'
    matrices are scaled up and the norm weights moved off 1, until
    attention, the rotation, the window, both norms and the router all
    decide the logits (a fault in any of them then shows)."""
    v = flax.core.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))

    def stir(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), x.shape)
        return x * 8.0 if "layers" in name else x

    return jax.tree_util.tree_map_with_path(stir, v)


@pytest.fixture(scope="module")
def variables():
    return seeded(build())


def distance(system, want):
    """Largest logit error in units of the reference's logit spread."""
    want = np.asarray(want)
    return float(np.abs(np.asarray(system) - want).max() / want.std())


def full_forward(model, v, tokens=TOKENS):
    return jax.jit(lambda p, t: model.apply({"params": p}, t))(
        v["params"], tokens)


served_of = sharing_programs(Served)


@sharing_programs
def engine_of(model, v, lanes=3, **kw):
    return ServingEngine(
        model, v, slots=lanes, cache_len=CACHE_LEN, page_size=PAGE,
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=8),
        prefill_chunk=CHUNK, prefill_bucket=8, **kw)


def test_full_forward_matches_the_reference(variables):
    want = reference(variables["params"], TOKENS)
    assert distance(full_forward(build(), variables), want) <= TOL


def test_separate_projections_match_too(variables):
    """``fuse_attn_qkv: False`` keeps three kernels; the fused one is their
    concatenation along the heads."""
    attn = variables["params"]["gpt"]["layers"]["layer"]["attn"]
    q, k, v = jnp.split(attn["qkv_proj"]["kernel"], (8, 10), axis=-2)
    split = jax.tree.map(lambda x: x, variables)
    split["params"]["gpt"]["layers"]["layer"]["attn"] = {
        "out_proj": attn["out_proj"], "q_proj": {"kernel": q},
        "k_proj": {"kernel": k}, "v_proj": {"kernel": v}}
    want = reference(variables["params"], TOKENS)
    assert distance(full_forward(build(fuse_attn_qkv=False), split),
                    want) <= TOL
    assert distance(reference(split["params"], TOKENS), want) <= TOL


def test_chunked_prefill_then_decode_through_both_page_classes(variables):
    """44 tokens prefilled in whole chunks of 16 (the first two overlap:
    ``Served.prefill``) and 12 decoded through the engine's own pool and
    allocators: three times the window, so every window layer's first pages
    were released and their pages handed out again, and a page released
    never lay inside a live query's window, or the logits would say so."""
    engine = engine_of(build(), variables)
    pool = engine.cache_manager.window_pool
    for row in TOKENS:
        mine = served_of(engine, CHUNK).sequence(row, 44)
        want = np.asarray(reference(variables["params"], row))
        # the last chunk's 16 positions and the 12 decode steps
        assert len(mine["logits"]) == 28
        assert distance(mine["logits"], want[-28:]) <= TOL
    assert pool.recycled >= 2 * (48 - WINDOW) // PAGE
    assert pool.pages_in_use == 0 == engine.cache_manager.pool.pages_in_use
    pool.check_invariants()


def heads_interleaved(v):
    """``v`` with the query heads permuted so that the system's head ``h``
    on key head ``h // group`` computes the published head ``g`` on key
    head ``g % kv_heads``."""
    heads, kv = MODEL["num_attention_heads"], MODEL["num_key_value_heads"]
    group = heads // kv
    place = [(g % kv) * group + g // kv for g in range(heads)]  # g -> place
    order = np.argsort(place)                 # the head at each place
    attn = v["params"]["gpt"]["layers"]["layer"]["attn"]
    qkv = attn["qkv_proj"]["kernel"]
    new = jax.tree.map(lambda x: x, v)
    new["params"]["gpt"]["layers"]["layer"]["attn"] = {
        "qkv_proj": {"kernel": jnp.concatenate(
            [qkv[..., :heads, :][..., order, :], qkv[..., heads:, :]], -2)},
        "out_proj": {"kernel": attn["out_proj"]["kernel"][:, order]}}
    return new


FAULTS = {
    "window_off_by_one": dict(sliding_window=WINDOW + 1),
    "window_ignored": dict(sliding_window=None, sliding_window_layout=None),
    "full_layer_rotated": dict(rope_layout=(1, 1, 1, 1)),
    "window_layer_not_rotated": dict(rope_layout=(0, 0, 1, 1)),
    "silu_for_relu": dict(mlp_act="swiglu"),
    "router_fed_the_post_attention_stream": dict(router_input="mlp_norm"),
    "softmax_over_all_not_renormalised": dict(norm_topk_prob=False),
    "query_head_h_on_key_head_h_mod_kv": {},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_tolerance_bites(variables, fault):
    """Each wrong system stands at least 30 times outside the tolerance on
    the full forward."""
    v = (heads_interleaved(variables)
         if fault == "query_head_h_on_key_head_h_mod_kv" else variables)
    want = reference(variables["params"], TOKENS)
    assert distance(full_forward(build(**FAULTS[fault]), v), want) >= 30 * TOL


@pytest.mark.parametrize("fault", ["window_off_by_one", "window_ignored"])
def test_the_tolerance_bites_through_the_pool(variables, fault):
    """And a wrong window shows through chunked prefill and decode."""
    engine = engine_of(build(**FAULTS[fault]), variables)
    mine = served_of(engine, CHUNK).sequence(TOKENS[0], 44)
    want = np.asarray(reference(variables["params"], TOKENS[0]))
    assert distance(mine["logits"], want[-len(mine["logits"]):]) >= 30 * TOL


def test_the_engine_serves_requests_past_the_window(variables):
    """Through ``submit`` and ``step``: four requests of unequal length on
    three lanes, chunked prefill, both allocators; every token the engine
    returns is the reference's own best at its position (float32: no tie),
    and every page of both classes comes back."""
    engine = engine_of(build(), variables)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 512, n, dtype=np.int32)
               for n in (50, 37, 70, 20)]
    ids = [engine.submit(p, max_length=8) for p in prompts]
    results = engine.drain()
    for i, prompt in zip(ids, prompts):
        tokens = np.asarray(results[i].tokens)
        rated = np.asarray(reference(
            variables["params"], np.concatenate([prompt, tokens])))
        assert (rated[len(prompt) - 1:-1].argmax(-1) == tokens).all()
    manager = engine.cache_manager
    assert manager.pool.pages_in_use == manager.window_pool.pages_in_use == 0
    manager.pool.check_invariants()
    manager.window_pool.check_invariants()
    snap = engine.metrics.snapshot()
    assert snap["window_pages_recycled"] > 0
    assert snap["pages_in_use_window"] == 0
    decodes = [s for s in get_recorder().spans()
               if s.name == "serving.decode" and "window_rows" in s.attrs]
    assert decodes and all(
        0 < s.attrs["window_rows"] <= s.attrs["full_rows"] for s in decodes)
    assert any(s.attrs["window_rows"] < s.attrs["full_rows"] for s in decodes)


def test_healthz_reports_both_page_classes_and_refusals(variables):
    engine = engine_of(build(), variables)
    health = engine.health()
    assert health["capabilities"]["page_classes"] == ["full", "window"]
    assert set(health["page_classes"]) == {"full", "window"}
    assert health["page_classes"]["window"]["usable_pages"] == 3 * 5
    model = build()
    for kw, word in ((dict(prefix_cache=True), "prefix reuse"),
                     (dict(role="prefill"), "role"),
                     (dict(kv_dtype="int8"), "int8 KV"),
                     (dict(spec=True), "speculative")):
        with pytest.raises(ValueError, match=word):
            engine_of(model, variables, **kw)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(model, variables, slots=2, cache_len=CACHE_LEN,
                      page_size=PAGE)


def test_recovery_replays_in_chunks_and_returns_every_page(variables):
    """A fault rolls the tick back and ``recover()`` replays the active
    request's history chunk by chunk (a window class holds the window plus
    one chunk, not a whole history); the tokens are what they would have
    been and both classes come back whole."""
    prompt = np.random.default_rng(2).integers(1, 512, 60, dtype=np.int32)
    clean = engine_of(build(), variables)
    rid = clean.submit(prompt, max_length=8)
    want = np.asarray(clean.drain()[rid].tokens)
    engine = engine_of(build(), variables)
    rid = engine.submit(prompt, max_length=8)
    while not engine._active:
        engine.step()
    engine.step()
    engine.recover()
    engine.cache_manager.window_pool.check_invariants()
    got = np.asarray(engine.drain()[rid].tokens)
    assert (got == want).all()
    manager = engine.cache_manager
    assert manager.pool.pages_in_use == manager.window_pool.pages_in_use == 0


@pytest.mark.parametrize("pairs", [6, 144, 256, 1024, 3070, 3072])
def test_the_running_count_of_the_row_layout_is_the_cumulative_sum(pairs):
    """The row layout's running count is taken a block of 128 rows at a
    time (``parallel/moe.py`` ``_running_count``), for a tick's few pairs
    (one block, padded) as for a prefill chunk's thousands."""
    from fleetx_tpu.parallel import moe

    picks = np.random.default_rng(pairs).integers(0, 64, pairs)
    onehot = np.eye(64, dtype=np.int32)[picks]
    got = np.asarray(jax.jit(moe._running_count)(jnp.asarray(onehot)))
    assert (got == np.cumsum(onehot, axis=0)).all()
    dest, src, sizes, tile_expert, num_tiles = moe.expert_row_layout(
        jnp.asarray(picks.reshape(-1, 2)), 64, 16)
    assert (np.asarray(sizes) == np.bincount(picks, minlength=64)).all()
    assert len(set(np.asarray(dest).tolist())) == pairs    # no row twice
    assert (np.asarray(src)[np.asarray(dest)] == np.arange(pairs) // 2).all()


def test_the_block_fields_are_checked_by_name():
    for changes, word in (
            (dict(num_key_value_heads=3), "num_key_value_heads"),
            (dict(rope_layout=(0, 1, 1)), "rope_layout"),
            (dict(sliding_window_layout=(0, 1, 2, 1)), "sliding_window_layout"),
            (dict(sliding_window=512), "sliding_window"),
            (dict(router_input="norm1"), "router_input"),
            (dict(mlp_act="reglu", expert_mode=False, num_experts=1,
                  router_input="mlp_norm"), "reglu")):
        with pytest.raises(ValueError, match=word):
            build(**changes)
    with pytest.raises(NotImplementedError, match="qk_norm"):
        build(qk_norm=True)
    cfg = build().cfg
    assert (cfg.head_dim, cfg.kv_heads, cfg.layer_kinds) == (16, 2, True)
    assert dataclasses.replace(cfg, head_size=None).head_dim == 8
    plain = GPTConfig()
    assert not plain.layer_kinds and plain.kv_heads == 16
    assert plain.head_dim == 64 and plain.window_layers == (0,) * 24


def test_the_parameter_count_is_the_programs_own():
    """The configuration file's arithmetic against the model's own tree at
    the published widths (shapes only)."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "perfbench/configs/smallthinker-21b-a3b-l8.json")) as f:
        sizes = json.load(f)["model"]
    model = GPTForPretraining(GPTConfig.from_model_config(sizes))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            np.zeros((1, 8), np.int32))["params"]
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    layer = 2560 * 3584 * 2 + 2560 * 512 * 2 + 2560 * 64 + 64 * 3 * 2560 * 768
    norms = 8 * 2 * 2560 + 2560
    assert count == 8 * layer + 2 * 151936 * 2560 + norms
    assert abs(count - 3.967e9) < 1e6
