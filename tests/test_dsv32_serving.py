"""DeepSeek-V3.2's block on the CPU at a small size, seeded random weights:
latent attention UNDER A LEARNED INDEXER (``models/gpt/latent.py``) served
through the page pool's three leaves, on prompts several times ``index_topk``
long, against the plain float32 reference
(``perfbench/reference/dsv32_f32.py``): the logits outside the cache, in
chunks and ticks, through the engine cold and on a hit that must resume the
indexer's keys; the index scores and the selected sets; the exact top-k
against a stable sort; the chunk kernel under a mask against its plain twin;
the router's bias in the groups and the choice and never in a weight, the
shares adding up to the uncut layer; a prompt no longer than ``index_topk``
against the same model without the indexer; what the family refuses; and
every planted fault failing."""

import dataclasses

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import plain_greedy, sharing_programs, traced_apply

from fleetx_tpu.models.gpt import latent
from fleetx_tpu.models.gpt.generation import (GenerationConfig,
                                              init_decode_cache)
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.parallel import moe_share
from perfbench import probe_dsv32
from perfbench.reference import dsv32_f32

TOPK = 24
SIZES = dict(
    family="dsv32", vocab_size=128, hidden_size=64, num_layers=3,
    num_attention_heads=4, ffn_hidden_size=32, max_position_embeddings=512,
    position_embedding="rope", norm="rmsnorm", norm_eps=1e-6,
    mlp_act="swiglu", use_bias=False, tie_word_embeddings=False,
    layer_types=["latent_attention"] * 3, num_dense_layers=1,
    dense_ffn_hidden_size=96, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_scaling_factor=40.0, rope_scaling_mscale=1.0,
    rope_scaling_mscale_all_dim=1.0, rope_scaling_original_max_position=64,
    num_experts=4, num_routed_experts=16, first_expert_held=4, top_k=4,
    gate="sigmoid_topk", n_group=4, topk_group=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, num_shared_experts=1, use_expert_bias=True,
    expert_bias_init_std=0.05, index_n_heads=4, index_head_dim=16,
    index_topk=TOPK, hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0, dtype="float32",
    use_flash_attention=False)
TOL = 2e-5    # float32 against float32: 2e-7 read, of logits up to 0.65
N = 120       # five times ``index_topk``


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
    return np.random.default_rng(0).integers(1, 128, N, dtype=np.int32)


@pytest.fixture(scope="module")
def reference(built, tokens):
    return {k: np.asarray(v) for k, v in dsv32_f32.configured(SIZES)(
        built[1]["params"], tokens, with_all=True).items()}


def paged(model, pages=17, page=8, cache_len=128):
    return model.clone(cfg=dataclasses.replace(
        model.cfg, decode_cache_len=cache_len, decode_num_pages=pages,
        decode_page_size=page))


def forward(model, params, cache, ids, at, tables, rows=None, probe=False):
    pos = at[:, None] + jnp.arange(ids.shape[1])[None]
    logits, mut = model.apply(
        {"params": params, "cache": cache}, ids, pos, rows, decode=True,
        cache_positions=at, block_tables=tables,
        mutable=["cache"] + (["routing"] if probe else []))
    return (logits, mut["cache"], mut["routing"]) if probe else (
        logits, mut["cache"])


def cached_logits(model, params, tokens, chunks):
    """The logits of ``tokens`` through the cache: ``chunks`` of one lane,
    then ticks of two lanes, one idle. Traced anew at every call (a planted
    fault is in the trace)."""
    served = paged(model)
    cache = init_decode_cache(served, 2)
    table = jnp.arange(1, 17, dtype=jnp.int32)[None]
    step = jax.jit(lambda cache, ids, at, tables, rows=None: forward(
        served, params, cache, ids, at, tables, rows))
    out, at = [], 0
    for n in chunks:
        logits, cache = step(cache, jnp.asarray(tokens[None, at:at + n]),
                             jnp.asarray([at]), table)
        out.append(logits[0])
        at += n
    tables = jnp.concatenate([table, jnp.zeros_like(table)])
    for i in range(at, len(tokens)):
        logits, cache = step(cache, jnp.asarray([[tokens[i]], [0]]),
                             jnp.asarray([i, 127]), tables,
                             jnp.asarray([[True], [False]]))
        out.append(logits[:1, 0])
    return np.concatenate([np.asarray(o) for o in out])


def sown(routing):
    return {jax.tree_util.keystr(path[-2:-1]).strip("[']"): np.asarray(leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(routing)[0]}


# ------------------------------------------------ against the reference

def test_the_plain_forward_is_the_reference_and_prunes(built, tokens,
                                                       reference):
    model, variables = built
    plain, mut = traced_apply(model, variables, jnp.asarray(tokens[None]),
                              mutable=["routing"])
    assert np.abs(np.asarray(plain[0]) - reference["logits"]).max() < TOL
    # (3) the index scores and the sets, every layer and position
    mine = sown(mut["routing"])
    seen = np.tril(np.ones((N, N), bool))
    assert np.abs(np.where(seen, mine["index_scores"][:, 0], 0)
                  - reference["index"]).max() < 1e-5
    assert (mine["index_sets"][:, 0] == reference["sets"]).all()
    sizes = reference["sets"].sum(-1)
    assert (sizes == np.minimum(np.arange(N) + 1, TOPK)[None]).all()
    # most of a long prompt's rows are NOT attended over
    assert reference["sets"][:, -1].mean() == TOPK / N


@pytest.mark.parametrize("chunks", [(96,), (32, 32, 32), (8, 88)])
def test_chunked_prefill_then_ticks_are_the_reference(built, tokens,
                                                      reference, chunks):
    """A chunk scores the index keys read back from the pool's third leaf
    and attends under its rows' sets; a tick gathers its lane's chosen rows
    and takes the absorbed form: both are the reference's full forward."""
    got = cached_logits(built[0], built[1]["params"], tokens, chunks)
    assert np.abs(got - reference["logits"]).max() < TOL


def test_the_cache_has_three_leaves_under_one_table(built):
    cache = init_decode_cache(paged(built[0]), 2)
    shapes = {path[-1].key: leaf.shape for path, leaf in
              jax.tree_util.tree_flatten_with_path(cache)[0]}
    assert shapes == {"cached_key": (51, 8, 32), "cached_value": (51, 8, 128),
                      "cached_index": (51, 8, 16), "moe_stats": (2, 24)}
    assert built[0].cfg.state_kinds == ("latent",) and built[0].cfg.indexed


def test_a_tick_and_a_chunk_sow_their_scores_and_sets(built, tokens,
                                                      reference):
    model, variables = built
    served = paged(model)
    cache = init_decode_cache(served, 2)
    table = jnp.arange(1, 17, dtype=jnp.int32)[None]
    _, cache, routing = forward(served, variables["params"], cache,
                                jnp.asarray(tokens[None, :96]),
                                jnp.asarray([0]), table, probe=True)
    chunk = sown(routing)
    assert (chunk["index_sets"][:, 0, :, :N] == reference["sets"][:, :96]
            ).all()
    tables = jnp.concatenate([table, jnp.zeros_like(table)])
    _, cache, routing = forward(
        served, variables["params"], cache, jnp.asarray([[tokens[96]], [0]]),
        jnp.asarray([96, 127]), tables, jnp.asarray([[True], [False]]),
        probe=True)
    tick = sown(routing)
    assert (tick["index_sets"][:, 0, 0, :N] == reference["sets"][:, 96]).all()
    assert not tick["index_sets"][:, 1].any()      # the idle lane: no row
    assert np.abs(tick["index_scores"][:, 0, 0, :97]
                  - reference["index"][:, 96, :97]).max() < 1e-5


# ------------------------------------------------------ the exact top-k

@pytest.mark.parametrize("k", [1, 5, 24, 64])
def test_select_rows_is_the_stable_sorts_top_k(k):
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(7, 64)).astype(np.float32)
    scores[0, :] = 0.5                      # every row ties
    scores[1, ::2] = -0.25                  # ties above and below others
    scores[2, 3], scores[2, 9] = 0.0, -0.0  # the two zeros are one value
    scores[3] = np.abs(scores[3])           # (ReLU-like: many equal zeros)
    scores[3, ::3] = 0.0
    valid = rng.random((7, 64)) < 0.8
    valid[4] = False                        # no row at all
    valid[5, 10:] = False                   # fewer than k
    got = np.asarray(latent.select_rows(jnp.asarray(scores),
                                        jnp.asarray(valid), k))
    want = np.asarray(dsv32_f32.select(jnp.asarray(scores),
                                       jnp.asarray(valid), k))
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(valid.sum(-1), k)).all()


def stable_top_rows(scores, end, k):
    """numpy: of each lane's rows ``[0, end)`` the ``k`` highest by a STABLE
    sort (a tie to the lower position), in position order; ``t`` behind."""
    lanes, t = scores.shape
    out = np.full((lanes, k), t, np.int32)
    for lane in range(lanes):
        best = np.argsort(-scores[lane, :end[lane]], kind="stable")[:k]
        out[lane, :len(best)] = np.sort(best)
    return out


@pytest.mark.parametrize("t,k", [
    (256, 24), (1040, 64), (384, 1), (256, 256), (1040, 1040), (128, 200),
    (1040, 1100)], ids=lambda v: str(v))
def test_top_rows_is_the_stable_sorts_top_k_in_position_order(t, k):
    """A table of whole 128-row blocks and not (1,040), ``k`` below, at and
    above ``t``; lanes that hold 0, 1, ``k - 1``, ``k``, ``k + 1`` and ``t``
    rows, then one of equal scores, one with ties across the ``k``-th
    place, one of zeros of both signs, one of negative scores."""
    rng = np.random.default_rng(t + k)
    scores = rng.normal(size=(10, t)).astype(np.float32)
    end = np.clip([0, 1, k - 1, k, k + 1, t, t, t, t, t - 3], 0, t).astype(
        np.int32)
    scores[6] = 0.5
    scores[7, ::3] = np.sort(scores[7])[-min(k, t)]
    scores[8] = np.abs(scores[8])
    scores[8, ::2], scores[8, 1::4] = 0.0, -0.0
    scores[9] = -np.abs(scores[9])
    scores[9, 5::7] = scores[9, 5]
    chosen, count = jax.jit(latent.top_rows, static_argnums=3)(
        jnp.asarray(scores), jnp.arange(t)[None, :] < end[:, None],
        jnp.asarray(end), k)
    assert chosen.dtype == jnp.int32 and chosen.shape == (10, k)
    assert (np.asarray(count) == np.minimum(end, k)).all()
    # (position for position, the ``t`` of every place past ``count`` too)
    assert (np.asarray(chosen) == stable_top_rows(scores, end, k)).all()


def sorting_primitives(jaxpr):
    """The equations of ``jaxpr``, and of every program nested in it, that
    sort, take a top-k or scatter (``jnp.argsort`` is a ``sort``)."""
    found = []
    for eqn in jaxpr.eqns:
        if any(word in eqn.primitive.name
               for word in ("sort", "top_k", "scatter")):
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += sorting_primitives(sub)
    return found


# the tick of the tiny preset (perfbench/cells/dsv32-l5-serve-longqa-sparse
# .json ``tiny``: 3 lanes of 256 rows, ``index_topk`` 24) and the cell's own
@pytest.mark.parametrize("lanes,t,k", [(3, 256, 24), (6, 50176, 2048)])
def test_a_ticks_selection_holds_no_sort(lanes, t, k):
    def call(scores, end):
        seen = jnp.arange(t, dtype=jnp.int32)[None, :] < end[:, None]
        return latent.top_rows(scores, seen, end, k)

    jaxpr = jax.make_jaxpr(call)(
        jax.ShapeDtypeStruct((lanes, t), jnp.float32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32))
    assert sorting_primitives(jaxpr.jaxpr) == []
    # (the reader reads what it is meant to: the form this one replaced)
    assert sorting_primitives(jax.make_jaxpr(
        lambda x: jnp.sort(jax.lax.top_k(x, 2)[1]))(jnp.zeros(8)).jaxpr
    ) == ["top_k", "sort"]


def test_the_chunk_kernel_under_a_mask_is_its_plain_twin(monkeypatch):
    from fleetx_tpu.ops.pallas import mla_prefill

    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    rng = np.random.default_rng(0)
    s, heads, nope, rot, vd, c, t = 32, 4, 16, 8, 16, 32, 2048
    q = jnp.asarray(rng.normal(size=(s, heads, nope + rot)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(c, heads, nope + vd)) * 0.2, jnp.float32)
    ckv = jnp.asarray(rng.normal(size=(t, c)), jnp.float32)
    kr = jnp.pad(jnp.asarray(rng.normal(size=(t, rot)), jnp.float32),
                 ((0, 0), (0, 128 - rot)))
    cfg = GPTConfig.from_model_config(SIZES)
    for start in (0, 1000, 1500, t - s):
        seen = np.arange(t)[None, :] <= start + np.arange(s)[:, None]
        mask = jnp.asarray(seen & (rng.random((s, t)) < 0.1))
        # rows past the chunk may hold anything, a NaN too
        dirty = ckv.at[start + s:].set(jnp.nan)
        got = mla_prefill.mla_prefill(
            q, w, dirty, kr, jnp.int32(start), nope=nope, scale=0.2,
            score_type=jnp.float32, mask=mask)
        want = latent._chunk(cfg, q, w, ckv, kr[:, :rot], jnp.int32(start),
                             0.2, mask)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-6, start


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


def test_a_chunks_attention_compiles_for_the_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """One layer's indexer, selection and attention of the cell's 512-row
    chunk over a lane's 50,176 rows, through XLA:TPU and Mosaic's own passes:
    it holds ``fleetx_dsa_prefill`` and no float32 scores of 128 heads."""
    from fleetx_tpu.ops.pallas import mla_prefill
    from perfbench import harness

    monkeypatch.setattr(mla_prefill, "_interpret", lambda: False)
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    cfg = GPTConfig.from_model_config({**harness.load_json(
        "perfbench/configs/dsv32-ep16-l5.json")["model"],
        "dtype": "bfloat16", "use_flash_attention": True})
    rows, t, pages = 512, 50176, 5 * 34497

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def chunk(q, qi, hw, w_kvb, ckv_pool, kr_pool, ki_pool, table, start):
        ckv, kr, ki = (pool[table].reshape(-1, pool.shape[-1])
                       for pool in (ckv_pool, kr_pool, ki_pool))
        seen = jnp.arange(t)[None, :] <= start + jnp.arange(rows)[:, None]
        mask = latent.select_rows(
            latent._chunk_index_scores(qi, hw, ki, start), seen, 2048)
        return latent._prefill(cfg, q, w_kvb, ckv, kr, start, 0.1, mask=mask)

    compiled = jax.jit(chunk).lower(
        spec((rows, 128, 192)), spec((rows, 64, 128)),
        spec((rows, 64), jnp.float32), spec((512, 128, 256)),
        spec((pages, 16, 512)), spec((pages, 16, 128)),
        spec((pages, 16, 128)), spec((t // 16,), jnp.int32),
        spec((), jnp.int32)).compile()
    text = compiled.as_text()
    assert mla_prefill.SELECTED_KERNEL_NAME in text
    assert "f32[128,512," not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


# ------------------------------------------------------------ the engine

@sharing_programs
def engine_of(model, variables, **kwargs):
    from fleetx_tpu.serving import ServingEngine

    defaults = dict(
        slots=3, cache_len=256, page_size=8, num_pages=3 * 32 + 1,
        prefill_chunk=32, prefill_bucket=16, prefix_cache=True,
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=6))
    return ServingEngine(model, variables, **{**defaults, **kwargs})


def test_the_engine_serves_it_cold_and_on_a_hit_with_spans_and_counters(
        built):
    """Through ``ServingEngine``: chunked prefill, the tick, and questions
    that resume from the document's pages in the trie, three leaves each;
    greedy tokens are the plain model's; the spans and counters say what
    was scored and what was selected."""
    import time

    from fleetx_tpu.obs.tracing import get_recorder

    model, variables = built
    began = time.perf_counter()
    engine = engine_of(model, variables)
    rng = np.random.default_rng(1)
    document = rng.integers(1, 128, 96, dtype=np.int32)

    saved = []
    for q in range(3):
        prompt = np.concatenate([document, rng.integers(
            1, 128, 10 + q, dtype=np.int32)])
        rid = engine.submit(prompt, max_length=6)
        result = engine.drain()[rid]
        assert [int(t) for t in result.tokens] == plain_greedy(
            model, variables, prompt, 6)
        saved.append(engine.metrics.snapshot()["prefill_tokens_saved"])
    assert saved == [0, 96, 192]
    snap = engine.metrics.snapshot()
    # three layers x 8 rows x (32 + 128 + 16) columns x 4 bytes
    assert snap["latent_page_bytes"] == 3 * 8 * 176 * 4
    assert snap["index_pool_bytes"] == 3 * 97 * 8 * 16 * 4
    spans = [s for s in get_recorder().spans() if s.start_s >= began]
    ticks = [s for s in spans if s.name == "serving.decode"
             and "index_rows" in s.attrs]
    chunks = [s for s in spans if s.name == "serving.prefill_chunk"
              and "index_rows" in s.attrs]
    assert ticks and chunks
    # the first chunk: row i scores i + 1 keys and keeps min(i + 1, 24)
    assert chunks[0].attrs["index_rows"] == 32 * 33 // 2
    assert chunks[0].attrs["selected_rows"] == 24 * 25 // 2 + 8 * 24
    assert chunks[1].attrs["selected_rows"] == 32 * 24
    assert chunks[1].attrs["latent_rows"] == 64
    last = ticks[-1].attrs                      # one lane decoding
    assert last["selected_rows"] == TOPK and last["index_rows"] == last[
        "latent_rows"] > 100
    admits = [s for s in spans if s.name == "serving.admit"
              and "selected_rows" in s.attrs]  # a question on a hit
    assert admits and admits[-1].attrs["selected_rows"] % TOPK == 0
    counted = sum(s.attrs["index_rows"] for s in ticks + chunks + admits)
    assert snap["index_rows_scored"] == counted
    assert snap["rows_selected"] == sum(
        s.attrs["selected_rows"] for s in ticks + chunks + admits)
    assert snap["rows_selected"] < snap["index_rows_scored"] / 3
    engine.cache_manager.pool.check_invariants()


def test_a_hit_resumes_the_index_keys_of_the_matched_pages(built, tokens):
    """The check's programs on a trie hit: the sets are the reference's; with
    the matched pages' index keys ZEROED (a hit that did not resume the third
    leaf) the document's rows all score 0, and the selection changes."""
    from perfbench.drivers import serve_closed_loop_dsa as driver

    model, variables = built
    engine = engine_of(model, variables)
    want = {k: np.asarray(v) for k, v in dsv32_f32.configured(SIZES)(
        variables["params"], tokens, tail=24, with_all=True).items()}
    engine.submit(np.concatenate([tokens[:96], tokens[:5]]), max_length=2)
    engine.drain()
    served = driver.Served(engine)
    hit = served.sequence(tokens, N - 4, 20)
    assert hit["matched"] == 96
    assert np.abs(hit["logits"] - want["logits"]).max() < TOL
    assert ((hit["index_sets"][..., :N] > 0) == want["sets"]).all()
    assert np.abs(hit["rows"] - want["rows"]).max() < 1e-5
    manager = engine.cache_manager
    manager.cache = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf)
        if path[-1].key == "cached_index" else leaf, manager.cache)
    blind = served.sequence(tokens, N - 4, 20)
    assert blind["matched"] == 96
    assert ((blind["index_sets"][..., :N] > 0) != want["sets"]).any()
    assert np.abs(blind["logits"] - want["logits"]).max() > 10 * TOL


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
    assert refused in str(err.value) and "'dsv32'" in str(err.value)


def test_a_mesh_and_one_shot_generate_are_refused(built):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 2), ("dp", "mp"))
    with pytest.raises(ValueError, match="supports_mesh"):
        engine_of(*built, mesh=mesh)
    contiguous = built[0].clone(cfg=dataclasses.replace(
        built[0].cfg, decode_cache_len=64))
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        init_decode_cache(contiguous, 1)


# ------------------------------------- no longer than ``index_topk``

def test_a_prompt_of_at_most_index_topk_rows_is_the_model_without_the_indexer(
        built, tokens):
    """Every row is selected by definition: value for value what the same
    weights give with the indexer switched off (a tick attends over its
    gathered rows, the same rows in the same order, where the model without
    an indexer reads them through the table: the sums agree to a rounding)."""
    model, variables = built
    off = GPTForPretraining(dataclasses.replace(
        model.cfg, index_n_heads=0, index_head_dim=0, index_topk=0))
    params = jax.tree_util.tree_map_with_path(lambda path, leaf: leaf,
                                              variables["params"])
    op = params["gpt"]["layers"]["attention"]["op"]
    params = {**params, "gpt": {**params["gpt"], "layers": {
        **params["gpt"]["layers"], "attention": {
            **params["gpt"]["layers"]["attention"],
            "op": {k: v for k, v in op.items()
                   if not k.startswith("index_")}}}}}
    short = tokens[:TOPK]
    with_it = traced_apply(model, variables, jnp.asarray(short[None]))
    without = traced_apply(off, {"params": params}, jnp.asarray(short[None]))
    assert (np.asarray(with_it) == np.asarray(without)).all()
    a = cached_logits(model, variables["params"], short, (16,))
    b = cached_logits(off, params, short, (16,))
    assert np.abs(a - b).max() < 2e-6
    # and one row further the two part
    longer = tokens[:TOPK + 40]
    assert np.abs(np.asarray(traced_apply(model, variables,
                                          jnp.asarray(longer[None])))
                  - np.asarray(traced_apply(off, {"params": params},
                                            jnp.asarray(longer[None])))
                  ).max() > 10 * TOL


# ----------------------------------------------------- the router's bias

def layer_of(cfg, x, params=None):
    layer = moe_share.SharedMoEMLP(cfg)
    if params is None:
        params = flax.core.meta.unbox(jax.jit(layer.init)(
            jax.random.PRNGKey(1), x))["params"]
    return traced_apply(layer, {"params": params}, x), params


def test_the_bias_joins_the_groups_sums_and_the_choice_on_a_written_out_case():
    scores = jnp.asarray([[0.9, 0.1, 0.5, 0.5, 0.6, 0.3, 0.2, 0.2]])
    none = moe_share.group_limited_topk(scores, 2, 4, 2)
    assert sorted(np.asarray(none)[0].tolist()) == [0, 2]
    # a bias lifts group 3 (experts 6, 7) over groups 1 and 2, and expert 7
    # over expert 6; the best of all stays expert 0
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 0.5, 0.75])
    with_it = moe_share.group_limited_topk(scores, 2, 4, 2, bias)
    assert sorted(np.asarray(with_it)[0].tolist()) == [0, 7]
    want, ranked = dsv32_f32._route(scores, bias, dict(
        n_group=4, topk_group=2, top_k=2))
    assert sorted(np.asarray(want)[0].tolist()) == [0, 7]
    assert np.isinf(np.asarray(ranked)[0, 2:6]).all()


def test_the_shares_add_up_to_the_uncut_layer_with_the_bias_in_the_choice():
    """4 shares of 4 of 16 routed experts: the routed parts of all the
    shares, with the shared expert counted ONCE, are the uncut reference's
    layer; the bias moves the choice and no weight."""
    base = {**SIZES, "num_routed_experts": 16, "top_k": 4, "n_group": 4,
            "topk_group": 2, "num_shared_experts": 1,
            "expert_bias_init_std": 0.3}
    whole_cfg = GPTConfig.from_model_config(
        {**base, "num_experts": 16, "first_expert_held": 0})
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 64), jnp.float32)
    whole, params = layer_of(whole_cfg, x)
    assert float(jnp.abs(params["expert_bias"]).max()) > 0.1
    no_shared = GPTConfig.from_model_config(
        {**base, "num_experts": 16, "first_expert_held": 0,
         "num_shared_experts": 0})
    routed_whole, _ = layer_of(no_shared, x, params)
    total = whole - routed_whole          # the shared expert, once
    for first in (0, 4, 8, 12):
        cfg = GPTConfig.from_model_config(
            {**base, "num_experts": 4, "first_expert_held": first,
             "num_shared_experts": 0})
        share = {**params, **{k: params[k][first:first + 4]
                              for k in ("w_gate", "w_up", "w_down")}}
        total = total + layer_of(cfg, x, share)[0]
    assert np.abs(np.asarray(total) - np.asarray(whole)).max() < 2e-6
    stack = jax.tree.map(lambda leaf: leaf[None], params)
    settings = dsv32_f32._settings(
        {**base, "num_layers": 1, "first_expert_held": 0})
    want, chosen, scores, _ = dsv32_f32._experts(x[0], stack, 0, settings)
    assert np.abs(np.asarray(whole[0]) - np.asarray(want)).max() < 2e-6
    # the bias changed the choice somewhere, and the weights are the raw
    # scores' (the reference without a bias chooses otherwise)
    unbiased = dsv32_f32._experts(
        x[0], {**stack, "expert_bias": jnp.zeros_like(stack["expert_bias"])},
        0, settings)[1]
    assert (np.sort(np.asarray(chosen)) != np.sort(np.asarray(unbiased))).any()
    with probe_dsv32.planted("bias_in_weights"):
        wrong, _ = layer_of(whole_cfg, x, params)
    assert np.abs(np.asarray(wrong) - np.asarray(whole)).max() > 1e-3


def test_a_share_without_the_bias_runs_as_before():
    """A.X-K1's layer (no ``use_expert_bias``) holds no bias leaf and
    chooses as ``group_limited_topk`` without one does."""
    cfg = GPTConfig.from_model_config({**SIZES, "use_expert_bias": False,
                                       "expert_bias_init_std": 0.0})
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 64), jnp.float32)
    _, params = layer_of(cfg, x)
    assert "expert_bias" not in params


# ------------------------------------------------------ planted faults

@pytest.mark.parametrize("fault", [
    "relu_left_out", "head_weights_left_out", "index_key_unrotated",
    "selects_from_unseen", "tick_ignores_selection", "bias_in_weights"])
def test_a_planted_fault_fails_the_tolerance(built, tokens, reference, fault):
    """Each fault moves the logits of chunks and ticks through the cache by
    far more than the tolerance the system as built is held to (``tick_
    ignores_selection``: the ticks' alone, the chunks' stay the
    reference's)."""
    with probe_dsv32.planted(fault):
        got = cached_logits(built[0], built[1]["params"], tokens, (32, 64))
    err = np.abs(got - reference["logits"]).max(-1)
    assert err[96:].max() > 10 * TOL, err.max()
    if fault == "tick_ignores_selection":
        assert err[:96].max() < TOL


def test_the_indexer_needs_all_three_sizes():
    with pytest.raises(ValueError, match="index_topk"):
        GPTConfig.from_model_config({**SIZES, "index_n_heads": 0})
    with pytest.raises(ValueError, match="without a latent_attention"):
        GPTConfig.from_model_config({
            **{k: v for k, v in SIZES.items() if not k.startswith((
                "q_lora", "kv_lora", "qk_", "v_head", "rope_scaling"))},
            "layer_types": ["full_attention"] * 3})
