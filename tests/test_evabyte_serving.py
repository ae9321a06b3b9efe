"""EvaByte's stack (EVA attention: an exact TUMBLING window beside one pooled
key/value row a chunk behind it, in two classes of page in every layer; a
float32 residual stream, RMSNorm as ``x^ (1 + w)``, eight prediction heads in
one untied matrix over a byte vocabulary) through the normal path, against the
plain float32 reference ``perfbench/reference/evabyte_f32.py`` at a tiny size
on seeded weights: the full forward; a prefill IN CHUNKS and decoding through
BOTH classes over three window boundaries, with prompt lengths at every
residue of the chunk (logits of all eight heads); a request preempted and
resumed, and an engine recovered after a tick; the window class's lifecycle
(never more than one window a lane, every page back at a boundary); a padded
bucket closing no chunk; the tick and both prefill buckets through the chip's
compiler at the published widths; what the configuration and the engine
refuse, each with its cause; and that the stacks WITHOUT the operator trace
the programs they traced before. Logits are compared, not tokens.

TOLERANCE. These tests compute in float32 on the CPU, where system and
reference differ only in the order of their sums: some 1e-6 of the
reference's logit deviation is read, and the limit is 2e-4. The bfloat16
limits of the chip are the benchmark driver's
(``perfbench/drivers/serve_closed_loop_eva.py``).
"""

import dataclasses
import functools

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import computed_once, traced_apply

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.serving import ServingEngine
from fleetx_tpu.serving.cache_manager import WindowPagePool
from perfbench import harness
from perfbench.drivers import serve_closed_loop_eva as driver
from perfbench.reference import evabyte_f32

TOL = 2e-4          # of the reference's logit standard deviation (docstring)
CONFIG = harness.load_json("perfbench", "configs", "evabyte-6.5b-pp4-l8.json")
MODEL = dict(harness.with_tiny(CONFIG, True)["model"])
WINDOW, CHUNK = MODEL["eva_window_size"], MODEL["eva_chunk_size"]   # 128, 8
PAGE, CACHE_LEN, PREFILL, BUCKET = CHUNK, 640, 32, 16
SIZES = dict(MODEL, use_flash_attention=False, dtype="float32")
reference = computed_once(evabyte_f32.configured(MODEL))
pooled_reference = computed_once(functools.partial(
    evabyte_f32.configured(MODEL), with_pooled=True))
VOCAB = MODEL["vocab_size"]


def build(**changes):
    return GPTForPretraining(GPTConfig.from_model_config({**SIZES, **changes}))


@pytest.fixture(scope="module")
def variables():
    """Seeded weights, the layers' matrices scaled up until the operator and
    the norms decide the logits, every norm weight moved OFF ZERO (the norm
    is ``x^ (1 + w)``: at ``w = 0`` an offset left out could not show)."""
    v = flax.core.meta.unbox(jax.jit(build().init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))

    def stir(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return 0.3 * jax.random.normal(jax.random.PRNGKey(len(name)),
                                           x.shape)
        if "eva_" in name:
            return x * 4.0
        return x * 2.0 if "layers" in name and "kernel']" in name else x

    return jax.tree_util.tree_map_with_path(stir, v)


def distance(got, expected) -> float:
    """Largest error in units of the expected values' deviation."""
    expected = np.asarray(expected)
    return float(np.abs(np.asarray(got) - expected).max() / expected.std())


def engine_of(model, variables, **kw):
    kw = {"slots": 3, "page_size": PAGE, "prefill_bucket": BUCKET,
          "cache_len": CACHE_LEN, "prefill_chunk": PREFILL,
          "prefix_cache": False, **kw}
    return ServingEngine(
        model, variables,
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=8), **kw)


@pytest.fixture(scope="module")
def engine(variables):
    return engine_of(build(), variables)


@pytest.fixture(scope="module")
def served(engine):
    """The check's own programs over that engine, compiled once."""
    return driver.Served(engine, 1)


def is_the_references_best(variables, prompt, tokens) -> bool:
    """Whether every one of ``tokens`` is the reference's own best (head 0)
    at its position behind ``prompt`` (float32: no tie)."""
    tokens = np.asarray(tokens)
    rated = np.asarray(reference(
        variables["params"], np.concatenate([prompt, tokens])))
    return bool((rated[len(prompt) - 1:-1, :VOCAB].argmax(-1) == tokens).all())


# ------------------------------------------------- the stack and the reference

def test_full_forward_matches_the_reference(variables):
    """Three windows and an open chunk at the end, every head."""
    tokens = np.random.default_rng(0).integers(0, VOCAB, 3 * WINDOW + 13,
                                               dtype=np.int32)
    mine = traced_apply(build(), variables, tokens[None])[0]
    theirs = reference(variables["params"], tokens)
    assert mine.shape == theirs.shape == (len(tokens), 8 * VOCAB)
    assert mine.dtype == jnp.float32
    assert distance(mine, theirs) < TOL


def test_separate_projections_match_too(variables):
    tokens = np.random.default_rng(1).integers(0, VOCAB, WINDOW + 21,
                                               dtype=np.int32)
    model = build(fuse_attn_qkv=False)
    v = flax.core.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(1), np.zeros((1, 8), np.int32)))
    assert distance(traced_apply(model, v, tokens[None])[0],
                    reference(v["params"], tokens)) < TOL


@pytest.mark.parametrize("prompt_len", [41 + 19 * r for r in range(CHUNK)])
def test_chunked_prefill_then_decode_through_both_classes(
        variables, served, prompt_len):
    """Prompt lengths at every residue of the chunk (41 + 19 r, r = 0..7),
    prefilled in chunks of 32 with a padded last bucket, then decoded one
    step at a time over THREE window boundaries: the logits of all eight
    heads at the prompt's last position and at every step."""
    assert sorted((41 + 19 * r) % CHUNK for r in range(CHUNK)) == list(
        range(CHUNK))
    decode = 3 * WINDOW + 3
    tokens = np.random.default_rng(prompt_len).integers(
        0, VOCAB, prompt_len + decode, dtype=np.int32)
    manager = served.engine.cache_manager
    tumbled = manager.window_pool.tumbled
    mine, pooled = served.sequence(tokens, prompt_len)
    assert manager.window_pool.tumbled - tumbled == (
        prompt_len + decode - 1) // WINDOW
    theirs, rows = pooled_reference(variables["params"], tokens,
                                    tail=1 + decode)
    assert mine.shape == theirs.shape == (1 + decode, 8 * VOCAB)
    assert distance(mine, theirs) < TOL
    # every chunk closed, by a chunk program or a tick, holds its pooled row
    assert pooled.shape == rows.shape == (2, 2, len(tokens) // CHUNK, 64)
    assert distance(pooled, rows) < TOL
    manager.pool.check_invariants()
    manager.window_pool.check_invariants()
    assert manager.pool.pages_in_use == manager.window_pool.pages_in_use == 0


def test_a_padded_bucket_closes_no_chunk(served):
    """27 true rows in a bucket of 32: three chunks close; the fourth, five
    padded rows long, leaves its summary row as the pool had it."""
    from fleetx_tpu.models.gpt.hybrid import layer_bases

    engine = served.engine
    manager, cfg = engine.cache_manager, engine.model.cfg
    manager.cache = jax.tree.map(jnp.zeros_like, manager.cache)
    tokens = np.random.default_rng(3).integers(0, VOCAB, 27, dtype=np.int32)
    lane, _ = manager.alloc(-1, tokens)
    try:
        served.prefill(lane, tokens)
        page = manager.lane_tables(lane)[0][0] + layer_bases(cfg)
        rows = np.asarray(manager.cache["gpt"]["layers"]["layer"]["attn"][
            "cached_key"][page])                     # [layers, page, width]
    finally:
        manager.free(lane)
    assert (np.abs(rows[:, :3]).max(-1) > 0).all()
    assert not rows[:, 3:].any()


def test_the_engine_serves_requests_across_boundaries(variables):
    """Requests of several lengths through ``submit`` and ``step``, together:
    three lanes, chunked prefill, both allocators; every token the engine
    returns is the reference's own best at its position (float32: no tie),
    no lane ever holds more than one window of the window class, and every
    page of both classes comes back."""
    from fleetx_tpu.obs import get_recorder

    engine = engine_of(build(), variables)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, VOCAB, n, dtype=np.int32)
               for n in (150, 77, 203, 64)]
    ids = [engine.submit(p, max_length=70) for p in prompts]
    manager = engine.cache_manager
    while engine._active or engine.scheduler.queue_depth or (
            engine._prefilling):
        engine.step()
        manager.window_pool.check_invariants()   # (at most a window a lane)
        held = manager.window_pool.end - manager.window_pool.first
        assert (held <= WINDOW // PAGE).all()
    results = engine.drain()
    for i, prompt in zip(ids, prompts):
        assert is_the_references_best(variables, prompt, results[i].tokens)
    assert manager.pool.pages_in_use == manager.window_pool.pages_in_use == 0
    manager.pool.check_invariants()
    snap = engine.metrics.snapshot()
    assert snap["eva_windows_tumbled"] >= 4 and snap["eva_chunks_closed"] > 60
    assert snap["pages_in_use_summary"] == snap["pages_in_use_window"] == 0
    decodes = [s for s in get_recorder().spans()
               if s.name == "serving.decode" and "eva_positions" in s.attrs]
    assert decodes and all(
        0 < s.attrs["eva_window_rows"] + s.attrs["eva_summary_rows"]
        <= s.attrs["eva_positions"] for s in decodes)
    assert any(s.attrs["eva_summary_rows"] for s in decodes)
    chunks = [s for s in get_recorder().spans()
              if s.name == "serving.prefill_chunk"
              and "eva_chunks_closed" in s.attrs]
    assert chunks and all(s.attrs["eva_chunks_closed"] <= PREFILL // CHUNK
                          for s in chunks)


def test_a_request_preempted_and_resumed_gives_the_same_answer(variables):
    """A request cancelled past a boundary with the tokens it has emitted and
    submitted again with that as its ``history``: the resumed lane rebuilds
    BOTH classes from ``prompt + history`` in chunks (the chunk programs
    close what the ticks had closed), and goes on as if never stopped."""
    prompt = np.random.default_rng(5).integers(0, VOCAB, 100, dtype=np.int32)
    engine = engine_of(build(), variables)
    rid = engine.submit(prompt, max_length=50)
    while len(engine.emitted_tokens(rid) or ()) < 33:
        engine.step()
    emitted = list(engine.emitted_tokens(rid))
    assert engine.cancel(rid) and 33 <= len(emitted) < 50
    again = engine.submit(prompt, max_length=50, history=emitted)
    got = list(engine.drain()[again].tokens)
    assert got[:len(emitted)] == emitted and len(got) == 50
    assert is_the_references_best(variables, prompt, got)
    manager = engine.cache_manager
    assert manager.pool.pages_in_use == manager.window_pool.pages_in_use == 0


def test_recovery_replays_in_chunks_and_returns_every_page(variables):
    """``recover()`` rebuilds both classes and replays the active request's
    history chunk by chunk; the tokens are what they would have been."""
    prompt = np.random.default_rng(2).integers(0, VOCAB, 120, dtype=np.int32)
    engine = engine_of(build(), variables)
    rid = engine.submit(prompt, max_length=20)
    while not engine._active:
        engine.step()
    for _ in range(11):    # past position 128: a boundary behind the lane
        engine.step()
    engine.recover()
    engine.cache_manager.window_pool.check_invariants()
    got = engine.drain()[rid].tokens
    assert len(got) == 20 and is_the_references_best(variables, prompt, got)
    manager = engine.cache_manager
    assert manager.pool.pages_in_use == manager.window_pool.pages_in_use == 0


# ------------------------------------------------------- the window class

def test_a_tumbling_window_gives_every_page_back_at_a_boundary():
    pool = WindowPagePool(num_pages=2 * 8 + 1, page_size=8, lanes=2,
                          table_pages=48, window=64, span=32, tumbling=True)
    assert pool.lane_pages == 8 and pool.can_admit(300)
    for start in range(0, 64, 32):
        assert pool.prepare(0, start, 32)
    assert pool.pages_in_use == 8 and pool.tumbled == 0
    pool.check_invariants()
    assert pool.prepare(0, 64)            # the first row of the next window
    assert (pool.pages_in_use, pool.tumbled, pool.recycled) == (1, 1, 8)
    assert not pool.tables[0, :8].any() and pool.tables[0, 8]
    for pos in range(65, 128):
        assert pool.prepare(0, pos)
        assert pool.end[0] - pool.first[0] <= 8
    assert pool.prepare(1, 0, 32) and pool.pages_in_use == 12
    pool.check_invariants()
    pool.free(0)
    pool.free(1)
    assert pool.pages_in_use == 0


def test_a_tumbling_window_holds_whole_spans():
    with pytest.raises(ValueError, match="tumbling"):
        WindowPagePool(17, 8, 2, 48, window=64, span=24, tumbling=True)


def test_admission_counts_both_classes(variables):
    """A summary class of ten pages admits two prompts of 300 (37 chunks,
    five pages each) and refuses a third beside them; the window class is
    counted by itself."""
    engine = engine_of(build(), variables, num_pages=11)
    manager = engine.cache_manager
    prompt = np.zeros(300, np.int32)
    lanes = []
    for _ in range(2):
        assert manager.can_admit(prompt)
        lanes.append(manager.alloc(len(lanes), prompt)[0])
    assert manager.pool.pages_in_use == 10
    assert not manager.can_admit(prompt)
    assert manager.class_counters()["admits_refused_summary"] == 1
    for lane in lanes:
        manager.free(lane)
    assert manager.tables.shape == (2, 3, CACHE_LEN // PAGE)


# ------------------------------------------------------------- refusals

@pytest.mark.parametrize("changes, word", [
    (dict(sliding_window=32), "sliding_window"),
    (dict(num_key_value_heads=2), "grouped heads"),
    (dict(kv_lora_rank=16), "latent"),
    (dict(index_topk=4), "indexer"),
    (dict(layer_types=("full_attention",) * 2), "layer_types"),
])
def test_check_refuses_eva_beside(changes, word):
    with pytest.raises((NotImplementedError, ValueError), match=word):
        build(**changes)


@pytest.mark.parametrize("changes, word", [
    (dict(eva_chunk_size=0), "come together"),
    (dict(eva_chunk_size=24), "whole chunks"),
    (dict(residual_dtype="float16"), "residual_dtype"),
    (dict(norm_unit_offset=True, norm="layernorm", eva_window_size=0,
          eva_chunk_size=0), "norm_unit_offset"),
    (dict(tie_word_embeddings=True), "num_pred_heads"),
])
def test_the_fields_are_checked_by_name(changes, word):
    with pytest.raises(ValueError, match=word):
        build(**changes)


def _mesh():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                             ("dp", "fsdp", "mp"))


@pytest.mark.parametrize("kw, word", [
    (dict(prefix_cache=True), "prefix reuse"),
    (dict(spec=True), "speculative"),
    (dict(kv_dtype="int8"), "int8 KV"),
    (dict(host_cache_bytes=1 << 20), "host or disk page tier"),
    (dict(mesh=None), "serving mesh"),
    (dict(role="prefill"), "role"),
])
def test_the_engine_refuses_for_the_family(variables, kw, word):
    if "mesh" in kw:
        kw = dict(mesh=_mesh())
    with pytest.raises(ValueError, match=word):
        engine_of(build(), variables, **kw)


@pytest.mark.parametrize("kw, word", [
    (dict(prefill_chunk=0), "prefill_chunk"),
    (dict(prefill_chunk=48), "divide the window"),
    (dict(prefill_bucket=12), "whole chunks"),
    (dict(page_size=16), "one chunk"),
])
def test_the_engine_refuses_shapes_a_chunk_program_cannot_take(
        variables, kw, word):
    with pytest.raises(ValueError, match=word):
        engine_of(build(), variables, **kw)


def test_healthz_reports_both_page_classes(engine):
    health = engine.health()
    assert health["capabilities"]["page_classes"] == ["summary", "window"]
    assert set(health["page_classes"]) == {"summary", "window"}
    assert health["page_classes"]["window"]["usable_pages"] == 3 * (
        WINDOW // PAGE)
    assert health["page_classes"]["summary"]["usable_pages"] == 3 * (
        CACHE_LEN // CHUNK // PAGE)
    assert not health["capabilities"]["supports_spec"]


# ------------------------ the stacks without the operator trace what they did

# the digests of tests/test_longcat_serving.py's ``traced_programs``, taken on
# the parent commit (c5adbb9); ``masked``: whether the call hands the rows'
# mask (a stack of grouped heads without ``layer_types`` refuses one)
UNCHANGED = {
    ("perfbench/configs/smallthinker-21b-a3b-l8.json", False): (
        "7d50bde3e87d2510", "f97a049eb7f0d2df"),
    ("perfbench/configs/keye-vl2-30b-l6.json", True): (
        "712006a87ba4b5f2", "c541974dbfce20f7"),
    ("perfbench/configs/ling3-flash-ep8-l7.json", True): (
        "89a6dc377ffea92a", "704952f27af1865b"),
    ("perfbench/configs/gpt-1.3b.json", False): (
        "a640dbc84d17e346", "05a61371d25630e1"),
}


def traced_programs(path, masked):
    """``tests/test_longcat_serving.py``'s ``traced_programs`` (the jaxprs
    of the tiny model's chunk and tick over a page pool), with the rows'
    mask handed over or not."""
    from fleetx_tpu.models.gpt.generation import init_decode_cache
    from tests.test_longcat_serving import paged

    data = harness.with_tiny(harness.load_json(path), True)
    cfg = GPTConfig.from_model_config(
        {**data["model"], "dtype": "float32", "use_flash_attention": False})
    model = paged(GPTForPretraining(cfg), pages=13, page=8, cache_len=96)
    classes = 2 if cfg.sliding_window else 1
    if classes == 2:
        model = model.clone(cfg=dataclasses.replace(
            model.cfg, decode_window_pages=13))
    variables = jax.eval_shape(lambda: flax.core.meta.unbox(model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))))
    cache = jax.eval_shape(lambda: init_decode_cache(model, 3))

    def call(params, cache, ids, at, tables, rows):
        pos = at[:, None] + jnp.arange(ids.shape[1])[None]
        return model.apply(
            {"params": params, "cache": cache}, ids, pos, rows, decode=True,
            cache_positions=at, block_tables=tables, mutable=["cache"])

    out = []
    for lanes, rows in ((1, 16), (3, 1)):
        shape = (lanes, 12) if classes == 1 else (classes, lanes, 12)
        out.append(str(jax.make_jaxpr(call)(
            variables["params"], cache, jnp.zeros((lanes, rows), jnp.int32),
            jnp.zeros((lanes,), jnp.int32), jnp.zeros(shape, jnp.int32),
            jnp.ones((lanes, rows), bool) if masked else None)))
    return out


@pytest.mark.parametrize("path, masked", sorted(UNCHANGED))
def test_a_stack_without_eva_traces_the_program_it_traced_before(
        path, masked):
    from tests.test_longcat_serving import digest

    texts = traced_programs(path, masked)
    assert tuple(digest(t) for t in texts) == UNCHANGED[path, masked]
    for text in texts:
        assert "eva_" not in text


# ------------------------------- the chip's compiler, without the chip

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


def test_the_programs_compile_for_the_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """The tick of 24 lanes and both prefill buckets (512 and 256 rows) over
    the cell's pool (8 layers x (1,729 summary + 3,073 window pages) of 16
    rows of 4,096 lanes, 10.07 GB) and the 3.26 GB of weights, through XLA's
    and Mosaic's own passes: the tick runs ``fleetx_decode_paged`` over the
    composed table, a chunk ``fleetx_prefill_gqa``, and the arguments are the
    cell's 13.3 GB."""
    from fleetx_tpu.models.gpt import hybrid
    from fleetx_tpu.models.gpt.generation import decode_step
    from fleetx_tpu.models.gpt.head import row_logits_step
    from fleetx_tpu.ops.pallas import (
        decode_attention,
        flash_attention,
        prefill_gqa,
    )

    for module in (flash_attention, decode_attention, prefill_gqa):
        if hasattr(module, "_interpret"):
            monkeypatch.setattr(module, "_interpret", lambda: False)
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    deploy = harness.load_json("perfbench", "cells",
                               "evabyte-l8-serve-bytedocs-longctx.json")
    lanes, ps = deploy["lanes"], deploy["page_size"]
    cfg = GPTConfig.from_model_config(
        dict(CONFIG["model"], fuse_attn_qkv=True, dtype="bfloat16"))
    cfg = dataclasses.replace(
        cfg, decode_cache_len=deploy["cache_len"], decode_page_size=ps,
        decode_num_pages=deploy["pool_tokens"] // ps + 1,
        decode_window_pages=lanes * (cfg.eva_window_size // ps) + 1)
    model = GPTForPretraining(cfg)

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shapes = jax.eval_shape(lambda: flax.core.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))))
    params = jax.tree.map(lambda x: spec(x.shape, jnp.bfloat16),
                          shapes["params"])
    cache = jax.tree.map(lambda x: spec(x.shape, x.dtype), jax.eval_shape(
        lambda: hybrid.init_cache(model, lanes)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params)) == (
        CONFIG["parameters"])
    pages = deploy["cache_len"] // ps

    def tick(params, cache, tok, pos, wpos, tables, live):
        return decode_step(model, params, cache, tok, pos, live,
                           cache_positions=wpos, block_tables=tables)

    compiled = jax.jit(tick, donate_argnums=1).lower(
        params, cache, spec((lanes, 1)), spec((lanes, 1)), spec((lanes,)),
        spec((2, lanes, pages)), spec((lanes, 1), jnp.bool_)).compile()
    assert decode_attention.PAGED_KERNEL_NAME in compiled.as_text()
    memory = compiled.memory_analysis()
    assert 13.2e9 < memory.argument_size_in_bytes < 13.5e9
    assert memory.temp_size_in_bytes < 1.0e9

    def chunk(params, cache, ids, pos, wpos, table, rows, wanted):
        return row_logits_step(model, params, cache, ids, pos, rows,
                               cache_positions=wpos, block_tables=table,
                               logit_rows=wanted)

    for rows in (deploy["prefill_chunk"], deploy["prefill_bucket"]):
        compiled = jax.jit(chunk, donate_argnums=1).lower(
            params, cache, spec((1, rows)), spec((1, rows)), spec((1,)),
            spec((2, 1, pages)), spec((1, rows), jnp.bool_),
            spec((1,))).compile()
        assert prefill_gqa.KERNEL_NAME in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9
