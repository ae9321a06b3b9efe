"""The paged KV pool rides the layer scan as a CARRY (ISSUE 24).

- **Form**: the jaxpr of a paged ``decode_step`` holds every cache leaf
  of the layer ``scan`` among its carries and none among its scanned
  inputs or stacked outputs, and a jitted tick / paged prefill that
  donates the cache aliases every leaf of it to its output.
- **Addressing**: after a prefill and two ticks on three lanes (a shared
  prefix page, one lane writing through a zeroed table entry) the WHOLE
  stack of pools, every layer's trash page included, equals bit for bit
  what an unrolled per-layer loop over each layer's own pool produces:
  this pins ``table + i * num_pages`` and the trash page of each layer.
- **What is left alone**: the contiguous layout and training keep the
  scanned form.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt.generation import (
    GenerationConfig,
    decode_step,
    init_decode_cache,
)
from fleetx_tpu.models.gpt.model import (
    DecoderLayer,
    GPTConfig,
    GPTForPretraining,
)
from fleetx_tpu.serving import ServingEngine

LAYERS, PAGES, PAGE, CACHE_LEN, LANES = 3, 7, 8, 32, 3
CFG = GPTConfig(
    vocab_size=61,
    hidden_size=32,
    num_layers=LAYERS,
    num_attention_heads=4,
    ffn_hidden_size=64,
    max_position_embeddings=64,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype=jnp.float32,
    use_flash_attention=False,
)
KV = pytest.mark.parametrize("kv_dtype", [None, "int8"],
                             ids=["bf16_kv", "int8_kv"])
TOKENS = pytest.mark.parametrize("tokens", [1, 5],
                                 ids=["decode", "prefill"])


def _paged(kv_dtype, dtype=jnp.float32):
    """(paged model, unboxed params, zero cache) at a tiny size."""
    cfg = dataclasses.replace(CFG, dtype=dtype)
    params = nn.meta.unbox(GPTForPretraining(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    model = GPTForPretraining(dataclasses.replace(
        cfg, decode_cache_len=CACHE_LEN, decode_page_size=PAGE,
        decode_num_pages=PAGES, decode_kv_dtype=kv_dtype))
    return model, params, init_decode_cache(model, LANES)


def _layer_scans(jaxpr, length):
    """Every ``scan`` of ``length`` steps in ``jaxpr``, sub-jaxprs
    included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan" and eqn.params["length"] == length:
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _layer_scans(sub, length)
    return found


def _scan_parts(eqn):
    """(carry avals, scanned-input avals, stacked-output avals) of a scan
    equation, each as a list of ``(shape, dtype)``."""
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]
    sig = lambda vs: [(v.aval.shape, v.aval.dtype) for v in vs]
    return (sig(eqn.invars[n_consts:n_consts + n_carry]),
            sig(eqn.invars[n_consts + n_carry:]),
            sig(eqn.outvars[n_carry:]))


def _step_args(tokens):
    """Operands of a cached forward: a one-token tick on every lane, or a
    batch-1 prefill of ``tokens`` tokens."""
    b = LANES if tokens == 1 else 1
    tables = jnp.arange(1, 1 + b * 2, dtype=jnp.int32).reshape(b, 2)
    tables = jnp.pad(tables, ((0, 0), (0, CACHE_LEN // PAGE - 2)))
    ids = jnp.ones((b, tokens), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(tokens, dtype=jnp.int32), (b, tokens))
    return ids, pos, jnp.zeros((b,), jnp.int32), tables


@KV
@TOKENS
def test_pool_is_a_carry_of_the_layer_scan(kv_dtype, tokens):
    model, params, cache = _paged(kv_dtype)
    ids, pos, wpos, tables = _step_args(tokens)
    jaxpr = jax.make_jaxpr(
        lambda p, c: decode_step(model, p, c, ids, pos, None, wpos, tables)
    )(params, cache).jaxpr
    (scan,) = _layer_scans(jaxpr, LAYERS)
    carries, scanned, stacked = _scan_parts(scan)
    leaves = [(x.shape, x.dtype) for x in jax.tree.leaves(cache)]
    assert len(leaves) == (5 if kv_dtype == "int8" else 3)
    for leaf in leaves:  # cached_key/value (+ scales), cache_index
        assert carries.count(leaf) >= leaves.count(leaf), (leaf, carries)
    pools = [leaf for leaf in leaves if len(leaf[0]) == 4]
    for shape, dtype in pools:
        assert shape[0] == LAYERS
        # neither a whole stack nor one layer's pool is sliced in or
        # stacked out
        assert (shape, dtype) not in scanned + stacked
        assert (shape[1:], dtype) not in scanned + stacked
    # the parameters still are scanned inputs: one compiled body
    assert any(shape[:1] == (LAYERS,) and len(shape) > 1
               for shape, _ in scanned)


def _donating_programs(eng, tokens):
    """The engine's decode tick (``tokens`` 1) or a paged prefill program
    of bucket ``tokens``, lowered with the cache donated as on the chip
    (on the CPU the engine leaves donation off)."""
    cache = eng.cache_manager.cache
    if tokens == 1:
        fn = jax.jit(eng._decode_fn, static_argnums=(4,),
                     donate_argnums=(1,))
        return fn.lower(eng.params, cache, eng._state,
                        eng._device_tables(), True)
    eng._donate_cache = True
    fn = eng._make_paged_prefill(tokens)
    return fn.lower(
        eng.params, cache,
        eng._prefill_ints((), tokens, 0, eng.cache_manager.lane_tables(0)),
        eng._inert_floats, jax.random.PRNGKey(0))


@KV
@pytest.mark.parametrize("tokens", [1, 8], ids=["tick", "prefill"])
def test_donated_cache_aliases_the_output(kv_dtype, tokens):
    model = GPTForPretraining(CFG)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    eng = ServingEngine(
        model, params, slots=LANES, cache_len=CACHE_LEN, page_size=PAGE,
        prefill_bucket=8, kv_dtype=kv_dtype or "bf16",
        gen_cfg=GenerationConfig(decode_strategy="greedy",
                                 eos_token_id=10**6, pad_token_id=60))
    lowered = _donating_programs(eng, tokens)
    n_leaves = len(jax.tree.leaves(eng.cache_manager.cache))
    # jax matched every donated leaf with an output of its shape ...
    assert lowered.as_text().count("tf.aliasing_output") == n_leaves
    # ... and the compiled program kept the aliases
    header = lowered.compile().as_text().split("\n", 1)[0]
    assert "input_output_alias" in header, header
    assert header.count("-alias)") >= n_leaves, header


def _unrolled_reference(model, params, cache, ids, pos, wpos, tables):
    """The cache one cached forward leaves behind, by a Python loop over
    the layers: layer ``i`` runs on ITS OWN pool ``[P, page, w]`` (slice
    ``i`` of every leaf) through the tables as given, and the results are
    stacked again. Independent of the scan and of the flat addressing."""
    cfg = model.cfg
    gpt = params["gpt"]
    x = (gpt["word_embeddings"][ids]
         + gpt["position_embeddings"][pos]).astype(cfg.dtype)
    layer = DecoderLayer(cfg)
    stack_p = gpt["layers"]["layer"]
    stack_c = cache["gpt"]["layers"]["layer"]
    out = []
    for i in range(cfg.num_layers):
        x, mut = layer.apply(
            {"params": jax.tree.map(lambda a: a[i], stack_p),
             "cache": jax.tree.map(lambda a: a[i], stack_c)},
            x, None, True, True, wpos, tables, mutable=["cache"])
        out.append(mut["cache"])
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *out)
    return {"gpt": {"layers": {"layer": stacked}}}


@KV
def test_whole_pool_equals_unrolled_per_layer_loop(kv_dtype):
    model, params, cache = _paged(kv_dtype)
    ref_cache = cache
    rng = np.random.RandomState(0)
    # lanes 0 and 1 share prefix page 1; lane 2 is free: its table is
    # zeroed, so its pinned write (last logical slot) lands on page 0
    tables = jnp.asarray([[1, 2, 0, 0], [1, 3, 0, 0], [0, 0, 0, 0]],
                         jnp.int32)
    step = jax.jit(lambda c, *a: decode_step(
        model, params, c, a[0], a[1], None, a[2], a[3])[1])
    ref = jax.jit(lambda c, *a: _unrolled_reference(model, params, c, *a))

    # one prefill: lane 0's 11 tokens in a bucket of 12, into pages 1, 2
    ids = jnp.asarray(rng.randint(1, 60, (1, 12)), jnp.int32)
    args = (ids, jnp.arange(12, dtype=jnp.int32)[None],
            jnp.zeros((1,), jnp.int32), tables[:1])
    cache, ref_cache = step(cache, *args), ref(ref_cache, *args)
    # two ticks: lane 0 at length 11, lane 1 right behind the shared
    # page, lane 2 pinned to the last slot of a zeroed table
    for t in range(2):
        wpos = jnp.asarray([11 + t, 8 + t, CACHE_LEN - 1], jnp.int32)
        args = (jnp.asarray(rng.randint(1, 60, (LANES, 1)), jnp.int32),
                wpos[:, None], wpos, tables)
        cache, ref_cache = step(cache, *args), ref(ref_cache, *args)

    got = jax.tree.leaves_with_path(cache)
    want = jax.tree.leaves_with_path(ref_cache)
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, a), (_, b) in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)), err_msg=str(path))
    keys = np.asarray(
        cache["gpt"]["layers"]["layer"]["attn"]["cached_key"]
        .astype(jnp.float32))
    assert keys.shape == (LAYERS, PAGES, PAGE, CFG.hidden_size)
    for i in range(LAYERS):
        # every layer's own trash page took lane 2's write, in its last
        # row alone; the shared page and both private pages are written;
        # the pages no table names are untouched
        assert keys[i, 0, PAGE - 1].any() and not keys[i, 0, :PAGE - 1].any()
        assert all(keys[i, p].any() for p in (1, 2, 3))
        assert not keys[i, 4:].any()


@pytest.mark.parametrize("case", ["contiguous_decode", "training"])
def test_other_branches_keep_the_scanned_form(case):
    """The contiguous layout (its kernel indexes rows by batch index, not
    through a table) and training are left as they were: the cache is a
    scanned input and a stacked output there, and training's scan takes
    no layer index."""
    model = GPTForPretraining(dataclasses.replace(
        CFG, decode_cache_len=CACHE_LEN))
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    ids = jnp.ones((2, 1), jnp.int32)
    if case == "training":
        jaxpr = jax.make_jaxpr(
            lambda p: model.apply({"params": p}, jnp.ones((2, 8), jnp.int32))
        )(params).jaxpr
        (scan,) = _layer_scans(jaxpr, LAYERS)
        _, scanned, _ = _scan_parts(scan)
        assert ((LAYERS,), jnp.dtype("int32")) not in scanned
        return
    cache = init_decode_cache(model, 2)
    jaxpr = jax.make_jaxpr(
        lambda p, c: decode_step(model, p, c, ids, jnp.zeros_like(ids))
    )(params, cache).jaxpr
    (scan,) = _layer_scans(jaxpr, LAYERS)
    _, scanned, stacked = _scan_parts(scan)
    key = cache["gpt"]["layers"]["layer"]["attn"]["cached_key"]
    assert (key.shape, key.dtype) in scanned
    assert (key.shape, key.dtype) in stacked
