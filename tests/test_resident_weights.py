"""Served weights are resident in the compute dtype (ISSUE 28).

At ``weight_dtype="bf16"`` the engine keeps, for every leaf the model
converts WHOLE to ``cfg.dtype`` before its first use, that converted value
(``models/gpt/resident.py`` ``resident_params``, behind
``GPTExecutor.resident_params``). It is the same arithmetic done once
instead of in every program, so everything here is held BIT for bit against
the float32 tree served as the parent served it (an executor whose
``resident_params`` is the identity):

- logits of a prefill and of paged decode ticks, and the tokens
  ``ServingEngine`` returns: a GPT-2 block (tied head, learned positions,
  biases) and the OLMoE block handed over in float32;
- the decode tick and a prefill program hold no float32 -> bfloat16
  convert of an operand as large as one layer's smallest kernel, but for
  the HEAD's table, which is left as handed over (on the chip the compiled
  head of a one-token program reads it unrounded: PERF.md, PR 28); the
  parent form holds them all: the walker is seen to find them;
- a bfloat16 tree and a float32 model come back leaf for leaf the objects
  they were, an int8 tree untouched;
- norms, position table, router, the word table and an untied head stay
  float32;
- a mesh engine shards the bfloat16 leaves;
- the ``weight_bytes`` gauge reads the resident tree.
"""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.models.gpt.resident import resident_params
from fleetx_tpu.ops.quant import quantize_tree_int8
from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh
from fleetx_tpu.serving import ServingEngine
from fleetx_tpu.serving.model_protocol import GPTExecutor, ModelExecutor

BF16, F32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
PAGE, CACHE_LEN, LANES, BUCKET = 8, 32, 2, 8
SIZES = dict(
    vocab_size=96, hidden_size=64, num_layers=2, num_attention_heads=4,
    ffn_hidden_size=128, max_position_embeddings=64,
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    use_flash_attention=False, dtype=jnp.bfloat16)
BLOCKS = {
    "gpt": {},
    "olmoe": dict(
        ffn_hidden_size=32, num_experts=4, expert_mode=True,
        gate="softmax_topk", top_k=2, position_embedding="rope",
        norm="rmsnorm", mlp_act="swiglu", use_bias=False, qk_norm=True,
        tie_word_embeddings=False, family="olmoe"),
}
# the smallest kernel of ONE layer (out_proj, hidden x hidden); every
# activation these programs convert is smaller
LAYER_KERNEL = SIZES["hidden_size"] ** 2
PROMPTS = [np.arange(1, 12, dtype=np.int32), np.arange(20, 25, dtype=np.int32)]


class AsParent(GPTExecutor):
    """The executor of the parent commit: the tree is served as handed
    over, and every program converts it."""

    resident_params = ModelExecutor.resident_params

    def bind(self, model):
        return AsParent(model, family=self.capabilities.family)


def build(block="gpt", boxed=False, **changes):
    """``(model, params)``; ``boxed`` keeps the flax boxes that
    ``model.init`` hands the leaves over in."""
    model = GPTForPretraining(GPTConfig(**{**SIZES, **BLOCKS[block], **changes}))
    made = jax.jit(model.init)(jax.random.PRNGKey(0),
                               np.zeros((1, 8), np.int32))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    # every leaf moved off its initial value (zero biases and unit scales
    # would hide a leaf held in the wrong type), the matrices scaled up
    # until the layers and not the head alone decide the logits
    params = jax.tree.map(
        lambda x: (4.0 * x if x.ndim > 2 else x)
        + 0.05 * jax.random.normal(next(keys), x.shape, x.dtype), made)
    return model, params if boxed else flax.core.meta.unbox(params)


def engine_of(model, params, **kw):
    return ServingEngine(model, {"params": params}, slots=LANES,
                         cache_len=CACHE_LEN, page_size=PAGE,
                         prefill_bucket=BUCKET, **kw)


def leaves_by_name(tree):
    """``{"gpt/layers/...": leaf}``; a flax box adds no name."""
    return {"/".join(k.key for k in path if hasattr(k, "key")): x
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def walk_logits(engine, params):
    """Logits of a prefill of eight tokens on one lane and of three paged
    decode ticks after it, through the engine's own executor."""
    forward = jax.jit(engine.executor.forward)
    table = jnp.arange(1, 1 + CACHE_LEN // PAGE, dtype=jnp.int32)[None]
    ids = jnp.arange(3, 3 + BUCKET, dtype=jnp.int32)[None]
    logits, cache = forward(
        params, engine.executor.init_cache(1), ids,
        jnp.arange(BUCKET, dtype=jnp.int32)[None],
        cache_positions=jnp.zeros((1,), jnp.int32), block_tables=table)
    out = [np.asarray(logits)]
    for at in range(BUCKET, BUCKET + 3):
        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        logits, cache = forward(
            params, cache, tok, jnp.full((1, 1), at, jnp.int32),
            cache_positions=jnp.full((1,), at, jnp.int32), block_tables=table)
        out.append(np.asarray(logits))
    return out


def served_tokens(engine):
    rids = [engine.submit(p, max_length=6) for p in PROMPTS]
    results = engine.drain()
    return [np.asarray(results[r].tokens) for r in rids]


@pytest.mark.parametrize("block,form", [
    ("gpt", "plain"), ("gpt", "boxed"), ("olmoe", "plain")])
def test_logits_and_tokens_are_bit_equal_to_the_float32_tree(block, form):
    model, params = build(block, boxed=form == "boxed")
    resident = engine_of(model, params)
    parent = engine_of(model, params, executor=AsParent(model))
    assert {x.dtype for x in jax.tree.leaves(parent.params)} == {F32}
    assert BF16 in {x.dtype for x in jax.tree.leaves(resident.params)}
    for got, want in zip(walk_logits(resident, resident.params),
                         walk_logits(parent, parent.params)):
        assert np.isfinite(want).all() and want.std() > 0.1
        np.testing.assert_array_equal(got, want)
    for got, want in zip(served_tokens(resident), served_tokens(parent)):
        np.testing.assert_array_equal(got, want)


def weight_recasts(jaxpr, least=LAYER_KERNEL):
    """Shapes of the float32 operands of at least ``least`` elements that
    ``jaxpr`` (sub-jaxprs included) converts to bfloat16."""
    found = []
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "convert_element_type"
                and eqn.params["new_dtype"] == BF16):
            aval = eqn.invars[0].aval
            if aval.dtype == F32 and aval.size >= least:
                found.append(aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += weight_recasts(sub, least)
    return found


def program_jaxpr(engine, program):
    cache = engine.cache_manager.cache
    if program == "tick":
        return jax.make_jaxpr(engine._decode_fn, static_argnums=(4,))(
            engine.params, cache, engine._state, engine._device_tables(),
            True).jaxpr
    with engine._mesh_context():
        return jax.make_jaxpr(engine._make_paged_prefill(BUCKET))(
            engine.params, cache,
            engine._prefill_ints(
                (), BUCKET, 0, engine.cache_manager.lane_tables(0)),
            engine._inert_floats, jax.random.PRNGKey(0)).jaxpr


@pytest.mark.parametrize("program", ["tick", "prefill"])
@pytest.mark.parametrize("block", list(BLOCKS))
def test_programs_convert_no_weight_but_the_heads_table(block, program):
    """Not one layer's kernel and not the expert stacks. The head's table
    (the tied word table, an untied ``lm_head``) is left float32 and the
    head still converts it: the one convert left. The parent form converts
    all of them in the same program."""
    model, params = build(block)
    head = (SIZES["vocab_size"], SIZES["hidden_size"])
    assert weight_recasts(
        program_jaxpr(engine_of(model, params), program)) == [head]
    before = weight_recasts(program_jaxpr(
        engine_of(model, params, executor=AsParent(model)), program))
    assert head in before and len(before) >= 5, before


@pytest.mark.parametrize("form", ["bfloat16_tree", "float32_model", "int8_tree"])
def test_trees_with_nothing_to_convert_come_back_as_they_were(form):
    if form == "float32_model":
        model, params = build(dtype=jnp.float32)
    else:
        model, params = build("olmoe" if form == "bfloat16_tree" else "gpt")
    if form == "bfloat16_tree":
        params = jax.tree.map(lambda x: x.astype(BF16), params)
    if form == "int8_tree":
        params = quantize_tree_int8(params)
        assert jnp.dtype("int8") in {x.dtype for x in jax.tree.leaves(params)}
    out = GPTExecutor(model).resident_params(params)
    was, now = leaves_by_name(params), leaves_by_name(out)
    assert list(was) == list(now)
    assert all(now[name] is was[name] for name in was)
    if form != "float32_model":  # and the engine serves it as it is
        kw = {"weight_dtype": "int8"} if form == "int8_tree" else {}
        held = leaves_by_name(engine_of(model, params, **kw).params)
        assert all(held[name] is was[name] for name in was)


@pytest.mark.parametrize("block", list(BLOCKS))
def test_what_the_model_uses_in_float32_stays_float32(block):
    for boxed in (False, True):
        model, tree = build(block, boxed=boxed)
        held = leaves_by_name(resident_params(model.cfg, tree))
        kept = {n for n, x in held.items() if x.dtype == F32}
        cast = {n for n, x in held.items() if x.dtype == BF16}
        assert kept | cast == set(held)
        for name in kept:
            assert any(part in name for part in (
                "norm", "position_embeddings", "word_embeddings", "router",
                "lm_head")), name
        for name in cast:
            assert any(part in name for part in (
                "_proj", "w_gate", "w_up", "w_down")), name
        assert held["gpt/word_embeddings"].dtype == F32
        assert sum("norm" in n for n in kept) >= 5
        assert len(cast) == (8 if block == "gpt" else 5)
    if block == "gpt":
        assert held["gpt/position_embeddings"].dtype == F32
    else:
        assert held["gpt/layers/layer/moe_mlp/router/kernel"].dtype == F32
        assert held["lm_head"].dtype == F32


def test_a_mesh_engine_shards_the_bfloat16_leaves(eight_devices, monkeypatch):
    model, params = build()
    mesh = build_mesh(MeshConfig(mp=2), eight_devices[:2])
    handed = []
    shard = ServingEngine._shard_params
    monkeypatch.setattr(
        ServingEngine, "_shard_params",
        lambda self, tree: handed.append(tree) or shard(self, tree))
    meshed = engine_of(model, params, mesh=mesh)
    (tree,) = handed
    want = leaves_by_name(resident_params(model.cfg, params))
    assert {n: x.dtype for n, x in leaves_by_name(tree).items()} == {
        n: x.dtype for n, x in want.items()}
    held = leaves_by_name(meshed.params)
    kernel = held["gpt/layers/layer/mlp/up_proj/kernel"]
    assert kernel.dtype == BF16
    assert kernel.addressable_shards[0].data.size * 2 == kernel.size
    assert held["gpt/word_embeddings"].dtype == F32
    for got, want in zip(served_tokens(meshed),
                         served_tokens(engine_of(model, params))):
        np.testing.assert_array_equal(got, want)


def test_weight_bytes_gauge_reads_the_resident_tree():
    model, params = build()
    engine = engine_of(model, params)
    resident = sum(x.nbytes for x in jax.tree.leaves(engine.params))
    float32 = sum(x.nbytes for x in jax.tree.leaves(params))
    snap = engine.metrics.snapshot()
    assert snap["weight_dtype"] == "bf16"
    assert snap["weight_bytes"] == resident
    assert resident < 0.75 * float32  # every converted leaf at half
    parent = engine_of(model, params, executor=AsParent(model))
    assert parent.metrics.snapshot()["weight_bytes"] == float32
