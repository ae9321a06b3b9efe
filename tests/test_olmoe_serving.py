"""The OLMoE block (rotary positions, RMSNorm, QK-norm, gated-SiLU softmax
top-k experts without capacity, untied head) through the normal path,
against the plain float32 reference ``perfbench/reference/olmoe_f32.py``,
at a tiny size on seeded weights: the full forward; prefill, then decoding
token by token through the PAGED cache, for lanes at different positions;
through ``ServingEngine.submit`` / ``step``. Logits are compared, not
tokens. And the tolerance bites: five wrong systems each turn the
comparison false.

TOLERANCE. These tests compute in float32 on the CPU, where system and
reference differ only in the order of their sums (the grouped matmuls sum
one expert's rows, the reference every expert's; the cache path splits the
attention sum at the page): the distance read is 2e-5 of the standard
deviation of the reference's logits, and the limit is ten times that,
2e-4. The smallest of the five faults (a router in bfloat16) stands 30
times over it. The bfloat16 limits of the chip are the benchmark driver's
(``perfbench/drivers/serve_closed_loop_ref.py``).
"""

import dataclasses
import functools

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import computed_once, sharing_programs, traced_apply

from fleetx_tpu.models.gpt import model as gpt_model
from fleetx_tpu.models.gpt.generation import (
    GenerationConfig,
    decode_step,
    init_decode_cache,
)
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.parallel import moe
from fleetx_tpu.serving import ServingEngine
from perfbench.probe_precision import router_dense_in_bfloat16
from perfbench.reference import olmoe_f32

TOL = 2e-4          # of the reference's logit standard deviation (docstring)
PAGE, CACHE_LEN = 8, 64
SIZES = dict(
    vocab_size=512, hidden_size=64, num_layers=2, num_attention_heads=4,
    ffn_hidden_size=32, max_position_embeddings=128, hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0, num_experts=8, expert_mode=True,
    gate="softmax_topk", top_k=2, position_embedding="rope", norm="rmsnorm",
    mlp_act="swiglu", use_bias=False, qk_norm=True, tie_word_embeddings=False,
    family="olmoe", use_flash_attention=False, dtype=jnp.float32)
reference = computed_once(functools.partial(olmoe_f32.logits, top_k=2))


def build(**changes):
    return GPTForPretraining(GPTConfig(**{**SIZES, **changes}))


@pytest.fixture(scope="module")
def variables():
    """Seeded weights. At width 64 with every weight at the initializer's
    0.02 the head dominates and the layers decide nothing, so the layers'
    matrices are scaled up and the norm weights moved off 1, until
    attention, the rotation, both norms and the router all decide the
    logits (a fault in any of them then shows)."""
    v = flax.core.meta.unbox(jax.jit(build().init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))

    def stir(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), x.shape)
        return x * 8.0 if "layers" in name else x

    return jax.tree_util.tree_map_with_path(stir, v)


def distance(system, tokens, v):
    """Largest logit error in units of the reference's logit spread."""
    want = np.asarray(reference(v["params"], tokens))
    return float(np.abs(np.asarray(system) - want).max() / want.std())


def through_the_paged_cache(model, v, tokens, prompt_lens):
    """Logits ``[lanes, len, vocab]`` of ``tokens`` ``[lanes, len]``: each
    lane's prompt prefilled alone into its own pages (batch 1, its block
    table), then ALL lanes decoded together, one token a step, each lane
    at its own position (they differ: ``prompt_lens``)."""
    lanes, length = tokens.shape
    rows = CACHE_LEN // PAGE
    served = model.clone(cfg=dataclasses.replace(
        model.cfg, decode_cache_len=CACHE_LEN, decode_page_size=PAGE,
        decode_num_pages=lanes * rows + 1))
    tables = 1 + np.arange(lanes * rows, dtype=np.int32).reshape(lanes, rows)
    cache = init_decode_cache(served, lanes)
    # a jit of its own for every call: a fault patched in is traced
    step = jax.jit(functools.partial(decode_step, served))
    out = np.zeros((lanes, length, model.cfg.vocab_size), np.float32)
    for lane, n in enumerate(prompt_lens):
        logits, cache = step(
            v["params"], cache, tokens[lane:lane + 1, :n],
            np.arange(n, dtype=np.int32)[None],
            cache_positions=np.zeros(1, np.int32),
            block_tables=tables[lane:lane + 1])
        out[lane, :n] = logits[0]
    at = np.asarray(prompt_lens, np.int32)
    while (at < length).any():
        live = at < length
        pos = np.where(live, at, CACHE_LEN - 1)    # a finished lane: pinned
        tok = tokens[np.arange(lanes), np.minimum(at, length - 1)]
        logits, cache = step(
            v["params"], cache, tok[:, None],
            np.minimum(at, length - 1)[:, None].astype(np.int32),
            cache_positions=pos.astype(np.int32), block_tables=tables)
        for lane in np.nonzero(live)[0]:
            out[lane, at[lane]] = logits[lane, 0]
        at = at + live
    return out


TOKENS = np.random.default_rng(0).integers(1, 512, (3, 40), dtype=np.int32)


def test_full_forward_matches_the_reference(variables):
    assert distance(traced_apply(build(), variables, TOKENS), TOKENS,
                    variables) < TOL


def test_unrolled_layers_match_too(variables):
    """``scan_layers: False`` holds the same layers under other names."""
    layer = variables["params"]["gpt"]["layers"]["layer"]
    gpt = {k: x for k, x in variables["params"]["gpt"].items() if k != "layers"}
    for i in range(2):
        gpt[f"layer_{i}"] = jax.tree.map(lambda x: x[i], layer)
    unrolled = {"params": {**variables["params"], "gpt": gpt}}
    got = traced_apply(build(scan_layers=False), unrolled, TOKENS)
    assert distance(got, TOKENS, variables) < TOL


def test_prefill_then_paged_decode_matches_the_reference(variables):
    got = through_the_paged_cache(build(), variables, TOKENS, (9, 17, 30))
    assert distance(got, TOKENS, variables) < TOL


def _top_k_with_capacity(capacity):
    """``lax.top_k`` whose weights are zero for the pairs that overflow a
    per-expert capacity (in token order): what capacity dropping does."""
    real = jax.lax.top_k

    def top_k(probs, k):
        weights, idx = real(probs, k)
        if probs.shape[-1] != SIZES["num_experts"]:
            return weights, idx
        onehot = jax.nn.one_hot(idx.reshape(-1), probs.shape[-1], dtype=jnp.int32)
        rank = (jnp.cumsum(onehot, 0) * onehot).sum(-1) - 1
        return jnp.where(rank.reshape(idx.shape) < capacity, weights, 0.0), idx

    return top_k


def _keys_cached_unrotated(real):
    """Rotation AFTER the cache write: the key goes into the cache as it
    was projected, and nothing rotates it on the way out (every second
    call of ``apply_rope`` in a layer is the key's)."""
    calls = []

    def apply_rope(x, rope):
        calls.append(1)
        return x if len(calls) % 2 == 0 else real(x, rope)

    return apply_rope


@pytest.mark.parametrize("fault", [
    "renormalised_topk_weights", "a_dropped_token", "router_in_bfloat16",
    "rotation_after_the_cache_write", "qk_norm_left_out"])
def test_the_tolerance_bites(variables, monkeypatch, fault):
    """Each of five wrong systems, run through prefill and paged decode as
    the right one is, stands outside the tolerance."""
    model = build()
    if fault == "renormalised_topk_weights":
        model = build(norm_topk_prob=True)
    elif fault == "a_dropped_token":     # capacity 1.2 x tokens x k / E
        monkeypatch.setattr(jax.lax, "top_k", _top_k_with_capacity(3))
    elif fault == "router_in_bfloat16":
        monkeypatch.setattr(moe.nn, "DenseGeneral",
                            router_dense_in_bfloat16(moe.nn.DenseGeneral))
    elif fault == "rotation_after_the_cache_write":
        monkeypatch.setattr(gpt_model, "apply_rope",
                            _keys_cached_unrotated(gpt_model.apply_rope))
    elif fault == "qk_norm_left_out":
        model = build(qk_norm=False)
    got = through_the_paged_cache(model, variables, TOKENS, (9, 17, 30))
    assert distance(got, TOKENS, variables) > 30 * TOL


def test_no_pair_is_dropped_however_uneven_the_routing():
    """Every (token, slot) pair gets a row of its own, whole groups, in
    expert order: also when every token chooses the same two experts."""
    n, k, experts = 24, 2, 8
    even = np.stack([np.random.default_rng(i).permutation(experts)[:k]
                     for i in range(n)]).astype(np.int32)
    for idx in (even, np.tile(np.int32([[5, 2]]), (n, 1))):
        for tm in (1, 8, 16):
            dest, src, sizes, tile_expert, num_tiles = map(
                np.asarray, moe.expert_row_layout(jnp.asarray(idx), experts, tm))
            assert len(set(dest.tolist())) == n * k == sizes.sum()
            assert (src[dest] == np.arange(n * k) // k).all()
            order = np.argsort(dest)
            assert (np.diff(idx.reshape(-1)[order]) >= 0).all()  # by expert
            if tm > 1:
                assert (tile_expert[dest // tm] == idx.reshape(-1)).all()
                assert num_tiles == (-(-sizes // tm)).sum() < len(tile_expert)


@pytest.mark.parametrize("layer", [0, 1])
def test_the_grouped_matmul_kernels_match_ragged_dot(layer):
    """``ops/pallas/moe_gmm.py`` in interpret mode, handed the whole layer
    stack and a layer, against ``jax.lax.ragged_dot`` on that layer's
    weights and the same rows, uneven groups and an expert without rows
    included."""
    from fleetx_tpu.ops.pallas import moe_gmm

    experts, h, f, n, k, tm = 8, 128, 256, 24, 2, 16
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    gate, up, down = (
        jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
        for s in ((2, experts, h, f), (2, experts, h, f), (2, experts, f, h)))
    idx = np.stack([rng.permutation(experts - 1)[:k] for _ in range(n)])
    idx[:12] = (3, 5)                          # uneven; expert 7 gets none
    idx = jnp.asarray(idx, jnp.int32)

    dest, src, sizes, _, _ = moe.expert_row_layout(idx, experts, 1)
    rows = x[src]
    want = jax.lax.ragged_dot(
        jax.nn.silu(jax.lax.ragged_dot(rows, gate[layer], sizes))
        * jax.lax.ragged_dot(rows, up[layer], sizes), down[layer], sizes)[dest]

    dest, src, _, tile_expert, num_tiles = moe.expert_row_layout(idx, experts, tm)
    act = moe_gmm.grouped_gate_up(x[src], gate, up, tile_expert, num_tiles,
                                  tm=tm, layer=jnp.int32(layer))
    got = moe_gmm.grouped_down(act, down, tile_expert, num_tiles, tm=tm,
                               layer=jnp.int32(layer))[dest]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="layer stack"):
        moe_gmm.grouped_down(act, down[layer], tile_expert, num_tiles, tm=tm,
                             layer=jnp.int32(0))


@sharing_programs
def engine_of(model, v, **kw):
    return ServingEngine(
        model, v, slots=4, cache_len=CACHE_LEN, page_size=PAGE, num_pages=40,
        prefill_bucket=8, gen_cfg=GenerationConfig(
            decode_strategy="greedy", eos_token_id=-1, pad_token_id=0,
            max_length=8), **kw)


def test_the_engine_serves_requests_of_unequal_length(variables):
    """Through ``submit`` and ``step``: paged cache, prefix trie, prefill
    buckets, sampler, several requests of unequal length in flight. Every
    returned token is the reference's own best at its position, to within
    the tolerance (greedy: a token's deficit is two logit errors)."""
    engine = engine_of(build(), variables)
    assert engine.model_family == engine.health()["model"] == "olmoe"
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 512, n, dtype=np.int32) for n in (9, 17, 30, 12, 25)]
    prompts.append(np.concatenate([prompts[2][:16], prompts[0]]))  # a shared page
    ids = [engine.submit(p, max_length=8) for p in prompts]
    results = engine.drain()
    for rid in ids:
        prompt, tokens = results[rid].prompt, np.asarray(results[rid].tokens)
        assert len(tokens) == 8
        rated = np.asarray(reference(
            variables["params"], np.concatenate([prompt, tokens])[None]))[0]
        at = rated[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
        deficit = at.max(-1) - at[np.arange(len(tokens)), tokens]
        assert deficit.max() <= 2 * TOL * rated.std()
    snap = engine.metrics.snapshot()
    assert snap["prefill_tokens_saved"] > 0          # the trie was used
    assert engine.cache_manager.pages_in_use == 0    # and no page leaked
    # the routing counters: kept on the device, fetched by snapshot() alone
    assert snap["moe_layers"] == 2
    assert snap["moe_tick_pairs"] == snap["moe_tick_layer_calls"] * 4 * 2
    assert snap["moe_pairs_routed"] == snap["moe_tick_pairs"] + snap["moe_prefill_pairs"]
    assert 1 <= snap["moe_tick_experts_read"] <= 8
    assert 1 <= snap["moe_tick_load_max_over_mean"] <= 8
    # the line a tick may log reads nothing from the device
    assert not [k for k in engine.metrics.snapshot(device=False)
                if k.startswith("moe_")]


def test_the_routing_counts_carry_and_a_donated_cache_is_an_event(variables):
    """A count is two uint32 words: from 2**32 - 1 the next call carries
    into the high word. A scrape that meets a deleted (donated) cache
    buffer reports no counters and emits an event; any other failure of
    the read is raised."""
    from fleetx_tpu.obs.events import get_event_log

    engine = engine_of(build(), variables)
    manager = engine.cache_manager
    full = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.full_like(x, 2 ** 32 - 1).at[..., 1::2].set(0)
        if "moe_stats" in jax.tree_util.keystr(path) else x, manager.cache)
    manager.cache = full
    engine.submit(np.arange(1, 10, dtype=np.int32), max_length=3)
    engine.drain()
    snap = engine.metrics.snapshot()
    layers, per_call = 2, 4 * 2                   # lanes x top_k
    assert snap["moe_tick_layer_calls"] > layers * (2 ** 32 - 1)
    calls = snap["moe_tick_layer_calls"] - layers * (2 ** 32 - 1)
    assert 0 < calls <= layers * 3
    assert snap["moe_tick_pairs"] == layers * (2 ** 32 - 1) + calls * per_call

    before = len(get_event_log().find("serving_device_counters_missed"))
    stats = [x for path, x in jax.tree_util.tree_flatten_with_path(
        manager.cache)[0] if "moe_stats" in jax.tree_util.keystr(path)]
    stats[0].delete()
    assert not [k for k in engine.metrics.snapshot() if k.startswith("moe_")]
    assert len(get_event_log().find(
        "serving_device_counters_missed")) == before + 1
    engine.executor.counters = lambda cache: (_ for _ in ()).throw(
        RuntimeError("another fault"))
    with pytest.raises(RuntimeError, match="another fault"):
        engine.metrics.snapshot()


def test_bfloat16_weights_stay_bfloat16_and_untried_features_are_refused(variables):
    """The engine holds the leaves in the type it was given (no float32
    copy, no recast in a program), and refuses at construction what no
    test covers over experts."""
    model = build(dtype=jnp.bfloat16)
    held = jax.tree.map(lambda x: x.astype(jnp.bfloat16), variables)
    engine = engine_of(model, held)
    assert {x.dtype for x in jax.tree.leaves(engine.params)} == {jnp.dtype("bfloat16")}
    program = str(jax.make_jaxpr(engine._decode_fn, static_argnums=(4,))(
        engine.params, engine.cache_manager.cache, engine._state,
        engine._device_tables(), True))
    shapes = {"bf16[%s]" % ",".join(map(str, x.shape))
              for x in jax.tree.leaves(engine.params) if x.size > 4096}
    recasts = [line for line in program.splitlines()
               if "convert_element_type[new_dtype=float32" in line
               and any(s in line for s in shapes)]
    assert not recasts, recasts[:3]
    rid = engine.submit(np.arange(1, 20, dtype=np.int32), max_length=4)
    assert len(engine.drain()[rid].tokens) == 4
    caps = engine.capabilities
    assert not (caps.supports_spec or caps.supports_int8_weights
                or caps.supports_int8_kv or caps.supports_mesh)
    for asked in (dict(spec=True), dict(weight_dtype="int8"),
                  dict(kv_dtype="int8")):
        with pytest.raises(ValueError, match="olmoe"):
            engine_of(model, held, **asked)


def test_the_block_kinds_are_checked_and_gpt_keeps_its_defaults():
    cfg = GPTConfig()
    assert (cfg.position_embedding, cfg.norm, cfg.mlp_act, cfg.use_bias,
            cfg.qk_norm, cfg.tie_word_embeddings, cfg.family) == (
        "learned", "layernorm", "gelu", True, False, True, "gpt")
    with pytest.raises(ValueError, match="norm"):
        GPTConfig(norm="batchnorm")
    with pytest.raises(NotImplementedError):
        GPTConfig(position_embedding="rope", pp_degree=2)
    from_yaml = GPTConfig.from_model_config(dict(
        num_experts=8, gate="softmax_topk", top_k=2, norm="rmsnorm",
        position_embedding="rope", mlp_act="swiglu", use_bias=False))
    assert from_yaml.expert_mode and from_yaml.norm == "rmsnorm"


def test_a_dense_gated_block_trains(variables):
    """The block without experts (gated SiLU MLP, RMSNorm, rotary, no bias,
    untied head) and the expert block both give finite gradients to every
    parameter: ``ragged_dot`` differentiates, and the router gets its share."""
    for model in (build(expert_mode=False, num_experts=1), build()):
        v = flax.core.meta.unbox(jax.jit(model.init)(
            jax.random.PRNGKey(0), TOKENS[:1, :16]))

        def loss(params):
            logits = model.apply({"params": params}, TOKENS[:1, :16])
            return gpt_model.pretraining_loss(
                logits, TOKENS[:1, :16], jnp.ones((1, 16)))

        grads = jax.jit(jax.grad(loss))(v["params"])   # (one program)
        assert all(np.isfinite(g).all() and np.abs(g).max() > 0
                   for g in jax.tree.leaves(grads))
