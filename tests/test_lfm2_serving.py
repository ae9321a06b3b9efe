"""The LFM2 stack (gated short-convolution layers whose state lives in tail
pages of the engine's page pool beside the attention layers' keys and values,
grouped-query attention with a per-head QK-norm, two dense layers then
experts behind a sigmoid router with a selection bias) through the normal
path, against the plain float32 reference
``perfbench/reference/lfm2_f32.py``, at a tiny size on seeded weights: the
full forward; prefill IN CHUNKS and then decoding token by token through the
engine's page pool; a prefill that STARTS from a prefix hit ending at each of
several page boundaries, bit for bit what a cold one gives; through
``ServingEngine.submit`` / ``step``. Logits are compared, not tokens. And the
tolerance bites: twelve wrong systems each turn the comparison false.

TOLERANCE. These tests compute in float32 on the CPU, where system and
reference differ only in the order of their sums (the grouped matmuls sum one
expert's rows, the reference every expert's; the cache path splits the
attention sum at the page and the chunk): the distance read is some 1e-5 of
the standard deviation of the reference's logits, and the limit is 2e-4. The
smallest of the twelve faults reads over 30 times the limit, which the test
asks of each. The bfloat16 limits of the chip are the benchmark driver's
(``perfbench/drivers/serve_closed_loop_lfm2.py``).
"""

import contextlib
import json
import os

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import computed_once, sharing_programs, traced_apply

from fleetx_tpu.models.gpt import mixed_stack
from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.obs.tracing import get_recorder
from fleetx_tpu.serving import ServingEngine
from perfbench import probe_lfm2
from perfbench.drivers.serve_closed_loop_lfm2 import (
    Served, lane_rows, lane_state, trie_off)
from perfbench.reference import lfm2_f32

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4          # of the reference's logit standard deviation (docstring)
PAGE, CACHE_LEN, CHUNK = 8, 128, 16
TYPES = ("conv", "conv", "full_attention", "conv", "conv", "full_attention",
         "conv")
MODEL = dict(
    vocab_size=512, hidden_size=64, num_layers=7, num_attention_heads=8,
    num_key_value_heads=2, ffn_hidden_size=32, dense_ffn_hidden_size=96,
    num_dense_layers=2, layer_types=TYPES, conv_L_cache=3,
    max_position_embeddings=256, num_experts=8, gate="sigmoid_topk", top_k=2,
    norm_topk_prob=True, routed_scaling_factor=1.0, use_expert_bias=True,
    expert_bias_init_std=0.05, position_embedding="rope", rope_theta=1e6,
    norm="rmsnorm", norm_eps=1e-5, mlp_act="swiglu", use_bias=False,
    qk_norm=True, qk_norm_scope="head", tie_word_embeddings=True)
SIZES = dict(MODEL, hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
             expert_mode=True, family="lfm2", use_flash_attention=False,
             dtype=jnp.float32)
reference = computed_once(lfm2_f32.configured(MODEL))
TOKENS = np.random.default_rng(0).integers(1, 512, (2, 56), dtype=np.int32)


def build(**changes):
    return GPTForPretraining(GPTConfig(**{**SIZES, **changes}))


@pytest.fixture(scope="module")
def variables():
    """Seeded weights. At width 64 with every weight at the initializer's
    0.02 the head dominates and the layers decide nothing, so the layers'
    matrices are scaled up and the norm weights moved off 1, until both
    operators, both norms, the router and its bias all decide the logits (a
    fault in any of them then shows)."""
    v = flax.core.meta.unbox(jax.jit(build().init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))

    def stir(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), x.shape)
        if "expert_bias" in name or "conv_kernel" in name:
            return x
        return x * 6.0 if "layers" in name else x

    return jax.tree_util.tree_map_with_path(stir, v)


def distance(logits, expected) -> float:
    """Largest error in units of the reference's logit deviation."""
    expected = np.asarray(expected)
    return float(np.abs(np.asarray(logits) - expected).max() / expected.std())


@sharing_programs
def engine_of(model, variables, **kw):
    kw = {"slots": 3, "page_size": PAGE, "prefill_chunk": CHUNK,
          "prefill_bucket": 8, "prefix_cache": True, **kw}
    return ServingEngine(
        model, variables, cache_len=CACHE_LEN,
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


served_of = sharing_programs(Served)


def served_logits(engine, tokens, prompt_len, served=served_of):
    out = served(engine).sequence(tokens, prompt_len, CHUNK)
    return out["logits"], out["matched"]


def test_chunked_prefill_then_decode_through_the_pool(variables):
    """40 prompt tokens in chunks of 16 (a first chunk of 8, padded: its
    padded rows must leave the state alone), then 16 decode steps."""
    engine = engine_of(build(), variables)
    tokens = TOKENS[0]
    logits, matched = served_logits(engine, tokens, 40)
    assert matched == 0
    expected = reference(variables["params"], tokens)[40 - CHUNK:]
    assert distance(logits, expected) < TOL
    engine.cache_manager.pool.check_invariants()
    assert engine.cache_manager.pages_in_use == 0


@pytest.mark.parametrize("boundary", [8, 16, 24, 40])
def test_a_prefix_hit_resumes_the_state_bit_for_bit(variables, boundary):
    """A request whose first ``boundary`` tokens (1 to 5 pages) another has
    registered gives, bit for bit, the keys and values, the convolution
    state and the logits of the same request admitted cold."""
    engine = engine_of(build(), variables)
    rng = np.random.default_rng(boundary)
    tokens = rng.integers(1, 512, 64, dtype=np.int32)
    other = np.concatenate([tokens[:boundary],
                            rng.integers(1, 512, 11, dtype=np.int32)])
    engine.submit(other, max_length=2)
    engine.drain()
    manager, served = engine.cache_manager, served_of(engine)

    def run(cold):
        with trie_off(manager.pool) if cold else contextlib.nullcontext():
            lane, matched = manager.alloc(-1, tokens[:52])
        try:
            logits, _ = served.prefill(lane, tokens[:52], matched, PAGE)
            steps = [served.step(lane, int(t))[0] for t in tokens[52:]]
            return (matched, np.asarray(logits), np.concatenate(steps),
                    lane_rows(engine, lane, 0, 64),
                    lane_state(engine, lane, 64))
        finally:
            manager.free(lane)

    hit, cold = run(False), run(True)
    assert (hit[0], cold[0]) == (boundary, 0)
    for mine, theirs in zip(hit[1:], cold[1:]):
        np.testing.assert_array_equal(mine, theirs)
    expected = reference(variables["params"], tokens)[52 - PAGE:]
    assert distance(np.concatenate(hit[1:3]), expected) < TOL
    assert engine.metrics.snapshot()["state_snapshots_resumed"] >= 1


FAULTS = ("taps_reversed", "gate_dropped", "thirds_exchanged",
          "bias_in_the_weight", "bias_left_out", "softmax_for_sigmoid",
          "no_normalising", "key_head_h_mod_kv", "qk_norm_over_the_projection",
          "dense_mlp_in_an_expert_layers_place")


def _map(variables, name, fn):
    return jax.tree_util.tree_map_with_path(
        lambda path, x: fn(x) if name in jax.tree_util.keystr(path) else x,
        variables)


def _declared_only(model, params):
    """``params`` cut to the leaves (and layers) ``model`` declares."""
    declared = flax.core.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))))["params"]

    def keep(have, like):
        if isinstance(like, dict):
            return {k: keep(have[k], v) for k, v in like.items()}
        return have[len(have) - like.shape[0]:] if have.ndim else have
    return {"params": keep(params["params"], declared)}


def _faulty_logits(variables, fault, monkeypatch):
    """The full forward of a system with ``fault`` planted: in its weights,
    its configuration or its program."""
    from flax import linen as nn

    from fleetx_tpu.models.gpt import hybrid
    from fleetx_tpu.parallel import moe

    changes, held, thirds = {}, variables, mixed_stack._thirds
    if fault == "taps_reversed":
        held = _map(variables, "conv_kernel", lambda x: x[..., ::-1])
    elif fault == "gate_dropped":        # C left out
        monkeypatch.setattr(mixed_stack, "_thirds", lambda x: (
            thirds(x)[0], jnp.ones_like(thirds(x)[1]), thirds(x)[2]))
    elif fault == "thirds_exchanged":    # C, B, u
        monkeypatch.setattr(mixed_stack, "_thirds", lambda x: (
            thirds(x)[1], thirds(x)[0], thirds(x)[2]))
    elif fault == "bias_in_the_weight":
        def gate(self, logits):
            scores = jax.nn.sigmoid(logits) + self.param(
                "expert_bias", nn.initializers.zeros_init(),
                (self.cfg.num_experts,), jnp.float32)
            weights, chosen = jax.lax.top_k(scores, self.cfg.top_k)
            return scores, weights / (weights.sum(-1, keepdims=True)
                                      + 1e-6), chosen
        monkeypatch.setattr(moe.DroplessMoEMLP, "_sigmoid_topk", gate)
    elif fault == "bias_left_out":
        changes = {"use_expert_bias": False}
    elif fault == "softmax_for_sigmoid":
        changes = {"gate": "softmax_topk", "use_expert_bias": False,
                   "expert_bias_init_std": 0.0}
    elif fault == "no_normalising":
        changes = {"norm_topk_prob": False}
    elif fault == "key_head_h_mod_kv":
        def attention(q, k, v, allowed):  # query head h on key head h % kv
            b, s, heads, d = q.shape
            pick = jnp.arange(heads) % (k.shape[-1] // d)
            k4 = k.reshape(b, k.shape[1], -1, d)[:, :, pick]
            v4 = v.reshape(b, v.shape[1], -1, d)[:, :, pick]
            scores = jnp.einsum("bshd,bthd->bhst", q, k4) / (d ** 0.5)
            scores = jnp.where(allowed, scores, -1e30)
            return jnp.einsum("bhst,bthd->bshd",
                              jax.nn.softmax(scores, -1), v4)
        monkeypatch.setattr(hybrid, "grouped_attention", attention)
    elif fault == "qk_norm_over_the_projection":
        real = nn.RMSNorm

        def norm(*args, **kw):
            if kw.get("name") in ("q_norm", "k_norm"):
                kw.update(reduction_axes=(-2, -1))
            return real(*args, **kw)
        monkeypatch.setattr(hybrid.nn, "RMSNorm", norm)
    elif fault == "dense_mlp_in_an_expert_layers_place":
        changes = {"num_dense_layers": 3}
    model = build(**changes)
    if fault == "dense_mlp_in_an_expert_layers_place":
        # the third layer takes a dense MLP (drawn anew) for its experts
        fresh = flax.core.meta.unbox(jax.jit(model.init)(
            jax.random.PRNGKey(5), np.zeros((1, 8), np.int32)))
        layers = dict(held["params"]["gpt"]["layers"])
        layers["dense"] = jax.tree.map(
            lambda new, old: jnp.concatenate([old, new[2:] * 6.0]),
            fresh["params"]["gpt"]["layers"]["dense"], layers["dense"])
        held = {"params": {**held["params"], "gpt": {
            **held["params"]["gpt"], "layers": layers}}}
    if changes:
        held = _declared_only(model, held)
    return traced_apply(model, held, TOKENS)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_tolerance_bites(variables, fault, monkeypatch):
    logits = _faulty_logits(variables, fault, monkeypatch)
    assert distance(logits, reference(variables["params"], TOKENS)) > 30 * TOL


@pytest.mark.parametrize("fault", ["zeroed_at_a_hit", "not_carried"])
def test_the_tolerance_bites_through_the_pool(variables, fault):
    """The state zeroed where a prefill starts: at a prefix hit, and between
    two chunks of a cold prefill."""
    engine = engine_of(build(), variables)
    tokens = TOKENS[1]
    if fault == "zeroed_at_a_hit":
        engine.submit(np.concatenate([tokens[:24], [7, 9, 11]]).astype(
            np.int32), max_length=2)
        engine.drain()
    with probe_lfm2.state_read("zeroed"):   # in every one-lane call
        # (the fault is in the trace: a check of its own, traced in here)
        logits, matched = served_logits(engine, tokens, 40, Served)
    assert matched == (24 if fault == "zeroed_at_a_hit" else 0)
    expected = reference(variables["params"], tokens)[40 - CHUNK:]
    assert distance(logits, expected) > 30 * TOL


def test_the_engine_serves_requests_on_shared_prefixes(variables):
    """Through ``submit`` and ``step``: three requests on one registered
    prefix, each token the reference's best at its position; the admission
    span says what was matched and that the state was resumed."""
    get_recorder().clear()
    engine = engine_of(build(), variables)
    rng = np.random.default_rng(3)
    prefix = rng.integers(1, 512, 32, dtype=np.int32)
    prompts = [np.concatenate([prefix, rng.integers(1, 512, n, dtype=np.int32)])
               for n in (13, 5, 21)]
    first = engine.submit(prompts[0], max_length=8)
    results = engine.drain()
    ids = [first] + [engine.submit(p, max_length=8) for p in prompts[1:]]
    results.update(engine.drain())
    for rid, prompt in zip(ids, prompts):
        got = np.asarray(results[rid].tokens)
        whole = np.concatenate([prompt, got])
        logits = np.asarray(reference(variables["params"], whole[:-1]))[
            len(prompt) - 1:]
        best = logits.max(-1)
        assert (best - logits[np.arange(len(got)), got]).max() < TOL * logits.std()
    spans = [s for s in get_recorder().spans() if s.name == "serving.admit"]
    assert [s.attrs["matched"] for s in spans] == [0, 32, 32]
    assert [s.attrs["state_resumed"] for s in spans] == [False, True, True]
    ticks = [s for s in get_recorder().spans() if s.name == "serving.decode"]
    assert ticks and all("attn_rows" in s.attrs for s in ticks)
    snap = engine.metrics.snapshot()
    assert snap["state_snapshots_resumed"] == 2
    assert snap["state_snapshots_written"] >= 4
    assert snap["moe_layers"] == 5           # the two dense layers count nothing
    assert engine.cache_manager.pages_in_use == 0


def test_healthz_reports_the_kinds_of_state_and_the_refusals(variables):
    engine = engine_of(build(), variables)
    health = engine.health()
    assert health["model"] == "lfm2"
    caps = health["capabilities"]
    assert caps["state_kinds"] == ["kv", "conv"]
    assert caps["supports_prefix_cache"] and not caps["supports_host_spill"]
    assert set(health["state_bytes"]) == {"kv", "conv"}
    for kw, flag in ((dict(spec=True), "supports_spec"),
                     (dict(weight_dtype="int8"), "supports_int8_weights"),
                     (dict(kv_dtype="int8"), "supports_int8_kv"),
                     (dict(host_cache_bytes=1 << 20), "supports_host_spill"),
                     (dict(role="prefill"), "supports_roles")):
        with pytest.raises(ValueError, match=flag):
            engine_of(build(), variables, **kw)


def test_recovery_rebuilds_the_state_and_leaves_no_lane_with_anothers(
        variables):
    """A tick fault rolls back and ``recover()`` replays every request in
    flight through prefill: the tokens are those of an engine without the
    fault, and every page comes back."""
    from fleetx_tpu.resilience import faults

    def serve(fault):
        engine = engine_of(build(), variables)
        rng = np.random.default_rng(9)
        prefix = rng.integers(1, 512, 24, dtype=np.int32)
        ids = [engine.submit(np.concatenate(
            [prefix, rng.integers(1, 512, n, dtype=np.int32)]), max_length=8)
            for n in (9, 17, 3)]
        if fault:
            faults.configure(tick_raise="3")   # the fourth decode tick
        try:
            results = engine.drain()
        finally:
            faults.reset()
        assert engine.cache_manager.pages_in_use == 0
        engine.cache_manager.pool.check_invariants()
        return ([list(results[i].tokens) for i in ids],
                engine.metrics.snapshot()["engine_recoveries"])

    clean, faulted = serve(False), serve(True)
    assert faulted[1] == 1 and clean[1] == 0
    assert faulted[0] == clean[0]


def test_the_stack_builds_from_the_published_list_with_no_unused_parameter():
    """The whole 24-entry ``layer_types`` (not periodic at its end) at a tiny
    width: every parameter moves the logits."""
    config = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "lfm2-8b-a1b-l14.json")))
    types = config["published_layer_types"]
    assert len(types) == 24 and types[:14] == config["layer_types"]
    model = build(num_layers=24, layer_types=tuple(types))
    plan = mixed_stack.layer_plan(model.cfg)
    assert plan["counts"] == {"conv": 18, "mamba": 0, "kda": 0, "attention": 6,
                              "dense": 2, "experts": 22}
    held = flax.core.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))
    tokens = TOKENS[:1, :16]

    def loss(params):
        return (model.apply({"params": params}, tokens) ** 2).mean()

    grads = jax.grad(loss)(held["params"])
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        name = jax.tree_util.keystr(path)
        size = np.abs(np.asarray(g))
        per_layer = (size.reshape(g.shape[0], -1).max(1) if "layers" in name
                     else size.max())
        if "expert_bias" in name:
            continue          # steers the choice alone: no gradient
        assert np.all(per_layer > 0), name


def test_the_block_fields_are_checked_by_name():
    ok = dict(SIZES)
    for changes, exc, word in (
            ({"layer_types": TYPES[:5]}, ValueError, "layer_types"),
            ({"layer_types": TYPES[:6] + ("gru",)}, ValueError, "layer_types"),
            ({"layer_types": TYPES[:6] + ("mamba",)}, NotImplementedError,
             "conv AND mamba"),
            ({"num_dense_layers": 9}, ValueError, "num_dense_layers"),
            ({"conv_L_cache": 1}, ValueError, "conv_L_cache"),
            ({"gate": "softmax_topk"}, ValueError, "use_expert_bias"),
            ({"qk_norm_scope": "row"}, ValueError, "qk_norm_scope"),
            ({"qk_norm_scope": "projection"}, NotImplementedError, "qk_norm"),
            ({"dense_ffn_hidden_size": None}, ValueError, "dense_ffn_hidden_size"),
            ({"sliding_window": 16}, NotImplementedError, "sliding_window")):
        with pytest.raises(exc, match=word):
            GPTConfig(**{**ok, **changes})


def test_grouped_heads_take_a_per_head_qk_norm():
    """What ``check`` used to refuse: QK-norm with grouped heads, now one
    weight a head size (no layer types needed)."""
    sizes = {k: v for k, v in SIZES.items() if k not in (
        "layer_types", "num_dense_layers", "dense_ffn_hidden_size", "gate",
        "use_expert_bias", "expert_bias_init_std", "num_experts", "top_k",
        "expert_mode", "norm_topk_prob")}
    model = GPTForPretraining(GPTConfig(**{**sizes, "num_layers": 2}))
    held = flax.core.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))
    attn = held["params"]["gpt"]["layers"]["layer"]["attn"]
    assert attn["q_norm"]["scale"].shape == (2, 8)
    assert np.isfinite(np.asarray(
        traced_apply(model, held, TOKENS[:1]))).all()


def test_the_configuration_is_the_catalogs_and_its_count_the_programs_own():
    config = json.load(open(os.path.join(
        ROOT, "perfbench", "configs", "lfm2-8b-a1b-l14.json")))
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_theta": 1000000,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 14 == config["model"]["num_layers"]
    assert sorted(config["reduced"]) == ["layer_types", "num_hidden_layers",
                                         "num_layers"]

    def count(layers, types):
        sizes = dict(config["model"], num_layers=layers, layer_types=types,
                     dtype="bfloat16")
        model = GPTForPretraining(GPTConfig.from_model_config(sizes))
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))

    cut = count(14, config["layer_types"])
    whole = count(24, config["published_layer_types"])
    assert abs(cut - 4.667e9) < 0.001e9 and abs(whole - 8.34e9) < 0.005e9
