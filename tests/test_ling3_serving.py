"""Ling-3.0-flash's stack (KDA delta-rule layers, whose matrix state is held
once a lane outside the engine's page pool, BESIDE a latent attention layer,
whose latents live in the pool under the lane's pages: a lane with TWO HOMES;
no query latent, a per-head query norm and a norm of the shared rotary key, a
head-wise output gate, full decay and gate projections under the bounded
"safe" decay; over an expert layer that holds exactly one router group)
through the normal path, against the plain float32 reference
``perfbench/reference/ling3_f32.py`` at a tiny size on seeded weights: the
full forward; a prefill IN CHUNKS (a boundary inside a run of tokens, a
padded last bucket) and decoding through BOTH homes; a request preempted and
resumed with its history, and an engine recovered after a tick fault; the
EIGHT shares of an expert layer adding up to the uncut layer, a token whose
groups exclude a chip's group getting the shared expert alone from it; the
published configuration's parameter count against the program's own tree;
what the configuration and the engine refuse, each with its cause; and that
the stacks WITHOUT the mix trace the programs they traced before. Logits are
compared, not tokens.

TOLERANCE. These tests compute in float32 on the CPU, where system and
reference differ only in the order of their sums: some 1e-5 of the
reference's logit deviation is read, and the limit is 2e-4. The bfloat16
limits of the chip are the benchmark driver's
(``perfbench/drivers/serve_closed_loop_ling.py``).
"""

import dataclasses

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import computed_once, traced_apply

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.parallel import moe_share
from fleetx_tpu.serving import ServingEngine
from perfbench import harness
from perfbench.drivers import serve_closed_loop_ling as driver
from perfbench.reference import ling3_f32

TOL = 2e-4          # of the reference's logit standard deviation (docstring)
PAGE, CACHE_LEN, CHUNK, BUCKET = 8, 128, 16, 8
CONFIG = harness.load_json("perfbench", "configs", "ling3-flash-ep8-l7.json")
MODEL = dict(harness.with_tiny(CONFIG, True)["model"], vocab_size=256,
             max_position_embeddings=512)
SIZES = dict(MODEL, use_flash_attention=False, dtype="float32")
reference = computed_once(ling3_f32.configured(MODEL))
TOKENS = np.random.default_rng(0).integers(1, 256, (2, 56), dtype=np.int32)


def build(**changes):
    return GPTForPretraining(GPTConfig.from_model_config({**SIZES, **changes}))


@pytest.fixture(scope="module")
def variables():
    """Seeded weights. At width 64 with every matrix at the initializer's
    0.02 the head dominates and the layers decide nothing, so the layers'
    matrices are scaled up and every norm weight moved off 1, until both
    operators, the router and all the norms decide the logits."""
    v = flax.core.meta.unbox(jax.jit(build().init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))

    def stir(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), x.shape)
        moved = "layers" in name and ("kernel']" in name or "_proj']" in name)
        return x * 2.0 if moved else x

    return jax.tree_util.tree_map_with_path(stir, v)


def distance(got, expected) -> float:
    """Largest error in units of the expected values' deviation."""
    expected = np.asarray(expected)
    return float(np.abs(np.asarray(got) - expected).max() / expected.std())


def engine_of(model, variables, **kw):
    kw = {"slots": 3, "page_size": PAGE, "prefill_bucket": BUCKET,
          "cache_len": CACHE_LEN, "prefill_chunk": CHUNK,
          "prefix_cache": False, **kw}
    return ServingEngine(
        model, variables,
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=8), **kw)


@pytest.fixture(scope="module")
def engine(variables):
    """ONE engine for the module (the lane-level test claims and frees its
    lanes; the request-level tests run after it)."""
    return engine_of(build(), variables)


@pytest.fixture(scope="module")
def served(engine):
    """The check's own programs over that engine, compiled once."""
    return driver.Served(engine)


def rated(variables, prompt, got) -> float:
    """How far the tokens ``got`` an engine returned for ``prompt`` stand
    below the reference's best at their positions, in its logits' unit."""
    got = np.asarray(got)
    tokens = np.concatenate([prompt, got])
    logits = np.asarray(reference(variables["params"], tokens[:-1],
                                  tail=len(got)))
    return float((logits.max(-1) - logits[np.arange(len(got)), got]).max()
                 / logits.std())


# ------------------------------------------------- the stack and the reference

def test_full_forward_matches_the_reference(variables):
    logits = traced_apply(build(), variables, TOKENS[:1])
    assert distance(logits[0], reference(variables["params"], TOKENS[0])) < TOL


def test_the_tree_is_stacked_by_kind_with_the_operators_own_leaves(variables):
    layers = variables["params"]["gpt"]["layers"]
    assert sorted(layers) == ["attention", "dense", "experts", "kda"]
    kda, latent = layers["kda"]["op"], layers["attention"]["op"]
    # full decay and gate projections: no low-rank pair, no gate bias
    assert kda["f_proj"]["kernel"].shape == kda["g_proj"]["kernel"].shape == (
        6, 64, 64)
    assert kda["f_proj"]["bias"].shape == (6, 64) and "bias" not in kda["g_proj"]
    assert not {"f_a", "f_b", "g_a", "g_b"} & set(kda)
    # no query latent; a head's query norm, the rotary key's, one gate a head
    assert "q_a_proj" not in latent and "q_a_norm" not in latent
    assert latent["q_proj"].shape == (1, 64, 4, 24)
    assert latent["q_norm"]["scale"].shape == (1, 24)
    assert latent["k_rope_norm"]["scale"].shape == (1, 8)
    assert latent["gate_proj"].shape == (1, 64, 4)
    assert layers["experts"]["op"]["w_gate"].shape == (6, 8, 64, 32)
    assert layers["experts"]["op"]["router"]["kernel"].shape == (6, 64, 32)


def test_the_published_parameter_count_is_the_programs_own():
    """The configuration's ``parameters`` (ISSUE 64's count: 2,866,268,352)
    against the program's tree at the published widths, abstractly; and the
    two homes of a lane's state in the cache tree."""
    model = GPTForPretraining(GPTConfig.from_model_config(
        {**CONFIG["model"], "dtype": "bfloat16"}))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == CONFIG["parameters"] == 2_866_268_352
    cache = jax.eval_shape(lambda: model.clone(cfg=dataclasses.replace(
        model.cfg, decode_cache_len=64, decode_num_pages=5,
        decode_page_size=16)).init(
            jax.random.PRNGKey(0), np.zeros((2, 1), np.int32), decode=True,
            cache_positions=np.zeros((2,), np.int32),
            block_tables=np.zeros((2, 6), np.int32)))["cache"]["gpt"]["layers"]
    # once a lane: 6 x (2,097,152 B + 73,728 B); in the pool: 576 values a
    # token, the rotary key's leaf held 128 wide
    assert cache["kda_state"].shape == (6, 2, 128, 32, 128)
    assert cache["kda_conv"].shape == (6, 2, 3 * 3 * 4096)
    assert cache["cached_key"].shape[-1] == 512
    assert cache["cached_value"].shape[-1] == 128
    assert cache["moe_stats"].shape == (6, moe_share.stats_words(model.cfg))
    assert model.cfg.held_group == 0 and model.cfg.state_kinds == (
        "kda", "latent")


# ---------------------------------------- prefill and decode through both homes

def test_chunked_prefill_then_ticks_are_the_reference(engine, served,
                                                      variables, prompt=44):
    """A prompt in the ENGINE'S chunk programs (16, 16, then 12 in 16 rows:
    chunk boundaries inside a run of tokens, a padded last bucket), then
    ticks over every lane through the lane's state AND its latent pages: the
    logits of the last call's rows and of every tick, ``S`` and the filter
    rows after the prefill and after the last tick, the latents cached."""
    tokens = TOKENS[0][:prompt + 8]
    with driver._latent_rows():
        mine = served.sequence_parts(tokens, prompt)
    own = mine["own"]
    theirs = reference(variables["params"], tokens, tail=own + 8,
                       with_parts=True, states_at=(prompt, prompt + 8))
    assert distance(mine["logits"], theirs["logits"]) < TOL
    assert mine["kv"].shape == theirs["kv"].shape == (1, 1, own + 8, 32 + 8)
    assert distance(mine["kv"], theirs["kv"]) < TOL
    for i, key in enumerate(("state_prefill", "state_end")):
        assert distance(mine[key][0], theirs["state"][:, i]) < TOL
        assert distance(mine[key][1], theirs["rows"][:, i]) < TOL
    # the safe gate: the log decay lies in (-5, 0); beta is not doubled
    kda_layers = [i for i, t in enumerate(MODEL["layer_types"]) if t == "kda"]
    g = mine["kda_g"][kda_layers]
    assert -5.0 <= g.min() < -2.5 and g.max() <= 0.0
    assert 0.0 < mine["kda_beta"].max() < 1.0
    engine.cache_manager.pool.check_invariants()
    assert engine.cache_manager.pages_in_use == 0


def test_the_other_reading_of_the_safe_gate_is_another_model(variables):
    """``kda_safe_gate`` off (``-exp(A_log) softplus(.)``, unbounded): the
    logits then stand far from the reference's."""
    logits = traced_apply(build(kda_safe_gate=False, kda_lower_bound=0.0),
                          variables, TOKENS[:1])
    assert distance(logits[0], reference(variables["params"], TOKENS[0])) > 0.05


@pytest.mark.parametrize("seam, value", [
    # (the norm is still called: its weight stays in the tree)
    ("_normed_rotary_key", lambda norm, key: (norm(key), key)[1]),
    ("_head_gated", lambda out, gate: out),
])
def test_the_rotary_keys_norm_and_the_head_gate_are_really_applied(
        variables, monkeypatch, seam, value):
    from fleetx_tpu.models.gpt import latent

    monkeypatch.setattr(latent, seam, value)
    logits = jax.jit(build().apply)(variables, TOKENS[:1])
    far = distance(logits[0], reference(variables["params"], TOKENS[0]))
    assert far > 20 * TOL


# --------------------------------------------------- the share and the model

def _uncut(v, whole):
    return {"router": {"kernel": v["router"]["kernel"][None]},
            "expert_bias": v["expert_bias"][None],
            "w_gate": whole[0][None], "w_up": whole[1][None],
            "w_down": whole[2].swapaxes(1, 2)[None],
            **{k: v[k][None] for k in ("shared_gate", "shared_up",
                                       "shared_down")}}


def test_all_eight_shares_add_up_to_the_uncut_layer_and_a_group_is_a_chip():
    """64 routed experts in EIGHT groups of 8 over EIGHT programs, each
    holding one whole group: every program routes over all 64 (top 8 inside
    the 4 best groups), computes the part its own group gives and adds the
    shared expert. Their sum, the shared expert counted once, is what the
    uncut reference gives for the whole layer; and a token whose 4 groups
    exclude a chip's group gets EXACTLY the shared expert from that chip."""
    shares, held = 8, 8
    sizes = {**SIZES, "num_routed_experts": 64, "num_experts": held,
             "n_group": 8, "topk_group": 4, "top_k": 8,
             "first_expert_held": 0}
    cfg = GPTConfig.from_model_config(sizes)
    assert cfg.held_group == 0
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64))
    whole = jax.random.normal(jax.random.PRNGKey(4), (3, 64, 64, 32)) * 0.2
    v = flax.core.meta.unbox(jax.jit(moe_share.SharedMoEMLP(cfg).init)(
        jax.random.PRNGKey(5), x))["params"]
    v = {**v, "router": {"kernel": v["router"]["kernel"] * 20.0},
         "expert_bias": v["expert_bias"] * 4.0}
    settings = dict(ling3_f32._settings(sizes), first=0)
    with jax.default_matmul_precision("highest"):
        want, chosen, _, _ = ling3_f32._experts(x[0], _uncut(v, whole), 0,
                                                settings)
    shared = moe_share._shared_expert(
        x[0], v["shared_gate"], v["shared_up"], v["shared_down"])
    groups = np.asarray(chosen) // held                      # [n, k]
    assert all(len(set(row)) <= 4 for row in groups)         # 4 groups a token
    total = jnp.zeros_like(x)
    for i in range(shares):
        layer = moe_share.SharedMoEMLP(dataclasses.replace(
            cfg, first_expert_held=i * held))
        assert layer.cfg.held_group == i
        mine = {**v, "w_gate": whole[0, i * held:(i + 1) * held],
                "w_up": whole[1, i * held:(i + 1) * held],
                "w_down": whole[2, i * held:(i + 1) * held].swapaxes(1, 2)}
        part = traced_apply(layer, {"params": mine}, x)
        total = total + part
        outside = ~(groups == i).any(-1)   # tokens that never reach chip i
        assert outside.any() and not outside.all()
        np.testing.assert_allclose(np.asarray(part[0])[outside],
                                   np.asarray(shared)[outside], atol=1e-6)
    got = total[0] - (shares - 1) * shared
    assert np.abs(np.asarray(got - want)).max() <= 1e-5 * float(
        np.abs(want).max())
    assert len(np.unique(groups)) == shares


def test_the_held_group_counts_the_tokens_that_reach_it(variables):
    """``moe_tick_group_tokens``: the rows of ticks with at least one chosen
    expert in the group held, counted on the device beside the pairs they
    brought; a share that is no whole group of 8 counts nothing."""
    from fleetx_tpu.serving.model_protocol import GPTExecutor

    cfg = build().cfg
    assert cfg.held_group == 1 and moe_share.stats_words(cfg) == 26
    layer = moe_share.SharedMoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (24, 1, 64), jnp.float32)
    params = flax.core.meta.unbox(jax.jit(layer.init)(
        jax.random.PRNGKey(1), x))["params"]
    params = {**params, "router": {"kernel": params["router"]["kernel"] * 40}}
    stats = jnp.zeros((2, moe_share.stats_words(cfg)), jnp.uint32)
    _, mut = traced_apply(
        layer, {"params": params, "cache": {"moe_stats": stats}}, x,
        decode=True, layer_index=jnp.int32(0), mutable=["cache", "routing"])
    chosen = np.asarray(mut["routing"]["experts"][0]).reshape(24, -1)
    here = ((chosen >= 8) & (chosen < 16))
    counters = GPTExecutor(build()).counters(
        {"layers": {"moe_stats": mut["cache"]["moe_stats"]}})
    assert counters["moe_tick_group_tokens"] == int(here.any(-1).sum())
    assert counters["moe_tick_pairs"] == int(here.sum())
    assert 0 < counters["moe_tick_group_tokens"] < 24
    for other in ({"first_expert_held": 4}, {"num_experts": 4}):
        assert GPTConfig.from_model_config(
            {**SIZES, **other}).held_group is None


# ------------------------------------------------------------ the refusals

LATENT_ONLY = {k: v for k, v in SIZES.items() if not k.startswith("kda")}


@pytest.mark.parametrize("over,exc,match", [
    ({"expert_swiglu_limit": 4.0}, NotImplementedError, "expert_swiglu_limit"),
    ({"shared_expert_swiglu_limit": 5.0}, NotImplementedError,
     "shared_expert_swiglu_limit"),
    ({"q_lora_rank": 24}, ValueError, "qk_norm over latent attention"),
    ({"attention_gate": "sigmoid"}, NotImplementedError, "sigmoid_head"),
    ({"kda_gate_rank": 8}, ValueError, "kda_no_lora with kda_gate_rank"),
    ({"kda_lower_bound": 0.0}, ValueError, "kda_lower_bound"),
    ({"kda_safe_gate": False}, ValueError, "kda_safe_gate"),
    ({"index_n_heads": 2, "index_head_dim": 8, "index_topk": 4},
     NotImplementedError, "beside kda layers"),
    ({"mla_scale_kv_lora": True}, NotImplementedError, "beside kda layers"),
    ({"layer_types": ["mamba"] * 4 + ["latent_attention"] + ["mamba"] * 2,
      "mamba_dt_rank": 8}, NotImplementedError,
     "beside another operator than kda"),
    ({"layer_types": ["kda", "full_attention"] * 3 + ["latent_attention"]},
     NotImplementedError, "beside another operator than kda"),
])
def test_the_configuration_refuses_what_nobody_wrote(over, exc, match):
    with pytest.raises(exc, match=match):
        GPTConfig.from_model_config({**SIZES, **over})


@pytest.mark.parametrize("over,exc,match", [
    # every OTHER mix of what this family brings stays refused: a stack of
    # latent layers alone takes a query latent, no qk_norm and no gate
    ({}, ValueError, "latent_attention needs .*q_lora_rank"),
    ({"q_lora_rank": 24}, ValueError, "qk_norm over latent attention"),
    ({"q_lora_rank": 24, "qk_norm": False, "qk_norm_scope": "projection"},
     NotImplementedError, "sigmoid_head"),
])
def test_a_stack_of_latent_layers_alone_takes_none_of_it(over, exc, match):
    with pytest.raises(exc, match=match):
        GPTConfig.from_model_config({
            **LATENT_ONLY, "layer_types": ["latent_attention"] * 7, **over})


@pytest.mark.parametrize("asked,cause", [
    ({"prefix_cache": True}, "prefix reuse.*delta-rule"),
    ({"role": "prefill"}, "prefill or decode role.*delta-rule"),
    ({"host_cache_bytes": 1 << 20}, "host or disk page tier.*delta-rule"),
    ({"spec": True}, "speculative decoding"),
])
def test_what_the_engine_refuses_for_this_family(variables, asked, cause):
    with pytest.raises(ValueError, match=cause):
        engine_of(build(), variables, **asked)


def test_the_cache_manager_holds_both_homes_and_refuses_the_trie(variables):
    from fleetx_tpu.serving.cache_manager import PagedKVCacheManager

    model = build().clone(cfg=dataclasses.replace(
        build().cfg, decode_cache_len=32, decode_num_pages=9,
        decode_page_size=8))
    with pytest.raises(ValueError, match="state kind 'kda'"):
        PagedKVCacheManager(model, 2, 32, 9, 8, prefix_cache=True)
    manager = PagedKVCacheManager(model, 2, 32, 9, 8, prefix_cache=False)
    assert manager.lane_state and manager.lane_state_kind == "kda"
    assert manager.state_kinds == ("kda", "latent")
    assert manager.lane_bytes == 6 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    # a page of the ONE latent layer: 8 rows of 32 + 128 float32 columns
    assert manager.page_bytes["kv"] == 8 * (32 + 128) * 4
    assert manager.tables.shape == (2, 1 + 4)
    counters = manager.class_counters()
    assert counters["latent_page_bytes"] == manager.page_bytes["kv"]
    assert counters["latent_pages_in_use"] == 0


# ------------------------------------------- through submit / step, last

def test_the_engine_serves_it_and_says_what_it_did(engine, variables):
    from fleetx_tpu.obs.tracing import get_recorder

    get_recorder().clear()
    first = engine.submit(TOKENS[0][:13], max_length=2)  # one call, bucket 16
    second = engine.submit(TOKENS[1][:40], max_length=6)  # chunks 16, 16, 8
    while first not in engine._results:
        engine.step()
    # the first request's lane is dead weight now and NOT decoding: the
    # second's ticks leave its matrix state bit for bit
    idle = [x.copy() for x in driver.lane_state(engine, 0)]
    assert np.abs(idle[0]).max() > 0
    results = engine.drain()
    for kept, held in zip(idle, driver.lane_state(engine, 0)):
        np.testing.assert_array_equal(held, kept)
    third = engine.submit(TOKENS[0][20:33], max_length=4)
    results.update(engine.drain())
    for rid, prompt in ((first, TOKENS[0][:13]), (second, TOKENS[1][:40]),
                        (third, TOKENS[0][20:33])):
        assert rated(variables, prompt, results[rid].tokens) < TOL
    spans = get_recorder().spans()
    rows = [s.attrs for s in spans
            if s.name in ("serving.admit", "serving.prefill_chunk")
            and "scan_rows" in s.attrs]
    assert sorted(r["scan_rows"] for r in rows) == [8, 16, 16, 16, 16]
    assert all("latent_rows" in r and "pairs" in r for r in rows)
    ticks = [s.attrs for s in spans if s.name == "serving.decode"]
    # BOTH homes on a tick's span: the lanes whose matrix state it advances
    # and the latent rows its one latent layer attends over
    assert ticks and all(
        t["state_lanes"] >= 1 and t["latent_rows"] >= t["state_lanes"]
        and "pairs" in t for t in ticks)
    snapshot = engine.metrics.snapshot()
    assert snapshot["kda_state_resets"] >= 3
    assert snapshot["moe_tick_group_tokens"] > 0
    lane = 6 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert snapshot["state_bytes_lanes"] == 3 * lane
    assert engine.health()["state_bytes"] == {"kv": 0, "kda": 3 * lane}
    assert engine.capabilities.state_kinds == ("kda", "latent")
    assert not engine.capabilities.supports_prefix_cache
    np.testing.assert_array_equal(engine.cache_manager.tables[:, 0],
                                  np.arange(3))


def test_a_request_preempted_and_resumed_gives_the_same_answer(variables):
    """Preemption is the router's: the request is cancelled with what it
    has emitted and submitted again with that as its ``history``. The
    resumed lane rebuilds BOTH homes from ``prompt + history`` (the matrix
    state through the chunk programs, the latents into fresh pages) and the
    answer is the uninterrupted one's."""
    prompt = TOKENS[1][:37]
    whole = engine_of(build(), variables)
    rid = whole.submit(prompt, max_length=10)
    uninterrupted = list(whole.drain()[rid].tokens)

    engine = engine_of(build(), variables)
    other = engine.submit(TOKENS[0][:21], max_length=10)   # a lane beside it
    rid = engine.submit(prompt, max_length=10)
    while len(engine.emitted_tokens(rid) or ()) < 4:
        engine.step()
    emitted = list(engine.emitted_tokens(rid))
    assert engine.cancel(rid) and 4 <= len(emitted) < 10
    again = engine.submit(prompt, max_length=10, history=emitted)
    results = engine.drain()
    resumed = list(results[again].tokens)
    assert resumed[:len(emitted)] == emitted and len(resumed) == 10
    assert rated(variables, prompt, resumed) < TOL
    assert rated(variables, prompt, uninterrupted) < TOL
    assert rated(variables, TOKENS[0][:21], results[other].tokens) < TOL
    assert engine.cache_manager.pages_in_use == 0
    engine.cache_manager.pool.check_invariants()


def test_recovery_rebuilds_both_homes(variables):
    """A tick fault rolls back and ``recover()`` replays every request in
    flight through prefill into a NEW cache tree (zeroed lanes, an empty
    pool): the tokens are those of an engine without the fault."""
    from fleetx_tpu.resilience import faults

    def serve(fault):
        engine = engine_of(build(), variables)
        rng = np.random.default_rng(9)
        prompts = [rng.integers(1, 256, n, dtype=np.int32)
                   for n in (33, 17, 9)]
        ids = [engine.submit(p, max_length=8) for p in prompts]
        if fault:
            faults.configure(tick_raise="3")   # the fourth decode tick
        try:
            results = engine.drain()
        finally:
            faults.reset()
        assert engine.cache_manager.pages_in_use == 0
        engine.cache_manager.pool.check_invariants()
        return ([(p, list(results[i].tokens)) for p, i in zip(prompts, ids)],
                engine.metrics.snapshot()["engine_recoveries"])

    clean, faulted = serve(False), serve(True)
    assert faulted[1] == 1 and clean[1] == 0
    for (prompt, tokens), (_, same) in zip(faulted[0], clean[0]):
        assert len(tokens) == len(same) == 8
        assert rated(variables, prompt, tokens) < TOL


# ------------------------------------------------ what stays as it is today

# sha256 of the jaxprs of each configuration's tiny stack (a 16-row chunk and
# a 3-lane tick through a page pool: ``tests/test_longcat_serving.py``
# ``traced_programs``), taken on the commit BEFORE the mix (736da2b): the
# stacks that share ``MixedStack``'s one scanned body and ``LatentAttention``
# trace the programs they traced then, instruction for instruction. Jamba2's
# and LongCat's are held to the same digests in ``tests/test_solar2_serving
# .py``, LFM2's, Trinity's, A.X-K1's and DeepSeek-V3.2's in
# ``tests/test_longcat_serving.py``; Solar2's is new here.
UNCHANGED = {
    "perfbench/configs/solar-open2-ep16-l8.json": (
        "ec7c37e01520067a", "5f74029ef27ec047"),
}


@pytest.mark.parametrize("path", sorted(UNCHANGED))
def test_a_stack_without_the_mix_traces_the_program_it_traced_before(path):
    from tests.test_longcat_serving import digest, traced_programs

    texts = traced_programs(path)
    assert tuple(digest(t) for t in texts) == UNCHANGED[path]
    for text in texts:
        assert "mla_qk_norm" not in text and "f_proj" not in text


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


def test_the_kernels_compile_for_the_v5e_at_the_published_widths(
        one_chip, monkeypatch):
    """32 heads of 128 (eight heads a sublane group: 32 divides): a chunk of
    512 rows of one lane and the last bucket's 256, and the tick's step over
    the cell's whole leaf ``[6, 128, 128, 32, 128]``, aliased to its output;
    the absorbed latent decode at 32 heads over 128 lanes."""
    from fleetx_tpu.ops.pallas import kda, mla_decode

    monkeypatch.setattr(kda, "_interpret", lambda: False)
    monkeypatch.setattr(mla_decode, "_interpret", lambda: False)
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    heads, d, lanes, layers = 32, 128, 128, 6
    for rows in (512, 256):
        row = spec((rows, heads, d))
        text = jax.jit(lambda *a: kda.kda_chunk(*a[:-1], skip=a[-1])).lower(
            row, row, row, row, spec((rows, heads)), spec((d, heads, d)),
            spec((), jnp.bool_)).compile().as_text()
        assert kda.CHUNK_KERNEL_NAME in text
    row = spec((lanes, heads, d))
    leaf = (layers, lanes, d, heads, d)
    text = jax.jit(
        lambda s, l, *a: kda.kda_step(s, l, *a[:-1], skip=a[-1]),
        donate_argnums=0).lower(
        spec(leaf), spec((), jnp.int32), row, row, row, row,
        spec((lanes, heads)), spec((lanes,), jnp.bool_),
        spec((), jnp.bool_)).compile().as_text()
    assert kda.STEP_KERNEL_NAME in text
    assert not [line for line in text.splitlines() if " copy(" in line
                and "f32[6,128,128,32,128]" in line.split("=")[0]]
    pages, per = 4097, 32
    text = jax.jit(lambda *a: mla_decode.mla_decode_paged(
        *a[:4], tables=a[4], end=a[5], scale=192 ** -0.5)).lower(
        spec((lanes, heads, 512), jnp.bfloat16),
        spec((lanes, heads, 128), jnp.bfloat16),
        spec((pages, 16, 512), jnp.bfloat16),
        spec((pages, 16, 128), jnp.bfloat16),
        spec((lanes, per), jnp.int32),
        spec((lanes,), jnp.int32)).compile().as_text()
    assert mla_decode.KERNEL_NAME in text
