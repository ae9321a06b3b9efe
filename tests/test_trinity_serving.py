"""The Trinity block (``model_type: afmoe``: grouped-query heads with a
per-head QK-norm and a sigmoid OUTPUT GATE, window layers that rotate beside
full layers that take no position, four norms a layer, the embedding times a
multiplier, a leading dense layer, then expert layers whose sigmoid router
chooses under a selection bias among 16 experts of which this program HOLDS
4, beside a shared expert) through the normal path, the stack of mixed
operators, against the plain float32 reference
``perfbench/reference/trinity_f32.py``, at a tiny size on seeded weights: the
full forward; prefill IN CHUNKS and then decoding token by token through the
engine's page pool of TWO CLASSES, past the (tiny) window so that window
pages are released and used again; through ``ServingEngine.submit`` /
``step``. Logits are compared, not tokens. The shares add up: the parts all
four shares give, the shared expert counted once, are the uncut reference's
whole expert layer. And the tolerance bites: each wrong system turns the
comparison false.

TOLERANCE. These tests compute in float32 on the CPU, where system and
reference differ only in the order of their sums: the distance read is 5e-6
to 7e-6 of the standard deviation of the reference's logits, and the limit
is 1e-4. The smallest fault (a window off by one) reads 50 times the limit.
The bfloat16 limits of the chip are the benchmark driver's
(``perfbench/drivers/serve_closed_loop_swa_share.py``).
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
from fleetx_tpu.parallel import moe_share
from fleetx_tpu.serving import ServingEngine
from perfbench.drivers.serve_closed_loop_swa import Served
from perfbench.reference import trinity_f32

TOL = 1e-4          # of the reference's logit standard deviation (docstring)
WINDOW, PAGE, CACHE_LEN, CHUNK = 16, 8, 128, 16
TYPES = ("sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention", "full_attention")
LAYOUT = tuple(int(t == "sliding_attention") for t in TYPES)
MODEL = dict(
    vocab_size=512, hidden_size=64, num_layers=5, num_attention_heads=8,
    num_key_value_heads=2, head_size=16, ffn_hidden_size=32,
    dense_ffn_hidden_size=96, num_dense_layers=1, layer_types=TYPES,
    max_position_embeddings=256, num_experts=4, num_routed_experts=16,
    first_expert_held=4, num_shared_experts=1, gate="sigmoid_topk", top_k=3,
    norm_topk_prob=True, routed_scaling_factor=2.448, use_expert_bias=True,
    expert_bias_init_std=0.05, position_embedding="rope", rope_theta=10000.0,
    rope_layout=LAYOUT, sliding_window=WINDOW, sliding_window_layout=LAYOUT,
    qk_norm=True, qk_norm_scope="head", attention_gate="sigmoid",
    sandwich_norm=True, embedding_multiplier=8.0, norm="rmsnorm",
    norm_eps=1e-5, mlp_act="swiglu", use_bias=False,
    tie_word_embeddings=False)
SIZES = dict(MODEL, hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
             expert_mode=True, family="trinity", use_flash_attention=False,
             dtype=jnp.float32)
reference = computed_once(trinity_f32.configured(MODEL))
TOKENS = np.random.default_rng(0).integers(1, 512, (2, 56), dtype=np.int32)


def build(**changes):
    return GPTForPretraining(GPTConfig(**{**SIZES, **changes}))


def seeded(model):
    """Seeded weights: the layers' matrices scaled up and the norm weights
    moved off 1, so that the gate, the rotation, the window, all four norms
    and the router decide the logits (a fault in any of them then shows)."""
    v = flax.core.meta.unbox(jax.jit(model.init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))

    def stir(path, x):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), x.shape)
        if "layers" in name and "expert_bias" not in name:
            return x * 8.0
        return x

    return jax.tree_util.tree_map_with_path(stir, v)


@pytest.fixture(scope="module")
def variables():
    return seeded(build())


def distance(system, want):
    """Largest logit error in units of the reference's logit spread."""
    want = np.asarray(want)
    return float(np.abs(np.asarray(system) - want).max() / want.std())


def full_forward(model, params, tokens=TOKENS):
    return jax.jit(lambda p, t: model.apply({"params": p}, t))(params, tokens)


served_of = sharing_programs(Served)


@sharing_programs
def engine_of(model, v, lanes=3, **kw):
    return ServingEngine(
        model, v, slots=lanes, cache_len=CACHE_LEN, page_size=PAGE,
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=8),
        prefill_chunk=CHUNK, prefill_bucket=8, **kw)


def without(params, model):
    """``params`` without the leaves ``model`` has no place for."""
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]

    def keep(have, want):
        return {k: keep(have[k], w) if isinstance(w, dict) else have[k]
                for k, w in want.items()}

    return keep(params, flax.core.meta.unbox(want))


def test_full_forward_matches_the_reference(variables):
    mine = full_forward(build(), variables["params"])
    for i, row in enumerate(TOKENS):
        assert distance(mine[i], reference(variables["params"], row)) <= TOL


def test_the_tree_is_stacked_by_kind_with_a_gate_and_post_norms(variables):
    layers = variables["params"]["gpt"]["layers"]
    assert set(layers) == {"attention", "dense", "experts"}
    for kind, count in (("attention", 5), ("dense", 1), ("experts", 4)):
        assert set(layers[kind]) == {"norm", "op", "post_norm"}
        assert layers[kind]["post_norm"]["scale"].shape == (count, 64)
    op = layers["attention"]["op"]
    assert op["gate_proj"]["kernel"].shape == (5, 64, 8, 16)
    assert op["qkv_proj"]["kernel"].shape == (5, 64, 12, 16)
    assert op["q_norm"]["scale"].shape == (5, 16)
    experts = layers["experts"]["op"]
    assert experts["router"]["kernel"].shape == (4, 64, 16)   # ALL routed
    assert experts["w_gate"].shape == (4, 4, 64, 32)          # the 4 held
    assert experts["expert_bias"].shape == (4, 16)
    assert experts["shared_gate"].shape == (4, 64, 32)


def test_chunked_prefill_then_decode_through_both_page_classes(variables):
    """44 tokens prefilled in whole chunks of 16 and 12 decoded through the
    engine's own pool and allocators: three times the window, so every
    window layer's first pages were released and handed out again, and a
    page released never lay inside a live query's window, or the logits
    would say so."""
    engine = engine_of(build(), variables)
    assert engine.health()["model"] == "trinity"
    assert engine.capabilities.page_classes == ("full", "window")
    assert engine.capabilities.state_kinds == ("kv",)
    pool = engine.cache_manager.window_pool
    for row in TOKENS:
        mine = served_of(engine, CHUNK).sequence(row, 44)
        want = np.asarray(reference(variables["params"], row))
        assert len(mine["logits"]) == 28
        assert distance(mine["logits"], want[-28:]) <= TOL
        # the experts' routing is sown for the four expert layers alone
        assert mine["experts"].shape == (4, 28, 3)
    assert pool.recycled >= 2 * (48 - WINDOW) // PAGE
    assert pool.pages_in_use == 0 == engine.cache_manager.pool.pages_in_use
    pool.check_invariants()
    engine.cache_manager.pool.check_invariants()


def test_the_pool_counts_two_classes_over_the_attention_layers(variables):
    from fleetx_tpu.models.gpt import hybrid

    engine = engine_of(build(), variables)
    cfg = engine.model.cfg
    full, window = cfg.decode_num_pages, cfg.decode_window_pages
    assert window == 3 * ((WINDOW + CHUNK) // PAGE + 1) + 1
    assert hybrid.layer_bases(cfg).tolist() == [
        0, window, 2 * window, 2 * window + full, 3 * window + full]
    assert hybrid.total_pages(cfg) == 3 * window + 2 * full
    assert engine.cache_manager.tables.shape == (2, 3, CACHE_LEN // PAGE)
    leaves = {path[-1].key: leaf for path, leaf in
              jax.tree_util.tree_flatten_with_path(
                  engine.cache_manager.cache)[0]}
    assert leaves["cached_key"].shape == (3 * window + 2 * full, PAGE, 32)
    assert "conv_state" not in leaves     # no convolution layer, no tail


def test_the_engine_serves_the_references_tokens_and_says_what_it_did(
        variables):
    engine = engine_of(build(), variables)
    ids = [engine.submit(row[:40], max_length=8) for row in TOKENS]
    results = engine.drain()
    for i, row in zip(ids, TOKENS):
        got = np.asarray(results[i].tokens)
        tokens = np.concatenate([row[:40], got])
        want = np.asarray(reference(variables["params"], tokens[:-1]))
        assert (want[39:].argmax(-1) == got).all()
    spans = get_recorder().spans()
    ticks = [s.attrs for s in spans if s.name == "serving.decode"][-3:]
    # 3 lanes x 3 experts a token x 4 expert layers, whoever holds them
    assert all(t["pairs"] == 36 and 0 < t["window_rows"] <= t["full_rows"]
               for t in ticks)
    chunks = [s.attrs for s in spans if s.name == "serving.prefill_chunk"]
    assert [c["pairs"] for c in chunks[-3:]] == [16 * 12, 16 * 12, 8 * 12]
    snapshot = engine.metrics.snapshot()
    assert snapshot["moe_layers"] == 4
    assert 0 < snapshot["moe_tick_pairs"] < snapshot["moe_pairs_routed"]
    assert snapshot["window_pages_recycled"] > 0
    assert snapshot["pages_in_use_window"] == 0
    assert snapshot["admits_refused_window"] == 0
    for kwargs in ({"prefix_cache": True}, {"spec": True},
                   {"kv_dtype": "int8"}, {"role": "prefill"}):
        with pytest.raises((ValueError, NotImplementedError)):
            engine_of(build(), variables, **kwargs)


def test_the_parts_all_the_shares_give_add_up_to_the_uncut_layer():
    """16 routed experts over FOUR programs of 4 each: every program routes
    over all 16, computes the part its own 4 give and adds the shared
    expert. Their sum, the shared expert counted once, is what the uncut
    reference gives for the whole layer (every expert held)."""
    shares, held = 4, 4
    cfg = GPTConfig(**{**SIZES, "first_expert_held": 0})
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64))
    whole = jax.random.normal(jax.random.PRNGKey(4), (3, 16, 64, 32)) * 0.2
    v = flax.core.meta.unbox(moe_share.SharedMoEMLP(cfg).init(
        jax.random.PRNGKey(5), x))["params"]
    v = {**v, "router": {"kernel": v["router"]["kernel"] * 20.0},
         "expert_bias": v["expert_bias"] * 4.0}
    total = jnp.zeros_like(x)
    for i in range(shares):
        layer = moe_share.SharedMoEMLP(dataclasses.replace(
            cfg, first_expert_held=i * held))
        mine = {**v, "w_gate": whole[0, i * held:(i + 1) * held],
                "w_up": whole[1, i * held:(i + 1) * held],
                "w_down": whole[2, i * held:(i + 1) * held].swapaxes(1, 2)}
        total = total + layer.apply({"params": mine}, x)
    shared = moe_share._shared_expert(
        x[0], v["shared_gate"], v["shared_up"], v["shared_down"])
    uncut = {"router": {"kernel": v["router"]["kernel"][None]},
             "expert_bias": v["expert_bias"][None],
             "w_gate": whole[0][None], "w_up": whole[1][None],
             "w_down": whole[2].swapaxes(1, 2)[None],
             **{k: v[k][None] for k in ("shared_gate", "shared_up",
                                        "shared_down")}}
    with jax.default_matmul_precision("highest"):
        want, chosen, _, _ = trinity_f32._experts(
            x[0], uncut, 0, dict(trinity_f32._settings(MODEL), first=0))
    got = total[0] - (shares - 1) * shared
    assert np.abs(np.asarray(got - want)).max() <= 1e-5 * float(
        np.abs(want).max())
    # and every share saw some pairs: the choice spreads over all 16
    assert len(np.unique(np.asarray(chosen) // held)) == shares


# --------------------------------------------- each wrong system shows

CONFIGURED = {
    "qk_norm_left_out": {"qk_norm": False, "qk_norm_scope": "projection"},
    "full_layers_rotated": {"rope_layout": (1,) * 5},
    "window_layers_unrotated": {"rope_layout": (0,) * 5},
    "window_one_short": {"sliding_window": WINDOW - 1},
    "window_one_long": {"sliding_window": WINDOW + 1},
    "post_norms_left_out": {"sandwich_norm": False},
    "embedding_unscaled": {"embedding_multiplier": 1.0},
    "route_scale_left_out": {"routed_scaling_factor": 1.0},
    "another_share_held": {"first_expert_held": 8},
}


@pytest.mark.parametrize("fault", sorted(CONFIGURED))
def test_a_wrong_configuration_stands_far_from_the_reference(variables, fault):
    wrong = build(**CONFIGURED[fault])
    mine = full_forward(wrong, without(variables["params"], wrong),
                        TOKENS[:1])
    assert distance(mine[0], reference(variables["params"], TOKENS[0])
                    ) >= 30 * TOL


@pytest.mark.parametrize("fault", [
    "gate_left_out", "gate_from_unnormed_input", "bias_in_the_weights",
    "unheld_pair_computed", "shared_expert_twice"])
def test_a_fault_planted_in_a_seam_stands_far_from_the_reference(
        variables, fault):
    from perfbench import probe_trinity

    with probe_trinity.planted(fault):
        mine = full_forward(build(), variables["params"], TOKENS[:1])
    assert distance(mine[0], reference(variables["params"], TOKENS[0])
                    ) >= 30 * TOL


def test_exchanged_norm_weights_stand_far_from_the_reference(variables):
    from perfbench import probe_trinity

    mine = full_forward(build(), probe_trinity.norms_exchanged(
        variables["params"]), TOKENS[:1])
    assert distance(mine[0], reference(variables["params"], TOKENS[0])
                    ) >= 30 * TOL


# ------------------------------------------------- the fields, by name

def test_window_layers_and_a_mixed_rotation_are_taken_with_layer_types():
    """What ``check`` used to refuse for every ``layer_types`` stack: a
    window, and rotating some layers and not others. The window layers may
    be named in ``layer_types`` alone."""
    cfg = GPTConfig(**{**SIZES, "sliding_window_layout": None})
    assert cfg.window_layers == LAYOUT and cfg.rope_layers == LAYOUT
    assert cfg.state_kinds == ("kv",) and cfg.expert_share
    assert cfg.of_attention_layers(cfg.window_layers) == LAYOUT
    full_named = GPTConfig(**{**SIZES, "layer_types": ("full_attention",) * 5})
    assert full_named.window_layers == LAYOUT      # the layout alone says it
    mixed = GPTConfig(**{**SIZES, "rope_layout": (0, 1, 0, 1, 1)})
    assert mixed.rope_layers == (0, 1, 0, 1, 1)


@pytest.mark.parametrize("changes, exc, word", [
    ({"attention_gate": "tanh"}, ValueError, "attention_gate"),
    ({"sliding_window": None, "sliding_window_layout": None}, ValueError,
     "sliding_attention"),
    ({"sliding_window_layout": (1, 1, 1, 1, 0)}, ValueError,
     "sliding_window_layout"),
    ({"layer_types": ("conv",) + TYPES[1:],
      "sliding_window_layout": (0,) + LAYOUT[1:]}, NotImplementedError,
     "sliding_window"),
    ({"layer_types": None, "num_dense_layers": 0,
      "dense_ffn_hidden_size": None, "gate": "softmax_topk",
      "use_expert_bias": False, "expert_bias_init_std": 0.0,
      "num_routed_experts": None, "num_shared_experts": 0,
      "first_expert_held": 0, "qk_norm": False,
      "qk_norm_scope": "projection"}, NotImplementedError,
     "attention_gate"),
])
def test_the_new_fields_are_checked_by_name(changes, exc, word):
    with pytest.raises(exc, match=word):
        GPTConfig(**{**SIZES, **changes})


def test_the_configuration_zoo_has_the_family():
    import os

    from fleetx_tpu.utils.config import get_config

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    model = get_config(os.path.join(
        root, "configs/nlp/trinity/serve_trinity_large_ep8_l5.yaml"),
        nranks=1, overrides=["Distributed.dp_degree=1"]).Model
    cfg = GPTConfig.from_model_config(model)
    assert cfg.family == "trinity" and cfg.experts_held == (0, 32)
    assert cfg.window_layers == (1, 1, 1, 1, 0) == cfg.rope_layers
    assert cfg.embedding_multiplier == pytest.approx(3072 ** 0.5)


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


def test_a_gated_chunks_attention_compiled_for_the_v5e_holds_the_kernel(
        one_chip, monkeypatch):
    """One layer's attention of the cell's 2,048-row chunk program at the
    published widths (48 heads over 8 of 128, a window of 4,096, the gate's
    rows behind the queries'), both kinds of layer behind the layer's own
    conditional, one lane's 3,200 pages gathered from the cell's pool of two
    classes: it holds ``fleetx_prefill_gqa`` once a kind and no float32
    scores of 48 heads over the lane's or the window's rows."""
    from fleetx_tpu.models.gpt import hybrid
    from fleetx_tpu.ops.pallas import prefill_gqa
    from perfbench import harness

    monkeypatch.setattr(prefill_gqa, "_interpret", lambda: False)
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    cfg = dataclasses.replace(
        GPTConfig.from_model_config(dict(harness.load_json(
            "perfbench/configs/trinity-large-ep8-l5.json")["model"])),
        dtype=jnp.bfloat16, use_flash_attention=True, decode_cache_len=51200,
        decode_page_size=16, decode_num_pages=8 * 3200 + 1,
        decode_window_pages=8 * 385 + 1)
    rows = 2048

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer = hybrid.HybridSelfAttention(cfg)
    out_proj = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8, 3072), jnp.bfloat16),
        layer_index=0))["params"]["out_proj"]

    def chunk(out_proj, q, k_pool, v_pool, tables, start, index):
        return layer.apply(
            {"params": {"out_proj": out_proj},
             "cache": {"cached_key": k_pool, "cached_value": v_pool,
                       "cache_index": jnp.int32(0)}},
            q, decode=True, cache_positions=start, block_tables=tables,
            layer_index=index, phase="attend", mutable=["cache"])[0]

    pool = (hybrid.total_pages(cfg), 16, 1024)
    assert pool[0] == (8 * 3200 + 1) + 4 * (8 * 385 + 1)
    text = jax.jit(chunk).lower(
        jax.tree.map(lambda x: spec(x.shape, x.dtype), out_proj),
        spec((1, rows, 96, 128)), spec(pool), spec(pool),
        spec((2, 1, 3200), jnp.int32), spec((1,), jnp.int32),
        spec((), jnp.int32)).compile().as_text()
    assert text.count(prefill_gqa.KERNEL_NAME) >= 2   # one call a kind
    assert f"f32[1,8,6,{rows}," not in text and f"f32[48,{rows}," not in text
