"""LongCat-Flash's double layer on the CPU at a small size, seeded random
weights: the shortcut-connected stack (two latent attentions, two dense MLPs,
one expert layer that leaves at the first half and lands after the second)
served through the page pool and the engine, against the plain float32
reference (``perfbench/reference/longcat_f32.py``), logits and not tokens;
the softmax gate whose bias moves a choice and never a weight; zero-compute
experts, which take no row of the grouped matmuls; an expert layer whose
shares ADD UP to the uncut layer with the zero-compute experts counted once;
the counters of the ``moe_stats`` leaf; what the family refuses at
construction, by the field's name; and that the stacks WITHOUT the shortcut
trace the programs they traced before it."""

import dataclasses
import hashlib

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import traced_apply

from fleetx_tpu.models.gpt import mixed_stack
from fleetx_tpu.models.gpt.generation import (GenerationConfig,
                                              init_decode_cache)
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.parallel import moe_share
from perfbench import harness
from perfbench.reference import longcat_f32

SIZES = dict(harness.with_tiny(harness.load_json(
    "perfbench", "configs", "longcat-flash-ep32-l4.json"), True)["model"],
    vocab_size=128, max_position_embeddings=512, dtype="float32",
    use_flash_attention=False)
TOL = 2e-5  # float32 against float32: 1.8e-7 read, of logits up to 0.6


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
    return np.random.default_rng(0).integers(1, 128, 48, dtype=np.int32)


@pytest.fixture(scope="module")
def reference(built, tokens):
    return np.asarray(longcat_f32.configured(SIZES)(built[1]["params"], tokens))


def paged(model, pages=13, page=8, cache_len=96):
    return model.clone(cfg=dataclasses.replace(
        model.cfg, decode_cache_len=cache_len, decode_num_pages=pages,
        decode_page_size=page))


@pytest.fixture(scope="module")
def forward(built):
    """ONE jitted cached forward of the paged model (a program a shape)."""
    served = paged(built[0])

    @jax.jit
    def call(params, cache, ids, at, tables, rows):
        pos = at[:, None] + jnp.arange(ids.shape[1])[None]
        logits, mut = served.apply(
            {"params": params, "cache": cache}, ids, pos, rows, decode=True,
            cache_positions=at, block_tables=tables, mutable=["cache"])
        return logits, mut["cache"]

    return served, call


# ------------------------------------------------- the stack and the reference

def test_the_plan_gives_every_half_its_places():
    cfg = GPTConfig.from_model_config(SIZES)
    plan = mixed_stack.layer_plan(cfg)
    assert plan["attention"].tolist() == [1, 1, 1, 1]
    assert plan["operator_index"].tolist() == [0, 1, 2, 3]
    assert plan["ffn_index"].tolist() == [0, 1, 2, 3]      # a dense MLP a half
    assert plan["experts"].tolist() == [1, 0, 1, 0]        # leaves at even halves
    assert plan["shortcut_index"].tolist() == [0, 0, 1, 1]  # the experts' own stack
    assert plan["counts"] == {"conv": 0, "mamba": 0, "kda": 0, "attention": 4,
                              "dense": 4, "experts": 2}
    assert cfg.expert_layers == 2 and cfg.router_width == 12
    assert cfg.span_pairs(5) == {"pairs": 5 * 3 * 2}
    assert np.allclose(cfg.mla_scales, ((64 / 24) ** 0.5, 2 ** 0.5))


def test_the_experts_stack_holds_no_norm_and_the_router_every_output(built):
    layers = built[1]["params"]["gpt"]["layers"]
    assert set(layers) == {"attention", "dense", "experts"}
    assert set(layers["experts"]) == {"op"}          # it reads the dense MLP's
    assert set(layers["dense"]) == {"norm", "op"}
    op = layers["experts"]["op"]
    assert op["router"]["kernel"].shape == (2, 64, 12)   # 8 routed + 4 zero
    assert op["expert_bias"].shape == (2, 12)
    assert op["w_gate"].shape == (2, 2, 64, 32)          # 2 held, no zero's
    assert layers["dense"]["op"]["gate_proj"]["kernel"].shape == (4, 64, 96)


def test_the_plain_forward_is_the_reference(built, tokens, reference):
    model, variables = built
    plain = traced_apply(model, variables, jnp.asarray(tokens[None]))[0]
    assert np.abs(np.asarray(plain) - reference).max() < TOL


@pytest.mark.parametrize("chunks", [(32,), (8, 24)])
def test_chunked_prefill_then_ticks_through_the_pool_are_the_reference(
        built, forward, tokens, reference, chunks):
    """A chunk attends over the scaled latents read back from the pool and
    its own; a tick of two lanes, one idle, takes the absorbed form: both are
    the reference's full forward, LOGITS and not tokens."""
    served, call = forward
    params = built[1]["params"]
    cache = init_decode_cache(served, 2)
    table = jnp.arange(1, 13, dtype=jnp.int32)[None]
    out, at = [], 0
    for n in chunks:
        logits, cache = call(params, cache, jnp.asarray(tokens[None, at:at + n]),
                             jnp.asarray([at]), table, None)
        out.append(logits[0])
        at += n
    tables = jnp.concatenate([table, jnp.zeros_like(table)])
    for i in range(at, 48):
        logits, cache = call(
            params, cache, jnp.asarray([[tokens[i]], [0]]),
            jnp.asarray([i, 95]), tables, jnp.asarray([[True], [False]]))
        out.append(logits[0])
    assert np.abs(np.asarray(jnp.concatenate(out)) - reference).max() < TOL


def test_the_pool_holds_the_latent_as_scaled_and_the_key_unscaled(
        built, forward, tokens):
    served, call = forward
    params = built[1]["params"]
    cache = init_decode_cache(served, 1)
    table = jnp.arange(1, 13, dtype=jnp.int32)[None]
    _, cache = call(params, cache, jnp.asarray(tokens[None, :32]),
                    jnp.asarray([0]), table, None)
    _, latents = longcat_f32.configured(SIZES)(
        params, tokens[:32], with_latents=True)
    pools = {p[-1].key: leaf for p, leaf in
             jax.tree_util.tree_flatten_with_path(cache)[0]}
    assert pools["moe_stats"].shape == (2, 24 + moe_share.ZERO_WORDS)
    for half in range(4):        # a half's pages follow the last's (13 each)
        rows = np.arange(32)
        page = 1 + rows // 8 + half * 13
        held = np.concatenate([
            np.asarray(pools["cached_key"])[page, rows % 8],
            np.asarray(pools["cached_value"])[page, rows % 8][:, :8]], -1)
        assert np.abs(held - np.asarray(latents[half])).max() < TOL
    # the scale is there: the normed latent's rms is s_kv, not 1
    rms = np.sqrt((np.asarray(latents)[..., :32] ** 2).mean())
    assert 0.7 * 2 ** 0.5 < rms < 1.3 * 2 ** 0.5


def test_the_engine_serves_it_and_counts_the_zero_pairs(built, tokens):
    """Through ``ServingEngine`` (scheduler, latent page pool, chunked
    prefill): greedy tokens are the reference's best, and ``snapshot()``
    alone fetches the zero-compute counters."""
    from fleetx_tpu.serving import ServingEngine

    model, variables = built
    engine = ServingEngine(
        model, variables, slots=2, cache_len=96, page_size=8, num_pages=25,
        gen_cfg=GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                                 pad_token_id=0, max_length=6),
        prefill_chunk=16, prefill_bucket=8, prefix_cache=False)
    ids = [engine.submit(tokens[:n], max_length=6) for n in (21, 9)]
    results = engine.drain()
    for rid, n in zip(ids, (21, 9)):
        got = np.asarray(results[rid].tokens)
        full = np.concatenate([tokens[:n], got])
        ref = np.asarray(longcat_f32.configured(SIZES)(
            variables["params"], full))
        at = ref[n - 1:n - 1 + len(got)]
        assert (at.max(-1) - at[np.arange(len(got)), got]).max() < TOL
    counters = engine.metrics.snapshot()
    assert counters["moe_layers"] == 2
    assert counters["moe_tick_zero_pairs"] > 0
    assert counters["moe_prefill_zero_pairs"] > 0
    pairs = counters["moe_tick_layer_calls"] * 2 * 3     # 2 lanes, top 3
    assert counters["moe_tick_zero_pairs"] < pairs
    assert (0 <= counters["moe_tick_routed_pairs_min"]
            <= counters["moe_tick_routed_pairs_max"] <= 3)


# ----------------------------------------------------- the gate and the shares

def layer_of(cfg, x, params=None, **kwargs):
    layer = moe_share.SharedMoEMLP(cfg)
    if params is None:
        params = flax.core.meta.unbox(
            jax.jit(layer.init)(jax.random.PRNGKey(1), x))["params"]
    out, mut = traced_apply(layer, {"params": params}, x,
                            mutable=["routing"], **kwargs)
    return out, params, {k: v[0] for k, v in mut["routing"].items()}


def test_the_bias_moves_a_choice_and_never_a_weight():
    cfg = GPTConfig.from_model_config({**SIZES, "num_experts": 8,
                                       "first_expert_held": 0})
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 21, 64), jnp.float32)
    _, params, plain = layer_of(cfg, x)
    scores = np.asarray(jax.nn.softmax(
        x[0] @ params["router"]["kernel"], -1))
    # a bias that lifts expert 7 above everything: every token now chooses it
    lifted = {**params, "expert_bias": params["expert_bias"].at[7].set(1.0)}
    _, _, biased = layer_of(cfg, x, lifted)
    chose = np.asarray(biased["experts"][0])
    assert (chose == 7).any(-1).all() and not (
        np.asarray(plain["experts"][0]) == 7).any(-1).all()
    # ... and weighs it by its RAW score x 6: no bias, no renormalisation
    want = 6.0 * np.take_along_axis(scores, chose, -1)
    assert np.abs(np.asarray(biased["weights"][0]) / want - 1).max() < 1e-5
    assert not np.allclose(np.asarray(biased["weights"][0]).sum(-1), 6.0)
    # softmax scores over ALL 12 outputs, the zero-compute ones among them
    assert np.abs(scores.sum(-1) - 1).max() < 1e-6 and (chose >= 8).any()


def test_a_zero_pair_takes_no_row_and_gives_its_input_weighed():
    idx = jnp.asarray([[2, 9, 3], [11, 8, 10], [1, 2, 3]])
    dest, src, sizes, tile_expert, num_tiles, held = (
        moe_share.held_row_layout(idx, 2, 2, 1))     # holds 2-3 of 8 + 4 zero
    assert held.tolist() == [[True, False, True], [False] * 3,
                             [False, True, True]]
    assert sizes.tolist() == [2, 2] and int(num_tiles) == 4
    assert int((dest < 9).sum()) == 4          # the zero pairs lie past the rows
    *_, num_tiles, _ = moe_share.held_row_layout(idx, 2, 2, 16)
    assert int(num_tiles) == 2                 # no tile for a zero pair
    # a token that chooses zero-compute experts alone gets (sum w) * x back
    cfg = GPTConfig.from_model_config(SIZES)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (1, 5, 64)))
    _, params, _ = layer_of(cfg, x)
    router = np.zeros((64, 12), np.float32)
    router[:, 8:11] = 1.0        # every token to zero experts 8-10
    params = {**params, "router": {"kernel": jnp.asarray(router)},
              "expert_bias": jnp.zeros(12)}
    out, _, sown = layer_of(cfg, x, params)
    assert (np.asarray(sown["experts"]) >= 8).all()
    by = np.asarray(sown["weights"][0]).sum(-1, keepdims=True)
    assert np.abs(np.asarray(out[0]) - by * np.asarray(x[0])).max() < 1e-5


def test_the_shares_add_up_with_the_zero_experts_counted_once():
    """4 shares of 2 of 8 routed experts: the routed parts of all the shares,
    with the zero-compute experts counted ONCE, are the uncut layer, which is
    the plain reference's; no share computes anything for an expert it
    lacks."""
    base = {**SIZES, "num_routed_experts": 8, "num_zero_experts": 4}
    whole_cfg = GPTConfig.from_model_config(
        {**base, "num_experts": 8, "first_expert_held": 0})
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 21, 64), jnp.float32)
    whole, params, sown = layer_of(whole_cfg, x)
    zero = np.asarray(sown["experts"][0]) >= 8
    assert 0 < zero.mean() < 1
    zero_once = (np.where(zero, np.asarray(sown["weights"][0]), 0.0).sum(
        -1, keepdims=True) * np.asarray(x[0]))
    total = -3.0 * zero_once         # every share computes them: once is kept
    for first in (0, 2, 4, 6):
        cfg = GPTConfig.from_model_config(
            {**base, "num_experts": 2, "first_expert_held": first})
        share = {**params, **{k: params[k][first:first + 2]
                              for k in ("w_gate", "w_up", "w_down")}}
        part, _, theirs = layer_of(cfg, x, share)
        assert np.array_equal(theirs["experts"], sown["experts"])
        total = total + np.asarray(part[0])
    assert np.abs(total - np.asarray(whole[0])).max() < 2e-6
    # against the plain reference's layer, uncut
    stack = jax.tree.map(lambda leaf: leaf[None], params)
    settings = longcat_f32._settings({**base, "first_expert_held": 0})
    want, chosen, _, _ = longcat_f32._experts(x[0], stack, 0, settings)
    assert np.abs(np.asarray(whole[0]) - np.asarray(want)).max() < 2e-6
    assert np.array_equal(np.sort(np.asarray(chosen), -1),
                          np.sort(np.asarray(sown["experts"][0]), -1))


def test_the_counters_count_zero_pairs_and_what_a_token_costs():
    cfg = GPTConfig.from_model_config(SIZES)
    layer = moe_share.SharedMoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (6, 1, 64), jnp.float32)
    params = flax.core.meta.unbox(jax.jit(layer.init)(
        jax.random.PRNGKey(1), x))["params"]
    stats = jnp.zeros((3, moe_share.stats_words(cfg)), jnp.uint32)
    _, mut = traced_apply(
        layer, {"params": params, "cache": {"moe_stats": stats}}, x,
        decode=True, layer_index=jnp.int32(1), mutable=["cache", "routing"])
    chose = np.asarray(mut["routing"]["experts"][0]).reshape(6, 3)
    words = np.asarray(mut["cache"]["moe_stats"])
    assert not words[[0, 2]].any()
    zero = (chose >= 8).sum(-1)
    base = moe_share.stats_words(cfg) - moe_share.ZERO_WORDS
    assert words[1, base] == zero.sum() and words[1, base + 2] == 0  # a tick's
    assert (words[1, base + 4] == 3 - zero.min()
            and words[1, base + 5] == zero.max())
    counters = moe_share.zero_counters(words[:, base:], 3)
    assert counters == {"moe_tick_zero_pairs": int(zero.sum()),
                        "moe_prefill_zero_pairs": 0,
                        "moe_tick_routed_pairs_max": int(3 - zero.min()),
                        "moe_tick_routed_pairs_min": int(3 - zero.max())}
    # a longer call is a prefill's: its pairs alone, no maximum
    _, mut = traced_apply(
        layer, {"params": params, "cache": {"moe_stats": stats}},
        x.reshape(1, 6, 64), decode=True, layer_index=jnp.int32(0),
        mutable=["cache"])
    words = np.asarray(mut["cache"]["moe_stats"])
    assert (words[0, base + 2] == zero.sum()
            and not words[0, [base, base + 4, base + 5]].any())


# ------------------------------------------------- refused, by the field's name

@pytest.mark.parametrize("over, match", [
    ({"layer_types": ["latent_attention", "conv"] * 2}, "moe_shortcut"),
    ({"sliding_window": 16}, "moe_shortcut"),
    ({"num_layers": 3, "layer_types": ["latent_attention"] * 3},
     "moe_shortcut"),
    ({"num_dense_layers": 1}, "moe_shortcut"),
    ({"dense_ffn_hidden_size": None}, "moe_shortcut"),
    ({"gate": "sigmoid_topk"}, "num_zero_experts"),
    ({"num_zero_experts": -1}, "num_zero_experts"),
    ({"n_group": 2, "topk_group": 1}, "n_group"),
    ({"top_k": 13}, "top_k"),
    ({"gate": "softmax_topk", "num_zero_experts": 0, "use_expert_bias": False,
      "expert_bias_init_std": 0.0, "top_k": 2}, "num_routed_experts"),
    ({"index_n_heads": 2, "index_head_dim": 16, "index_topk": 4},
     "mla_scale_q_lora"),
])
def test_the_configuration_refuses_what_nobody_wrote(over, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        GPTConfig.from_model_config({**SIZES, **over})


@pytest.mark.parametrize("over, match", [
    ({"moe_shortcut": True}, "moe_shortcut"),
    ({"mla_scale_kv_lora": True}, "mla_scale_kv_lora"),
    ({"num_experts": 4, "num_zero_experts": 2, "gate": "softmax_topk"},
     "num_zero_experts"),
    ({"num_experts": 4, "gate": "softmax_bias_topk"}, "softmax_bias_topk"),
    ({"num_experts": 4, "gate": "softmax_topk", "use_expert_bias": True},
     "use_expert_bias"),
])
def test_the_new_fields_without_their_stack_are_refused(over, match):
    plain = dict(vocab_size=128, hidden_size=64, num_layers=2,
                 num_attention_heads=4, max_position_embeddings=64)
    with pytest.raises((ValueError, NotImplementedError), match=match):
        GPTConfig.from_model_config({**plain, **over})


def test_no_shared_expert_and_no_leading_dense_layer_under_a_share(built):
    cfg = built[0].cfg
    assert cfg.expert_share and cfg.num_shared_experts == 0
    assert cfg.num_dense_layers == 0
    op = built[1]["params"]["gpt"]["layers"]["experts"]["op"]
    assert not [k for k in op if k.startswith("shared_")]


# ------------------------------------------------ what stays as it is today

# sha256 of ``str(jax.make_jaxpr(cached forward))`` of each configuration's
# tiny stack (a 16-row chunk and a 3-lane tick through a page pool), taken
# on the commit BEFORE the shortcut (1581b9b) with this file's own function:
# the stacks without ``moe_shortcut`` trace the programs they traced then,
# instruction for instruction. A PR that changes ``mixed_stack.py``'s body
# for them on purpose takes the digests anew (``python
# tests/test_longcat_serving.py`` prints them). Taken anew at PR 60, whose
# two more counts a kind (``moe.MOE_STATS``: the tiles walked and laid)
# widen the ``moe_stats`` leaf from 16 words to 24 and add two terms to the
# sum ``_count`` makes: nothing else differs from the texts of the parent.
# DeepSeek-V3.2's TICK taken anew at PR 65, whose ``indexer.top_rows`` lays
# the rows of ``select_rows``' search out densely where it ran ``lax.top_k``
# and a sort (its chunk's text did not move: ``select_rows`` now shares its
# keys and its search with the tick through two helpers and traces what it
# traced; every other stack here has no indexer).
UNCHANGED = {
    "perfbench/configs/lfm2-8b-a1b-l14.json": (
        "1ec650880ab8da35", "1fc7bbf953fae765"),
    "perfbench/configs/trinity-large-ep8-l5.json": (
        "70c1b453d98ead52", "87f001db3f5070e7"),
    "perfbench/configs/axk1-ep16-l6.json": (
        "48bcd44c0b0d768d", "56e2215bb59c6dbf"),
    "perfbench/configs/dsv32-ep16-l5.json": (
        "74ba8f253a84bd04", "7810a41bf2df7f73"),
}


def traced_programs(path):
    """``(chunk, tick)``: the jaxprs, as text, of the cached forward of the
    configuration's tiny model over a page pool, and the carries of its
    layer scan."""
    data = harness.with_tiny(harness.load_json(path), True)
    cfg = GPTConfig.from_model_config(
        {**data["model"], "dtype": "float32", "use_flash_attention": False})
    model = paged(GPTForPretraining(cfg), pages=13, page=8, cache_len=96)
    if cfg.sliding_window:  # (two classes of page: the engine sets both)
        model = model.clone(cfg=dataclasses.replace(
            model.cfg, decode_window_pages=13))
    ids = np.zeros((1, 8), np.int32)
    variables = jax.eval_shape(lambda: flax.core.meta.unbox(model.init(
        jax.random.PRNGKey(0), ids)))
    classes = 2 if model.cfg.sliding_window else 1
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
            jnp.ones((lanes, rows), bool))))
    return out


def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("path", sorted(UNCHANGED))
def test_a_stack_without_the_shortcut_traces_the_program_it_traced_before(
        path):
    texts = traced_programs(path)
    assert tuple(digest(t) for t in texts) == UNCHANGED[path]
    for text in texts:       # and nothing of the shortcut is in it by name
        assert "moe_shortcut" not in text and "moe_zero" not in text


def test_the_shortcut_is_one_more_carry_of_the_one_scan(built):
    """One ``lax.scan`` over the halves, whose carry holds the stream, the
    pools and, here alone, the shortcut ``[b, s, h]``."""
    served = paged(built[0])
    cache = jax.eval_shape(lambda: init_decode_cache(served, 3))

    def call(params, cache):
        return served.apply(
            {"params": params, "cache": cache}, jnp.zeros((3, 1), jnp.int32),
            jnp.zeros((3, 1), jnp.int32), jnp.ones((3, 1), bool), decode=True,
            cache_positions=jnp.zeros((3,), jnp.int32),
            block_tables=jnp.zeros((3, 12), jnp.int32), mutable=["cache"])

    jaxpr = jax.make_jaxpr(call)(built[1]["params"], cache)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 4
    carried = [v.aval.shape for v in
               scans[0].outvars[:scans[0].params["num_carry"]]]
    # the stream and the shortcut, then the two latent leaves and the counters
    assert carried.count((3, 1, 64)) == 2 and len(carried) == 5


if __name__ == "__main__":
    for config in sorted(UNCHANGED):
        print(config, tuple(digest(t) for t in traced_programs(config)))
