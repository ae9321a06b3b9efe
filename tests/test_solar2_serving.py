"""Solar-Open2's stack (KDA delta-rule linear attention layers, whose matrix
state ``[heads, d, d]`` float32 is held once a lane outside the engine's page
pool, beside gated position-free GQA layers, over an expert layer that holds
a share) through the normal path, against the plain float32 reference
``perfbench/reference/solar2_f32.py`` at a tiny size on seeded weights: the
full forward; a one-shot prefill; a prefill IN CHUNKS (a boundary inside a
run of tokens, a padded last bucket); decoding through the lane's state; the
state and the filter rows a lane holds after them; a decay so strong that a
chunk's summed log decay passes -100; ``beta`` in (1, 2) really applied; a
lane recycled mid-run and a lane that is not decoding; the two kernels in
interpret mode against their plain twins; the shares of the expert layer
adding up to the uncut layer; the published configuration's parameter count
against the program's own tree; what the configuration and the engine
refuse; and that the stacks WITHOUT the new kind trace the programs they
traced before it. Logits are compared, not tokens.

TOLERANCE. These tests compute in float32 on the CPU, where system and
reference differ only in the order of their sums: some 1e-5 of the
reference's logit deviation is read, and the limit is 2e-4. The bfloat16
limits of the chip are the benchmark driver's
(``perfbench/drivers/serve_closed_loop_kda.py``).
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
from fleetx_tpu.ops.pallas import kda
from fleetx_tpu.parallel import moe_share
from fleetx_tpu.serving import ServingEngine
from perfbench import harness
from perfbench.drivers.serve_closed_loop_kda import Served, lane_state
from perfbench.reference import solar2_f32

TOL = 2e-4          # of the reference's logit standard deviation (docstring)
PAGE, CACHE_LEN, CHUNK, BUCKET = 8, 128, 16, 8
CONFIG = harness.load_json("perfbench", "configs", "solar-open2-ep16-l8.json")
MODEL = dict(harness.with_tiny(CONFIG, True)["model"], vocab_size=256,
             max_position_embeddings=512)
SIZES = dict(MODEL, use_flash_attention=False, dtype="float32")
reference = computed_once(solar2_f32.configured(MODEL))
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
        return x * 2.0 if "layers" in name and "kernel']" in name else x

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
    """ONE engine for the module (the lane-level tests claim and free its
    lanes; the request-level test runs last on it)."""
    return engine_of(build(), variables)


@pytest.fixture(scope="module")
def served(engine):
    """The check's own programs over that engine, compiled once."""
    return Served(engine)


# ------------------------------------------------- the stack and the reference

def test_full_forward_matches_the_reference(variables):
    logits = traced_apply(build(), variables, TOKENS[:1])
    assert distance(logits[0], reference(variables["params"], TOKENS[0])) < TOL


def test_the_tree_is_stacked_by_kind_with_the_operators_own_leaves(variables):
    layers = variables["params"]["gpt"]["layers"]
    assert sorted(layers) == ["attention", "experts", "kda"]
    op = layers["kda"]["op"]
    assert op["qkv_proj"]["kernel"].shape == (6, 64, 3 * 64)
    assert op["conv_kernel"].shape == (6, 3 * 64, 4)
    assert op["A_log"].shape == (6, 4) and op["o_norm"]["scale"].shape == (6, 16)
    assert op["f_b"]["bias"].shape == op["g_b"]["bias"].shape == (6, 64)
    assert "gate_proj" in layers["attention"]["op"]      # the GQA gate


def test_the_published_parameter_count_is_the_programs_own():
    """The configuration's ``parameters`` (ISSUE 56's count: 3,898,842,752)
    against the program's tree at the published widths, abstractly."""
    model = GPTForPretraining(GPTConfig.from_model_config(
        {**CONFIG["model"], "dtype": "bfloat16"}))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32)))["params"]
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == CONFIG["parameters"] == 3_898_842_752
    cache = jax.eval_shape(lambda: model.clone(cfg=dataclasses.replace(
        model.cfg, decode_cache_len=64, decode_num_pages=5,
        decode_page_size=16)).init(
            jax.random.PRNGKey(0), np.zeros((2, 1), np.int32), decode=True,
            cache_positions=np.zeros((2,), np.int32),
            block_tables=np.zeros((2, 5), np.int32)))["cache"]["gpt"]["layers"]
    # a lane's state in one KDA layer: 4,194,304 B + 147,456 B
    assert cache["kda_state"].shape == (6, 2, 128, 64, 128)
    assert cache["kda_conv"].shape == (6, 2, 3 * 3 * 8192)


# -------------------------------------- prefill and decode through the state

def test_chunked_prefill_then_ticks_are_the_reference(engine, served,
                                                      variables, prompt=44):
    """A prompt in the ENGINE'S chunk programs (16, 16, then 12 in 16 rows:
    chunk boundaries inside a run of tokens, a padded last bucket), then
    ticks over every lane through the lane's state: the logits of the last
    call's rows and of every tick, the state ``S`` and the filter rows after
    the prefill and after the last tick, the keys and values cached."""
    tokens = TOKENS[0][:prompt + 8]
    mine = served.sequence_parts(tokens, prompt)
    own = mine["own"]
    theirs = reference(variables["params"], tokens, tail=own + 8,
                       with_parts=True, states_at=(prompt, prompt + 8))
    assert distance(mine["logits"], theirs["logits"]) < TOL
    assert distance(mine["kv"], theirs["kv"]) < TOL
    for i, key in enumerate(("state_prefill", "state_end")):
        assert distance(mine[key][0], theirs["state"][:, i]) < TOL
        assert distance(mine[key][1], theirs["rows"][:, i]) < TOL
    # beta in (1, 2) occurs, and the rule alone on the rows it saw agrees
    assert 1.0 < mine["kda_beta"].max() < 2.0
    engine.cache_manager.pool.check_invariants()
    assert engine.cache_manager.pages_in_use == 0


def test_the_negative_eigenvalue_is_really_applied(variables):
    """``kda_neg_eigval`` off halves ``beta``: the logits then stand far
    from the reference's (which doubles it)."""
    logits = traced_apply(build(kda_neg_eigval=False), variables,
                          TOKENS[:1])
    assert distance(logits[0], reference(variables["params"], TOKENS[0])) > 0.05


def test_a_strong_decay_stays_finite_and_is_the_reference(variables):
    """``A_log`` and ``dt_bias`` moved until a chunk's summed log decay
    passes -100 (every row's is under -40 in every channel; a form that
    multiplies by ``exp(-cumsum g)`` overflows float32 at -88): finite, and
    equal to the reference. The kernels meet such a decay in
    ``test_the_chunk_kernel_is_its_plain_twin``."""
    def strong(path, x):
        name = jax.tree_util.keystr(path)
        if "A_log" in name:
            return jnp.full_like(x, np.log(16.0))
        return jnp.full_like(x, 3.0) if "f_b']['bias" in name else x

    held = jax.tree_util.tree_map_with_path(strong, variables)
    logits = traced_apply(build(), held, TOKENS[1:])[0]
    assert np.isfinite(np.asarray(logits)).all()
    theirs = reference(held["params"], TOKENS[1], with_parts=True,
                       states_at=(56,))
    # (after such a decay a row's ``o`` is what its own token writes, small,
    # and the per-head norm divides by it: float32's own rounding reads
    # 4.4e-4 of the logits' deviation here, 3e-5 at the configuration's decay)
    assert distance(logits, theirs["logits"]) < 5 * TOL
    # softplus(3 + x) > 2.5 where |x| < 0.5: g < -16 x 2.5 a row and channel
    assert np.isfinite(np.asarray(theirs["state"])).all()


# ------------------------------------------------------------- the kernels

@pytest.fixture()
def interpreted(monkeypatch):
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")


def _rows(rng, rows, heads=2, d=32, decay=3.0):
    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    k = draw(rows, heads, d)
    return (draw(rows, heads, d), k / jnp.linalg.norm(k, axis=-1,
                                                      keepdims=True),
            draw(rows, heads, d), -jnp.abs(draw(rows, heads, d)) * decay,
            2.0 * jax.nn.sigmoid(draw(rows, heads)))


def _state(rng):
    """A state that is not zero, in its home layout ``[d_k, heads, d_v]``."""
    return jnp.asarray(rng.standard_normal((32, 2, 32)), jnp.float32)


@pytest.mark.parametrize("rows,decay", [
    (16, 3.0), (96, 0.1), (272, 3.0), (272, 0.1), (512, 3.0), (20, 3.0)])
def test_the_chunk_kernel_is_its_plain_twin(interpreted, rows, decay):
    """2 heads of 32, interpreted, from a state that is not zero: one
    sub-block a block (16 rows; 272 in 17 blocks, the state resident from
    one to the next), two (96 in blocks of 32), four (512 in blocks of 64:
    the sub-blocks below the diagonal go through the products); at decay 3.0
    every channel's summed log decay over a block of 64 rows is under -100
    (``exp(-cumsum g)`` overflows float32 at 88); no block divides 20 rows
    (the plain scan itself); ``skip`` hands the state back."""
    rng = np.random.default_rng(rows)
    operands = _rows(rng, rows, decay=decay)
    s0 = _state(rng)
    if decay == 3.0 and rows >= 64:
        assert float(operands[3][:64].sum(0).max()) < -100
    # (one trace for both calls: interpreted, the block's unrolled body is
    # most of a case's seconds)
    run = jax.jit(lambda *a: kda.kda_chunk(*a[:-1], skip=a[-1]))
    o, s = run(*operands, s0, False)
    want_o, want_s = kda.kda_chunk_plain(*operands, s0)
    assert np.isfinite(np.asarray(s)).all()
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)
    np.testing.assert_array_equal(run(*operands, s0, True)[1], s0)


def test_rows_that_are_no_token_leave_the_state_of_the_rows_before(
        interpreted, rows=96):
    """The last third padded (``g = beta = 0``, the caller's mask): the
    state is the one the live rows left, whole blocks of padding and a
    block's padded tail alike."""
    rng = np.random.default_rng(3)
    q, k, v, g, beta = _rows(rng, rows, decay=0.5)
    live = jnp.arange(rows) < 2 * rows // 3 + 8        # ends inside a block
    padded = (q, k, v, jnp.where(live[:, None, None], g, 0.0),
              jnp.where(live[:, None], beta, 0.0))
    s0 = _state(rng)
    _, s = jax.jit(kda.kda_chunk)(*padded, s0)
    count = int(live.sum())
    _, want = kda.kda_chunk_plain(*(t[:count] for t in padded), s0)
    np.testing.assert_allclose(s, want, atol=1e-6)


def test_the_state_carried_across_blocks_is_two_calls_of_half_the_rows(
        interpreted, rows=128):
    """Two blocks of 64 rows in one call, the state resident between them,
    against a call a block: the same arithmetic (to 2e-6: two programs of
    the interpreter, fused differently)."""
    rng = np.random.default_rng(4)
    operands = _rows(rng, rows, decay=0.5)
    s0 = _state(rng)
    run = jax.jit(kda.kda_chunk)
    o, s = run(*operands, s0)
    first, mid = run(*(t[:rows // 2] for t in operands), s0)
    second, last = run(*(t[rows // 2:] for t in operands), mid)
    np.testing.assert_allclose(o, jnp.concatenate([first, second]),
                               atol=2e-6)
    np.testing.assert_allclose(s, last, atol=2e-6)


def test_no_exponent_is_positive_and_every_product_is_float32(
        interpreted, monkeypatch):
    """An ``exp`` that answers NaN to a positive exponent changes nothing
    (the one exponent taken is a row's own log decay; the decay between two
    rows is a product of those), and the kernel's products take float32
    operands at ``HIGHEST``, Mosaic's float32 contraction (its default is
    one bfloat16 pass)."""
    class Strict:
        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def exp(x):
            return jnp.where(x > 0, jnp.nan, jnp.exp(x))

    rng = np.random.default_rng(5)
    operands = _rows(rng, 32, decay=3.0)
    s0 = _state(rng)
    want_o, want_s = kda.kda_chunk(*operands, s0)
    monkeypatch.setattr(kda, "jnp", Strict())
    o, s = kda.kda_chunk(*operands, s0)
    np.testing.assert_allclose(o, want_o, atol=2e-6)    # (a NaN fails it)
    np.testing.assert_allclose(s, want_s, atol=2e-6)

    def products(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for param in eqn.params.values():
                for inner in param if isinstance(param, tuple) else (param,):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        yield from products(inner)

    found = list(products(jax.make_jaxpr(kda.kda_chunk)(*operands, s0).jaxpr))
    assert found
    for eqn in found:
        assert {str(v.aval.dtype) for v in eqn.invars} == {"float32"}
        assert set(eqn.params["precision"]) == {jax.lax.Precision.HIGHEST}


def test_the_step_kernel_updates_one_layer_of_the_leaf_in_place(interpreted):
    """Three lanes in one call: one decoding, one beginning from zero, one
    not decoding (``g = beta = 0``: its state stays bit for bit)."""
    rng = np.random.default_rng(1)
    q, k, v, g, beta = _rows(rng, 3)               # one row a lane
    idle = jnp.asarray([False, False, True])[:, None]
    operands = (q, k, v, jnp.where(idle[..., None], 0.0, g),
                jnp.where(idle, 0.0, beta))
    state = jnp.asarray(rng.standard_normal((2, 3, 32, 2, 32)), jnp.float32)
    fresh = jnp.asarray([False, True, False])
    step = jax.jit(kda.kda_step)
    o, new = step(state, jnp.asarray(1), *operands, fresh)
    want_o, want = kda.kda_step_plain(state, 1, *operands, fresh)
    np.testing.assert_allclose(o, want_o, atol=2e-5)
    np.testing.assert_allclose(new, want, atol=2e-5)
    np.testing.assert_array_equal(new[0], state[0])     # the other layer
    np.testing.assert_array_equal(new[1, 2], state[1, 2])   # the idle lane
    _, kept = step(state, jnp.asarray(1), *operands, fresh,
                   skip=jnp.asarray(True))
    np.testing.assert_array_equal(kept, state)


# --------------------------------------------------- the share and the model

def test_the_parts_all_the_shares_give_add_up_to_the_uncut_layer():
    """16 routed experts over FOUR programs of 4 each: every program routes
    over all 16, computes the part its own 4 give and adds the shared
    expert. Their sum, the shared expert counted once, is what the uncut
    reference gives for the whole layer (every expert held)."""
    shares, held = 4, 4
    cfg = GPTConfig.from_model_config({**SIZES, "first_expert_held": 0})
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64))
    whole = jax.random.normal(jax.random.PRNGKey(4), (3, 16, 64, 32)) * 0.2
    v = flax.core.meta.unbox(jax.jit(moe_share.SharedMoEMLP(cfg).init)(
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
        total = total + traced_apply(layer, {"params": mine}, x)
    shared = moe_share._shared_expert(
        x[0], v["shared_gate"], v["shared_up"], v["shared_down"])
    uncut = {"router": {"kernel": v["router"]["kernel"][None]},
             "expert_bias": v["expert_bias"][None],
             "w_gate": whole[0][None], "w_up": whole[1][None],
             "w_down": whole[2].swapaxes(1, 2)[None],
             **{k: v[k][None] for k in ("shared_gate", "shared_up",
                                        "shared_down")}}
    with jax.default_matmul_precision("highest"):
        want, chosen, _, _ = solar2_f32._experts(
            x[0], uncut, 0, dict(solar2_f32._settings(MODEL), first=0))
    got = total[0] - (shares - 1) * shared
    assert np.abs(np.asarray(got - want)).max() <= 1e-5 * float(
        np.abs(want).max())
    assert len(np.unique(np.asarray(chosen) // held)) == shares


# ------------------------------------------------------------ the refusals

@pytest.mark.parametrize("over,exc,match", [
    ({"layer_types": ["kda", "mamba"] + MODEL["layer_types"][2:],
      "mamba_dt_rank": 8}, NotImplementedError, "mamba AND kda"),
    ({"layer_types": ["conv", "kda"] + MODEL["layer_types"][2:]},
     NotImplementedError, "conv AND kda"),
    ({"kda_gate_rank": 0}, ValueError, "kda_gate_rank"),
    ({"kda_num_heads": 0}, ValueError, "kda_num_heads"),
    ({"sandwich_norm": True}, NotImplementedError, "sandwich_norm"),
    ({"layer_types": ["full_attention"] * 8}, ValueError,
     "without a kda layer"),
])
def test_the_configuration_refuses_what_nobody_wrote(over, exc, match):
    with pytest.raises(exc, match=match):
        GPTConfig.from_model_config({**SIZES, **over})


def test_the_gate_beside_another_recurrent_kind_stays_refused():
    types = ["full_attention", "mamba"] * 4
    with pytest.raises(NotImplementedError, match="attention_gate"):
        GPTConfig.from_model_config({
            **{k: v for k, v in SIZES.items() if not k.startswith("kda")},
            "layer_types": types, "mamba_dt_rank": 8})


@pytest.mark.parametrize("asked,cause", [
    ({"prefix_cache": True}, "prefix reuse.*delta-rule"),
    ({"role": "prefill"}, "prefill or decode role.*delta-rule"),
    ({"host_cache_bytes": 1 << 20}, "host or disk page tier.*delta-rule"),
    ({"spec": True}, "speculative decoding"),
])
def test_what_the_engine_refuses_for_this_state(variables, asked, cause):
    with pytest.raises(ValueError, match=cause):
        engine_of(build(), variables, **asked)


def test_the_cache_manager_refuses_the_trie_by_the_kinds_own_name(variables):
    from fleetx_tpu.serving.cache_manager import PagedKVCacheManager

    model = build().clone(cfg=dataclasses.replace(
        build().cfg, decode_cache_len=32, decode_num_pages=9,
        decode_page_size=8))
    with pytest.raises(ValueError, match="state kind 'kda'"):
        PagedKVCacheManager(model, 2, 32, 9, 8, prefix_cache=True)
    manager = PagedKVCacheManager(model, 2, 32, 9, 8, prefix_cache=False)
    assert manager.lane_state and manager.lane_state_kind == "kda"
    assert manager.lane_bytes == 6 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)


# ------------------------------------------- through submit / step, last

def test_the_engine_serves_it_and_says_what_it_did(engine, variables):
    from fleetx_tpu.obs.tracing import get_recorder

    get_recorder().clear()
    first = engine.submit(TOKENS[0][:13], max_length=2)  # one call, bucket 16
    second = engine.submit(TOKENS[1][:40], max_length=6)  # chunks 16, 16, 8
    while first not in engine._results:
        engine.step()
    # the first request's lane is dead weight now and NOT decoding: the
    # second's ticks leave its state bit for bit
    idle = [x.copy() for x in lane_state(engine, 0)]
    assert np.abs(idle[0]).max() > 0
    results = engine.drain()
    for kept, held in zip(idle, lane_state(engine, 0)):
        np.testing.assert_array_equal(held, kept)
    # a third request is given the recycled lane and begins from zero there
    third = engine.submit(TOKENS[0][20:33], max_length=4)
    results.update(engine.drain())
    assert engine.metrics.snapshot()["kda_state_resets"] >= 3
    for rid, prompt in ((first, TOKENS[0][:13]), (second, TOKENS[1][:40]),
                        (third, TOKENS[0][20:33])):
        got = np.asarray(results[rid].tokens)
        tokens = np.concatenate([prompt, got])
        rated = np.asarray(reference(variables["params"], tokens[:-1],
                                     tail=len(got)))
        best = rated.max(-1)
        assert np.abs(best - rated[np.arange(len(got)), got]).max() < (
            TOL * rated.std())
    spans = get_recorder().spans()
    rows = [s.attrs["scan_rows"] for s in spans
            if s.name in ("serving.admit", "serving.prefill_chunk")
            and "scan_rows" in s.attrs]
    assert sorted(rows) == [8, 16, 16, 16, 16]
    ticks = [s.attrs for s in spans if s.name == "serving.decode"]
    assert ticks and all("state_lanes" in t and "pairs" in t for t in ticks)
    snapshot = engine.metrics.snapshot()
    lane = 6 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert snapshot["state_bytes_lanes"] == 3 * lane
    assert engine.health()["state_bytes"] == {"kv": 0, "kda": 3 * lane}
    assert engine.capabilities.state_kinds == ("kv", "kda")
    np.testing.assert_array_equal(engine.cache_manager.tables[:, 0],
                                  np.arange(3))


# ------------------------------------------------ what stays as it is today

# sha256 of the jaxprs of each configuration's tiny stack (a 16-row chunk
# and a 3-lane tick through a page pool:
# ``tests/test_longcat_serving.py`` ``traced_programs``), taken on the commit
# BEFORE the new kind (69ade5d): the stacks without ``kda`` trace the
# programs they traced then, instruction for instruction. Taken anew at PR
# 60, which widens the ``moe_stats`` leaf by two counts a kind (Jamba2
# carries the leaf and counts nothing in it: ``u32[1,16]`` became
# ``u32[1,24]`` and no other word of its texts moved). Jamba2's TICK taken
# anew at PR 62, whose ``selective_step(skip=)`` hands the leaf back under a
# ``select_n`` on the plain path these texts trace (its chunk's text, and
# every other stack's, did not move: the writer that branches on the layer's
# kind is a kernel, and these texts are traced without the kernels).
UNCHANGED = {
    "perfbench/configs/jamba2-3b.json": (
        "39694e4f37ecc4ac", "b660ada6b2777a0e"),
    "perfbench/configs/longcat-flash-ep32-l4.json": (
        "1063b0d9e9ed6c65", "5f0b8493f6c68f39"),
}
# (LFM2's and Trinity's stacks are held to the same digests, by the same
# function, in ``tests/test_longcat_serving.py``)


@pytest.mark.parametrize("path", sorted(UNCHANGED))
def test_a_stack_without_the_kind_traces_the_program_it_traced_before(path):
    from tests.test_longcat_serving import digest, traced_programs

    texts = traced_programs(path)
    assert tuple(digest(t) for t in texts) == UNCHANGED[path]
    for text in texts:
        assert "kda" not in text


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
    """64 heads of 128: a chunk of 512 rows of one lane and the last bucket's
    256, and the tick's step over the cell's whole leaf ``[6, 48, 128, 64,
    128]``, aliased to its output (no copy of it in the program)."""
    monkeypatch.setattr(kda, "_interpret", lambda: False)
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    heads, d, lanes, layers = 64, 128, 48, 6
    for rows in (512, 256):
        row = spec((rows, heads, d))
        text = jax.jit(lambda *a: kda.kda_chunk(*a[:-1], skip=a[-1])).lower(
            row, row, row, row, spec((rows, heads)), spec((d, heads, d)),
            spec((), jnp.bool_)).compile().as_text()
        assert kda.CHUNK_KERNEL_NAME in text
    row = spec((lanes, heads, d))
    leaf = (layers, lanes, d, heads, d)
    compiled = jax.jit(
        lambda s, l, *a: kda.kda_step(s, l, *a[:-1], skip=a[-1]),
        donate_argnums=0).lower(
        spec(leaf), spec((), jnp.int32), row, row, row, row,
        spec((lanes, heads)), spec((lanes,), jnp.bool_),
        spec((), jnp.bool_)).compile()
    text = compiled.as_text()
    assert kda.STEP_KERNEL_NAME in text
    assert not [line for line in text.splitlines() if " copy(" in line
                and "f32[6,48,128,64,128]" in line.split("=")[0]]
