"""``correct`` in the serving cells is decided on the ENGINE: the tokens it
returns through ``submit`` and ``step`` and the logits of its executor on
the weights as it holds them, both against the float32 reference on the
weights as made. A page that goes stale between prefill and decode, or
int8 weights at engine level, must turn the check false (a private copy of
the model fed the raw float32 weights would notice neither)."""

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness, serving


@pytest.fixture(scope="module")
def tiny():
    """The docs-batch cell at rehearsal size. At two layers of width 64
    with every weight at the initializer's 0.02, the tied head dominates
    and the model repeats its input whatever the cache holds, so the layer
    kernels are scaled until attention and MLP decide the logits, as they
    do at the published widths."""
    cell = harness.load_cell("gpt1.3b-serve-docs-batch", tiny=True)
    model, variables = serving.build_model(cell, 3)
    variables = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 16.0 if "['layers']" in jax.tree_util.keystr(path)
        and "['kernel']" in jax.tree_util.keystr(path) else x, variables)
    return cell, model, variables


def test_the_engine_as_built_agrees_with_the_reference(tiny):
    cell, model, variables = tiny
    engine = serving.build_engine(cell, model, variables)
    out = serving.reference_check(engine, variables, cell, 3)
    assert out["reference_ok"], out
    assert out["engine_tokens_checked"] == 64
    # bf16 may swap two near-tied candidates, never pick a far worse one
    assert out["engine_tokens_reference_best"] >= 56
    answers = serving.engine_answers(engine, cell, 3)
    assert len({tuple(t) for _, t in answers}) == len(answers)
    assert all(len(set(t)) > 4 for _, t in answers)  # no repeated input


def test_a_stale_page_turns_the_check_false(tiny):
    cell, model, variables = tiny
    engine = serving.build_engine(cell, model, variables)
    step, calls = engine.step, []

    def step_then_lose_the_cache():
        out = step()
        calls.append(1)
        if len(calls) == 1:  # after the prefills: every page reads zeros
            engine.cache_manager.cache = jax.tree.map(
                jnp.zeros_like, engine.cache_manager.cache)
        return out

    engine.step = step_then_lose_the_cache
    out = serving.reference_check(engine, variables, cell, 3)
    assert not out["reference_ok"]
    unit = out["reference_logit_std"]
    assert out["engine_token_max_deficit"] > 5 * serving.REFERENCE_TOKEN_TOL * unit
    # the logits part has a cache of its own and cannot see this one
    assert out["reference_rms_err"] <= serving.REFERENCE_RMS_TOL * unit


def test_int8_weights_in_the_engine_turn_the_check_false(tiny, monkeypatch):
    cell, model, variables = tiny
    bf16 = serving.reference_check(
        serving.build_engine(cell, model, variables), variables, cell, 3)
    monkeypatch.setenv("FLEETX_SERVING_WEIGHT_DTYPE", "int8")
    engine = serving.build_engine(cell, model, variables)
    assert engine.weight_dtype == "int8"
    out = serving.reference_check(engine, variables, cell, 3)
    assert not out["reference_ok"]
    assert out["reference_rms_err"] > 2 * bf16["reference_rms_err"]
    assert out["reference_rms_err"] > (serving.REFERENCE_RMS_TOL
                                       * out["reference_logit_std"])


@pytest.mark.parametrize("cell_name,key", [
    ("gpt1.3b-serve-chat-steady-v3", 16),
    ("gpt1.3b-serve-docs-batch", None),
], ids=["the cell's key", "no key: the engine's default"])
def test_build_engine_hands_the_cells_prefill_bucket_to_the_engine(
        tiny, cell_name, key):
    _, model, variables = tiny
    cell = harness.load_cell(cell_name, tiny=True)
    assert cell.deploy.get("prefill_bucket") == key
    engine = serving.build_engine(cell, model, variables)
    # 32 is the engine's default (FLEETX_SERVING_PREFILL_BUCKET unset)
    assert engine.prefill_bucket == (key or 32)
    if key:  # and the warm-up follows it: one program a bucket
        longest = cell.traffic["tenants"][0]["prompt"]["max"]
        assert serving.warm_up(engine, cell, 3) == list(
            range(key, longest + 1, key))
