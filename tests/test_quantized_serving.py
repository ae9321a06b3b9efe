"""Quantized serving path (ISSUE 10): int8 KV cache + int8 weight-only.

The acceptance gates for ``FLEETX_SERVING_KV_DTYPE=int8`` /
``FLEETX_SERVING_WEIGHT_DTYPE=int8`` (docs/QUANTIZATION.md):

- **Tolerance parity** — serving under int8 KV (dense
  fallback AND the dequant-in-kernel flash-decode variants in interpret
  mode) reproduce the bf16 one-shot ``generate()`` streams within the
  documented ``QUANT_ATOL`` prefix budget from ``serving_parity.py``;
  weight-only int8 likewise.
- **Determinism under faults** — a quantized engine is exactly as
  crash-safe as a bf16 one: an injected tick failure replay-recovers to
  BYTE-identical streams vs the same quantized config unfaulted (the
  quant noise is deterministic; recovery re-prefills through the same
  quantize-on-write seam).
- **The HBM claim** — the int8 cache tree measures less than half the
  fp32 tree's device bytes (values 4→1 bytes, plus one fp32 scale per
  head vector), scrapeable via ``kv_cache_bytes``.
- **Quant helpers** — per-vector ``quantize_kv`` round-trip error is
  bounded by half an int8 step; ``quantize_tree_int8`` is idempotent so
  an InferenceEngine's pre-quantized tree survives the ServingEngine
  seam unchanged.

The default (bf16) path's byte-identity is NOT re-tested here — that is
the whole existing serving suite, unchanged.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from serving_parity import (QUANT_ATOL, assert_token_parity, one_shot_tokens,
                            sharing_programs)

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.resilience.faults import faults
from fleetx_tpu.serving import ServingEngine

CFG = GPTConfig(
    vocab_size=97,
    hidden_size=48,
    num_layers=2,
    num_attention_heads=4,
    ffn_hidden_size=96,
    max_position_embeddings=64,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype=jnp.float32,
    use_flash_attention=False,
)
GREEDY = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                          pad_token_id=96)
PROMPT_LENS = (3, 5, 4)
MAX_NEW = 5


@pytest.fixture(scope="module")
def model_and_params():
    model = GPTForPretraining(CFG)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return model, params


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(7)
    return [rng.randint(1, 97, (n,)).astype(np.int32) for n in PROMPT_LENS]


@pytest.fixture(scope="module")
def reference(model_and_params, prompts):
    """bf16(fp32)-precision one-shot streams — THE quality reference every
    quantized config is measured against."""
    model, params = model_and_params
    return [one_shot_tokens(model, params, p, MAX_NEW, gen_cfg=GREEDY)
            for p in prompts]


@sharing_programs
def _engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("cache_len", 32)
    kw.setdefault("gen_cfg", GREEDY)
    kw.setdefault("prefill_bucket", 8)
    kw.setdefault("page_size", 8)
    return ServingEngine(model, params, **kw)


def _serve(model, params, prompts, **kw):
    eng = _engine(model, params, **kw)
    rids = [eng.submit(p, max_length=MAX_NEW) for p in prompts]
    res = eng.drain()
    return eng, [np.asarray(res[r].tokens) for r in rids]


# ------------------------------------------------------------ quant helpers

def test_quantize_kv_roundtrip_bound():
    """Per-vector absmax int8: round-trip error <= half a quantization
    step of each vector's own scale; all-zero vectors survive exactly."""
    from fleetx_tpu.ops.quant import dequantize_kv, quantize_kv

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(3, 16, 4, 12) * 3.0, jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == (3, 16, 4, 1)
    err = np.abs(np.asarray(dequantize_kv(q, s)) - np.asarray(x))
    bound = np.asarray(s) * 0.5 + 1e-7
    assert (err <= bound).all(), float((err - bound).max())
    zq, zs = quantize_kv(jnp.zeros((2, 4, 2, 8)))
    assert not np.asarray(zq).any() and not np.asarray(zs).any()
    np.testing.assert_array_equal(np.asarray(dequantize_kv(zq, zs)), 0.0)


def test_quantize_tree_int8_idempotent():
    """Double-quantization must be a no-op: a ServingEngine handed an
    InferenceEngine's already-quantized params passes them through."""
    from fleetx_tpu.ops.quant import dequantize_tree_int8, quantize_tree_int8

    rng = np.random.RandomState(1)
    tree = {"layer": {"kernel": jnp.asarray(rng.randn(8, 8), jnp.float32),
                      "bias": jnp.zeros((8,))}}
    once = quantize_tree_int8(tree)
    assert set(once["layer"]["kernel"]) == {"_q8", "_scale"}
    twice = quantize_tree_int8(once)
    assert twice["layer"]["kernel"]["_q8"] is once["layer"]["kernel"]["_q8"]
    deq = dequantize_tree_int8(twice)
    np.testing.assert_allclose(np.asarray(deq["layer"]["kernel"]),
                               np.asarray(tree["layer"]["kernel"]),
                               atol=float(once["layer"]["kernel"]["_scale"]
                                          .max()) * 0.5 + 1e-7)


def test_prequantized_params_at_bf16_raise_clearly():
    """Regression: serving an already-quantized tree with
    weight_dtype='bf16' has no dequant seam — it must raise a clear
    error at the seam, not crash deep inside the first traced apply."""
    from fleetx_tpu.ops.quant import quantize_tree_int8, serving_weight_params

    tree = {"layer": {"kernel": jnp.asarray(np.random.RandomState(0)
                                            .randn(8, 8), jnp.float32)}}
    q = quantize_tree_int8(tree)
    with pytest.raises(ValueError, match="already int8-quantized"):
        serving_weight_params(q, "bf16")
    # float trees pass through both ways; int8 is idempotent
    assert serving_weight_params(tree, "bf16") is tree
    assert (serving_weight_params(q, "int8")["layer"]["kernel"]["_q8"]
            is q["layer"]["kernel"]["_q8"])


def test_quant_parity_frac_contract():
    """The shared bench/test parity measure: length mismatch fails
    outright (0.0), divergence measures the common prefix."""
    from fleetx_tpu.ops.quant import quant_parity_frac

    assert quant_parity_frac([1, 2, 3, 4], [1, 2, 3, 4]) == 1.0
    assert quant_parity_frac([1, 2, 9, 9], [1, 2, 3, 4]) == 0.5
    assert quant_parity_frac([1, 2, 3], [1, 2, 3, 4]) == 0.0  # truncated


def test_kv_dtype_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="KV_DTYPE"):
        _engine(model, params, kv_dtype="fp4")
    with pytest.raises(ValueError, match="WEIGHT_DTYPE"):
        _engine(model, params, weight_dtype="int3")


# ------------------------------------------------- tolerance-parity gates

@pytest.mark.slow  # 5.0s+5.2s (PR 15 tier-1 budget audit): the dense/XLA
# FALLBACK's int8 parity — the production flash-interpret variants stay
# tier-1 below, and the dense path re-runs in the slow int8 matrix
def test_int8_kv_parity_dense(model_and_params, prompts, reference):
    """int8 KV on the dense/XLA fallback: streams within
    the QUANT_ATOL prefix budget of the bf16 one-shot reference, and the
    engine publishes its precision config."""
    model, params = model_and_params
    eng, toks = _serve(model, params, prompts, kv_dtype="int8")
    for i, t in enumerate(toks):
        assert_token_parity(t, reference[i], atol=QUANT_ATOL,
                            err_msg=f"int8-kv req {i}")
    snap = eng.metrics.snapshot()
    assert snap["kv_dtype"] == "int8" and snap["weight_dtype"] == "bf16"
    assert snap["kv_bytes_per_token"] > 0 and snap["kv_cache_bytes"] > 0


def test_int8_kv_parity_flash_interpret(model_and_params, prompts, reference,
                                        monkeypatch):
    """The dequant-in-kernel paged flash-decode variant (interpret
    mode): int8 tiles rescaled in VMEM inside the online
    softmax must land inside the same tolerance budget as the dense
    dequant — one quantization contract across every attention path."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    model, params = model_and_params
    flash_model = GPTForPretraining(
        dataclasses.replace(CFG, use_flash_attention=True))
    _, toks = _serve(flash_model, params, prompts, kv_dtype="int8")
    for i, t in enumerate(toks):
        assert_token_parity(t, reference[i], atol=QUANT_ATOL,
                            err_msg=f"int8-kv flash req {i}")


@pytest.mark.slow  # 6.4s (PR 15 tier-1 budget audit): weight-int8
# quality stays tier-1 via the test_eval_cli WikiText ppl-budget gate
# and the int8-KV flash parity gates above; full parity re-runs slow
def test_int8_weight_only_parity(model_and_params, prompts, reference):
    """Weight-only int8: params live in HBM as {"_q8", "_scale"} leaves
    (measurably smaller than float), dequant happens inside the jitted
    prefill/decode, and streams stay inside the tolerance budget."""
    model, params = model_and_params
    eng, toks = _serve(model, params, prompts,
                       weight_dtype="int8")
    for i, t in enumerate(toks):
        assert_token_parity(t, reference[i], atol=QUANT_ATOL,
                            err_msg=f"int8-weight req {i}")
    leaves = jax.tree.leaves(eng.params)
    assert any(leaf.dtype == jnp.int8 for leaf in leaves)
    float_bytes = sum(int(l.size) * 4 for l in jax.tree.leaves(params))
    snap = eng.metrics.snapshot()
    assert snap["weight_dtype"] == "int8"
    assert 0 < snap["weight_bytes"] < float_bytes


def test_int8_kv_halves_cache_bytes(model_and_params):
    """The HBM claim, measured: the int8 cache tree (int8 values + one
    fp32 scale per head vector) is under half the fp32 tree's bytes."""
    model, params = model_and_params
    full = _engine(model, params)
    quant = _engine(model, params, kv_dtype="int8")
    fb = full.cache_manager.cache_nbytes()
    qb = quant.cache_manager.cache_nbytes()
    assert qb < 0.5 * fb, (qb, fb)
    assert quant.metrics.snapshot()["kv_cache_bytes"] == qb
    assert quant.metrics.snapshot()["kv_bytes_per_token"] < (
        full.metrics.snapshot()["kv_bytes_per_token"])


# ---------------------------------------------- crash-safety determinism

def test_int8_replay_recovery_byte_identical(model_and_params, prompts):
    """Quantized crash-safety: an injected tick failure under int8 KV +
    int8 weights replay-recovers BYTE-identically to the same quantized
    config run clean — quantization noise is deterministic and recovery
    re-prefills through the same quantize-on-write seam (atol=0, not the
    tolerance budget)."""
    model, params = model_and_params
    kw = dict(kv_dtype="int8", weight_dtype="int8")
    _, clean = _serve(model, params, prompts, **kw)
    faults.configure(tick_raise="1")
    try:
        eng, faulty = _serve(model, params, prompts, **kw)
    finally:
        faults.reset()
    assert eng.metrics.engine_recoveries == 1
    eng.cache_manager.pool.check_invariants()
    for i, (a, b) in enumerate(zip(clean, faulty)):
        assert_token_parity(a, b, err_msg=f"int8 replay req {i}")


@pytest.mark.slow  # 8.9s (PR 15 tier-1 budget audit): int8 recovery
# byte-identity stays tier-1 via test_int8_replay_recovery_byte_identical
# (the fault path) and bf16 manual recover() in test_serving_recovery
def test_int8_manual_recover_byte_identical(model_and_params, prompts):
    """recover() mid-flight (external device reset) under int8 KV: the
    rebuilt pool re-quantizes the replayed history and resumes exactly
    where the unfaulted quantized run goes."""
    model, params = model_and_params
    kw = dict(kv_dtype="int8")
    _, clean = _serve(model, params, prompts, **kw)
    eng = _engine(model, params, **kw)
    rids = [eng.submit(p, max_length=MAX_NEW) for p in prompts]
    eng.step()
    eng.recover()
    res = eng.drain()
    eng.cache_manager.pool.check_invariants()
    for i, r in enumerate(rids):
        assert_token_parity(np.asarray(res[r].tokens), clean[i],
                            err_msg=f"int8 recover req {i}")
