"""The prefill kernel over a latent cache (``ops/pallas/mla_prefill.py``,
``fleetx_mla_prefill``) interpreted on the CPU at small widths and the
kernel's own block of 1,024 rows, against its plain twin
``latent._chunk``: every place a chunk can stand in its lane, rows past the
chunk poisoned, the score type planted as ``perfbench/probe_axk1.py``
plants it, what chooses the kernel, the model's chunks through it, and the
span field that counts its key rows. (Compiled for a described v5e at the
published widths beside the decode kernel, under the one topology fixture
of ``tests/test_axk1_serving.py``.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_axk1_serving as axk1  # sibling module (pytest rootdir import)
from fleetx_tpu.models.gpt import latent
from fleetx_tpu.models.gpt.generation import init_decode_cache
from fleetx_tpu.models.gpt.model import GPTConfig
from fleetx_tpu.ops.pallas import mla_prefill

HEADS, NOPE, ROPE, VD, C = 4, 16, 8, 16, 32
ROWS = 4 * mla_prefill.BLOCK_ROWS   # a lane of four key blocks
BUCKET, CHUNK = 16, 32
SCALE = 0.2
CFG = GPTConfig.from_model_config(axk1.SIZES)
# a float32 sum in another order; one bfloat16 step of values near 2
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def operands(s, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(k[0], (s, HEADS, NOPE + ROPE), dtype)
    w = (jax.random.normal(k[1], (C, HEADS, NOPE + VD), jnp.float32)
         * 0.3).astype(dtype)
    ckv = jax.random.normal(k[2], (ROWS, C), dtype)
    kr = jnp.pad(jax.random.normal(k[3], (ROWS, ROPE), dtype),
                 ((0, 0), (0, latent.rope_leaf_width(CFG) - ROPE)))
    return q, w, ckv, kr


def kernel(q, w, ckv, kr, start, score_type=jnp.float32):
    return np.asarray(mla_prefill.mla_prefill(
        q, w, ckv, kr, jnp.int32(start), nope=NOPE, scale=SCALE,
        score_type=score_type), np.float32)


def plain(q, w, ckv, kr, start):
    return np.asarray(latent._chunk(CFG, q, w, ckv, kr[:, :ROPE],
                                    jnp.int32(start), SCALE), np.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("s", [BUCKET, CHUNK], ids=["bucket", "chunk"])
@pytest.mark.parametrize("start", [0, 1500, 2048, ROWS - CHUNK],
                         ids=["first", "mid_block", "boundary", "last_block"])
def test_the_kernel_is_the_plain_chunk(start, s, dtype):
    args = operands(s, dtype, seed=start + s)
    assert np.abs(kernel(*args, start) - plain(*args, start)).max() < TOL[
        dtype]


@pytest.mark.parametrize("start, s", [(0, BUCKET), (1500, CHUNK),
                                      (mla_prefill.BLOCK_ROWS - CHUNK, CHUNK)],
                         ids=["first", "mid_block", "ends_on_a_boundary"])
def test_rows_past_the_chunk_change_nothing_whatever_they_hold(start, s):
    """The rows of the last live block that no query sees, and every block
    behind it, as NaN: not a bit of the output moves."""
    q, w, ckv, kr = operands(s, jnp.float32)
    clean = kernel(q, w, ckv, kr, start)
    poisoned = kernel(q, w, ckv.at[start + s:].set(jnp.nan),
                      kr.at[start + s:].set(jnp.nan), start)
    assert np.isfinite(clean).all()
    assert (poisoned == clean).all()


@pytest.mark.parametrize("s", [BUCKET, CHUNK], ids=["bucket", "chunk"])
def test_a_planted_score_type_moves_the_kernel_as_it_moves_the_plain_chunk(
        s, monkeypatch):
    """``perfbench/probe_axk1.py`` plants bfloat16 in ``latent._SCORE_TYPE``
    and the cell's check must go on refusing it: the kernel (through
    ``latent._prefill``, which reads the seam when it is traced) follows
    the plain chunk there, away from the float32 scores."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    flash = dataclasses.replace(CFG, use_flash_attention=True)
    q, w, ckv, kr = operands(s, jnp.float32)
    start = jnp.int32(1500)

    def through(cfg):
        return np.asarray(latent._prefill(cfg, q, w, ckv, kr, start, SCALE))

    exact = through(flash)
    monkeypatch.setattr(latent, "_SCORE_TYPE", jnp.bfloat16)
    planted, planted_plain = through(flash), through(CFG)
    assert np.abs(planted - planted_plain).max() < 2e-5
    assert np.abs(planted - exact).max() > 1e-3


@pytest.mark.parametrize("flash, forced, calls", [
    (True, "1", 1), (False, "1", 0), (True, None, 0)],
    ids=["kernel", "configured_off", "no_kernels_here"])
def test_the_kernel_is_chosen_by_what_the_decode_kernel_is_chosen_by(
        flash, forced, calls, monkeypatch):
    if forced:
        monkeypatch.setenv("FLEETX_FORCE_FLASH", forced)
    else:
        monkeypatch.delenv("FLEETX_FORCE_FLASH", raising=False)
    seen = []
    real = mla_prefill.mla_prefill
    monkeypatch.setattr(mla_prefill, "mla_prefill",
                        lambda *a, **k: seen.append(1) or real(*a, **k))
    args = operands(BUCKET, jnp.float32)
    out = latent._prefill(dataclasses.replace(CFG, use_flash_attention=flash),
                          *args, jnp.int32(64), SCALE)
    assert len(seen) == calls
    assert np.abs(np.asarray(out) - plain(*args, 64)).max() < 2e-5


@pytest.mark.parametrize("chunks", [(32,), (16, 16), (8, 24)])
def test_the_models_chunks_through_the_kernel_are_the_reference(
        chunks, monkeypatch):
    """``test_axk1_serving``'s chunked prefill with the kernels on: every
    chunk's logits are the plain float32 reference's."""
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    model, variables = axk1.build()
    tokens = np.random.default_rng(0).integers(1, 128, 32, dtype=np.int32)
    reference = np.asarray(axk1.axk1_f32.configured(axk1.SIZES)(
        variables["params"], tokens))
    served = axk1.paged(model.clone(cfg=dataclasses.replace(
        model.cfg, use_flash_attention=True)))
    traced = []
    real = mla_prefill.mla_prefill
    monkeypatch.setattr(mla_prefill, "mla_prefill",
                        lambda *a, **k: traced.append(1) or real(*a, **k))
    cache = init_decode_cache(served, 1)
    table = jnp.arange(1, 13, dtype=jnp.int32)[None]
    forward = axk1.traced_anew()    # (the recording entry is in the trace)
    out, at = [], 0
    for n in chunks:
        logits, cache = forward(
            served, variables["params"], cache,
            jnp.asarray(tokens[None, at:at + n]), jnp.asarray([at]), table)
        out.append(logits[0])
        at += n
    assert traced
    assert np.abs(np.asarray(jnp.concatenate(out)) - reference).max() < axk1.TOL


@pytest.mark.parametrize("rows, behind, key_rows", [
    (32, 0, 1024), (512, 512, 1024), (512, 1024, 2048), (100, 7000, 7168)])
def test_a_chunks_span_fields_count_the_kernels_key_rows(rows, behind,
                                                         key_rows):
    fields = CFG.spans(rows, behind)
    assert fields["latent_rows"] == behind + rows
    assert fields["latent_key_rows"] == key_rows
    assert mla_prefill.key_rows(behind + rows) == key_rows
    plain_cfg = GPTConfig.from_model_config(dict(
        vocab_size=128, hidden_size=64, num_layers=2, num_attention_heads=4,
        ffn_hidden_size=32, max_position_embeddings=64))
    assert plain_cfg.spans(rows, behind) == {}
