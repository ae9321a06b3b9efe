"""The plain float32 reference against GPTForPretraining at a tiny size on
the CPU (on the chip the benchmark compares them at the published widths,
outside the window). Closes ROADMAP D7 for GPT."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from perfbench.reference import gpt_f32


@pytest.fixture(scope="module")
def tiny():
    model = GPTForPretraining(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=3, num_attention_heads=4,
        ffn_hidden_size=64, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        fuse_attn_qkv=True, use_flash_attention=False, dtype=jnp.float32))
    variables = jax.jit(model.init)(jax.random.PRNGKey(0),
                                    np.zeros((1, 8), np.int32))
    # biases and LayerNorm scales start at 0 and 1: move them so they count
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    variables = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape), variables)
    tokens = np.random.RandomState(0).randint(0, 128, (2, 24)).astype(np.int32)
    return model, variables, tokens


def test_logits_match_the_model(tiny):
    model, variables, tokens = tiny
    want = np.asarray(model.apply(variables, tokens))
    got = np.asarray(jax.jit(gpt_f32.logits)(variables["params"], tokens))
    assert got.dtype == np.float32 and got.shape == want.shape
    # float32 against float32: rounding only
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=0)


def test_loss_matches_the_masked_mean_cross_entropy(tiny):
    model, variables, tokens = tiny
    labels = np.roll(tokens, -1, axis=1)
    mask = np.ones(tokens.shape, np.float32)
    mask[:, -3:] = 0.0
    logits = np.asarray(model.apply(variables, tokens), np.float64)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    picked = np.take_along_axis(logp, labels[..., None], -1)[..., 0]
    want = -(picked * mask).sum() / mask.sum()
    got = float(jax.jit(gpt_f32.loss)(variables["params"], tokens, labels, mask))
    assert abs(got - want) < 1e-5


def test_reference_is_causal(tiny):
    _, variables, tokens = tiny
    changed = tokens.copy()
    changed[:, 12:] = (changed[:, 12:] + 1) % 128
    a = np.asarray(gpt_f32.logits(variables["params"], tokens))
    b = np.asarray(gpt_f32.logits(variables["params"], changed))
    np.testing.assert_array_equal(a[:, :12], b[:, :12])
    assert np.abs(a[:, 12:] - b[:, 12:]).max() > 1e-3


def _system_loss(model, variables, shift=0, label_shift=0):
    """A stand-in for the trainer's evaluation step: the model's masked
    mean loss of a batch, optionally with positions or labels off by one."""
    def loss(batch):
        pos = np.minimum(batch["position_ids"] + shift, 63)
        labels = np.roll(batch["labels"], label_shift, axis=1)
        logits = np.asarray(model.apply(variables, batch["tokens"], pos),
                            np.float64)
        logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        picked = np.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return float(-(picked * batch["loss_mask"]).sum()
                     / batch["loss_mask"].sum())
    return loss


@pytest.mark.parametrize("fault,ok", [
    ({}, True), ({"shift": 1}, False), ({"label_shift": 1}, False)],
    ids=["as_is", "position_off_by_one", "label_off_by_one"])
def test_training_agreement_is_chunk_by_chunk(tiny, fault, ok):
    """The training cells' check (``train_fit.reference_agreement``): the
    system's loss under a mask for each run of tokens against the
    reference's per-token losses. A position or a label off by one leaves
    the mean near ln V and fails chunk by chunk."""
    from perfbench.drivers import train_fit

    model, variables, _ = tiny
    tokens = np.random.RandomState(1).randint(0, 128, (2, 65)).astype(np.int32)
    sample = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:],
              "position_ids": np.broadcast_to(
                  np.arange(64, dtype=np.int32), (2, 64)).copy(),
              "loss_mask": np.ones((2, 64), np.float32)}
    reference = np.asarray(jax.jit(gpt_f32.token_losses)(
        variables["params"], sample["tokens"], sample["labels"]))
    out = train_fit.reference_agreement(
        _system_loss(model, variables, **fault), reference, sample)
    assert out["reference_ok"] is ok, out
    assert out["reference_chunk_tokens"] == 4
    if ok:
        assert out["reference_chunk_max_err"] < 1e-4
    else:  # the whole-sample mean alone would not have told
        assert out["reference_chunk_max_err"] > 10 * out["reference_abs_err"] \
            or out["reference_abs_err"] > train_fit.REFERENCE_LOSS_TOL
