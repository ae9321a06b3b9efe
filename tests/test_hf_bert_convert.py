"""HF BERT conversion: converted ERNIE encoder must reproduce transformers'
BERT hidden states — external ground truth for the encoder stack (post-LN
order, erf gelu, embeddings LN, tanh pooler)."""

import subprocess
import sys

import numpy as np
import pytest

REPO = __file__.rsplit("/tests/", 1)[0]

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def tiny_bert_ckpt(tmp_path_factory):
    from transformers import BertConfig, BertModel

    torch.manual_seed(0)
    cfg = BertConfig(
        vocab_size=99, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32, type_vocab_size=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    )
    model = BertModel(cfg)
    model.eval()
    d = tmp_path_factory.mktemp("hf_bert")
    model.save_pretrained(d)
    return str(d), model


@pytest.mark.slow  # 10.5s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_converted_encoder_matches_transformers(tmp_path, tiny_bert_ckpt):
    hf_dir, hf_model = tiny_bert_ckpt
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.ernie.model import ErnieConfig, ErnieModel
    from tools.convert_hf_bert import convert_state_dict

    sd = {k: v.numpy() for k, v in hf_model.state_dict().items()}
    tree = convert_state_dict(sd, 2, 4)

    cfg = ErnieConfig(
        vocab_size=99, hidden_size=32, num_layers=2, num_attention_heads=4,
        ffn_hidden_size=64, max_position_embeddings=32, type_vocab_size=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        hidden_act="gelu", dtype=jnp.float32,
    )
    model = ErnieModel(cfg)
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 99, (2, 16)).astype(np.int32)  # no pad: full attention
    seq, pooled = model.apply({"params": tree}, jnp.asarray(ids))

    with torch.no_grad():
        hf_out = hf_model(torch.from_numpy(ids.astype(np.int64)))
    np.testing.assert_allclose(
        np.asarray(seq), hf_out.last_hidden_state.numpy(), rtol=2e-3, atol=2e-3
    )
    np.testing.assert_allclose(
        np.asarray(pooled), hf_out.pooler_output.numpy(), rtol=2e-3, atol=2e-3
    )


@pytest.mark.slow  # 16.9s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_cli_artifact_serves(tmp_path, tiny_bert_ckpt):
    hf_dir, _ = tiny_bert_ckpt
    out = str(tmp_path / "artifact")
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/convert_hf_bert.py",
         "--hf-dir", hf_dir, "--output", out],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]

    sys.path.insert(0, REPO)
    from fleetx_tpu.core.inference_engine import InferenceEngine

    engine = InferenceEngine(out)
    ids = np.ones((1, 512), np.int32)
    mlm, sop = engine.predict({"input_ids": ids})
    assert np.isfinite(np.asarray(mlm)).all()
    assert np.asarray(sop).shape == (1, 2)
