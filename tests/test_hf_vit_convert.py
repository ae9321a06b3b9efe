"""HF ViT conversion: converted backbone must reproduce transformers' ViT
logits — external ground truth for the vision stack (conv patch embed,
pre-LN blocks, cls pooling)."""

import subprocess
import sys

import numpy as np
import pytest

REPO = __file__.rsplit("/tests/", 1)[0]

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def tiny_vit_ckpt(tmp_path_factory):
    from transformers import ViTConfig, ViTForImageClassification

    torch.manual_seed(0)
    cfg = ViTConfig(
        image_size=32, patch_size=16, num_channels=3, hidden_size=32,
        num_hidden_layers=2, num_attention_heads=4, intermediate_size=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        num_labels=7,
    )
    model = ViTForImageClassification(cfg)
    model.eval()
    d = tmp_path_factory.mktemp("hf_vit")
    model.save_pretrained(d)
    return str(d), model


@pytest.mark.slow  # 9.8s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_converted_logits_match_transformers(tmp_path, tiny_vit_ckpt):
    hf_dir, hf_model = tiny_vit_ckpt
    sys.path.insert(0, REPO)
    import jax.numpy as jnp

    from fleetx_tpu.models.vision.vit import ViTConfig as FxViTConfig, ViT
    from tools.convert_hf_vit import convert_state_dict

    sd = {k: v.numpy() for k, v in hf_model.state_dict().items()}
    tree = convert_state_dict(sd, 2, 4, num_classes=7)

    cfg = FxViTConfig(
        image_size=32, patch_size=16, num_classes=7, hidden_size=32,
        num_layers=2, num_attention_heads=4, mlp_ratio=2.0,
        drop_rate=0.0, attn_drop_rate=0.0, drop_path_rate=0.0,
        hidden_act="gelu", dtype=jnp.float32,
    )
    model = ViT(cfg)
    rng = np.random.RandomState(0)
    images = rng.randn(2, 32, 32, 3).astype(np.float32)
    ours = model.apply({"params": tree}, jnp.asarray(images))

    with torch.no_grad():
        theirs = hf_model(
            torch.from_numpy(images.transpose(0, 3, 1, 2))  # NHWC -> NCHW
        ).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, rtol=2e-3, atol=2e-3)


@pytest.mark.slow  # 15.7s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_cli_artifact_serves(tmp_path, tiny_vit_ckpt):
    hf_dir, hf_model = tiny_vit_ckpt
    out = str(tmp_path / "artifact")
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/convert_hf_vit.py",
         "--hf-dir", hf_dir, "--output", out, "--num-classes", "7"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]

    sys.path.insert(0, REPO)
    from fleetx_tpu.core.inference_engine import InferenceEngine

    engine = InferenceEngine(out)
    rng = np.random.RandomState(1)
    images = rng.randn(1, 32, 32, 3).astype(np.float32)
    logits = engine.predict({"images": images})
    assert np.asarray(logits).shape == (1, 7)

    with torch.no_grad():
        theirs = hf_model(
            torch.from_numpy(images.transpose(0, 3, 1, 2))
        ).logits.numpy()
    np.testing.assert_allclose(np.asarray(logits), theirs, rtol=2e-3, atol=2e-3)
