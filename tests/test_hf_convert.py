"""HF GPT-2 conversion: converted artifact must reproduce transformers'
logits — an external ground truth for the whole GPT stack (embeddings,
pre-LN blocks, gelu_new, tied lm head)."""

import subprocess
import sys

import numpy as np
import pytest

REPO = __file__.rsplit("/tests/", 1)[0]

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def tiny_hf_ckpt(tmp_path_factory):
    from transformers import GPT2Config, GPT2LMHeadModel

    torch.manual_seed(0)
    cfg = GPT2Config(
        vocab_size=97, n_positions=32, n_embd=32, n_layer=2, n_head=4,
        resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
    )
    model = GPT2LMHeadModel(cfg)
    model.eval()
    d = tmp_path_factory.mktemp("hf_gpt2")
    model.save_pretrained(d)
    return str(d), model


@pytest.mark.slow  # 11.2s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_converted_logits_match_transformers(tmp_path, tiny_hf_ckpt):
    hf_dir, hf_model = tiny_hf_ckpt
    out = str(tmp_path / "artifact")
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/convert_hf_gpt2.py",
         "--hf-dir", hf_dir, "--output", out],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]

    sys.path.insert(0, REPO)
    from fleetx_tpu.core.inference_engine import InferenceEngine

    engine = InferenceEngine(out)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 97, (2, 16)).astype(np.int32)
    ours = engine.predict({"tokens": tokens})

    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(tokens.astype(np.int64))).logits.numpy()

    np.testing.assert_allclose(ours, theirs, rtol=2e-3, atol=2e-3)


@pytest.mark.slow  # 12.3s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_vocab_padding_preserves_real_logits(tmp_path, tiny_hf_ckpt):
    hf_dir, hf_model = tiny_hf_ckpt
    out = str(tmp_path / "artifact_padded")
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/convert_hf_gpt2.py",
         "--hf-dir", hf_dir, "--output", out, "--pad-vocab-multiple", "64"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]

    sys.path.insert(0, REPO)
    from fleetx_tpu.core.inference_engine import InferenceEngine

    engine = InferenceEngine(out)
    tokens = np.arange(32, dtype=np.int32).reshape(2, 16)
    ours = engine.predict({"tokens": tokens})
    assert ours.shape[-1] == 128  # padded to the multiple
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(tokens.astype(np.int64))).logits.numpy()
    np.testing.assert_allclose(ours[..., :97], theirs, rtol=2e-3, atol=2e-3)


@pytest.mark.slow  # 14.4s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_gpt_module_warm_starts_from_converted_artifact(tmp_path, tiny_hf_ckpt):
    """Model.pretrained on the pretraining module loads a converted HF
    backbone (eval/generation warm-start path)."""
    hf_dir, hf_model = tiny_hf_ckpt
    out = str(tmp_path / "artifact")
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/convert_hf_gpt2.py",
         "--hf-dir", hf_dir, "--output", out],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]

    sys.path.insert(0, REPO)
    import jax

    from fleetx_tpu.core.engine import Trainer, _unbox
    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import AttrDict, process_configs

    cfg = AttrDict(
        Global=AttrDict(seed=0, local_batch_size=2, micro_batch_size=2),
        Engine=AttrDict(max_steps=1,
                        save_load=AttrDict(output_dir=str(tmp_path / "o"))),
        Model=AttrDict(module="GPTModule", pretrained=out,
                       vocab_size=97, hidden_size=32, num_layers=2,
                       num_attention_heads=4, ffn_hidden_size=128,
                       max_position_embeddings=32,
                       hidden_dropout_prob=0.0,
                       attention_probs_dropout_prob=0.0,
                       use_flash_attention=False),
        Optimizer=AttrDict(name="AdamW", lr=AttrDict(
            name="CosineAnnealingWithWarmupDecay", decay_steps=10,
            max_lr=1e-3, min_lr=1e-4)),
        Distributed=AttrDict(dp_degree=None, mp_degree=1, pp_degree=1),
    )
    process_configs(cfg, nranks=1)
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    batch = {
        "tokens": np.zeros((2, 16), np.int32),
        "labels": np.zeros((2, 16), np.int32),
        "loss_mask": np.ones((2, 16), np.float32),
    }
    trainer.init_state(batch)
    params = jax.tree.map(np.asarray, _unbox(trainer.state.params))
    wte = hf_model.transformer.wte.weight.detach().numpy()
    np.testing.assert_allclose(params["gpt"]["word_embeddings"], wte, atol=1e-6)


@pytest.mark.slow  # 16.2s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_int8_quantized_artifact_close_to_fp32(tmp_path, tiny_hf_ckpt):
    """--quantize int8 stores int8 weights; served logits stay close to the
    fp32 artifact (weight-only per-channel quantization)."""
    hf_dir, hf_model = tiny_hf_ckpt
    out = str(tmp_path / "artifact_int8")
    r = subprocess.run(
        [sys.executable, f"{REPO}/tools/convert_hf_gpt2.py",
         "--hf-dir", hf_dir, "--output", out, "--quantize", "int8"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]

    sys.path.insert(0, REPO)
    from fleetx_tpu.core.inference_engine import InferenceEngine

    engine = InferenceEngine(out)
    tokens = np.arange(32, dtype=np.int32).reshape(2, 16)
    ours = engine.predict({"tokens": tokens})
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(tokens.astype(np.int64))).logits.numpy()
    # int8 drift tolerance is looser than the fp32 parity tests
    np.testing.assert_allclose(ours, theirs, rtol=0.2, atol=0.5)
