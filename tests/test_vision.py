"""ViT family tests: presets, forward shapes, droppath, dataset transforms,
and an end-to-end GeneralClsModule training run."""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.vision.vit import (
    VIT_PRESETS,
    ViT,
    ViTConfig,
    build_vision_model,
)

TINY = ViTConfig(
    image_size=32, patch_size=8, num_classes=10, hidden_size=32,
    num_layers=2, num_attention_heads=4, drop_rate=0.0, attn_drop_rate=0.0,
    dtype=jnp.float32,
)


@pytest.mark.slow  # 17.6s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_vit_forward_shapes():
    model = ViT(TINY)
    imgs = jnp.zeros((2, 32, 32, 3))
    vars_ = jax.jit(model.init)(jax.random.PRNGKey(0), imgs)
    logits = model.apply(vars_, imgs)
    assert logits.shape == (2, 10)


def test_presets_table():
    assert len(VIT_PRESETS) >= 14
    m = build_vision_model("ViT_base_patch16_224", num_classes=10)
    assert m.cfg.hidden_size == 768 and m.cfg.num_layers == 12
    with pytest.raises(ValueError):
        build_vision_model("ViT_nonexistent")


@pytest.mark.slow  # 15.1s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_droppath_train_vs_eval():
    cfg = ViTConfig(**{**TINY.__dict__, "drop_path_rate": 0.5})
    model = ViT(cfg)
    imgs = jnp.ones((4, 32, 32, 3))
    vars_ = jax.jit(model.init)(jax.random.PRNGKey(0), imgs)
    eval1 = model.apply(vars_, imgs, deterministic=True)
    eval2 = model.apply(vars_, imgs, deterministic=True)
    np.testing.assert_array_equal(np.asarray(eval1), np.asarray(eval2))
    tr = model.apply(vars_, imgs, deterministic=False,
                     rngs={"dropout": jax.random.PRNGKey(1)})
    assert not np.allclose(np.asarray(tr), np.asarray(eval1))


def test_synthetic_dataset_and_transforms(tmp_path):
    from fleetx_tpu.data.vision_dataset import GeneralClsDataset, SyntheticClsDataset

    syn = SyntheticClsDataset(image_size=32, num_classes=10, num_samples=8)
    s = syn[0]
    assert s["images"].shape == (32, 32, 3)
    assert 0 <= int(s["labels"]) < 10

    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 255, (6, 48, 48, 3)).astype(np.uint8)
    labels = rng.randint(0, 10, 6)
    np.savez(tmp_path / "train.npz", images=imgs, labels=labels)
    ds = GeneralClsDataset(str(tmp_path), image_size=32, mode="Train")
    s = ds[0]
    assert s["images"].shape == (32, 32, 3)
    assert s["images"].dtype == np.float32
    # mmap .npy-pair path (the scalable layout)
    np.save(tmp_path / "eval_images.npy", imgs)
    np.save(tmp_path / "eval_labels.npy", labels.astype(np.int64))
    ev = GeneralClsDataset(str(tmp_path), image_size=32, mode="Eval")
    assert isinstance(ev.images, np.memmap)
    np.testing.assert_array_equal(ev[1]["images"], ev[1]["images"])


@pytest.mark.slow  # 9.0s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_cls_module_end_to_end(tmp_path, eight_devices):
    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.data import build_dataloader
    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import get_config

    text = textwrap.dedent(
        """
        Global:
          seed: 7
          local_batch_size: 8
          micro_batch_size: 8
        Engine:
          max_steps: 4
          logging_freq: 2
          eval_freq: 0
          save_load:
            save_steps: 1000
        Model:
          module: GeneralClsModule
          image_size: 32
          patch_size: 8
          num_classes: 10
          hidden_size: 32
          num_layers: 2
          num_attention_heads: 4
          mixup_alpha: 0.2
          label_smoothing: 0.1
          drop_rate: 0.0
          attn_drop_rate: 0.0
        Optimizer:
          name: AdamW
          weight_decay: 0.01
          lr:
            name: ViTLRScheduler
            learning_rate: 1.0e-3
            epochs: 10
            step_each_epoch: 10
            warmup_epochs: 1
          grad_clip:
            name: ClipGradByGlobalNorm
            clip_norm: 1.0
        Data:
          Train:
            dataset:
              name: SyntheticClsDataset
              image_size: 32
              num_classes: 10
              num_samples: 128
            sampler:
              name: GPTBatchSampler
              shuffle: True
            loader:
              num_workers: 0
        Distributed:
          dp_degree: 4
          mp_degree: 2
        """
    )
    p = tmp_path / "vit.yaml"
    p.write_text(text)
    cfg = get_config(str(p), nranks=8)
    cfg.Engine.save_load.output_dir = str(tmp_path / "out")
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    loader = build_dataloader(cfg, "Train")
    trainer.fit(loader)
    assert int(trainer.state.step) == 4


@pytest.mark.slow  # 21.9s baseline (PR 12 tier-1 budget audit): the
def test_vit_flash_matches_xla(monkeypatch):
    # flash-vs-dense parity gate stays tier-1 on the GPT suites
    # (test_flash_attention / test_decode_attention)
    """Flash-routed ViT encoder (seq 17 pads to a single kernel tile) must
    match the XLA attention path."""
    imgs = jnp.asarray(np.random.default_rng(0).random((2, 32, 32, 3)),
                       jnp.float32)
    xla_model = ViT(ViTConfig(**{**TINY.__dict__,
                                 "use_flash_attention": False}))
    vars_ = jax.jit(xla_model.init)(jax.random.PRNGKey(0), imgs)
    ref = xla_model.apply(vars_, imgs)
    monkeypatch.setenv("FLEETX_FORCE_FLASH", "1")
    out = ViT(TINY).apply(vars_, imgs)  # flash default ON
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
