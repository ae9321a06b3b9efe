"""MoCo tests: ResNet backbone, queue/momentum mechanics, and an e2e
MOCOModule training run through the extra-state Trainer path."""

import textwrap

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from fleetx_tpu.models.vision.resnet import ResNetConfig, ResNet, build_resnet


@pytest.mark.slow  # 55.1s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_resnet_backbone_shapes():
    model = build_resnet("resnet18", width=16, dtype=jnp.float32)
    imgs = jnp.zeros((2, 32, 32, 3))
    vars_ = jax.jit(model.init)(jax.random.PRNGKey(0), imgs)
    feats = model.apply(vars_, imgs)
    assert feats.shape == (2, 16 * 8)  # width * 2^3, basic blocks
    logits = build_resnet("resnet50", width=16, num_classes=7, dtype=jnp.float32)
    vars_ = jax.jit(logits.init)(jax.random.PRNGKey(0), imgs)
    assert logits.apply(vars_, imgs).shape == (2, 7)


def _moco_cfg(tmp_path, nranks=8):
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
          module: MOCOModule
          backbone: resnet18
          width: 16
          dim: 16
          queue_size: 64
          momentum: 0.99
          temperature: 0.2
          mlp: True
          image_size: 32
        Optimizer:
          name: Momentum
          weight_decay: 1.0e-4
          momentum: 0.9
          lr:
            name: CosineDecay
            learning_rate: 0.03
            decay_steps: 100
          grad_clip:
        Data:
          Train:
            dataset:
              name: ContrastiveViewsDataset
              synthetic: True
              image_size: 32
              num_samples: 512
            sampler:
              name: GPTBatchSampler
              shuffle: True
            loader:
              num_workers: 0
        Distributed:
          dp_degree: 8
        """
    )
    p = tmp_path / "moco.yaml"
    p.write_text(text)
    cfg = get_config(str(p), nranks=nranks)
    cfg.Engine.save_load.output_dir = str(tmp_path / "out")
    return cfg


@pytest.mark.slow  # 23.5s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_moco_end_to_end_queue_and_ema(tmp_path, eight_devices):
    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.data import build_dataloader
    from fleetx_tpu.models import build_module
    import fleetx_tpu.parallel.env as dist_env

    cfg = _moco_cfg(tmp_path)
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    loader = build_dataloader(cfg, "Train")
    batch = next(iter(loader))
    trainer.init_state(batch)

    q0 = np.asarray(jax.tree.leaves(trainer.state.extra["queue"])[0]).copy()
    kp0 = jax.tree.map(np.asarray, trainer.state.extra["key_params"])

    step = trainer._get("train", trainer._build_train_step)
    db = trainer._shard_batch(batch)
    state, metrics = step(trainer.state, db, dist_env.data_rank_key(0))

    assert np.isfinite(float(metrics["loss"]))
    assert 0.0 <= float(metrics["contrast_acc"]) <= 1.0
    # queue advanced by global batch (64 slots, batch 64 -> ptr wraps to 0)
    new_queue = np.asarray(state.extra["queue"])
    assert not np.allclose(new_queue, q0)
    # EMA moved key params toward the updated query params but not onto them
    kp1 = jax.tree.map(np.asarray, state.extra["key_params"])
    p1 = jax.tree.map(np.asarray, state.params)
    moved = changed = 0
    from fleetx_tpu.core.engine import _unbox

    for a, b, c in zip(
        jax.tree.leaves(kp0), jax.tree.leaves(kp1), jax.tree.leaves(_unbox(p1))
    ):
        if not np.allclose(a, b):
            moved += 1
        if not np.allclose(b, np.asarray(c)):
            changed += 1
    assert moved > 0  # EMA actually updated
    assert changed > 0  # but key != query


@pytest.mark.slow  # 17.1s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_moco_trains_with_fit(tmp_path, eight_devices):
    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.data import build_dataloader
    from fleetx_tpu.models import build_module

    cfg = _moco_cfg(tmp_path)
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    loader = build_dataloader(cfg, "Train")
    trainer.fit(loader)
    assert int(trainer.state.step) == 4


@pytest.mark.slow  # 24.2s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_moco_lincls_loads_pretrained_backbone(tmp_path, eight_devices):
    """MOCOClsModule maps the MoCo encoder backbone onto the linear probe
    (frozen), errors on checkpoints with nothing to transfer, and its decay
    mask covers only the head."""
    import jax
    import orbax.checkpoint as ocp

    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import AttrDict, process_configs

    # a tiny MoCo pretraining encoder -> params artifact
    pre_cfg = AttrDict(
        Global=AttrDict(seed=0, local_batch_size=2, micro_batch_size=2),
        Engine=AttrDict(mix_precision=AttrDict(use_pure_fp16=False)),
        Model=AttrDict(module="MOCOModule", backbone="resnet18", dim=16,
                       queue_size=64, image_size=32, width=8),
        Optimizer=AttrDict(name="Momentum", lr=AttrDict(
            name="CosineDecay", learning_rate=0.03, decay_steps=10)),
        Distributed=AttrDict(dp_degree=1),
    )
    process_configs(pre_cfg, nranks=1)
    moco = build_module(pre_cfg)
    batch = {"query": np.zeros((2, 32, 32, 3), np.float32),
             "key": np.zeros((2, 32, 32, 3), np.float32)}
    variables = moco.init_params(jax.random.PRNGKey(7), batch)
    ck = ocp.StandardCheckpointer()
    ck.save(str(tmp_path / "moco_params"), dict(variables["params"]), force=True)
    ck.wait_until_finished()

    cls_cfg = AttrDict(
        Global=AttrDict(seed=0, local_batch_size=2, micro_batch_size=2),
        Engine=AttrDict(mix_precision=AttrDict(use_pure_fp16=False)),
        Model=AttrDict(module="MOCOClsModule", backbone="resnet18",
                       num_classes=10, image_size=32, width=8,
                       pretrained=str(tmp_path / "moco_params")),
        Optimizer=AttrDict(name="Momentum", lr=AttrDict(
            name="CosineDecay", learning_rate=30.0, decay_steps=10)),
        Distributed=AttrDict(dp_degree=1),
    )
    process_configs(cls_cfg, nranks=1)
    probe = build_module(cls_cfg)
    init = probe.init_params(jax.random.PRNGKey(0),
                             {"images": batch["query"]})["params"]
    loaded = probe.load_pretrained(init)
    assert loaded is not None
    # the backbone subtree must now equal the MoCo encoder's
    src_flat = {
        tuple(str(getattr(k, "key", k)) for k in p): v
        for p, v in jax.tree_util.tree_flatten_with_path(
            dict(variables["params"]))[0]
    }
    moved = 0
    for p, v in jax.tree_util.tree_flatten_with_path(loaded)[0]:
        key = tuple(str(getattr(k, "key", k)) for k in p)
        if key in src_flat:
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(src_flat[key]))
            moved += 1
    assert moved > 10  # the whole resnet transferred

    # decay mask: True only under cls_head
    mask = probe.weight_decay_mask()(loaded)
    flat = jax.tree_util.tree_flatten_with_path(mask)[0]
    heads = [v for p, v in flat
             if any(str(getattr(k, "key", k)) == "cls_head" for k in p)]
    others = [v for p, v in flat
              if not any(str(getattr(k, "key", k)) == "cls_head" for k in p)]
    assert all(heads) and not any(others)

    # wrong checkpoint: nothing matches -> hard error
    bogus_dir = tmp_path / "bogus"
    ck.save(str(bogus_dir), {"something": np.zeros((3, 3), np.float32)},
            force=True)
    ck.wait_until_finished()
    probe.cfg.Model.pretrained = str(bogus_dir)
    with pytest.raises(ValueError, match="no matching weights"):
        probe.load_pretrained(init)


def test_moco_lincls_reads_trainer_checkpoint_layout(tmp_path):
    """Model.pretrained pointing at a Trainer output dir (CheckpointManager
    checkpoints/<step>/{state,meta}) must load — the shipped lincls config
    uses exactly that layout."""
    import jax
    import orbax.checkpoint as ocp

    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import AttrDict, process_configs

    pre_cfg = AttrDict(
        Global=AttrDict(seed=0, local_batch_size=2, micro_batch_size=2),
        Engine=AttrDict(mix_precision=AttrDict(use_pure_fp16=False)),
        Model=AttrDict(module="MOCOModule", backbone="resnet18", dim=16,
                       queue_size=64, image_size=32, width=8),
        Optimizer=AttrDict(name="Momentum", lr=AttrDict(
            name="CosineDecay", learning_rate=0.03, decay_steps=10)),
        Distributed=AttrDict(dp_degree=1),
    )
    process_configs(pre_cfg, nranks=1)
    moco = build_module(pre_cfg)
    batch = {"query": np.zeros((2, 32, 32, 3), np.float32),
             "key": np.zeros((2, 32, 32, 3), np.float32)}
    variables = moco.init_params(jax.random.PRNGKey(7), batch)

    # mimic the engine's manager layout (engine.py save())
    ckdir = tmp_path / "output" / "checkpoints"
    mgr = ocp.CheckpointManager(str(ckdir))
    mgr.save(3, args=ocp.args.Composite(
        state=ocp.args.StandardSave(
            # 0-d ndarray, not a numpy scalar: StandardSave rejects bare
            # np.int32 — the real Trainer state's step is an array too
            {"step": np.asarray(3, np.int32),
             "params": dict(variables["params"])}),
        meta=ocp.args.JsonSave({"epoch": 0, "consumed_samples": 0}),
    ))
    mgr.wait_until_finished()

    cls_cfg = AttrDict(
        Global=AttrDict(seed=0, local_batch_size=2, micro_batch_size=2),
        Engine=AttrDict(mix_precision=AttrDict(use_pure_fp16=False)),
        Model=AttrDict(module="MOCOClsModule", backbone="resnet18",
                       num_classes=10, image_size=32, width=8,
                       pretrained=str(tmp_path / "output")),
        Optimizer=AttrDict(name="Momentum", lr=AttrDict(
            name="CosineDecay", learning_rate=30.0, decay_steps=10)),
        Distributed=AttrDict(dp_degree=1),
    )
    process_configs(cls_cfg, nranks=1)
    probe = build_module(cls_cfg)
    init = probe.init_params(jax.random.PRNGKey(0),
                             {"images": batch["query"]})["params"]
    loaded = probe.load_pretrained(init)
    assert loaded is not None
