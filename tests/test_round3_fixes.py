"""Regression tests for the round-3 fix sweep: quant weight filter, SR serving conditioning input,
tree-path opt-state sharding, sharding_offload gating, and the
non-deprecated ambient-mesh lookup."""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.utils.config import AttrDict, get_config, process_configs


# ------------------------------------------------------------ quant filter

def test_quantize_tree_skips_non_weight_leaves():
    from fleetx_tpu.ops.quant import quantize_tree_int8

    params = {
        "dense": {"kernel": jnp.ones((4, 4)), "bias": jnp.ones((4,))},
        "norm": {"scale_table": jnp.ones((4, 4))},  # 2-D but not a weight
    }
    q = quantize_tree_int8(params)
    assert set(q["dense"]["kernel"]) == {"_q8", "_scale"}
    # bias is 1-D, scale_table is not kernel/embedding-named: pass through
    assert isinstance(q["dense"]["bias"], jax.Array)
    assert isinstance(q["norm"]["scale_table"], jax.Array)


# ----------------------------------------------- imagen SR serving contract

@pytest.mark.slow  # ~20s (PR 13 tier-1 budget audit): two diffusion-UNet
def test_sr_serving_takes_explicit_lowres_input():
    # forwards; lowres conditioning stays tier-1 via test_imagen.py::
    # test_sr_unet_lowres_conditioning and the serving-export contract
    # via test_imagen.py::test_imagen_export_serving_contract
    from fleetx_tpu.models import build_module

    cfg = AttrDict(
        Global=AttrDict(seed=0, local_batch_size=2, micro_batch_size=2),
        Engine=AttrDict(mix_precision=AttrDict(use_pure_fp16=False)),
        Model=AttrDict(module="ImagenModule", dim=16, dim_mults=[1, 2],
                       num_resnet_blocks=1, layer_attns=[False, True],
                       layer_cross_attns=[False, True], attn_heads=2,
                       cond_dim=12, image_size=16, lowres_size=8,
                       lowres_cond=True, max_text_len=6),
        Optimizer=AttrDict(name="AdamW", lr=AttrDict(
            name="CosineDecay", learning_rate=1e-4, decay_steps=10)),
        Distributed=AttrDict(dp_degree=1),
    )
    process_configs(cfg, nranks=1)
    module = build_module(cfg)
    fn, spec = module.serving_forward(module.input_spec())
    assert "lowres_cond_img" in spec, (
        "SR serving must condition on an explicit clean low-res image, not "
        "derive it from the noisy x_t"
    )
    params = module.init_params(
        jax.random.PRNGKey(0),
        {k: np.zeros(v.shape, v.dtype) for k, v in module.input_spec().items()},
    )["params"]
    # final_conv is zero-initialized (diffusion convention), which makes the
    # net constant-zero at init; randomize it so input sensitivity shows.
    params = dict(params)
    params["final_conv"] = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(1), x.shape, x.dtype),
        params["final_conv"],
    )
    feed = {k: np.zeros(v.shape, v.dtype) for k, v in spec.items()}
    zero_low = np.asarray(fn(params, feed))
    feed2 = dict(feed)
    feed2["lowres_cond_img"] = np.ones_like(feed["lowres_cond_img"])
    one_low = np.asarray(fn(params, feed2))
    # the conditioning input actually reaches the UNet
    assert np.abs(zero_low - one_low).max() > 0


# ------------------------------------------- opt-state sharding by tree path

def _gpt_cfg(tmp_path, **over):
    text = textwrap.dedent(
        """
        Global:
          seed: 1
          local_batch_size: 4
          micro_batch_size: 4
        Engine:
          max_steps: 2
          logging_freq: 10
          eval_freq: 0
          save_load:
            save_steps: 1000
        Model:
          module: GPTModule
          vocab_size: 128
          hidden_size: 64
          num_layers: 2
          num_attention_heads: 4
          ffn_hidden_size: 128
          max_position_embeddings: 32
          hidden_dropout_prob: 0.0
          attention_probs_dropout_prob: 0.0
          use_flash_attention: False
        Optimizer:
          name: AdamW
          weight_decay: 0.01
          lr:
            name: CosineAnnealingWithWarmupDecay
            decay_steps: 100
            max_lr: 1.0e-3
            min_lr: 1.0e-4
        Distributed:
          dp_degree: 4
          mp_degree: 2
          pp_degree: 1
        """
    )
    p = tmp_path / "cfg.yaml"
    p.write_text(text)
    cfg = get_config(str(p), overrides=[f"{k}={v}" for k, v in over.items()], nranks=8)
    cfg.Engine.save_load.output_dir = str(tmp_path / "out")
    return cfg


def _batch(cfg, seq=32):
    gbs = cfg.Global.global_batch_size
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.Model.vocab_size, (gbs, seq)).astype(np.int32)
    return {
        "tokens": tokens,
        "labels": tokens,
        "loss_mask": np.ones((gbs, seq), np.float32),
    }


def test_opt_state_shardings_match_param_shardings_by_path(tmp_path, eight_devices):
    from fleetx_tpu.core.engine import Trainer, _unbox
    from fleetx_tpu.models import build_module

    cfg = _gpt_cfg(tmp_path)
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    trainer.init_state(_batch(cfg))

    param_leaves = jax.tree_util.tree_flatten_with_path(
        _unbox(trainer.state.params)
    )[0]
    spec_by_path = {
        trainer._path_keys(path): (leaf.shape, leaf.sharding.spec)
        for path, leaf in param_leaves
    }
    # every >=1-D moment leaf whose path suffix names a param must carry that
    # param's sharding (two same-shaped params with different shardings would
    # collide under the old (shape, dtype) matching) — plus, since PR 12,
    # the ZeRO update-shard axes folded on top when FLEETX_ZERO_UPDATE is
    # live (the moment's spec still derives from ITS param's, which is
    # what this regression test pins)
    from fleetx_tpu.parallel.sharding import zero_update_spec

    checked = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        trainer.state.opt_state
    )[0]:
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            continue
        keys = trainer._path_keys(path)
        for start in range(len(keys)):
            hit = spec_by_path.get(keys[start:])
            if hit is not None and hit[0] == leaf.shape:
                want = hit[1]
                if trainer._zero_update:
                    want = zero_update_spec(want, leaf.shape, trainer.mesh)
                assert leaf.sharding.spec == want, (keys, leaf.sharding.spec, want)
                checked += 1
                break
    assert checked >= 10  # moments for embeddings + qkv + mlp kernels etc.
    # sanity: at least one matched moment is actually mp-sharded
    specs = [
        l.sharding.spec
        for _, l in jax.tree_util.tree_flatten_with_path(trainer.state.opt_state)[0]
        if hasattr(l, "ndim") and l.ndim >= 2
    ]
    assert any("mp" in str(s) for s in specs)


def test_sharding_offload_raises_off_tpu(tmp_path, eight_devices):
    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.models import build_module

    cfg = _gpt_cfg(tmp_path, **{
        "Distributed.dp_degree": 2,
        "Distributed.sharding.sharding_degree": 2,
        "Distributed.sharding.sharding_stage": 2,
        "Distributed.sharding.sharding_offload": True,
    })
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    with pytest.raises(NotImplementedError, match="sharding_offload"):
        trainer.init_state(_batch(cfg))


# ------------------------------------------------------------- ambient mesh

def test_use_mesh_registry_found_without_deprecated_api(eight_devices):
    import warnings

    from jax.sharding import Mesh

    from fleetx_tpu.parallel.context_parallel import _ambient_mesh
    from fleetx_tpu.parallel.mesh import active_mesh, use_mesh

    mesh = Mesh(np.array(eight_devices).reshape(2, 4), ("dp", "cp"))
    assert active_mesh() is None
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        with use_mesh(mesh):
            assert active_mesh() is mesh
            assert _ambient_mesh() is mesh
    assert active_mesh() is None
