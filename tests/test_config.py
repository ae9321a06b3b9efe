"""Config system tests: _base_ inheritance, overrides, batch/degree algebra
(reference semantics: config.py:31-174, 227-374)."""

import os
import textwrap

import pytest

from fleetx_tpu.utils.config import (
    AttrDict,
    get_config,
    override_config,
    parse_config,
    process_configs,
)


def _write(tmp_path, name, text):
    p = tmp_path / name
    p.write_text(textwrap.dedent(text))
    return str(p)


BASE = """
Global:
  seed: 1024
  local_batch_size: 8
  micro_batch_size: 8
Engine:
  max_steps: 100
  mix_precision:
    use_pure_fp16: True
Model:
  module: GPTModule
  hidden_size: 1024
Distributed:
  dp_degree: 1
"""


def test_base_inheritance(tmp_path):
    base = _write(tmp_path, "base.yaml", BASE)
    child = _write(
        tmp_path,
        "child.yaml",
        f"""
        _base_: {os.path.basename(base)}
        Model:
          hidden_size: 2048
        """,
    )
    cfg = parse_config(child)
    assert cfg.Model.hidden_size == 2048
    assert cfg.Model.module == "GPTModule"  # inherited
    assert cfg.Global.seed == 1024


def test_inherited_false_replaces_section(tmp_path):
    base = _write(tmp_path, "base.yaml", BASE)
    child = _write(
        tmp_path,
        "child.yaml",
        f"""
        _base_: {os.path.basename(base)}
        Model:
          _inherited_: False
          name: ViT
        """,
    )
    cfg = parse_config(child)
    assert cfg.Model.name == "ViT"
    assert cfg.Model.get("module") is None  # base section dropped


def test_override_dot_paths(tmp_path):
    cfg = parse_config(_write(tmp_path, "b.yaml", BASE))
    override_config(
        cfg,
        ["Model.hidden_size=4096", "Engine.mix_precision.use_pure_fp16=False",
         "Optimizer.lr.max_lr=1e-4", "Data.Train.dataset.split=[949,50,1]"],
    )
    assert cfg.Model.hidden_size == 4096
    assert cfg.Engine.mix_precision.use_pure_fp16 is False
    assert cfg.Optimizer.lr.max_lr == pytest.approx(1e-4)
    assert cfg.Data.Train.dataset.split == [949, 50, 1]


def test_dp_degree_derived_from_nranks(tmp_path):
    cfg = parse_config(_write(tmp_path, "b.yaml", BASE))
    cfg.Distributed = AttrDict(mp_degree=2, pp_degree=2)
    process_configs(cfg, nranks=8)
    assert cfg.Distributed.dp_degree == 2
    assert cfg.Distributed.sharding.sharding_degree == 1


def test_degree_product_validated(tmp_path):
    cfg = parse_config(_write(tmp_path, "b.yaml", BASE))
    cfg.Distributed = AttrDict(dp_degree=3, mp_degree=2)
    with pytest.raises(ValueError):
        process_configs(cfg, nranks=8)


def test_partial_degree_product_raises(tmp_path):
    cfg = parse_config(_write(tmp_path, "b.yaml", BASE))
    cfg.Distributed = AttrDict(dp_degree=2, sharding=AttrDict(sharding_degree=2))
    with pytest.raises(ValueError):  # 2*1*1*2 = 4 != 8 devices
        process_configs(cfg, nranks=8)


def test_batch_algebra(tmp_path):
    cfg = parse_config(_write(tmp_path, "b.yaml", BASE))
    cfg.Distributed = AttrDict(dp_degree=4, sharding=AttrDict(sharding_degree=2))
    cfg.Global.local_batch_size = 4
    cfg.Global.micro_batch_size = 1
    process_configs(cfg, nranks=8)
    assert cfg.Global.global_batch_size == 4 * 8  # local × dp_world(dp*sharding)
    assert cfg.Engine.accumulate_steps == 4  # local/micro


def test_local_derived_from_global(tmp_path):
    cfg = parse_config(_write(tmp_path, "b.yaml", BASE))
    cfg.Distributed = AttrDict(dp_degree=8)
    cfg.Global.global_batch_size = 64
    cfg.Global.local_batch_size = None
    cfg.Global.micro_batch_size = None
    process_configs(cfg, nranks=8)
    assert cfg.Global.local_batch_size == 8
    assert cfg.Global.micro_batch_size == 8
    assert cfg.Engine.accumulate_steps == 1


def test_inconsistent_batch_sizes_raise(tmp_path):
    cfg = parse_config(_write(tmp_path, "b.yaml", BASE))
    cfg.Distributed = AttrDict(dp_degree=8)
    cfg.Global.global_batch_size = 63
    with pytest.raises(ValueError):
        process_configs(cfg, nranks=8)


def test_get_config_end_to_end(tmp_path):
    base = _write(tmp_path, "base.yaml", BASE)
    cfg = get_config(base, overrides=["Model.num_layers=2"], nranks=1)
    assert cfg.Model.num_layers == 2
    assert cfg.Engine.mix_precision.dtype == "bfloat16"


def test_reference_yaml_schema_launches(tmp_path):
    """The reference's own YAML schema (pretrain_gpt_base + child) must load
    unchanged (BASELINE.md north star)."""
    base = _write(
        tmp_path,
        "pretrain_gpt_base.yaml",
        """
        Global:
          device: gpu
          seed: 1024
          global_batch_size:
          local_batch_size: 1
          micro_batch_size: 1
        Engine:
          max_steps: 500000
          eval_freq: 500
          mix_precision:
            use_pure_fp16: True
            scale_loss: 32768.0
          save_load:
            save_steps: 1000
            output_dir: ./output
        Model:
          module: "GPTModule"
          name: "GPT"
          fused_linear: False
          fuse_attn_qkv: True
          sequence_parallel: False
        Optimizer:
          name: FusedAdamW
          weight_decay: 0.01
          lr:
            name: CosineAnnealingWithWarmupDecay
            decay_steps: 360000
            max_lr: 5.0e-5
            min_lr: 1.0e-5
          grad_clip:
            name: "ClipGradByGlobalNorm"
            clip_norm: 1.0
        Distributed:
          fuse_sequence_parallel_allreduce: False
        """,
    )
    child = _write(
        tmp_path,
        "pretrain_345M.yaml",
        """
        _base_: ./pretrain_gpt_base.yaml
        Global:
          local_batch_size: 8
          micro_batch_size: 8
        Model:
          vocab_size: 50304
          hidden_size: 1024
          num_layers: 24
          num_attention_heads: 16
        Distributed:
          dp_degree: 1
          mp_degree: 1
          pp_degree: 1
          sharding:
            sharding_degree: 1
            sharding_stage: 1
        """,
    )
    cfg = get_config(child, nranks=1)
    assert cfg.Model.vocab_size == 50304
    assert cfg.Global.global_batch_size == 8
    assert cfg.Optimizer.lr.name == "CosineAnnealingWithWarmupDecay"


@pytest.mark.parametrize("case,overrides,nranks,expected", [
    ("unset-mp2", ["Distributed.mp_degree=2"], 4, True),
    ("unset-mp1", [], 4, False),
    ("unset-mp2-length-unknown",
     ["Distributed.mp_degree=2", "Data.Train.dataset.max_seq_len="], 4, True),
    ("unset-mp2-length-does-not-divide",
     ["Distributed.mp_degree=2", "Data.Train.dataset.max_seq_len=1023"], 4,
     False),
    ("unset-mp2-cp2-length-divides-by-mp-alone",
     ["Distributed.mp_degree=2", "Distributed.cp_degree=2",
      "Data.Train.dataset.max_seq_len=1022"], 4, False),
    ("false-mp2",
     ["Distributed.mp_degree=2", "Model.sequence_parallel=False"], 4, False),
    ("true-mp2",
     ["Distributed.mp_degree=2", "Model.sequence_parallel=True"], 4, True),
    ("true-mp1", ["Model.sequence_parallel=True"], 4, False),
])
def test_sequence_parallel_default(case, overrides, nranks, expected):
    """``Model.sequence_parallel`` unset is the program's to decide: on
    where there is an ``mp`` axis and the configured sequence length (if
    any) divides over ``mp * cp``; an explicit value is honoured, but for
    ``True`` at ``mp`` 1, which has nothing to ride. The published
    configurations leave it unset and inherit this."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = get_config(
        os.path.join(repo, "configs/nlp/gpt/pretrain_gpt_1.3B_dp8.yaml"),
        overrides=["Distributed.dp_degree="] + overrides, nranks=nranks)
    assert cfg.Model.sequence_parallel is expected
