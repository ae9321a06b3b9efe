"""Every shipped YAML config must parse, inherit, pass degree/batch
validation at its intended device count, AND instantiate its module
(reference configs launch unchanged — the north-star claim)."""

import os

import pytest

from fleetx_tpu.utils.config import get_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# device count per topology; inferred from the config's name
_NRANKS = {
    "single_card": 1, "dp8": 8, "sharding16": 16, "mp8_pp16": 128,
    "cp8": 8, "mp8": 8, "3D": 8, "mp2": 2,
    "1n8c": 8, "2n16c": 16, "dap8": 8, "tiny_cpu": 1,
}

# configs whose names carry no topology token: intended device counts
_EXPLICIT = {
    "imagen_397M_text2im_64x64.yaml": 8,
    "imagen_super_resolution_256.yaml": 8,
    "imagen_super_resolution_512.yaml": 8,
    "imagen_super_resolution_1024.yaml": 8,
    "imagen_base64.yaml": 8,
    "moco_v2_resnet50.yaml": 8,
    "vit_base_patch16_224.yaml": 8,
    "pretrain_moe_small.yaml": 8,
    "pretrain_gpt_1.3B_longcontext_cp8.yaml": 8,
    "ViT_base_patch16_224_inference.yaml": 1,
    "serve_olmoe_1b_7b_l8.yaml": 1,  # one replica on one chip
    "serve_smallthinker_21b_a3b_l8.yaml": 1,  # one replica on one chip
    "serve_lfm2_8b_a1b_l14.yaml": 1,  # one replica on one chip
    "serve_jamba2_3b.yaml": 1,        # the whole model on one chip
    "serve_axk1_ep16_l6.yaml": 1,     # one chip's share of sixteen
    "serve_dsv32_ep16_l5.yaml": 1,    # one chip's share of sixteen
    "serve_trinity_large_ep8_l5.yaml": 1,  # one chip's share of eight
    "serve_longcat_flash_ep32_l4.yaml": 1,  # one chip's share of thirty-two
    "serve_solar_open2_ep16_l8.yaml": 1,  # one chip's share of sixteen
    "serve_ling3_flash_ep8_l7.yaml": 1,   # one chip's share of eight
    "serve_keye_vl2_30b_l6.yaml": 1,  # one pipeline stage of eight
    "serve_evabyte_6.5b_pp4_l8.yaml": 1,  # one pipeline stage of four
}

# _base_ fragments: not launchable topologies on their own
_BASES = {
    "pretrain_gpt_base.yaml", "finetune_gpt_base.yaml",
    "pretrain_moe_base.yaml", "imagen_base.yaml",
    "base.yaml", "pretrain_ernie_base.yaml",
}


def _infer_nranks(name: str) -> int:
    if name in _EXPLICIT:
        return _EXPLICIT[name]
    # longest key first: 'mp8_pp16' must win over 'mp8'
    for key in sorted(_NRANKS, key=len, reverse=True):
        if key in name:
            return _NRANKS[key]
    # fail loudly on unrecognized topology names so new configs are tested
    # at their intended device count, not a silent default
    raise AssertionError(
        f"config name {name!r} matches no topology key; add one to _NRANKS "
        "or name the file with its topology (e.g. *_dp8.yaml)")


def _zoo():
    cases = []
    base = os.path.join(REPO, "configs")
    for root, _, files in os.walk(base):
        for f in sorted(files):
            if not f.endswith(".yaml"):
                continue
            rel = os.path.relpath(os.path.join(root, f), base)
            if f in _BASES:
                cases.append((rel, 8, False))
            else:
                cases.append((rel, _infer_nranks(f), True))
    assert len(cases) >= 48  # reference zoo size — parity floor
    return cases


@pytest.mark.parametrize("rel,nranks,build", _zoo())
def test_zoo_config_validates_and_builds(rel, nranks, build):
    cfg = get_config(os.path.join(REPO, "configs", rel), nranks=nranks)
    assert cfg.Global.global_batch_size >= 1
    if not build:
        return  # _base_ fragment: parse + batch algebra is the contract
    assert cfg.Model.module
    from fleetx_tpu.models import build_module

    module = build_module(cfg)
    assert module.nets is not None


def test_reference_config_launches_unchanged():
    """A YAML from the reference repo itself must load through our config
    system (same schema)."""
    ref = "/root/reference/ppfleetx/configs/nlp/gpt/pretrain_gpt_345M_single_card.yaml"
    if not os.path.isfile(ref):
        pytest.skip("reference not mounted")
    cfg = get_config(ref, nranks=1)
    assert cfg.Model.module == "GPTModule"
    assert cfg.Global.global_batch_size == 8


def test_reference_qat_and_generation_configs_launch():
    for ref, nranks in [
        ("/root/reference/ppfleetx/configs/nlp/gpt/qat_gpt_345M_mp8.yaml", 8),
        ("/root/reference/ppfleetx/configs/nlp/gpt/generation_gpt_345M_single_card.yaml", 1),
    ]:
        if not os.path.isfile(ref):
            pytest.skip("reference not mounted")
        cfg = get_config(ref, nranks=nranks)
        assert cfg.Model.module
