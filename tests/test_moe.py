"""MoE tests: routing semantics (capacity, top-k weighting, balance loss),
MoEMLP forward/grad, expert-parallel sharded training step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt.model import GPTConfig
from fleetx_tpu.parallel.moe import MoEMLP, compute_routing


@pytest.mark.slow  # 15.3s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_routing_top1_all_tokens_placed_when_capacity_ample():
    logits = jnp.asarray(np.random.RandomState(0).randn(32, 4), jnp.float32)
    dispatch, combine, aux = compute_routing(logits, top_k=1, capacity=32,
                                             gate_type="switch")
    # every token lands in exactly one (expert, slot)
    assert int(dispatch.sum()) == 32
    # weights on the single expert are 1 after normalization
    np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))), 1.0, rtol=1e-5)
    assert np.isfinite(float(aux))


@pytest.mark.slow  # 17.0s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_routing_capacity_drops_tokens():
    # all tokens prefer expert 0 -> only `capacity` fit
    logits = jnp.tile(jnp.asarray([[10.0, 0.0]]), (16, 1))
    dispatch, combine, aux = compute_routing(logits, top_k=1, capacity=4,
                                             gate_type="switch")
    assert int(dispatch[:, 0].sum()) == 4
    placed = np.asarray(dispatch.any(axis=(1, 2)))
    assert placed.sum() == 4  # 12 dropped
    # dropped tokens have zero combine weight
    assert np.allclose(np.asarray(combine[~placed]).sum(), 0.0)


@pytest.mark.slow  # 14.5s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_routing_no_slot_collisions():
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(64, 8), jnp.float32)
    dispatch, _, _ = compute_routing(logits, top_k=2, capacity=16, gate_type="naive")
    # at most one token per (expert, slot)
    per_slot = np.asarray(dispatch).sum(axis=0)
    assert per_slot.max() <= 1


def test_top2_weights_normalized():
    logits = jnp.asarray(np.random.RandomState(2).randn(16, 4), jnp.float32)
    _, combine, _ = compute_routing(logits, top_k=2, capacity=16, gate_type="naive")
    sums = np.asarray(combine.sum(axis=(1, 2)))
    np.testing.assert_allclose(sums, 1.0, rtol=1e-5)


@pytest.mark.slow  # 41.7s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_moe_mlp_forward_and_grad():
    cfg = GPTConfig(
        hidden_size=32, ffn_hidden_size=64, num_experts=4, expert_mode=True,
        top_k=2, gate="gshard", dtype=jnp.float32,
    )
    layer = MoEMLP(cfg)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 32), jnp.float32)
    vars_ = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    y, mut = layer.apply(vars_, x, mutable=["intermediates"])
    assert y.shape == x.shape
    assert "balance_loss" in mut["intermediates"]

    def loss(params):
        out, _ = layer.apply({"params": params}, x, mutable=["intermediates"])
        return (out**2).sum()

    g = jax.grad(loss)(vars_["params"])
    flat = jax.tree.leaves(jax.tree.map(lambda a: np.abs(np.asarray(a)).sum(), g))
    assert all(np.isfinite(v) for v in flat)
    # expert weights received gradient
    w_up_grad = g["w_up"].value if hasattr(g["w_up"], "value") else g["w_up"]
    assert np.abs(np.asarray(w_up_grad)).sum() > 0


@pytest.mark.slow  # 13.5s baseline (PR 12 tier-1 budget audit): MoE layer
def test_moe_module_trains_sharded(tmp_path, eight_devices):
    # math/dispatch parity stays tier-1; this is the e2e sharded-fit variant
    """Full MoE GPT training step on a dp4xmp2 mesh with experts sharded
    over the data axes."""
    import textwrap

    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import get_config

    p = tmp_path / "moe.yaml"
    p.write_text(textwrap.dedent("""
        Global:
          seed: 7
          local_batch_size: 2
          micro_batch_size: 2
        Engine:
          max_steps: 4
          logging_freq: 2
          eval_freq: 0
          save_load:
            save_steps: 1000
        Model:
          module: MoEModule
          vocab_size: 128
          hidden_size: 32
          num_layers: 2
          num_attention_heads: 4
          ffn_hidden_size: 64
          max_position_embeddings: 32
          hidden_dropout_prob: 0.0
          attention_probs_dropout_prob: 0.0
          use_flash_attention: False
          num_experts: 4
          gate: gshard
          top_k: 2
        Optimizer:
          name: AdamW
          weight_decay: 0.0
          lr:
            name: CosineAnnealingWithWarmupDecay
            decay_steps: 100
            max_lr: 1.0e-3
            min_lr: 1.0e-4
          grad_clip:
            name: ClipGradForMOEByGlobalNorm
            clip_norm: 1.0
        Distributed:
          dp_degree: 4
          mp_degree: 2
          pp_degree: 1
    """))
    cfg = get_config(str(p), nranks=8)
    cfg.Engine.save_load.output_dir = str(tmp_path / "out")
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    rng = np.random.RandomState(0)
    gbs = cfg.Global.global_batch_size
    data = [
        {
            "tokens": rng.randint(0, 128, (gbs, 32)).astype(np.int32),
            "labels": rng.randint(0, 128, (gbs, 32)).astype(np.int32),
            "loss_mask": np.ones((gbs, 32), np.float32),
        }
        for _ in range(4)
    ]
    trainer.fit(data)
    assert int(trainer.state.step) == 4


def test_scatter_dispatch_matches_einsum():
    """The O(n) scatter/gather dispatch must produce identical outputs to
    the dense [n,E,C] einsum dispatch (same params, same routing)."""
    from fleetx_tpu.models.gpt.model import GPTConfig
    from fleetx_tpu.parallel.moe import MoEMLP

    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 32), jnp.float32)
    outs = {}
    for mode in ("einsum", "scatter"):
        cfg = GPTConfig(
            hidden_size=32, ffn_hidden_size=64, num_experts=4,
            expert_mode=True, top_k=2, gate="gshard", dtype=jnp.float32,
            moe_dispatch=mode,
        )
        layer = MoEMLP(cfg)
        vars_ = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
        outs[mode] = np.asarray(layer.apply(vars_, x))
    np.testing.assert_allclose(outs["scatter"], outs["einsum"],
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow  # 44.4s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_moe_e16_on_mesh_with_capacity_drops(eight_devices):
    """E=16 experts sharded over the 8-device data axes with the scatter
    dispatch: runs, differentiates, and the tight capacity actually drops
    tokens (VERDICT r2 item 9 done-criterion)."""
    import flax.linen as nn
    from jax.sharding import Mesh

    from fleetx_tpu.models.gpt.model import GPTConfig
    from fleetx_tpu.parallel.moe import MoEMLP, compute_routing_indices
    from fleetx_tpu.parallel.sharding import make_rules

    cfg = GPTConfig(
        hidden_size=32, ffn_hidden_size=64, num_experts=16, expert_mode=True,
        top_k=2, gate="gshard", dtype=jnp.float32, capacity_factor=0.5,
        moe_dispatch="scatter",
    )
    layer = MoEMLP(cfg)
    x = jnp.asarray(np.random.RandomState(1).randn(8, 32, 32), jnp.float32)
    mesh = Mesh(np.array(eight_devices).reshape(1, 4, 2, 1, 1),
                ("pp", "dp", "fsdp", "cp", "mp"))
    with mesh, nn.logical_axis_rules(make_rules()):
        vars_ = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
        y, grads = jax.jit(
            jax.value_and_grad(
                lambda v: (layer.apply(v, x) ** 2).mean()
            )
        )(vars_)
    assert np.isfinite(float(y))
    gnorm = float(jnp.sqrt(sum(jnp.sum(g**2) for g in jax.tree.leaves(grads))))
    assert np.isfinite(gnorm) and gnorm > 0

    # capacity_factor=0.5 with top-2: capacity < demand, so drops must occur
    n, E = 8 * 32, 16
    capacity = max(1, int(0.5 * n * 2 / E))
    logits = jnp.asarray(np.random.RandomState(2).randn(n, E), jnp.float32)
    _, _, _, keep, _ = compute_routing_indices(logits, 2, capacity, "naive")
    dropped = int((~np.asarray(keep)).sum())
    assert dropped > 0, "tight capacity must drop tokens"
