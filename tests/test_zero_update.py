"""ZeRO weight-update sharding gates (ISSUE 12, arxiv 2004.13336).

The contract: FLEETX_ZERO_UPDATE=1 restructures the jitted train step as
reduce-scatter(grads) -> shard-local optax update -> all-gather(params),
with the optimizer state RESIDENT on the update shards. It is a layout
transformation, never a math change — final params after N steps must
match the unsharded step to tight fp32 tolerance on every mesh, the
sentry skip must stay byte-exact, donation must survive, and the
resident opt-state bytes must shrink by the dp*fsdp factor.

Compact dp gate + the spec unit tests are tier-1; the mesh-matrix
variants (fsdp stage-2, dp x mp, 8-device dp x fsdp x mp) ride the slow
tier per the PR 12 budget audit.
"""

import textwrap

import numpy as np
import pytest

from fleetx_tpu.core.engine import Trainer, _unbox
from fleetx_tpu.models import build_module
from fleetx_tpu.utils.config import get_config


def _cfg(tmp_path, nranks, name, dist_yaml, max_steps=3, **over):
    text = textwrap.dedent(
        """
        Global:
          seed: 42
          local_batch_size: 4
          micro_batch_size: 4
        Engine:
          max_steps: %d
          logging_freq: 100
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
          grad_clip:
            name: ClipGradByGlobalNorm
            clip_norm: 1.0
        """ % max_steps
    ) + textwrap.dedent(dist_yaml)
    p = tmp_path / f"{name}.yaml"
    p.write_text(text)
    cfg = get_config(
        str(p), overrides=[f"{k}={v}" for k, v in over.items()],
        nranks=nranks)
    cfg.Engine.save_load.output_dir = str(tmp_path / f"out_{name}")
    return cfg


def _batches(cfg, n, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    gbs = cfg.Global.global_batch_size
    vocab = cfg.Model.vocab_size
    out = []
    for _ in range(n):
        start = rng.randint(0, vocab, (gbs, 1))
        tokens = (start + np.arange(seq)[None, :]) % vocab
        out.append({
            "tokens": tokens.astype(np.int32),
            "labels": ((tokens + 1) % vocab).astype(np.int32),
            "loss_mask": np.ones((gbs, seq), np.float32),
        })
    return out


def _run(cfg, data, monkeypatch, zero, nan_batch=None):
    """Fit a fresh Trainer over ``data`` with FLEETX_ZERO_UPDATE pinned."""
    from fleetx_tpu.resilience.faults import faults

    monkeypatch.setenv("FLEETX_ZERO_UPDATE", zero)
    trainer = Trainer(cfg, build_module(cfg))
    if nan_batch is not None:
        faults.configure(nan_batch=nan_batch)
    try:
        trainer.fit(data)
    finally:
        if nan_batch is not None:
            faults.reset()
    return trainer


def _leaves(tree):
    import jax

    return [np.asarray(x) for x in jax.tree.leaves(
        jax.tree.map(np.asarray, _unbox(tree)))]


def _assert_close(a_tree, b_tree, atol=2e-6):
    for a, b in zip(_leaves(a_tree), _leaves(b_tree)):
        np.testing.assert_allclose(a, b, rtol=0, atol=atol)


MESHES = {
    # name -> (nranks, Distributed yaml)
    "dp4": (4, "Distributed:\n  dp_degree: 4\n"),
    "fsdp4-stage2": (4, (
        "Distributed:\n  dp_degree: 1\n  sharding:\n"
        "    sharding_degree: 4\n    sharding_stage: 2\n")),
    "dp2-mp2": (4, "Distributed:\n  dp_degree: 2\n  mp_degree: 2\n"),
    "dp2-fsdp2-mp2": (8, (
        "Distributed:\n  dp_degree: 2\n  mp_degree: 2\n  sharding:\n"
        "    sharding_degree: 2\n    sharding_stage: 2\n")),
}


def test_zero_update_spec_unit():
    """The shard-spec derivation: folds free dp/fsdp axes onto the first
    evenly-divisible dim, composes with existing mp sharding, leaves
    undividable leaves alone."""
    import jax

    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh
    from fleetx_tpu.parallel.sharding import zero_update_spec
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, mp=2), devs)
    # plain 2d param: dp*fsdp=4 folds onto dim 0
    assert zero_update_spec(P(), (8, 6), mesh) == P(("dp", "fsdp"), None)
    # mp-sharded dim composes: dp x fsdp land on the free dim
    assert zero_update_spec(P("mp", None), (4, 8), mesh) == \
        P("mp", ("dp", "fsdp"))
    # dim 0 not divisible by 4 but by 2 -> falls back to one axis
    assert zero_update_spec(P(), (6, 5), mesh) == P("dp", None)
    # nothing divides -> untouched (stays replicated)
    assert zero_update_spec(P(), (3, 5), mesh) == P()
    # scalars untouched
    assert zero_update_spec(P(), (), mesh) == P()
    # axes already used are not re-applied
    assert zero_update_spec(P(("dp", "fsdp")), (8, 8), mesh) == \
        P(("dp", "fsdp"))


def test_zero_update_spec_reshard_derivation():
    """ISSUE 20 reshard-on-load contract: ZeRO update layouts are
    RE-DERIVED from the restoring mesh, never assumed from the writer —
    the same leaf shape gets each mesh's own fold, and a leaf a bigger
    mesh sharded can fall back to replicated on a mesh it no longer
    divides (restore still works: the abstract restore reshards)."""
    import jax

    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh
    from fleetx_tpu.parallel.sharding import zero_update_spec
    from jax.sharding import PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh4 = build_mesh(MeshConfig(dp=4), devs[:4])
    mesh2 = build_mesh(MeshConfig(dp=2), devs[:2])
    mesh22 = build_mesh(MeshConfig(dp=2, fsdp=2), devs[:4])

    # dp4 -> dp2: same leaf, same fold target, different shard factor
    assert zero_update_spec(P(), (8, 6), mesh4) == P("dp", None)
    assert zero_update_spec(P(), (8, 6), mesh2) == P("dp", None)
    # (the specs PRINT alike but the mesh extents differ: 1/4 vs 1/2
    # shards — byte parity across the pair is gated in test_elastic.py)
    assert mesh4.shape["dp"] == 4 and mesh2.shape["dp"] == 2

    # dp2 x fsdp2 -> dp2: the product fold collapses to the single axis
    assert zero_update_spec(P(), (8, 6), mesh22) == P(("dp", "fsdp"), None)
    assert zero_update_spec(P(), (8, 6), mesh2) == P("dp", None)

    # undividable on the source mesh, dividable on the target (and the
    # reverse): each mesh derives its own answer from the same shape
    assert zero_update_spec(P(), (6, 5), mesh4) == P()       # 6 % 4 != 0
    assert zero_update_spec(P(), (6, 5), mesh2) == P("dp", None)
    assert zero_update_spec(P(), (4, 5), mesh4) == P("dp", None)
    assert zero_update_spec(P(), (2, 5), mesh22) == P("dp", None)  # 2%4!=0


@pytest.mark.slow  # 27.7s (PR 16 tier-1 budget audit): heaviest
# trainer gate; tier-1 keeps the spec/flag units here, the sentry
# NaN-skip byte parity single-device (tests/test_resilience.py), and
# this joins the mesh-matrix variants already behind the slow mark
def test_zero_update_parity_and_sentry_dp(tmp_path, eight_devices,
                                          monkeypatch):
    """Tier-1 compact gate on the dp4 mesh: (a) 3-step final params match
    the unsharded step (tight fp32 atol); (b) a NaN-batch sentry skip
    under ZeRO stays byte-identical to a clean run that never saw the
    batch (params AND opt state); (c) opt state lives dp-sharded and its
    resident bytes shrink ~4x; (d) the step's output shardings equal its
    input shardings, the precondition buffer donation needs."""
    import jax

    nranks, dist = MESHES["dp4"]
    data = _batches(_cfg(tmp_path, nranks, "probe", dist), 4)

    t_on = _run(_cfg(tmp_path, nranks, "on", dist), data[:3],
                monkeypatch, "1")
    assert t_on._zero_update
    t_off = _run(_cfg(tmp_path, nranks, "off", dist), data[:3],
                 monkeypatch, "0")
    assert not t_off._zero_update
    assert int(t_on.state.step) == int(t_off.state.step) == 3
    _assert_close(t_on.state.params, t_off.state.params)

    # (b) sentry-skip byte parity ON the sharded step: stream with a NaN
    # batch injected at index 1 vs the same stream without it
    t_clean = _run(_cfg(tmp_path, nranks, "clean", dist),
                   [data[0], data[2], data[3]], monkeypatch, "1")
    t_faulty = _run(_cfg(tmp_path, nranks, "faulty", dist, max_steps=3),
                    data, monkeypatch, "1", nan_batch="1")
    assert t_faulty.sentry_skips == 1
    for a, b in zip(_leaves(t_clean.state.params),
                    _leaves(t_faulty.state.params)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_leaves(t_clean.state.opt_state),
                    _leaves(t_faulty.state.opt_state)):
        np.testing.assert_array_equal(a, b)

    # (c) resident opt bytes shrink by ~dp (scalars stay replicated)
    on_bytes = t_on.opt_state_device_bytes()
    off_bytes = t_off.opt_state_device_bytes()
    assert on_bytes < 0.3 * off_bytes, (on_bytes, off_bytes)
    specs = {
        str(l.sharding.spec)
        for l in jax.tree.leaves(_unbox(t_on.state.opt_state))
        if hasattr(l, "sharding") and getattr(l, "ndim", 0) > 0
    }
    assert any("dp" in s for s in specs), specs
    # the live gauge reports the shrunk number
    from fleetx_tpu.obs import get_registry

    snap = get_registry().snapshot()
    gauge = snap["fleetx_train_opt_state_bytes"]["series"][0]["value"]
    assert gauge in (float(on_bytes), float(off_bytes),
                     float(t_clean.opt_state_device_bytes()),
                     float(t_faulty.opt_state_device_bytes()))

    # (d) donation precondition: out shardings == in shardings, leafwise
    sh = t_on._state_sharding_tree
    for leaf, want in zip(jax.tree.leaves(_unbox(t_on.state)),
                          jax.tree.leaves(sh)):
        if hasattr(leaf, "sharding"):
            assert leaf.sharding == want, (leaf.sharding, want)


@pytest.mark.slow  # mesh-matrix variants of the tier-1 dp gate
@pytest.mark.parametrize("mesh_name", ["fsdp4-stage2", "dp2-mp2",
                                       "dp2-fsdp2-mp2"])
def test_zero_update_parity_mesh_matrix(tmp_path, eight_devices,
                                        monkeypatch, mesh_name):
    """N-step param parity zero-on vs zero-off across fsdp (stage 2),
    dp x mp (4-device), and dp x fsdp x mp (8-device) meshes."""
    nranks, dist = MESHES[mesh_name]
    data = _batches(_cfg(tmp_path, nranks, "probe", dist), 3)
    t_on = _run(_cfg(tmp_path, nranks, "on", dist), data, monkeypatch, "1")
    assert t_on._zero_update
    t_off = _run(_cfg(tmp_path, nranks, "off", dist), data,
                 monkeypatch, "0")
    assert int(t_on.state.step) == int(t_off.state.step) == 3
    _assert_close(t_on.state.params, t_off.state.params)
    assert t_on.opt_state_device_bytes() < t_off.opt_state_device_bytes()


def test_overlap_flags_env_logic():
    """utils/xla_flags.py: the set goes to LIBTPU_INIT_ARGS (never
    XLA_FLAGS, whose parser aborts on TPU flag names), with the off gate,
    idempotence, and operator overrides winning — all on plain env dicts,
    no backend touched."""
    from fleetx_tpu.utils.xla_flags import (
        OVERLAP_FLAGS, apply_overlap_flags, overlap_flags_state,
    )

    # default on, whatever platform is coming up: only libtpu reads the
    # variable, so there is nothing to guess
    for env in ({}, {"JAX_PLATFORMS": "cpu"}, {"FLEETX_XLA_OVERLAP": "1"}):
        assert apply_overlap_flags(env) == list(OVERLAP_FLAGS)
        assert "XLA_FLAGS" not in env
        assert apply_overlap_flags(env) == []  # second call is a no-op
        state = overlap_flags_state(env)
        assert state["variable"] == "LIBTPU_INIT_ARGS"
        assert set(state["active"]) == set(OVERLAP_FLAGS)
    # forced off
    env = {"FLEETX_XLA_OVERLAP": "0"}
    assert apply_overlap_flags(env) == []
    assert overlap_flags_state(env)["active"] == []
    # an operator's explicit value for one flag is never overridden
    env = {"LIBTPU_INIT_ARGS":
           "--xla_tpu_enable_latency_hiding_scheduler=false"}
    added = apply_overlap_flags(env)
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" not in added
    assert "=false" in env["LIBTPU_INIT_ARGS"].split()[0]


@pytest.mark.slow  # 9.4s (PR 15 tier-1 budget audit): a perf-hygiene
# unit (memoized relowering), not output correctness — a regression
# shows up as per-window slowdown in the bench/mfu trajectory, and the
# gauges it feeds are asserted tier-1 in test_trainer's TRAIN-line test
def test_cost_analysis_cached_per_signature(tmp_path, monkeypatch):
    """Trainer.cost_analysis memoizes per compiled-step signature: the
    per-step mfu/hbm gauges must query the (cache-hit but still ms-cost)
    relower exactly once, not once per logging window."""
    cfg = _cfg(tmp_path, 1, "cost", "Distributed:\n  dp_degree: 1\n",
               max_steps=1)
    trainer = _run(cfg, _batches(cfg, 1), monkeypatch, "0")

    raw = trainer._compiled_raw["train"]
    calls = {"n": 0}
    real_lower = raw.lower

    def counting_lower(*a, **kw):
        calls["n"] += 1
        return real_lower(*a, **kw)

    monkeypatch.setattr(raw, "lower", counting_lower)
    trainer._flops_per_step = None  # force the gauges to (re)query
    trainer._hbm_bytes_per_step = None
    trainer._cost_cache.clear()
    c1 = trainer.cost_analysis("train")
    assert c1 is not None
    assert trainer._step_mfu(0.1) is None  # CPU: no peak on record
    trainer._step_hbm_bytes()
    c2 = trainer.cost_analysis("train")
    assert calls["n"] == 1, calls["n"]
    assert c1 is c2
