"""End-to-end Trainer tests on the 8-device CPU mesh: loss goes down under
dp/mp/fsdp sharding, grad accumulation matches the big-batch step,
checkpoint save/load resumes exactly, and every topology of the N1C8 grid
reads the one-device run's losses."""

import os

import numpy as np
import pytest

from fleetx_tpu.core.engine import Trainer
from fleetx_tpu.models import build_module
from fleetx_tpu.utils.config import AttrDict, get_config
import textwrap


def _cfg(tmp_path, nranks=8, **over):
    text = textwrap.dedent(
        """
        Global:
          seed: 42
          local_batch_size: 4
          micro_batch_size: 4
        Engine:
          max_steps: 8
          logging_freq: 4
          eval_freq: 0
          eval_iters: 2
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
        Distributed:
          dp_degree: 2
          mp_degree: 2
          pp_degree: 1
          sharding:
            sharding_degree: 2
            sharding_stage: 2
        """
    )
    p = tmp_path / "cfg.yaml"
    p.write_text(text)
    cfg = get_config(str(p), overrides=[f"{k}={v}" for k, v in over.items()], nranks=nranks)
    cfg.Engine.save_load.output_dir = str(tmp_path / "output")
    return cfg


def _batches(cfg, n, seq=32, seed=0):
    """Synthetic LM data with a learnable pattern (next token = +1 mod V)."""
    rng = np.random.RandomState(seed)
    gbs = cfg.Global.global_batch_size
    vocab = cfg.Model.vocab_size
    out = []
    for _ in range(n):
        start = rng.randint(0, vocab, (gbs, 1))
        tokens = (start + np.arange(seq)[None, :]) % vocab
        labels = (tokens + 1) % vocab
        out.append(
            {
                "tokens": tokens.astype(np.int32),
                "labels": labels.astype(np.int32),
                "loss_mask": np.ones((gbs, seq), np.float32),
            }
        )
    return out


def _step_losses(cfg, steps):
    """Per-step losses of ``steps`` train steps of a fresh Trainer on
    ``_batches``' data."""
    import fleetx_tpu.parallel.env as dist_env

    trainer = Trainer(cfg, build_module(cfg))
    data = _batches(cfg, steps)
    trainer.init_state(data[0])
    step_fn = trainer._get("train", trainer._build_train_step)
    losses = []
    for i, b in enumerate(data):
        db = trainer._shard_batch(b)
        trainer.state, m = step_fn(trainer.state, db, dist_env.data_rank_key(i))
        losses.append(float(m["loss"]))
    return losses


@pytest.mark.slow  # 17.4s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_fit_loss_decreases(tmp_path, eight_devices):
    losses = _step_losses(_cfg(tmp_path), 8)
    assert losses[-1] < losses[0], losses
    assert np.isfinite(losses).all()


@pytest.mark.slow  # 10.1s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_fit_api_and_eval(tmp_path, eight_devices, capsys):
    cfg = _cfg(tmp_path)
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    data = _batches(cfg, 8)
    trainer.fit(data, valid_data=data[:2])
    assert int(trainer.state.step) == 8
    loss = trainer.evaluate(data[:2])
    assert np.isfinite(loss)


@pytest.mark.slow  # 12.2s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_grad_accumulation_matches_big_batch(tmp_path, eight_devices):
    """Accumulated grads (accum=2, micro=2) must equal the one-shot grads
    (accum=1, micro=4) on the same data. Compared pre-optimizer: Adam's
    sign-sensitivity would amplify benign reduction-order noise."""
    import jax
    from fleetx_tpu.core.engine import make_grad_fn, _unbox

    cfg1 = _cfg(tmp_path)
    cfg2 = _cfg(tmp_path)
    cfg2.Global.micro_batch_size = 2
    cfg2.Engine.accumulate_steps = 2
    data = _batches(cfg1, 1)

    def run(cfg):
        module = build_module(cfg)
        tr = Trainer(cfg, module)
        tr.init_state(data[0])
        fn = tr._in_context(jax.jit(make_grad_fn(module, tr.accumulate_steps)))
        db = tr._shard_batch(data[0])
        loss, grads = fn(tr.state.params, db, jax.random.PRNGKey(0))
        return float(loss), jax.tree.map(np.asarray, _unbox(grads))

    l1, g1 = run(cfg1)
    l2, g2 = run(cfg2)
    assert l1 == pytest.approx(l2, rel=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=1e-6)


@pytest.mark.slow  # 10.5s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_save_load_resume(tmp_path, eight_devices):
    import jax

    cfg = _cfg(tmp_path)
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    data = _batches(cfg, 4)
    trainer.fit(data)
    trainer.save(epoch=0)
    step_before = int(trainer.state.step)

    # fresh trainer restores
    module2 = build_module(cfg)
    trainer2 = Trainer(cfg, module2)
    trainer2.init_state(data[0])
    assert trainer2.load()
    assert int(trainer2.state.step) == step_before
    from fleetx_tpu.core.engine import _unbox

    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, _unbox(trainer.state.params))),
        jax.tree.leaves(jax.tree.map(np.asarray, _unbox(trainer2.state.params))),
    ):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("stage", [1, 3])
@pytest.mark.slow  # 14.2s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_sharding_stages_run(tmp_path, eight_devices, stage):
    cfg = _cfg(tmp_path)
    cfg.Distributed.sharding.sharding_stage = stage
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    data = _batches(cfg, 2)
    trainer.fit(data)
    assert int(trainer.state.step) == 2


@pytest.mark.slow  # 14.8s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_predict_matches_direct_forward(tmp_path, eight_devices):
    """Trainer.predict (reference eager_engine.py:502-632) feeds the serving
    contract and returns per-batch host logits equal to a direct apply."""
    import jax

    from fleetx_tpu.core.engine import _unbox

    cfg = _cfg(tmp_path)
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    data = _batches(cfg, 2)
    trainer.init_state(data[0])
    outs = trainer.predict(data[:2])
    assert len(outs) == 2
    gbs = cfg.Global.global_batch_size
    assert outs[0].shape == (gbs, 32, cfg.Model.vocab_size)

    params = jax.tree.map(np.asarray, _unbox(trainer.state.params))
    direct = module.nets.apply({"params": params}, data[0]["tokens"])
    np.testing.assert_allclose(outs[0], np.asarray(direct), rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # 19.1s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_profiler_window_and_summary(tmp_path, eight_devices):
    """Profiler config traces a [lo, hi] step window and then prints the
    summary views (reference eager_engine.py:761-820). Captured via a
    temporary handler: conftest runs tests at WARNING and the stream
    handler binds pre-capture stdout."""
    import io
    import logging

    from fleetx_tpu.utils.log import logger as fx_logger

    cfg = _cfg(tmp_path)
    cfg.Engine.max_steps = 5
    cfg.Profiler = AttrDict(
        enable=True,
        scheduler=[1, 3],
        profiler_log=str(tmp_path / "prof"),
        summary=AttrDict(overview=True, model=True, kernel=True, mem=True),
    )
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    data = _batches(cfg, 5)
    buf = io.StringIO()
    tap = logging.StreamHandler(buf)
    old_level = fx_logger.level
    fx_logger.addHandler(tap)
    fx_logger.setLevel(logging.INFO)
    try:
        trainer.fit(data)
    finally:
        fx_logger.setLevel(old_level)
        fx_logger.removeHandler(tap)
    text = buf.getvalue()
    assert "profiler overview" in text, text[:500]
    assert "model view" in text
    assert "memory view" in text
    assert "steps profiled" in text
    # ADVICE r3 #2: jit wrappers expose no cost_analysis — the model view
    # must go through the AOT Compiled object (cache-hit relower)
    assert "xla cost analysis" in text, text[:1500]
    # the jax CPU backend still writes a trace dir
    import os

    assert os.path.isdir(str(tmp_path / "prof"))


@pytest.mark.slow  # 8.3s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_preemption_sigterm_checkpoints_and_resumes(tmp_path, eight_devices):
    """SIGTERM mid-fit checkpoints the current step and exits cleanly; a
    fresh trainer resumes from it (TPU preemption path; the reference has
    no preemption handling)."""
    import os
    import signal

    cfg = _cfg(tmp_path)
    cfg.Engine.max_steps = 50

    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    data = _batches(cfg, 4)

    class SignalAfter:
        """Iterable that delivers SIGTERM to this process after 2 batches."""

        def __iter__(self):
            for i, b in enumerate(data * 20):
                if i == 2:
                    os.kill(os.getpid(), signal.SIGTERM)
                yield b

    trainer.fit(SignalAfter())
    assert trainer._preempted
    saved_step = int(trainer.state.step)
    assert 0 < saved_step < 50  # stopped early, not at max_steps

    module2 = build_module(cfg)
    trainer2 = Trainer(cfg, module2)
    trainer2.init_state(data[0])  # resumable dir -> restores in init_state
    assert int(trainer2.state.step) == saved_step


@pytest.mark.slow  # 10.0s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_sigterm_with_pending_async_save_finalizes(tmp_path, eight_devices):
    """SIGTERM arriving while a periodic async save is still in flight:
    the grace-window save must finalize BOTH checkpoints (no
    *.orbax-checkpoint-tmp debris) and resume must be step-exact."""
    import os
    import pathlib
    import signal

    cfg = _cfg(tmp_path)
    cfg.Engine.max_steps = 50
    cfg.Engine.save_load.save_steps = 2  # async save at step 2 ...

    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    data = _batches(cfg, 4)

    class SignalAfter:
        """Delivers SIGTERM right after the step-2 async save started."""

        def __iter__(self):
            for i, b in enumerate(data * 20):
                if i == 3:
                    os.kill(os.getpid(), signal.SIGTERM)
                yield b

    trainer.fit(SignalAfter())
    assert trainer._preempted
    saved_step = int(trainer.state.step)
    assert saved_step == 3  # preemption save, after the step-2 periodic one
    out = pathlib.Path(cfg.Engine.save_load.output_dir)
    leftovers = list(out.rglob("*.orbax-checkpoint-tmp*"))
    assert not leftovers, leftovers  # every async save finalized

    trainer2 = Trainer(cfg, build_module(cfg))
    trainer2.init_state(data[0])
    assert int(trainer2.state.step) == saved_step


@pytest.mark.slow  # 8.6s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_sentry_skip_resume_epoch_and_consumed_samples(tmp_path, eight_devices):
    """A sentry-skipped step still consumed its batch: after save/restore
    the resumed trainer reports the skipped batch in consumed_samples and
    the step counter reflects only applied updates."""
    from fleetx_tpu.resilience.faults import faults

    cfg = _cfg(tmp_path)
    cfg.Engine.max_steps = 4
    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    data = _batches(cfg, 5)
    faults.configure(nan_batch="2")
    try:
        trainer.fit(data)
    finally:
        faults.reset()
    assert trainer.sentry_skips == 1
    assert int(trainer.state.step) == 4  # 4 applied updates from 5 batches
    gbs = cfg.Global.global_batch_size
    assert trainer.consumed_samples == 5 * gbs
    trainer.save(epoch=0)

    trainer2 = Trainer(cfg, build_module(cfg))
    trainer2.init_state(data[0])  # resumable dir -> restores in init_state
    assert int(trainer2.state.step) == 4
    assert trainer2.consumed_samples == 5 * gbs  # skipped batch not re-fed
    assert trainer2.start_epoch == 0


# the N1C8 grid of the reference's CI (test_tipc), by its case names: the
# same data and seed under every topology, so parallelism may change the
# schedule and never the math
TOPOLOGIES = {
    "DP8-MP1-PP1": {"Distributed.dp_degree": 8},
    # mp > 1 runs sequence-sharded activations by default (utils/config.py);
    # the explicit-False twin keeps Megatron's all-reduce form alive
    "DP4-MP2-PP1": {"Distributed.dp_degree": 4, "Distributed.mp_degree": 2},
    "DP4-MP2-PP1-noSP": {"Distributed.dp_degree": 4,
                         "Distributed.mp_degree": 2,
                         "Model.sequence_parallel": False},
    "DP2-MP2-PP2": {"Distributed.dp_degree": 2, "Distributed.mp_degree": 2,
                    "Distributed.pp_degree": 2},
    "DP2-Sharding4": {"Distributed.dp_degree": 2,
                      "Distributed.sharding.sharding_degree": 4,
                      "Distributed.sharding.sharding_stage": 2},
    "DP4-CP2": {"Distributed.dp_degree": 4, "Distributed.cp_degree": 2},
    "DP8-Recompute": {"Distributed.dp_degree": 8,
                      "Model.use_recompute": True,
                      "Model.recompute_granularity": "core_attn"},
}
TOPOLOGY_BATCH = 16  # global, under every topology
TOPOLOGY_STEPS = 3
# the grid's own bound was 0.03 (it ran dropout 0.1 and a global batch that
# grew with dp); without dropout and at one global batch every case read
# 3e-7 of the one-device run at PR 45, so the bound is held 300x above that
TOPOLOGY_LOSS_RTOL = 1e-4


def _topology_losses(tmp_path, nranks, **over):
    """Per-step losses of TOPOLOGY_STEPS steps at the shared global batch."""
    data_world = (over.get("Distributed.dp_degree", 1)
                  * over.get("Distributed.sharding.sharding_degree", 1))
    layout = {"Distributed.dp_degree": 1, "Distributed.mp_degree": 1,
              "Distributed.sharding.sharding_degree": 1,
              "Global.local_batch_size": TOPOLOGY_BATCH // data_world,
              "Global.micro_batch_size": TOPOLOGY_BATCH // data_world}
    cfg = _cfg(tmp_path, nranks=nranks, **{**layout, **over})
    assert cfg.Global.global_batch_size == TOPOLOGY_BATCH
    return _step_losses(cfg, TOPOLOGY_STEPS)


@pytest.fixture(scope="module")
def one_device_losses(tmp_path_factory):
    return _topology_losses(tmp_path_factory.mktemp("one_device"), 1)


@pytest.mark.parametrize("case", list(TOPOLOGIES))
def test_losses_agree_across_topologies(tmp_path, eight_devices,
                                        one_device_losses, case):
    """Every step's loss under each 8-device topology is the one-device
    run's (the reference CI's N1C8 convergence contract)."""
    losses = _topology_losses(tmp_path, 8, **TOPOLOGIES[case])
    assert np.isfinite(losses).all(), losses
    np.testing.assert_allclose(losses, one_device_losses,
                               rtol=TOPOLOGY_LOSS_RTOL)
    assert losses[-1] < losses[0], losses  # and it trains


def _compiled_step_collectives(tmp_path, caplog, **over):
    """The dp2 x mp2 step of the tiny GPT-1.3B (the benchmark cell's CPU
    rehearsal size) after one step of ``fit``: the four counts of the gauge
    ``fleetx_train_step_collectives``, the first TRAIN line, and what
    ``Model.sequence_parallel`` resolved to."""
    import json
    import logging

    from fleetx_tpu.obs.registry import get_registry
    from fleetx_tpu.parallel.collective_matmul import COLLECTIVE_KINDS
    from fleetx_tpu.utils.log import logger

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "perfbench/configs/gpt-1.3b.json")) as f:
        tiny = json.load(f)["tiny"]["model"]
    tmp_path.mkdir()
    cfg = _cfg(tmp_path, nranks=4, **{
        **{f"Model.{k}": v for k, v in tiny.items()},
        "Distributed.dp_degree": 2, "Distributed.mp_degree": 2,
        "Distributed.sharding.sharding_degree": 1,
        "Global.local_batch_size": 4, "Global.micro_batch_size": 4,
        "Engine.max_steps": 2, "Engine.logging_freq": 1, **over})
    trainer = Trainer(cfg, build_module(cfg))
    caplog.clear()
    logger.propagate = True
    try:
        with caplog.at_level(logging.INFO, logger="fleetx_tpu"):
            trainer.fit(_batches(cfg, 2))
    finally:
        logger.propagate = False
    gauge = get_registry().gauge("fleetx_train_step_collectives",
                                 labelnames=("kind",))
    counts = {kind: int(gauge.labels(kind=kind).value)
              for kind in COLLECTIVE_KINDS}
    assert counts == trainer._step_collectives()
    lines = [r.message for r in caplog.records if "ips_total" in r.message]
    return counts, lines, bool(cfg.Model.sequence_parallel)


def test_default_layout_trades_all_reduces_for_gathers(tmp_path, eight_devices,
                                                       caplog):
    """The compiled dp2 x mp2 step, by the gauge: with the default layout
    (sequence-sharded activations between the tensor-parallel products) the
    four products of a block carry their own collectives
    (parallel/collective_matmul.py): collective-permutes, one a product in
    the forward loop and their mirrors in the backward one, where
    ``Model.sequence_parallel: False`` keeps the activation-sized
    all-reduces (which the CPU partitioner counts the same either way: it
    writes what is left of a reduce-scatter as an all-reduce and a slice;
    on the v5e compiler they fall from 16 to 11, PERF.md, PR 48). The four
    counts ride the first TRAIN line and no later one."""
    default, lines, resolved = _compiled_step_collectives(
        tmp_path / "default", caplog)
    assert resolved
    assert len(lines) == 2
    for kind, n in default.items():
        assert f", {kind}: {n}" in lines[0], lines[0]
        assert kind not in lines[1], lines[1]
    twin, _, resolved = _compiled_step_collectives(
        tmp_path / "twin", caplog, **{"Model.sequence_parallel": False})
    assert not resolved
    assert default["all-reduce"] <= twin["all-reduce"] + 2, (default, twin)
    assert (default["collective-permute"]
            >= twin["collective-permute"] + 8), (default, twin)
