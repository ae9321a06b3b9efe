"""Chaos smoke driver: run the resilience scenarios end-to-end on CPU.

A CPU tool (``JAX_PLATFORMS=cpu``): the scenarios run engines in this
process and ``serving_http`` then spawns replica children from it, which
an accelerator forbids — a chip belongs to one process at a time, and a
parent that has touched jax holds it.

Exercises the fault-injection story outside pytest — one PASS/FAIL line
per scenario, non-zero exit on any failure:

- ``sentry``: a NaN-poisoned batch is skipped and the final params are
  byte-identical to a run that never saw it;
- ``sentry_zero``: the same contract on the ZeRO-sharded step
  (FLEETX_ZERO_UPDATE=1, dp mesh): the skip's rollback select runs on
  the 1/N update shards and params AND dp-sharded opt state stay
  byte-identical to the clean stream;
- ``ckpt``: the newest checkpoint is corrupted on disk, restore falls
  back to the prior step and quarantines the bad one;
- ``serving``: a bounded queue rejects, a queue-TTL expires to
  ``finish_reason="timeout"``, ``cancel()`` frees the slot, and a
  raising ``on_token`` callback retires only its own request while a
  clean request keeps one-shot parity;
- ``serving_recovery``: an injected decode-tick failure rolls the tick
  back and replay recovery resumes byte-identically (PagePool
  invariants checked);
- ``serving_poison``: a poison request is isolated by bisection and
  quarantined with partial tokens while neighbors keep byte parity;
- ``serving_hang``: a hung tick trips the FLEETX_SERVING_TICK_TIMEOUT_S
  watchdog, diagnostics are banked, recovery keeps parity;
- ``serving_drain``: shutdown() under load returns EVERY request with a
  terminal finish_reason (partials kept) and rejects new submits;
- ``serving_spec``: a fault injected during a SPECULATIVE verify call
  (``FLEETX_FAULT_TICK_RAISE`` — with ``FLEETX_SERVING_SPEC=1`` the
  verify call is the decode device call): the transactional rollback
  drops the un-verified draft (per-request draft counters included),
  replay recovery resumes with speculation still enabled, and the
  streams stay byte-identical to BOTH a clean speculative run and the
  non-speculative engine (tick_fault / engine_recovery / spec_enabled
  events asserted);
- ``serving_mesh``: a decode-tick fault on a MESH-SHARDED engine
  (``mesh=mp2`` — params TP-sharded, KV cache heads split over mp):
  rollback + ``recover()`` rebuild the SHARDED device state from host
  truth, streams stay byte-identical to a clean single-device engine,
  per-device cache bytes stay halved, and the ``engine_recovery`` event
  is banked (skips gracefully below 2 devices);
- ``serving_spill``: the two-level page cache under a mid-chunk fault —
  a warm prefix spills to the host-DRAM tier under pool pressure, a
  chunked-prefill request reviving it is killed mid-chunk, the tick
  rolls back and recovery requeues it, and the HOST TIER SURVIVES: the
  replayed request revives the same spilled pages again (inclusive
  store) and finishes byte-identical to one-shot ``generate()``
  (page_spill / page_revive / tick_fault / engine_recovery events
  asserted);
- ``router_kill``: a replica of a 3-replica ``ServingRouter`` is KILLED
  mid-burst (``FLEETX_FAULT_REPLICA_KILL``): every request still reaches
  exactly one terminal result, migrated requests resume on survivors
  BYTE-IDENTICAL to a clean single replica (zero token loss through the
  admit-with-history replay seam), the seeded-workload goodput score
  shows a latency blip but no lost requests, and ``replica_dead`` +
  ``request_migrated`` events are banked;
- ``router_saturation``: a router pushed PAST saturation (bounded queue
  + tight deadlines) degrades gracefully — over-bound submits reject
  with ``QueueFull``, expired queued requests shed as
  ``finish_reason="timeout"``, every accepted request still reaches
  exactly one terminal result, and the router keeps serving afterwards
  (never collapses);
- ``serving_http``: a REAL replica subprocess (``tools/serve.py``
  worker) is SIGKILLed while an OpenAI-compatible SSE stream is mid-
  flight: the front door's stream completes through the router's
  cross-process RPC migration, byte-identical to a clean in-process
  engine — zero tokens lost or duplicated — and ``replica_dead`` +
  ``request_migrated`` events are banked;
- ``serving_hetero``: a HETEROGENEOUS fleet (2 GPT + 2 ViT embedding
  replicas behind one model-aware router) with a GPT replica killed
  mid-stream AND an embedding replica killed mid-batch: every request
  of both families reaches exactly one terminal result, migrated GPT
  streams are byte-identical to a clean single replica, embedding bits
  match a lone-engine reference, and dispatch never crosses model
  families (asserted on every prompt each engine ever saw);
- ``train_elastic``: a dp4 training run LOSES A HOST at step 3
  (``FLEETX_FAULT_HOST_LOSS_STEP``): the elastic supervisor takes an
  emergency snapshot, shrinks the mesh dp4→dp2 (global batch held
  fixed), resumes through reshard-on-load, and the applied-loss
  trajectory over the post-shrink batches matches an uninterrupted dp2
  run — every batch consumed exactly once, none re-fed or skipped
  (skips gracefully below 4 devices).

Usage::

    JAX_PLATFORMS=cpu python tools/chaos_check.py [--only sentry,serving]

docs/RESILIENCE.md has the architecture; tests/test_resilience.py is the
full chaos suite these scenarios are distilled from.
"""

import argparse
import os
import shutil
import sys
import tempfile
import textwrap

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_TRAIN_YAML = textwrap.dedent(
    """
    Global:
      seed: 7
      local_batch_size: 2
      micro_batch_size: 2
    Engine:
      max_steps: 4
      logging_freq: 100
      eval_freq: 0
      eval_iters: 1
      save_load:
        save_steps: 1000
    Model:
      module: GPTModule
      vocab_size: 64
      hidden_size: 32
      num_layers: 1
      num_attention_heads: 2
      ffn_hidden_size: 64
      max_position_embeddings: 16
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
    """
)


def _cfg(tmp, name, nranks=1, **over):
    """Tiny trainer config rooted at ``tmp/name`` (nranks>1 derives a
    dp mesh over the first nranks devices)."""
    from fleetx_tpu.utils.config import get_config

    os.makedirs(tmp, exist_ok=True)
    path = os.path.join(tmp, "cfg.yaml")
    if not os.path.exists(path):
        with open(path, "w") as f:
            f.write(_TRAIN_YAML)
    cfg = get_config(path, nranks=nranks)
    for k, v in over.items():
        node = cfg
        *parents, leaf = k.split(".")
        for p in parents:
            node = node[p]
        node[leaf] = v
    cfg.Engine.save_load.output_dir = os.path.join(tmp, name)
    return cfg


def _batches(cfg, n, seed=0):
    """Synthetic next-token LM batches."""
    import numpy as np

    rng = np.random.RandomState(seed)
    gbs = cfg.Global.global_batch_size
    vocab = cfg.Model.vocab_size
    out = []
    for _ in range(n):
        start = rng.randint(0, vocab, (gbs, 1))
        tokens = (start + np.arange(16)[None, :]) % vocab
        out.append({
            "tokens": tokens.astype(np.int32),
            "labels": ((tokens + 1) % vocab).astype(np.int32),
            "loss_mask": np.ones((gbs, 16), np.float32),
        })
    return out


def _fit(cfg, data):
    """Train a fresh tiny Trainer over ``data``; returns the trainer."""
    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.models import build_module

    t = Trainer(cfg, build_module(cfg))
    t.fit(data)
    return t


def _params(trainer):
    import jax
    import numpy as np

    from fleetx_tpu.core.engine import _unbox

    return [np.asarray(x) for x in
            jax.tree.leaves(jax.tree.map(np.asarray,
                                         _unbox(trainer.state.params)))]


def scenario_sentry(tmp):
    """NaN batch skipped; params byte-identical to the clean stream; the
    skip banked its structured event (docs/OBSERVABILITY.md)."""
    import numpy as np

    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.resilience.faults import faults

    over = {"Engine.max_steps": 3}
    data = _batches(_cfg(tmp, "probe", **over), 4)
    clean = _fit(_cfg(tmp, "clean", **over), [data[0], data[2], data[3]])
    faults.configure(nan_batch="1")
    try:
        faulty = _fit(_cfg(tmp, "faulty", **over), data)
    finally:
        faults.reset()
    assert faulty.sentry_skips == 1, faulty.sentry_skips
    assert int(faulty.state.step) == int(clean.state.step) == 3
    for a, b in zip(_params(clean), _params(faulty)):
        assert np.array_equal(a, b), "params diverged after sentry skip"
    ev = get_event_log()
    assert ev.find("fault_injected", fault="nan"), "nan injection unbanked"
    skips = ev.find("sentry_skip")
    assert len(skips) == 1 and skips[0].attrs["step"] == 1, skips
    return "1 NaN step skipped, params byte-identical, sentry_skip banked"


def scenario_sentry_zero(tmp):
    """The PR 3 sentry parity contract on the ZeRO-SHARDED step (ISSUE
    12): under FLEETX_ZERO_UPDATE=1 on a dp mesh, a NaN-batch skip must
    leave sharded params AND opt state byte-identical to a run that
    never saw the batch — the in-jit rollback select operates on the
    1/N update shards, and the param all-gather must reproduce the
    exact prior bytes."""
    import jax
    import numpy as np

    from fleetx_tpu.resilience.faults import faults

    if jax.device_count() < 2:
        return ("skipped: needs >=2 devices for a dp mesh (run with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    from fleetx_tpu.core.engine import _unbox

    over = {"Engine.max_steps": 3}
    prev = os.environ.get("FLEETX_ZERO_UPDATE")
    os.environ["FLEETX_ZERO_UPDATE"] = "1"
    try:
        data = _batches(_cfg(tmp, "probe", nranks=2, **over), 4)
        clean = _fit(_cfg(tmp, "clean", nranks=2, **over),
                     [data[0], data[2], data[3]])
        faults.configure(nan_batch="1")
        try:
            faulty = _fit(_cfg(tmp, "faulty", nranks=2, **over), data)
        finally:
            faults.reset()
    finally:
        if prev is None:
            os.environ.pop("FLEETX_ZERO_UPDATE", None)
        else:
            os.environ["FLEETX_ZERO_UPDATE"] = prev
    assert clean._zero_update and faulty._zero_update, \
        "ZeRO update sharding was not active; the scenario tested nothing"
    assert faulty.sentry_skips == 1, faulty.sentry_skips
    assert int(faulty.state.step) == int(clean.state.step) == 3
    for a, b in zip(_params(clean), _params(faulty)):
        assert np.array_equal(a, b), "sharded params diverged after skip"
    for a, b in zip(
        jax.tree.leaves(jax.tree.map(np.asarray, clean.state.opt_state)),
        jax.tree.leaves(jax.tree.map(np.asarray, faulty.state.opt_state)),
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "sharded opt state diverged after skip"
    shards = {
        str(leaf.sharding.spec)
        for leaf in jax.tree.leaves(_unbox(faulty.state.opt_state))
        if hasattr(leaf, "sharding") and getattr(leaf, "ndim", 0) > 0
    }
    assert any("dp" in s for s in shards), (
        f"opt state is not dp-sharded under FLEETX_ZERO_UPDATE=1: {shards}")
    return ("NaN step skipped on the ZeRO-sharded step: params + "
            "dp-sharded opt state byte-identical to the clean stream")


def scenario_ckpt(tmp):
    """Corrupt newest checkpoint -> fallback restore + quarantine."""
    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.models import build_module

    cfg = _cfg(tmp, "ckpt", **{"Engine.max_steps": 4,
                               "Engine.save_load.save_steps": 2})
    data = _batches(cfg, 4)
    t1 = _fit(cfg, data)
    t1.wait_for_checkpoints()
    root = os.path.join(cfg.Engine.save_load.output_dir, "checkpoints")
    state_dirs = [os.path.join(root, "4", n)
                  for n in os.listdir(os.path.join(root, "4"))
                  if "state" in n]
    shutil.rmtree(state_dirs[0])  # the kill-between-save-and-finalize wound
    t2 = Trainer(cfg, build_module(cfg))
    t2.init_state(data[0])
    assert int(t2.state.step) == 2, int(t2.state.step)
    qdir = os.path.join(cfg.Engine.save_load.output_dir, "quarantine")
    assert os.path.isdir(qdir) and os.listdir(qdir)
    from fleetx_tpu.obs import get_event_log

    quar = get_event_log().find("checkpoint_quarantine", step=4)
    assert quar, "quarantine left no structured event"
    return "corrupt step 4 quarantined (event banked), resumed from step 2"


def scenario_serving(tmp):
    """Reject / TTL timeout / cancel / raising callback, plus parity."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fleetx_tpu.models.gpt.generation import GenerationConfig, generate
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
    from fleetx_tpu.resilience.faults import raising_on_token
    from fleetx_tpu.serving import QueueFull, ServingEngine

    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    gen_cfg = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                               pad_token_id=60, max_length=4)
    model = GPTForPretraining(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    eng = ServingEngine(model, params, slots=1, cache_len=16,
                        gen_cfg=gen_cfg, prefill_bucket=4, max_queue=1)
    clock = {"t": 0.0}
    eng._now = lambda: clock["t"]

    pa = np.asarray([1, 2, 3], np.int32)
    ra = eng.submit(pa, max_length=4)
    try:
        eng.submit(pa, max_length=4)
        raise AssertionError("bounded queue did not reject")
    except QueueFull:
        pass
    eng.step()  # ra admitted
    rb = eng.submit(np.asarray([4, 4, 4], np.int32), max_length=4,
                    queue_ttl_s=1.0)
    clock["t"] += 5.0
    eng.step()  # rb expires waiting
    res = eng.drain()
    assert res[rb].finish_reason == "timeout" and not len(res[rb].tokens)
    want = np.asarray(generate(model, params, jnp.asarray(pa[None]),
                               gen_cfg))[0][3:]
    assert np.array_equal(res[ra].tokens, want), "slot holder disturbed"

    rc = eng.submit(pa, max_length=8)
    eng.step()
    assert eng.cancel(rc) and eng.cache_manager.free_count == 1
    rd = eng.submit(pa, max_length=4,
                    on_token=raising_on_token(after_tokens=1))
    res = eng.drain()
    assert res[rc].finish_reason == "cancelled"
    assert res[rd].finish_reason == "error"
    re_ = eng.submit(pa, max_length=4)  # engine healthy after all that
    res = eng.drain()
    assert np.array_equal(res[re_].tokens, want)
    m = eng.metrics
    assert m.rejected == 1 and m.timeouts == 1 and m.cancels == 1 \
        and m.callback_errors == 1, m.snapshot()
    from fleetx_tpu.obs import get_event_log

    ev = get_event_log()
    assert ev.find("queue_reject"), "reject left no structured event"
    assert ev.find("request_timeout", request=rb), "timeout event missing"
    assert ev.find("request_cancelled", request=rc), "cancel event missing"
    assert ev.find("callback_error", request=rd), \
        "callback-error event missing"
    return ("reject/timeout/cancel/error all observed (each with its "
            "structured event), parity held "
            f"(rejected={m.rejected} timeouts={m.timeouts} "
            f"cancels={m.cancels} callback_errors={m.callback_errors})")


def _serving_fixture():
    """Tiny GPT + engine factory + mixed-length workload shared by the
    serving-recovery scenarios."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
    from fleetx_tpu.serving import ServingEngine

    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    gen_cfg = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                               pad_token_id=60, max_length=8)
    model = GPTForPretraining(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    prompts = [np.asarray([1, 2, 3], np.int32),
               np.asarray([4, 5, 6, 7, 8], np.int32),
               np.asarray([9, 10], np.int32),
               np.asarray([11, 12, 13], np.int32)]

    def make(**kw):
        return ServingEngine(model, params, slots=3, cache_len=32,
                             gen_cfg=gen_cfg, prefill_bucket=4, page_size=8,
                             **kw)

    return make, prompts


def _run_workload(eng, prompts, max_length=8):
    import numpy as np

    rids = [eng.submit(p, max_length=max_length) for p in prompts]
    res = eng.drain()
    return [np.asarray(res[r].tokens) for r in rids], res, rids


def scenario_serving_recovery(tmp):
    """Tick-raise -> rollback + replay recovery, byte parity."""
    import numpy as np

    from fleetx_tpu.resilience.faults import faults

    make, prompts = _serving_fixture()
    clean, _, _ = _run_workload(make(), prompts)
    faults.configure(tick_raise="1")
    try:
        eng = make()
        faulty, _, _ = _run_workload(eng, prompts)
    finally:
        faults.reset()
    assert eng.metrics.engine_recoveries == 1, eng.metrics.snapshot()
    assert all(np.array_equal(a, b) for a, b in zip(clean, faulty)), \
        "tokens diverged after recovery"
    eng.cache_manager.pool.check_invariants()
    from fleetx_tpu.obs import get_event_log

    ev = get_event_log()
    assert len(ev.find("engine_recovery")) == 1, \
        "the recovery must bank an engine_recovery event"
    assert len(ev.find("tick_fault")) == 1, "tick fault unbanked"
    return ("tick-raise recovered byte-identically "
            f"(engine_recoveries={eng.metrics.engine_recoveries}, "
            "events banked)")


def scenario_serving_poison(tmp):
    """Poison request bisected out; neighbors byte-identical."""
    import numpy as np

    from fleetx_tpu.resilience.faults import faults

    make, prompts = _serving_fixture()
    clean, _, _ = _run_workload(make(), prompts)
    faults.configure(poison_request="1")
    try:
        eng = make()
        _, res, rids = _run_workload(eng, prompts)
    finally:
        faults.reset()
    assert res[rids[1]].finish_reason == "error", res[rids[1]]
    assert len(res[rids[1]].tokens) >= 1, "partial tokens lost"
    for i in (0, 2, 3):
        assert np.array_equal(np.asarray(res[rids[i]].tokens), clean[i]), \
            f"neighbor {i} disturbed by quarantine"
    eng.cache_manager.pool.check_invariants()
    m = eng.metrics
    assert m.poison_retired == 1, m.snapshot()
    from fleetx_tpu.obs import get_event_log

    poison = get_event_log().find("poison_retired")
    assert len(poison) == 1 and poison[0].attrs["request"] == rids[1], (
        "poison quarantine must bank a poison_retired event naming the "
        f"culprit request; got {poison}")
    return (f"poison request {rids[1]} quarantined with partial tokens "
            f"(event banked) after {m.engine_recoveries} recoveries; "
            "3 neighbors byte-identical")


def scenario_serving_hang(tmp):
    """Hung tick -> watchdog timeout -> recovery, parity held."""
    import numpy as np

    from fleetx_tpu.resilience.faults import faults

    make, prompts = _serving_fixture()
    clean, _, _ = _run_workload(make(), prompts)
    eng = make()
    eng.submit(np.asarray([50, 51], np.int32), max_length=3)
    eng.drain()  # warm the decode jit: the budget is for steady-state ticks
    faults.configure(tick_hang=str(eng._fault_ticks + 1), tick_hang_s=2.0)
    try:
        eng.tick_timeout_s = 0.3
        faulty, _, _ = _run_workload(eng, prompts)
    finally:
        faults.reset()
    assert eng.hang_diagnostics is not None, "diagnostics not banked"
    assert eng.metrics.engine_recoveries >= 1
    assert all(np.array_equal(a, b) for a, b in zip(clean, faulty))
    from fleetx_tpu.obs import get_event_log

    assert get_event_log().find("tick_timeout"), \
        "watchdog left no tick_timeout event"
    return ("hung tick abandoned at 0.3s, diagnostics + tick_timeout "
            "event banked, recovery kept byte parity")


def scenario_serving_drain(tmp):
    """shutdown() under load: every request returns, partials kept."""
    import numpy as np

    from fleetx_tpu.serving import ShuttingDown

    make, prompts = _serving_fixture()
    eng = make()
    rids = [eng.submit(p, max_length=50) for p in prompts]
    eng.step()
    eng.step()
    res = eng.shutdown(grace_s=0.0)
    assert set(res) == set(rids), "a request vanished in shutdown"
    assert all(res[r].finish_reason == "shutdown" for r in rids)
    partials = sum(1 for r in rids if len(res[r].tokens))
    assert partials >= 3, "partial tokens lost in drain"
    try:
        eng.submit(prompts[0])
        raise AssertionError("draining engine accepted a submit")
    except ShuttingDown:
        pass
    assert eng.metrics.drain_rejects == 1
    from fleetx_tpu.obs import get_event_log

    ev = get_event_log()
    assert ev.find("shutdown"), "drain left no shutdown event"
    assert ev.find("drain_reject"), "drain reject left no event"
    return (f"shutdown returned {len(res)}/{len(rids)} requests "
            f"({partials} with partial tokens); admission rejected; "
            "shutdown + drain_reject events banked")


def scenario_serving_spec(tmp):
    """Fault during a speculative verify call: rollback drops the
    un-verified draft, recovery replays byte-identically with the
    speculative path still enabled."""
    import numpy as np

    from fleetx_tpu.resilience.faults import faults

    make, prompts = _serving_fixture()
    plain, _, _ = _run_workload(make(), prompts)
    clean_eng = make(spec=True, spec_k=4)
    clean, _, _ = _run_workload(clean_eng, prompts)
    # speculation must not move a byte even before any fault
    assert all(np.array_equal(a, b) for a, b in zip(plain, clean)), \
        "speculative engine diverged from the plain engine"
    faults.configure(tick_raise="1")  # the first verify attempt dies
    try:
        eng = make(spec=True, spec_k=4)
        faulty, _, _ = _run_workload(eng, prompts)
    finally:
        faults.reset()
    assert eng.metrics.engine_recoveries == 1, eng.metrics.snapshot()
    assert all(np.array_equal(a, b) for a, b in zip(clean, faulty)), \
        "tokens diverged after a mid-verify fault + recovery"
    eng.cache_manager.pool.check_invariants()
    snap = eng.metrics.snapshot()
    # the post-recovery engine kept speculating: drafts were proposed
    # and accepted across the fault, not silently disabled
    assert snap["spec_proposed_tokens"] > 0, snap
    assert snap["spec_tokens_per_tick_mean"] is not None, snap
    from fleetx_tpu.obs import get_event_log

    ev = get_event_log()
    assert ev.find("spec_enabled"), "speculation left no spec_enabled event"
    faults_banked = ev.find("tick_fault")
    assert faults_banked and not faults_banked[-1].attrs["during_prefill"], \
        "the injected verify fault was not banked as a decode-phase fault"
    assert ev.find("engine_recovery"), "recovery left no structured event"
    return ("mid-verify fault rolled back the un-verified draft; recovery "
            "replayed byte-identically with speculation still on "
            f"(acceptance_rate={snap['spec_acceptance_rate']:.2f}, "
            f"tokens_per_tick_mean={snap['spec_tokens_per_tick_mean']:.2f}, "
            "events banked)")


def scenario_serving_mesh(tmp):
    """Tick fault + recover() on an mp2-sharded engine: byte parity vs a
    clean single-device run, sharded rebuild, events banked."""
    import jax
    import numpy as np

    from fleetx_tpu.resilience.faults import faults

    if jax.device_count() < 2:
        return ("skipped: needs >=2 devices for an mp mesh (run with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh

    make, prompts = _serving_fixture()
    mesh = build_mesh(MeshConfig(mp=2), jax.devices()[:2])
    single = make()
    clean, _, _ = _run_workload(single, prompts)
    meshed, _, _ = _run_workload(make(mesh=mesh), prompts)
    assert all(np.array_equal(a, b) for a, b in zip(clean, meshed)), \
        "mesh-sharded engine diverged from the single-device engine"
    faults.configure(tick_raise="1")
    try:
        eng = make(mesh=mesh)
        faulty, _, _ = _run_workload(eng, prompts)
    finally:
        faults.reset()
    assert eng.metrics.engine_recoveries == 1, eng.metrics.snapshot()
    assert all(np.array_equal(a, b) for a, b in zip(clean, faulty)), \
        "tokens diverged after a fault + recovery on the mesh"
    eng.cache_manager.pool.check_invariants()
    # the REBUILT cache kept its per-device shard (heads / mp)
    single_bytes = single.cache_manager.cache_nbytes()
    mesh_bytes = eng.cache_manager.cache_nbytes()
    assert mesh_bytes < 0.55 * single_bytes, (
        f"recovered cache is {mesh_bytes}B/device vs {single_bytes}B "
        "single-device — the rebuild lost the mp shard")
    from fleetx_tpu.obs import get_event_log

    ev = get_event_log()
    assert ev.find("tick_fault"), "the injected fault was not banked"
    assert ev.find("engine_recovery"), "recovery left no structured event"
    snap = eng.metrics.snapshot()
    assert snap["mesh_devices"] == 2, snap
    return ("mp2 engine recovered byte-identically "
            f"(per-device cache {mesh_bytes}B vs {single_bytes}B "
            "single-device; engine_recovery event banked)")


def scenario_serving_spill(tmp):
    """Mid-chunk fault over the two-level page cache: rollback +
    requeue, host tier survives, revived pages reused, byte parity."""
    import numpy as np

    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.resilience.faults import faults
    from fleetx_tpu.serving import ServingEngine

    import jax
    import jax.numpy as jnp

    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    gen_cfg = GenerationConfig(decode_strategy="greedy", eos_token_id=10**6,
                               pad_token_id=60, max_length=4)
    model = GPTForPretraining(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    # smallest legal pool (4 usable pages) + chunked prefill + host tier
    eng = ServingEngine(model, params, slots=2, cache_len=32, gen_cfg=gen_cfg,
                        prefill_bucket=4, page_size=8,
                        num_pages=5, prefill_chunk=6,
                        host_cache_bytes=1 << 20)
    rng = np.random.RandomState(5)
    sys_a = rng.randint(1, 61, (16,)).astype(np.int32)
    sys_b = rng.randint(1, 61, (16,)).astype(np.int32)
    # populate A's prefix pages, then force their eviction -> host spill
    for pre in (sys_a, sys_b):
        p = np.concatenate([pre, rng.randint(1, 61, (3,))]).astype(np.int32)
        eng.submit(p, max_length=4)
        eng.drain()
    ev = get_event_log()
    assert ev.find("page_spill"), "pool pressure never spilled a page"
    store = eng._host_store
    assert len(store) > 0 and store.spilled_pages > 0
    # the victim: an A-prefixed prompt whose suffix chunks (10 > 6);
    # its alloc revives A from host, then its FINAL chunk is killed
    victim = np.concatenate(
        [sys_a, rng.randint(1, 61, (10,))]).astype(np.int32)
    want = _run_workload(  # byte-parity reference from a clean engine
        ServingEngine(model, params, slots=2, cache_len=32, gen_cfg=gen_cfg,
                      prefill_bucket=4, page_size=8,
                      num_pages=5, prefill_chunk=6,
                      host_cache_bytes=1 << 20), [victim], 4)[0][0]
    revived_before = store.revived_pages
    faults.configure(prefill_raise=str(eng._fault_prefills + 1))
    try:
        rid = eng.submit(victim, max_length=4)
        res = eng.drain()
    finally:
        faults.reset()
    assert eng.metrics.engine_recoveries == 1, eng.metrics.snapshot()
    assert eng._host_store is store, "recovery replaced the host store"
    assert eng.cache_manager.pool.host_store is store, \
        "rebuilt pool not re-threaded onto the surviving host tier"
    assert len(store) > 0, "host tier lost its entries across recovery"
    # the replayed (requeued) request revived A's spilled pages AGAIN —
    # once before the fault, once after recovery (inclusive store)
    assert store.revived_pages >= revived_before + 4, (
        f"revived {store.revived_pages} vs {revived_before} before: the "
        "replayed request did not reuse the host tier")
    assert np.array_equal(res[rid].tokens, want), \
        "tokens diverged after mid-chunk fault + host-tier revival"
    eng.cache_manager.pool.check_invariants()
    assert ev.find("page_revive"), "revival left no structured event"
    fault_evs = ev.find("tick_fault")
    assert fault_evs and fault_evs[-1].attrs["during_prefill"], \
        "the injected fault was not banked as a prefill-phase tick_fault"
    assert ev.find("engine_recovery"), "recovery left no structured event"
    m = eng.metrics.snapshot()
    return ("mid-chunk fault rolled back; host tier survived recovery "
            f"(spilled={m['host_spilled_pages']} "
            f"revived={m['host_revived_pages']} "
            f"bytes={m['host_cache_bytes']}); replayed request reused "
            "revived pages, byte parity held, events banked")


def scenario_router_kill(tmp):
    """A replica killed mid-burst: zero-token-loss migration, exactly
    one terminal result per request, byte parity vs a clean single
    replica, goodput shows a blip but no lost requests."""
    import numpy as np

    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.resilience.faults import faults
    from fleetx_tpu.serving import (
        ServingRouter,
        TenantSpec,
        WorkloadSpec,
        generate_trace,
        run_trace,
        score_goodput,
        trace_hash,
    )

    make, prompts = _serving_fixture()
    # clean single-replica reference streams (batch composition never
    # changes greedy tokens, so one engine is THE reference)
    clean, _, _ = _run_workload(make(), prompts)
    streams = {}

    def cb(rid, tok, fin):
        streams.setdefault(rid, []).append(int(tok))

    faults.configure(replica_kill="1:3")
    try:
        router = ServingRouter([make() for _ in range(3)],
                               probe_every=1)
        rids = [router.submit(p, max_length=8, on_token=cb)
                for p in prompts]
        res = router.drain(max_ticks=500)
    finally:
        faults.reset()
    assert len(res) == len(prompts), (
        f"{len(prompts)} submitted, {len(res)} terminal results — "
        "requests were lost or duplicated")
    for i, rid in enumerate(rids):
        assert np.array_equal(np.asarray(res[rid].tokens), clean[i]), (
            f"request {rid} diverged from the clean single replica "
            "after the kill")
        assert streams[rid] == list(clean[i]), (
            f"request {rid} callback stream has lost/duplicated tokens")
    ev = get_event_log()
    dead = ev.find("replica_dead", replica=1)
    assert dead, "replica death left no replica_dead event"
    migrated = ev.find("request_migrated")
    assert migrated, "failover left no request_migrated event"
    assert ev.find("fault_injected", fault="replica_kill"), \
        "kill injection left no fault_injected event"
    m = router.metrics.snapshot()
    assert m["replica_deaths"] == 1 and m["migrated"] >= 1, m
    # the goodput view of the same story: a seeded trace over a freshly
    # killed router — the kill is a latency blip, never a lost request
    spec = WorkloadSpec(seed=11, n_requests=8, arrival_rate=200.0,
                        vocab=61,
                        tenants=(TenantSpec("burst", prompt_len=(3, 6),
                                            gen_len=(4, 8)),))
    trace = generate_trace(spec)
    faults.configure(replica_kill="0:4")
    try:
        router2 = ServingRouter([make() for _ in range(3)],
                                probe_every=1)
        score = score_goodput(run_trace(router2, trace))
    finally:
        faults.reset()
    assert score["completed_frac"] == 1.0, (
        f"kill lost requests under the seeded workload: {score}")
    return (f"kill at tick 3 migrated {m['migrated']} request(s) "
            f"byte-identically ({len(prompts)}/{len(prompts)} exactly-one-"
            f"result); workload {trace_hash(trace)} goodput "
            f"{score['goodput']} with ttft_p99 {score['ttft_ms_p99']:.0f}ms"
            " blip, zero lost")


def scenario_router_saturation(tmp):
    """Past-saturation load: bounded-queue rejects + deadline sheds,
    every accepted request exactly one terminal result, router alive."""
    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.resilience.faults import faults  # noqa: F401 (reset)
    from fleetx_tpu.serving import QueueFull, ServingRouter

    make, prompts = _serving_fixture()
    router = ServingRouter([make()], max_queue=6)
    accepted, rejected = [], 0
    # a burst far past one 3-slot replica: the bounded queue must reject
    # the overflow, and the tight-deadline stragglers must shed as
    # timeouts instead of waiting forever
    for i in range(12):
        kw = {"deadline_s": 1e-6} if i in (4, 5) else {}
        try:
            accepted.append(router.submit(prompts[i % len(prompts)],
                                          max_length=8, **kw))
        except QueueFull:
            rejected += 1
    res = router.drain(max_ticks=500)
    assert rejected > 0, "queue bound never rejected under a 12-burst"
    assert len(res) == len(accepted), (
        f"{len(accepted)} accepted, {len(res)} terminal results")
    reasons = {r: res[r].finish_reason for r in res}
    assert any(v == "timeout" for v in reasons.values()), (
        f"tight deadlines never shed: {reasons}")
    assert all(v in ("eos", "max_length", "timeout")
               for v in reasons.values()), reasons
    ev = get_event_log()
    assert ev.find("queue_reject"), "rejects left no queue_reject event"
    assert ev.find("request_timeout"), "sheds left no request_timeout event"
    # never collapses: the router serves normally after the storm
    rid = router.submit(prompts[0], max_length=8)
    after = router.drain(max_ticks=200)
    assert after[rid].finish_reason in ("eos", "max_length")
    m = router.metrics.snapshot()
    return (f"12-burst on a 3-slot replica: {rejected} rejected, "
            f"{sum(v == 'timeout' for v in reasons.values())} shed, "
            f"{sum(v != 'timeout' for v in reasons.values())} completed, "
            f"exactly-one-result held ({m['finished']} finished), router "
            "alive after the storm")


def scenario_serving_disagg(tmp):
    """Disaggregated prefill/decode under fire: a prefill replica
    killed mid-export AND a corrupted shipped page — both fall back to
    the replay ladder, byte parity vs a clean colocated run holds, and
    the ship/fallback events are banked."""
    import numpy as np

    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.resilience.faults import faults
    from fleetx_tpu.serving import ServingRouter

    make, prompts = _serving_fixture()
    clean, _, _ = _run_workload(make(), prompts)

    def run_router(router):
        rids = [router.submit(p, max_length=8) for p in prompts]
        res = router.drain(max_ticks=500)
        assert len(res) == len(prompts), (
            f"{len(prompts)} submitted, {len(res)} terminal results")
        return [np.asarray(res[r].tokens) for r in rids]

    # 1) clean disaggregated pass: 1 prefill + 1 decode == colocated
    router = ServingRouter([make(role="prefill"),
                            make(role="decode")], probe_every=1)
    got = run_router(router)
    assert all(np.array_equal(a, b) for a, b in zip(clean, got)), \
        "disaggregated tokens diverged from colocated"
    pre = router._replicas[0].engine
    dec = router._replicas[1].engine
    shipped = pre.metrics.kv_pages_shipped
    assert shipped > 0 and dec.metrics.kv_pages_revived_remote == shipped
    ev = get_event_log()
    assert ev.find("kv_shipped"), "handoffs left no kv_shipped event"
    assert ev.find("kv_revived_remote"), "no kv_revived_remote event"

    # 2) corrupt one shipped page: the decode replica's wire checksum
    #    rejects it at admit, the request replays — same bytes out
    faults.configure(kv_ship_corrupt="1")
    try:
        got = run_router(ServingRouter(
            [make(role="prefill"), make(role="decode")],
            probe_every=1))
    finally:
        faults.reset()
    assert all(np.array_equal(a, b) for a, b in zip(clean, got)), \
        "corrupted ship diverged after replay fallback"
    failed = ev.find("kv_ship_failed")
    assert any(e.attrs.get("where") == "admit" for e in failed), \
        "corrupt blob left no admit-side kv_ship_failed event"
    assert ev.find("fault_injected", fault="kv_ship_corrupt")

    # 3) kill the prefill replica mid-run: every parked/queued request
    #    migrates to the decode replica and replays — zero tokens lost
    faults.configure(replica_kill="0:3")
    try:
        got = run_router(ServingRouter(
            [make(role="prefill"), make(role="decode")],
            probe_every=1, probe_max_failures=1))
    finally:
        faults.reset()
    assert all(np.array_equal(a, b) for a, b in zip(clean, got)), \
        "prefill-replica kill diverged after migration replay"
    assert ev.find("replica_dead", replica=0), "no replica_dead event"
    n_fail = len(ev.find("kv_ship_failed"))
    return (f"disaggregated 1P+1D byte-identical to colocated "
            f"({shipped} pages shipped); corrupt ship + prefill kill "
            f"both replayed to parity ({n_fail} kv_ship_failed "
            "fallback(s) banked)")


def scenario_serving_http(tmp):
    """A replica PROCESS SIGKILLed mid-SSE-stream: the OpenAI front
    door's stream completes through router migration over the replica
    RPC — byte-identical to a clean in-process engine, zero tokens
    lost or duplicated."""
    import json
    import urllib.request

    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.serving import ServingRouter
    from fleetx_tpu.serving.api.replica_client import ReplicaClient
    from fleetx_tpu.serving.api.server import ApiServer
    from tools.serve import _build_demo_engine, _spawn_replicas

    os.makedirs(tmp, exist_ok=True)
    gen_len = 20
    # clean reference: the same demo engine serve.py replicas build
    eng = _build_demo_engine()
    rid = eng.submit([1, 2, 3], max_length=gen_len)
    clean = [int(t) for t in eng.drain()[rid].tokens]
    assert len(clean) == gen_len

    procs, urls = _spawn_replicas(2, grace_s=5.0, tmpdir=tmp)
    api = None
    try:
        clients = [ReplicaClient(u, connect_wait_s=60) for u in urls]
        router = ServingRouter(clients, probe_every=1)
        api = ApiServer(router, model_id="fleetx-demo").start()
        req = urllib.request.Request(
            api.url + "/v1/chat/completions",
            json.dumps({"model": "fleetx-demo", "stream": True,
                        "max_tokens": gen_len,
                        "messages": [{"role": "user",
                                      "content": "1 2 3"}]}).encode(),
            {"Content-Type": "application/json"})
        toks, finish, killed = [], None, None
        with urllib.request.urlopen(req, timeout=120) as resp:
            for line in resp:
                line = line.decode().strip()
                if not line.startswith("data: ") or line[6:] == "[DONE]":
                    continue
                chunk = json.loads(line[6:])
                if "token" in chunk:
                    toks.append(chunk["token"])
                if chunk["choices"][0]["finish_reason"]:
                    finish = chunk["choices"][0]["finish_reason"]
                if len(toks) == 3 and killed is None:
                    # find the replica actually decoding this stream and
                    # SIGKILL its whole process mid-flight
                    for i, c in enumerate(clients):
                        if c.health().get("active", 0) > 0:
                            killed = i
                            procs[i].kill()
                            break
                    assert killed is not None, "no replica was active"
        assert killed is not None, "stream finished before the kill fired"
        assert toks == clean, (
            f"stream diverged after replica-process kill: {toks} != {clean}"
            " (token lost or duplicated)")
        assert finish == "length", finish
        ev = get_event_log()
        assert ev.find("replica_dead", replica=killed), \
            "process kill left no replica_dead event"
        assert ev.find("request_migrated"), "no request_migrated event"
    finally:
        if api is not None:
            api.stop()
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)
    return (f"replica process {killed} SIGKILLed after 3 tokens; SSE "
            f"stream completed {len(toks)}/{gen_len} tokens byte-"
            "identical through RPC migration (zero loss/dup)")


def scenario_serving_hetero(tmp):
    """Heterogeneous fleet under fire: a GPT replica killed mid-stream
    AND an embedding replica killed mid-batch in the SAME router —
    every request of both families still reaches exactly one terminal
    result, migrated GPT streams stay byte-identical to a clean single
    replica, and dispatch never crosses model families."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fleetx_tpu.models.vision.vit import ViT, ViTConfig
    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.resilience.faults import faults
    from fleetx_tpu.serving import (
        EmbeddingEngine,
        ServingRouter,
        decode_floats,
        encode_floats,
    )

    make, prompts = _serving_fixture()
    clean, _, _ = _run_workload(make(), prompts)

    vcfg = ViTConfig(image_size=8, patch_size=4, in_channels=3,
                     num_classes=0, hidden_size=32, num_layers=2,
                     num_attention_heads=2, drop_rate=0.0,
                     attn_drop_rate=0.0, dtype=jnp.float32,
                     use_flash_attention=False)
    vit = ViT(vcfg)
    shape = (8, 8, 3)
    vit_vars = jax.jit(vit.init)(jax.random.PRNGKey(1),
                                 np.zeros((1,) + shape, np.float32))
    rng = np.random.RandomState(7)
    images = [rng.rand(*shape).astype(np.float32) for _ in range(4)]

    def make_emb():
        return EmbeddingEngine(vit, vit_vars, slots=2)

    # clean embedding bits from a lone engine — the determinism
    # reference the post-kill fleet must reproduce
    ref_emb = make_emb()
    ref_rids = [ref_emb.submit(encode_floats(img)) for img in images]
    ref_res = ref_emb.drain()
    ref_bits = [np.asarray(ref_res[r].tokens) for r in ref_rids]

    # every prompt each engine ever sees, for the cross-model gate: GPT
    # prompts are a few tokens, embedding prompts are H*W*C=192 wire
    # ints — a single misrouted request is unambiguous in these logs
    seen = {"gpt": [], "vit": []}

    def tap(eng, fam):
        orig = eng.submit

        def submit(prompt, **kw):
            seen[fam].append(int(np.asarray(prompt).size))
            return orig(prompt, **kw)

        eng.submit = submit
        return eng

    # fleet layout: replicas 0-1 GPT, 2-3 embedding. Kill the embedding
    # replica 2 at tick 1 — its coalesced batch is dispatched but has
    # not run yet, so the whole in-flight batch must migrate — and GPT
    # replica 1 at tick 3, mid-stream with tokens already emitted.
    faults.configure(replica_kill="2:1,1:3")
    try:
        router = ServingRouter(
            [tap(make(), "gpt"), tap(make(), "gpt"),
             tap(make_emb(), "vit"), tap(make_emb(), "vit")],
            probe_every=1)
        rids = []  # (family, index, rid)
        for i, (p, img) in enumerate(zip(prompts, images)):
            rids.append(("gpt", i, router.submit(p, max_length=8,
                                                 model="gpt")))
            rids.append(("vit", i, router.submit(encode_floats(img),
                                                 model="vit")))
        res = router.drain(max_ticks=500)
    finally:
        faults.reset()
    assert len(res) == len(rids), (
        f"{len(rids)} submitted, {len(res)} terminal results — "
        "requests were lost or duplicated")
    for fam, i, rid in rids:
        if fam == "gpt":
            assert np.array_equal(np.asarray(res[rid].tokens), clean[i]), (
                f"GPT request {rid} diverged from the clean single "
                "replica after the mid-stream kill")
        else:
            assert res[rid].finish_reason == "complete", res[rid]
            assert np.array_equal(np.asarray(res[rid].tokens),
                                  ref_bits[i]), (
                f"embedding request {rid} bits diverged after the "
                "mid-batch kill")
            assert decode_floats(res[rid].tokens).size == vcfg.hidden_size
    # cross-model gate: no GPT engine ever saw an image-sized prompt
    # and no embedding engine ever saw a text-sized one
    img_elems = int(np.prod(shape))
    assert seen["gpt"] and all(n < 16 for n in seen["gpt"]), seen["gpt"]
    assert seen["vit"] and all(n == img_elems for n in seen["vit"]), \
        seen["vit"]
    ev = get_event_log()
    for replica in (1, 2):
        assert ev.find("fault_injected", fault="replica_kill",
                       replica=replica), \
            f"kill injection on replica {replica} left no event"
        assert ev.find("replica_dead", replica=replica), \
            f"replica {replica} death left no replica_dead event"
    assert ev.find("request_migrated"), "failover left no request_migrated"
    m = router.metrics.snapshot()
    assert m["replica_deaths"] == 2 and m["migrated"] >= 2, m
    groups = router.models()
    assert groups["gpt"]["live"] == 1 and groups["vit"]["live"] == 1, groups
    return (f"killed GPT replica 1 mid-stream + embedding replica 2 "
            f"mid-batch; {len(rids)}/{len(rids)} exactly-one-result, "
            f"{m['migrated']} migrated, GPT byte-identical, embedding "
            f"bits identical, zero cross-model dispatches "
            f"({len(seen['gpt'])} gpt / {len(seen['vit'])} vit submits)")


def scenario_serving_qos(tmp):
    """Per-tenant QoS under fire: a flooding tenant saturates the fleet,
    a priority tenant preempts its way in, and a replica is SIGKILLed
    right in the middle of the preemption churn — the priority tenant's
    streams stay byte-identical to a clean uncontended engine, every
    preempted flood request still finishes byte-identically (zero-loss
    preemption across the kill), and all shed stays on the flood lane."""
    import numpy as np

    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.resilience.faults import faults
    from fleetx_tpu.serving import QueueFull, ServingRouter, TenantPolicy

    make, prompts = _serving_fixture()
    flood_prompts = [np.asarray([20 + j, 25, 30 + j], np.int32)
                     for j in range(16)]
    # clean references from a lone uncontended engine: greedy decode is
    # batch-composition-invariant, so these are THE bytes every tenant
    # must reproduce through preemption, migration, and the kill
    clean_paid, _, _ = _run_workload(make(), prompts)
    flood_ref = {}
    ref = make()
    for j, p in enumerate(flood_prompts):
        flood_ref[j] = ref.submit(p, max_length=16)
    ref_res = ref.drain()
    clean_flood = {j: np.asarray(ref_res[r].tokens)
                   for j, r in flood_ref.items()}

    faults.configure(replica_kill="1:6")
    try:
        router = ServingRouter(
            [make(max_queue=1) for _ in range(2)],
            tenants={"paid": TenantPolicy(weight=4.0, priority=1),
                     "flood": TenantPolicy(weight=1.0, max_queue=4)},
            probe_every=1, preempt_risk_frac=0.0)
        # flood in rounds so dispatch keeps both replicas' slots AND
        # engine queues pinned full while the lane holds a backlog —
        # long generations (16 tokens) keep them busy past the kill
        flood_rids, rejected = {}, 0
        fi = iter(range(len(flood_prompts)))
        for _ in range(4):
            for j in (next(fi), next(fi), next(fi), next(fi)):
                try:
                    flood_rids[j] = router.submit(
                        flood_prompts[j], max_length=16, tenant="flood")
                except QueueFull:
                    rejected += 1
            router.step()
        # the priority tenant arrives into a saturated fleet: a generous
        # total deadline arms preemption (risk_frac=0.0 -> any capacity
        # refusal preempts a lower-priority victim) without shed risk
        paid_rids = [router.submit(p, max_length=8, tenant="paid",
                                   deadline_s=120.0) for p in prompts]
        res = router.drain(max_ticks=500)
    finally:
        faults.reset()
    accepted = len(flood_rids) + len(paid_rids)
    assert len(res) == accepted, (
        f"{accepted} accepted, {len(res)} terminal results — requests "
        "were lost or duplicated")
    assert rejected > 0, "the flood never overflowed its bounded lane"
    for i, rid in enumerate(paid_rids):
        assert res[rid].finish_reason in ("eos", "max_length"), (
            f"priority request {rid} shed under flood: "
            f"{res[rid].finish_reason}")
        assert np.array_equal(np.asarray(res[rid].tokens), clean_paid[i]), (
            f"priority request {rid} diverged from the clean "
            "uncontended engine")
    for j, rid in flood_rids.items():
        assert np.array_equal(np.asarray(res[rid].tokens),
                              clean_flood[j]), (
            f"flood request {rid} diverged after preemption/kill — "
            "preemption lost or duplicated tokens")
    ev = get_event_log()
    preempted = ev.find("request_preempted")
    assert preempted, "saturated fleet + priority deadline never preempted"
    assert all(e.attrs["tenant"] == "flood" for e in preempted), (
        "a non-flood request was preempted: "
        f"{[e.attrs for e in preempted]}")
    assert ev.find("replica_dead", replica=1), "the kill never landed"
    assert ev.find("fault_injected", fault="replica_kill")
    assert ev.find("request_migrated"), "no request_migrated event"
    m = router.metrics.snapshot()
    assert m["preempted"] >= 1 and m["replica_deaths"] == 1, m
    return (f"flood saturated 2 replicas ({rejected} lane rejects); "
            f"{len(preempted)} preemption(s), replica 1 SIGKILLed at "
            f"tick 6 mid-churn; {len(paid_rids)}/{len(paid_rids)} "
            f"priority + {len(flood_rids)}/{len(flood_rids)} flood "
            "streams byte-identical, shed confined to the flood lane")


def scenario_train_elastic(tmp):
    """Host loss mid-training -> elastic shrink -> reshard-on-load parity.

    A dp4 run (global batch 8) loses a host before step 3 runs
    (``FLEETX_FAULT_HOST_LOSS_STEP=3``); the elastic supervisor
    (resilience/elastic.py) snapshots at step 3, shrinks the mesh to dp2
    with the global batch held fixed (local batch 2 -> 4), resumes
    through reshard-on-load, and finishes the run. The applied-loss
    trajectory over the post-shrink batches must match an uninterrupted
    dp2 run over the same 6 batches at tight fp32 atol (dp4 vs dp2
    differ only in reduction order; FLEETX_THREEFRY_PARTITIONABLE makes
    init mesh-independent), with every batch consumed exactly once —
    the aborted step's batch is re-fed once, nothing else re-fed or
    skipped."""
    import jax
    import numpy as np

    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.resilience.faults import faults

    if jax.device_count() < 4:
        return ("skipped: needs >=4 devices for the dp4 mesh (run with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.models import build_module
    from fleetx_tpu.resilience.elastic import run_elastic
    from fleetx_tpu.utils.config import get_config

    STEPS, GBS = 6, 8

    def cfg_for(name, nranks, local_batch):
        # the shared _cfg rig bakes local_batch_size=2; the dp2 runs here
        # need local_batch 4 so every mesh sees the SAME global batch of 8
        d = os.path.join(tmp, name + "_cfg")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, "cfg.yaml")
        text = _TRAIN_YAML.replace(
            "local_batch_size: 2", f"local_batch_size: {local_batch}"
        ).replace("micro_batch_size: 2", f"micro_batch_size: {local_batch}")
        with open(path, "w") as f:
            f.write(text)
        cfg = get_config(path, nranks=nranks)
        cfg.Engine.max_steps = STEPS
        cfg.Engine.logging_freq = 1  # per-step loss capture
        cfg.Engine.save_load.output_dir = os.path.join(tmp, name)
        return cfg

    def recording_trainer(cfg, sink):
        module = build_module(cfg)
        module.training_step_end = lambda log: sink.append(float(log["loss"]))
        return Trainer(cfg, module)

    cfg_ref = cfg_for("ref", nranks=2, local_batch=4)
    data = _batches(cfg_ref, STEPS)
    assert cfg_ref.Global.global_batch_size == GBS

    ref_losses = []
    ref = recording_trainer(cfg_ref, ref_losses)
    ref.fit(data)
    assert len(ref_losses) == STEPS

    cfg_el = cfg_for("elastic", nranks=4, local_batch=2)
    assert cfg_el.Global.global_batch_size == GBS
    el_losses = []
    faults.configure(host_loss_step="3")
    try:
        t = run_elastic(
            cfg_el, recording_trainer(cfg_el, el_losses), data,
            build_trainer=lambda c: recording_trainer(c, el_losses),
            make_loader=lambda c, consumed: data[consumed // GBS:])
        injected = dict(faults.injected)
    finally:
        faults.reset()

    assert injected["host_loss"] == 1, injected
    assert t.mesh_cfg.dp == 2, f"mesh did not shrink: dp{t.mesh_cfg.dp}"
    assert int(t.state.step) == STEPS, int(t.state.step)
    # exactly-once accounting: 6 batches x 8 samples, no re-feed/skip
    assert t.consumed_samples == STEPS * GBS, t.consumed_samples
    assert t.sentry_skips == 0
    assert len(el_losses) == STEPS, el_losses
    assert t._restored_step == 3, t._restored_step
    # post-shrink trajectory parity vs the uninterrupted dp2 run (tight
    # fp32 atol: same batches, same order, same global batch)
    np.testing.assert_allclose(el_losses[3:], ref_losses[3:], atol=2e-5,
                               rtol=0)
    # pre-shrink dp4 steps see the same batches too (reduction order is
    # the only difference)
    np.testing.assert_allclose(el_losses[:3], ref_losses[:3], atol=2e-5,
                               rtol=0)
    ev = get_event_log()
    assert ev.find("fault_injected", fault="host_loss")
    assert ev.find("elastic_shrink")
    assert ev.find("elastic_reshard")
    assert ev.find("checkpoint_saved", step=3)
    return ("host lost at step 3: snapshot -> dp4->dp2 reshard-on-load -> "
            "loss trajectory matches uninterrupted dp2 (6/6 batches "
            "consumed exactly once)")


SCENARIOS = {
    "sentry": scenario_sentry,
    "sentry_zero": scenario_sentry_zero,
    "ckpt": scenario_ckpt,
    "serving": scenario_serving,
    "serving_recovery": scenario_serving_recovery,
    "serving_poison": scenario_serving_poison,
    "serving_hang": scenario_serving_hang,
    "serving_drain": scenario_serving_drain,
    "serving_spec": scenario_serving_spec,
    "serving_mesh": scenario_serving_mesh,
    "serving_spill": scenario_serving_spill,
    "router_kill": scenario_router_kill,
    "router_saturation": scenario_router_saturation,
    "serving_disagg": scenario_serving_disagg,
    "serving_http": scenario_serving_http,
    "serving_hetero": scenario_serving_hetero,
    "serving_qos": scenario_serving_qos,
    "train_elastic": scenario_train_elastic,
}


def main(argv=None) -> int:
    """Run the selected chaos scenarios; 0 iff all pass."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(SCENARIOS))
    ap.add_argument("--workdir", default=None,
                    help="scratch dir (default: a fresh tempdir)")
    args = ap.parse_args(argv)
    names = (args.only.split(",") if args.only else list(SCENARIOS))
    tmp = args.workdir or tempfile.mkdtemp(prefix="chaos_check_")
    failures = 0
    for name in names:
        fn = SCENARIOS.get(name.strip())
        if fn is None:
            print(f"FAIL {name}: unknown scenario")
            failures += 1
            continue
        try:
            # each scenario asserts on the structured event log — start it
            # empty so a previous scenario's events can't satisfy (or
            # pollute) this one's expectations
            from fleetx_tpu.obs import get_event_log

            get_event_log().clear()
            detail = fn(os.path.join(tmp, name.strip()))
            print(f"PASS {name}: {detail}")
        except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
            import traceback

            traceback.print_exc()
            print(f"FAIL {name}: {type(e).__name__}: {e}")
            failures += 1
    print(f"chaos_check: {len(names) - failures}/{len(names)} scenarios passed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
