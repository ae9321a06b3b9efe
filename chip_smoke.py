"""chip_smoke.py: does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls, at
the full width of GPT-345M (hidden 1024, 24 layers, 16 heads of 64, vocab
50304) with weights made from a seed, and checks what comes out:

- trainer leg: the ``tools/train.py`` path (``get_config`` ->
  ``build_module`` -> ``build_dataloader`` -> ``Trainer`` ->
  ``run_elastic``/``fit``) on ``pretrain_gpt_345M_single_card.yaml`` with
  the bench anchor's settings (batch 8 x 1024, core_attn recompute, flash
  attention, dropout 0.1, bf16) on a token file generated here from a seed.
  Every step's loss must be finite and near ln(50304) = 10.83, and the
  compiled train step must hold the Mosaic custom calls of the flash
  forward, dq and dk/dv kernels.
- serving leg: a ``ServingEngine`` at the serving defaults the cells
  share (bf16, flash decode, 8 lanes, paged cache, page 16, prefill
  bucket 32) answering 8 requests with prompts of 32-192 tokens and 16-160
  new tokens. Every request must return exactly its token budget with
  ``finish_reason == "max_length"``, with no recovery, poison retirement or
  fault event, the compiled decode tick must hold the paged decode
  kernel's Mosaic call, and neither the tick nor a prefill program may hold
  a copy of the page pool among its temporaries. Then the paged decode
  kernel alone at the chat cell's call, ten of 24 lanes without a token:
  their output blocks, prefilled with NaN, must come back exact zeros, and
  the busy lanes equal a call without the empty ones bit for bit.
- on a host with four chips, the same two paths again over the mesh: the
  trainer at dp2 x mp2 (parameter shards on all four chips, first-step
  loss against the one-chip run on the same batch with dropout off) and
  the engine at mp2 (same requests, zero recoveries; the share of tokens
  equal to the one-chip run is reported, not asserted). On one chip these
  legs are skipped, and the output says so.

A chip belongs to one process at a time, so this parent process never
touches jax: each leg runs as a child (``--leg``), one after the other, and
the first thing a child does is refuse to run unless
``jax.devices()[0].platform == "tpu"``. Any assertion, exception or
time-out in a leg makes the whole run exit non-zero with no result line.
On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Everything written (token file, result files) goes to ``.chip_smoke/``;
compiled programs go to the persistent compile cache
(fleetx_tpu/utils/compile_cache.py), which a second run hits.
"""

import argparse
import contextlib
import json
import math
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")
CONFIG = os.path.join(REPO, "configs", "nlp", "gpt",
                      "pretrain_gpt_345M_single_card.yaml")
VOCAB = 50304
SEQ = 1024
GLOBAL_BATCH = 8
# random weights on random tokens: ln(50304) = 10.83 (an earlier round's
# first on-chip run read 11.02)
LOSS_BAND = (10.0, 12.0)
# the whole run must fit the driver's 1200 s, compilation included
LEG_TIMEOUT_S = 500

# serving leg: (prompt_len, new_tokens) spanning 32-192 and 16-160
REQUESTS = ((32, 160), (48, 16), (64, 96), (96, 32),
            (128, 128), (160, 48), (176, 64), (192, 144))
SLOTS, PAGE_SIZE, PREFILL_BUCKET = 8, 16, 32


# ---------------------------------------------------------------- children

def _own_the_chip() -> dict:
    """First jax call of a leg: say what we run on, refuse anything but a
    TPU. No platform override lives in this file."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}",
          flush=True)
    if device["platform"] != "tpu":
        sys.exit("chip_smoke: no TPU (jax.devices()[0].platform is "
                 f"{device['platform']!r}); refusing to run")
    return device


class _CompileClock:
    """Seconds jax spent in backend compiles, and persistent-cache hits and
    misses, from jax's own monitoring events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self) -> dict:
        return {"compile_s": round(self.compile_s, 1),
                "cache_hits": self.hits, "cache_misses": self.misses}


def _mosaic_calls(hlo_text: str, kernel_name: str) -> int:
    """How many Mosaic custom calls of the named Pallas kernel the
    optimized HLO holds (the kernel's ``name`` is in the instruction name;
    tests/test_flash_tp.py checks the same target string)."""
    return sum(1 for line in hlo_text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and kernel_name in line)


def _token_file() -> str:
    """The ``{prefix}_ids.npy`` + ``{prefix}_idx.npz`` pair GPTDataset
    reads (256 random documents of 1500-2500 tokens, concatenated) at the real
    vocabulary, from a seed."""
    import numpy as np

    prefix = os.path.join(WORK, "data", "smoke")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    rng = np.random.RandomState(0)
    docs = [rng.randint(0, VOCAB, size=rng.randint(1500, 2500))
            .astype(np.int32) for _ in range(256)]
    np.save(prefix + "_ids.npy", np.concatenate(docs))
    np.savez(prefix + "_idx.npz",
             lens=np.asarray([len(d) for d in docs], np.int32))
    return prefix


def leg_train(dp: int, mp: int, dropout: float, steps: int) -> dict:
    """The tools/train.py path for ``steps`` steps on a dp x mp mesh."""
    from fleetx_tpu.utils.xla_flags import (apply_overlap_flags,
                                            overlap_flags_state)

    apply_overlap_flags()  # environment only; must precede the backend
    device = _own_the_chip()

    import jax

    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.data import build_dataloader
    from fleetx_tpu.models import build_module
    from fleetx_tpu.ops.pallas.flash_attention import KERNEL_NAMES
    from fleetx_tpu.parallel.env import init_dist_env
    from fleetx_tpu.resilience.elastic import run_elastic
    from fleetx_tpu.utils.compile_cache import enable_compile_cache
    from fleetx_tpu.utils.config import get_config

    init_dist_env()
    cache_dir = enable_compile_cache()
    clock = _CompileClock()
    local = GLOBAL_BATCH // dp
    cfg = get_config(CONFIG, nranks=dp * mp, overrides=[
        f"Distributed.dp_degree={dp}", f"Distributed.mp_degree={mp}",
        f"Global.local_batch_size={local}", f"Global.micro_batch_size={local}",
        "Model.use_recompute=True", "Model.recompute_granularity=core_attn",
        "Model.use_flash_attention=True",
        f"Model.hidden_dropout_prob={dropout}",
        f"Model.attention_probs_dropout_prob={dropout}",
        f"Engine.max_steps={steps}", "Engine.logging_freq=1",
        "Engine.eval_freq=0", "Engine.save_load.save_steps=1000000000",
        f"Engine.save_load.output_dir={os.path.join(WORK, 'out')}",
        f"Data.Train.dataset.input_dir={_token_file()}",
        f"Data.Train.dataset.max_seq_len={SEQ}",
    ])
    assert cfg.Global.global_batch_size == GLOBAL_BATCH, cfg.Global
    module = build_module(cfg)
    logs = []
    log_line = module.training_step_end

    def record(log):  # the Trainer's per-step callback: keep every step
        logs.append({"loss": float(log["loss"]),
                     "seconds": float(log["batch_cost"])})
        log_line(log)

    module.training_step_end = record
    loader = build_dataloader(cfg, "Train")
    trainer = Trainer(cfg, module)
    t0 = time.perf_counter()
    trainer = run_elastic(cfg, trainer, loader, None)
    wall_s = time.perf_counter() - t0

    assert int(trainer.state.step) == steps, int(trainer.state.step)
    losses = [rec["loss"] for rec in logs]
    assert len(losses) == steps, (len(losses), steps)
    for i, loss in enumerate(losses):
        assert (math.isfinite(loss)
                and LOSS_BAND[0] < loss < LOSS_BAND[1]), (
            f"step {i + 1} loss {loss} is outside {LOSS_BAND} "
            f"(ln({VOCAB}) = {math.log(VOCAB):.2f})")
    hlo = trainer.compiled_text("train")
    kernels = {name: _mosaic_calls(hlo, name) for name in KERNEL_NAMES}
    assert all(kernels.values()), (
        f"flash attention gave way to the XLA path: Mosaic calls {kernels}")
    # which devices hold a shard of the parameters
    holders = sorted({shard.device.id
                      for leaf in jax.tree.leaves(trainer.state.params)
                      for shard in leaf.addressable_shards})
    assert len(holders) == dp * mp, (holders, dp, mp)
    steady = sorted(rec["seconds"] for rec in logs[2:])
    return {
        "device": device, "mesh": {"dp": dp, "mp": mp},
        "dropout": dropout, "losses": losses,
        "first_step_s": round(logs[0]["seconds"], 2),
        "steady_step_s": (round(steady[len(steady) // 2], 3)
                          if steady else None),
        "fit_wall_s": round(wall_s, 1),
        "mosaic_calls": kernels, "param_shard_devices": holders,
        "zero_update": bool(trainer._zero_update),
        "overlap_flags": overlap_flags_state(),
        "compile_cache_dir": cache_dir, **clock.report(),
    }


def leg_serve(mp: int) -> dict:
    """A ServingEngine at GPT-345M width (bf16, flash decode, paged cache)
    answering REQUESTS; over an mp mesh when ``mp`` > 1."""
    device = _own_the_chip()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
    from fleetx_tpu.obs import get_event_log
    from fleetx_tpu.ops.pallas.decode_attention import PAGED_KERNEL_NAME
    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh
    from fleetx_tpu.serving import ServingEngine
    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = _CompileClock()
    max_prompt = max(p for p, _ in REQUESTS)
    max_new = max(g for _, g in REQUESTS)
    model = GPTForPretraining(GPTConfig(
        vocab_size=VOCAB, hidden_size=1024, num_layers=24,
        num_attention_heads=16, ffn_hidden_size=4096,
        max_position_embeddings=max_prompt + max_new,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        fuse_attn_qkv=True, use_flash_attention=True, dtype=jnp.bfloat16))
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0), np.zeros((1, max_prompt), np.int32))
    gen_cfg = GenerationConfig(decode_strategy="greedy", eos_token_id=-1,
                               pad_token_id=0, max_length=max_new)
    mesh = (build_mesh(MeshConfig(mp=mp), jax.devices()[:mp]) if mp > 1
            else None)
    engine = ServingEngine(
        model, variables, slots=SLOTS,
        cache_len=model.cfg.max_position_embeddings, gen_cfg=gen_cfg,
        page_size=PAGE_SIZE, prefill_bucket=PREFILL_BUCKET, mesh=mesh)
    assert engine.page_size == PAGE_SIZE

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, VOCAB, p).astype(np.int32)
               for p, _ in REQUESTS]
    t0 = time.perf_counter()
    ids = [engine.submit(prompt, max_length=new)
           for prompt, (_, new) in zip(prompts, REQUESTS)]
    results = engine.drain()
    wall_s = time.perf_counter() - t0

    tokens = []
    for rid, (_, new) in zip(ids, REQUESTS):
        res = results[rid]
        assert res.finish_reason == "max_length", (rid, res.finish_reason)
        assert len(res.tokens) == new, (rid, len(res.tokens), new)
        toks = np.asarray(res.tokens)
        assert ((0 <= toks) & (toks < VOCAB)).all(), rid
        tokens.append([int(t) for t in toks])
    snap = engine.metrics.snapshot()
    assert snap["engine_recoveries"] == 0, snap["engine_recoveries"]
    assert snap["poison_retired"] == 0, snap["poison_retired"]
    events = get_event_log().counts()
    for kind in ("fault_injected", "engine_recovery", "tick_fault",
                 "poison_retired"):
        assert not events.get(kind), (kind, events)
    tick = engine.compiled_decode()
    calls = _mosaic_calls(tick.as_text(), PAGED_KERNEL_NAME)
    assert calls, ("the decode tick holds no Mosaic call of "
                   f"{PAGED_KERNEL_NAME}: flash decode gave way")
    temporaries = _pool_stays_in_place(engine, tick)
    # (one chip: the kernel takes a mesh through the engine alone)
    empty_lanes = empty_lanes_are_zeros() if mp == 1 else None
    return {
        "device": device, "mesh": {"mp": mp}, "requests": len(REQUESTS),
        "tokens_generated": int(snap["tokens_generated"]),
        "ticks": int(snap["ticks"]), "drain_wall_s": round(wall_s, 1),
        "mosaic_calls": {PAGED_KERNEL_NAME: calls},
        "temporaries": temporaries, "empty_lanes": empty_lanes,
        "kv_cache_bytes_per_device": int(snap["kv_cache_bytes"]),
        "tokens": tokens, "compile_cache_dir": cache_dir, **clock.report(),
    }


@contextlib.contextmanager
def nan_prefilled_outputs():
    """Inside, every ``pl.pallas_call`` of a decode kernel (grid ``(lanes,
    blocks)``, one output block a lane) first fills a lane's output block
    with NaN, at the lane's step 0 and before the kernel's own code. A
    block the kernel never writes then comes back NaN, on the chip as in
    the interpreter, where it would else come back as whatever the buffer
    held: zeros by luck, or a lane's result of two steps before.
    ``tests/test_decode_attention.py`` holds the empty lanes to exact
    zeros under it as :func:`empty_lanes_are_zeros` does here."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    real = pl.pallas_call

    def prefilling(kernel, *args, grid_spec, **kwargs):
        out_at = grid_spec.num_scalar_prefetch + len(grid_spec.in_specs)

        def prefilled(*refs):
            o_ref = refs[out_at]

            @pl.when(pl.program_id(1) == 0)
            def _prefill():
                o_ref[...] = jnp.full(o_ref.shape, jnp.nan, o_ref.dtype)

            kernel(*refs)

        return real(prefilled, *args, grid_spec=grid_spec, **kwargs)

    pl.pallas_call = prefilling
    try:
        yield
    finally:
        pl.pallas_call = real


def empty_lanes_are_zeros() -> dict:
    """The paged decode kernel at the chat cell's call (24 lanes of 64
    pages of 16 rows, 16 heads of 128), ten lanes of it without a token
    (``end`` 0: first, last, and two and three in a row), at several pages
    a step, at one (``block_k`` = the page) and over an int8 pool: under
    :func:`nan_prefilled_outputs` the empty lanes' blocks must be exact
    zeros, and the busy lanes equal bit for bit a call that holds them
    alone (the chain of copies passes over the empty ones)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fleetx_tpu.ops.pallas.decode_attention import (
        flash_decode_paged_attention,
    )
    from fleetx_tpu.ops.quant import quantize_kv

    lanes, n_row, ps, h, d = 24, 64, 16, 16, 128
    rng = np.random.RandomState(0)
    empty = np.asarray([0, 3, 4, 8, 12, 13, 14, 19, 22, 23])
    ends = rng.randint(1, n_row * ps + 1, lanes).astype(np.int32)
    ends[empty] = 0
    tables = np.zeros((lanes, n_row), np.int32)
    busy = np.flatnonzero(ends)
    tables[busy] = 1 + np.arange(len(busy) * n_row).reshape(-1, n_row)
    pool = (len(busy) * n_row + 1, ps, h * d)
    q = jnp.asarray(rng.randn(lanes, 1, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(*pool), jnp.bfloat16)
    v = jnp.asarray(rng.randn(*pool), jnp.bfloat16)
    k8, ks = quantize_kv(k.reshape(pool[:2] + (h, d)))
    v8, vs = quantize_kv(v.reshape(pool[:2] + (h, d)))
    int8 = dict(k_scale=ks[..., 0], v_scale=vs[..., 0])
    checked = []
    for name, k_pool, v_pool, kw in (
            ("pages_16", k, v, {}), ("pages_1", k, v, dict(block_k=ps)),
            ("int8", k8.reshape(pool), v8.reshape(pool), int8)):
        def call(which, k_pool=k_pool, v_pool=v_pool, kw=kw):
            with nan_prefilled_outputs():
                return np.asarray(jax.jit(
                    lambda q, t, e: flash_decode_paged_attention(
                        q, k_pool, v_pool, tables=t, end=e, **kw))(
                    q[which], jnp.asarray(tables[which]),
                    jnp.asarray(ends[which])).astype(jnp.float32))

        whole = call(np.arange(lanes))
        assert not whole[empty].any(), (   # (a NaN is truthy too)
            name, "an empty lane's output block is not zeros",
            whole[empty].reshape(len(empty), -1)[:, :4])
        assert np.isfinite(whole[busy]).all() and whole[busy].any(), name
        assert np.array_equal(whole[busy], call(busy)), (
            name, "a busy lane differs from the call without empty lanes")
        checked.append(name)
    return {"empty_lanes": len(empty), "checked": checked}


def _pool_stays_in_place(engine, tick) -> dict:
    """The decode tick and the largest prefill bucket the requests
    compiled must update the page pool in place: beyond the bf16 copy of
    the float32 weights that every serving program makes, their
    temporaries must stay under half a copy of the pool. (With the pool a
    scanned input and stacked output of the layer loop both held a whole
    copy of it, and moved it three times: PERF.md, PR 24. At this size the
    pool is smaller than the weights' copy, so the temporaries alone
    cannot be held against it.) Bytes are per device."""
    import jax

    def device_bytes(tree, itemsize=None):
        return sum(
            x.addressable_shards[0].data.size * (itemsize or x.dtype.itemsize)
            for x in jax.tree.leaves(tree))

    cache = engine.cache_manager.cache
    pool = device_bytes(cache)
    weights_bf16 = device_bytes(engine.params, itemsize=2)
    bucket = max(engine._prefill_jits)
    with engine._mesh_context():
        prefill = engine._prefill_jits[bucket].lower(
            engine.params, cache,
            engine._prefill_ints(
                (), bucket, 0, engine.cache_manager.lane_tables(0)),
            engine._inert_floats, jax.random.PRNGKey(0)).compile()
    out = {"pool_bytes": pool, "weights_bf16_bytes": weights_bf16}
    for name, program in (("tick", tick), (f"prefill_{bucket}", prefill)):
        temp = int(program.memory_analysis().temp_size_in_bytes)
        assert temp < weights_bf16 + pool // 2, (
            f"{name} holds {temp} bytes of temporaries beside "
            f"{weights_bf16} of bf16 weights: a copy of the {pool}-byte "
            "page pool is back")
        out[f"{name}_temp_bytes"] = temp
    return out


LEGS = {
    # name: (function, kwargs); the first two are the one-chip main path
    "train": (leg_train, dict(dp=1, mp=1, dropout=0.1, steps=6)),
    "serve": (leg_serve, dict(mp=1)),
    "train_ref": (leg_train, dict(dp=1, mp=1, dropout=0.0, steps=1)),
    "train_dp2mp2": (leg_train, dict(dp=2, mp=2, dropout=0.0, steps=3)),
    "serve_mp2": (leg_serve, dict(mp=2)),
}


def run_leg(name: str) -> None:
    """Child entry: run one leg, leave its result in WORK/<name>.json."""
    fn, kwargs = LEGS[name]
    result = fn(**kwargs)
    os.makedirs(WORK, exist_ok=True)
    with open(os.path.join(WORK, name + ".json"), "w") as f:
        json.dump(result, f)


# ------------------------------------------------------------------ parent

def _child(name: str) -> dict:
    """Run leg ``name`` as a child that owns the chip; its result, or exit
    non-zero. ``subprocess.run`` kills the child at the time-out."""
    path = os.path.join(WORK, name + ".json")
    if os.path.exists(path):
        os.remove(path)
    print(f"chip_smoke: --- leg {name} ---", flush=True)
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--leg", name],
            cwd=REPO, timeout=LEG_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        sys.exit(f"chip_smoke: leg {name} exceeded {LEG_TIMEOUT_S}s")
    if proc.returncode != 0:
        sys.exit(f"chip_smoke: leg {name} failed (exit {proc.returncode})")
    with open(path) as f:
        result = json.load(f)
    result["leg_wall_s"] = round(time.perf_counter() - t0, 1)
    shown = {k: v for k, v in result.items() if k != "tokens"}
    print(f"chip_smoke: leg {name} ok: {json.dumps(shown)}", flush=True)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--leg", choices=sorted(LEGS), help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.leg:
        run_leg(args.leg)
        return 0

    os.makedirs(WORK, exist_ok=True)
    train = _child("train")
    serve = _child("serve")
    device = train["device"]
    assert serve["device"] == device, (serve["device"], device)
    if device["count"] >= 4:
        ref = _child("train_ref")
        mesh_train = _child("train_dp2mp2")
        one, four = ref["losses"][0], mesh_train["losses"][0]
        rel = abs(four - one) / abs(one)
        print(f"chip_smoke: first-step loss dp2xmp2 {four:.6f} vs one chip "
              f"{one:.6f}: relative difference {rel:.2e} (bound 2e-4)",
              flush=True)
        assert rel <= 2e-4, (one, four, rel)
        assert len(mesh_train["param_shard_devices"]) == 4, mesh_train
        mesh_serve = _child("serve_mp2")
        pairs = [(a, b) for x, y in zip(serve["tokens"],
                                        mesh_serve["tokens"])
                 for a, b in zip(x, y)]
        same = sum(a == b for a, b in pairs)
        print(f"chip_smoke: mp2 engine tokens equal to the one-chip "
              f"engine's: {same}/{len(pairs)} "
              f"({same / len(pairs):.4f}; reported, not asserted)",
              flush=True)
    else:
        print(f"chip_smoke: {device['count']} device(s): the four-chip legs "
              "(trainer dp2xmp2, engine mp2) are SKIPPED", flush=True)
    print(f"chip_smoke: cold-or-cached compile seconds: train "
          f"{train['compile_s']} ({train['cache_hits']} cache hits, "
          f"{train['cache_misses']} misses), serve {serve['compile_s']} "
          f"({serve['cache_hits']} hits, {serve['cache_misses']} misses); "
          f"leg wall seconds: train {train['leg_wall_s']}, serve "
          f"{serve['leg_wall_s']}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
