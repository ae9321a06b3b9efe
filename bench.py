"""Benchmark: GPT-345M pretraining throughput on the available chip(s).

Prints ONE JSON line (the driver records it verbatim):
  {"metric": ..., "value": N, "unit": "tokens/s", "vs_baseline": N/16260}
The anchor record is the batch-8 pretrain config (comparable across rounds
and to the A100 baseline); `detail` carries `mfu` / `tflops_per_chip` (the
BASELINE.json north-star metric is MFU) plus, unless BENCH_EXTRA=0,
`detail.extra_records`: a best-MFU training config and decode (serving)
throughput per mode — greedy/beam x batch 1/8 (VERDICT r3 items 2 & 10) —
all folded into the single line so the driver's one-record parse contract
holds.

Baseline: the reference's GPT-345M single-card number — ~16,260 tokens/s on
one A100-40G (BASELINE.md row 2, projects/gpt/docs/single_card.md:41-49).
"""

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

BASELINE_TOKENS_PER_SEC = 16260.0  # A100-40G, reference single_card.md


def model_flops_per_token(n_params: int, num_layers: int, seq: int, hidden: int) -> float:
    """MODEL-FLOPs accounting: what the math requires, not what the chip
    executes — rematerialised forward passes are excluded, so MFU here is
    comparable across remat settings and across rounds. 6 FLOPs per
    parameter per token (fwd 2 + bwd 4, tied-embedding logits included via
    the shared weight) + causal attention score/value matmuls (fwd 4*s*h
    per layer per token, halved for causality, x3 for fwd+bwd)."""
    return 6.0 * n_params + num_layers * 6.0 * seq * hidden


# process-lifetime high-water mark already attributed to an earlier record
_PEAK_SEEN = [0]


def _overlap_detail(trainer) -> dict:
    """The overlap-lever state of one training record: ZeRO update
    sharding on/off (+ resident opt-state bytes), the XLA overlap flag
    set, and the virtual-pp schedule — None unless this config actually
    ran a virtual pipeline (docs/PERFORMANCE.md)."""
    from fleetx_tpu.parallel.pipeline import stream_chunks_default
    from fleetx_tpu.utils.xla_flags import overlap_flags_state

    model_cfg = trainer.cfg.get("Model") or {}
    v = model_cfg.get("virtual_pp_degree") or 1
    if trainer.mesh_cfg.pp <= 1 or v <= 1:
        schedule = None
    else:
        stream = model_cfg.get("virtual_pp_stream")
        stream = stream_chunks_default() if stream is None else bool(stream)
        schedule = "streamed" if stream else "sequential"
    return {
        "zero_update": bool(trainer._zero_update),
        "opt_state_bytes_per_device": trainer.opt_state_device_bytes(),
        "xla_flags": overlap_flags_state(),
        "virtual_pp_schedule": schedule,
    }


def train_record(batch: int, *, seq: int, steps: int, warmup: int,
                 recompute: bool, granularity: str) -> dict:
    """Build the 345M trainer at ``batch`` and time ``steps`` train steps."""
    import jax

    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import AttrDict, process_configs
    from fleetx_tpu.utils.hw import UnknownDeviceKind, peak_flops_per_chip
    import fleetx_tpu.parallel.env as dist_env

    cfg = AttrDict(
        Global=AttrDict(seed=0, local_batch_size=batch, micro_batch_size=batch),
        Engine=AttrDict(
            max_steps=steps,
            logging_freq=10**9,
            mix_precision=AttrDict(use_pure_fp16=True),
            save_load=AttrDict(save_steps=10**9, output_dir="/tmp/fleetx_bench"),
        ),
        Model=AttrDict(
            module="GPTModule",
            # model dims are env-overridable ONLY so harnesses (e.g.
            # bench_matrix --train-tuning smoke on CPU) can shrink the
            # model; the anchor record always runs the 345M defaults
            vocab_size=int(os.environ.get("BENCH_VOCAB", 50304)),
            hidden_size=int(os.environ.get("BENCH_HIDDEN", 1024)),
            num_layers=int(os.environ.get("BENCH_LAYERS", 24)),
            num_attention_heads=int(os.environ.get("BENCH_HEADS", 16)),
            ffn_hidden_size=int(os.environ.get("BENCH_FFN", 4096)),
            max_position_embeddings=seq,
            # overridable for perf triage (e.g. quantifying the in-kernel
            # attention-dropout cost); the anchor keeps the reference's 0.1
            hidden_dropout_prob=float(
                os.environ.get("BENCH_HIDDEN_DROPOUT", 0.1)),
            attention_probs_dropout_prob=float(
                os.environ.get("BENCH_ATTN_DROPOUT", 0.1)),
            fuse_attn_qkv=True,
            use_flash_attention=os.environ.get("BENCH_FLASH", "1") == "1",
            use_recompute=recompute,
            recompute_granularity=granularity,
            # e.g. BENCH_EXTRA_SAVES=qkv_out,ffn_gelu : spend HBM on saved
            # activations to cut backward recompute (docs/PERFORMANCE.md)
            recompute_extra_saves=os.environ.get("BENCH_EXTRA_SAVES"),
            # BENCH_SCAN=0 unrolls the layer stack: slower compile, but no
            # scan-carry dynamic-update-slice traffic (~9%/step in the r4
            # profile at 345M)
            scan_layers=os.environ.get("BENCH_SCAN", "1") == "1",
            # BENCH_FUSED_CE=1: blockwise fused LM-head + cross-entropy
            # (ops/pallas/ce_loss.py) — the [tokens, 50304] f32 logits
            # never materialize (~1.6 GB at b8) at +2 recompute matmul
            # passes in backward
            fused_ce=os.environ.get("BENCH_FUSED_CE", "0") == "1",
        ),
        Optimizer=AttrDict(
            name="FusedAdamW",
            # BENCH_MOMENT_DTYPE=bfloat16 halves the Adam mu buffer —
            # headroom for remat save-sets (docs/PERFORMANCE.md)
            moment_dtype=os.environ.get("BENCH_MOMENT_DTYPE"),
            weight_decay=0.01,
            lr=AttrDict(name="CosineAnnealingWithWarmupDecay", decay_steps=360000,
                        max_lr=5e-5, min_lr=1e-5),
            grad_clip=AttrDict(name="ClipGradByGlobalNorm", clip_norm=1.0),
        ),
        Distributed=AttrDict(dp_degree=None, mp_degree=1, pp_degree=1),
    )
    n = jax.device_count()
    process_configs(cfg, nranks=n)

    module = build_module(cfg)
    trainer = Trainer(cfg, module)
    gbs = cfg.Global.global_batch_size
    vocab = cfg.Model.vocab_size
    rng = np.random.RandomState(0)
    host_batch = {
        "tokens": rng.randint(0, vocab, (gbs, seq)).astype(np.int32),
        "labels": rng.randint(0, vocab, (gbs, seq)).astype(np.int32),
        "loss_mask": np.ones((gbs, seq), np.float32),
    }
    trainer.init_state(host_batch)
    step_fn = trainer._get("train", trainer._build_train_step)
    db = trainer._shard_batch(host_batch)

    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(trainer.state.params)
    )

    state = trainer.state
    for i in range(warmup):
        state, metrics = step_fn(state, db, dist_env.data_rank_key(i))
    if warmup:  # host transfer = hard sync (BENCH_WARMUP=0 skips cleanly)
        float(jax.device_get(metrics["loss"]))

    t0 = time.perf_counter()
    for i in range(steps):
        state, metrics = step_fn(state, db, dist_env.data_rank_key(warmup + i))
    final_loss = float(jax.device_get(metrics["loss"]))  # hard sync
    dt = time.perf_counter() - t0

    tokens_per_sec = gbs * seq * steps / dt
    n_chips = jax.device_count()
    # peak HBM: how much headroom a remat save-set / batch bump has.
    # peak_bytes_in_use is PROCESS-lifetime-monotone, so a second in-process
    # record only reports a number when it actually set a new peak
    # (peak_before captured in the caller); None = unavailable or masked.
    try:
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak is None or peak <= _PEAK_SEEN[0]:
            peak_hbm_gb = None
        else:
            peak_hbm_gb = round(peak / 2**30, 2)
            _PEAK_SEEN[0] = peak
    except Exception:
        peak_hbm_gb = None
    flops_per_token = model_flops_per_token(
        n_params, cfg.Model.num_layers, seq, cfg.Model.hidden_size
    )
    achieved_flops = tokens_per_sec * flops_per_token
    try:
        mfu = round(achieved_flops
                    / (peak_flops_per_chip(jax.devices()[0]) * n_chips), 4)
    except UnknownDeviceKind:
        mfu = None  # no peak on record (every CPU run): not measured
    rec = {
        "metric": "gpt_345m_pretrain_throughput",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / BASELINE_TOKENS_PER_SEC, 4),
        "detail": {
            "chips": n_chips,
            "platform": jax.devices()[0].platform,
            "device": jax.devices()[0].device_kind,
            "global_batch": gbs,
            "seq_len": seq,
            "steps": steps,
            "step_time_s": round(dt / steps, 4),
            "loss": round(final_loss, 4),
            "mfu": mfu,
            "tflops_per_chip": round(achieved_flops / n_chips / 1e12, 2),
            "peak_hbm_gb": peak_hbm_gb,
            "model_flops_per_token": round(flops_per_token / 1e9, 3),
            "flops_accounting": "model-flops (remat forward excluded)",
            "recompute": f"{recompute}:{granularity}",
            "baseline": "A100-40G 16260 tokens/s (reference single_card.md)",
            # overlap attribution (ISSUE 12): which step-overlap levers
            # were live, so trajectory gains are attributable to them
            "overlap": _overlap_detail(trainer),
        },
    }
    # feed the obs layer this record's numbers (gauges are last-writer-
    # wins; the process-cumulative registry snapshot is embedded ONCE per
    # bench invocation, in main(), so no record carries another record's
    # blended histograms); xla_mfu is the cost_analysis-flops MFU the
    # live TRAIN line reports — remat recompute included, unlike the
    # model-flops `mfu` above, so the two bracket the true utilization
    trainer._obs_step_time.observe(dt / steps)
    trainer._obs_tokens_per_s.set(tokens_per_sec)
    trainer._obs_loss.set(final_loss)
    xla_mfu = trainer._step_mfu(dt / steps)
    if xla_mfu is not None:
        trainer._obs_mfu.set(xla_mfu)
        rec["detail"]["xla_mfu"] = round(xla_mfu, 4)
    # checkpoint-cadence pricing (ISSUE 20): the step-path stall of one
    # save under FLEETX_CKPT_ASYNC_SNAPSHOT is the D2H snapshot alone —
    # time it (no disk write) so the cadence-vs-MFU trade is priced on
    # every hardware window: stall fraction = snapshot_blocking_s /
    # (save_steps * step_time_s)
    try:
        from fleetx_tpu.core.engine import _unbox
        t_snap = time.perf_counter()
        host_state = jax.device_get(_unbox(state))
        snap_s = time.perf_counter() - t_snap
        state_bytes = sum(getattr(l, "nbytes", 0)
                         for l in jax.tree.leaves(host_state))
        del host_state
        rec["detail"]["ckpt"] = {
            "snapshot_blocking_s": round(snap_s, 4),
            "state_gb": round(state_bytes / 2**30, 3),
            "save_steps_for_1pct_stall": round(snap_s / (dt / steps) * 100, 1),
            "note": "blocking stall per save cadence under "
                    "FLEETX_CKPT_ASYNC_SNAPSHOT (D2H copy only; upload "
                    "is off the step path)",
        }
    except Exception:
        pass
    # release the model/opt state before the next in-process bench run
    del state, trainer, module, db
    gc.collect()
    return rec


def _child_bench_records(tool: str, timeout_s: int):
    """A bench tool in a CHILD process with a hard timeout, run BEFORE the
    parent touches the TPU (the chip is exclusive: two live processes can't
    both hold it, and an in-process compile hang would sink the anchor
    record — the driver contract is one JSON line, printed at the end).
    Serves both serving-side benches: tools/bench_decode.py (one-shot
    decode throughput) and tools/bench_serving.py (static-vs-continuous
    batching). A child that times out, exits non-zero or prints no record
    fails the whole run: partial records must not read as a bench."""
    import subprocess

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", tool)
    try:
        proc = subprocess.run([sys.executable, path], capture_output=True,
                              text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        sys.exit(f"bench: {tool} exceeded {timeout_s}s")
    if proc.returncode != 0:
        sys.exit(f"bench: {tool} exited {proc.returncode}: "
                 f"{proc.stderr[-2000:]}")
    recs = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    if not recs:
        sys.exit(f"bench: {tool} printed no record")
    return recs


def main():
    # the overlap flags must be in the environment before ANY backend init
    # — here, before the bench children (which inherit it) and the
    # parent's own first device touch. The Trainer-ctor call would be too
    # late (and refuses to append post-init, keeping the detail.overlap
    # report honest).
    from fleetx_tpu.utils.compile_cache import enable_compile_cache
    from fleetx_tpu.utils.xla_flags import apply_overlap_flags

    apply_overlap_flags()
    extras = []
    if os.environ.get("BENCH_EXTRA", "1") != "0":
        # children first: each must own the chip before the parent does
        extras.extend(_child_bench_records(
            "bench_decode.py",
            int(os.environ.get("BENCH_DECODE_TIMEOUT", 900))))
        extras.extend(_child_bench_records(
            "bench_serving.py",
            int(os.environ.get("BENCH_SERVING_TIMEOUT", 900))))
    enable_compile_cache()

    seq = int(os.environ.get("BENCH_SEQ", 1024))
    batch = int(os.environ.get("BENCH_BATCH", 8))
    # 20 timed steps: the r4 session saw ~±5% run-to-run spread at 10
    # (17.4k vs 18.1k tok/s on back-to-back identical configs); doubling
    # the window costs ~5s against multi-minute compiles
    steps = int(os.environ.get("BENCH_STEPS", 20))
    warmup = int(os.environ.get("BENCH_WARMUP", 5))
    # The reference's own large-model configs pick selective recompute
    # (pretrain_gpt_175B_mp8_pp16.yaml recompute_granularity=core_attn);
    # "full" remat costs an extra forward pass per step. no-remat at 345M
    # OOMed v5e's 16GiB HBM in an earlier round (not reproduced), so
    # core_attn stays the anchor.
    recompute = os.environ.get("BENCH_RECOMPUTE", "1") == "1"
    granularity = os.environ.get("BENCH_GRANULARITY", "core_attn")

    anchor = train_record(batch, seq=seq, steps=steps, warmup=warmup,
                          recompute=recompute, granularity=granularity)

    if os.environ.get("BENCH_EXTRA", "1") != "0":
        second = int(os.environ.get("BENCH_SECOND_BATCH", 16))
        if second != batch:
            # a failure here (e.g. OOM at 2x batch) fails the run
            best = train_record(second, seq=seq, steps=steps,
                                warmup=warmup, recompute=recompute,
                                granularity=granularity)
            best["metric"] += f"_b{second}"
            best["vs_baseline"] = None  # the b8 anchor has the baseline
            extras.append(best)
    if extras:
        anchor["detail"]["extra_records"] = extras
    # full metric context for the perf trajectory (docs/OBSERVABILITY.md):
    # the registry/event snapshot is PROCESS-CUMULATIVE over everything
    # this bench invocation ran (anchor + in-process extras), embedded
    # once here rather than per record so no record misattributes another
    # record's histogram samples as its own
    from fleetx_tpu.obs import get_event_log, get_registry

    anchor["detail"]["obs"] = {
        "scope": "process-cumulative (anchor + in-process extra records)",
        "metrics": get_registry().snapshot(),
        "events": get_event_log().counts(),
    }
    print(json.dumps(anchor))


if __name__ == "__main__":
    main()
