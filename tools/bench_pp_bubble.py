"""Pipeline-bubble measurement: step time vs num_microbatches (VERDICT r4
item #8), plus the virtual-pipeline schedule sweep (ISSUE 12).

The SPMD pipe (fleetx_tpu/parallel/pipeline.py) answers the reference's
interleaved-1F1B runtime schedule (/root/reference/ppfleetx/models/
language_model/gpt/dygraph/hybrid_model.py:1095) with "raise
num_microbatches" — the scan streams M microbatches through pp stages in
M + pp - 1 ticks, so the drain-tick fraction is (pp-1)/(M+pp-1) and
shrinks with M at constant global batch. This harness measures that
claim: jitted fwd+bwd wall time per GLOBAL batch at fixed global batch
size, sweeping M, on the virtual CPU mesh (relative shape is what
matters; absolute CPU times are not TPU times).

Two bubble numbers per record:

- ``model_bubble_fraction`` — the schedule's *predicted* dead-tick
  fraction: (rows-1)/(M+rows-1) per scan with ``rows`` pipe rows,
  summed over chained scans for the sequential-chunk schedule.
- ``measured_bubble_fraction`` — 1 - t_plain/t_pipe against the SAME
  model/batch through the plain (no-pp) scan stack: every cost the
  pipeline adds over ideal (dead ticks, per-tick collective permutes,
  scan-loop overhead), clamped at 0.

``--virtual-pp`` sweeps the two virtual-chunk schedules at equal
(pp, v, M): *streamed* (one fused scan over v*pp rows, M + v*pp - 1
ticks) vs *sequential* (v chained scans, v*(M + pp - 1) ticks). The
streamed schedule trades ~v x fewer ticks for dead-row work in its
single longer fill/drain, so it wins exactly where per-tick overhead
dominates per-row compute — thin virtual stages, the regime virtual-pp
exists for; the sweep's default config sits in that regime on purpose
and ``--gate`` turns "streamed measured bubble < sequential's" into a
non-zero-exit regression gate. Results are banked machine-readably
(default ``--out benchmarks/pp_bubble.json``).

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/bench_pp_bubble.py --virtual-pp --gate
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

DEFAULT_OUT = os.path.join(REPO, "benchmarks", "pp_bubble.json")

# the non-virtual sweep keeps the historical r05 shape; the virtual-pp
# sweep uses a THIN-STAGE config (small hidden/seq, lpc=1..2) because the
# streamed-vs-sequential trade is about per-tick overhead vs per-row
# compute, and fat CPU matmuls would bury the schedule signal the sweep
# exists to measure
BASE = dict(
    vocab_size=256, hidden_size=256, num_layers=8,
    num_attention_heads=4, ffn_hidden_size=1024,
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    use_flash_attention=False,
)
VPP_BASE = dict(
    vocab_size=64, hidden_size=16, num_layers=8,
    num_attention_heads=2, ffn_hidden_size=32,
    hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
    use_flash_attention=False,
)


def _models():
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.model import (
        GPTConfig, GPTForPretraining, pretraining_loss,
    )
    return GPTConfig, GPTForPretraining, pretraining_loss, jnp


def _seq_params(base):
    """Init the sequential twin once; every schedule remaps from it."""
    import flax
    import jax

    GPTConfig, GPTForPretraining, _, jnp = _models()
    model = GPTForPretraining(GPTConfig(**base))
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    unboxed = jax.tree.map(
        lambda x: x.value if hasattr(x, "value") else x,
        flax.core.unfreeze(v["params"]),
        is_leaf=lambda x: hasattr(x, "value"),
    )
    return {"params": unboxed}


def _batch(base, global_batch, seq):
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    V = base["vocab_size"]
    return (
        jnp.asarray(rng.randint(0, V, (global_batch, seq)), jnp.int32),
        jnp.asarray(rng.randint(0, V, (global_batch, seq)), jnp.int32),
        jnp.ones((global_batch, seq), jnp.float32),
    )


def _time_grad(model, params, batch, mesh, repeats):
    """Median jitted fwd+bwd wall seconds (hard-synced)."""
    import flax.linen as nn
    import jax

    from fleetx_tpu.models.gpt.model import pretraining_loss
    from fleetx_tpu.parallel.mesh import use_mesh
    from fleetx_tpu.parallel.sharding import make_rules

    tokens, labels, mask = batch

    def loss_fn(p):
        return pretraining_loss(model.apply(p, tokens), labels, mask)

    ctx = (use_mesh(mesh) if mesh is not None else _nullctx())
    with ctx, nn.logical_axis_rules(list(make_rules())):
        step = jax.jit(jax.grad(loss_fn))
        g = step(params)  # compile + warm
        jax.block_until_ready(jax.tree.leaves(g))
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            g = step(params)
            jax.block_until_ready(jax.tree.leaves(g))
            times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _nullctx():
    import contextlib

    return contextlib.nullcontext()


def predicted_bubble(pp: int, v: int, M: int, schedule: str) -> float:
    """Dead-tick fraction of one schedule (module docstring): plain /
    sequential chain scans of ``rows`` pipe rows each, streamed fuses
    into one scan of v*pp rows."""
    if schedule == "streamed":
        rows = pp * v
        return (rows - 1) / (M + rows - 1)
    # plain (v==1) and sequential-chunk: every pass drains pp-1 ticks
    return (pp - 1) / (M + pp - 1)


def measure(pp, microbatches, global_batch=16, seq=128, repeats=3,
            base=None, virtual_pp=1, schedules=("plain",)):
    """Records for one (pp, virtual_pp) config across ``microbatches``,
    one per schedule, each with predicted + measured bubble fractions
    (measured against the no-pp scan stack on the same batch)."""
    import jax

    from fleetx_tpu.parallel.mesh import MeshConfig, build_mesh
    from fleetx_tpu.parallel.pipeline import sequential_params_to_pipeline

    GPTConfig, GPTForPretraining, _, jnp = _models()
    base = dict(base or BASE)
    base.setdefault("max_position_embeddings", seq)
    base["dtype"] = jnp.float32
    devs = jax.devices()
    dp = max(1, len(devs[:8]) // pp)
    mesh = build_mesh(MeshConfig(dp=dp, pp=pp), devs[: dp * pp])
    v_seq = _seq_params(base)
    batch = _batch(base, global_batch, seq)

    # the zero-pipeline ideal: same math through the plain scan stack
    plain_model = GPTForPretraining(GPTConfig(**base))
    t_plain = _time_grad(plain_model, v_seq, batch, None, repeats)

    records = []
    for m in microbatches:
        for schedule in schedules:
            stream = schedule == "streamed"
            vv = virtual_pp if schedule != "plain" else 1
            model = GPTForPretraining(GPTConfig(
                **{**base, "pp_degree": pp, "num_microbatches": m,
                   "virtual_pp_degree": vv,
                   "virtual_pp_stream": stream}))
            params = sequential_params_to_pipeline(
                v_seq, pp, vv, stream=stream)
            t = _time_grad(model, params, batch, mesh, repeats)
            records.append({
                "pp": pp, "virtual_pp": vv, "schedule": schedule,
                "num_microbatches": m, "global_batch": global_batch,
                "seq": seq, "hidden": base["hidden_size"],
                "num_layers": base["num_layers"],
                # 6 decimals: the streamed-vs-sequential verdict compares
                # these, and 4-decimal rounding could tie a sub-0.1ms win
                "step_s": round(t, 6),
                "plain_stack_s": round(t_plain, 6),
                "model_bubble_fraction": round(
                    predicted_bubble(pp, vv, m, schedule), 4),
                "measured_bubble_fraction": round(
                    max(0.0, 1.0 - t_plain / t), 4),
            })
            print(json.dumps(records[-1]), flush=True)
    return records


def virtual_pp_summary(records):
    """Streamed-vs-sequential comparison at equal (pp, v, M): the
    regression gate of the streamed schedule."""
    by_key = {}
    for r in records:
        if r["schedule"] in ("streamed", "sequential"):
            key = (r["pp"], r["virtual_pp"], r["num_microbatches"])
            by_key.setdefault(key, {})[r["schedule"]] = r
    comparisons = []
    for (pp, v, m), pair in sorted(by_key.items()):
        if "streamed" not in pair or "sequential" not in pair:
            continue
        s, q = pair["streamed"], pair["sequential"]
        comparisons.append({
            "pp": pp, "virtual_pp": v, "num_microbatches": m,
            "streamed_bubble": s["measured_bubble_fraction"],
            "sequential_bubble": q["measured_bubble_fraction"],
            "streamed_step_s": s["step_s"],
            "sequential_step_s": q["step_s"],
            # verdict on the step times (µs-precision), NOT the derived
            # bubble fractions: both share t_plain, so this is the same
            # ordering without the clamp-at-0 artifact (both pipes
            # beating the plain baseline would tie the fractions at 0)
            "streamed_wins": s["step_s"] < q["step_s"],
        })
    return {
        "metric": "pp_bubble_virtual_pp",
        "configs": len(comparisons),
        "streamed_wins": sum(c["streamed_wins"] for c in comparisons),
        "comparisons": comparisons,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="bank the records here ('' = don't write)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--virtual-pp", action="store_true",
                    help="sweep streamed vs sequential virtual-chunk "
                         "schedules instead of the plain-M sweep")
    ap.add_argument("--gate", action="store_true",
                    help="with --virtual-pp: exit non-zero unless the "
                         "streamed schedule's measured bubble is strictly "
                         "below the sequential one at every (pp, v, M)")
    ap.add_argument("--pp", type=int, nargs="*", default=None,
                    help="pp degrees to sweep (defaults per mode)")
    ap.add_argument("--microbatches", type=int, nargs="*", default=None)
    ap.add_argument("--virtual", type=int, default=2,
                    help="virtual_pp degree of the --virtual-pp sweep")
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--tiny", action="store_true",
                    help="shrink everything for smoke tests")
    args = ap.parse_args(argv)

    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    records = []
    if args.virtual_pp:
        # default sweep sits in the thin-stage regime deliberately (module
        # docstring): M large vs v*pp so the streamed schedule's dead-row
        # fill/drain amortizes, per-row compute small so the ~v x tick
        # reduction is the dominant term
        pps = args.pp or ([2] if args.tiny else [2, 4])
        mbs = tuple(args.microbatches or ([4] if args.tiny else [16]))
        gb = args.global_batch or (8 if args.tiny else 16)
        seq = args.seq or 8
        repeats = max(args.repeats, 5) if not args.tiny else args.repeats
        base = dict(VPP_BASE)
        if args.tiny:
            base.update(num_layers=4)
        for pp in pps:
            records += measure(
                pp, mbs, global_batch=gb, seq=seq, repeats=repeats,
                base=base, virtual_pp=args.virtual,
                schedules=("streamed", "sequential"))
        summary = virtual_pp_summary(records)
        print(json.dumps(summary), flush=True)
    else:
        pps = args.pp or ([2] if args.tiny else [2, 4])
        mbs = tuple(args.microbatches
                    or ((2,) if args.tiny else (1, 2, 4, 8, 16)))
        gb = args.global_batch or (4 if args.tiny else 16)
        seq = args.seq or (16 if args.tiny else 128)
        base = dict(BASE)
        if args.tiny:
            base.update(num_layers=4, hidden_size=32, ffn_hidden_size=64,
                        vocab_size=64)
        for pp in pps:
            records += measure(pp, mbs, global_batch=gb, seq=seq,
                               repeats=args.repeats, base=base)
        summary = None
    if args.out:
        payload = {"records": records}
        if summary is not None:
            payload["virtual_pp_summary"] = summary
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
    if args.gate and args.virtual_pp:
        losing = [c for c in summary["comparisons"] if not c["streamed_wins"]]
        if losing or not summary["comparisons"]:
            raise SystemExit(
                f"virtual-pp gate: streamed schedule did not beat the "
                f"sequential baseline at {losing or 'any config'}")
    return records


if __name__ == "__main__":
    main()
