"""Decode (serving) throughput bench: kv-cache generation on GPT-345M.

The reference ships generation/inference as first-class products
(/root/reference/tasks/gpt/generation.py, projects/gpt/inference.py), so
serving perf is tracked like training perf (VERDICT r3 item 10): one JSON
record per decode mode — greedy and beam-4, batch 1 and 8 — measuring
generated tokens/s through the jitted prefill+while_loop decode path.

Standalone:  python tools/bench_decode.py
In-process:  from tools.bench_decode import decode_records
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

# BENCH_DECODE_TINY=1 shrinks everything for CPU smoke tests of the
# harness itself (schema + decode-path liveness, not perf)
_TINY = os.environ.get("BENCH_DECODE_TINY") == "1"
VOCAB = 128 if _TINY else 50304
PROMPT_LEN = 8 if _TINY else 128
GEN_LEN = 8 if _TINY else 128


def _model_345m(max_pos: int):
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

    cfg = GPTConfig(
        vocab_size=VOCAB,
        hidden_size=64 if _TINY else 1024,
        num_layers=2 if _TINY else 24,
        num_attention_heads=4 if _TINY else 16,
        ffn_hidden_size=128 if _TINY else 4096,
        max_position_embeddings=max_pos,
        hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0,
        fuse_attn_qkv=True,
        # length-1 decode queries route to the Pallas flash-decode kernel
        # (ops/pallas/decode_attention.py) on TPU; prefill and non-tiling
        # shapes fall back to the XLA path inside the model
        use_flash_attention=True,
        dtype=jnp.float32 if _TINY else jnp.bfloat16,
    )
    return GPTForPretraining(cfg)


def _prefill_latency_s(model, variables, ids, steps: int) -> float:
    """Median latency of the jitted prefill alone — the same right-sized
    cache + masked forward ``generate()`` runs before its decode loop, so
    ``total - prefill`` isolates the while_loop's steady-state cost."""
    import jax
    import jax.numpy as jnp

    from fleetx_tpu.models.gpt.generation import right_size_decode_cache

    b, prompt_len = ids.shape
    sized, cache_len = right_size_decode_cache(model, prompt_len + GEN_LEN)
    params = variables["params"] if "params" in variables else variables

    @jax.jit
    def prefill(params, ids):
        cache_shapes = jax.eval_shape(
            lambda: sized.init(
                jax.random.PRNGKey(0),
                jnp.zeros((b, 1), jnp.int32),
                jnp.zeros((b, 1), jnp.int32),
                decode=True,
            )
        )["cache"]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             cache_shapes)
        kv_mask = jnp.ones((b, 1, 1, cache_len), bool)
        pos = jnp.broadcast_to(jnp.arange(prompt_len), (b, prompt_len))
        logits, _ = sized.apply(
            {"params": params, "cache": cache},
            ids, pos.astype(jnp.int32), kv_mask,
            decode=True, mutable=["cache"],
        )
        return logits

    jax.device_get(prefill(params, ids))  # compile + warmup
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        jax.device_get(prefill(params, ids))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def decode_records(modes=("greedy", "beam"), batches=(1, 8), steps: int = 3):
    """Returns one record per (mode, batch): median-of-``steps`` timed runs
    after a compile warmup. min_length pins the decode length (see below)
    so random-weight runs can't finish early and inflate tokens/s.

    ``detail`` splits the end-to-end time into the prefill latency and the
    steady-state per-token decode latency so serving wins can be attributed
    to the right phase (prompt processing vs the kv-cache loop)."""
    import jax

    from fleetx_tpu.models.gpt.generation import GenerationConfig, generate

    max_pos = PROMPT_LEN + GEN_LEN
    model = _model_345m(max_pos)
    rng = np.random.RandomState(0)
    prompt1 = jax.numpy.asarray(
        rng.randint(0, VOCAB, (max(batches), PROMPT_LEN)), jax.numpy.int32
    )
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0), prompt1[:1, :8]
    )

    # prefill cost depends only on the batch (beam search prefills at batch
    # size too, expanding to beams afterwards) — measure once per batch
    prefill_s = {
        b: _prefill_latency_s(model, variables, prompt1[:b], steps)
        for b in batches
    }

    records = []
    for mode in modes:
        gen_cfg = GenerationConfig(
            max_length=GEN_LEN,
            # min_length == max_length suppresses EOS for the whole run, so
            # every timing decodes exactly GEN_LEN tokens (no early-finish
            # variance from random weights)
            min_length=GEN_LEN,
            decode_strategy="beam_search" if mode == "beam" else "greedy",
            pad_token_id=0,
            num_beams=4 if mode == "beam" else 1,
            length_penalty=1.0,
        )

        @functools.partial(jax.jit, static_argnums=())
        def run(params, ids):
            return generate(model, params, ids, gen_cfg)

        for b in batches:
            ids = prompt1[:b]
            out = run(variables, ids)  # compile + warmup
            # every timing ends with a host transfer, exactly like
            # bench.py's trainer
            jax.device_get(out)
            times = []
            for _ in range(steps):
                t0 = time.perf_counter()
                out = run(variables, ids)
                jax.device_get(out)
                times.append(time.perf_counter() - t0)
            dt = float(np.median(times))
            toks = b * GEN_LEN
            # steady-state decode: what the while_loop costs once the prompt
            # is in the cache (clamped at 0 in case of timing noise on very
            # small runs)
            decode_s = max(dt - prefill_s[b], 0.0)
            records.append({
                "metric": f"gpt_345m_decode_{mode}_b{b}",
                "value": round(toks / dt, 1),
                "unit": "tokens/s",
                "vs_baseline": None,  # reference publishes no decode tok/s
                "detail": {
                    "batch": b,
                    "prompt_len": PROMPT_LEN,
                    "gen_len": GEN_LEN,
                    "num_beams": gen_cfg.num_beams,
                    "latency_s_per_seq": round(dt, 3),
                    "ms_per_token": round(dt / GEN_LEN * 1e3, 2),
                    "prefill_ms": round(prefill_s[b] * 1e3, 2),
                    "decode_ms_per_token": round(decode_s / GEN_LEN * 1e3, 2),
                    "device": getattr(jax.devices()[0], "device_kind", "?"),
                },
            })
    return records


if __name__ == "__main__":
    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    for rec in decode_records():
        print(json.dumps(rec))
