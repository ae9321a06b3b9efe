"""Pipeline-parallel tests: numerical equivalence with the sequential stack,
and an end-to-end pp2 x dp2 x mp2 training step on the 8-device mesh."""

import textwrap

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

BASE = dict(
    vocab_size=128,
    hidden_size=64,
    num_layers=4,
    num_attention_heads=4,
    ffn_hidden_size=128,
    max_position_embeddings=32,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype=jnp.float32,
    use_flash_attention=False,
)


def _remap_scan_params_to_pipeline(v_seq, pp, layers_per_stage):
    from fleetx_tpu.parallel.pipeline import sequential_params_to_pipeline

    unboxed = jax.tree.map(
        lambda v: v.value if hasattr(v, "value") else v,
        flax.core.unfreeze(v_seq["params"]),
        is_leaf=lambda v: hasattr(v, "value"),
    )
    return sequential_params_to_pipeline({"params": unboxed}, pp)


@pytest.mark.slow  # 16.5s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_pipeline_param_remap_roundtrip():
    from fleetx_tpu.parallel.pipeline import (
        maybe_pipeline_params_to_sequential,
        sequential_params_to_pipeline,
    )

    model = GPTForPretraining(GPTConfig(**BASE))
    tokens = jnp.zeros((1, 4), jnp.int32)
    v = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    v = {"params": jax.tree.map(
        lambda x: x.value if hasattr(x, "value") else x, flax.core.unfreeze(v["params"]),
        is_leaf=lambda x: hasattr(x, "value"),
    )}
    pipe = sequential_params_to_pipeline(v, 2)
    back = maybe_pipeline_params_to_sequential(pipe)
    flat_v = flax.traverse_util.flatten_dict(v["params"], sep="/")
    flat_b = flax.traverse_util.flatten_dict(back["params"], sep="/")
    assert set(flat_v) == set(flat_b)
    for k in flat_v:
        np.testing.assert_array_equal(np.asarray(flat_v[k]), np.asarray(flat_b[k]))
    # no-op on already-sequential trees
    assert maybe_pipeline_params_to_sequential(v) is v


@pytest.mark.slow  # 31.0s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_pipeline_matches_sequential():
    seq_model = GPTForPretraining(GPTConfig(**BASE))
    pipe_model = GPTForPretraining(
        GPTConfig(**{**BASE, "pp_degree": 2, "num_microbatches": 2})
    )
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (4, 16)), jnp.int32
    )
    v_seq = jax.jit(seq_model.init)(jax.random.PRNGKey(0), tokens)
    v_pipe = _remap_scan_params_to_pipeline(v_seq, 2, 2)
    out_seq = seq_model.apply(v_seq, tokens)
    out_pipe = pipe_model.apply(v_pipe, tokens)
    np.testing.assert_allclose(
        np.asarray(out_seq), np.asarray(out_pipe), rtol=2e-4, atol=2e-4
    )


@pytest.mark.slow  # 53.3s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_pipeline_grads_match_sequential():
    from fleetx_tpu.models.gpt.model import pretraining_loss

    seq_model = GPTForPretraining(GPTConfig(**BASE))
    pipe_model = GPTForPretraining(
        GPTConfig(**{**BASE, "pp_degree": 2, "num_microbatches": 2})
    )
    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, 128, (4, 16)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, 128, (4, 16)), jnp.int32)
    mask = jnp.ones((4, 16), jnp.float32)
    v_seq = jax.jit(seq_model.init)(jax.random.PRNGKey(0), tokens)
    v_pipe = _remap_scan_params_to_pipeline(v_seq, 2, 2)

    def loss(model, v):
        def f(p):
            return pretraining_loss(model.apply(p, tokens), labels, mask)

        return jax.value_and_grad(f)(v)

    l_seq, g_seq = loss(seq_model, v_seq)
    l_pipe, g_pipe = loss(pipe_model, v_pipe)
    assert float(l_seq) == pytest.approx(float(l_pipe), rel=1e-5)
    # compare word embedding grads (tied head -> exercises shared-embedding
    # gradient summing across pipeline boundary)
    ge_seq = g_seq["params"]["gpt"]["word_embeddings"]
    ge_pipe = g_pipe["params"]["gpt"]["word_embeddings"]
    ge_seq = ge_seq.value if hasattr(ge_seq, "value") else ge_seq
    np.testing.assert_allclose(
        np.asarray(ge_seq), np.asarray(ge_pipe), rtol=2e-3, atol=1e-5
    )
    # layer param grads: reshape seq [L,...] to [pp,Lp,...] and compare
    flat_seq = flax.traverse_util.flatten_dict(
        flax.core.unfreeze(g_seq["params"]), sep="/"
    )
    flat_pipe = flax.traverse_util.flatten_dict(
        flax.core.unfreeze(g_pipe["params"]), sep="/"
    )
    for k, v in flat_seq.items():
        if not k.startswith("gpt/layers/layer/"):
            continue
        val = v.value if hasattr(v, "value") else v
        pk = k.replace("gpt/layers/layer/", "gpt/layers/pipe/stages/layers/layer/")
        pv = flat_pipe[pk]
        pv = pv.value if hasattr(pv, "value") else pv
        np.testing.assert_allclose(
            np.asarray(val).reshape(pv.shape), np.asarray(pv),
            rtol=2e-3, atol=1e-5, err_msg=k,
        )


@pytest.mark.slow  # 9.4s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_pp_training_step_on_mesh(tmp_path, eight_devices):
    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import get_config

    p = tmp_path / "pp.yaml"
    p.write_text(textwrap.dedent("""
        Global:
          seed: 7
          local_batch_size: 8
          micro_batch_size: 2
        Engine:
          max_steps: 2
          logging_freq: 1
          eval_freq: 0
          save_load:
            save_steps: 1000
        Model:
          module: GPTModule
          vocab_size: 128
          hidden_size: 64
          num_layers: 4
          num_attention_heads: 4
          ffn_hidden_size: 128
          max_position_embeddings: 32
          hidden_dropout_prob: 0.1
          attention_probs_dropout_prob: 0.0
          use_flash_attention: False
          use_recompute: True
          recompute_granularity: full
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
          pp_degree: 2
    """))
    cfg = get_config(str(p), nranks=8)
    cfg.Engine.save_load.output_dir = str(tmp_path / "out")
    assert cfg.Engine.accumulate_steps == 4  # local 8 / micro 2
    module = build_module(cfg)
    assert module.gpt_config.pp_degree == 2
    assert module.gpt_config.num_microbatches == 4
    trainer = Trainer(cfg, module)
    rng = np.random.RandomState(0)
    gbs = cfg.Global.global_batch_size
    data = [
        {
            "tokens": rng.randint(0, 128, (gbs, 32)).astype(np.int32),
            "labels": rng.randint(0, 128, (gbs, 32)).astype(np.int32),
            "loss_mask": np.ones((gbs, 32), np.float32),
        }
        for _ in range(2)
    ]
    trainer.fit(data)
    assert int(trainer.state.step) == 2
    # stage axis is sharded over pp
    from fleetx_tpu.core.engine import _unbox
    flat = flax.traverse_util.flatten_dict(
        flax.core.unfreeze(_unbox(trainer.state.params)), sep="/"
    )
    qkv = [v for k, v in flat.items() if "qkv_proj/kernel" in k][0]
    assert qkv.shape[0] == 2  # [pp, Lp, ...]


@pytest.mark.slow  # 10.1s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_pipeline_per_example_mask_matches_sequential():
    """A padded batch (per-example attention masks) must stream through the
    stages with its microbatch and reproduce the sequential output
    (VERDICT r2 weak #7: PP previously rejected per-example masks)."""
    seq_model = GPTForPretraining(GPTConfig(**BASE))
    pipe_model = GPTForPretraining(
        GPTConfig(**{**BASE, "pp_degree": 2, "num_microbatches": 2})
    )
    rng = np.random.RandomState(3)
    b, s = 4, 16
    tokens = jnp.asarray(rng.randint(0, 128, (b, s)), jnp.int32)
    # distinct left-pad per example -> masks genuinely differ across the
    # microbatches
    pad = np.zeros((b, s), np.int32)
    for i in range(b):
        pad[i, : rng.randint(0, 6)] = 1
    valid = 1 - pad
    attn_mask = jnp.asarray(valid[:, None, None, :])  # [b, 1, 1, kv]

    v_seq = jax.jit(seq_model.init)(jax.random.PRNGKey(0), tokens)
    v_pipe = _remap_scan_params_to_pipeline(v_seq, 2, 2)
    out_seq = seq_model.apply(v_seq, tokens, None, attn_mask)
    out_pipe = pipe_model.apply(v_pipe, tokens, None, attn_mask)
    np.testing.assert_allclose(
        np.asarray(out_seq), np.asarray(out_pipe), rtol=2e-4, atol=2e-4
    )
    # and the mask actually matters (masked vs unmasked outputs differ)
    out_nomask = pipe_model.apply(v_pipe, tokens)
    assert np.abs(np.asarray(out_pipe) - np.asarray(out_nomask)).max() > 1e-3


def test_virtual_pipeline_stream_compact_parity():
    """Tier-1 compact gate for the streamed virtual-chunk schedule
    (ISSUE 12): forward parity streamed vs sequential-chunk vs plain
    scan stack on a tiny model, and the streamed param layout equals
    the plain-pipe layout with v*pp stage rows (so the remap helpers
    round-trip it unchanged)."""
    from fleetx_tpu.parallel.pipeline import (
        maybe_pipeline_params_to_sequential,
        sequential_params_to_pipeline,
    )

    pp, v = 2, 2
    cfg = {**BASE, "num_layers": 4, "hidden_size": 32,
           "ffn_hidden_size": 64, "max_position_embeddings": 8}
    seq_model = GPTForPretraining(GPTConfig(**cfg))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 128, (4, 8)), jnp.int32)
    v_seq = jax.jit(seq_model.init)(jax.random.PRNGKey(0), tokens)
    unboxed = {"params": jax.tree.map(
        lambda x: x.value if hasattr(x, "value") else x,
        flax.core.unfreeze(v_seq["params"]),
        is_leaf=lambda x: hasattr(x, "value"))}
    out_plain = seq_model.apply(unboxed, tokens)

    outs = {}
    for stream in (True, False):
        model = GPTForPretraining(GPTConfig(
            **{**cfg, "pp_degree": pp, "num_microbatches": 2,
               "virtual_pp_degree": v, "virtual_pp_stream": stream}))
        params = sequential_params_to_pipeline(unboxed, pp, virtual_pp=v,
                                               stream=stream)
        outs[stream] = model.apply(params, tokens)
        np.testing.assert_allclose(
            np.asarray(out_plain), np.asarray(outs[stream]),
            rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(
        np.asarray(outs[True]), np.asarray(outs[False]),
        rtol=2e-4, atol=2e-4)

    # layout contract: streamed == plain pipe with v*pp rows, and the
    # inverse remap reproduces the sequential tree byte-exactly
    streamed = sequential_params_to_pipeline(unboxed, pp, virtual_pp=v,
                                             stream=True)
    plain_vpp = sequential_params_to_pipeline(unboxed, pp * v)
    fa = flax.traverse_util.flatten_dict(streamed["params"], sep="/")
    fb = flax.traverse_util.flatten_dict(plain_vpp["params"], sep="/")
    assert set(fa) == set(fb)
    for k in fa:
        np.testing.assert_array_equal(np.asarray(fa[k]), np.asarray(fb[k]))
    back = maybe_pipeline_params_to_sequential(streamed)
    fb = flax.traverse_util.flatten_dict(back["params"], sep="/")
    fo = flax.traverse_util.flatten_dict(unboxed["params"], sep="/")
    assert set(fb) == set(fo)
    for k in fo:
        np.testing.assert_array_equal(np.asarray(fo[k]), np.asarray(fb[k]))


@pytest.mark.parametrize("pp,v,stream", [(2, 2, True), (2, 2, False),
                                         (4, 2, True), (4, 2, False)])
@pytest.mark.slow  # 71.3s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_virtual_pipeline_matches_sequential(pp, v, stream):
    """pp x virtual chunks: outputs AND grads must match the sequential
    stack (VERDICT r2 item 10 done-criterion) — on BOTH virtual-chunk
    schedules (streamed fused scan and chained per-chunk scans)."""
    from fleetx_tpu.parallel.pipeline import sequential_params_to_pipeline

    cfg = {**BASE, "num_layers": 8}
    seq_model = GPTForPretraining(GPTConfig(**cfg))
    pipe_model = GPTForPretraining(GPTConfig(
        **{**cfg, "pp_degree": pp, "num_microbatches": 2,
           "virtual_pp_degree": v, "virtual_pp_stream": stream}
    ))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 128, (4, 16)), jnp.int32)
    labels = jnp.asarray(rng.randint(0, 128, (4, 16)), jnp.int32)

    v_seq = jax.jit(seq_model.init)(jax.random.PRNGKey(0), tokens)
    unboxed = {"params": jax.tree.map(
        lambda x: x.value if hasattr(x, "value") else x,
        flax.core.unfreeze(v_seq["params"]),
        is_leaf=lambda x: hasattr(x, "value"))}
    v_pipe = sequential_params_to_pipeline(unboxed, pp, virtual_pp=v,
                                           stream=stream)

    out_seq = seq_model.apply(v_seq, tokens)
    out_pipe = pipe_model.apply(v_pipe, tokens)
    np.testing.assert_allclose(
        np.asarray(out_seq), np.asarray(out_pipe), rtol=2e-4, atol=2e-4)

    from fleetx_tpu.models.gpt.model import pretraining_loss
    from fleetx_tpu.parallel.pipeline import pipeline_params_to_sequential

    mask = jnp.ones_like(tokens, jnp.float32)

    def loss_seq(p):
        return pretraining_loss(seq_model.apply(p, tokens), labels, mask)

    def loss_pipe(p):
        return pretraining_loss(pipe_model.apply(p, tokens), labels, mask)

    g_seq = jax.grad(loss_seq)(unboxed)["params"]
    g_pipe = jax.grad(loss_pipe)(v_pipe)
    g_pipe_seq = pipeline_params_to_sequential(g_pipe)["params"]
    flat_a = flax.traverse_util.flatten_dict(g_seq, sep="/")
    flat_b = flax.traverse_util.flatten_dict(g_pipe_seq, sep="/")
    assert set(flat_a) == set(flat_b)
    for k in flat_a:
        np.testing.assert_allclose(
            np.asarray(flat_a[k]), np.asarray(flat_b[k]),
            rtol=5e-3, atol=1e-5, err_msg=k)
