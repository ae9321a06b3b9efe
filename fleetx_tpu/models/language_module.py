"""Language-model modules (reference /root/reference/ppfleetx/models/
language_model/language_module.py:47-222).

One GPTModule serves every topology — the reference's class-per-parallelism
dispatch (GPTModel | GPTModelHybrid | GPTForPretrainingPipe picked by
nranks/pp_degree, language_module.py:153-188) is unnecessary when sharding is
annotation-driven.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from fleetx_tpu.models.gpt.model import (
    GPTConfig,
    GPTForPretraining,
    pretraining_loss,
)
from fleetx_tpu.models.module import BasicModule
from fleetx_tpu.utils.log import logger

__all__ = ["LanguageModule", "GPTModule"]


class LanguageModule(BasicModule):
    """Adds LM-style logging: loss, lr, avg step cost, ips (tokens/s) — the
    ``ips:`` keyword line is the reference's log contract (reference
    run_benchmark.sh:20-22)."""

    def training_step_end(self, log: Dict) -> None:
        # mfu rides the same parsed line (perfbench/run.py reports ``train_mfu``,
        # docs/OBSERVABILITY.md; "-" when XLA exposed no flops for this step),
        # and on the FIRST line the compiled step's collectives by kind, which
        # the Trainer hands over once. (No line more: model code below is traced.)
        mfu = log.get("mfu")
        logger.train(
            "[train] epoch: %d, batch: %d, loss: %.9f, avg_batch_cost: %.5f sec, "
            "speed: %.2f step/s, ips_total: %.0f tokens/s, ips: %.0f tokens/s, "
            "mfu: %s, learning rate: %.3e%s",
            log["epoch"],
            log["batch"],
            log["loss"],
            log["batch_cost"],
            1.0 / max(log["batch_cost"], 1e-9),
            log["ips_total"],
            log["ips"],
            ("%.4f" % mfu) if mfu is not None else "-",
            log["lr"], "".join(", %s: %d" % kv for kv in (log.get("collectives") or {}).items()),
        )

    def validation_step_end(self, log: Dict) -> None:
        logger.eval(
            "[eval] epoch: %d, batch: %d, loss: %.9f, avg_eval_cost: %.5f sec",
            log["epoch"],
            log["batch"],
            log["loss"],
            log["batch_cost"],
        )


def resolve_compute_dtype(engine_cfg):
    """AMP config → compute dtype. fp16 maps to bf16: TPU-native mixed
    precision needs no loss scaling (the reference's GradScaler + AMP-O2
    decorate, eager_engine.py:162-172, has no TPU equivalent to need)."""
    mp = (engine_cfg.get("mix_precision") or {}) if isinstance(engine_cfg, dict) else {}
    name = mp.get("dtype") or ("bfloat16" if mp.get("use_pure_fp16") else "float32")
    return {"bfloat16": jnp.bfloat16, "float16": jnp.bfloat16,
            "float32": jnp.float32}[str(name)]


def load_pretrained_gpt_backbone(params, artifact_dir, fuse_attn_qkv):
    """Merge a pretrained GPT backbone from an export artifact into a fresh
    param tree: weights copied by path under the 'gpt' subtree, fused/split
    qkv layouts converted to the target config, heads without a pretrained
    counterpart left at fresh init (reference checkpoint conversion,
    language_module.py:293-372). Shared by GPTModule (pretrain/eval/
    generation warm starts, e.g. a converted HF GPT-2) and
    GPTFinetuneModule."""
    import numpy as np

    from fleetx_tpu.models.gpt.model import convert_qkv_layout
    from fleetx_tpu.utils.export import load_exported

    _, src_params, _ = load_exported(artifact_dir)
    src = src_params.get("gpt", src_params)
    src = convert_qkv_layout(src, to_fused=fuse_attn_qkv)
    if "gpt" not in params:
        raise ValueError("params have no 'gpt' backbone subtree")

    stats = {"matched": 0, "fresh": 0}

    def merge(dst, srcd, path):
        out = {}
        for k, v in dst.items():
            here = f"{path}/{k}"
            if isinstance(v, dict):
                out[k] = (
                    merge(v, srcd[k], here)
                    if isinstance(srcd.get(k), dict) else v
                )
            elif k in srcd:
                sv = np.asarray(srcd[k])
                if sv.shape != np.shape(v):
                    raise ValueError(
                        f"pretrained shape mismatch at {here}: "
                        f"{sv.shape} vs {np.shape(v)}"
                    )
                out[k] = sv.astype(np.asarray(v).dtype)
                stats["matched"] += 1
            else:
                out[k] = v  # no pretrained counterpart: keep fresh init
                stats["fresh"] += 1
        return out

    new = dict(params)
    new["gpt"] = merge(params["gpt"], src, "gpt")
    if stats["matched"] == 0:
        raise ValueError(
            f"no parameter in {artifact_dir} matched the target tree — "
            "layouts disagree (e.g. scan_layers on one side only); refusing "
            "to 'warm start' from random init"
        )
    logger.info(
        "loaded pretrained backbone from %s (%d leaves matched, %d fresh)",
        artifact_dir, stats["matched"], stats["fresh"],
    )
    return new


def init_pipeline_params_via_sequential(nets, rng, tokens):
    """Initialize a pp>1 GPT through its SEQUENTIAL twin, then remap.

    The pipeline scopes (nn.scan over ticks -> nn.vmap over stages -> nn.scan
    over layers) fold the init RNG differently than the plain layer scan, so
    initializing the pp model directly gives different weights than the
    single-device model for the same seed. Parallelism must stay a layout
    choice (sharded 1-step loss == single-device loss): init the pp=1 twin,
    reshape [L, ...] -> [pp, L/pp, ...] with the checkpoint converter, and
    graft the values into the pp model's own axis-metadata boxes so sharding
    derivation still sees the pipeline's logical axes ('stage', 'layers')."""
    import dataclasses

    import flax
    import flax.linen as nn

    from fleetx_tpu.parallel.pipeline import sequential_params_to_pipeline

    gcfg = nets.cfg
    seq_cfg = dataclasses.replace(
        gcfg, pp_degree=1, num_microbatches=1, virtual_pp_degree=1,
        scan_layers=True, no_recompute_layers=None,
    )
    seq_vars = type(nets)(seq_cfg).init(rng, tokens)
    is_box = lambda x: isinstance(x, nn.meta.AxisMetadata)
    unboxed = jax.tree.map(
        lambda x: x.unbox() if is_box(x) else x,
        flax.core.unfreeze(seq_vars),
        is_leaf=is_box,
    )
    remapped = sequential_params_to_pipeline(
        unboxed, gcfg.pp_degree, max(gcfg.virtual_pp_degree, 1),
        stream=getattr(gcfg, "virtual_pp_stream", None),
    )
    abstract = jax.eval_shape(lambda r: nets.init(r, tokens), rng)
    flat_abs = flax.traverse_util.flatten_dict(
        flax.core.unfreeze(abstract), sep="/"
    )
    flat_val = flax.traverse_util.flatten_dict(
        flax.core.unfreeze(remapped), sep="/"
    )
    if set(flat_abs) != set(flat_val):
        missing = set(flat_abs) ^ set(flat_val)
        raise ValueError(
            f"sequential->pipeline param remap mismatch at: {sorted(missing)[:5]}"
        )
    out = {
        k: box.replace_boxed(flat_val[k].astype(box.unbox().dtype))
        if is_box(box) else flat_val[k]
        for k, box in flat_abs.items()
    }
    return flax.traverse_util.unflatten_dict(out, sep="/")


class GPTModule(LanguageModule):
    """GPT pretraining module: batch = (tokens, position_ids, labels,
    loss_mask)."""

    def get_model(self):
        model_cfg = self.cfg.Model if hasattr(self.cfg, "Model") else self.cfg
        gcfg = GPTConfig.from_model_config(model_cfg)
        eng = getattr(self.cfg, "Engine", None) or {}
        extra = {"dtype": resolve_compute_dtype(eng)}
        dist = getattr(self.cfg, "Distributed", None) or {}
        pp = dist.get("pp_degree") or 1
        if pp > 1:
            # PP folds grad accumulation into the pipeline's microbatch
            # stream (reference pipeline_configs accumulate_steps semantics,
            # env.py:103-107)
            extra["pp_degree"] = pp
            extra["num_microbatches"] = max(eng.get("accumulate_steps") or 1, 1)
        cp = dist.get("cp_degree") or 1
        if cp > 1:
            extra["cp_degree"] = cp
        gcfg = GPTConfig(**{**gcfg.__dict__, **extra})
        if gcfg.fused_ce:
            # the fused LM-head+CE kernel needs a lane-aligned PER-SHARD
            # vocab block (mp>1 runs the vocab-parallel form); cp/pp stay
            # demoted — fall back to the XLA logits path instead of
            # crashing at trace time
            from fleetx_tpu.ops.pallas.ce_loss import fit_vocab_block

            mp = dist.get("mp_degree") or 1
            why = None
            if gcfg.vocab_size % mp or fit_vocab_block(
                    gcfg.vocab_size // mp) is None:
                why = (f"vocab {gcfg.vocab_size} / mp {mp} admits no "
                       "lane-aligned block (128-multiple or 64)")
            elif cp > 1 or pp > 1:
                # mp>1 is supported (vocab-parallel kernel); cp would
                # gather the seq-sharded hidden states and pp runs the
                # loss outside the validated path
                why = f"cp_degree={cp}/pp_degree={pp} (validated for 1/1)"
            if why:
                logger.warning(
                    "Model.fused_ce disabled: %s; using the XLA logits "
                    "path", why)
                gcfg = GPTConfig(**{**gcfg.__dict__, "fused_ce": False})
        sharding = dist.get("sharding") or {}
        self._data_world = (dist.get("dp_degree") or 1) * (
            sharding.get("sharding_degree") or 1)
        self.gpt_config = gcfg
        return GPTForPretraining(gcfg)

    def init_params(self, rng, batch):
        tokens = batch["tokens"]
        if (getattr(self.gpt_config, "pp_degree", 1) or 1) <= 1:
            return self.nets.init(rng, tokens)
        return init_pipeline_params_via_sequential(self.nets, rng, tokens)

    def load_pretrained(self, params):
        """``Model.pretrained`` (export artifact dir, e.g. from
        tools/convert_hf_gpt2.py) warm-starts the GPT backbone for
        pretraining / eval / generation modules."""
        pre = (self.cfg.Model or {}).get("pretrained")
        if not pre:
            return None
        return load_pretrained_gpt_backbone(
            params, pre, self.gpt_config.fuse_attn_qkv
        )

    def cp_prepare(self, batch):
        """(tokens, position_ids, labels, loss_mask), zig-zag-permuted along
        the sequence when context parallelism is on.

        Ring attention runs on zig-zag sequence order; tokens/labels/mask are
        permuted identically and true positions carried explicitly, so the
        order-invariant masked losses/scores need no un-permute. Every module
        that feeds the GPT model (pretrain/MoE/eval) must go through here.
        """
        tokens = batch["tokens"]
        position_ids = batch.get("position_ids")
        labels = batch.get("labels")
        loss_mask = batch.get("loss_mask")
        cp = getattr(self.gpt_config, "cp_degree", 1)
        if cp <= 1:
            return tokens, position_ids, labels, loss_mask
        from fleetx_tpu.parallel.context_parallel import zigzag_split

        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :], tokens.shape
            )
        z = lambda x: None if x is None else zigzag_split(x, cp, axis=1)
        return z(tokens), z(position_ids), z(labels), z(loss_mask)

    def loss_fn(self, params, batch, rng, train: bool):
        tokens, position_ids, labels, loss_mask = self.cp_prepare(batch)
        rngs = {"dropout": rng} if train and rng is not None else None
        nd = getattr(self, "_data_world", 1)
        # per-SHARD token count must stay 8-aligned (the kernel shard_maps
        # over dp/fsdp); otherwise fall back to the logits path
        shard_ok = labels.size % nd != 0 or (labels.size // nd) % 8 == 0
        if (getattr(self.gpt_config, "fused_ce", False)
                and labels.size % 8 == 0 and shard_ok):
            # fused LM-head+CE path: the model returns per-token losses
            # and [b, s, vocab] logits never materialize (Model.fused_ce,
            # ops/pallas/ce_loss.py)
            from fleetx_tpu.models.gpt.model import masked_loss_mean

            token_loss = self.nets.apply(
                {"params": params}, tokens, position_ids,
                deterministic=not train, rngs=rngs, labels=labels,
            )
            return masked_loss_mean(token_loss, loss_mask), {}
        logits = self.nets.apply(
            {"params": params},
            tokens,
            position_ids,
            deterministic=not train,
            rngs=rngs,
        )
        loss = pretraining_loss(logits, labels, loss_mask)
        return loss, {}

    def input_spec(self):
        glb = self.cfg.Global
        seq = self.cfg.Data.Train.dataset.max_seq_len if self.cfg.Data else 1024
        b = glb.micro_batch_size or 1
        return {
            "tokens": jax.ShapeDtypeStruct((b, seq), jnp.int32),
            "position_ids": jax.ShapeDtypeStruct((b, seq), jnp.int32),
        }
