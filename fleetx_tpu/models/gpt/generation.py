"""Autoregressive generation with kv-cache — greedy / temperature sampling /
top-k / top-p, plus logits processors.

Parity with the reference decode stack (/root/reference/ppfleetx/models/
language_model/gpt/dygraph/single_model.py:781-1247 ``GPTForGeneration`` and
processor.py logits processors), redesigned for XLA: the decode loop is a
``lax.while_loop`` over a static-shape token buffer (no dynamic shapes), the
cache is the flax 'cache' collection, and one compiled step serves the whole
generation — the reference re-runs a Python loop per token.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

__all__ = ["GenerationConfig", "generate", "process_logits", "prompt_seen",
           "mark_seen", "init_decode_cache", "decode_step"]


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Decode-strategy knobs (reference GPTForGeneration config surface:
    top-k/p, beams, penalties, forced tokens)."""
    max_length: int = 64  # new tokens to generate
    min_length: int = 0
    decode_strategy: str = "sampling"  # 'greedy' | 'sampling' | 'beam_search'
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    eos_token_id: int = 50256
    pad_token_id: int = 50256
    forced_eos_token_id: Optional[int] = None
    # beam search (reference config surface single_model.py:803-818)
    num_beams: int = 1
    num_beam_groups: int = 1
    diversity_rate: float = 0.0
    length_penalty: float = 0.0
    early_stopping: bool = False
    forced_bos_token_id: Optional[int] = None
    num_return_sequences: int = 1

    @classmethod
    def from_config(cls, gen_cfg) -> "GenerationConfig":
        d = dict(gen_cfg or {})
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in known and v is not None}
        if d.get("max_dec_len") is not None:
            kw["max_length"] = d["max_dec_len"]
        if d.get("min_dec_len") is not None:
            kw["min_length"] = d["min_dec_len"]
        # surface config typos (e.g. `topk` for `top_k`) instead of silently
        # decoding with defaults; aliases + keys other components read from
        # the Generation section are not typos (use_cache: the kv-cache loop
        # is unconditional here; vocab_dir/seed: tokenizer + rng plumbing)
        aliases = {"max_dec_len", "min_dec_len", "use_cache", "vocab_dir",
                   "seed"}
        ignored = sorted(k for k in d if k not in known and k not in aliases)
        if ignored:
            from fleetx_tpu.utils.log import logger

            logger.warning(
                "GenerationConfig.from_config ignoring unknown keys %s "
                "(known: %s)", ignored, sorted(known | aliases),
            )
        return cls(**kw)


def process_logits(logits, seen, cur_len, cfg: GenerationConfig, *,
                   prompt_len=0, total_len=None):
    """Min-length EOS suppression, repetition penalty, forced EOS (reference
    processor.py: MinLengthLogitsProcessor, RepetitionPenaltyLogitsProcessor,
    ForcedEOSTokenLogitsProcessor).

    ``cur_len`` is the absolute buffer position; min_length counts DECODED
    tokens, so the EOS ban runs while cur_len < prompt_len + min_length
    (the reference offsets min_length by the input length,
    single_model.py:1222). ``seen`` is the [b, vocab] bool scoreboard of
    tokens already emitted/fed (required iff repetition_penalty != 1.0) —
    carried through the decode loop and updated in O(vocab) per step via
    :func:`mark_seen`, replacing the per-step O(total_len * vocab) one-hot
    rebuild over the whole token buffer. ``total_len`` is the token-buffer
    length (forced EOS fires at its last slot)."""
    vocab = logits.shape[-1]
    if cfg.min_length > 0:
        logits = jnp.where(
            (cur_len < prompt_len + cfg.min_length)
            & (jnp.arange(vocab)[None, :] == cfg.eos_token_id),
            -1e9,
            logits,
        )
    if cfg.repetition_penalty != 1.0:
        if seen is None:
            raise ValueError("repetition_penalty != 1.0 needs a seen-token "
                             "scoreboard (see prompt_seen/mark_seen)")
        penalized = jnp.where(
            logits > 0, logits / cfg.repetition_penalty, logits * cfg.repetition_penalty
        )
        logits = jnp.where(seen, penalized, logits)
    if cfg.forced_eos_token_id is not None:
        if total_len is None:
            raise ValueError("forced_eos_token_id needs total_len")
        at_last = cur_len >= (total_len - 1)
        forced = jnp.full_like(logits, -1e9).at[:, cfg.forced_eos_token_id].set(0.0)
        logits = jnp.where(at_last, forced, logits)
    return logits


def prompt_seen(input_ids, attention_mask, vocab: int):
    """[b, vocab] bool scoreboard of the tokens each prompt row actually
    contains (left-pad slots excluded). One O(prompt_len * vocab) pass at
    prefill; decode steps then extend it with :func:`mark_seen`."""
    onehot = jax.nn.one_hot(input_ids, vocab, dtype=jnp.bool_.dtype)
    return (onehot & attention_mask.astype(bool)[..., None]).any(axis=1)


def mark_seen(seen, tok):
    """Fold one sampled token [b] into the [b, vocab] scoreboard — O(vocab)
    per step vs the O(total_len * vocab) rebuild it replaces."""
    return seen | jax.nn.one_hot(tok, seen.shape[-1], dtype=jnp.bool_.dtype)


def right_size_decode_cache(model, total_len: int):
    """(model, cache_len) with the kv cache sized to the decode span.

    The dense fallback streams the whole cache every step, so a
    1024-position cache for a 256-token decode would 4x its per-step HBM
    traffic (the flash-decode kernel reads only the live prefix, but a
    right-sized buffer still saves HBM and beam-reorder traffic); unless
    the caller preset ``decode_cache_len``, clone the model with the cache
    capped at ``total_len`` (rounded up to the flash kernel's 8-row tile).
    A preset that cannot hold the decode raises — an undersized cache
    would silently clamp writes to the last slot and corrupt the output."""
    if model.cfg.decode_cache_len is None:
        cache_len = total_len
        if model.cfg.use_flash_attention:
            # round up to the flash-decode kernel's 8-row KV tile so the
            # Pallas fast path engages for any prompt/gen split; the kernel
            # never reads past cache_index, so the extra slots cost nothing
            cache_len += -cache_len % 8
        model = model.clone(
            cfg=dataclasses.replace(model.cfg, decode_cache_len=cache_len))
    cache_len = model.cfg.decode_cache_len
    if cache_len < total_len:
        raise ValueError(
            f"decode_cache_len({cache_len}) cannot hold prompt_len + "
            f"max_length = {total_len}"
        )
    return model, cache_len


def init_decode_cache(model, batch: int):
    """Zero decode kv-cache for ``batch`` rows at the model's cache length.

    The fresh cache is deterministically zeros (+ zero index), so it is
    built from ``eval_shape`` only — no param sampling or forward trace.
    THE cache constructor for every decode driver: ``generate()``,
    ``beam_search()``, and the continuous-batching serving engine
    (fleetx_tpu/serving/) all start from this tree, so its layout
    ([batch, cache_len, heads*head_dim] per layer + a scalar
    ``cache_index``; [num_pages, page_size, heads*head_dim] shared pages
    when the model carries ``cfg.decode_num_pages``) is defined in exactly
    one place. A model whose layers have kinds (grouped heads, window
    layers: ``models/gpt/hybrid.py``) keeps its pages in one flat pool of
    two classes, built there."""
    if (getattr(model.cfg, "layer_kinds", False)
            and model.cfg.decode_num_pages is not None):
        from fleetx_tpu.models.gpt.hybrid import init_cache

        return init_cache(model, batch)
    cache_shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((batch, 1), jnp.int32),
            jnp.zeros((batch, 1), jnp.int32),
            decode=True,
        )
    )["cache"]
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_shapes)


def decode_step(model, params, cache, input_ids, position_ids, kv_mask=None,
                cache_positions=None, block_tables=None, **rows_in):
    """One cached decode forward: ``(logits, new_cache)``.

    The single reusable step both the ``generate()`` loop body and the
    serving engine's scheduler tick are built from (multi-token
    ``input_ids`` is the prefill case). ``cache_positions`` ([b] int32,
    optional) routes each row's kv write to its own offset — the
    continuous-batching path where slots sit at different decode depths;
    None keeps the shared ``cache_index`` scalar (the one-shot loop).
    ``block_tables`` ([b, pages_per_row] int32) comes along when the model
    carries a paged decode cache (``cfg.decode_num_pages``): each row's
    logical positions then live in the shared page pool at the physical
    pages its table names (serving/cache_manager.py)."""
    logits, mut = model.apply(
        {"params": params, "cache": cache},
        input_ids,
        position_ids,
        kv_mask,
        decode=True,
        cache_positions=cache_positions,
        block_tables=block_tables,
        mutable=["cache"], **rows_in,
    )
    return logits, mut["cache"]


def _top_p_cutoff_bisect(logits, top_p, iters: int = 40):
    """Probability threshold t such that keeping {prob >= t} matches the
    smallest descending-sorted prefix with cumulative prob >= top_p.
    ``top_p`` is a python float or a broadcastable [b, 1] array (the
    serving engine passes per-request values); rows with top_p >= 1 keep
    the whole distribution (the threshold bisects to 0).

    Bisection over the threshold: each step is one O(vocab) masked-sum VPU
    pass, replacing the O(vocab log vocab) full sort (TPU sorts lower to
    sorting networks — the dominant per-step scalar cost at GPT vocab
    sizes). The returned t always satisfies mass({prob >= t}) >= top_p, so
    the kept set is never too small and always contains the argmax; at
    float32 resolution near-tied probabilities at the cutoff may keep a
    tie the sort-based version would have dropped (measure-zero for real
    logits, and sampling is stochastic there anyway)."""
    probs = jax.nn.softmax(logits, axis=-1)

    def bisect(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        mass = jnp.sum(jnp.where(probs >= mid, probs, 0.0), axis=-1,
                       keepdims=True)
        keep = mass >= top_p
        return jnp.where(keep, mid, lo), jnp.where(keep, hi, mid)

    lo = jnp.zeros((logits.shape[0], 1), jnp.float32)
    hi = jnp.full((logits.shape[0], 1), 1.1, jnp.float32)  # mass(>=1.1) == 0
    lo, _ = jax.lax.fori_loop(0, iters, bisect, (lo, hi))
    return probs, lo


def _sample(logits, rng, cfg: GenerationConfig):
    if cfg.decode_strategy == "greedy":
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(cfg.temperature, 1e-6)
    vocab = logits.shape[-1]
    # clamp: top_k >= vocab keeps the whole distribution (the previous
    # full-sort indexing crashed on [:, -top_k] out of range)
    top_k = min(cfg.top_k, vocab)
    if 0 < top_k < vocab:
        # one partial sort serves both filters: lax.top_k streams the vocab
        # once; the old path ran TWO full jnp.sort calls over [b, vocab]
        vals = jax.lax.top_k(logits, top_k)[0]  # descending [b, top_k]
        logits = jnp.where(logits < vals[:, -1:], -1e9, logits)
        if cfg.top_p < 1.0:
            # top-p inside the top-k survivors: the masked tail underflows
            # to exactly 0 probability, so softmax over `vals` equals the
            # full filtered softmax and the same partial sort is reused
            probs = jax.nn.softmax(vals, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            cutoff_idx = jnp.minimum(
                jnp.sum(cum < cfg.top_p, axis=-1, keepdims=True), top_k - 1
            )
            cutoff = jnp.take_along_axis(vals, cutoff_idx, axis=-1)
            logits = jnp.where(logits < cutoff, -1e9, logits)
    elif cfg.top_p < 1.0:
        # top_k off (or clamped to the whole vocab, a no-op filter): no
        # partial sort to piggyback on — bisect the probability threshold
        probs, thresh = _top_p_cutoff_bisect(logits, cfg.top_p)
        logits = jnp.where(probs >= thresh, logits, -1e9)
    return jax.random.categorical(rng, logits, axis=-1)


def generate(
    model,
    variables: Dict[str, Any],
    input_ids: jax.Array,
    gen_cfg: GenerationConfig,
    rng: Optional[jax.Array] = None,
    attention_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Returns [batch, prompt_len + max_length] tokens (padded after EOS).

    Prefill runs the full prompt once to populate the cache; the while_loop
    then decodes one token per iteration with static shapes throughout.
    ``attention_mask`` [b, prompt_len] marks real prompt tokens (0 = left
    pad): pad slots are never attended to, and position ids are shifted so
    each row's first real token sits at position 0.
    """
    if gen_cfg.decode_strategy == "beam_search":
        from fleetx_tpu.models.gpt.beam_search import beam_search

        out = beam_search(model, variables, jnp.asarray(input_ids), gen_cfg,
                          attention_mask=attention_mask)
        # flatten [b, num_return_sequences, L] to the reference's
        # expand_inputs_for_generation row layout [b*nret, L]
        return out.reshape(-1, out.shape[-1])
    if rng is None:
        rng = jax.random.PRNGKey(0)
    b, prompt_len = input_ids.shape
    total_len = prompt_len + gen_cfg.max_length
    max_pos = model.cfg.max_position_embeddings
    if total_len > max_pos:
        raise ValueError(
            f"prompt_len({prompt_len}) + max_length({gen_cfg.max_length}) "
            f"exceeds max_position_embeddings({max_pos})"
        )
    model, cache_len = right_size_decode_cache(model, total_len)

    params = variables["params"] if "params" in variables else variables
    if attention_mask is None:
        attention_mask = jnp.ones((b, prompt_len), jnp.int32)
    attention_mask = attention_mask.astype(jnp.int32)
    # per-row left-pad count; generated token at buffer slot i has position
    # i - pad_count (first REAL token of each row sits at position 0)
    pad_counts = prompt_len - attention_mask.sum(axis=1)
    # which kv-cache slots hold real tokens: prompt slots per the mask,
    # everything generated afterwards is real
    kv_valid = jnp.concatenate(
        [attention_mask.astype(bool),
         jnp.ones((b, cache_len - prompt_len), bool)], axis=1,
    )
    kv_mask = kv_valid[:, None, None, :]  # [b, 1, 1(q), cache_len(kv)]

    # static token buffer
    tokens = jnp.full((b, total_len), gen_cfg.pad_token_id, jnp.int32)
    tokens = jax.lax.dynamic_update_slice(tokens, input_ids.astype(jnp.int32), (0, 0))

    cache = init_decode_cache(model, b)

    # prefill: feed the whole prompt, cache fills positions [0, prompt_len)
    pos = jnp.clip(jnp.cumsum(attention_mask, axis=1) - 1, 0)
    logits, cache = decode_step(
        model, params, cache, input_ids.astype(jnp.int32), pos, kv_mask
    )
    vocab = logits.shape[-1]
    # repetition penalty reads a [b, vocab] seen-token scoreboard updated in
    # O(vocab) per step (mark_seen) instead of rebuilding a one-hot over the
    # whole [b, total_len] buffer every iteration; a 1-element dummy rides
    # the loop state when the penalty is off
    track_seen = gen_cfg.repetition_penalty != 1.0
    seen = (prompt_seen(input_ids.astype(jnp.int32), attention_mask, vocab)
            if track_seen else jnp.zeros((b, 1), jnp.bool_.dtype))
    rng, step_rng = jax.random.split(rng)
    next_logits = process_logits(
        logits[:, -1, :], seen if track_seen else None,
        jnp.asarray(prompt_len), gen_cfg, prompt_len=prompt_len,
        total_len=total_len,
    )
    next_tok = _sample(next_logits, step_rng, gen_cfg).astype(jnp.int32)
    tokens = jax.lax.dynamic_update_slice(tokens, next_tok[:, None], (0, prompt_len))
    if track_seen:
        seen = mark_seen(seen, next_tok)
    finished = next_tok == gen_cfg.eos_token_id

    def cond(state):
        i, _, _, _, finished, _ = state
        return (i < total_len) & ~jnp.all(finished)

    def body(state):
        i, tokens, seen, cache, finished, rng = state
        cur = jax.lax.dynamic_slice(tokens, (0, i - 1), (b, 1))
        logits, cache = decode_step(
            model, params, cache, cur,
            (i - 1 - pad_counts)[:, None].astype(jnp.int32), kv_mask,
        )
        rng, step_rng = jax.random.split(rng)
        nl = process_logits(logits[:, -1, :], seen if track_seen else None,
                            i, gen_cfg, prompt_len=prompt_len,
                            total_len=total_len)
        tok = _sample(nl, step_rng, gen_cfg).astype(jnp.int32)
        tok = jnp.where(finished, gen_cfg.pad_token_id, tok)
        tokens = jax.lax.dynamic_update_slice(tokens, tok[:, None], (0, i))
        if track_seen:
            seen = mark_seen(seen, tok)
        finished = finished | (tok == gen_cfg.eos_token_id)
        return i + 1, tokens, seen, cache, finished, rng

    _, tokens, _, _, _, _ = jax.lax.while_loop(
        cond, body,
        (jnp.asarray(prompt_len + 1), tokens, seen, cache, finished, rng),
    )
    return tokens
