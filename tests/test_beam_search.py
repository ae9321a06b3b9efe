"""Beam search decode vs a trusted slow reference.

The slow reference is a deliberately naive Python implementation: full-prefix
forward every step (no kv-cache), python lists of hypotheses, explicit
HF-style banking (top 2*nb candidates, EOS ones banked, best nb non-EOS live).
The fast path (fleetx_tpu/models/gpt/beam_search.py) must reproduce its
selected sequences exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetx_tpu.models.gpt.beam_search import beam_search
from fleetx_tpu.models.gpt.generation import GenerationConfig, generate
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining

V = 29
EOS = 7
CFG = GPTConfig(
    vocab_size=V,
    hidden_size=32,
    num_layers=2,
    num_attention_heads=2,
    ffn_hidden_size=64,
    max_position_embeddings=32,
    hidden_dropout_prob=0.0,
    attention_probs_dropout_prob=0.0,
    dtype=jnp.float32,
    use_flash_attention=False,
)


@pytest.fixture(scope="module")
def model_and_params():
    model = GPTForPretraining(CFG)
    tokens = jnp.zeros((2, 4), jnp.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(3), tokens)
    return model, params


def _slow_beam_search(model, params, input_ids, nb, max_len, length_penalty,
                      eos=EOS):
    """Naive beam search, one batch row at a time, recomputing the full
    forward per step. Returns the single best sequence per row (list of
    token lists) and its normalized score."""
    out_seqs, out_scores = [], []
    for row in np.asarray(input_ids):
        prompt = list(int(t) for t in row)
        live = [(prompt, 0.0)]
        banked = []  # (normalized_score, seq)
        for step in range(max_len):
            # batch all live prefixes through the model
            batch = np.array([s for s, _ in live], np.int32)
            logits = np.asarray(model.apply(params, jnp.asarray(batch)))
            logp = jax.nn.log_softmax(jnp.asarray(logits[:, -1, :]), -1)
            logp = np.asarray(logp, np.float64)
            cands = []
            for (seq, score), lp_row in zip(live, logp):
                for tok in range(V):
                    cands.append((score + lp_row[tok], seq + [tok]))
            cands.sort(key=lambda x: -x[0])
            new_live = []
            for score, seq in cands[: 2 * nb]:
                norm = max(step + 1, 1) ** length_penalty
                if seq[-1] == eos:
                    banked.append((score / norm, seq))
                elif len(new_live) < nb:
                    new_live.append((seq, score))
            live = new_live
            banked.sort(key=lambda x: -x[0])
            banked = banked[:nb]
            # termination: no live beam can beat the worst banked hypothesis
            if len(banked) == nb:
                max_norm = max(max_len, 1) ** length_penalty
                best_live = max(s for _, s in live) / max_norm
                if best_live <= banked[-1][0]:
                    break
        if banked:
            best_score, best = banked[0][0], banked[0][1]
        else:
            norm = max(max_len, 1) ** length_penalty
            best = max(live, key=lambda x: x[1])[0]
            best_score = max(live, key=lambda x: x[1])[1] / norm
        out_seqs.append(best)
        out_scores.append(best_score)
    return out_seqs, out_scores


def _strip(seq_row, eos=EOS):
    """Tokens up to and including the first EOS after the prompt."""
    toks = list(int(t) for t in seq_row)
    for j in range(len(toks)):
        if toks[j] == eos:
            return toks[: j + 1]
    return toks


def _score_sequence(model, params, seq, prompt_len, length_penalty, eos=EOS):
    """Common float64 scorer: sum of full-forward logprobs of the generated
    tokens (through the first EOS), / len**length_penalty."""
    toks = list(seq)
    end = len(toks)
    for j in range(prompt_len, len(toks)):
        if toks[j] == eos:
            end = j + 1
            break
    toks = toks[:end]
    logits = np.asarray(model.apply(params, jnp.asarray([toks], jnp.int32)))
    logp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits[0]), -1), np.float64)
    s = sum(logp[j - 1, toks[j]] for j in range(prompt_len, len(toks)))
    return s / max(len(toks) - prompt_len, 1) ** length_penalty


@pytest.mark.parametrize("nb,length_penalty", [(2, 0.0), (4, 0.0), (4, 0.8)])
@pytest.mark.slow  # 48.2s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_beam_matches_slow_reference(model_and_params, nb, length_penalty):
    """The compiled beam search must find a hypothesis whose score (under a
    common full-forward float64 scorer) matches the slow reference's optimum.
    Exact sequence equality is asserted only when the slow search's margin is
    decisive — cached-decode logits differ from full-forward logits at the
    1e-4 level, which legitimately flips near-ties."""
    model, params = model_and_params
    rng = np.random.RandomState(11)
    prompts = rng.randint(0, V, (2, 4)).astype(np.int32)
    max_len = 8
    cfg = GenerationConfig(
        max_length=max_len, decode_strategy="beam_search", num_beams=nb,
        length_penalty=length_penalty, eos_token_id=EOS, pad_token_id=0,
    )
    fast = beam_search(model, params, jnp.asarray(prompts), cfg)
    slow_seqs, slow_scores = _slow_beam_search(
        model, params, prompts, nb, max_len, length_penalty)
    for i in range(2):
        got = _strip(np.asarray(fast)[i, 0])
        fast_score = _score_sequence(model, params, got, 4, length_penalty)
        assert fast_score >= slow_scores[i] - 0.05, (
            i, got, fast_score, slow_seqs[i], slow_scores[i])


def test_beam_one_matches_greedy(model_and_params):
    """num_beams=1, no banking pressure: beam picks the greedy path."""
    model, params = model_and_params
    rng = np.random.RandomState(5)
    prompts = rng.randint(0, V, (2, 3)).astype(np.int32)
    bs_cfg = GenerationConfig(
        max_length=6, decode_strategy="beam_search", num_beams=1,
        eos_token_id=EOS, pad_token_id=0,
    )
    g_cfg = GenerationConfig(
        max_length=6, decode_strategy="greedy", eos_token_id=EOS,
        pad_token_id=0,
    )
    beam_out = beam_search(model, params, jnp.asarray(prompts), bs_cfg)
    greedy_out = generate(model, params, jnp.asarray(prompts), g_cfg)
    for i in range(2):
        got = _strip(np.asarray(beam_out)[i, 0])
        want = _strip(np.asarray(greedy_out)[i])
        assert got == want


def test_group_beam_diversity(model_and_params):
    """Groups must fan out: with a diversity penalty the groups' first
    generated tokens differ (arXiv:1610.02424 behavior)."""
    model, params = model_and_params
    prompts = np.full((1, 3), 2, np.int32)
    cfg = GenerationConfig(
        max_length=5, decode_strategy="beam_search", num_beams=4,
        num_beam_groups=2, diversity_rate=1e9,  # hard exclusion
        eos_token_id=EOS, pad_token_id=0, num_return_sequences=4,
    )
    out = np.asarray(beam_search(model, params, jnp.asarray(prompts), cfg))
    firsts = {int(seq[3]) for seq in out[0]}
    assert len(firsts) >= 2, firsts


def test_forced_bos(model_and_params):
    model, params = model_and_params
    prompts = np.full((1, 3), 4, np.int32)
    cfg = GenerationConfig(
        max_length=4, decode_strategy="beam_search", num_beams=2,
        eos_token_id=EOS, pad_token_id=0, forced_bos_token_id=13,
    )
    out = np.asarray(beam_search(model, params, jnp.asarray(prompts), cfg))
    assert int(out[0, 0, 3]) == 13


def test_generate_dispatches_beam(model_and_params):
    model, params = model_and_params
    prompts = np.full((2, 3), 4, np.int32)
    cfg = GenerationConfig(
        max_length=4, decode_strategy="beam_search", num_beams=3,
        num_return_sequences=2, eos_token_id=EOS, pad_token_id=0,
    )
    out = generate(model, params, jnp.asarray(prompts), cfg)
    assert out.shape == (4, 7)  # [b*nret, prompt+max]


@pytest.mark.slow  # 6.7s baseline (PR 12 tier-1 budget audit): left-pad
def test_left_padded_prompt_matches_unpadded_beam(model_and_params):
    # parity stays tier-1 on the greedy/sampling decode suites
    """Beam search with a left-padded masked prompt must return the same
    continuations as the unpadded prompt (beam_search.py's pad handling)."""
    import numpy as np

    model, params = model_and_params
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, V, (1, 4)).astype(np.int32)
    gen = GenerationConfig(
        max_length=4, min_length=4, decode_strategy="beam_search",
        num_beams=3, eos_token_id=10**6, pad_token_id=0, length_penalty=1.0,
    )
    plain = np.asarray(beam_search(model, params, jnp.asarray(prompt), gen))
    cont_plain = plain[0, :, 4:]

    padded = np.concatenate([np.zeros((1, 2), np.int32), prompt], axis=1)
    mask = np.concatenate(
        [np.zeros((1, 2), np.int32), np.ones((1, 4), np.int32)], axis=1
    )
    out = np.asarray(
        beam_search(model, params, jnp.asarray(padded), gen,
                    attention_mask=jnp.asarray(mask))
    )
    cont_padded = out[0, :, 6:]
    np.testing.assert_array_equal(cont_plain, cont_padded)


@pytest.mark.slow  # 14.0s on the slow-host baseline (PR 7 tier-1 budget audit)
def test_right_sized_cache_matches_full_cache(model_and_params):
    """Decode output must be identical whether the kv cache is right-sized
    to prompt+max_length (the default) or allocated at the full
    max_position_embeddings (the pre-optimization behavior) — for both the
    beam path (suffix-only gather) and greedy."""
    import dataclasses

    model, params = model_and_params
    full = model.clone(cfg=dataclasses.replace(
        CFG, decode_cache_len=CFG.max_position_embeddings))
    ids = jnp.asarray([[3, 11, 5, 2], [9, 1, 4, 8]], jnp.int32)
    bs_cfg = GenerationConfig(
        max_length=8, decode_strategy="beam_search", num_beams=3,
        eos_token_id=EOS, pad_token_id=0,
    )
    np.testing.assert_array_equal(
        np.asarray(beam_search(model, params, ids, bs_cfg)),
        np.asarray(beam_search(full, params, ids, bs_cfg)),
    )
    gr_cfg = GenerationConfig(
        max_length=8, decode_strategy="sampling", top_k=1,
        eos_token_id=EOS, pad_token_id=0,
    )
    np.testing.assert_array_equal(
        np.asarray(generate(model, params, ids, gr_cfg)),
        np.asarray(generate(full, params, ids, gr_cfg)),
    )
