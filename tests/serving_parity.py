"""Shared greedy-parity harness for the serving suites.

One comparison contract, four consumers (``test_serving.py``,
``test_paged_serving(_slow).py``, ``test_serving_recovery.py``,
``test_quantized_serving.py``):

- :func:`one_shot_tokens` — the per-request reference: a one-shot
  ``generate()`` call trimmed at EOS, the stream every serving mode must
  reproduce.
- :func:`assert_token_parity` — the gate. ``atol=0`` (the default, and
  the contract for every bf16 config) is byte parity:
  ``np.testing.assert_array_equal``. ``atol>0`` is the QUANTIZED
  tolerance contract (docs/QUANTIZATION.md): greedy decode is chaotic
  after a first argmax flip — one near-tie resolved differently rewrites
  every later token — so elementwise closeness of token IDs is
  meaningless and the meaningful measure is the longest common PREFIX.
  ``atol`` is the tolerated diverging-tail fraction: the streams must
  agree on at least ``ceil((1 - atol) * len(want))`` leading tokens
  (and on their lengths), e.g. ``atol=0.25`` demands the first 75%.

``QUANT_ATOL`` is the repo-wide budget quantized parity tests assert
against — the same number docs/QUANTIZATION.md documents. Tighten it
only with hardware evidence; loosening it needs a quality argument.
"""

import dataclasses
import math

import numpy as np

import jax.numpy as jnp

# Documented tolerance budget for int8 kv/weight serving configs
# (docs/QUANTIZATION.md "Tolerance contract"): greedy token streams must
# match the bf16 one-shot reference on at least the first 75% of tokens.
# In practice the tiny test models match 100% — the budget absorbs
# near-tie argmax flips, not systematic drift (that is what the
# tools/eval.py perplexity gate measures). ONE number and ONE prefix
# measure, owned by ops/quant.py and shared by every quantized
# serving test.
from fleetx_tpu.ops.quant import QUANT_PREFIX_BUDGET as QUANT_ATOL
from fleetx_tpu.ops.quant import common_prefix_len  # noqa: F401  (re-export)


def one_shot_tokens(model, params, prompt, max_length, *, gen_cfg,
                    eos=None):
    """Reference stream: per-request one-shot ``generate()``, trimmed at
    EOS. ``gen_cfg`` supplies the suite's decode defaults (each test
    module passes its own GREEDY config); ``eos`` overrides its
    ``eos_token_id``."""
    from fleetx_tpu.models.gpt.generation import generate

    prompt = np.asarray(prompt)
    eos = gen_cfg.eos_token_id if eos is None else eos
    cfg = dataclasses.replace(gen_cfg, max_length=max_length,
                              eos_token_id=eos)
    out = np.asarray(generate(model, params, jnp.asarray(prompt[None]),
                              cfg))[0]
    gen = out[len(prompt):]
    if eos in gen.tolist():
        gen = gen[:gen.tolist().index(eos) + 1]
    return gen


def assert_token_parity(got, want, *, atol: float = 0.0, err_msg: str = ""):
    """Assert serving tokens match the reference under the parity
    contract (module docstring): byte-identical at ``atol=0``, longest-
    common-prefix >= ``(1 - atol) * len(want)`` (and equal lengths)
    otherwise."""
    got, want = np.asarray(got), np.asarray(want)
    if atol == 0.0:
        np.testing.assert_array_equal(got, want, err_msg=err_msg)
        return
    assert len(got) == len(want), (
        f"{err_msg}: stream length {len(got)} != reference {len(want)} "
        f"(tolerance covers diverging tails, not missing tokens)")
    need = math.ceil((1.0 - atol) * len(want))
    lcp = common_prefix_len(got, want)
    assert lcp >= need, (
        f"{err_msg}: token streams share only {lcp}/{len(want)} leading "
        f"tokens; the atol={atol} contract requires >= {need} "
        f"(got={got.tolist()}, want={want.tolist()})")
