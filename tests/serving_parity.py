"""Shared greedy-parity harness for the serving suites.

One comparison contract (``test_serving.py``,
``test_paged_serving(_slow).py``, ``test_serving_recovery.py``,
``test_quantized_serving.py`` and the suites after them), and one way to build
an engine a second time without compiling it a second time:

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

- :func:`plain_greedy` — the reference of the model-family suites: the
  plain forward's own greedy continuation, one program a padded width.
- :func:`traced_apply` — a full forward as one program, traced at the call.
- :func:`computed_once` — a file's reference, once for each weights and
  tokens it is asked for, as one program.
- :func:`sharing_programs` — wraps a file's engine builder so that engines
  built with EQUAL arguments run ONE set of jitted programs.

``QUANT_ATOL`` is the repo-wide budget quantized parity tests assert
against — the same number docs/QUANTIZATION.md documents. Tighten it
only with hardware evidence; loosening it needs a quality argument.
"""

import dataclasses
import functools
import math
import os

import numpy as np

import jax
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


@functools.partial(jax.jit, static_argnums=0)
def _best_next(model, variables, toks, last):
    return jnp.argmax(model.apply(variables, toks)[0, last])


def plain_greedy(model, variables, prompt, n, *, bucket=32):
    """``n`` greedy tokens of the plain forward (no cache) after ``prompt``.
    The row is padded on the right to a multiple of ``bucket``, which no
    earlier position sees (every operator here is causal), so the whole
    continuation runs ONE compiled program and not a fresh shape, eagerly,
    a token."""
    end = len(prompt) + n
    toks = np.zeros((1, -(-end // bucket) * bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    for i in range(len(prompt), end):
        toks[0, i] = _best_next(model, variables, toks, i - 1)
    return toks[0, len(prompt):end].tolist()


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


def traced_apply(model, variables, *args, **kw):
    """``model.apply(variables, *args, **kw)`` as ONE program, traced anew at
    this call (a fault a test planted is in the trace): eagerly every
    primitive of every layer is a dispatch, and at its first shape a compile,
    of its own."""
    return jax.jit(lambda v, *a: model.apply(v, *a, **kw))(variables, *args)


def computed_once(reference):
    """``reference(params, tokens, **kw)``, wrapped: computed once for each
    weights (by identity), tokens and keywords, however many tests of a file
    hold the same run to it, and as ONE program (the keywords fixed in the
    trace): the float32 references are plain ``jax.numpy``, and eagerly
    every length asked for compiles each of its primitives anew."""
    seen = {}

    @functools.wraps(reference)
    def once(params, tokens, **kw):
        tokens = np.asarray(tokens)
        key = (id(params), tokens.shape, tokens.tobytes(),
               repr(sorted(kw.items())))
        if key not in seen:   # (``params`` held: its ``id`` stays its own)
            seen[key] = params, jax.jit(functools.partial(reference, **kw))(
                params, tokens)
        return seen[key][1]

    return once


# keywords no program reads: the watchdog's bound is the host's, read at
# every tick (``ServingEngine._run_device``), so the synchronous engine runs
# the overlapped one's programs
_HOST_ONLY = ("tick_timeout_s",)


def sharing_programs(build):
    """``build(*args, **kw)``, wrapped: what it builds with EQUAL arguments
    (a model by its fields, an engine of this wrapper by the arguments IT was
    built with, weights by identity, keywords by their ``repr`` less
    ``_HOST_ONLY``, under the same ``FLEETX_*`` environment) runs the jitted
    programs of ONE more built with them that no test is handed: every
    attribute that is a jitted function, and the engine's ``_prefill_jits``.
    So a file traces and compiles each DISTINCT construction once however
    many clean engines (or ``Served`` checks of them) its tests take. Each
    keeps its own cache, pool, lanes, metrics and host state, and dies with
    its test as it always did; the programs read only what the constructor's
    arguments fixed. (The one that owns them lives as long as they do, so it
    serves nothing and answers no health probe.) A test whose construction
    is what it tests, or that patches what a trace reads (a module's
    constant, a kernel's entry), calls the builder bare:
    ``build.__wrapped__``."""
    owners = {}

    def by_value(x):
        x = getattr(x, "built_alike", x)
        try:
            hash(x)
        except TypeError:   # a list of settings by what it says; arrays by
            # identity
            return repr(x) if all(
                isinstance(leaf, (bool, int, float, str))
                for leaf in jax.tree.leaves(x)) else id(x)
        return x

    @functools.wraps(build)
    def alike(*args, **kw):
        key = (tuple(map(by_value, args)),
               repr(sorted((k, by_value(v)) for k, v in kw.items()
                           if k not in _HOST_ONLY)),
               tuple(sorted(kv for kv in os.environ.items()
                            if kv[0].startswith("FLEETX_"))))
        if key not in owners:
            # (a ``Served`` of the engine that owns the engine's programs;
            # ``args`` held: an ``id`` in the key stays that object's)
            owner = build(*(getattr(a, "programs_of", a) for a in args), **kw)
            if hasattr(owner, "_health_name"):
                from fleetx_tpu.obs import http

                http.unregister_health(owner._health_name)
            owners[key] = args, owner, {
                name: value for name, value in vars(owner).items()
                if name == "_prefill_jits" or (
                    callable(value) and hasattr(value, "lower"))}
            if hasattr(owner, "_make_paged_prefill"):
                # a bucket's program is made at its first call: by the
                # owner, or the engine that made it would live as long
                owners[key][2]["_make_paged_prefill"] = \
                    owner._make_paged_prefill
        _, owner, programs = owners[key]
        made = build(*args, **kw)
        vars(made).update(programs, built_alike=key, programs_of=owner)
        return made

    return alike
