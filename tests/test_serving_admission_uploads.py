"""An admission's operands cross to the device packed (PERF.md, PR 32).

- **Uploads**: on every path that admits (a one-call admission, a chunked
  one with its intermediate chunks, ``recover()``'s replay, a shipped
  admission) ``serving.prefill_args`` and the lane-install
  ``serving.install`` span count their host-to-device ``transfers`` and
  start no device program of their own: after ``jax.clear_caches()`` every
  program a block runs compiles, jax logs each compile, and the log is
  booked to the span open at that moment.
- **Exactness**: the packed form sends the values, the dtypes and the key
  stream that one ``jnp.asarray(x, dtype)`` an operand and an eager
  ``jax.random.split`` sent: the lane state after an install, the in-program
  split, and a sampled and a greedy request's tokens through all four
  paths against the stream written down eagerly.
"""

import contextlib
import functools
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import sharing_programs

from fleetx_tpu.models.gpt.generation import GenerationConfig
from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
from fleetx_tpu.obs import get_recorder
from fleetx_tpu.serving import ServingEngine
from fleetx_tpu.serving.engine import sample_tokens

PROMPT = np.asarray([5, 17, 3, 42, 8, 23, 11, 2, 30, 9], np.int32)
WARM_UP = PROMPT[::-1].copy()       # shares no prefix page with PROMPT
NEW = 6
# a request whose every scalar differs from a free lane's and a replay's
SAMPLED = dict(decode_strategy="sampling", temperature=0.7, top_k=5,
               top_p=0.85, min_length=2, eos_token_id=17, seed=1234)
GREEDY = dict(decode_strategy="greedy", eos_token_id=10**6)


@pytest.fixture(scope="module")
def tiny():
    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=2, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=64,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return model, params


@sharing_programs
def _engine(tiny, **kwargs):
    model, params = tiny
    kwargs.setdefault("prefill_bucket", 4)
    return ServingEngine(
        model, params, slots=4, cache_len=32, page_size=8,
        gen_cfg=GenerationConfig(decode_strategy="greedy",
                                 eos_token_id=10**6, pad_token_id=60),
        **kwargs)


def _submit(eng, request, prompt=PROMPT, **kwargs):
    return eng.submit(prompt, max_length=NEW, **request, **kwargs)


def _ship(pre, dec, request, prompt=PROMPT):
    """``request`` prefilled on ``pre``, its pages shipped to ``dec``."""
    rid = _submit(pre, request, prompt)
    pre.step()
    assert pre.prefilled_ready() == [rid]
    blobs = pre.export_kv(rid)
    return _submit(dec, request, prompt, kv_payloads=blobs,
                   history=list(pre.take_result(rid).tokens))


# ------------------------------------------------------------------ uploads

@contextlib.contextmanager
def programs_by_span():
    """``{span name: [program, ...]}`` of the device programs the block
    starts, each booked to the innermost span open when it compiled: where
    the block FIRST runs it (the spans' ``transfers`` say the rest)."""
    seen = {}
    stack = get_recorder()._stack()

    class Book(logging.Handler):
        def emit(self, record):
            found = re.match(r"Compiling (?:jit\()?(\w+)", record.getMessage())
            if found:
                seen.setdefault(stack[-1] if stack else None, []).append(
                    found.group(1))

    log = logging.getLogger("jax._src.interpreters.pxla")
    book = Book()
    log.addHandler(book)
    jax.clear_caches()
    try:
        with jax.log_compiles():
            yield seen
    finally:
        log.removeHandler(book)


def _admission(tiny, **kwargs):
    eng = _engine(tiny, **kwargs)
    _submit(eng, GREEDY, WARM_UP)
    eng.step()                      # an admission and a tick
    _submit(eng, SAMPLED)
    return eng.step, dict(prefill_args=[2], install=[1])


def _chunks(tiny, **kwargs):
    # (a chunk is one bucket)
    eng = _engine(tiny, prefill_chunk=kwargs.get("prefill_bucket", 4),
                  **kwargs)
    _submit(eng, GREEDY, WARM_UP)
    eng.drain()
    _submit(eng, SAMPLED)

    def run():                      # chunks of 4, 4 and the final 2
        for _ in range(3):
            eng.step()
    run.__self__ = eng              # as a bound ``eng.step`` has it
    return run, dict(prefill_args=[1, 1, 2], install=[1])


def _replay(tiny, **kwargs):
    eng = _engine(tiny, **kwargs)
    _submit(eng, SAMPLED)
    eng.step()
    eng.step()
    return eng.recover, dict(prefill_args=[1], install=[2])


def _shipped(tiny):
    pre, dec = _engine(tiny, role="prefill"), _engine(tiny, role="decode")
    _ship(pre, dec, GREEDY, WARM_UP)
    dec.step()
    _ship(pre, dec, SAMPLED)
    return dec.step, dict(prefill_args=[], install=[2])


@pytest.mark.parametrize("path", [_admission, _chunks, _replay, _shipped],
                         ids=["admission", "chunks", "replay", "shipped"])
def test_admission_uploads_are_counted_and_start_no_program(tiny, path):
    run, transfers = path(tiny)
    rec = get_recorder()
    rec.clear()
    with programs_by_span() as programs:
        run()
    # between the uploads there is no program but the install itself (the
    # prefix registration is a `serving.install` too: host work alone) ...
    assert programs.get("serving.prefill_args", []) == []
    assert programs["serving.install"] == ["_admit_fn"]
    spans = rec.spans()
    args = [s.attrs["transfers"] for s in spans
            if s.name == "serving.prefill_args"]
    installs = [s.attrs["transfers"] for s in spans
                if s.name == "serving.install" and "transfers" in s.attrs]
    assert (args, installs) == (transfers["prefill_args"],
                                transfers["install"])
    assert all(n <= 4 for n in args) and all(n <= 2 for n in installs)
    # ... and the detector did see the programs the path runs, where it
    # runs them
    if args:
        assert set(programs["serving.prefill"]) == {"prefill"}


# ------------------------------------------------- the unit of the cache write

@pytest.mark.parametrize("path,bucket,pages", [
    (_admission, 8, [2]),           # 10 tokens in a bucket of 16: two pages
    (_chunks, 8, [1, 1]),           # a chunk of 8 and the final 2 in 8
    (_replay, 8, [2]),              # the history, 11-16 tokens, in 16
    (_admission, 4, [0]),           # a bucket of 12 is no whole number of
    (_chunks, 4, [0, 0, 0]),        # pages of 8: a row at a time
    (_replay, 4, [0]),
], ids=["admission", "chunks", "replay", "admission_rows", "chunks_rows",
        "replay_rows"])
def test_prefill_span_and_counters_say_the_unit_of_the_write(
        tiny, path, bucket, pages):
    """``serving.prefill``'s ``page_writes`` is the call's bucket over the
    page size where that is whole (the model's own predicate on the
    program's shape, ``paged_write.page_writes``), else 0, and the metrics
    count the prefill programs each way."""
    run, _ = path(tiny, prefill_bucket=bucket)
    eng = run.__self__
    before = eng.metrics.snapshot()
    rec = get_recorder()
    rec.clear()
    run()
    prefills = [s.attrs for s in rec.spans() if s.name == "serving.prefill"]
    assert [a["page_writes"] for a in prefills] == pages
    assert all(a["page_writes"] in (0, a["bucket"] // 8) for a in prefills)
    after = eng.metrics.snapshot()
    by_page = sum(1 for n in pages if n)
    assert (after["prefill_page_writes"] - before["prefill_page_writes"],
            after["prefill_row_writes"] - before["prefill_row_writes"]) == (
        by_page, len(pages) - by_page)


# ---------------------------------------------------------------- exactness

def test_in_program_split_is_the_eager_split():
    """The admission's split moved into the prefill program: same bits,
    for both ways the engine makes a request's key."""
    for key in (jax.random.PRNGKey(1234),
                jax.random.fold_in(jax.random.PRNGKey(0), 7)):
        assert key.dtype == jnp.uint32 and key.shape == (2,)
        np.testing.assert_array_equal(
            np.asarray(jax.jit(jax.random.split)(key)),
            np.asarray(jax.random.split(key)))


@pytest.mark.parametrize("request_", [SAMPLED, GREEDY],
                         ids=["sampled", "greedy"])
def test_lane_state_after_install_is_what_twelve_scalar_writes_gave(
        tiny, request_):
    eng = _engine(tiny)
    _submit(eng, GREEDY, WARM_UP)
    eng.step()                      # lane 0 busy: the install goes to lane 1
    installed = []
    admit = eng._admit_jit
    eng._admit_jit = lambda *a: installed.append(admit(*a)) or installed[-1]
    before = eng._state
    rid = _submit(eng, request_)
    req = eng.scheduler.peek()
    eng.step()
    (got,) = installed
    i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    f32 = lambda v: jnp.asarray(v, jnp.float32)  # noqa: E731
    writes = {
        "last_tok": i32(req.tokens[0]), "lengths": i32(len(PROMPT)),
        "decoded": i32(1), "active": jnp.asarray(True),
        "eos": i32(req.eos_token_id), "max_new": i32(req.max_new_tokens),
        "min_new": i32(req.min_new_tokens), "greedy": jnp.asarray(req.greedy),
        "temperature": f32(req.temperature), "top_k": i32(req.top_k),
        "top_p": f32(req.top_p), "rng": jax.random.split(req.rng_key)[1],
    }
    assert req.id == rid and req.slot == 1 and set(got) == set(writes)
    for name, value in writes.items():
        want = before[name].at[req.slot].set(value)
        assert got[name].dtype == want.dtype, name
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want), err_msg=name)


@pytest.fixture(scope="module")
def plain_stream(tiny):
    """``plain_stream(greedy, topk_cap)``: the tokens of ``GREEDY`` or of
    ``SAMPLED`` as the engine has always drawn them, written down eagerly: a
    full forward over the sequence so far, every sampler operand one
    ``jnp.asarray(x, dtype)``, one eager split of the request's key an
    emitted token (greedy consumes none), EOS suppressed under the minimum
    length. Once a request: every path is held to the same stream."""
    return functools.cache(functools.partial(_plain_stream, tiny))


def _plain_stream(tiny, greedy, topk_cap):
    request = GREEDY if greedy else SAMPLED
    model, params = tiny
    ids, out = list(PROMPT), []
    carry = jax.random.PRNGKey(request.get("seed", 0))
    eos = request["eos_token_id"]
    for n in range(NEW):
        last = model.apply(params, jnp.asarray([ids]))[0, -1:].astype(
            jnp.float32)
        if n < request.get("min_length", 0):
            last = jnp.where(jnp.arange(last.shape[-1])[None] == eos, -1e9,
                             last)
        step_key, carry = jax.random.split(carry)
        tok = int(sample_tokens(
            last, step_key[None],
            jnp.asarray(request["decode_strategy"] == "greedy")[None],
            jnp.asarray(request.get("temperature", 1.0), jnp.float32)[None],
            jnp.asarray(request.get("top_k", 0), jnp.int32)[None],
            jnp.asarray(request.get("top_p", 1.0), jnp.float32)[None],
            topk_cap=topk_cap)[0])
        out.append(tok)
        ids.append(tok)
        if tok == eos:
            break
    return out


def _through_admission(tiny, request, **kwargs):
    eng = _engine(tiny, **kwargs)
    rid = _submit(eng, request)
    return eng, eng.drain()[rid].tokens


def _through_chunks(tiny, request):
    return _through_admission(tiny, request, prefill_chunk=4)


def _through_replay(tiny, request):
    eng = _engine(tiny)
    rid = _submit(eng, request)
    for _ in range(3):
        eng.step()
    eng.recover()
    return eng, eng.drain()[rid].tokens


def _through_shipping(tiny, request):
    pre, dec = _engine(tiny, role="prefill"), _engine(tiny, role="decode")
    rid = _ship(pre, dec, request)
    return dec, dec.drain()[rid].tokens


@pytest.mark.parametrize("request_", [SAMPLED, GREEDY],
                         ids=["sampled", "greedy"])
@pytest.mark.parametrize(
    "through", [_through_admission, _through_chunks, _through_replay,
                _through_shipping],
    ids=["admission", "chunks", "replay", "shipped"])
def test_tokens_are_the_plain_streams(tiny, plain_stream, through, request_):
    eng, tokens = through(tiny, request_)
    assert list(tokens) == plain_stream(request_ is GREEDY, eng.topk_cap)
    assert len(tokens) == NEW       # EOS under the minimum length, then none
