"""Leaf spans inside ``ServingEngine.step()`` and ``Trainer.fit()``, and
the model-part scopes on the compiled programs (docs/OBSERVABILITY.md,
"Spans" and "Device-trace scopes"): every host phase and every blocking
fetch lies under a span that names one activity, nests under the stated
parent and carries the stated attrs; scopes are metadata only."""

import contextlib
import re
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serving_parity import sharing_programs

from fleetx_tpu.obs import SpanRecorder, get_recorder, span


def test_span_records_its_parent_and_hands_out_its_attrs():
    rec = SpanRecorder(capacity=8)
    with span("serving.tick", recorder=rec, tick=1):
        with span("serving.claim", recorder=rec, request=7, shared=0) as at:
            at["shared"] = 32  # known only inside the span
    claim, tick = rec.spans()
    assert (claim.name, claim.parent, claim.attrs) == (
        "serving.claim", "serving.tick", {"request": 7, "shared": 32})
    assert (tick.name, tick.parent) == ("serving.tick", None)
    event = next(e for e in rec.chrome_trace()["traceEvents"]
                 if e.get("name") == "serving.claim")
    assert event["args"] == {"parent": "serving.tick", "request": 7,
                             "shared": 32}


def test_default_ring_and_truncated_export_says_so(monkeypatch):
    monkeypatch.delenv("FLEETX_OBS_SPANS", raising=False)
    assert SpanRecorder().capacity == 65536
    rec = SpanRecorder(capacity=2)
    for _ in range(5):
        with span("serving.tick", recorder=rec):
            pass
    assert rec.chrome_trace()["dropped"] == 3


# ---------------------------------------------------------------- serving

LANES = 16


@sharing_programs
def _engine(**kwargs):
    from fleetx_tpu.models.gpt.generation import GenerationConfig
    from fleetx_tpu.models.gpt.model import GPTConfig, GPTForPretraining
    from fleetx_tpu.serving import ServingEngine

    cfg = GPTConfig(
        vocab_size=61, hidden_size=32, num_layers=2, num_attention_heads=2,
        ffn_hidden_size=64, max_position_embeddings=32,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        dtype=jnp.float32, use_flash_attention=False)
    model = GPTForPretraining(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))
    return ServingEngine(
        model, params, slots=LANES, cache_len=16, prefill_bucket=4,
        page_size=8,
        gen_cfg=GenerationConfig(decode_strategy="greedy",
                                 eos_token_id=10**6, pad_token_id=60,
                                 max_length=6), **kwargs)


class _SlowFetch:
    """A device array whose transfer to the host takes ``delay_s``."""

    def __init__(self, array, delay_s):
        self.array, self.delay_s = array, delay_s

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.delay_s)
        return np.asarray(self.array)


@pytest.fixture(scope="module")
def ticked():
    """One engine: sixteen admissions, then a warm decode-only tick that
    reads a tick whose token fetch takes 20 ms (the engine keeps one tick
    in flight: a step() dispatches its own tick, then reads the one
    dispatched a step earlier). Returns ``(admission tick's spans,
    decode-only tick's spans)`` from the process recorder."""
    eng = _engine()
    eng._probed_at = float("inf")   # no admission sampled to be read at once
    rng = np.random.default_rng(0)
    for _ in range(LANES):
        eng.submit(rng.integers(1, 60, 5, dtype=np.int32), max_length=6)
    rec = get_recorder()
    rec.clear()
    eng.step()            # admits all sixteen, then decodes once
    admission = rec.spans()
    eng.step()            # warm: every program is compiled
    decode = eng._decode_jit

    def slow(*args):
        cache, st, tok, done = decode(*args)
        return cache, st, _SlowFetch(tok, 0.02), done

    eng._decode_jit = slow
    eng.step()            # dispatches the tick whose tokens come slowly
    rec.clear()
    eng.step()            # dispatches the next one, then reads that one
    return admission, rec.spans()


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_admission_spans_nest_under_admit_with_their_attrs(ticked):
    admission, _ = ticked
    admits = _named(admission, "serving.admit")
    assert len(admits) == LANES
    request = admits[0].attrs["request"]
    mine = [s for s in admission if s.attrs.get("request") == request]
    # the dry run of the prefix match before the admission is the tick's
    (check,) = [s for s in mine if s.name == "serving.can_admit"]
    assert check.parent == "serving.tick"
    mine.remove(check)
    by_name = {}
    for s in mine:
        by_name.setdefault(s.name, []).append(s)
    assert set(by_name) == {"serving.admit", "serving.claim",
                            "serving.prefill_args", "serving.prefill",
                            "serving.first_token", "serving.install"}
    admit = by_name["serving.admit"][0]
    # the wait for the first token is no longer the admission's: the token
    # stays in flight, and the tick reads it after a later dispatch
    (first_token,) = by_name.pop("serving.first_token")
    assert first_token.parent == "serving.tick"
    for name, spans in by_name.items():
        if name != "serving.admit":
            assert all(s.parent == "serving.admit" for s in spans), name
            assert all(admit.start_s <= s.start_s and s.end_s <= admit.end_s
                       for s in spans), name
    assert by_name["serving.claim"][0].attrs == {"request": request,
                                                 "shared": 0}
    # the uploads before the dispatch: the int32 vector that ends in the
    # padded prompt, and the float32 pair, each one plain copy of a
    # host-built array
    assert by_name["serving.prefill_args"][0].attrs == {
        "request": request, "bucket": 8, "transfers": 2}
    # the prefix registration (during the prefill) and the lane install,
    # which sends its int32 vector and takes the prefill's float32 pair
    register, install = by_name["serving.install"]
    assert register.attrs == {"request": request}
    prefill = by_name["serving.prefill"][0].attrs["program"]
    assert install.attrs == {"request": request, "transfers": 1,
                             "program": prefill + 1}
    # the install is dispatched right behind the prefill, with the token
    # still on the device; the read that names the prefill's program comes
    # after the next admission's prefill was dispatched
    install_span = by_name["serving.install"][-1]
    assert install_span.end_s <= admit.end_s <= first_token.start_s
    assert first_token.attrs == {"request": request, "reads": prefill,
                                 "overlapped": 1}
    behind = next(s for s in _named(admission, "serving.prefill")
                  if s.attrs["program"] == prefill + 2)
    assert behind.end_s <= first_token.start_s
    # at most 6 new spans an admission, the re-commit of the snapshot
    # among them
    new = [s for s in mine if s.name not in ("serving.admit",
                                              "serving.prefill")]
    assert len(new) + 1 <= 6
    commits = [s for s in _named(admission, "serving.snapshot")
               if s.parent == "serving.tick"]
    assert len(commits) == LANES


def test_decode_only_tick_has_one_leaf_span_per_phase(ticked):
    _, spans = ticked
    names = [s.name for s in spans]
    assert names.count("serving.tick") == 1
    assert not _named(spans, "serving.admit")
    tick = _named(spans, "serving.tick")[0]
    # the two deadline sweeps are the tick's own time, under no span
    assert not _named(spans, "serving.expire")
    for name in ("serving.grow", "serving.decode", "serving.fetch",
                 "serving.emit"):
        found = _named(spans, name)
        assert found, name
        assert all(s.parent == "serving.tick" for s in found), name
        assert all(tick.start_s <= s.start_s and s.end_s <= tick.end_s
                   for s in found), name
    # the snapshot before the tick is the tick's sibling, and so is the
    # metrics block after it, a leaf of its own since PR 68 (no span
    # encloses the step: nobody's parent changed); the re-commit after the
    # delivered tokens is the tick's
    snapshot, commit = _named(spans, "serving.snapshot")
    (observe,) = _named(spans, "serving.observe")
    assert snapshot.parent is None and snapshot.end_s <= tick.start_s
    assert observe.parent is None and observe.attrs == {}
    assert spans[-2] is tick and spans[-1] is observe
    assert tick.end_s <= observe.start_s
    assert {s.parent for s in spans} == {None, "serving.tick"}
    # what the step carried, said as the tick closes
    assert tick.attrs == {"tick": tick.attrs["tick"], "admitted": 0,
                          "chunked": 0, "tower": 0, "decoded": LANES,
                          "prefill_rows": 0}
    # ONE emit span for sixteen lanes, never one per lane
    (emit,) = _named(spans, "serving.emit")
    assert commit.parent == "serving.tick" and commit.start_s >= emit.end_s
    (fetch,) = _named(spans, "serving.fetch")
    # an overlapped read: of the tick dispatched a step() earlier, which
    # nothing flushed
    (decode,) = _named(spans, "serving.decode")
    assert emit.attrs == {"batch": LANES}
    assert fetch.attrs == {"batch": LANES,
                           "reads": decode.attrs["program"] - 1}
    assert len(spans) - names.count("serving.tick") \
        - names.count("serving.decode") <= 10


def test_submit_is_a_leaf_span_that_carries_the_id_it_returns():
    eng = _engine()
    rec = get_recorder()
    rec.clear()
    rid = eng.submit(np.asarray([1, 2, 3], np.int32), max_length=2)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.zeros(0, np.int32))
    taken, refused = rec.spans()
    assert (taken.name, taken.parent, taken.attrs) == (
        "serving.submit", None, {"prompt_len": 3, "request": rid})
    # a refusal lies under the span too, and has no request to name
    assert (refused.name, refused.attrs) == ("serving.submit",
                                             {"prompt_len": 0})
    eng.drain()


def test_construction_is_one_span_that_holds_what_it_compiled():
    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # what every entry point calls before its first jit
    rec = get_recorder()
    rec.clear()
    eng = _engine.__wrapped__()     # (what an engine compiles is the test)
    spans = rec.spans()
    (build,) = _named(spans, "serving.build")
    assert build.parent is None and spans[-1] is build
    assert build.attrs == {} and eng._prefill_jits == {}
    # what construction compiled lies inside it and names it as parent
    # (the weights' and the pool's bytes are gauges: `_publish_quant_
    # metrics`); no span of a tick was opened
    inside = [s for s in spans if s.name.startswith("jit.")
              and s.end_s >= build.start_s]  # (the model's init came before)
    assert inside and all(s.parent == "serving.build" and build.start_s <= s.start_s
               and s.end_s <= build.end_s for s in inside)
    assert {s.name.split(".")[0] for s in spans} == {"serving", "jit"}
    assert not _named(spans, "serving.tick")


def test_a_buckets_first_prefill_says_so_and_holds_its_compile():
    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    eng = _engine.__wrapped__()     # (its programs' first compile is the test)
    rng = np.random.default_rng(1)
    rec = get_recorder()
    rec.clear()
    for n in (3, 4, 7, 6, 2):  # buckets 4, 4, 8, 8, 4
        eng.submit(rng.integers(1, 60, n, dtype=np.int32), max_length=n + 2)
    eng.drain()
    spans = rec.spans()
    prefills = _named(spans, "serving.prefill")
    assert [(s.attrs["bucket"], s.attrs.get("first")) for s in prefills] == [
        (4, True), (4, None), (8, True), (8, None), (4, None)]
    assert eng.metrics.snapshot()["prefill_programs"] == 2
    compiles = [s for s in _named(spans, "jit.compile")
                if s.attrs["fun_name"] == "jit(prefill)"]
    assert len(compiles) == 2
    for compile_, first in zip(compiles, [prefills[0], prefills[2]]):
        # found by thread and time, named as its parent
        assert compile_.parent == "serving.prefill"
        assert compile_.thread_id == first.thread_id
        assert first.start_s <= compile_.start_s
        assert compile_.end_s <= first.end_s
    # the tick that compiled is the jit.compile's parent: serving.decode
    # carries no attr for it
    (tick,) = [s for s in _named(spans, "jit.compile")
               if s.attrs["fun_name"] == "jit(_decode_fn)"]
    assert tick.parent == "serving.decode"
    assert not any("first" in s.attrs for s in _named(spans, "serving.decode"))


def test_the_wait_for_the_device_is_in_fetch_not_in_decode(ticked):
    _, spans = ticked
    (fetch,) = _named(spans, "serving.fetch")
    (decode,) = _named(spans, "serving.decode")
    assert fetch.duration_s >= 0.02
    assert decode.duration_s < 0.02      # a dispatch span
    # this step's tick is dispatched BEFORE the wait for the one before it
    assert decode.attrs["inflight"] == 1
    assert decode.end_s <= fetch.start_s


# ------------------------------ which program a span dispatched, which it read

DISPATCH = ("serving.decode", "serving.verify", "serving.prefill",
            "serving.install")


def _dispatched(spans):
    """The dispatch spans that number a program, in the order they began."""
    return sorted((s for s in spans
                   if s.name in DISPATCH and "program" in s.attrs),
                  key=lambda s: s.start_s)


def _steps(spans):
    """``[[the spans inside one serving.tick], ...]`` in tick order."""
    ticks = sorted(_named(spans, "serving.tick"), key=lambda s: s.start_s)
    return [[s for s in spans
             if t.start_s <= s.start_s and s.end_s <= t.end_s and s is not t]
            for t in ticks]


def test_programs_are_numbered_without_gap_or_reuse_through_a_recovery():
    """An admission, decode-only ticks, a chunked admission, a tick that
    fails and is rolled back, the recovery's replays and the ticks after
    it: every dispatch takes the next number, and none comes twice."""
    from fleetx_tpu.resilience.faults import faults

    eng = _engine(prefill_chunk=4)
    rec = get_recorder()
    rec.clear()
    short = eng.submit(np.asarray([1, 2, 3], np.int32), max_length=6)
    eng.step()                     # one-call admission, first decode tick
    eng.step()                     # decode-only
    chunked = eng.submit(np.arange(1, 11, dtype=np.int32), max_length=6)
    for _ in range(3):             # chunks of 4, 4 and 2 beside the ticks
        eng.step()
    faults.configure(tick_raise=str(eng._fault_ticks))
    try:
        summary = eng.step()       # the dispatch raises: rollback, recovery
    finally:
        faults.reset()
    assert summary["recovered"] and eng.metrics.engine_recoveries == 1
    results = eng.drain()
    assert set(results) == {short, chunked}
    spans = rec.spans()
    programs = [s.attrs["program"] for s in _dispatched(spans)]
    assert programs == list(range(1, len(programs) + 1))
    assert eng._programs == len(programs)
    names = [s.name for s in _dispatched(spans)]
    assert names[:3] == ["serving.prefill", "serving.install",
                         "serving.decode"]
    # the failed dispatch kept its number, and the replays come after it
    (rollback,) = _named(spans, "serving.rollback")
    failed = [s for s in _dispatched(spans) if s.end_s <= rollback.start_s][-1]
    assert failed.name == "serving.decode"
    assert not [s for s in _named(spans, "serving.fetch")
                if s.attrs["reads"] == failed.attrs["program"]]
    replays = [s for s in _dispatched(spans) if s.parent == "serving.recover"]
    assert {s.name for s in replays} == {"serving.prefill", "serving.install"}
    # a chunked admission waits for its FINAL chunk's program
    chunks = [s for s in _named(spans, "serving.prefill")
              if s.attrs["request"] == chunked
              and s.parent == "serving.prefill_chunk"]
    assert len(chunks) == 3
    (wait,) = [s for s in _named(spans, "serving.first_token")
               if s.attrs["request"] == chunked]
    assert wait.attrs["reads"] == chunks[-1].attrs["program"]
    assert wait.start_s >= chunks[-1].end_s


def test_first_token_reads_its_requests_prefill(ticked):
    admission, _ = ticked
    prefills = {s.attrs["request"]: s.attrs["program"]
                for s in _named(admission, "serving.prefill")}
    waits = _named(admission, "serving.first_token")
    assert len(waits) == len(prefills) == LANES
    assert all(s.attrs["reads"] == prefills[s.attrs["request"]]
               for s in waits)


@pytest.mark.parametrize("kw, cause", [
    (dict(spec=True, spec_k=2), "spec"), (dict(tick_timeout_s=60.0), "watchdog"),
    ({}, "idle")])
def test_a_fetch_names_the_program_it_reads_and_why_it_was_flushed(kw, cause):
    """An overlapped fetch reads the tick dispatched in the step BEFORE
    its own; a flushed one names its cause and reads the tick of its own
    step (``idle``: there is none, so the last one dispatched)."""
    eng = _engine(**kw)
    rec = get_recorder()
    rec.clear()
    eng.submit(np.asarray([3, 1, 4, 1, 5], np.int32), max_length=6)
    eng.step()
    eng.submit(np.asarray([2, 7, 1, 8], np.int32), max_length=6)
    eng.drain()
    steps = _steps(rec.spans())
    ticks = [[s for s in step if s.name in ("serving.decode",
                                            "serving.verify")]
             for step in steps]
    fetches = [(n, s) for n, step in enumerate(steps)
               for s in _named(step, "serving.fetch")]
    assert fetches
    flushed = [(n, s) for n, s in fetches if "flushed" in s.attrs]
    assert {s.attrs["flushed"] for _, s in flushed} == {cause}
    for n, fetch in fetches:
        if "flushed" not in fetch.attrs:
            (before,) = ticks[n - 1]
            assert before.name == "serving.decode"
            assert before.attrs["inflight"] in (0, 1)
            assert fetch.attrs["reads"] == before.attrs["program"]
            assert ticks[n] and ticks[n][0].end_s <= fetch.start_s
        elif cause == "idle":
            assert not ticks[n]
            assert fetch.attrs["reads"] == ticks[n - 1][0].attrs["program"]
        else:
            (own,) = ticks[n]
            assert fetch.attrs["reads"] == own.attrs["program"]
    if cause == "idle":
        assert len(flushed) == 1 and len(fetches) > 1
    else:
        assert len(flushed) == len(fetches)


@pytest.mark.parametrize("profiling", [True, False])
def test_the_annotation_keeps_the_bare_name_and_takes_the_identity(
        monkeypatch, profiling):
    """Inside a profiling window the identity attrs become arguments of
    the annotation, whose name stays the bare span name; outside one
    nothing is handed over."""
    from fleetx_tpu.obs import tracing

    seen = []

    class Annotation:
        def __init__(self, name, **kwargs):
            seen.append([name, kwargs, None])

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **kwargs):
            seen[-1][2] = kwargs

    monkeypatch.setattr(tracing, "TraceAnnotation", Annotation)
    monkeypatch.setattr(tracing, "_profiling", lambda: profiling)
    rec = SpanRecorder(capacity=4)
    with span("serving.fetch", recorder=rec, batch=3, reads=7, request=2,
              tick=5, flushed="idle") as at:
        at["program"] = 9   # known too late to ride
    identity = {"reads": 7, "request": 2, "tick": 5}
    assert seen == [["serving.fetch", {}, identity if profiling else None]]
    assert rec.spans()[0].attrs == {"batch": 3, "flushed": "idle",
                                    "program": 9, **identity}


def test_tables_upload_is_spanned_only_when_it_uploads():
    eng = _engine()
    eng.submit(np.asarray([1, 2, 3], np.int32), max_length=6)
    rec = get_recorder()
    rec.clear()
    eng.step()
    assert len(_named(rec.spans(), "serving.tables")) == 1
    rec.clear()
    eng.step()  # same pages: nothing to upload
    assert not _named(rec.spans(), "serving.tables")


# ---------------------------------------------------------------- trainer

_YAML = textwrap.dedent("""
    Global:
      seed: 7
      local_batch_size: 2
      micro_batch_size: 2
    Engine:
      max_steps: 2
      logging_freq: 1
      eval_freq: 0
      eval_iters: 1
      save_load:
        save_steps: 1000
    Model:
      module: GPTModule
      vocab_size: 64
      hidden_size: 32
      num_layers: 2
      num_attention_heads: 2
      ffn_hidden_size: 64
      max_position_embeddings: 16
      hidden_dropout_prob: 0.0
      attention_probs_dropout_prob: 0.0
      use_flash_attention: False
    Optimizer:
      name: AdamW
      weight_decay: 0.01
      lr:
        name: CosineAnnealingWithWarmupDecay
        decay_steps: 100
        max_lr: 1.0e-3
        min_lr: 1.0e-4
""")


def _trainer_and_data(tmp_path):
    from fleetx_tpu.core.engine import Trainer
    from fleetx_tpu.models import build_module
    from fleetx_tpu.utils.config import get_config

    path = tmp_path / "cfg.yaml"
    path.write_text(_YAML)
    cfg = get_config(str(path), nranks=1)
    cfg.Engine.save_load.output_dir = str(tmp_path / "out")
    gbs = cfg.Global.global_batch_size
    tokens = np.random.RandomState(0).randint(0, 64, (gbs, 16)).astype(np.int32)
    data = [{"tokens": tokens, "labels": ((tokens + 1) % 64).astype(np.int32),
             "loss_mask": np.ones((gbs, 16), np.float32)}] * 2
    return Trainer(cfg, build_module(cfg)), data


def test_fit_spans_the_batch_the_fetches_and_the_log_line(tmp_path):
    trainer, data = _trainer_and_data(tmp_path)
    rec = get_recorder()
    rec.clear()
    trainer.fit(data)
    spans = rec.spans()
    shard = _named(spans, "train.shard_batch")
    assert [s.attrs["step"] for s in shard] == [0, 1]
    assert all(s.parent is None for s in shard)
    fetches = _named(spans, "train.loss_fetch")
    # each step: the sentry's read right after the dispatch (top level),
    # then the loss window's at the logging step (inside the callback)
    assert [s.parent for s in fetches] == [None, "train.callback"] * 2
    logs = _named(spans, "train.log")
    assert [s.parent for s in logs] == ["train.callback"] * 2
    for step, fetch in zip(_named(spans, "train.step"), fetches[::2]):
        assert step.end_s <= fetch.start_s  # train.step ends at dispatch


def test_trainer_construction_restore_and_first_step_are_spans(tmp_path):
    from fleetx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    trainer, data = _trainer_and_data(tmp_path)
    rec = get_recorder()
    rec.clear()
    trainer.fit(data)
    spans = rec.spans()
    (build,) = _named(spans, "train.build")
    assert build.parent is None and build.attrs == {}
    assert not _named(spans, "train.restore")
    # the init program compiled inside the construction span
    assert [s for s in _named(spans, "jit.compile")
            if s.parent == "train.build" and s.attrs["fun_name"] == "jit(_init)"]
    steps = _named(spans, "train.step")
    assert [s.attrs for s in steps] == [{"step": 0, "first": True},
                                        {"step": 1}]
    (compiled,) = [s for s in _named(spans, "jit.compile")
                   if s.attrs["fun_name"] == "jit(train_step)"
                   and s.parent == "train.step"]
    assert steps[0].start_s <= compiled.start_s
    assert compiled.end_s <= steps[0].end_s
    # the first log line asks for the step's AOT twin: this jax finds the
    # trace, the lowering and the executable in memory, so nothing is
    # built under train.log (no second lowering)
    assert not [s for s in spans if s.name in ("jit.lower", "jit.compile")
                and s.parent == "train.log"
                and "train_step" in s.attrs["fun_name"]]
    trainer.save()
    trainer.wait_for_checkpoints()

    resumed, _ = _trainer_and_data(tmp_path)
    rec.clear()
    resumed.init_state(data[0])
    spans = rec.spans()
    (build,) = _named(spans, "train.build")
    assert build.attrs == {"restored": True}
    (restore,) = _named(spans, "train.restore")
    assert restore.parent == "train.build"
    assert build.start_s <= restore.start_s and restore.end_s <= build.end_s
    assert int(resumed.state.step) == 2


# ------------------------------------------------- scopes on the programs

_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _instructions(hlo_text):
    """``[(instruction name, op_name or None)]`` of optimized HLO text."""
    out = []
    for line in hlo_text.splitlines():
        head = _INSTRUCTION.match(line)
        if head:
            op = _OP_NAME.search(line)
            out.append((head.group(1), op and op.group(1)))
    return out


def _unscoped_share(hlo_text):
    from perfbench import trace_reduce
    from perfbench.layer_metrics import _parts

    # a parameter's op_name is its argument's name and a reducer's body
    # has bare primitives: only paths from the traced program count
    named = [(trace_reduce.instruction("%" + name), op)
             for name, op in _instructions(hlo_text)
             if op and op.startswith("jit(")]
    parts = [_parts.part_of(instruction, op) for instruction, op in named]
    assert len(parts) > 50
    return parts.count("unscoped") / len(parts), sorted(
        {op for (_, op), part in zip(named, parts) if part == "unscoped"})


@contextlib.contextmanager
def _no_scopes(monkeypatch):
    with monkeypatch.context() as patch:
        patch.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
        yield


def _decode_tick_text():
    eng = _engine.__wrapped__()     # (traced anew: under the scopes or not)
    eng.submit(np.asarray([1, 2, 3], np.int32), max_length=6)
    eng.step()
    compiled = eng.compiled_decode()
    return compiled.as_text(), compiled.cost_analysis()


def _train_step_text(tmp_path):
    trainer, data = _trainer_and_data(tmp_path)
    trainer.fit(data)
    return trainer.compiled_text("train"), trainer.cost_analysis("train")


@pytest.mark.parametrize("program", ["decode_tick", "train_step"])
def test_every_named_instruction_falls_in_a_part(program, tmp_path):
    text, _ = (_decode_tick_text() if program == "decode_tick"
               else _train_step_text(tmp_path))
    share, which = _unscoped_share(text)
    assert share <= 0.05, which


@pytest.mark.parametrize("program", ["decode_tick", "train_step"])
def test_scopes_are_metadata_only(program, tmp_path, monkeypatch):
    """The same program compiled with every ``jax.named_scope`` (flax's
    and ours) switched off has the same instructions and the same cost."""
    build = (_decode_tick_text if program == "decode_tick"
             else lambda: _train_step_text(tmp_path))
    text, cost = build()
    with _no_scopes(monkeypatch):
        bare_text, bare_cost = build()
    # instruction for instruction the same, but for the numbers in names
    assert ([re.sub(r"[.0-9]+$", "", name) for name, _ in _instructions(text)]
            == [re.sub(r"[.0-9]+$", "", name)
                for name, _ in _instructions(bare_text)])
    cost, bare_cost = (c[0] if isinstance(c, (list, tuple)) else c
                       for c in (cost, bare_cost))
    for key in ("flops", "bytes accessed"):
        assert cost.get(key) == bare_cost.get(key), key
    scoped = sum(1 for _, op in _instructions(text)
                 if op and re.search(r"sampler|optimizer", op))
    assert scoped and not any(
        op and re.search(r"sampler|optimizer", op)
        for _, op in _instructions(bare_text))
