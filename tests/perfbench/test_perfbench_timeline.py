"""The join of ring spans, host events and the device's program line
(``perfbench/layer_metrics/_timeline.py``) and the five readers on it: on
a hand-built timeline whose answers are known, on the faults the join must
refuse, and on the cut of a traced docs-batch run recorded on the chip by
PR 35 (``perfbench/fixtures/timeline/``)."""

import glob
import json
import os

import pytest

from fleetx_tpu.obs.tracing import Span
from perfbench import harness
from perfbench.layer_metrics import (_timeline, admit_idle_ms_p50,
                                     first_token_queue_ms_p50,
                                     first_token_return_ms_p50,
                                     launch_gap_ms_p50, tick_overlap_share)

T0 = 1000.0          # perf_counter at the hand-built timeline's start, s
OFFSET = -999.5      # trace clock less perf_counter, s


class Built:
    """A hand-built traced stretch. Times are given in ms from ``T0``.

    Cycle c (c = 0, 1, ...) starts when decode program ``a`` starts on the
    device and holds one admission and one decode-only step:

    - ``a`` runs 10 + 0.2 c ms; the admission's prefill was dispatched
      2.6 ms into the cycle and starts 0.3 ms after ``a`` ends (the launch
      gap), so it queued 7.7 + 0.2 c ms; it runs 12 ms; its first token is
      on the host 1.0 + 0.1 c ms after its end;
    - install dispatch 0.5 ms later for 0.6 ms, its program 0.2 ms after
      that for 0.1 ms; 1.0 ms later decode ``b`` is dispatched (0.5 ms),
      starts 0.4 ms after and runs 10 ms; ``a`` is fetched after ``b``'s
      dispatch;
    - the decode-only step dispatches ``c`` 1.1 ms into ``b`` and fetches
      ``b`` 1.1 ms after its end; ``c`` starts 0.4 ms after ``b`` ends
      (the launch gap) and is the next cycle's ``a``.

    The admission's span opens 1.0 ms into the cycle, so the device idles
    0.3 + return + 0.5 + 0.6 + 0.2 + 1.0 + 0.5 + 0.4 = 3.5 + return ms
    between there and ``b``'s start: 4.5 + 0.1 c."""

    def __init__(self, cycles=6):
        self.modules, self.host, self.spans = [], [], []
        self.program = 40
        t = 0.0
        a = self.dispatch("serving.decode", -9.5, -9.0, "serving.tick",
                          batch=4, inflight=0)
        for c in range(cycles):
            a_len = 10 + 0.2 * c
            self.device("jit__decode_fn", t, a_len)
            self.span("serving.tick", t + 0.5, None, tick=c * 2)
            tick = self.spans[-1]
            self.span("serving.admit", t + 1.0, None, "serving.tick",
                      request=c, prompt_len=64)
            admit = self.spans[-1]
            p = self.dispatch("serving.prefill", t + 2.0, t + 2.6,
                              "serving.admit", request=c, bucket=64)
            start = t + a_len + 0.3
            self.device("jit_prefill", start, 12.0)
            token = start + 12.0 + 1.0 + 0.1 * c
            self.span("serving.first_token", t + 2.8, token, "serving.admit",
                      request=c, reads=p)
            self.dispatch("serving.install", token + 0.5, token + 1.1,
                          "serving.admit", request=c, transfers=1)
            self.device("jit__admit_fn", token + 1.3, 0.1)
            admit.end_s = T0 + (token + 1.5) / 1e3
            b = self.dispatch("serving.decode", token + 2.4, token + 2.9,
                              "serving.tick", batch=4, inflight=1)
            b_start = token + 3.3
            self.device("jit__decode_fn", b_start, 10.0)
            self.span("serving.fetch", token + 3.0, token + 3.2,
                      "serving.tick", batch=4, reads=a)
            tick.end_s = T0 + (token + 3.25) / 1e3
            self.span("serving.tick", b_start + 0.2, b_start + 11.2,
                      tick=c * 2 + 1)
            a = self.dispatch("serving.decode", b_start + 1.1, b_start + 1.6,
                              "serving.tick", batch=4, inflight=1)
            self.span("serving.fetch", b_start + 1.7, b_start + 11.1,
                      "serving.tick", batch=4, reads=b)
            t = b_start + 10.4
        self.device("jit__decode_fn", t, 10.0)   # the last one is never read
        self.end_ms = t + 10.0

    def span(self, name, start, end, parent=None, **attrs):
        """A ring span (``end`` None: set later) and its host event, with
        the identity attrs as its stats."""
        s = Span(name=name, start_s=T0 + start / 1e3,
                 end_s=T0 + (end if end is not None else start) / 1e3,
                 thread_id=0, depth=0, attrs=attrs, parent=parent)
        self.spans.append(s)
        stats = {k: v for k, v in attrs.items()
                 if k in ("program", "reads", "request", "tick")}
        self.host.append([name, (s.start_s + OFFSET) * 1e9, 1000.0, stats])

    def dispatch(self, name, start, end, parent, **attrs):
        self.program += 1
        self.span(name, start, end, parent, program=self.program, **attrs)
        return self.program

    def device(self, name, start, dur):
        self.modules.append([f"{name}(123)", (T0 + start / 1e3 + OFFSET) * 1e9,
                             dur * 1e6])


def _run(tmp_path, monkeypatch, modules, host, spans, traced=(0.0, 0.0),
         window=(0.0, 1e9)):
    """A traced run whose trace file holds ``modules`` and ``host``."""
    path = tmp_path / "trace.json"
    path.write_text(json.dumps({"modules": modules, "host": host,
                                "spans": []}))
    monkeypatch.setattr(_timeline, "_trace_files", lambda: [str(path)])
    _timeline._JOINED.clear()
    return harness.Run(
        cell=None, device={}, setup_s=0.0, window=window, attempted=0,
        failed=0, correct=True, checks={}, samples={}, spans=spans,
        counters={}, traced=traced, trace={"idle_share": 0.0})


READERS = {"launch_gap": launch_gap_ms_p50, "queue": first_token_queue_ms_p50,
           "return": first_token_return_ms_p50, "idle": admit_idle_ms_p50}


@pytest.mark.parametrize("reader, answer", [
    ("launch_gap", 0.35),   # six of 0.3 (a -> prefill), six of 0.4 (b -> c)
    ("queue", 8.2),         # 7.7, 7.9, ... 8.7
    ("return", 1.25),       # 1.0, 1.1, ... 1.5
    ("idle", 4.75)])        # 4.5, 4.6, ... 5.0
def test_a_reader_finds_the_known_answer(reader, answer, tmp_path, monkeypatch):
    built = Built()
    run = _run(tmp_path, monkeypatch, built.modules, built.host, built.spans)
    assert READERS[reader].read(run) == pytest.approx(answer, abs=1e-6)


def test_the_join_numbers_every_program_and_bounds_the_skew():
    built = Built()
    built.host[3][1] -= 3e3       # one annotation opened 3 us earlier
    t = _timeline.join(built.modules, built.host, built.spans)
    assert t.violations == []
    assert t.offset_s == pytest.approx(OFFSET, abs=1e-9)
    assert t.clock_residual_us == pytest.approx(3.0, abs=1e-3)
    # every host event but the six serving.admit, which carry a request
    # alone (that names several spans, so it matches none)
    assert t.pairs == len(built.host) - 6
    # 1 + 6 x (prefill, install, b, c) dispatches, each joined, in order
    assert [r.program for r in t.rows] == list(range(41, 66))
    assert len(t.programs) == len(built.modules) == 25
    assert t.rows[-1].wait is None and t.rows[0].wait.name == "serving.fetch"
    # no program starts before its dispatch span began, so the device's
    # clock stays where it is; the tightest wait is the first admission's
    assert t.device_shift_us == 0.0
    assert t.slack_wait_us == pytest.approx(1000.0, abs=1e-3)
    assert "25 programs joined of 25" in t.summary()


def test_a_device_clock_that_runs_ahead_is_moved_back_by_what_shows():
    """On the chip programs appear to start before their dispatch span
    began. With every program 1.0 ms early, the install's (0.8 ms after
    its span began) shows 0.2 ms of it: the join moves the device's clock
    back by that, and what is left (0.8 ms) is inside the wait slack."""
    built = Built()
    for module in built.modules:
        module[1] -= 1.0e6
    t = _timeline.join(built.modules, built.host, built.spans)
    assert t.violations == []
    assert t.device_shift_us == pytest.approx(200.0, abs=1e-3)
    assert t.slack_wait_us == pytest.approx(1800.0, abs=1e-3)
    returns = sorted(a["return_ms"] for a in t.admissions())
    assert returns[0] == pytest.approx(1.8, abs=1e-6)


def test_queue_program_and_return_make_up_the_wait_for_the_first_token():
    built = Built()
    admissions = _timeline.join(built.modules, built.host,
                                built.spans).admissions()
    assert [a["request"] for a in admissions] == list(range(6))
    for c, a in enumerate(admissions):
        assert a["program_ms"] == pytest.approx(12.0, abs=1e-6)
        assert (a["queue_ms"] + a["program_ms"] + a["return_ms"]
                == pytest.approx((a["first_token_end_s"] - a["prefill_end_s"])
                                 * 1e3, abs=1e-6))
        assert a["queue_ms"] == pytest.approx(7.7 + 0.2 * c, abs=1e-6)


def _swapped(built):
    """The device ran the first admission's prefill and install in the
    other order."""
    built.modules[1][0], built.modules[2][0] = (built.modules[2][0],
                                                built.modules[1][0])


def _early(built):
    """The third program starts 3 ms before its dispatch span began."""
    span = next(s for s in built.spans if s.attrs.get("program") == 43)
    built.modules[2][1] = (span.start_s + OFFSET) * 1e9 - 3e6


@pytest.mark.parametrize("fault, says", [
    (_swapped, "the device ran jit__admit_fn"),
    (_early, "before its dispatch span began: more than a clock's skew")])
def test_a_broken_order_gives_no_reading_and_says_why(
        fault, says, tmp_path, monkeypatch, capsys):
    built = Built()
    fault(built)
    run = _run(tmp_path, monkeypatch, built.modules, built.host, built.spans)
    assert all(reader.read(run) is None for reader in READERS.values())
    out = capsys.readouterr().out
    assert "timeline: violation:" in out and says in out
    assert "launch_gap_ms_p50: no reading: the join found" in out


def test_fewer_than_five_samples_give_no_reading_and_say_so(
        tmp_path, monkeypatch, capsys):
    built = Built(cycles=4)
    run = _run(tmp_path, monkeypatch, built.modules, built.host, built.spans)
    assert launch_gap_ms_p50.read(run) == pytest.approx(0.35)  # 8 pairs
    for name in ("queue", "return", "idle"):
        assert READERS[name].read(run) is None
    assert ("first_token_queue_ms_p50: no reading: 4 joined samples in the "
            "trace, 5 needed") in capsys.readouterr().out


def test_a_program_that_numbers_nothing_gives_no_timeline(
        tmp_path, monkeypatch, capsys):
    """The parent's spans carry no ``program``: every reader returns None
    and nothing raises."""
    built = Built()
    for s in built.spans:
        for key in ("program", "reads"):
            s.attrs.pop(key, None)
    run = _run(tmp_path, monkeypatch, built.modules, built.host, built.spans)
    assert all(reader.read(run) is None for reader in READERS.values())
    assert "timeline: nothing to join" in capsys.readouterr().out
    run.trace = None            # and a run that was not traced reads nothing
    assert launch_gap_ms_p50.read(run) is None


def test_tick_overlap_share_counts_the_windows_ticks_outside_the_trace():
    decode = lambda at, inflight: Span(  # noqa: E731
        name="serving.decode", start_s=at, end_s=at + 0.0005, thread_id=0,
        depth=0, attrs={"batch": 4, "inflight": inflight})
    spans = [decode(10.0 + 0.01 * i, i != 3) for i in range(8)]
    spans += [decode(20.5, 0), decode(31.0, 0)]  # traced; beyond the window
    run = harness.Run(
        cell=None, device={}, setup_s=0.0, window=(9.0, 30.0), attempted=0,
        failed=0, correct=True, checks={}, samples={}, spans=spans,
        counters={}, traced=(20.0, 24.0), trace=None)
    assert tick_overlap_share.read(run) == pytest.approx(7 / 8)
    for s in spans:
        del s.attrs["inflight"]
    assert tick_overlap_share.read(run) is None


def test_annotation_arguments_come_back_as_stats_of_a_bare_named_event(tmp_path):
    """Through the real profiler: ``load_xplane`` finds the span's host
    event under its bare name with the identity attrs as stats, and the
    clock offset from them fits to microseconds."""
    import jax

    from fleetx_tpu.obs import SpanRecorder, span

    rec = SpanRecorder(capacity=16)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for n in range(5):
            with span("serving.decode", recorder=rec, batch=3, inflight=1,
                      program=70 + n):
                with span("serving.fetch", recorder=rec, batch=3,
                          reads=69 + n, flushed="idle"):
                    pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    trace = _timeline.load_xplane(path)
    assert trace["modules"] == []          # no TPU here: no program line
    assert [(name, stats) for name, _, _, stats in trace["host"]
            if name.startswith("serving.")] == [
        pair for n in range(5) for pair in (
            ("serving.decode", {"program": 70 + n}),
            ("serving.fetch", {"reads": 69 + n}))]
    _, residual_us, pairs = _timeline.clock_offset(trace["host"], rec.spans())
    assert pairs == 10 and residual_us < 200


FIXTURE = os.path.join(harness.HERE, "fixtures", "timeline",
                       "serve_docs_batch_v5e.json")


@pytest.fixture()
def recorded(tmp_path, monkeypatch):
    """The run behind the recorded cut: 400 ms of a traced docs-batch
    window, one second into the trace (my chip run, PR 35, seed
    3500000101): ten admissions in two bursts and eleven decode ticks."""
    cut = _timeline.load_dump(FIXTURE)
    monkeypatch.setattr(_timeline, "_trace_files", lambda: [FIXTURE])
    _timeline._JOINED.clear()
    return harness.Run(
        cell=None, device={}, setup_s=0.0, window=(0.0, 1e9), attempted=0,
        failed=0, correct=True, checks={}, samples={}, spans=cut["spans"],
        counters={}, traced=(0.0, 0.0), trace={"idle_share": 0.0})


def test_recorded_cut_joins_without_a_violation(recorded):
    assert os.path.getsize(FIXTURE) < 1_000_000
    t = _timeline.of_run(recorded)
    assert t.violations == []
    assert len(t.rows) == 32 and len(t.programs) == 52
    assert t.pairs == 64 and t.clock_residual_us < 10
    # looked at by hand: request 832's prefill (program 2689) starts
    # 658.5 us before its serving.prefill span began, the most of any
    assert t.device_shift_us == pytest.approx(658.5, abs=0.1)
    assert t.slack_wait_us == pytest.approx(1295.4, abs=0.1)
    admissions = t.admissions()
    assert len(admissions) == 10
    first = admissions[0]        # request 832, by hand from the raw lists
    assert first["request"] == 832
    assert first["program_ms"] == pytest.approx(30.623, abs=1e-3)
    assert first["return_ms"] == pytest.approx(1.473, abs=2e-3)
    assert first["idle_ms"] == pytest.approx(4.848, abs=2e-3)
    for a in admissions:
        assert (a["queue_ms"] + a["program_ms"] + a["return_ms"]
                == pytest.approx((a["first_token_end_s"] - a["prefill_end_s"])
                                 * 1e3, abs=1e-6))


@pytest.mark.parametrize("reader, reading", [
    ("launch_gap", 0.00095),    # queued programs follow each other at 1 us
    ("queue", -0.335),          # most admissions find the device idle
    ("return", 1.412),
    ("idle", 4.173)])
def test_recorded_cut_reads_what_was_looked_at_by_hand(recorded, reader,
                                                       reading):
    assert READERS[reader].read(recorded) == pytest.approx(reading, abs=1e-3)
    if reader == "launch_gap":
        assert tick_overlap_share.read(recorded) == 1.0   # 51 ticks, all
