"""The four start-up readers (``setup_trace_lower_s``,
``setup_cache_load_s``, ``setup_compile_s``, ``setup_programs``: each moves
``setup_s``) on a hand-made run and ring: they take the program's ``jit.*``
spans that ended BEFORE the measured window from the program's own ring,
construction's among them, read nothing in a rehearsal, and nothing from a
build of the program that records no such span."""

import types

import pytest

from fleetx_tpu.obs.tracing import Span, get_recorder
from perfbench import harness
from perfbench.layer_metrics import (
    setup_cache_load_s,
    setup_compile_s,
    setup_programs,
    setup_trace_lower_s,
)

READERS = {"setup_trace_lower_s": setup_trace_lower_s,
           "setup_cache_load_s": setup_cache_load_s,
           "setup_compile_s": setup_compile_s,
           "setup_programs": setup_programs}
WINDOW = (100.0, 140.0)


def _span(name, start_s, end_s, parent=None, **attrs):
    return Span(name=name, start_s=start_s, end_s=end_s, thread_id=1,
                depth=0, attrs=attrs, parent=parent)


def _compile(start_s, end_s, cache, parent=None, **attrs):
    return _span("jit.compile", start_s, end_s, parent,
                 fun_name="jit(prefill)", cache=cache, **attrs)


# a serving process: construction (the pool's zeros compile inside it), a
# first prefill that misses, a second bucket that hits, the tick's program
# with the cache off; then the window, with a compile INSIDE it that no
# reader counts, and the check's program after it
SERVING = [
    _span("jit.trace", 10.0, 10.5, "serving.build", fun_name="zeros"),
    _span("jit.lower", 10.5, 10.75, "serving.build", fun_name="jit(zeros)"),
    _compile(10.75, 12.75, "miss", "serving.build"),
    _span("serving.build", 2.0, 14.0),
    _span("jit.trace", 20.0, 21.0, "serving.prefill", fun_name="prefill"),
    _span("jit.lower", 21.0, 21.5, "serving.prefill",
          fun_name="jit(prefill)"),
    _compile(21.5, 29.5, "miss", "serving.prefill"),
    _span("jit.trace", 40.0, 41.0, "serving.prefill", fun_name="prefill"),
    _span("jit.lower", 41.0, 41.5, "serving.prefill",
          fun_name="jit(prefill)"),
    _compile(41.5, 43.0, "hit", "serving.prefill", load_s=1.25),
    _compile(50.0, 54.0, "off", "serving.decode"),
    _span("serving.tick", 99.0, 99.5),
    _span("jit.trace", 120.0, 121.0, "serving.prefill", fun_name="prefill"),
    _span("jit.lower", 121.0, 122.0, "serving.prefill",
          fun_name="jit(prefill)"),
    _compile(122.0, 130.0, "miss", "serving.prefill"),
    _compile(150.0, 151.0, "hit", load_s=0.5),
]
WANT = {"setup_trace_lower_s": 0.75 + 1.5 + 1.5,
        "setup_cache_load_s": 1.5,
        "setup_compile_s": 2.0 + 8.0 + 4.0,
        "setup_programs": 4.0}


def _run(tiny=False):
    return harness.Run(
        cell=types.SimpleNamespace(tiny=tiny), device={}, setup_s=99.0,
        window=WINDOW, attempted=0, failed=0, correct=True, checks={},
        counters={}, spans=[], samples={})


@pytest.fixture
def ring():
    """The program's ring with ``spans`` in it, and emptied afterwards."""
    rec = get_recorder()

    def fill(spans):
        rec.clear()
        for s in spans:
            rec.record(s)
    yield fill
    rec.clear()


@pytest.mark.parametrize("name", READERS)
def test_a_reader_sums_what_ended_before_the_window(ring, name):
    ring(SERVING)
    assert READERS[name].read(_run()) == pytest.approx(WANT[name])


def test_the_programs_are_the_compile_spans_before_the_window(ring):
    ring(SERVING)
    before = [s for s in SERVING
              if s.name == "jit.compile" and s.end_s <= WINDOW[0]]
    assert setup_programs.read(_run()) == len(before) == 4
    # a span that ends AT the window's start is set-up; one a moment
    # later is the window's
    ring(SERVING + [_compile(99.0, WINDOW[0], "hit", load_s=0.5),
                    _compile(99.5, WINDOW[0] + 1e-6, "hit", load_s=0.5)])
    assert setup_programs.read(_run()) == 5


@pytest.mark.parametrize("name", READERS)
def test_a_rehearsal_reads_nothing(ring, name):
    """A CPU's set-up seconds are no device metric."""
    ring(SERVING)
    assert READERS[name].read(_run(tiny=True)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_these_spans_reads_nothing(ring, name):
    """The parent of PR 51 records none: the metric is left out, not 0."""
    ring([s for s in SERVING if s.name == "serving.tick"])
    assert READERS[name].read(_run()) is None


def test_a_cached_run_reads_no_compile_and_a_cold_one_no_load(ring):
    hits = [_compile(10.0 + i, 10.5 + i, "hit", load_s=0.4)
            for i in range(3)]
    ring(hits)
    assert setup_compile_s.read(_run()) == 0.0
    assert setup_cache_load_s.read(_run()) == pytest.approx(1.5)
    ring([_compile(10.0, 30.0, "miss")])
    assert setup_cache_load_s.read(_run()) == 0.0
    assert setup_compile_s.read(_run()) == pytest.approx(20.0)


def test_the_seconds_entries_are_parts_of_one_set_up(ring):
    """What construction compiles is counted with every other program's,
    once: the three seconds entries sum to no more than the stretch the
    spans cover (``jit.trace`` and ``jit.lower`` are outermost sections)."""
    ring([_span("jit.lower", 2.0, 4.0, "train.build", fun_name="jit(_init)"),
          _compile(4.0, 9.0, "miss", "train.build"),
          _span("train.build", 1.0, 10.0),
          _span("jit.trace", 10.5, 11.0, "train.step", fun_name="step"),
          _compile(11.0, 12.0, "hit", "train.step", load_s=0.75)])
    parts = {name: READERS[name].read(_run()) for name in READERS}
    assert parts == pytest.approx({
        "setup_trace_lower_s": 2.5, "setup_cache_load_s": 1.0,
        "setup_compile_s": 5.0, "setup_programs": 2.0})
    assert sum(parts.values()) - parts["setup_programs"] <= 12.0 - 2.0
