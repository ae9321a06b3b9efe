"""The four readers of the step's own account (``batch.lanes_prefilling_
share``, ``batch.lanes_waiting_share``, ``batch.step_prefill_time_share``,
``batch.step_caller_ms_p50``: each moves ``serve_tokens_per_s``) on
hand-made runs: a reading from spans that carry the account, nothing from
the spans of a program that does not (the parent of PR 68), nothing in a
rehearsal."""

import types

import pytest

from fleetx_tpu.obs.tracing import Span
from perfbench import harness
from perfbench.layer_metrics import (
    lane_occupancy,
    lanes_prefilling_share,
    lanes_waiting_share,
    step_caller_ms_p50,
    step_prefill_time_share,
)

READERS = {"batch.lanes_prefilling_share": lanes_prefilling_share,
           "batch.lanes_waiting_share": lanes_waiting_share,
           "batch.step_prefill_time_share": step_prefill_time_share,
           "batch.step_caller_ms_p50": step_caller_ms_p50}
WINDOW = (100.0, 110.0)
LANES = 8


def _span(name, start_s, end_s, parent=None, **attrs):
    return Span(name=name, start_s=start_s, end_s=end_s, thread_id=1,
                depth=0, attrs=attrs, parent=parent)


def _step(at, length, caller_s, *, batch, prefilling=0, waiting=0,
          finishing=0, carried=(0, 0, 0), submit_s=0.0, account=True):
    """One ``step()`` that begins at ``at`` and whose tick takes
    ``length``, as the program records it: the snapshot, the tick with its
    dispatch inside, the metrics block; before it ``submit_s`` of
    ``serving.submit`` inside the ``caller_s`` since the step before.
    ``account=False``: the spans of a program without the account."""
    lanes = dict(lanes_finishing=finishing, lanes_prefilling=prefilling,
                 lanes_waiting=waiting,
                 lanes_unasked=LANES - batch - finishing - prefilling
                 - waiting)
    if waiting:
        lanes["waiting_on"] = "slot"
    admitted, chunked, tower = carried
    said = dict(admitted=admitted, chunked=chunked, tower=tower,
                decoded=batch, prefill_rows=64 * (admitted + chunked))
    spans = []
    if submit_s:
        spans.append(_span("serving.submit", at - caller_s / 2,
                           at - caller_s / 2 + submit_s, request=1,
                           prompt_len=9))
    tick = at + 0.001
    spans += [
        _span("serving.snapshot", at, tick),
        _span("serving.decode", tick, tick + 0.0005, "serving.tick",
              batch=batch, empty_lanes=LANES - batch,
              **(lanes if account else {})),
        _span("serving.snapshot", tick + length - 0.0005, tick + length,
              "serving.tick"),
        _span("serving.tick", tick, tick + length, tick=0,
              **(said if account else {}))]
    if account:
        spans.append(_span("serving.observe", tick + length,
                           tick + length + 0.0001))
    return spans, tick + length + 0.0001


def _run(account=True, tiny=False, traced=None):
    """Five steps in the window: three plain ones, a chunk beside seven
    lanes with a request queued behind it, an admission; a sixth begins
    after the window; 2, 4, 2, 6 and 3 ms of caller before each, 1 ms of
    the 4 a ``serving.submit``."""
    plan = [
        dict(length=0.010, caller_s=0.002, batch=8),
        dict(length=0.010, caller_s=0.004, batch=6, finishing=2,
             submit_s=0.001),
        dict(length=0.050, caller_s=0.002, batch=6, prefilling=1, waiting=1,
             carried=(0, 1, 0)),
        dict(length=0.030, caller_s=0.006, batch=7, carried=(1, 0, 1)),
        dict(length=0.010, caller_s=0.003, batch=8),
    ]
    spans, at = [], WINDOW[0] + 1.0
    for step in plan:
        made, end = _step(at + step["caller_s"], account=account, **step)
        spans += made
        at = end
    late, _ = _step(WINDOW[1] + 1.0, 0.010, 0.0, batch=1, account=account)
    return harness.Run(
        cell=types.SimpleNamespace(tiny=tiny), device={}, setup_s=99.0,
        window=WINDOW, attempted=0, failed=0, correct=True, checks={},
        counters={}, spans=sorted(spans + late, key=lambda s: s.end_s),
        samples={"lanes": LANES}, traced=traced)


def test_the_readers_read_the_steps_own_account():
    run = _run()
    assert lane_occupancy.read(run) == pytest.approx(35 / 5 / LANES)
    assert lanes_prefilling_share.read(run) == pytest.approx(1 / 5 / LANES)
    assert lanes_waiting_share.read(run) == pytest.approx(1 / 5 / LANES)
    # the three shares leave the two finishing lanes and the unasked one of 1
    assert (lane_occupancy.read(run) + lanes_prefilling_share.read(run)
            + lanes_waiting_share.read(run)) == pytest.approx(1 - 3 / 5 / LANES)
    # the chunk's step and the admission's, of a window of ten seconds
    assert step_prefill_time_share.read(run) == pytest.approx(0.08 / 10.0)
    # between five steps lie four stretches of the caller: 4 - 1 (the
    # submit is the engine's), 2, 6 and 3 ms
    assert step_caller_ms_p50.read(run) == pytest.approx(3.0, abs=1e-6)


def test_the_caller_is_not_read_across_the_profilers_opening():
    """The stretch in which the profiler opened is the tracer's, and steps
    inside the traced stretch are left out."""
    run = _run()
    third = [s for s in run.spans if s.name == "serving.observe"][2]
    # opened right after the third step's end: its 6 ms to the fourth step
    # drop out, and so do the fourth step and the 3 ms behind it
    fourth = [s for s in run.spans if s.name == "serving.observe"][3]
    traced = (third.end_s + 0.001, fourth.end_s + 0.0005)
    run = _run(traced=traced)
    assert step_caller_ms_p50.read(run) == pytest.approx(2.5, abs=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_the_parents_spans_read_nothing(name):
    """A program whose spans lack the account (PR 67's): the metric is
    left out, not 0, and nothing raises."""
    assert READERS[name].read(_run(account=False)) is None


@pytest.mark.parametrize("name", READERS)
def test_a_rehearsal_reads_nothing(name):
    """A CPU's steps are no device metric (and no cell's ``TINY_REPORTS``
    lists these)."""
    assert READERS[name].read(_run(tiny=True)) is None


def test_the_entries_list_the_closed_loop_cells_in_their_order():
    bench = harness.load_json("BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    new = [by_name[name] for name in READERS]
    cells = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")["workloads"]
    for m in new:
        assert m["workloads"] == cells and m["moves"] == "serve_tokens_per_s"
        assert (m["source"], m["better"]) == ("program_span", "lower")
        assert harness.by_name("layer_metrics", m["name"]) is READERS[m["name"]]
    assert [m["unit"] for m in new] == ["share", "share", "share", "ms"]
    assert [m["layer"] for m in new] == ["scheduler and cache"] * 3 + [
        "serving engine"]
    # no twin for the open loop: its free lanes are unasked by design
    assert not [n for n in by_name if n.startswith("chat.")
                and n.split(".")[1] in {r.split(".")[1] for r in READERS}]
