"""``chat.admit_gap_share``: the share of the client's gaps between whose
two token stamps another request's whole ``serving.admit`` span lies. It
says where a percentile of the gaps stands against their two modes (a plain
tick, or a tick that also held an admission): PERF.md section 2 reads it to
say when an open-loop cell's rate is due anew."""

import pytest

from fleetx_tpu.obs.tracing import Span
from perfbench import harness
from perfbench.layer_metrics import admit_gap_share


def _admit(start_s, end_s, request=7):
    return Span(name="serving.admit", start_s=start_s, end_s=end_s,
                thread_id=1, depth=0, attrs={"request": request})


def _run(gaps, spans, window=(10.0, 50.0), traced=None):
    return harness.Run(
        cell=None, device={}, setup_s=0.0, window=window, attempted=0,
        failed=0, correct=True, checks={}, counters={}, spans=list(spans),
        samples={"gaps": [(end, ms) for end, ms in gaps]}, traced=traced)


# one gap of 60 ms that ends at 20.000 s: it began at 19.940 s
GAP = (20.0, 60.0)
CASES = {
    "span inside the gap": ((19.950, 19.990), 1.0),
    "span wholly before the gap": ((19.900, 19.935), 0.0),
    "span wholly after the gap": ((20.001, 20.030), 0.0),
    # the request's own admission: begun before its first token's stamp
    "span begun before the gap": ((19.930, 19.950), 0.0),
    "span ending after the gap": ((19.990, 20.010), 0.0),
}


@pytest.mark.parametrize("case", CASES)
def test_a_gap_counts_only_with_a_whole_admission_inside_it(case):
    (start, end), want = CASES[case]
    run = _run([GAP, (21.0, 30.0)], [_admit(start, end)])
    assert admit_gap_share.read(run) == pytest.approx(want / 2)


def test_gaps_outside_the_window_or_in_the_traced_stretch_are_left_out():
    spans = [_admit(9.960, 9.990), _admit(19.950, 19.990),
             _admit(30.950, 30.990)]
    gaps = [(10.02, 80.0),   # began before the window opened: left out
            GAP, (21.0, 30.0), (22.0, 30.0), (31.0, 60.0)]
    assert admit_gap_share.read(_run(gaps, spans)) == pytest.approx(2 / 4)
    # the profiler disturbed the run from 30 s on: the last gap goes too
    assert admit_gap_share.read(_run(gaps, spans, traced=(30.0, 35.0))) \
        == pytest.approx(1 / 3)


def test_several_admissions_in_one_gap_count_it_once():
    spans = [_admit(19.945, 19.960, 1), _admit(19.961, 19.980, 2)]
    assert admit_gap_share.read(_run([GAP], spans)) == 1.0


@pytest.mark.parametrize("gaps,spans", [
    ([GAP], []),                          # a program without the spans
    ([], [_admit(19.950, 19.990)]),       # no gap was measured
    ([(10.02, 80.0)], [_admit(19.950, 19.990)]),  # none inside the window
], ids=["no spans", "no gaps", "no gap in the window"])
def test_nothing_to_read_gives_none(gaps, spans):
    assert admit_gap_share.read(_run(gaps, spans)) is None
