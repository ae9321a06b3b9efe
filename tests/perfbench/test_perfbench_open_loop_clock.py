"""The open loop times a request from when it was DUE, not from when the
generator got round to submitting it: a stall shows in the TTFT of the
requests that arrived during it, and the generator's lateness is kept."""

import time

import numpy as np

from perfbench import harness, traffic
from perfbench.drivers import serve_open_loop


class StallingEngine:
    """The engine surface ``replay`` uses; every step blocks ``step_s``
    and then gives each open request one token."""

    def __init__(self, step_s):
        self.step_s, self.open, self.next_id = step_s, {}, 0

    def submit(self, prompt, *, max_length, on_token):
        self.next_id += 1
        self.open[self.next_id] = [max_length, on_token]
        return self.next_id

    def step(self):
        time.sleep(self.step_s)
        for rid, state in list(self.open.items()):
            state[0] -= 1
            state[1](rid, 0, state[0] == 0)
            if state[0] == 0:
                del self.open[rid]


def _request(index, due_s, new_tokens):
    return traffic.Request(index, due_s, "t", np.ones(4, np.int32), new_tokens)


def test_ttft_runs_from_the_due_time_and_lateness_is_reported():
    engine = StallingEngine(step_s=0.2)
    # the second request falls due while the first step is blocking
    pending = [_request(0, 0.0, 3), _request(1, 0.05, 2)]
    clients, measured, live, (start, end) = serve_open_loop.replay(
        engine, pending, 0.0, 0.7, 2.0, harness.ProfilerWindow(False, 0.0))
    assert [r["request"].index for r in measured] == [0, 1]
    second = measured[1]
    assert abs((second["due_s"] - start) - 0.05) < 1e-6
    late = second["submit_s"] - second["due_s"]
    assert 0.1 < late < 0.3                      # submitted after the stall
    # of that the generator's own part, from the stalled step's return to
    # the submit, is next to nothing: the wait was for the engine's step
    assert 0 <= serve_open_loop.lateness_ms(second)[1] < 20
    ttft_from_due = second["stamps"][0] - second["due_s"]
    ttft_from_submit = second["stamps"][0] - second["submit_s"]
    assert ttft_from_due > 0.3 > ttft_from_submit  # the stall is not hidden
    assert len(clients.token_s) == 5 and not clients.open
    assert live and live[-1][1] == 0             # nothing cached at the end
    gaps = clients.gaps(start, end + 2.0)
    assert len(gaps) == 3 and all(150 < ms < 400 for _, ms in gaps)
    assert all(start < at <= end + 2.0 for at, _ in gaps)


def test_requests_due_in_the_ramp_are_served_but_not_measured():
    engine = StallingEngine(step_s=0.01)
    pending = [_request(0, 0.02, 2), _request(1, 0.25, 2)]
    clients, measured, _, (start, end) = serve_open_loop.replay(
        engine, pending, 0.2, 0.3, 1.0, harness.ProfilerWindow(False, 0.0))
    assert [r["request"].index for r in measured] == [1]
    assert len(clients.records) == 2 and len(clients.finished) == 2
    assert abs((end - start) - 0.3) < 1e-9


class StallingProfiler(harness.ProfilerWindow):
    """Opens 0.1 s into the window and closes 0.1 s later, holding the
    thread ``stall_s`` each time, as the real one does for seconds."""

    def __init__(self, stall_s):
        super().__init__(True, 0.1)
        self.stall_s = stall_s

    def arm(self, window_start, seconds):
        self.start_at = window_start + 0.1

    def poll(self, now):
        if self.opened is None and now >= self.start_at:
            time.sleep(self.stall_s)
            self.opening = now
            self.opened = time.perf_counter()
            return True
        if (self.opened is not None and self.closed is None
                and now >= self.opened + self.length_s):
            self.closed = now
            time.sleep(self.stall_s)
            self.stopped = time.perf_counter()
            return True
        return False


def test_the_schedule_stands_still_while_the_profiler_holds_the_thread():
    """Ten requests fall due 0.05 s apart from 0.15 s on; the profiler
    holds the thread twice for 0.4 s. None falls due meanwhile: each is
    submitted when it falls due on a clock that stood still, and only one
    submitted in the very pass that then polls the profiler waits it out
    (one a stall at most; without the rule eight a stall would)."""
    engine = StallingEngine(step_s=0.005)
    pending = [_request(i, 0.15 + 0.05 * i, 1) for i in range(10)]
    profiler = StallingProfiler(0.4)
    clients, measured, _, (start, end) = serve_open_loop.replay(
        engine, pending, 0.0, 1.0, 2.0, profiler)
    assert profiler.closed is not None and len(measured) == 10
    assert 1.75 < end - start < 2.3     # the window and the two stalls
    late = [r["submit_s"] - r["due_s"] for r in measured]
    ttft = [r["stamps"][0] - r["due_s"] for r in measured]
    assert max(late) < 0.25, late             # a stall is 0.4
    assert sum(t > 0.1 for t in ttft) <= 2, ttft
    due = [r["due_s"] for r in measured]
    assert max(b - a for a, b in zip(due, due[1:])) > 0.4  # across a stall


def test_client_side_readers_leave_out_what_the_profiler_disturbed():
    """Starting the profiler stalls the host for seconds, which an open
    loop feels as a queue: in a traced run the client-side per-layer
    readers take only what was due before it."""
    from perfbench.layer_metrics import gap_p99_ms, gen_late_p99_ms, ttft_p90_ms

    requests = [{"id": i, "due_s": float(i), "late_ms": 1.0, "ttft_ms": 100.0}
                for i in range(10)]
    requests += [{"id": 10 + i, "due_s": 10.0 + i, "late_ms": 3000.0,
                  "ttft_ms": 4000.0} for i in range(10)]
    gaps = [(float(t), 50.0) for t in range(10)] + [(12.0, 3000.0)]

    def run(traced):
        return harness.Run(
            cell=None, device={}, setup_s=0.0, window=(0.0, 20.0), attempted=20,
            failed=0, correct=True, checks={}, spans=[], counters={},
            samples={"requests": requests, "gaps": gaps}, traced=traced)

    assert ttft_p90_ms.read(run(None)) == 4000.0
    assert ttft_p90_ms.read(run((10.0, 14.0))) == 100.0
    assert gen_late_p99_ms.read(run((10.0, 14.0))) == 1.0
    assert gap_p99_ms.read(run((10.0, 14.0))) == 50.0
    assert gap_p99_ms.read(run(None)) > 50.0
