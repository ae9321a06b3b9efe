"""One timeline for host and chip: what the ``*_queue``, ``*_return``,
``launch_gap`` and ``admit_idle`` readers share.

The program numbers every prefill, lane install, decode tick and verify
call it dispatches (``ServingEngine._next_program``). The DISPATCH span
(``serving.prefill``, ``serving.install``, ``serving.decode``,
``serving.verify``) records the number as attr ``program``, the WAIT span
that reads the program's result (``serving.first_token``,
``serving.fetch``) as attr ``reads``, and inside a profiling window the
same attrs ride into the trace as arguments of the span's host event: on a
TPU v5e the ``.xplane.pb`` keeps them as STATS of the event, whose name
stays the bare span name (looked at by hand, PR 35). The device's ``XLA
Modules`` line holds one event per executed program, in the order of the
dispatches: one device runs its programs in the order it was given them.

:func:`join` puts the three together, once a run, and CHECKS what it
assumes:

- the offset between ``time.perf_counter`` (ring spans) and the trace's
  clock, from the host events whose ``program`` / ``reads`` / ``tick``
  names their ring span (the median of the differences of their starts;
  ``clock_residual_us`` is the largest deviation from it);
- for every event of the first device's program line that is one of the
  four numbered kinds, the dispatch span with its ``program`` and the
  wait span whose ``reads`` equals it. The program names in order must
  agree with the spans' kinds (:data:`KINDS`); no program may start
  before its dispatch span began; no wait span may end before its
  program ended. Each breach is one entry of ``violations``; the least
  slack seen in the two inequalities (``slack_dispatch_us``,
  ``slack_wait_us``) bounds the skew between the host's and the device's
  clock in the trace from either side.

A reader built on it returns None, and the run's log says why, when the
join found any violation or gives the reader fewer than :data:`MIN_ROWS`
samples. A program without numbered spans (a build before PR 35) gives no
timeline and no reading.

``python3 perfbench/layer_metrics/_timeline.py record <out.json> <max_ms>
<skip_ms> -- <perfbench/run.py's arguments with --trace 1>`` runs the cell
in this process and writes a cut of its program line and host events with
the ring spans around it, which is how
``perfbench/fixtures/timeline/serve_docs_batch_v5e.json`` was recorded.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import json
import os
import sys
from typing import Optional

if __name__ == "__main__":  # run as a script: make ``perfbench`` importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from perfbench import harness, trace_reduce  # noqa: E402

# dispatch span -> the program it puts on the device's program line
KINDS = {"serving.decode": "jit__decode_fn", "serving.prefill": "jit_prefill",
         "serving.install": "jit__admit_fn", "serving.verify": "jit__verify_fn"}
WAITS = ("serving.fetch", "serving.first_token")
# the span attrs that ride into the trace and name one ring span
IDENTITY = ("program", "reads", "tick")
MIN_ROWS = 5
# how far before a program's start its dispatch span may have begun and
# still be tried as the first program's (a long chunk ahead of it)
_ANCHOR_S = 2.0
# the most the device's clock may run ahead of the host's in a trace (on a
# v5e programs appear to start 0 to 0.9 ms BEFORE their dispatch span
# began, by the profiling session: my chip runs, PR 35): the join moves
# the device's clock back by what it sees, and refuses more than this as
# no skew but a wrong join
MAX_SHIFT_S = 2e-3


@dataclasses.dataclass
class Row:
    """One executed program joined to its spans; ``start_s`` and ``end_s``
    are the device's, moved onto the ring's clock."""

    program: int
    start_s: float
    end_s: float
    dispatch: object            # the dispatch span
    wait: Optional[object]      # the wait span that read it, if any did


@dataclasses.dataclass
class Timeline:
    rows: list                  # Row, in device order
    programs: list              # (start_s, end_s, Row or None): every
    #                             program event, the unnumbered kinds too
    spans: list                 # the run's ring spans
    offset_s: float             # trace clock less perf_counter
    clock_residual_us: float
    pairs: int                  # host events matched to ring spans
    device_shift_us: float      # the device's clock was moved back by this
    slack_wait_us: Optional[float]
    violations: list

    def summary(self) -> str:
        slack = ("-" if self.slack_wait_us is None
                 else f"{self.slack_wait_us:.1f}")
        return (f"{len(self.rows)} programs joined of {len(self.programs)} "
                f"on the program line, {len(self.violations)} violations; "
                f"clock offset from {self.pairs} span pairs, residual "
                f"{self.clock_residual_us:.1f} us; device clock moved back "
                f"{self.device_shift_us:.1f} us (a program began that long "
                f"before its dispatch span), least wait slack after it "
                f"{slack} us")

    @functools.cached_property
    def _by_name(self) -> dict:
        out = {}
        for s in self.spans:
            out.setdefault(s.name, []).append(s)
        return out

    @functools.cached_property
    def _openings(self) -> list:
        """Sorted starts of the spans an admission's stretch opens with."""
        return sorted(s.start_s for name in ("serving.admit",
                                             "serving.prefill_chunk")
                      for s in self._by_name.get(name, ()))

    # ------------------------------------------------- what readers take

    def launch_gaps_ms(self) -> list:
        """Start of a program less the end of the one before it on the
        device, over the consecutive pairs whose second was already
        dispatched (its dispatch span had ENDED) when the first ended."""
        out = []
        for (_, end, _), (start, _, row) in zip(self.programs,
                                                self.programs[1:]):
            if row is not None and row.dispatch.end_s <= end:
                out.append((start - end) * 1e3)
        return out

    def admissions(self) -> list:
        """One dict per admission whose prefill ran inside the trace and
        whose first token was read: ``queue_ms`` (end of its
        ``serving.prefill`` span to the program's start), ``program_ms``,
        ``return_ms`` (the program's end to the end of the
        ``serving.first_token`` span that ``reads`` it), ``prefill_end_s``
        and ``first_token_end_s`` (ring clock), and ``idle_ms``: device
        time with no program running between the start of its
        ``serving.admit`` span (of its final ``serving.prefill_chunk`` in a
        chunked admission) and the start of the first decode program
        dispatched after its lane install, or the start of the next
        admission's span where that comes first (a ``step()`` may admit
        several before its one tick); None where that decode lies beyond
        the trace."""
        out = []
        for row in self.rows:
            wait = row.wait
            if wait is None or wait.name != "serving.first_token":
                continue
            out.append({
                "request": wait.attrs.get("request"),
                "queue_ms": (row.start_s - row.dispatch.end_s) * 1e3,
                "program_ms": (row.end_s - row.start_s) * 1e3,
                "return_ms": (wait.end_s - row.end_s) * 1e3,
                "prefill_end_s": row.dispatch.end_s,
                "first_token_end_s": wait.end_s,
                "idle_ms": self._admit_idle_ms(row)})
        return out

    def _admit_idle_ms(self, row: Row) -> Optional[float]:
        dispatch = row.dispatch
        outer = ("serving.prefill_chunk"
                 if dispatch.parent == "serving.prefill_chunk"
                 else "serving.admit")
        request = dispatch.attrs.get("request")
        opened = [s for s in self._by_name.get(outer, ())
                  if s.attrs.get("request") == request
                  and s.start_s <= dispatch.start_s <= s.end_s]
        install = next((r for r in self.rows if r.program > row.program
                        and r.dispatch.name == "serving.install"
                        and r.dispatch.attrs.get("request") == request), None)
        decode = install and next(
            (r for r in self.rows if r.program > install.program
             and r.dispatch.name == "serving.decode"), None)
        if not opened or not decode or opened[-1].start_s < self.programs[0][0]:
            return None
        start, end = opened[-1].start_s, decode.start_s
        # admissions of one step() share the decode that ends them: each
        # one's stretch ends where the next one's begins
        later = bisect.bisect_right(self._openings, start)
        end = min([end, *self._openings[later:later + 1]])
        busy = sum(max(0.0, min(e, end) - max(s, start))
                   for s, e, _ in self.programs)
        return (end - start - busy) * 1e3


# --------------------------------------------------------------- the join

def _span_keys(spans) -> dict:
    """``{(name, attr, value): span}`` for the identity attrs; a key two
    spans share is dropped (it names no one span)."""
    keys, twice = {}, set()
    for s in spans:
        for attr in IDENTITY:
            if attr in s.attrs:
                key = (s.name, attr, s.attrs[attr])
                if key in keys:
                    twice.add(key)
                keys[key] = s
    for key in twice:
        del keys[key]
    return keys


def clock_offset(host, spans):
    """``(offset_s, residual_us, pairs)``: the trace's clock less
    ``perf_counter``, from the host events ``[name, start_ns, dur_ns,
    {stat: value}]`` whose identity stats name a ring span; None without
    a pair."""
    keys = _span_keys(spans)
    diffs = []
    for name, start_ns, _, stats in host:
        span = next((keys[(name, a, stats[a])] for a in IDENTITY
                     if a in stats and (name, a, stats[a]) in keys), None)
        if span is not None:
            diffs.append(start_ns / 1e9 - span.start_s)
    if not diffs:
        return None
    offset = sorted(diffs)[len(diffs) // 2]
    return offset, max(abs(d - offset) for d in diffs) * 1e6, len(diffs)


def _breaches(numbered, dispatches, first: int, waits: dict):
    """``(violations, shift_s, slack_wait_s)`` of joining the numbered
    program events, in device order, to ``dispatches[first:]``:
    ``shift_s`` is what the device's clock must be moved back by for no
    program to start before its dispatch span began (0 where none does),
    ``slack_wait_s`` the least time a wait span ended after its program
    did, on the clock so moved."""
    out, early = [], 0.0
    pairs = list(zip(numbered, dispatches[first:]))
    for (name, start, _), span in pairs:
        if KINDS[span.name] != name:
            out.append(f"program {span.attrs['program']}: {span.name} "
                       f"dispatched {KINDS[span.name]}, the device ran {name}")
        elif span.start_s - start > early:
            early, worst = span.start_s - start, (span.attrs["program"], name)
    if early > MAX_SHIFT_S:
        out.append(f"program {worst[0]} ({worst[1]}) starts "
                   f"{early * 1e6:.0f} us before its dispatch span began: "
                   f"more than a clock's skew ({MAX_SHIFT_S * 1e6:.0f} us)")
    slack = None
    for (name, _, end), span in pairs:
        wait = waits.get(span.attrs["program"])
        if wait is None or KINDS[span.name] != name:
            continue
        left = wait.end_s - (end + early)
        slack = left if slack is None else min(slack, left)
        if left < 0:
            out.append(f"program {span.attrs['program']} ({name}) ends "
                       f"{-left * 1e6:.0f} us after the {wait.name} that "
                       f"read it")
    if len(numbered) > len(dispatches) - first:
        out.append(f"{len(numbered) - len(dispatches) + first} programs "
                   f"ran after the last numbered dispatch span")
    return out, early, slack


def join(modules, host, spans) -> Optional[Timeline]:
    """The timeline of one traced run: ``modules`` the first device's
    program line ``[name, start_ns, dur_ns]``, ``host`` the trace's host
    span events ``[name, start_ns, dur_ns, {stat: value}]``, ``spans`` the
    run's ring spans. None where no span carries a program number or no
    host event can be matched (nothing to join); otherwise every breach
    of the order rule is in ``violations``."""
    dispatches = sorted((s for s in spans if s.name in KINDS
                         and "program" in s.attrs),
                        key=lambda s: s.attrs["program"])
    clock = clock_offset(host, spans)
    if not dispatches or clock is None or not modules:
        return None
    offset, residual, pairs = clock
    violations = []
    numbers = [s.attrs["program"] for s in dispatches]
    if len(set(numbers)) != len(numbers):
        violations.append("a program number was handed out twice")
    waits = {}
    for s in spans:
        if s.name in WAITS and "reads" in s.attrs:
            if s.attrs["reads"] in waits:
                violations.append(f"program {s.attrs['reads']} was read twice")
            waits[s.attrs["reads"]] = s
    events = sorted((start / 1e9 - offset, (start + dur) / 1e9 - offset,
                     trace_reduce.module_name(name))
                    for name, start, dur in modules)
    known = set(KINDS.values())
    numbered = [(name, start, end) for start, end, name in events
                if name in known]
    if not numbered:
        return None
    # the first numbered program is one of the dispatches that began before
    # it started (on a clock that may be MAX_SHIFT_S ahead): try each, keep
    # the one the order rule holds for
    starts = [s.start_s for s in dispatches]
    low = bisect.bisect_left(starts, numbered[0][1] - _ANCHOR_S)
    high = bisect.bisect_right(starts, numbered[0][1] + MAX_SHIFT_S)
    tried = [(_breaches(numbered, dispatches, first, waits), first)
             for first in range(low, min(max(high, low + 1), len(dispatches)))]
    if not tried:
        return None
    fits = [t for t in tried if not t[0][0]]
    if len(fits) > 1:
        violations.append(f"{len(fits)} alignments of the program line to "
                          f"the dispatch spans satisfy the order rule")
    (breaches, shift, slack), first = (
        fits[-1] if fits else min(tried, key=lambda t: len(t[0][0])))
    violations += breaches
    rows, by_event = [], {}
    for (name, start, end), span in zip(numbered, dispatches[first:]):
        by_event[(start, end)] = Row(
            span.attrs["program"], start + shift, end + shift, span,
            waits.get(span.attrs["program"]))
        rows.append(by_event[(start, end)])
    programs = [(start + shift, end + shift, by_event.get((start, end)))
                for start, end, _ in events]
    return Timeline(rows, programs, list(spans), offset, residual, pairs,
                    shift * 1e6, None if slack is None else slack * 1e6,
                    violations)


# ------------------------------------------------------ the run's timeline

def load_xplane(path: str) -> dict:
    """``{"modules": [[name, start_ns, dur_ns], ...], "host": [[name,
    start_ns, dur_ns, {stat: value}], ...]}`` of an ``.xplane.pb``: the
    first device's program line and the host span events with the stats
    the profiler kept of their annotations' arguments."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    modules, host = {}, []
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.MODULES_LINE:
                    modules[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if trace_reduce.HOST_SPAN.match(e.name):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), dict(e.stats)])
    first = modules[sorted(modules)[0]] if modules else []
    return {"modules": sorted(first, key=lambda ev: ev[1]),
            "host": sorted(host, key=lambda ev: ev[1])}


def load_dump(path: str) -> dict:
    """A file written by :func:`dump`: ``modules`` and ``host`` as
    :func:`load_xplane` gives them, and ``spans`` as ring spans."""
    from fleetx_tpu.obs.tracing import Span

    with open(path) as f:
        packed = json.load(f)
    packed["spans"] = [Span(name=n, start_s=a, end_s=b, thread_id=0, depth=0,
                            attrs=attrs, parent=parent)
                       for n, a, b, parent, attrs in packed["spans"]]
    return packed


def _trace_files() -> list:
    """Where a traced run left its ``.xplane.pb`` (``_parts.traced_shares``
    looks in the same place)."""
    return glob.glob(os.path.join(harness.WORK, "trace", "plugins",
                                  "profile", "*", "*.xplane.pb"))


_JOINED: dict = {}  # (file, mtime) -> Timeline or None


def of_run(run) -> Optional[Timeline]:
    """The timeline of the trace this run wrote, joined once a file; None
    for a run that was not traced, wrote none, or whose program numbers
    nothing."""
    files = _trace_files() if run.trace else []
    if not files:
        return None
    key = (files[0], os.path.getmtime(files[0]))
    if key not in _JOINED:
        load = load_dump if files[0].endswith(".json") else load_xplane
        trace = load(files[0])
        _JOINED.clear()
        timeline = _JOINED[key] = join(trace["modules"], trace["host"],
                                       run.spans)
        harness.log("timeline: " + (
            timeline.summary() if timeline else "nothing to join (no span "
            "carries a program number, or no host event names its span)"))
        for line in (timeline.violations[:5] if timeline else ()):
            harness.log("timeline: violation: " + line)
    return _JOINED[key]


def median(run, what: str, pick) -> Optional[float]:
    """What a reader on the timeline returns: the median of ``pick(the
    run's timeline)``, None (and a line in the run's log) where the join
    found a violation or gives fewer than :data:`MIN_ROWS` samples."""
    timeline = of_run(run)
    if timeline is None:
        return None
    if timeline.violations:
        harness.log(f"{what}: no reading: the join found "
                    f"{len(timeline.violations)} violations")
        return None
    values = [v for v in pick(timeline) if v is not None]
    if len(values) < MIN_ROWS:
        harness.log(f"{what}: no reading: {len(values)} joined samples in "
                    f"the trace, {MIN_ROWS} needed")
        return None
    return harness.percentile(values, 50)


# --------------------------------------------------------- the fixtures

def dump(path: str, spans, out: str, max_ms: float, skip_ms: float) -> None:
    """Write the program line and the host events of ``path`` cut to
    ``max_ms`` from ``skip_ms`` after the first program (times in ns from
    the cut's start), with the ring ``spans`` that overlap the cut and
    half a second either side of it (``perf_counter`` seconds from the
    first kept)."""
    trace = load_xplane(path)
    clock = clock_offset(trace["host"], spans)
    start = trace["modules"][0][1] + skip_ms * 1e6
    end = start + max_ms * 1e6
    inside = lambda evs: [[ev[0], round(ev[1] - start), round(ev[2]), *ev[3:]]  # noqa: E731
                          for ev in evs if start <= ev[1] and ev[1] + ev[2] <= end]
    kept = []
    if clock is not None:
        a, b = start / 1e9 - clock[0] - 0.5, end / 1e9 - clock[0] + 0.5
        kept = [s for s in spans if s.end_s >= a and s.start_s <= b]
    zero = min((s.start_s for s in kept), default=0.0)
    packed = {"modules": inside(trace["modules"]),
              "host": inside(trace["host"]),
              "spans": [[s.name, round(s.start_s - zero, 9),
                         round(s.end_s - zero, 9), s.parent, s.attrs]
                        for s in kept]}
    with open(out, "w") as f:
        json.dump(packed, f, separators=(",", ":"))


def _record(out: str, max_ms: float, skip_ms: float, argv: list) -> None:
    import runpy

    from fleetx_tpu.obs.tracing import get_recorder

    sys.argv = [os.path.join(harness.HERE, "run.py"), *argv]
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    except SystemExit as done:
        if done.code:
            raise
    dump(_trace_files()[0], get_recorder().spans(), out, max_ms, skip_ms)


if __name__ == "__main__":
    if len(sys.argv) < 7 or sys.argv[1] != "record" or sys.argv[5] != "--":
        sys.exit(__doc__)
    _record(sys.argv[2], float(sys.argv[3]), float(sys.argv[4]), sys.argv[6:])
