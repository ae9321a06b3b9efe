"""Host-side span tracing with an XLA-profiler bridge.

``with span("serving.tick"):`` records one nested host span into a
bounded ring buffer (``FLEETX_OBS_SPANS`` spans, default 65,536, oldest
dropped) AND — the bridge — enters a ``jax.profiler.TraceAnnotation`` of
the same name, so when a profiling window is open
(``jax.profiler.start_trace`` / ``Profiler.enable`` in the Trainer) the
host phases show up in the profiler's trace (its host events agree with
the ring to microseconds; the device's lines run 0 to 0.9 ms ahead of
them on a v5e, a constant of the profiling session:
docs/OBSERVABILITY.md): the benchmark books every idle gap of the device
to the innermost span open at that time (``perfbench/trace_reduce.py``),
so the bridge has no off switch. Outside a profiling window
TraceAnnotation is a near-free TraceMe no-op, so spans stay on
permanently (about 5 us each). Inside one, the annotation also takes the
span's identity attrs (``program``, ``reads``, ``request``, ``tick``) as
arguments, which the trace keeps as stats of the event under its bare
name: they say which ring span a host event is.

The ring buffer is exported as Chrome-trace JSON
(:meth:`SpanRecorder.chrome_trace`, ``chrome://tracing`` / Perfetto
loadable) by ``tools/obs_dump.py`` or ``GET /trace`` on the exposition
server — the always-on, no-profiler view of where host time went; its
``dropped`` field says how many spans the ring has already pushed out.

Span taxonomy (docs/OBSERVABILITY.md has the table): dotted snake_case
names, ``<subsystem>.<phase>``. A LEAF span names one activity of the
host: ``serving.decode`` and ``serving.prefill`` end when the device call
is DISPATCHED, ``serving.fetch`` and ``serving.first_token`` are the
blocking waits for its result, ``train.loss_fetch`` the trainer's.
Nesting is tracked per thread; every span records its ``parent`` (the
name of the span open on its thread when it began), spans of one request
share the ``request`` attr, and attrs ride into the Chrome trace as
``args``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from fleetx_tpu.obs._util import env_int, json_safe as _json_safe

__all__ = ["Span", "SpanRecorder", "get_recorder", "span"]

# The attrs that say WHICH program a span dispatched (``program``) or read
# (``reads``) and whose work it was: they alone ride into the profiler's
# trace, as arguments of the span's TraceAnnotation, so a host event there
# can be matched to its ring span exactly (docs/OBSERVABILITY.md).
_IDENTITY = frozenset(("program", "reads", "request", "tick"))
_profiling = TraceAnnotation.is_enabled


@dataclasses.dataclass
class Span:
    """One completed host span (times from ``time.perf_counter``)."""

    name: str
    start_s: float
    end_s: float
    thread_id: int
    depth: int
    attrs: Dict
    parent: Optional[str] = None  # span open on this thread when it began

    @property
    def duration_s(self) -> float:
        """Wall-clock length of the span."""
        return self.end_s - self.start_s


class SpanRecorder:
    """Bounded ring buffer of completed spans + Chrome-trace export.

    Capacity 0 disables recording entirely (the TraceAnnotation bridge
    in :func:`span` still runs — profiler alignment costs nothing)."""

    def __init__(self, capacity: Optional[int] = None):
        cap = (env_int("FLEETX_OBS_SPANS", 65536, minimum=0)
               if capacity is None else capacity)
        self.capacity = max(cap, 0)
        self._lock = threading.Lock()
        self._spans: collections.deque = collections.deque(
            maxlen=self.capacity or 1)
        self._local = threading.local()
        self.dropped = 0  # spans pushed out of the ring (or cap-0 culled)

    def record(self, s: Span) -> None:
        """Append one completed span (oldest evicted at capacity)."""
        if self.capacity == 0:
            self.dropped += 1
            return
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(s)

    def spans(self) -> List[Span]:
        """Current ring contents, oldest first."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        """Empty the ring (tests / between benchmark passes)."""
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    # ---------------------------------------------------- nesting helpers
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    # -------------------------------------------------------------- export
    def chrome_trace(self) -> Dict:
        """Chrome-trace JSON dict (``traceEvents`` of complete ``X``
        events, microsecond timestamps) — load in chrome://tracing or
        Perfetto; ``tools/obs_dump.py`` writes it to disk. ``dropped``
        counts the spans the ring pushed out before this export: above
        0 the trace is truncated at its old end."""
        pid = os.getpid()
        events = [{
            "ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": "fleetx_obs host spans"},
        }]
        for s in self.spans():
            events.append({
                "ph": "X",
                "pid": pid,
                "tid": s.thread_id,
                "name": s.name,
                "ts": s.start_s * 1e6,
                "dur": max(s.duration_s, 0.0) * 1e6,
                "args": {"parent": s.parent,
                         **{k: _json_safe(v) for k, v in s.attrs.items()}},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "dropped": self.dropped}


_RECORDER = SpanRecorder()


def get_recorder() -> SpanRecorder:
    """The process-global span recorder."""
    return _RECORDER


@contextlib.contextmanager
def span(name: str, recorder: Optional[SpanRecorder] = None, **attrs):
    """Record one nested host span named ``name`` (module docstring);
    ``attrs`` become Chrome-trace args, and the ``with`` target is that
    dict, for an attr known only inside the span (``as at: at["shared"] =
    n``). Re-entrant and thread-safe; exceptions propagate (the span
    still closes and records)."""
    rec = recorder or _RECORDER
    stack = rec._stack()
    parent = stack[-1] if stack else None
    # the annotation's NAME stays the bare span name (the benchmark books
    # idle gaps by it); the identity attrs known when the span opens become
    # its arguments, and only inside a profiling window: a closed profiler
    # costs this one check
    with TraceAnnotation(name) as annotation:
        if _profiling():
            annotation.set_metadata(**{k: v for k, v in attrs.items()
                                       if k in _IDENTITY})
        start = time.perf_counter()
        stack.append(name)
        try:
            yield attrs
        finally:
            stack.pop()
            end = time.perf_counter()
            rec.record(Span(
                name=name, start_s=start, end_s=end,
                thread_id=threading.get_ident(), depth=len(stack),
                attrs=attrs, parent=parent,
            ))
