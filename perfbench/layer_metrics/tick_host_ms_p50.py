"""Serving engine: median host stretch of a decode-only tick, from the end
of the previous tick's ``serving.fetch`` (its tokens are on the host: the
device has nothing to run) to the end of this tick's ``serving.decode``
(the next program is dispatched). Read from pairs of consecutive
decode-only ticks outside the traced stretch; None from a program without
``serving.fetch`` spans."""
import bisect

from perfbench import harness


def _first_inside(spans, starts, tick):
    """The first of ``spans`` (sorted, with their ``starts``) that lies
    inside ``tick``, else None."""
    i = bisect.bisect_left(starts, tick.start_s)
    return spans[i] if i < len(spans) and spans[i].end_s <= tick.end_s else None


def read(run):
    ticks = run.spans_named("serving.tick", untraced_only=True)
    leaves = {}
    for name in ("serving.admit", "serving.fetch", "serving.decode"):
        spans = run.spans_named(name)
        leaves[name] = (spans, [s.start_s for s in spans])
    host = []
    for before, tick in zip(ticks, ticks[1:]):
        if (tick.attrs.get("tick") != before.attrs.get("tick", -2) + 1
                or tick.end_s > run.window[1]
                or _first_inside(*leaves["serving.admit"], before)
                or _first_inside(*leaves["serving.admit"], tick)):
            continue
        fetch = _first_inside(*leaves["serving.fetch"], before)
        decode = _first_inside(*leaves["serving.decode"], tick)
        if fetch and decode:
            host.append((decode.end_s - fetch.end_s) * 1e3)
    return harness.percentile(host, 50) if host else None
