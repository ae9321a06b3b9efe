"""Serving engine: median length of a decode-only tick, from the
program's ``serving.tick`` spans that hold no admission (the span runs to
the end of ``step()``, so it covers the blocking token fetch; the
``serving.decode`` span inside it ends when the call is dispatched)."""
import bisect

from perfbench import harness


def read(run):
    ticks = run.spans_named("serving.tick", untraced_only=True)
    admits = sorted(s.start_s for s in run.spans_named("serving.admit"))
    plain = []
    for t in ticks:
        i = bisect.bisect_left(admits, t.start_s)
        if (i == len(admits) or admits[i] > t.end_s) and t.end_s <= run.window[1]:
            plain.append(t.duration_s * 1e3)
    return harness.percentile(plain, 50) if plain else None
