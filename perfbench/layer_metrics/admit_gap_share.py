"""Scheduler and cache: of the gaps between consecutive ``on_token`` calls
of one request, the share between whose two stamps a whole
``serving.admit`` span lies. A request's own admission begins before its
first token, so a span wholly inside a gap is another request's: the gap
held that request's batch-1 prefill and the host's admission work, which is
the upper of the two modes the gaps of an open loop fall into. Over the
gaps that began inside the window and ended before the profiler disturbed
the run. It says where a percentile of the gaps lies against the modes
(PERF.md section 2 has the rule that reads it); None without admit spans."""
import bisect


def read(run):
    admits = sorted((s.start_s, s.end_s)
                    for s in run.spans_named("serving.admit"))
    if not admits:
        return None
    starts = [a for a, _ in admits]
    held = total = 0
    for end, ms in run.samples.get("gaps", ()):
        begin = end - ms / 1e3
        if begin < run.window[0] or not run.before_trace(end):
            continue
        total += 1
        first = bisect.bisect_left(starts, begin)
        held += any(e <= end for _, e in
                    admits[first:bisect.bisect_right(starts, end)])
    return held / total if total else None
