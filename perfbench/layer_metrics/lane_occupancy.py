"""Scheduler and cache: mean share of the lanes that decoded, over the
decode calls of the window (the ``batch`` of each ``serving.decode`` span
over the lanes)."""


def read(run):
    spans = [s for s in run.spans_named("serving.decode")
             if s.end_s <= run.window[1]]
    if not spans:
        return None
    return (sum(s.attrs["batch"] for s in spans) / len(spans)
            / run.samples["lanes"])
