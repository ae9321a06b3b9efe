"""Trainer input: the share of the window the loop spent waiting for the
next batch (sum of the program's ``train.data`` spans over the window)."""


def read(run):
    spans = run.spans_named("train.data")
    if not spans:
        return None
    start, end = run.window
    return sum(s.duration_s for s in spans if s.end_s <= end) / (end - start)
