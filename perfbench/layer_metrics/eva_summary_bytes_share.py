"""Scheduler and cache: bytes of the summary class's pages in use over both
classes', from the pool's counters by class after the window (a page of
either class holds rows of the same width, so pages stand for bytes)."""


def read(run):
    summary = run.counters.get("pages_in_use_summary")
    window = run.counters.get("pages_in_use_window")
    if summary is None or not window:
        return None
    return summary / (summary + window)
