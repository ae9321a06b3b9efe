"""Scheduler and cache: bytes of the pages in use, both classes, over what
one class of page would hold for the same lanes (every layer keeping every
token, as the full layers' pages do), from the pool's counters by class
after the window (``flops_swa.pool_bytes_share``)."""
from perfbench import flops_swa


def read(run):
    full = run.counters.get("pages_in_use_full")
    window = run.counters.get("pages_in_use_window")
    if not full or window is None:
        return None
    return flops_swa.pool_bytes_share(full, window, run.cell.config["model"])
