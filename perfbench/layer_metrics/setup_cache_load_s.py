"""Start-up: seconds spent getting programs from the persistent compile
cache before the measured window (the program's ``jit.compile`` spans
with ``cache: hit``); 0 in a cold run."""

from perfbench.layer_metrics.setup_programs import before_window


def read(run):
    compiles = before_window(run, "jit.compile")
    if compiles is None:
        return None
    return sum(s.duration_s for s in compiles
               if s.attrs["cache"] == "hit")
