"""Start-up: seconds the backend spent compiling programs before the
measured window (the program's ``jit.compile`` spans with ``cache: miss``,
or ``off`` where jax keeps no entry for the program); 0 in a run that found
every program in the persistent cache."""

from perfbench.layer_metrics.setup_programs import before_window


def read(run):
    compiles = before_window(run, "jit.compile")
    if compiles is None:
        return None
    return sum(s.duration_s for s in compiles
               if s.attrs["cache"] != "hit")
