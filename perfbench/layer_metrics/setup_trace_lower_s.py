"""Start-up: seconds jax spent tracing programs and lowering them to
modules before the measured window (the program's ``jit.trace`` and
``jit.lower`` spans, each the outermost such section on its thread, so
their seconds add up): paid for every program on a persistent-cache hit
as on a miss, since the cache's key is computed from the lowered module."""

from perfbench.layer_metrics.setup_programs import before_window


def read(run):
    spans = before_window(run, "jit.trace", "jit.lower")
    if spans is None:
        return None
    return sum(s.duration_s for s in spans)
