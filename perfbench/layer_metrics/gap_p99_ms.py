"""Serving engine, client side: 99th percentile of the gaps between
consecutive ``on_token`` calls of one request (the ticks that held several
admissions or a long prompt), over the gaps that ended before the profiler
disturbed the run. It swings by 2-4% of itself from seed to seed, with an
outlier in one run of six (PERF.md), so it carries no bound."""
from perfbench import harness


def read(run):
    gaps = [ms for t, ms in run.samples.get("gaps", ()) if run.before_trace(t)]
    return harness.percentile(gaps, 99) if gaps else None
