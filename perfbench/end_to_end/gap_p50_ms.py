"""50th percentile over all gaps between consecutive ``on_token`` calls of
one request, for the gaps that ended inside the window."""
from perfbench import harness


def read(run):
    gaps = [ms for _, ms in run.samples.get("gaps", ())]
    return harness.percentile(gaps, 50) if gaps else None
