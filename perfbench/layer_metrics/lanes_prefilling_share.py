"""Scheduler and cache: mean share of the lanes that a prompt mid-prefill
held (or one parked on a prefill replica) at a tick's dispatch, over the
decode calls of the window: ``lanes_prefilling`` of each ``serving.decode``
span over the lanes, summed as ``lane_occupancy`` sums ``batch``. The
engine prefills one prompt at a time, so in a chunked cell it cannot pass
1 / lanes. None from a program whose ticks do not say where their lanes
stood, and in a rehearsal (a CPU's steps: no device metric)."""


def lanes_share(run, field):
    """Mean of ``field`` of the window's ``serving.decode`` spans over the
    lanes; None where no span carries it, and in a rehearsal."""
    if run.cell.tiny:
        return None
    spans = [s for s in run.spans_named("serving.decode")
             if s.end_s <= run.window[1] and field in s.attrs]
    if not spans:
        return None
    return (sum(s.attrs[field] for s in spans) / len(spans)
            / run.samples["lanes"])


def read(run):
    return lanes_share(run, "lanes_prefilling")
