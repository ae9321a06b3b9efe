"""Scheduler and cache: share of the window spent in steps that carried
prefill work: the seconds of the window's ``serving.tick`` spans whose
``admitted + chunked + tower`` is above 0 (what the step ran, said as the
span closes), over the window's length. Such a step's tick waits behind its
chunk, admission or tower program, with every decoding lane. None from a
program whose ticks do not say what they carried, and in a rehearsal."""


def read(run):
    if run.cell.tiny:
        return None
    ticks = [s for s in run.spans_named("serving.tick")
             if s.end_s <= run.window[1] and "admitted" in s.attrs]
    if not ticks:
        return None
    held = sum(s.duration_s for s in ticks
               if s.attrs["admitted"] + s.attrs["chunked"] + s.attrs["tower"])
    return held / (run.window[1] - run.window[0])
