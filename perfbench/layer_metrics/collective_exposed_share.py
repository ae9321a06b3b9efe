"""Sharding: time in collective instructions during which the core runs
nothing else (their self time on the instruction line, ``-done`` waits
included), over the traced window, on the worst device."""


def read(run):
    if not run.trace or run.cell.chips < 2:
        return None
    return run.trace["collective_exposed_s"] / run.trace["window_s"]
