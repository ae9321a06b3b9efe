"""Device: 1 - union of device-instruction intervals over the traced
steady window, on the worst device."""


def read(run):
    return run.trace["idle_share"] if run.trace else None
