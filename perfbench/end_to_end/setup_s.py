"""Process start to the first measured step or request: import, weights on
the device from the seed, compile or cache load, warm-up, reference check."""


def read(run):
    return run.setup_s
