"""Kernels (paged decode attention): the share of device busy time in
instructions whose name holds ``fleetx_decode``."""


def read(run):
    if not run.trace or not run.trace["family_calls"].get("decode"):
        return None
    return run.trace["family_s"]["decode"] / run.trace["busy_s"]
