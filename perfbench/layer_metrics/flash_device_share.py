"""Kernels (flash attention): the share of device busy time in
instructions whose name holds ``fleetx_flash_``."""


def read(run):
    if not run.trace or not run.trace["family_calls"].get("flash"):
        return None
    return run.trace["family_s"]["flash"] / run.trace["busy_s"]
