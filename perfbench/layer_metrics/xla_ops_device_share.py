"""Model: the share of device busy time spent in compiler-generated
instructions, outside the named kernel families and the collectives."""


def read(run):
    if not run.trace or not run.trace["busy_s"]:
        return None
    return run.trace["xla_s"] / run.trace["busy_s"]
