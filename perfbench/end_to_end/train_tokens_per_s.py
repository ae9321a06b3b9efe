"""Tokens consumed by the optimizer steps completed inside the window, over
the window (whole job, all chips). The window holds whole steps: it runs
from the stamp that ended warm-up to the last step's stamp."""


def read(run):
    steps = run.samples.get("step_end_s")
    if not steps:
        return None
    return (len(steps) * run.samples["tokens_per_step"]
            / (steps[-1] - run.window[0]))
