"""Trainer: model FLOP/s utilization. Tokens per second over the steps the
profiler did not disturb, times the benchmark's model FLOPs per token
(recomputation excluded), over chips x the bf16 peak."""
from perfbench import flops


def read(run):
    steps = run.samples.get("step_end_s")
    if not steps or run.peaks is None:
        return None
    edges = [run.window[0]] + list(steps)
    pairs = list(zip(edges[:-1], edges[1:]))
    if run.traced:
        a, b = run.traced
        pairs = [(s, e) for s, e in pairs if e < a or s > b]
    seconds = sum(e - s for s, e in pairs)
    if seconds <= 0:
        return None
    job = run.cell.traffic
    per_token = flops.train_flops_per_token(run.cell.config["model"],
                                            job["seq_len"])
    tokens_per_s = len(pairs) * run.samples["tokens_per_step"] / seconds
    return tokens_per_s * per_token / (run.cell.chips
                                       * run.peaks["bf16_flops"])
