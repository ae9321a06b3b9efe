"""What is left of the pre-chip runner: the model-FLOPs arithmetic that
``tests/perfbench/test_perfbench_flops.py`` holds ``perfbench/flops.py`` to.
Measuring is ``perfbench/run.py`` (``BENCHMARK.json``); importing this file
starts nothing and reads no environment."""


def model_flops_per_token(n_params: int, num_layers: int, seq: int, hidden: int) -> float:
    """MODEL-FLOPs accounting: what the math requires, not what the chip
    executes — rematerialised forward passes are excluded, so MFU here is
    comparable across remat settings and across rounds. 6 FLOPs per
    parameter per token (fwd 2 + bwd 4, tied-embedding logits included via
    the shared weight) + causal attention score/value matmuls (fwd 4*s*h
    per layer per token, halved for causality, x3 for fwd+bwd)."""
    return 6.0 * n_params + num_layers * 6.0 * seq * hidden
