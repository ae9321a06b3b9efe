"""Drivers: one module per way of loading the system, found by the
``driver`` key of a traffic file. Each has ``run(cell, seed, seconds,
trace, t_process) -> harness.Run``."""
