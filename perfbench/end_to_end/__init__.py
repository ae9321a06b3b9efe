"""End-to-end metric readers, one module per metric of BENCHMARK.json's
``end_to_end``, each ``read(run) -> float | None``."""
