"""Per-layer metric readers, one module per metric of BENCHMARK.json's
``per_layer``, each ``read(run) -> float | None`` from the run's spans,
counters, samples and reduced device trace. A reader that finds nothing to
read returns None and the harness leaves its metric out."""
