"""Plain references the benchmark holds the system to."""
