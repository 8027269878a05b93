"""On-chip benchmark of the TMU serving stack (see ``BENCHMARK.json``)."""
