"""On-chip benchmark of MicroEP training (see BENCHMARK.json and PERF.md)."""
