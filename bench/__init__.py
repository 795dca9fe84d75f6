"""Chip benchmark of the serving engine: see BENCHMARK.json and PERF.md."""
