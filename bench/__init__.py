"""Chip benchmark of the served delivery path (see BENCHMARK.json and PERF.md)."""
