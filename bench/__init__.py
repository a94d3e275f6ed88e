"""Chip benchmark: see BENCHMARK.json and PERF.md."""
