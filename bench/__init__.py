"""The on-chip serving benchmark: see BENCHMARK.json and PERF.md."""
