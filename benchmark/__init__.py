"""The benchmark: see BENCHMARK.json and PERF.md."""
