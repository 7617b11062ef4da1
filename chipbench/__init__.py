"""On-chip benchmark of COSTREAM serving: see BENCHMARK.json and PERF.md."""
