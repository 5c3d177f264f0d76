"""The chip benchmark of HI² serving (see BENCHMARK.json and PERF.md)."""
