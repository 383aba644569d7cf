"""The chip benchmark of distributedfft_tpu (see BENCHMARK.json, PERF.md)."""
