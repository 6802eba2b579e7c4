"""On-chip benchmark of ZipNN: see BENCHMARK.json and PERF.md."""
