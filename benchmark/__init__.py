"""The benchmark: see BENCHMARK.json at the root and benchmark/README.md."""
