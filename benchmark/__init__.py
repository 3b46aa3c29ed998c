"""The benchmark (BENCHMARK.json ``paths``): harness, yardstick, references."""
