"""The benchmark: BENCHMARK.json names what is here, by file."""
