"""The on-chip benchmark: BENCHMARK.json names its cells, configurations,
traffic mixes and metrics, and the files here serve them by name."""
