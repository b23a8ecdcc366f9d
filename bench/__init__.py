"""Chip benchmark of the taskgraph runtime, driven by the data in BENCHMARK.json."""
