"""The benchmark: one run of one cell, driven by BENCHMARK.json."""
