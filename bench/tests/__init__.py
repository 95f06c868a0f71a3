"""CPU tests of the benchmark: trace reduction, cost arithmetic, traffic,
references, and each driver run end to end at a tiny size."""
