"""Median latency over every request due in the window (host clock)."""
from bench.harness import percentile


def read(record, trace, ctx):
    return percentile(record["latency_ms"], 50)
