"""95th percentile latency over every request due in the window, each
timed from its due time until its result is on the host (host clock)."""
from bench.harness import percentile


def read(record, trace, ctx):
    return percentile(record["latency_ms"], 95)
