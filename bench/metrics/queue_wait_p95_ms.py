"""95th percentile of the time from a request's due time to the
``PortEngine.submit`` call that carries it (host clock): the admission
layer's wait, which includes the previous slate still being served."""
from bench.harness import percentile


def read(record, trace, ctx):
    return percentile(record["queue_wait_ms"], 95)
