"""collective_s_per_query: a chip's seconds in collectives, a query (device_trace)."""
from benchmark.harness import collectives


def read(spec, window):
    got = collectives.of_window(window)
    return got["total"] if got else None
