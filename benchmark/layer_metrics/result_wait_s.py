"""result_wait_s: how long a finished answer waited to be fetched (program_counter)."""
from benchmark.harness import engine_spans


def read(spec, window):
    return engine_spans.histogram_mean_gain(window, spec["histograms"])
