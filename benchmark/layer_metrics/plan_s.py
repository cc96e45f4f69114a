"""plan_s: parse + plan/optimize + local planning, a query (program_counter)."""
from benchmark.harness import engine_spans


def read(spec, window):
    return engine_spans.histogram_mean_gain(window, spec["histograms"])
