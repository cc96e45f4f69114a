"""driver_idle_s: execute time in which no driver quantum ran (device_trace)."""
from benchmark.harness import engine_spans


def read(spec, window):
    return engine_spans.driver_idle_s(engine_spans.of_window(window))
