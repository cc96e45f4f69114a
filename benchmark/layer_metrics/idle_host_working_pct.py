"""idle_host_working_pct: idle time of the chips while the query's host Python works (device_trace)."""
from benchmark.harness import pump_spans


def read(spec, window):
    return pump_spans.idle_host_working_pct(pump_spans.of_window(window))
