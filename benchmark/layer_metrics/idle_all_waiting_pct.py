"""idle_all_waiting_pct: idle time of the chips while nothing of the query runs on the host (device_trace)."""
from benchmark.harness import pump_spans


def read(spec, window):
    return pump_spans.idle_all_waiting_pct(pump_spans.of_window(window))
