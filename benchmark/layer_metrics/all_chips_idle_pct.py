"""all_chips_idle_pct: execute time in which no chip runs an op (device_trace)."""
from benchmark.harness import pump_spans


def read(spec, window):
    return pump_spans.all_chips_idle_pct(pump_spans.of_window(window))
