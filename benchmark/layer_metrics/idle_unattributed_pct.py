"""idle_unattributed_pct: idle time of the chip that no engine span explains (device_trace)."""
from benchmark.harness import engine_spans


def read(spec, window):
    return engine_spans.idle_unattributed_pct(engine_spans.of_window(window))
