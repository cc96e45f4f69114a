"""exchange_ici_roofline: least wire time of a query's exchanged bytes over
the seconds its collectives took (device_trace). Only on the device kind
whose ICI peak the metric's file states: a CPU has no such roofline."""
import jax

from benchmark.harness import collectives, readers


def read(spec, window):
    got = collectives.of_window(window)
    if not got or jax.devices()[0].device_kind != spec["device_kind"]:
        return None
    return collectives.ici_roofline_pct(
        readers.counter_per_query(spec, window), jax.device_count(),
        spec["ici_bytes_per_s"], got["total"])
