"""The reductions a per-layer metric's file may name. Each takes the metric's
spec (`layer_metrics/<name>.json`) and the window's record, and returns the
number, or None where there is nothing to read: the harness then leaves the
metric out of the line. A share of a peak is never reported as 0.

window: {"walls", "completed", "before"/"after" (the program's raw
counters), "xla_compiles", "trace" (trace_reduce.reduce or None),
"memory_peak_bytes", "least_s" (sum over completed queries of bytes their SQL
must read once over the peak bytes/s of the cell's chips together,
run.least_seconds; the traced queries alone where there is a trace)}
"""


def _counter_delta(window, name):
    before = window["before"]["counters"].get(name, 0)
    return window["after"]["counters"].get(name, 0) - before


def counter_per_query(spec, window):
    if not window["completed"]:
        return None
    return sum(_counter_delta(window, c) for c in spec["counters"]) / \
        window["completed"]


def counter_sum(spec, window):
    total = sum(_counter_delta(window, c) for c in spec.get("counters", []))
    if spec.get("xla_compiles"):
        total += window["xla_compiles"]
    return total


def gauge(spec, window):
    return window["after"]["gauges"].get(spec["gauge"])


def client_wall_minus_histogram(spec, window):
    """Mean client wall minus the mean the program's own histogram gained."""
    hist = spec["histogram"]
    after = window["after"]["histograms"].get(hist)
    before = window["before"]["histograms"].get(hist, {"n": 0, "total": 0.0})
    if not after or after["n"] <= before["n"] or not window["walls"]:
        return None
    inside = (after["total"] - before["total"]) / (after["n"] - before["n"])
    return sum(window["walls"]) / len(window["walls"]) - inside


def programs_per_query(spec, window):
    tr = window["trace"]
    if not tr or not tr["queries"] or not tr["programs"]:
        return None
    return tr["programs"] / tr["queries"]


def scan_roofline(spec, window):
    tr = window["trace"]
    if not tr or not tr["busy_s"] or not window["least_s"]:
        return None
    return 100.0 * window["least_s"] / tr["busy_s"]


def device_idle(spec, window):
    tr = window["trace"]
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def memory_peak(spec, window):
    return window["memory_peak_bytes"] or None


READERS = {f.__name__: f for f in (
    counter_per_query, counter_sum, gauge, client_wall_minus_histogram,
    programs_per_query, scan_roofline, device_idle, memory_peak)}
