"""From the profiler's `.xplane.pb` to numbers. Reads with nothing but JAX.

What a v5e trace holds (looked at by hand, PR 26): one plane per chip named
`/device:TPU:<n>`, whose line `XLA Ops` has one event per executed HLO
operation (start and duration in ns on the trace's clock) and whose line
`XLA Modules` has one per program run; the host plane `/host:CPU` has a line
per thread, where `jax.profiler.TraceAnnotation` spans appear by name. A CPU
trace has no device plane; there the events that carry an `hlo_op` stat stand
in, so the reduction can be rehearsed, never reported.
"""
import bisect
import glob
import os
import re

SPAN = "bench.query"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path):
    """-> {"spans": [(start, end)], "devices": {plane: [(start, end, name)]},
    "programs": [start of every program run on a device]}"""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    spans, devices, stand_in, programs = [], {}, [], []
    for plane in data.planes:
        on_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if on_device and line.name == OPS_LINE:
                devices[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
            elif on_device and line.name == MODULES_LINE:
                programs += [e.start_ns for e in line.events]
            elif plane.name == "/host:CPU":
                for e in line.events:
                    if e.name == SPAN:
                        spans.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.duration_ns > 0 and \
                            any(k == "hlo_op" for k, _v in e.stats):
                        stand_in.append((e.start_ns, e.start_ns + e.duration_ns,
                                         e.name))
    if not devices and stand_in:
        devices["/host:CPU (stand-in, no device plane)"] = stand_in
    return {"spans": sorted(spans), "devices": devices, "programs": programs}


def union(intervals):
    """Merged [(start, end)] of possibly nested or overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def label_gaps(prev, nxt, s, e, spans, starts):
    """The idle gap (s, e) between the busy intervals `prev` and `nxt` (None
    at an edge), cut at the benchmark's span edges: [(label, ns)] saying what
    the span shows the host was doing in each piece."""
    out = []
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    at = s
    while at < e:
        while i < len(spans) and spans[i][1] <= at:
            i += 1
        if i == len(spans) or spans[i][0] >= e:
            out.append(("between_queries", e - at))
            break
        q0, q1 = spans[i]
        if q0 > at:
            out.append(("between_queries", q0 - at))
            at = q0
        end = min(e, q1)
        before = prev is not None and prev[1] > q0
        after = nxt is not None and nxt[0] < q1
        if before and after:
            label = "in_query.between_ops"
        elif before:
            label = "in_query.after_last_op"
        elif after:
            label = "in_query.before_first_op"
        else:
            label = "in_query.no_device_op"
        out.append((label, end - at))
        at = end
    return out


def short_name(hlo):
    """An HLO instruction's text without its layouts, cut to a line: the name,
    the result shape, the opcode and the first operands are what tell ops apart."""
    return re.sub(r"\{[^{}]*\}", "", hlo)[:160]


def reduce(trace):
    """-> {"window_s", "busy_s" (mean over chips), "queries", "programs" (runs
    of a compiled program inside the window), "device_ops": [[name, s]],
    "idle_gaps": [[label, s]]}; None where the trace holds no span or no op."""
    spans, devices = trace["spans"], trace["devices"]
    if not spans or not devices:
        return None
    w0, w1 = spans[0][0], max(e for _s, e in spans)
    busy_ns, by_op, by_gap = [], {}, {}
    starts = [q0 for q0, _q1 in spans]
    for ops in devices.values():
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                  if e > w0 and s < w1]
        busy = union((s, e) for s, e, _n in inside)
        busy_ns.append(sum(e - s for s, e in busy))
        for s, e, n in inside:
            by_op[n] = by_op.get(n, 0) + (e - s)
        bounds = [None] + busy + [None]
        for prev, nxt in zip(bounds, bounds[1:]):
            s = prev[1] if prev else w0
            e = nxt[0] if nxt else w1
            for lab, ns in label_gaps(prev, nxt, s, e, spans, starts):
                by_gap[lab] = by_gap.get(lab, 0) + ns
    chips = len(devices)
    if not sum(busy_ns):
        return None

    def top(d, scale):
        return [[short_name(k), v / scale] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"window_s": (w1 - w0) / 1e9,
            "busy_s": sum(busy_ns) / chips / 1e9,
            "queries": len(spans),
            "programs": sum(1 for t in trace.get("programs", ())
                            if w0 <= t < w1),
            "device_ops": top(by_op, 1e9 * chips),
            "idle_gaps": top(by_gap, 1e9 * chips)}
