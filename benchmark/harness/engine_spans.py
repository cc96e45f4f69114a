"""The engine's own spans, read from the profiler's trace: where each query's
wall went, and what the host was doing while the chip sat idle.

While a jax.profiler trace is live the program writes every span of
`presto_tpu/utils/trace.py` into the trace's host plane as an event named
`presto.<category>.<name>` with the client-visible query id in its `qid` stat
(looked at by hand, PR 27): `presto.query` is a query's root, from the POST's
submit to the GET that served the final state; `presto.protocol.queued |
serialize | result_wait`, `presto.lifecycle.parse | plan | local_plan |
execute | result` (result: the answer's pages fetched from the device and made
rows), `presto.driver.<first->last operator>` (one per driver quantum, any
thread) and `presto.http.<METHOD> <route>` lie inside it. They are on the
clock of the device plane's `XLA Ops`, so an idle gap of the chip can be laid
over them. A trace of a program without such spans (the parent of PR 27, or no
trace at all) reads as None everywhere: nothing here raises for that.

Each root is cut into pieces that carry one label each and add up to it
exactly: the innermost phase open at that time, `execute` split by whether any
driver quantum of the query was running, `http` where only a request handler
was, `unattributed` where nothing but the root was.
"""
import bisect
import os

from . import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE_DIR = os.path.join(ROOT, ".benchmark_out", "trace")   # as run.py names it

QUERY = "presto.query"
PHASES = {"presto.protocol.queued": "queued",
          "presto.lifecycle.parse": "parse",
          "presto.lifecycle.plan": "plan",
          "presto.lifecycle.local_plan": "local_plan",
          "presto.lifecycle.execute": "execute",
          "presto.lifecycle.result": "result",
          "presto.protocol.serialize": "serialize",
          "presto.protocol.result_wait": "result_wait"}
DRIVER = "presto.driver."
HTTP = "presto.http."
LABELS = ("queued", "parse", "plan", "local_plan", "execute.driver",
          "execute.no_driver", "result", "serialize", "result_wait", "http",
          "unattributed")

_CACHE = {}   # path -> (mtime_ns, what read() gave): the file is parsed once


def newest():
    """The newest kept trace of this checkout, or None."""
    try:
        return trace_reduce.newest_xplane(TRACE_DIR)
    except FileNotFoundError:
        return None


def read(path):
    """-> {"queries": {qid: {"root": (s, e), "phases": [(s, e, label)],
    "drivers": [(s, e)], "http": [(s, e)]}}, "bench": [(s, e)] of the
    benchmark's own spans, "busy": {device plane: merged [(s, e)]},
    "programs": [start of every program run]}; whole nanoseconds."""
    stamp = os.stat(path).st_mtime_ns
    if _CACHE.get(path, (None,))[0] == stamp:
        return _CACHE[path][1]
    import jax.profiler

    queries, bench, ops, stand_in, programs = {}, [], {}, [], []

    def query(qid):
        return queries.setdefault(qid, {"root": None, "phases": [],
                                        "drivers": [], "http": []})

    for plane in jax.profiler.ProfileData.from_file(path).planes:
        on_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if on_device and line.name == trace_reduce.OPS_LINE:
                ops[plane.name] = [(round(e.start_ns),
                                    round(e.start_ns + e.duration_ns))
                                   for e in line.events]
            elif on_device and line.name == trace_reduce.MODULES_LINE:
                programs += [round(e.start_ns) for e in line.events]
            if plane.name != "/host:CPU":
                continue
            for e in line.events:
                name = e.name
                at = (round(e.start_ns), round(e.start_ns + e.duration_ns))
                if name == trace_reduce.SPAN:
                    bench.append(at)
                elif name.startswith("presto."):
                    qid = dict(e.stats).get("qid")
                    if not qid:
                        continue
                    if name == QUERY:
                        query(qid)["root"] = at
                    elif name in PHASES:
                        query(qid)["phases"].append(at + (PHASES[name],))
                    elif name.startswith(DRIVER):
                        query(qid)["drivers"].append(at)
                    elif name.startswith(HTTP):
                        query(qid)["http"].append(at)
                elif e.duration_ns > 0 and \
                        any(k == "hlo_op" for k, _v in e.stats):
                    stand_in.append(at)   # a CPU trace: rehearsal only
    if not ops and stand_in:
        ops["/host:CPU (stand-in, no device plane)"] = stand_in
    # a query whose root began before the trace did, or ended after it, has
    # no root event: it is left out, its pieces belong to no whole
    got = {"queries": {q: v for q, v in queries.items() if v["root"]},
           "bench": sorted(bench),
           "busy": {p: [tuple(iv) for iv in trace_reduce.union(v)]
                    for p, v in ops.items()},
           "programs": sorted(programs)}
    _CACHE.clear()
    _CACHE[path] = (stamp, got)
    return got


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def partition(query):
    """[(start, end, label)] in time order, covering the query's root exactly
    once: every piece between two neighbouring span edges takes the label of
    the phase that began last among those open over it (the innermost)."""
    r0, r1 = query["root"]
    phases = [(max(s, r0), min(e, r1), lab) for s, e, lab in query["phases"]
              if e > r0 and s < r1]
    drivers = [tuple(iv) for iv in
               trace_reduce.union(_clip(query["drivers"], r0, r1))]
    http = [tuple(iv) for iv in
            trace_reduce.union(_clip(query["http"], r0, r1))]
    edges = sorted({r0, r1}
                   | {t for s, e, _l in phases for t in (s, e)}
                   | {t for s, e in drivers + http for t in (s, e)})
    pieces = []
    for a, b in zip(edges, edges[1:]):
        over = [(s, lab) for s, e, lab in phases if s <= a and e >= b]
        if over:
            label = max(over)[1]
            if label == "execute":
                label = "execute.driver" \
                    if any(s <= a and e >= b for s, e in drivers) \
                    else "execute.no_driver"
        elif any(s <= a and e >= b for s, e in http):
            label = "http"
        else:
            label = "unattributed"
        if pieces and pieces[-1][2] == label:
            pieces[-1] = (pieces[-1][0], b, label)
        else:
            pieces.append((a, b, label))
    return pieces


def overlay(pieces, intervals):
    """{label: ns of the sorted disjoint `intervals` that fall in pieces of
    that label}. Both lists are in time order."""
    out, i = {}, 0
    for a, b, label in pieces:
        while i < len(intervals) and intervals[i][1] <= a:
            i += 1
        j = i
        while j < len(intervals) and intervals[j][0] < b:
            ns = min(b, intervals[j][1]) - max(a, intervals[j][0])
            out[label] = out.get(label, 0) + ns
            j += 1
    return out


def idle_of(busy, lo, hi):
    """The gaps of the merged busy intervals inside (lo, hi)."""
    gaps, at = [], lo
    for s, e in _clip(busy, lo, hi):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < hi:
        gaps.append((at, hi))
    return gaps


def bench_labels(busy, bench):
    """[(start, end, label)]: the benchmark's own spans cut as
    trace_reduce.label_gaps labels the idle inside them, with the place of
    every piece kept: before the span's first device op, between its ops
    (busy or not), after its last."""
    pieces = []
    for q0, q1 in bench:
        inside = _clip(busy, q0, q1)
        if not inside:
            pieces.append((q0, q1, "in_query.no_device_op"))
            continue
        first, last = inside[0][0], inside[-1][1]
        for a, b, label in ((q0, first, "in_query.before_first_op"),
                            (first, last, "in_query.between_ops"),
                            (last, q1, "in_query.after_last_op")):
            if b > a:
                pieces.append((a, b, label))
    return pieces


def summarize(trace):
    """Everything the metrics and the table need, or None where the trace
    holds no `presto.query` root or no device op:
    {"queries": [{"qid", "wall_ns", "wall": {label: ns}, "idle": {label: ns},
                  "busy": {label: ns}}] in time order,
     "wall" / "idle" / "busy": the same summed over the queries (idle and
     busy are means over the chips),
     "by_bench": {benchmark's gap label: {engine label: idle ns}}, with
     `outside_query` for what lies in the benchmark's span and in no root
     (the client; exact while roots do not overlap: one client),
     "programs": runs of a program inside the first..last root,
     "programs_in_execute": those that began inside some query's execute}"""
    if not trace["queries"] or not trace["busy"]:
        return None
    order = sorted(trace["queries"].items(), key=lambda kv: kv[1]["root"])
    chips = len(trace["busy"])
    rows, by_bench, executes = [], {}, []
    marked = {p: bench_labels(ops, trace["bench"])
              for p, ops in trace["busy"].items()}
    for ops, pieces in ((trace["busy"][p], m) for p, m in marked.items()):
        for a, b, blabel in pieces:
            into = by_bench.setdefault(blabel, {})
            into["outside_query"] = into.get("outside_query", 0) + \
                sum(e - s for s, e in idle_of(ops, a, b)) / chips
    for qid, q in order:
        r0, r1 = q["root"]
        pieces = partition(q)
        wall, idle, busy = {}, {}, {}
        for a, b, label in pieces:
            wall[label] = wall.get(label, 0) + (b - a)
        for plane, ops in trace["busy"].items():
            gaps = idle_of(ops, r0, r1)
            for lab, ns in overlay(pieces, gaps).items():
                idle[lab] = idle.get(lab, 0) + ns / chips
            for lab, ns in overlay(pieces, _clip(ops, r0, r1)).items():
                busy[lab] = busy.get(lab, 0) + ns / chips
            for a, b, blabel in marked[plane]:
                into = by_bench[blabel]
                for lab, ns in overlay(pieces, _clip(gaps, a, b)).items():
                    into[lab] = into.get(lab, 0) + ns / chips
                    into["outside_query"] -= ns / chips
        executes += [(s, e) for s, e, lab in q["phases"] if lab == "execute"]
        rows.append({"qid": qid, "wall_ns": r1 - r0, "wall": wall,
                     "idle": idle, "busy": busy})

    def total(key):
        out = {}
        for row in rows:
            for lab, ns in row[key].items():
                out[lab] = out.get(lab, 0) + ns
        return out

    w0, w1 = order[0][1]["root"][0], max(q["root"][1] for _q, q in order)
    inside = [t for t in trace["programs"] if w0 <= t < w1]
    merged = trace_reduce.union(executes)
    ends = [e for _s, e in merged]
    in_execute = 0
    for t in inside:
        i = bisect.bisect_right(ends, t)   # the first execute that ends later
        in_execute += i < len(merged) and merged[i][0] <= t
    return {"queries": rows, "wall": total("wall"), "idle": total("idle"),
            "busy": total("busy"), "by_bench": by_bench,
            "programs": len(inside), "programs_in_execute": in_execute}


def of_window(window):
    """The summary of the run's own trace: None for an untraced run (an older
    trace may lie in the directory) and where there is nothing to read."""
    if not window.get("trace"):
        return None
    path = newest()
    return summarize(read(path)) if path else None


def driver_idle_s(summary):
    """Mean over the traced queries of `execute.no_driver`: the execute span
    less the union of its driver quanta. None without an execute span."""
    if not summary:
        return None
    had = [q for q in summary["queries"]
           if "execute.driver" in q["wall"] or "execute.no_driver" in q["wall"]]
    if not had:
        return None
    return sum(q["wall"].get("execute.no_driver", 0) for q in had) / len(had) / 1e9


def idle_unattributed_pct(summary):
    """Share of the chip's idle time inside the traced queries' roots that
    lies under no span but the root. None where the chip was never idle."""
    if not summary:
        return None
    idle = sum(summary["idle"].values())
    if not idle:
        return None
    return 100.0 * summary["idle"].get("unattributed", 0) / idle


def histogram_mean_gain(window, names):
    """Sum over `names` of (what the histogram's total gained in the window /
    the observations it gained): a mean a query. None where any of them
    gained nothing (the parent of PR 27 has no such histogram)."""
    total = 0.0
    for name in names:
        after = window["after"]["histograms"].get(name)
        before = window["before"]["histograms"].get(name, {"n": 0, "total": 0.0})
        if not after or after["n"] <= before["n"]:
            return None
        total += (after["total"] - before["total"]) / (after["n"] - before["n"])
    return total
