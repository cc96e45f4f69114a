"""The exchange pumps' states, read from the profiler's trace: while a chip
sat idle inside a mesh query, what was the host doing?

On the mesh the drivers only enqueue programs and park; the pumps of
`presto_tpu/parallel/streaming_exchange.py` do the rest, and since PR 38 a
pump carries exactly ONE state at every moment (`PUMP_STATES` there). Every
state but `queued` is a span `presto.exchange.<name> f<fragment>` of the
trace's host plane with the query's `qid` (looked at by hand, PR 38):
`pump_stall` (starved of producer pages), `pump_sync` (blocked in
`jax.device_get`), `pump_fill` (send side's host Python), `chunk_dispatch`
(assemble, the collective lock, the program call), `chunk_deliver` (receive
side's host Python), `pump_backpressure` (a full consumer queue),
`pump_skew_wait`. `queued` is what lies between a pump's spans. They are on
the clock of the device planes' `XLA Ops`.

Each traced query's `presto.lifecycle.execute` span is cut into pieces that
carry ONE label, by priority: `driver` (a driver quantum is open) >
`pump_host` (some pump is in fill, dispatch or deliver: Python works) >
`pump_sync` (some pump is blocked in a device_get: the host waits for a chip)
> `waiting` (none of these: every pump is starved, queued, under
back-pressure or waiting for its peer). The wait for the one collective lock
has no span of its own (it is `chunk_dispatch`'s `lock_wait_us`), so in the
trace it reads `pump_host`; the counter `exchange.lock_wait_s` has it. Each
chip's idle gaps are laid over the pieces chip by chip, and beside their mean
stands their intersection: the time in which NO chip runs an op.

A trace without the new spans (the parent of PR 38 has `pump_stall` and
`chunk_dispatch` alone, a local runner none), or no trace at all, reads as
None everywhere: nothing here raises for that.
"""
import bisect
import os

from . import engine_spans, trace_reduce

PREFIX = "presto.exchange."
STATE_OF = {"pump_stall": "starved", "pump_sync": "sync", "pump_fill": "fill",
            "chunk_dispatch": "dispatch", "chunk_deliver": "deliver",
            "pump_backpressure": "backpressure",
            "pump_skew_wait": "skew_wait"}
STATES = tuple(STATE_OF.values()) + ("queued",)
HOST_STATES = ("fill", "dispatch", "deliver")
LABELS = ("driver", "pump_host", "pump_sync", "waiting")
# a trace has the pumps' states only if it has the spans PR 38 brought
NEW_STATES = ("fill", "sync", "deliver")

_CACHE = {}   # path -> (mtime_ns, what read() gave): the file is parsed once


def read(path):
    """-> {qid: {fragment: [(start, end, state)] in time order}}: the pumps'
    state spans of the trace's host plane; whole nanoseconds."""
    stamp = os.stat(path).st_mtime_ns
    if _CACHE.get(path, (None,))[0] == stamp:
        return _CACHE[path][1]
    import jax.profiler

    pumps = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                kind, _, fragment = e.name[len(PREFIX):].partition(" f")
                qid = dict(e.stats).get("qid")
                if kind not in STATE_OF or not fragment.isdigit() or not qid:
                    continue
                pumps.setdefault(qid, {}).setdefault(int(fragment), []).append(
                    (round(e.start_ns), round(e.start_ns + e.duration_ns),
                     STATE_OF[kind]))
    for by_fragment in pumps.values():
        for spans in by_fragment.values():
            spans.sort()
    _CACHE.clear()
    _CACHE[path] = (stamp, pumps)
    return pumps


def gather(path):
    """The trace as `summarize` takes it: {"queries": {qid: {"execute":
    [(s, e)], "drivers": [(s, e)], "pumps": {fragment: [(s, e, state)]}}},
    "busy": {device plane: merged [(s, e)]}}. The roots, phases, driver
    quanta and device ops are `engine_spans.read`'s."""
    base, pumps = engine_spans.read(path), read(path)
    queries = {}
    for qid, q in base["queries"].items():
        execute = [(s, e) for s, e, lab in q["phases"] if lab == "execute"]
        if execute and qid in pumps:
            queries[qid] = {"execute": execute, "drivers": q["drivers"],
                            "pumps": pumps[qid]}
    return {"queries": queries, "busy": base["busy"]}


def _covers(merged, starts, a, b):
    """Does one of the merged, sorted intervals cover the piece (a, b)?"""
    i = bisect.bisect_right(starts, a) - 1
    return i >= 0 and merged[i][1] >= b


def partition(query):
    """[(start, end, label)] in time order, covering the query's execute
    spans exactly once: every piece between two neighbouring span edges takes
    the first of LABELS that holds over it."""
    execute = [tuple(iv) for iv in trace_reduce.union(query["execute"])]
    spans = [sp for by in query["pumps"].values() for sp in by]
    layers = []
    for intervals in (
            query["drivers"],
            [(s, e) for s, e, st in spans if st in HOST_STATES],
            [(s, e) for s, e, st in spans if st == "sync"]):
        merged = [tuple(iv) for iv in trace_reduce.union(intervals)]
        layers.append((merged, [s for s, _e in merged]))
    pieces = []
    for x0, x1 in execute:
        edges = sorted({x0, x1} | {t for merged, _s in layers
                                   for iv in merged for t in iv
                                   if x0 < t < x1})
        for a, b in zip(edges, edges[1:]):
            label = LABELS[-1]
            for (merged, starts), name in zip(layers, LABELS):
                if _covers(merged, starts, a, b):
                    label = name
                    break
            if pieces and pieces[-1][2] == label and pieces[-1][1] == a:
                pieces[-1] = (pieces[-1][0], b, label)
            else:
                pieces.append((a, b, label))
    return pieces


def intersect(a, b):
    """The sorted disjoint intervals that lie in both sorted disjoint lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def state_seconds(spans):
    """{state: ns} of one pump's spans; `queued` is what lies between them,
    from the first one's start to the last one's end."""
    out = dict.fromkeys(STATES, 0)
    for s, e, state in spans:
        out[state] += e - s
    if spans:
        life = max(e for _s, e, _st in spans) - spans[0][0]
        out["queued"] = max(life - sum(out.values()), 0)
    return out


def summarize(trace):
    """Everything the metrics and the table need, or None where no traced
    query holds the pumps' new spans or the trace holds no device op:
    {"planes": [device plane names in order],
     "queries": [{"qid", "execute_ns", "wall": {label: ns},
                  "idle": {plane: {label: ns}}, "busy": {plane: {label: ns}},
                  "all_idle": {label: ns} (no plane runs an op),
                  "states": {fragment: {state: ns}}}] in time order,
     "execute_ns" / "wall" / "idle" (summed over the planes) / "all_idle":
     the same summed over the queries}"""
    queries = {qid: q for qid, q in trace["queries"].items()
               if any(st in NEW_STATES for by in q["pumps"].values()
                      for _s, _e, st in by)}
    if not queries or not trace["busy"]:
        return None
    planes = sorted(trace["busy"])
    rows = []
    for qid, q in sorted(queries.items(), key=lambda kv: min(kv[1]["execute"])):
        pieces = partition(q)
        wall, idle, busy = {}, {}, {}
        for a, b, label in pieces:
            wall[label] = wall.get(label, 0) + (b - a)
        nobody = None
        for plane in planes:
            ops, gaps = trace["busy"][plane], []
            for x0, x1 in trace_reduce.union(q["execute"]):
                gaps += engine_spans.idle_of(ops, x0, x1)
            idle[plane] = engine_spans.overlay(pieces, gaps)
            busy[plane] = engine_spans.overlay(pieces, ops)
            nobody = gaps if nobody is None else intersect(nobody, gaps)
        rows.append({"qid": qid, "execute_ns": sum(wall.values()),
                     "wall": wall, "idle": idle, "busy": busy,
                     "all_idle": engine_spans.overlay(pieces, nobody),
                     "states": {f: state_seconds(by)
                                for f, by in sorted(q["pumps"].items())}})

    def total(dicts):
        out = {}
        for d in dicts:
            for lab, ns in d.items():
                out[lab] = out.get(lab, 0) + ns
        return out

    return {"planes": planes, "queries": rows,
            "execute_ns": sum(r["execute_ns"] for r in rows),
            "wall": total(r["wall"] for r in rows),
            "idle": total(d for r in rows for d in r["idle"].values()),
            "all_idle": total(r["all_idle"] for r in rows)}


def of_window(window):
    """The summary of the run's own trace: None for an untraced run (an older
    trace may lie in the directory) and where there is nothing to read."""
    if not window.get("trace"):
        return None
    path = engine_spans.newest()
    return summarize(gather(path)) if path else None


def idle_share(summary, labels):
    """Share of the chips' idle time inside execute under `labels`, or None."""
    if not summary:
        return None
    idle = sum(summary["idle"].values())
    if not idle:
        return None
    return 100.0 * sum(summary["idle"].get(lab, 0) for lab in labels) / idle


def idle_host_working_pct(summary):
    """Share of the chips' idle time inside the traced queries' execute that
    lies under `driver` or `pump_host`: the chip waits for Python."""
    return idle_share(summary, ("driver", "pump_host"))


def idle_all_waiting_pct(summary):
    """The share under `waiting`: nothing of the query runs on the host or
    blocks in a device_get, and the chip is idle all the same."""
    return idle_share(summary, ("waiting",))


def all_chips_idle_pct(summary):
    """Share of the traced queries' execute time in which NO plane runs an
    op: the intersection of the planes' gaps, where `device_idle_pct` is
    their mean."""
    if not summary or not summary["execute_ns"]:
        return None
    return 100.0 * sum(summary["all_idle"].values()) / summary["execute_ns"]
