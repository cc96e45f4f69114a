"""Print, for a traced run of a cell that streams its tables (`q1_sf10_files`),
what the scan pipeline was doing while the chip sat idle inside a query. For
PERF.md section 5.

While a jax.profiler trace is live the stages of
`presto_tpu/ops/scan_pipeline.py` lie in the trace's host plane as
`presto.scan.<stage>` with the query's `qid` (looked at by hand, PR 40):
`read` (a range of a file copied off its mapping into the narrow dtype, one a
reader of the pool), `rebatch` (chunks cut into device-shaped pages), `upload`
(a page's `jax.device_put` until it is on the chip), and four waits:
`compute_stall` (the driver waits for a page), `read_stall` (a reader waits
for room in the staging budget), `decode_stall` (the re-batcher waits for the
chunk that is next in order), `upload_stall` (the re-batcher waits for room in
the uploaded-pages budget: the consumer is the slower side).

Each traced query's `presto.lifecycle.execute` is cut into pieces of ONE
label, the first of these that holds: `upload` (a page is on the link) >
`read` (a reader or the re-batcher works, nothing is on the link) >
`scan_wait` (only waits are open) > `no_scan` (no scan span at all: the
driver dispatches, or the query is past its scan); the chip's idle gaps are
laid over them. Beside that, the seconds each stage was open (readers side by
side, so `read` can pass the wall). A trace without such spans (every cell
that replays resident pages, a tree before the spans) prints that it found
none; nothing here raises for that.

    python3 benchmark/tools/scan_gaps.py [file.xplane.pb | trace dir] [--json]

Without a path: the newest kept trace of this checkout (.benchmark_out/trace).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import engine_spans, trace_reduce  # noqa: E402

PREFIX = "presto.scan."
STAGES = ("read", "rebatch", "upload", "compute_stall", "read_stall",
          "decode_stall", "upload_stall")
LABELS = ("upload", "read", "scan_wait", "no_scan")
_LAYER_OF = {"upload": "upload", "read": "read", "rebatch": "read"}


def read(path):
    """-> {qid: [(start, end, stage)]}: the scan stages' spans of the trace's
    host plane; whole nanoseconds."""
    import jax.profiler

    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                stage = e.name[len(PREFIX):]
                qid = dict(e.stats).get("qid") \
                    if e.name.startswith(PREFIX) else None
                if qid and stage in STAGES:
                    spans.setdefault(qid, []).append(
                        (round(e.start_ns), round(e.start_ns + e.duration_ns),
                         stage))
    return spans


def partition(execute, spans):
    """[(start, end, label)] covering the merged `execute` intervals once."""
    layers = [[tuple(iv) for iv in trace_reduce.union(
        [(s, e) for s, e, st in spans if _LAYER_OF.get(st, "scan_wait") == lab])]
        for lab in LABELS[:3]]
    pieces = []
    for x0, x1 in (tuple(iv) for iv in trace_reduce.union(execute)):
        edges = sorted({x0, x1} | {t for merged in layers for iv in merged
                                   for t in iv if x0 < t < x1})
        for a, b in zip(edges, edges[1:]):
            label = next((lab for lab, merged in zip(LABELS, layers)
                          if any(s <= a and e >= b for s, e in merged)),
                         LABELS[-1])
            pieces.append((a, b, label))
    return pieces


def summarize(path):
    """None where the trace holds no query with scan spans or no device op,
    else {"queries": [{"qid", "execute_s", "stage_s", "wall_s", "idle_s"}],
    "idle_pct": {label: share of the chip's idle inside execute}}; idle is
    the chips' mean."""
    base, scans = engine_spans.read(path), read(path)
    rows, idle_all = [], dict.fromkeys(LABELS, 0)
    for qid, q in sorted(base["queries"].items(),
                         key=lambda kv: kv[1]["root"]):
        execute = [(s, e) for s, e, lab in q["phases"] if lab == "execute"]
        if not execute or qid not in scans or not base["busy"]:
            continue
        pieces = partition(execute, scans[qid])
        wall, idle = dict.fromkeys(LABELS, 0), dict.fromkeys(LABELS, 0)
        for a, b, label in pieces:
            wall[label] += b - a
        for ops in base["busy"].values():
            for x0, x1 in execute:
                gaps = engine_spans.idle_of(ops, x0, x1)
                for lab, ns in engine_spans.overlay(pieces, gaps).items():
                    idle[lab] += ns / len(base["busy"])
        stage = dict.fromkeys(STAGES, 0)
        for s, e, st in scans[qid]:
            stage[st] += e - s
        for lab in LABELS:
            idle_all[lab] += idle[lab]
        rows.append({"qid": qid,
                     "execute_s": sum(e - s for s, e in execute) / 1e9,
                     "stage_s": {k: v / 1e9 for k, v in stage.items()},
                     "wall_s": {k: v / 1e9 for k, v in wall.items()},
                     "idle_s": {k: v / 1e9 for k, v in idle.items()}})
    if not rows:
        return None
    total = sum(idle_all.values())
    return {"queries": rows,
            "idle_pct": {k: 100.0 * v / total if total else 0.0
                         for k, v in idle_all.items()}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    path = args.path or engine_spans.newest()
    if path and os.path.isdir(path):
        path = trace_reduce.newest_xplane(path)
    if not path:
        sys.exit("scan_gaps: no trace given and none kept in this checkout")
    summary = summarize(path)
    if summary is None:
        sys.exit(f"scan_gaps: {path} holds no query with presto.scan.* spans "
                 "or no device op: nothing to attribute")
    if args.json:
        print(json.dumps(summary, indent=1))
        return
    print(path)
    for q in summary["queries"]:
        print(f"query {q['qid']}: execute {q['execute_s']:.6f} s")
        print("  stage seconds (readers side by side): "
              + ", ".join(f"{k} {v:.6f}" for k, v in q["stage_s"].items()))
        print(f"  {'':>10}{'wall':>12}{'chip idle':>12}")
        for label in LABELS:
            print(f"  {label:>10}{q['wall_s'][label]:12.6f}"
                  f"{q['idle_s'][label]:12.6f}")
    print("of the chip's idle inside execute: "
          + ", ".join(f"{k} {v:.2f}%" for k, v in summary["idle_pct"].items()))


if __name__ == "__main__":
    main()
