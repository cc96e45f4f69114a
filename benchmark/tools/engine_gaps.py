"""Print where a traced run's queries spent their wall and where the chip sat
idle, by the engine's own spans (harness/engine_spans.py). For a look by hand
and for PERF.md section 5.

    python3 benchmark/tools/engine_gaps.py [file.xplane.pb | trace dir] [--queries N] [--json]

Without a path: the newest kept trace of this checkout (.benchmark_out/trace).
"""
import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import engine_spans, trace_reduce  # noqa: E402

COLUMNS = engine_spans.LABELS + ("outside_query",)


def report(summary):
    """What the table prints, as numbers (seconds and shares)."""
    n = len(summary["queries"])
    covered = [1.0 - q["wall"].get("unattributed", 0) / q["wall_ns"]
               for q in summary["queries"]]

    def seconds(d):
        return {k: d[k] / 1e9 for k in COLUMNS if d.get(k)}

    return {"queries": n,
            "wall_s": seconds(summary["wall"]),
            "idle_s": seconds(summary["idle"]),
            "busy_s": seconds(summary["busy"]),
            "idle_s_by_benchmark_label": {
                k: seconds(v) for k, v in sorted(summary["by_bench"].items())},
            "driver_idle_s": engine_spans.driver_idle_s(summary),
            "idle_unattributed_pct": engine_spans.idle_unattributed_pct(summary),
            "median_query_covered_pct": 100.0 * statistics.median(covered),
            "programs": summary["programs"],
            "programs_in_execute_pct":
                100.0 * summary["programs_in_execute"] / summary["programs"]
                if summary["programs"] else None}


def table(title, columns, per=1):
    """columns: [(name, {label: seconds})], one line a label; `per` divides
    (a mean a query)."""
    width = max(12, *(len(name) + 2 for name, _c in columns))
    print(title)
    print(f"  {'':>18}" + "".join(f"{name:>{width}}" for name, _c in columns))
    for label in [c for c in COLUMNS if any(col.get(c) for _n, col in columns)]:
        print(f"  {label:>18}" + "".join(
            f"{col.get(label, 0.0) / per:{width}.6f}" for _n, col in columns))
    print(f"  {'total':>18}" + "".join(
        f"{sum(col.values()) / per:{width}.6f}" for _n, col in columns))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?")
    ap.add_argument("--queries", type=int, default=0,
                    help="also print the first N queries one by one")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    path = args.path or engine_spans.newest()
    if path and os.path.isdir(path):
        path = trace_reduce.newest_xplane(path)
    if not path:
        sys.exit("engine_gaps: no trace given and none kept in this checkout")
    summary = engine_spans.summarize(engine_spans.read(path))
    if summary is None:
        sys.exit(f"engine_gaps: {path} holds no presto.query span or no "
                 "device op: nothing to attribute")
    rep = report(summary)
    if args.json:
        print(json.dumps(rep, indent=1))
        return
    n = rep["queries"]
    print(path)
    table(f"window: {n} queries, seconds",
          [("wall", rep["wall_s"]), ("chip idle", rep["idle_s"]),
           ("chip busy", rep["busy_s"])])
    table("mean a query, seconds",
          [("wall", rep["wall_s"]), ("chip idle", rep["idle_s"]),
           ("chip busy", rep["busy_s"])], per=n)
    table("chip idle seconds: engine label by the benchmark's gap label",
          list(rep["idle_s_by_benchmark_label"].items()))
    for q in summary["queries"][:args.queries]:
        table(f"query {q['qid']}: wall {q['wall_ns'] / 1e9:.6f} s",
              [(k, {c: v / 1e9 for c, v in q[k].items()})
               for k in ("wall", "idle", "busy")])
    print(f"driver_idle_s {rep['driver_idle_s']}  idle_unattributed_pct "
          f"{rep['idle_unattributed_pct']}")
    print(f"median query: {rep['median_query_covered_pct']:.2f}% of "
          "presto.query under a label other than unattributed")
    if rep["programs"]:
        print(f"{rep['programs']} program runs in the window, "
              f"{rep['programs_in_execute_pct']:.2f}% began inside a "
              "presto.lifecycle.execute span")


if __name__ == "__main__":
    main()
