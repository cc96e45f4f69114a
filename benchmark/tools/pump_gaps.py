"""Print, for a traced mesh run, what every exchange pump was doing and what
the host was doing while the chips sat idle (harness/pump_spans.py). The look
by hand that tells WHICH exchange of the chain waits on which: an exchange
long in `starved` has a slow producer fragment, one long in `backpressure` a
slow consumer. For PERF.md section 5.

    python3 benchmark/tools/pump_gaps.py [file.xplane.pb | trace dir] [--queries N] [--json]

Without a path: the newest kept trace of this checkout (.benchmark_out/trace).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import engine_spans, pump_spans, trace_reduce  # noqa: E402


def _seconds(d, keys):
    return {k: d.get(k, 0) / 1e9 for k in keys}


def report(summary):
    """What the tables print, as numbers (seconds and shares)."""
    queries = []
    for q in summary["queries"]:
        queries.append({
            "qid": q["qid"], "execute_s": q["execute_ns"] / 1e9,
            "state_s": {f"f{f}": _seconds(states, pump_spans.STATES)
                        for f, states in q["states"].items()},
            "wall_s": _seconds(q["wall"], pump_spans.LABELS),
            "idle_s": {plane: _seconds(q["idle"][plane], pump_spans.LABELS)
                       for plane in summary["planes"]},
            "busy_s": {plane: _seconds(q["busy"][plane], pump_spans.LABELS)
                       for plane in summary["planes"]},
            "all_idle_s": _seconds(q["all_idle"], pump_spans.LABELS)})
    sync = pump_spans.idle_share(summary, ("pump_sync",))
    return {"queries": queries, "planes": summary["planes"],
            "idle_host_working_pct": pump_spans.idle_host_working_pct(summary),
            "idle_all_waiting_pct": pump_spans.idle_all_waiting_pct(summary),
            "idle_pump_sync_pct": sync,
            "all_chips_idle_pct": pump_spans.all_chips_idle_pct(summary)}


def show(q, planes):
    print(f"query {q['qid']}: execute {q['execute_s']:.6f} s")
    print("  pump seconds by state (queued: between an exchange's spans)")
    print(f"  {'':>6}" + "".join(f"{s:>13}" for s in pump_spans.STATES)
          + f"{'life':>13}")
    for f, states in q["state_s"].items():
        print(f"  {f:>6}" + "".join(f"{states[s]:13.6f}"
                                    for s in pump_spans.STATES)
              + f"{sum(states.values()):13.6f}")
    chips = [p.rsplit(":", 1)[-1][:6] for p in planes]
    print("  execute by label: wall, each chip's idle, all chips idle, "
          "each chip's busy")
    print(f"  {'':>10}{'wall':>12}"
          + "".join(f"{'idle ' + c:>12}" for c in chips) + f"{'all idle':>12}"
          + "".join(f"{'busy ' + c:>12}" for c in chips))
    for label in pump_spans.LABELS + ("total",):
        def cell(d):
            return sum(d.values()) if label == "total" else d[label]
        print(f"  {label:>10}{cell(q['wall_s']):12.6f}"
              + "".join(f"{cell(q['idle_s'][p]):12.6f}" for p in planes)
              + f"{cell(q['all_idle_s']):12.6f}"
              + "".join(f"{cell(q['busy_s'][p]):12.6f}" for p in planes))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("path", nargs="?")
    ap.add_argument("--queries", type=int, default=0,
                    help="print the first N queries only (0: all)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    path = args.path or engine_spans.newest()
    if path and os.path.isdir(path):
        path = trace_reduce.newest_xplane(path)
    if not path:
        sys.exit("pump_gaps: no trace given and none kept in this checkout")
    summary = pump_spans.summarize(pump_spans.gather(path))
    if summary is None:
        sys.exit(f"pump_gaps: {path} holds no query with the pumps' state "
                 "spans (presto.exchange.pump_fill ...) or no device op: "
                 "nothing to attribute")
    rep = report(summary)
    if args.queries:
        rep["queries"] = rep["queries"][:args.queries]
    if args.json:
        print(json.dumps(rep, indent=1))
        return
    print(path)
    for q in rep["queries"]:
        show(q, rep["planes"])
    print("of the chips' idle inside execute: "
          f"host working {rep['idle_host_working_pct']:.2f}%, "
          f"pump_sync {rep['idle_pump_sync_pct']:.2f}%, "
          f"waiting {rep['idle_all_waiting_pct']:.2f}%; "
          f"all chips idle {rep['all_chips_idle_pct']:.2f}% of execute")


if __name__ == "__main__":
    main()
