"""One run of one cell: python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

Starts the server in this process as `python -m presto_tpu.server` does, warms
up the cell's queries (set-up), drives them over /v1/statement for the window,
then compares EVERY answer the window returned with the plain reference and
prints one JSON object as the last line of standard output. `--trace 0` gives
the cell's end-to-end metrics, `--trace 1` a short profiled window and the
per-layer metrics. Without the TPU chips the cell asks for: exit 1, no line.
"""
import time

T0 = time.perf_counter()   # set-up counts from here: imports are set-up too

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, compare, readers, tpch_data, trace_reduce  # noqa: E402
from benchmark.harness.traffic import Plan  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".benchmark_out", "trace")
WARMUP_RUNS = (2, 5)   # at least, at most: until a run compiles nothing


def require_chips(chips):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"benchmark: jax reports platform {devs[0].platform!r} "
                 f"({devs[0].device_kind}), not a TPU - nothing was run")
    if len(devs) < chips:
        sys.exit(f"benchmark: the cell needs {chips} TPU chips, jax sees "
                 f"{len(devs)} - nothing was run")
    return devs


def percentile(values, q):
    """Nearest rank: the smallest value with at least q of the sample at or
    below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def peaks_for(kind):
    table = cells.load_json(cells.BENCH_DIR, "harness", "peaks.json")["kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in harness/peaks.json")
    return table[kind]


def least_seconds(nbytes, kind, chips):
    """The least time the cell's chips need to read `nbytes` once: the tables
    are dealt over the chips, so the peak is `chips` times one chip's. It
    stands over `busy_s`, a chip's mean (trace_reduce.reduce)."""
    return nbytes / (chips * peaks_for(kind)["hbm_bytes_per_s"])


def scanned(queries, sf):
    """{query: (rows, logical bytes) its SQL must read once at this scale}"""
    widths = cells.load_json(cells.BENCH_DIR, "harness", "logical_widths.json")
    counts, out = {}, {}
    for name, query in queries.items():
        rows = nbytes = 0
        for table, columns in query.scans.items():
            if table not in counts:
                counts[table] = tpch_data.row_count(table, sf)
            rows += counts[table]
            nbytes += counts[table] * sum(
                widths["types"][widths["columns"][table][c]] for c in columns)
        out[name] = (rows, nbytes)
    return out


def warm_up(served, plan, watch):
    """Each query of the mix until a run builds nothing (tables resident,
    every program compiled or fetched from the cache), WARMUP_RUNS[1] runs at
    the most: run_cell refuses to measure a query that never got there."""
    from benchmark.harness.served import ask, counters

    def built_so_far():
        return watch.compiles + counters()["counters"].get(
            "kernel_cache.misses", 0)

    conn = served.connect("bench-warmup")
    log = []
    for query in sorted(plan.sql):
        for run in range(WARMUP_RUNS[1]):
            start = built_so_far()
            _rows, wall = ask(conn, plan.sql[query], query, -1 - run)
            built = built_so_far() - start
            log.append({"query": query, "wall_s": wall, "built": built})
            if run + 1 >= WARMUP_RUNS[0] and not built:
                break
    conn.close()
    return log


def never_warmed(log):
    """The queries whose last warm-up run still built a program."""
    last = {w["query"]: w["built"] for w in log}
    return sorted(q for q, built in last.items() if built)


def run_cell(workload, seed, seconds, trace, t0=None, need_chips=True,
             scale=None):
    """-> the result object. `scale` ({"schema", "scale_factor"}) and
    `need_chips=False` exist for the CPU rehearsal and the tests alone."""
    t0 = time.perf_counter() if t0 is None else t0
    cell = cells.Cell(workload)
    config = dict(cell.config, **(scale or {}))
    plan = Plan(cell.traffic, cell.queries, seed)

    import jax
    devs = require_chips(cell.chips) if need_chips else jax.devices()
    import presto_tpu  # noqa: F401 - x64, and the compile cache inside the checkout
    from benchmark.harness import served as sv

    watch = sv.CompileWatch()
    served = sv.Served(config)
    try:
        warm = warm_up(served, plan, watch)
        stuck = never_warmed(warm)
        if stuck:   # a window opened now would be timed with compiles in it
            sys.exit(f"benchmark: {stuck} still built programs "
                     f"in warm-up run {WARMUP_RUNS[1]}, the last - nothing "
                     f"was measured. The warm-up's log: {json.dumps(warm)}")
        min_queries = 0
        if trace:
            seconds = min(seconds, cell.traffic.get("trace_seconds", 5))
            min_queries = cell.traffic.get("trace_min_queries", 2) * plan.clients
            # one trace is kept, the newest, for a look by hand; it replaces the last
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        before, xla_before = sv.counters(), watch.compiles
        setup_s = time.perf_counter() - t0
        win = sv.run_window(served, plan, seconds, min_queries)
        after, xla_in_window = sv.counters(), watch.compiles - xla_before
        if trace:
            jax.profiler.stop_trace()
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs[:cell.chips])
    finally:
        served.stop()
    del served
    gc.collect()

    # the window has closed and the peak is read: now the reference
    sf = config["scale_factor"]
    t_ref = time.perf_counter()
    expected = {q: cell.queries[q].reference(sf, plan.params[q])
                for q in sorted(plan.sql)}
    reference_s = time.perf_counter() - t_ref
    numbers, failed = compare.judge(win["answers"], expected,
                                    config["guarantees"]["double_rel_tol"])

    per_query = scanned(cell.queries, sf)
    done = [q for q, answer in win["answers"] if answer is not None]
    rows = sum(per_query[q][0] for q in done)
    least_bytes = sum(per_query[q][1] for q in done)
    reduced = None
    if trace:
        reduced = trace_reduce.reduce(trace_reduce.read(
            trace_reduce.newest_xplane(TRACE_DIR)))
    kind = devs[0].device_kind
    window = {"walls": win["walls"], "completed": len(win["walls"]),
              "before": before, "after": after,
              "xla_compiles": xla_in_window, "trace": reduced,
              "memory_peak_bytes": peak,
              "least_s": least_seconds(least_bytes, kind, cell.chips)
              if need_chips else None}

    values = {"setup_s": setup_s}
    if win["walls"]:
        values["rows_per_s"] = rows / win["seconds"]
        values["query_wall_p95_s"] = percentile(win["walls"], 0.95)
    for m in cell.metrics("per_layer"):
        spec = cells.load_json(cells.BENCH_DIR, "layer_metrics",
                               m["name"] + ".json")
        if spec["reader"] == "file":
            read = cells.load_module(os.path.join(
                cells.BENCH_DIR, "layer_metrics", m["name"] + ".py"),
                "benchmark_metric_" + m["name"].replace(".", "_")).read
        else:
            read = readers.READERS[spec["reader"]]
        values[m["name"]] = read(spec, window)
    group = "per_layer" if trace else "end_to_end"
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in cell.metrics(group)
               if values.get(m["name"]) is not None}

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": compare.within(numbers) and not win["errors"],
              "attempted": win["attempted"], "failed": failed,
              "metrics": metrics, "device": device}
    if reduced:
        device["busy_s"], device["window_s"] = \
            reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["run"] = {
        "workload": workload, "seed": int(seed), "window_s": win["seconds"],
        "completed": len(win["walls"]), "parameters": plan.params,
        "warm_up": warm, "reference_s": reference_s,
        "xla_compiles_total": watch.compiles,
        "xla_compile_s_total": watch.compile_s,
        "persistent_cache_hits": watch.cache_hits,
        "late_s_max": max(win["late_s"], default=0.0),
        "wall_p50_s": percentile(win["walls"], 0.5) if win["walls"] else None,
        "wall_max_s": max(win["walls"], default=None),
        "errors": win["errors"][:5]}
    result["compared"] = numbers
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      t0=T0)
    for err in result["run"]["errors"]:
        print(f"benchmark: a query failed: {err}", file=sys.stderr)
    for name, n in result["compared"].items():
        print(f"compared {name}: {n['value']!r} (has to be {n['must']} "
              f"{n['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
