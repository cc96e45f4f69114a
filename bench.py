"""Benchmark runner — prints ONE JSON line for the round driver.

Ladder (BASELINE.md): Q6 SF1 -> Q1 SF10 -> Q3. Headline metric is TPC-H Q1
rows/sec on the device, with a single-thread numpy evaluation of the same Q1
arithmetic (the presto-benchmark HandTpchQuery1 pattern,
presto-benchmark/.../HandTpchQuery1.java) as the vs_baseline denominator.
Rungs that fail record an error entry in `detail` instead of aborting the run;
any top-level failure still emits a parseable JSON record with "error", and
either kind makes the exit code non-zero.

Run: python bench.py [--sf N] [--quick]
"""
import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

# module-level so the bench_error record can include rungs completed before a
# top-level failure
DETAIL = {}

def bench_q1_kernel(sf: float, seconds_budget: float = 60.0, quick: bool = False):
    """Headline: warm-table Q1 device throughput (data resident in HBM — the
    presto-benchmark LocalQueryRunner pattern, where benchmark pages are already
    in memory). Detail: the streaming-ingest run (host generation + upload
    overlapped with compute), reported as honest end-to-end WALL rows/s with no
    overlap-subtraction games."""
    from presto_tpu.models.kernels import q1_resident, q1_stream

    resident_rps, batch_rows, step_ms, _ = q1_resident(
        sf, batch_rows=1 << 20 if quick else 1 << 22,
        runs=5 if quick else 10)
    stream = {}
    try:
        rows, wall, gen_stall, compile_s, _ = q1_stream(
            sf, seconds_budget=seconds_budget)
        stream = {
            "rows": rows,
            "wall_s": round(wall, 3),
            "wall_rows_per_sec": round(rows / max(wall, 1e-9)),
            "hostgen_stall_s": round(gen_stall, 3),
            "first_compile_s": round(compile_s or 0, 2),
        }
    except Exception as e:
        stream = {"error": repr(e)[:300]}
    return resident_rps, batch_rows, step_ms, stream


class _CompileCounter:
    """Counts XLA compilations via the jax dispatch log (per-rung kernel
    counts feed the bench detail — VERDICT round-4 ask #2)."""

    def __enter__(self):
        import logging

        import jax as _jax

        self.n = 0
        outer = self

        class H(logging.Handler):
            def emit(self, record):
                if "Finished XLA compilation" in record.getMessage():
                    outer.n += 1

        self._handler = H()
        self._logger = logging.getLogger("jax._src.dispatch")
        self._prev_level = self._logger.level
        self._logger.addHandler(self._handler)
        self._logger.setLevel(logging.DEBUG)
        self._prev_flag = _jax.config.jax_log_compiles
        _jax.config.update("jax_log_compiles", True)
        return self

    def __exit__(self, *exc):
        import jax as _jax

        self._logger.removeHandler(self._handler)
        self._logger.setLevel(self._prev_level)
        _jax.config.update("jax_log_compiles", self._prev_flag)
        return False


def _blackbox_overhead(sql: str, schema: str, runs: int = 7) -> dict:
    """Always-on black-box ring overhead on warm walls: the same query, same
    schema, `query_blackbox` on (the production default) vs off (recorder
    compiled out). Both sides run on fresh runners over the process-global
    kernel/resident caches, runs strictly alternating so drift hits both
    equally, and the MEDIAN wall is compared — warm walls on small schemas
    have multi-x outliers (GC, XLA autotuning re-checks) that would swamp a
    mean of 3. The acceptance bar is <= 2% — recorded, not asserted: the
    bench blob is the measurement of record. Never fails the rung."""
    import statistics

    from presto_tpu.metadata import Session
    from presto_tpu.runner import LocalQueryRunner

    try:
        on = LocalQueryRunner(session=Session(catalog="tpch", schema=schema))
        off = LocalQueryRunner(session=Session(
            catalog="tpch", schema=schema,
            properties={"query_blackbox": False}))
        on.execute(sql)   # warm both paths (kernels + resident pages)
        off.execute(sql)
        on_w, off_w = [], []
        for _ in range(runs):
            t0 = time.perf_counter()
            on.execute(sql)
            on_w.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            off.execute(sql)
            off_w.append(time.perf_counter() - t0)
        on_med = statistics.median(on_w)
        off_med = statistics.median(off_w)
        return {"blackbox_on_wall_s": round(on_med, 4),
                "blackbox_off_wall_s": round(off_med, 4),
                "blackbox_overhead": round(on_med / max(off_med, 1e-9) - 1,
                                           4)}
    except Exception as e:  # noqa: BLE001 - observability must not kill rungs
        return {"blackbox_error": repr(e)[:200]}


def _traced_overlap(sql: str, schema: str) -> dict:
    """One flight-recorded run: exports the Chrome trace and derives the
    scan-vs-compute overlap ratio (how much of the scan pipeline's stage
    work ran WHILE driver quanta were executing — the overlap the streaming
    scan exists to create). Never fails the rung."""
    import json as _json

    from presto_tpu.metadata import Session
    from presto_tpu.runner import LocalQueryRunner
    from presto_tpu.utils import trace as _trace

    try:
        from presto_tpu.ops.scan import RESIDENT_CACHE

        # a warm scan replays resident device pages and skips the scan
        # pipeline — trace a COLD run so the ratio measures real ingest
        # overlapping compute
        RESIDENT_CACHE.clear()
        runner = LocalQueryRunner(session=Session(
            catalog="tpch", schema=schema,
            properties={"query_trace": True}))
        res = runner.execute(sql)
        with open(res.trace_path) as f:
            doc = _json.load(f)
        return {"trace_scan_compute_overlap": round(
                    _trace.overlap_ratio(doc, "scan", "driver"), 3),
                "trace_spans": _trace.span_categories(doc)}
    except Exception as e:  # noqa: BLE001 - observability must not kill rungs
        return {"trace_error": repr(e)[:200]}


def bench_sql_query(query_id: int, schema: str, seconds_budget: float,
                    escalate_to: str = None, escalate_budget_s: float = 30.0,
                    escalate_ratio: float = 100.0,
                    compare_unfused: bool = False,
                    record_trace: bool = False):
    """One rung of the SQL ladder: the FULL engine path (parse -> plan ->
    optimize -> drivers), the presto-benchmark BenchmarkSuite pattern run
    through LocalQueryRunner rather than hand-built pipelines — rung numbers
    measure what users get.

    The rung first runs at `schema`; if the measured warm wall extrapolated to
    `escalate_to` (x escalate_ratio rows) fits `escalate_budget_s`, it re-runs
    there and reports that instead — a slow build never blows the round's time
    budget but a fast one still gets measured at full scale.
    """
    from presto_tpu.metadata import Session
    from presto_tpu.models import hand_queries as hq
    from presto_tpu.models.tpch_sql import QUERIES
    from presto_tpu.runner import LocalQueryRunner

    sql = QUERIES[query_id]

    def measure(sch):
        runner = LocalQueryRunner(
            session=Session(catalog="tpch", schema=sch))
        t0 = time.time()
        with _CompileCounter() as cc:
            rows0 = len(runner.execute(sql).rows)  # warm-up compiles kernels
        compile_wall = time.time() - t0
        runs, t0, last = 0, time.time(), None
        while True:
            last = runner.execute(sql)
            runs += 1
            if time.time() - t0 > seconds_budget or runs >= 3:
                break
        wall = (time.time() - t0) / runs
        src_rows = hq.source_rows(f"q{query_id}", sch)
        out = {"schema": sch,
               "rows_per_sec": round(src_rows / wall),
               "source_rows": src_rows,
               "wall_s": round(wall, 3),
               "first_run_s": round(compile_wall, 3),
               "kernel_compiles": cc.n,
               "output_rows": rows0}
        # percentile observability (process-cumulative histograms from the
        # MetricsRegistry — the same numbers /v1/metrics serves)
        from presto_tpu.utils.metrics import METRICS
        wall_hist = METRICS.histogram_summary("query.wall_s")
        if wall_hist:
            out["query_wall_p50_s"] = wall_hist["p50"]
            out["query_wall_p99_s"] = wall_hist["p99"]
        disp_hist = METRICS.histogram_summary("segments.page_dispatch_s")
        if disp_hist:
            out["page_dispatch_p50_s"] = disp_hist["p50"]
            out["page_dispatch_p99_s"] = disp_hist["p99"]
        # fused-segment observability: per-segment dispatch/compile counts
        # of the LAST timed run (exec/local_planner segment compiler)
        seg = (last.stats or {}).get("segments") if last is not None else None
        if seg:
            out["segments"] = {
                "count": seg["count"], "dispatches": seg["dispatches"],
                "compiles": seg["compiles"],
                "fused": [s["operators"] for s in seg["segments"]]}
        return out

    def unfused_wall(sch):
        """One warm per-operator run at `sch` (global kernel/resident caches
        keep a fresh runner warm): the fusion speedup denominator. Runs only
        for the FINALLY-reported schema — measuring it pre-escalation would
        pay the unfused compile set twice for a discarded number."""
        runner = LocalQueryRunner(session=Session(
            catalog="tpch", schema=sch).with_properties(segment_fusion=False))
        try:
            runner.execute(sql)  # compile/warm the per-operator kernels
            t0 = time.time()
            runner.execute(sql)
            return {"unfused_wall_s": round(time.time() - t0, 3)}
        except Exception as e:
            return {"unfused_error": repr(e)[:200]}

    out = measure(schema)
    # the escalated schema costs ~(warm-up + >=1 timed run + recompile
    # slack) = >= 3x one run; guard on the predicted spend. The wall-ratio
    # prediction is far too pessimistic when the small-schema wall is FIXED
    # overhead (dispatch, not per-row work) — on the CPU backend (local,
    # cached compiles) predict from measured THROUGHPUT instead: per-row
    # rate only improves at scale, so src_rows/rate upper-bounds one run;
    # allow 2x the budget for that bound (measured: Q3 sf1 actual ~7s vs a
    # ~320s wall-ratio prediction and a ~105s throughput bound).
    import jax as _jax

    if _jax.default_backend() == "cpu":
        predicted = (hq.source_rows(f"q{query_id}", escalate_to or "sf1")
                     / max(out["rows_per_sec"], 1))
        fits = predicted <= 2 * escalate_budget_s
    else:
        fits = out["wall_s"] * escalate_ratio * 3 <= escalate_budget_s
    if escalate_to and fits:
        try:
            escalated = measure(escalate_to)
            escalated["small_schema"] = out
            out = escalated
        except Exception as e:  # keep the small-schema number
            out["escalate_error"] = repr(e)[:200]
    if compare_unfused:
        out.update(unfused_wall(out["schema"]))
    if record_trace:
        out.update(_traced_overlap(sql, out["schema"]))
        # the always-on black-box ring must be ~free: measured here on the
        # q3 rung (warm walls, recorder on vs compiled out) and recorded in
        # the blob — the ladder's standing <=2% overhead check
        out.update(_blackbox_overhead(sql, out["schema"]))
    return out


def bench_pcol_scan(sf: float, seconds_budget: float = 30.0,
                    materialize_budget_s: float = 240.0) -> dict:
    """Materialized-warehouse rung: Q6 over PCOL files via the file connector
    (mmap -> host view -> device upload -> fused filter+agg), the production
    shape where data is ingested once and scanned many times (the reference
    benchmarks run on materialized ORC, presto-benchto-benchmarks/tpch.yaml).
    The dataset materializes ONCE into .bench_data/ and is reused by every
    later bench run — the generator is out of the measured loop entirely.
    """
    from presto_tpu.connectors.file import FileConnector
    from presto_tpu.connectors.tpch.connector import TpchConnector
    from presto_tpu.metadata import CatalogManager, Session
    from presto_tpu.runner import LocalQueryRunner

    base = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        ".bench_data", "warehouse")
    # the source schema quantizes sf (sf1/sf2/...): name the table after the
    # schema actually materialized and report THAT schema's row count —
    # otherwise a fractional --sf reports rows/s against the wrong row total
    schema = "sf1" if sf <= 1 else f"sf{int(sf)}"
    sf = 1.0 if sf <= 1 else float(int(sf))
    table = f"lineitem_{schema}"
    catalogs = CatalogManager()
    catalogs.register("tpch", TpchConnector("tpch"))
    catalogs.register("warehouse", FileConnector("warehouse", base))
    runner = LocalQueryRunner(
        session=Session(catalog="warehouse", schema="bench"),
        catalogs=catalogs)
    out = {"schema": schema}
    exists = runner.metadata.get_table_handle(
        runner.session,
        runner.metadata.resolve_table_name(
            runner.session, ("warehouse", "bench", table))) is not None
    if not exists:
        t0 = time.time()
        runner.execute(
            f"create table warehouse.bench.{table} as "
            f"select l_quantity, l_extendedprice, l_discount, l_shipdate "
            f"from tpch.{schema}.lineitem")
        out["materialize_s"] = round(time.time() - t0, 1)
        if out["materialize_s"] > materialize_budget_s:
            out["note"] = "materialization over budget; scan still measured"
    import glob as _glob
    files = _glob.glob(os.path.join(base, "bench", table, "*"))
    out["file_bytes"] = sum(os.path.getsize(f) for f in files)
    q6 = (f"select sum(l_extendedprice * l_discount) as revenue "
          f"from warehouse.bench.{table} where l_shipdate >= date '1994-01-01'"
          f" and l_shipdate < date '1995-01-01'"
          f" and l_discount between 0.05 and 0.07 and l_quantity < 24")
    t0 = time.time()
    runner.execute(q6)  # compile + first mmap touch
    out["first_run_s"] = round(time.time() - t0, 2)
    runs, t0 = 0, time.time()
    last = None
    while True:
        last = runner.execute(q6)
        runs += 1
        if time.time() - t0 > seconds_budget or runs >= 5:
            break
    wall = (time.time() - t0) / runs
    from presto_tpu.connectors.tpch import generator as g
    src_rows = g.table_row_count("lineitem", sf)
    out.update({"rows": src_rows, "wall_s": round(wall, 3),
                "rows_per_sec": round(src_rows / wall)})
    # per-stage busy/stall attribution of the LAST timed run (the streaming
    # scan pipeline's read/decode/upload/compute breakdown) — bench rounds
    # compare these fields to see which stage the wall clock went to
    if last is not None and last.stats and last.stats.get("scan_pipeline"):
        out["stages"] = last.stats["scan_pipeline"]
    return out


def bench_multichip_exchange(n_devices: int = 2,
                             budget_s: float = 300.0) -> dict:
    """Streaming mesh-exchange rung: a distributed group-by + broadcast-join
    mix over an n-device VIRTUAL cpu mesh in a subprocess (the real-TPU mesh
    numbers come from the round driver's dryrun_multichip, which prints the
    same stats blob into MULTICHIP_*.json). Records per-exchange chunk
    counts, collective compile counts (expect <= one per (kind, shape) per
    query — the fixed chunk shape replaced the barrier path's per-pow2-bucket
    recompiles) and overlap/stall seconds."""
    import subprocess

    script = (
        "import os, json\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "flags = os.environ.get('XLA_FLAGS', '')\n"
        "if 'host_platform_device_count' not in flags:\n"
        f"    os.environ['XLA_FLAGS'] = (flags + "
        f"' --xla_force_host_platform_device_count={n_devices}').strip()\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from presto_tpu.metadata import Session\n"
        "from presto_tpu.parallel.mesh import MeshContext\n"
        "from presto_tpu.parallel.runner import DistributedQueryRunner\n"
        "from presto_tpu.utils import trace as _tr\n"
        "from presto_tpu.utils.metrics import METRICS\n"
        f"mesh = MeshContext(jax.devices()[:{n_devices}])\n"
        "r = DistributedQueryRunner(mesh, session=Session(\n"
        "    catalog='tpch', schema='tiny',\n"
        "    properties={'exchange_chunk_rows': 256, 'query_trace': True}))\n"
        "out = {}\n"
        "for name, sql in (\n"
        "    ('group_by', 'select o_custkey % 11, count(*), "
        "sum(o_totalprice) from orders group by 1'),\n"
        "    ('join', 'select c_name, o_orderkey from customer join orders "
        "on c_custkey = o_custkey order by o_orderkey limit 20'),\n"
        "):\n"
        "    res = r.execute(sql)\n"
        "    ex = dict((res.stats or {}).get('exchange', {}))\n"
        "    ex.pop('per_exchange', None)\n"
        "    if res.trace_path:\n"
        "        doc = json.load(open(res.trace_path))\n"
        "        ex['trace_overlap_ratio'] = round(\n"
        "            _tr.overlap_ratio(doc, 'exchange', 'driver'), 3)\n"
        "    out[name] = ex\n"
        "out['chunk_latency'] = "
        "METRICS.histogram_summary('exchange.chunk_latency_s')\n"
        "print('EXCH=' + json.dumps(out))\n")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=budget_s, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        for line in proc.stdout.splitlines():
            if line.startswith("EXCH="):
                out = json.loads(line[5:])
                out["n_devices"] = n_devices
                return out
        return {"error": (proc.stderr or proc.stdout)[-300:]}
    except Exception as e:  # noqa: BLE001 - the rung must never kill the run
        return {"error": repr(e)[:300]}


def drive_serving_clients(base: str, mix, expected, n_clients: int,
                          per_client: int, barrier_timeout_s: float = 60.0,
                          join_timeout_s: float = 600.0) -> dict:
    """Shared concurrent-client driver for the `serving` bench rung AND
    `__graft_entry__.dryrun_serving` (one harness, two reporters): N client
    threads round-robin the mixed TPC-H workload through /v1/statement,
    row-checking every response against `expected`. Returns {"errors",
    "walls", "lats", "wall"}; a client that never finishes within the join
    timeout is an ERROR — a wedged serving stack must never be folded into
    a (distorted) passing qps number."""
    import threading

    from presto_tpu.client import execute as http_execute
    from presto_tpu.models.tpch_sql import QUERIES

    errors: list = []
    walls = [0.0] * n_clients
    lats: list = [[] for _ in range(n_clients)]
    barrier = threading.Barrier(n_clients)

    def client(i: int) -> None:
        try:
            barrier.wait(timeout=barrier_timeout_s)
            t0 = time.perf_counter()
            for k in range(per_client):
                qid = mix[(i + k) % len(mix)]
                q0 = time.perf_counter()
                rows = http_execute(base, QUERIES[qid])
                lats[i].append(time.perf_counter() - q0)
                if rows != expected[qid]:
                    errors.append(f"client {i} q{qid}: rows diverged "
                                  "under concurrent load")
            walls[i] = time.perf_counter() - t0
        except BaseException as e:  # noqa: BLE001 - reported to the caller
            errors.append(f"client {i}: {e!r}")

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"serve-client-{i}")
               for i in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=join_timeout_s)
    wall = time.perf_counter() - t0
    if not errors and not all(walls):
        errors.append("a client never finished (join timeout) — "
                      "serving stack wedged")
    return {"errors": errors, "walls": walls, "lats": lats, "wall": wall}


def serving_percentile(lats, q: float):
    """Client-observed latency percentile over the measured phase only."""
    flat = sorted(x for ls in lats for x in ls)
    if not flat:
        return None
    return round(flat[min(len(flat) - 1, int(q * len(flat)))], 4)


def bench_serving(clients=(1, 4, 8), per_client: int = 4,
                  schema: str = "tiny") -> dict:
    """Concurrent-load serving rung: N concurrent clients through the HTTP
    server (/v1/statement) on a mixed TPC-H workload (Q1/Q3/Q6). Reports
    per-N queries/sec, client-observed wall p50/p99, a fairness ratio
    (slowest/fastest client wall — 1.0 = perfectly fair shared pools), plus
    the engine-side `query.wall_s` histogram (PR 6) and the shared-pool
    step counters. Results are row-checked against the warmup oracle; the
    c4/c1 qps ratio is the concurrency-overlap verdict (>1 = the shared
    pools genuinely overlap tenants, not serialize them)."""
    from presto_tpu.client import execute as http_execute
    from presto_tpu.exec import shared_pools as _sp
    from presto_tpu.metadata import Session
    from presto_tpu.models.tpch_sql import QUERIES
    from presto_tpu.runner import LocalQueryRunner
    from presto_tpu.server.http_server import PrestoTpuServer
    from presto_tpu.utils.metrics import METRICS

    mix = [1, 3, 6]
    runner = LocalQueryRunner(session=Session(catalog="tpch", schema=schema))
    server = PrestoTpuServer(runner, port=0)
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    out = {"schema": schema, "mix": [f"q{q}" for q in mix],
           "per_client": per_client, "rungs": {}}
    try:
        # warmup + row oracle: every kernel compiles here, so the measured
        # rungs compare execution under load, not compilation
        expected = {qid: http_execute(base, QUERIES[qid]) for qid in mix}

        def run_rung(n: int) -> dict:
            r = drive_serving_clients(base, mix, expected, n, per_client)
            if r["errors"]:
                return {"error": "; ".join(r["errors"][:3])[:300]}
            wall = max(r["walls"])
            return {"clients": n, "queries": n * per_client,
                    "wall_s": round(wall, 3),
                    "qps": round(n * per_client / wall, 3),
                    "query_wall_p50_s": serving_percentile(r["lats"], 0.50),
                    "query_wall_p99_s": serving_percentile(r["lats"], 0.99),
                    "fairness_ratio": round(
                        wall / max(min(r["walls"]), 1e-9), 3)}

        for n in clients:
            out["rungs"][f"c{n}"] = run_rung(int(n))
        q1 = out["rungs"].get("c1", {}).get("qps")
        q4 = out["rungs"].get("c4", {}).get("qps")
        if q1 and q4:
            # > 1.0 = aggregate throughput grew with concurrency (overlap)
            out["overlap_speedup_4c"] = round(q4 / q1, 3)
        # engine-side wall histogram (MetricsRegistry, PR 6) + pool
        # telemetry. The histogram is PROCESS-CUMULATIVE — it includes the
        # warmup oracles and any rungs run earlier in this process, so the
        # per-rung client-observed percentiles above are the load numbers;
        # this blob is the /v1/metrics surface check, labeled accordingly
        out["engine_query_wall_hist_cumulative"] = \
            METRICS.histogram_summary("query.wall_s") or None
        out["scan_pool"] = _sp.SCAN_POOL.stats()
        out["exchange_pool"] = _sp.EXCHANGE_POOL.stats()
        return out
    finally:
        server.stop()


def bench_chaos() -> dict:
    """Chaos rung (reported, never gated): the same high-cardinality
    aggregation on an in-process 2-worker HTTP cluster, run (a) clean,
    (b) with a worker killed mid-stream under TASK retry — delivered+acked
    chunks must replay from the producer spool — and (c) with one leaf
    stalled far past the straggler-speculation threshold. Reports recovery
    overhead (wall vs clean), attempts/retries/speculations, the peak
    spooled bytes the workers reported, and row correctness — the
    robustness analogue of a perf number."""
    import threading as _th
    import urllib.request as _rq

    from presto_tpu.cluster import faults
    from presto_tpu.cluster.coordinator import ClusterQueryRunner
    from presto_tpu.cluster.scheduler import _remote_source_ids
    from presto_tpu.cluster.worker import WorkerServer
    from presto_tpu.metadata import Session
    from presto_tpu.runner import LocalQueryRunner

    sql = ("select l_orderkey, count(*), sum(l_quantity) "
           "from lineitem group by l_orderkey")
    want_rows = sorted(LocalQueryRunner(
        session=Session(catalog="tpch", schema="tiny")).execute(sql).rows)

    def run_mode(mode: str) -> dict:
        props = {"retry_policy": "TASK",
                 "exchange_flush_rows": 512,
                 "retry_initial_delay_s": 0.01,
                 "retry_max_delay_s": 0.05}
        if mode == "speculation":
            props.update({"speculative_execution": True,
                          "speculation_min_wall_s": 0.4,
                          "speculation_multiplier": 2.0})
        runner = ClusterQueryRunner(
            session=Session(catalog="tpch", schema="tiny", properties=props),
            min_workers=2, worker_wait_s=10.0)
        workers = [WorkerServer(port=0).start() for _ in range(2)]
        dead, stop = set(), _th.Event()
        for w in workers:
            runner.nodes.announce(w.node_id, w.uri)

        def keep_alive():
            while not stop.wait(0.5):
                for w in workers:
                    if w.node_id not in dead:
                        runner.nodes.announce(w.node_id, w.uri)
                for nid in list(dead):
                    runner.nodes.remove(nid)

        _th.Thread(target=keep_alive, daemon=True).start()
        sub = runner.plan_sql(sql)
        leaf = next(f.id for f in sub.fragments
                    if not _remote_source_ids(f.root)
                    and f.id != sub.root_fragment.id)
        inj = faults.FaultInjector(seed=23)
        if mode == "mid_stream_kill":
            victim = min(workers, key=lambda w: w.node_id)
            killed = _th.Event()

            def kill(ctx):
                token = int(ctx["path"].partition("?")[0]
                            .rstrip("/").rsplit("/", 1)[-1])
                if token < 1 or killed.is_set():
                    return
                killed.set()
                dead.add(victim.node_id)
                victim.stop()
                runner.nodes.remove(victim.node_id)
                raise faults.InjectedDisconnect("worker killed")

            # kill only once a consumer asks for token >= 1 of the victim's
            # leaf stream: chunk 0 was delivered AND acked by then, so the
            # recovery must replay mid-stream from the spool
            inj.add("worker.results", faults.CALLBACK,
                    node_id=victim.node_id, task_re=rf"\.{leaf}\.0$",
                    times=None, callback=kill)
        elif mode == "speculation":
            inj.add("worker.task_run", faults.DELAY, delay_s=5.0, times=1,
                    task_re=rf"\.{leaf}\.0$")
        faults.install(inj)

        # sample the workers' reported spool while the query runs: the
        # acceptance surface for "spooled bytes live in the unified pool"
        spool_peak = [0]
        mon_stop = _th.Event()

        def spool_monitor():
            while not mon_stop.wait(0.05):
                for w in workers:
                    if w.node_id in dead:
                        continue
                    try:
                        with _rq.urlopen(f"{w.uri}/v1/status",
                                         timeout=1.0) as r:
                            st = json.loads(r.read())
                        spool_peak[0] = max(spool_peak[0],
                                            int(st.get("spooledBytes") or 0))
                    except Exception:  # noqa: BLE001 - monitor is best-effort
                        pass

        _th.Thread(target=spool_monitor, daemon=True).start()
        t0 = time.time()
        try:
            got = runner.execute(sql)
            wall = time.time() - t0
        finally:
            mon_stop.set()
            stop.set()
            faults.clear()
            runner.detector.stop()
            for w in workers:
                if w.node_id not in dead:
                    w.stop()
        return {"wall_s": round(wall, 3),
                "rows_match": sorted(got.rows) == want_rows,
                "query_attempts": got.stats.get("query_attempts"),
                "task_retries": got.stats.get("task_retries"),
                "task_speculations": got.stats.get("task_speculations"),
                "faults_injected": got.stats.get("faults_injected"),
                "spooled_bytes_peak": spool_peak[0]}

    out = {"schema": "tiny"}
    for mode in ("clean", "mid_stream_kill", "speculation"):
        out[mode] = run_mode(mode)
    clean = out["clean"].get("wall_s")
    kill_wall = out["mid_stream_kill"].get("wall_s")
    if clean and kill_wall:
        out["recovery_overhead_x"] = round(kill_wall / clean, 3)
    return out


def bench_churn(schema: str = "tiny") -> dict:
    """Membership-churn rung (reported, never gated): a high-cardinality
    aggregation on an in-process 2-worker HTTP cluster, run (a) clean and
    (b) with the membership changing mid-query — once the query is
    mid-stream (a consumer has acked chunk 0 of the victim's leaf output)
    a THIRD worker joins AND the victim is gracefully drained. Unlike the
    chaos rung's kill, a planned drain must be invisible: the victim's
    tasks are handed to replacements via the exactly-once replay splice,
    so rows stay identical AND `query_attempts == 1` — no query-level
    retry, no 410. Reports recovery overhead vs clean, the drain handoff
    summary, and the peak spooled bytes (overall + inside the drain
    window, where the pinned spools do the replaying)."""
    import threading as _th
    import urllib.request as _rq

    from presto_tpu.cluster import faults
    from presto_tpu.cluster.coordinator import ClusterQueryRunner
    from presto_tpu.cluster.scheduler import _remote_source_ids
    from presto_tpu.cluster.worker import WorkerServer
    from presto_tpu.metadata import Session
    from presto_tpu.runner import LocalQueryRunner

    sql = ("select l_suppkey, count(*), sum(l_quantity) "
           "from lineitem group by l_suppkey")
    want_rows = sorted(LocalQueryRunner(
        session=Session(catalog="tpch", schema=schema)).execute(sql).rows)

    def run_mode(mode: str) -> dict:
        props = {"retry_policy": "TASK",
                 "exchange_flush_rows": 512,
                 "retry_initial_delay_s": 0.01,
                 "retry_max_delay_s": 0.05}
        runner = ClusterQueryRunner(
            session=Session(catalog="tpch", schema=schema, properties=props),
            min_workers=2, worker_wait_s=10.0)
        workers = [WorkerServer(port=0).start() for _ in range(2)]
        stop = _th.Event()
        for w in workers:
            runner.nodes.announce(w.node_id, w.uri)

        def keep_alive():
            # re-announce while ACTIVE or DRAINING (a draining node still
            # serves its streams); stop at DRAINED — drain_worker removed it
            # from discovery and announcing again would resurrect it
            while not stop.wait(0.5):
                for w in list(workers):
                    if w.state in ("ACTIVE", "DRAINING"):
                        runner.nodes.announce(w.node_id, w.uri)

        _th.Thread(target=keep_alive, daemon=True).start()
        sub = runner.plan_sql(sql)
        leaf = next(f.id for f in sub.fragments
                    if not _remote_source_ids(f.root)
                    and f.id != sub.root_fragment.id)
        drain_result: dict = {}
        drain_window = [0.0, 0.0]
        churned = _th.Event()
        if mode == "churn":
            victim = min(workers, key=lambda w: w.node_id)

            def churn_async():
                # membership change off the handler thread: ADD a worker,
                # then gracefully DRAIN the victim mid-stream. drain_worker
                # re-places the victim's tasks through the replay splice
                # and deregisters the node once it reports DRAINED.
                drain_window[0] = time.time()
                joiner = WorkerServer(port=0).start()
                workers.append(joiner)
                runner.nodes.announce(joiner.node_id, joiner.uri)
                drain_result.update(runner.drain_worker(
                    victim.node_id, signal={"trigger": "churn-rung"}))
                drain_window[1] = time.time()

            def trigger(ctx):
                token = int(ctx["path"].partition("?")[0]
                            .rstrip("/").rsplit("/", 1)[-1])
                if token < 1 or churned.is_set():
                    return
                churned.set()
                _th.Thread(target=churn_async, daemon=True).start()

            # fire only once a consumer asks for token >= 1 of the victim's
            # leaf stream: chunk 0 was delivered AND acked by then, so the
            # drain handoff must splice mid-stream from the pinned spool.
            # The callback raises nothing — it only triggers the churn.
            inj = faults.FaultInjector(seed=29)
            inj.add("worker.results", faults.CALLBACK,
                    node_id=victim.node_id, task_re=rf"\.{leaf}\.0$",
                    times=None, callback=trigger)
            faults.install(inj)

        spool_peak = [0, 0]  # overall, inside the drain window
        mon_stop = _th.Event()

        def spool_monitor():
            while not mon_stop.wait(0.05):
                now = time.time()
                for w in list(workers):
                    if w.state == "SHUT_DOWN":
                        continue
                    try:
                        with _rq.urlopen(f"{w.uri}/v1/status",
                                         timeout=1.0) as r:
                            st = json.loads(r.read())
                        b = int(st.get("spooledBytes") or 0)
                        spool_peak[0] = max(spool_peak[0], b)
                        if drain_window[0] and now >= drain_window[0] \
                                and not drain_window[1]:
                            spool_peak[1] = max(spool_peak[1], b)
                    except Exception:  # noqa: BLE001 - monitor is best-effort
                        pass

        _th.Thread(target=spool_monitor, daemon=True).start()
        t0 = time.time()
        try:
            got = runner.execute(sql)
            wall = time.time() - t0
        finally:
            mon_stop.set()
            stop.set()
            faults.clear()
            runner.detector.stop()
            for w in list(workers):
                w.stop()
        entry = {"wall_s": round(wall, 3),
                 "rows_match": sorted(got.rows) == want_rows,
                 "query_attempts": got.stats.get("query_attempts"),
                 "task_retries": got.stats.get("task_retries"),
                 "spooled_bytes_peak": spool_peak[0]}
        if mode == "churn":
            entry["churn_fired"] = churned.is_set()
            entry["drain"] = drain_result or None
            entry["spooled_bytes_peak_drain_window"] = spool_peak[1]
        return entry

    out = {"schema": schema}
    for mode in ("clean", "churn"):
        out[mode] = run_mode(mode)
    clean = out["clean"].get("wall_s")
    churn_wall = out["churn"].get("wall_s")
    if clean and churn_wall:
        out["recovery_overhead_x"] = round(churn_wall / clean, 3)
    return out


def bench_spill(quick: bool = False) -> dict:
    """Spill rung (reported, never gated): TPC-H Q1 and Q3 run uncapped,
    then under a `memory_pool_bytes` cap far smaller than their live hash
    state — the capped run must survive by walking the memory ladder
    (device HBM -> host RAM -> disk PCOL runs, exec/spill.py) and return
    IDENTICAL rows. Reports both walls, the spill traffic the capped run
    generated, and the overhead ratio — the price of graceful degradation,
    the robustness analogue of a perf number. (Q1's tiny group domain uses
    the direct builder and may legitimately spill nothing; Q3's join build
    and high-cardinality aggregation are the spilling path.)"""
    from presto_tpu.metadata import Session
    from presto_tpu.models.tpch_sql import QUERIES
    from presto_tpu.runner import LocalQueryRunner
    from presto_tpu.utils.metrics import METRICS

    schema = "tiny" if quick else "sf1"
    out = {"schema": schema}
    for qid in (1, 3):
        sql = QUERIES[qid]
        base = LocalQueryRunner(
            session=Session(catalog="tpch", schema=schema))
        base.execute(sql)  # warm-up compiles the kernels
        t0 = time.time()
        want = base.execute(sql).rows
        base_wall = time.time() - t0
        capped = LocalQueryRunner(session=Session(
            catalog="tpch", schema=schema,
            properties={"memory_pool_bytes": 1}))
        capped.execute(sql)  # warm-up (also spills; traffic not counted)
        w0 = METRICS.counter_value("spill.bytes_written")
        r0 = METRICS.counter_value("spill.bytes_read")
        t0 = time.time()
        got = capped.execute(sql).rows
        capped_wall = time.time() - t0
        entry = {
            "schema": schema,
            "uncapped_wall_s": round(base_wall, 3),
            # wall_s is the CAPPED wall so --compare trends the survival
            # path itself (report-only: spill I/O dominates, not the engine)
            "wall_s": round(capped_wall, 3),
            "rows_match": sorted(got) == sorted(want),
            "spill_bytes_written": int(
                METRICS.counter_value("spill.bytes_written") - w0),
            "spill_bytes_read": int(
                METRICS.counter_value("spill.bytes_read") - r0),
        }
        if base_wall > 0:
            entry["spill_overhead_x"] = round(capped_wall / base_wall, 3)
        out[f"q{qid}"] = entry
    return out


def bench_hash_kernels(quick: bool = False, skew_devices: int = 4,
                       skew_budget_s: float = 600.0) -> dict:
    """Pallas hash-kernel rung (VERDICT ask #6: one Pallas kernel that wins
    — or a written negative result). Three measurements:

    - micro: open-addressing insert+probe (ops/pallas_hash.py, interpreted
      off-TPU) vs the sorted build (argsort) + binary-search probe, same
      keys, SF1-scale N — the isolated build/probe wall comparison;
    - engine: warm TPC-H Q3 wall with `hash_kernels=pallas` vs `sorted`
      (the strategy knob end to end, SF1 on the full ladder);
    - skew: a 99%-one-key partitioned INNER join on a virtual mesh
      (subprocess), skew-aware vs not — wall + per-partition row spread.

    The rung's top-level `wall_s` is the DEFAULT path's Q3 wall, so
    `--compare` gates the production path; the pallas numbers ride along as
    the measured verdict (win or dated negative result, recorded either
    way)."""
    import statistics

    import jax
    import jax.numpy as jnp

    from presto_tpu.ops import pallas_hash as ph
    from presto_tpu.ops.hash_join import (_probe_match_sorted_unique,
                                          _sorted_kernel_ck)

    out = {"interpreted": ph.interpret_mode()}

    def median_wall(fn, runs=5):
        walls = []
        for _ in range(runs):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            walls.append(time.perf_counter() - t0)
        return statistics.median(walls)

    # ---- micro: build + probe walls on identical keys --------------------
    n = 1 << 17 if quick else 1 << 20
    rng = np.random.RandomState(42)
    keys = jnp.asarray(rng.permutation(8 * n)[:n].astype(np.int64))
    mask = jnp.ones(n, dtype=jnp.bool_)
    probes = jnp.asarray(rng.randint(0, 8 * n, n).astype(np.int64))
    slots = ph.table_slots(n)
    insert = ph.insert_table_jit(1, n, slots)
    (slot_keys,), slot_rows, _gid, stats = jax.block_until_ready(
        insert((keys,), mask))
    trips = ph.probe_trips_for(int(np.asarray(stats)[1]))
    import functools as _ft
    pallas_probe = jax.jit(_ft.partial(ph.probe_table, trips=trips))
    sorted_key, sorted_row = jax.block_until_ready(
        _sorted_kernel_ck(keys, mask))
    micro = {
        "n_rows": n, "table_slots": slots, "probe_trips": trips,
        "pallas_build_wall_s": round(
            median_wall(lambda: insert((keys,), mask)), 4),
        "sorted_build_wall_s": round(
            median_wall(lambda: _sorted_kernel_ck(keys, mask)), 4),
        "pallas_probe_wall_s": round(
            median_wall(lambda: pallas_probe(slot_keys, slot_rows, probes,
                                             mask)), 4),
        "sorted_probe_wall_s": round(
            median_wall(lambda: _probe_match_sorted_unique(
                sorted_key, sorted_row, probes, (probes,), mask,
                (keys,))), 4),
    }
    micro["build_speedup"] = round(
        micro["sorted_build_wall_s"] /
        max(micro["pallas_build_wall_s"], 1e-9), 3)
    micro["probe_speedup"] = round(
        micro["sorted_probe_wall_s"] /
        max(micro["pallas_probe_wall_s"], 1e-9), 3)
    out["micro"] = micro

    # ---- engine: Q3 warm wall, strategy knob end to end -------------------
    from presto_tpu.metadata import Session
    from presto_tpu.runner import LocalQueryRunner
    from presto_tpu.models.tpch_sql import QUERIES
    from presto_tpu.utils.metrics import METRICS

    schema = "tiny" if quick else "sf1"
    engine = {"schema": schema}
    for strategy in ("sorted", "pallas"):
        runner = LocalQueryRunner(session=Session(
            catalog="tpch", schema=schema,
            properties={"hash_kernels": strategy}))
        before = METRICS.snapshot().get("pallas.join_builds", 0)
        runner.execute(QUERIES[3])  # warm
        walls = []
        for _ in range(2 if quick else 3):
            t0 = time.perf_counter()
            runner.execute(QUERIES[3])
            walls.append(time.perf_counter() - t0)
        engine[f"{strategy}_q3_wall_s"] = round(statistics.median(walls), 3)
        if strategy == "pallas":
            engine["pallas_join_builds"] = \
                METRICS.snapshot().get("pallas.join_builds", 0) - before
    engine["pallas_vs_sorted"] = round(
        engine["sorted_q3_wall_s"] / max(engine["pallas_q3_wall_s"], 1e-9),
        3)
    out["engine"] = engine
    out["wall_s"] = engine["sorted_q3_wall_s"]  # --compare gates the default

    # ---- skew: 99%-one-key join, spread + wall (subprocess mesh) ----------
    if not quick:
        out["skew"] = _bench_skew_join(skew_devices, skew_budget_s)
    return out


def _bench_skew_join(n_devices: int, budget_s: float) -> dict:
    """Skew-aware repartitioning on a virtual mesh in a subprocess: the
    99%-one-key INNER join with spreading on vs off — wall clock and the
    per-partition delivered-row counts from the new exchange stats."""
    import subprocess

    script = (
        "import os, json, time\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "flags = os.environ.get('XLA_FLAGS', '')\n"
        "if 'host_platform_device_count' not in flags:\n"
        f"    os.environ['XLA_FLAGS'] = (flags + "
        f"' --xla_force_host_platform_device_count={n_devices}').strip()\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "from presto_tpu.metadata import Session\n"
        "from presto_tpu.parallel.mesh import MeshContext\n"
        "from presto_tpu.parallel.runner import DistributedQueryRunner\n"
        f"mesh = MeshContext(jax.devices()[:{n_devices}])\n"
        "sql = ('select count(*), sum(o.k) from '\n"
        "       '(select case when o_orderkey % 100 = 0 then o_custkey '\n"
        "       ' else 7 end as k from orders) o '\n"
        "       'join (select c_custkey as k from customer) c "
        "on o.k = c.k')\n"
        "out = {}\n"
        "rows = None\n"
        "for name, aware in (('skew_off', False), ('skew_on', True)):\n"
        "    r = DistributedQueryRunner(mesh, session=Session(\n"
        "        catalog='tpch', schema='sf1', properties={\n"
        "            'join_distribution_type': 'PARTITIONED',\n"
        "            'skew_aware_exchange': aware}))\n"
        "    t0 = time.perf_counter()\n"
        "    res = r.execute(sql)\n"
        "    out[name + '_wall_s'] = round(time.perf_counter() - t0, 2)\n"
        "    if rows is None:\n"
        "        rows = res.rows\n"
        "    elif res.rows != rows:\n"
        "        out['error'] = 'rows diverged between skew modes'\n"
        "    for e in (res.stats or {}).get('exchange', {}).get(\n"
        "            'per_exchange', []):\n"
        "        if e.get('skew_role') == 'probe' or (\n"
        "                not aware and e.get('kind') == 'repartition'\n"
        "                and max(e.get('partition_rows', [0])) >\n"
        "                0.5 * max(sum(e.get('partition_rows', [1])), 1)):\n"
        "            out[name + '_partition_rows'] = e['partition_rows']\n"
        "            if aware:\n"
        "                out['hot_keys'] = e.get('hot_keys', 0)\n"
        "print('SKEW=' + json.dumps(out))\n")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", script], capture_output=True, text=True,
            timeout=budget_s, env=dict(os.environ, JAX_PLATFORMS="cpu"))
        for line in proc.stdout.splitlines():
            if line.startswith("SKEW="):
                skew = json.loads(line[5:])
                skew["n_devices"] = n_devices
                parts = skew.get("skew_on_partition_rows")
                if parts:
                    skew["partitions_used"] = sum(p > 0 for p in parts)
                return skew
        return {"error": (proc.stderr or proc.stdout)[-300:]}
    except Exception as e:  # noqa: BLE001 - the rung must never kill the run
        return {"error": repr(e)[:300]}


WALL_REGRESSION_THRESHOLD = 0.15


def compare_benches(prev: dict, cur: dict,
                    threshold: float = WALL_REGRESSION_THRESHOLD) -> dict:
    """Per-rung wall deltas of two bench blobs (the regression gate behind
    `--compare prev.json`). A rung regresses when its warm wall grew more
    than `threshold` on the SAME schema and platform; rungs missing from
    either blob, schema changes and platform changes are reported but never
    gate — a bench run that fell back to CPU must not read as a 10x
    regression of the TPU number."""
    pd = prev.get("detail", {}) or {}
    cd = cur.get("detail", {}) or {}
    deltas = {}
    regressions = []

    def record(rung, p, c, gate):
        pw, cw = p.get("wall_s"), c.get("wall_s")
        if not (isinstance(pw, (int, float)) and pw > 0
                and isinstance(cw, (int, float))):
            return
        delta = (cw - pw) / pw
        entry = {"prev_wall_s": pw, "cur_wall_s": cw,
                 "delta": round(delta, 4), "gated": gate}
        deltas[rung] = entry
        if gate and delta > threshold:
            entry["regression"] = True
            regressions.append(rung)

    comparable = pd.get("platform") == cd.get("platform")
    for rung in ("q6", "q1", "q3", "pcol_q6"):
        p, c = pd.get(rung) or {}, cd.get(rung) or {}
        same_schema = p.get("schema") == c.get("schema")
        record(rung, p, c, gate=comparable and same_schema)
    # hash_kernels rung: its wall_s is the DEFAULT (sorted) Q3 wall — the
    # pallas/skew numbers are a recorded comparison, not a gate
    p = pd.get("hash_kernels") or {}
    c = cd.get("hash_kernels") or {}
    same_schema = (p.get("engine") or {}).get("schema") == \
        (c.get("engine") or {}).get("schema")
    record("hash_kernels", p, c, gate=comparable and same_schema)
    for key in sorted((pd.get("serving") or {}).get("rungs", {})):
        p = (pd.get("serving") or {}).get("rungs", {}).get(key) or {}
        c = (cd.get("serving") or {}).get("rungs", {}).get(key) or {}
        # same WORKLOAD, not just same platform: a --quick blob's serving
        # rungs run fewer queries per client — their walls are not
        # comparable to a full run's and must never gate
        same_load = (p.get("queries") == c.get("queries")
                     and p.get("clients") == c.get("clients"))
        record(f"serving.{key}", p, c, gate=comparable and same_load)
    # chaos rung: recovery walls are dominated by injected faults and retry
    # backoff, not engine speed — reported for trend-watching, never gated
    for key in ("clean", "mid_stream_kill", "speculation"):
        p = (pd.get("chaos") or {}).get(key) or {}
        c = (cd.get("chaos") or {}).get(key) or {}
        record(f"chaos.{key}", p, c, gate=False)
    # churn rung: the churn wall includes a live drain handoff and the
    # clean/churn pair is the signal — reported for trend-watching, never
    # gated
    for key in ("clean", "churn"):
        p = (pd.get("churn") or {}).get(key) or {}
        c = (cd.get("churn") or {}).get(key) or {}
        record(f"churn.{key}", p, c, gate=False)
    # spill rung: capped walls are dominated by spill I/O and revocation
    # cadence, not engine speed — reported for trend-watching, never gated
    for key in ("q1", "q3"):
        p = (pd.get("spill") or {}).get(key) or {}
        c = (cd.get("spill") or {}).get(key) or {}
        record(f"spill.{key}", p, c, gate=False)
    return {"threshold": threshold, "comparable_platform": comparable,
            "prev_platform": pd.get("platform"),
            "cur_platform": cd.get("platform"),
            "deltas": deltas, "regressions": regressions}


def _cpu_engine_q3_baseline(budget_s: float = 300.0) -> int:
    """Q3 SF1 through the SAME engine pinned to the CPU backend, measured in
    a subprocess (the single-node CPU engine baseline the TPU number is
    judged against). Returns rows/s, or a round-4-measured fallback if the
    subprocess fails."""
    import subprocess

    script = (
        "import os; os.environ['JAX_PLATFORMS']='cpu';\n"
        "import jax; jax.config.update('jax_platforms','cpu')\n"
        "import time\n"
        "from presto_tpu.runner import LocalQueryRunner\n"
        "from presto_tpu.metadata import Session\n"
        "from presto_tpu.models.tpch_sql import QUERIES\n"
        "from presto_tpu.models import hand_queries as hq\n"
        "r = LocalQueryRunner(session=Session(catalog='tpch', schema='sf1'))\n"
        "r.execute(QUERIES[3])\n"
        "t0=time.time(); r.execute(QUERIES[3]); w=time.time()-t0\n"
        "print('RPS=' + str(round(hq.source_rows('q3','sf1')/w)))\n")
    try:
        out = subprocess.run([sys.executable, "-c", script],
                             capture_output=True, text=True,
                             timeout=budget_s,
                             env=dict(os.environ, JAX_PLATFORMS="cpu"))
        for line in out.stdout.splitlines():
            if line.startswith("RPS="):
                return int(line[4:])
    except Exception:
        pass
    return 2_268_981  # round-4 measured live CPU engine Q3 SF1 rows/s


def cpu_baseline_rows_per_sec(sample_rows: int = 2_000_000) -> float:
    """Single-node CPU reference: numpy evaluation of the same Q1 arithmetic
    (the presto-benchmark HandTpchQuery1 pattern on this host)."""
    from presto_tpu.connectors.tpch import generator as g

    cols = ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
            "l_discount", "l_tax", "l_shipdate"]
    data = g.lineitem_for_orders(0, sample_rows // 4, 1.0, cols)
    n = len(data["l_returnflag"])
    t0 = time.time()
    keep = data["l_shipdate"] <= 10471
    gid = (data["l_returnflag"] * 2 + data["l_linestatus"]).astype(np.int64)
    disc_price = data["l_extendedprice"] * (100 - data["l_discount"])
    charge = disc_price * (100 + data["l_tax"])
    for col in (data["l_quantity"], data["l_extendedprice"], disc_price, charge,
                data["l_discount"]):
        np.bincount(gid[keep], weights=col[keep].astype(np.float64), minlength=6)
    np.bincount(gid[keep], minlength=6)
    dt = time.time() - t0
    return n / dt


# (env var, presto_tpu.utils module, result-blob key, what it would skew)
_SANITIZERS = (
    ("PRESTO_TPU_LOCKSAN", "locksan", "locksan",
     "instrumented locks would skew every number"),
    ("PRESTO_TPU_LEAKSAN", "leaksan", "leaksan",
     "instrumented lifecycles would skew the numbers"),
    ("PRESTO_TPU_COMPILESAN", "compilesan", "compilesan",
     "per-build key tracking would skew compile-path timings"),
)


def _strip_sanitizer_env():
    """Never benchmark instrumented code: a stray sanitizer env var from a
    debugging run would silently tax the hot path in the numbers. Strip
    each env (subprocess rungs inherit it), uninstall if the import hook
    already fired, and RECORD the off state in the result blob."""
    import importlib

    for env, mod_name, key, why in _SANITIZERS:
        if os.environ.pop(env, None):
            print(f"bench: {env} was set — sanitizer disabled for "
                  f"benchmarking ({why})", file=sys.stderr)
            try:
                mod = importlib.import_module(f"presto_tpu.utils.{mod_name}")
                mod.uninstall()
            except Exception:  # noqa: BLE001 - presto_tpu not imported yet: env strip suffices
                pass
        DETAIL[key] = False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=10.0)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--platform", default=None,
                    help="run on this jax platform (sets JAX_PLATFORMS); "
                         "without `cpu` here, anything but a TPU is an error")
    ap.add_argument("--compare", default=None, metavar="PREV_JSON",
                    help="compare per-rung warm walls against a previous "
                         "BENCH_r*.json and exit non-zero on a >15%% wall "
                         "regression — the ladder doubles as a gate")
    args = ap.parse_args()
    sf = 1.0 if args.quick else args.sf
    if args.platform:
        # before anything imports jax, which reads it once
        os.environ["JAX_PLATFORMS"] = args.platform

    _strip_sanitizer_env()

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and args.platform != "cpu":
        print(f"bench: jax reports platform {platform!r}, not a TPU; pass "
              "--platform cpu for the labelled CPU ladder", file=sys.stderr)
        sys.exit(1)
    detail = DETAIL
    detail["platform"] = platform

    # ladder rungs: the full SQL engine — on an accelerator, straight at SF1
    # (warm runs replay the resident device pages, so tiny-schema numbers
    # would only measure dispatch overhead); under --platform cpu, tiny with
    # escalation so a slow environment never blows the round's time budget
    rung_budget = 5.0 if args.quick else 15.0
    for rung, qid in (("q6", 6), ("q1", 1), ("q3", 3)):
        # q1/q3 additionally record per-segment dispatch counts and the
        # fused-vs-unfused warm wall (the segment compiler's win, measured)
        compare = rung in ("q1", "q3") and not args.quick
        try:
            # the q3 rung additionally records a flight-recorded run: the
            # Chrome-trace-derived scan-vs-compute overlap ratio
            record_trace = rung == "q3" and not args.quick
            if platform != "cpu" and not args.quick:
                detail[rung] = bench_sql_query(
                    qid, schema="sf1", seconds_budget=rung_budget,
                    compare_unfused=compare, record_trace=record_trace)
            else:
                detail[rung] = bench_sql_query(
                    qid, schema="tiny", seconds_budget=rung_budget,
                    escalate_to=None if args.quick else "sf1",
                    escalate_budget_s=60.0, compare_unfused=compare,
                    record_trace=record_trace)
        except Exception as e:
            detail[rung] = {"error": repr(e)[:300]}

    try:
        detail["pcol_q6"] = bench_pcol_scan(
            1.0 if args.quick else min(args.sf, 10.0),
            seconds_budget=10.0 if args.quick else 30.0)
    except Exception as e:
        detail["pcol_q6"] = {"error": repr(e)[:300]}

    # multi-tenant serving rung: N concurrent HTTP clients on the shared
    # pools — qps/p50/p99/fairness, and the c4/c1 overlap verdict
    try:
        detail["serving"] = bench_serving(
            clients=(1, 4) if args.quick else (1, 4, 8),
            per_client=2 if args.quick else 4)
    except Exception as e:
        detail["serving"] = {"error": repr(e)[:300]}

    # chaos rung: mid-stream worker kill + straggler speculation on an
    # in-process cluster — recovery-overhead numbers ride along with every
    # bench run (reported in --compare, never gated)
    try:
        detail["chaos"] = bench_chaos()
    except Exception as e:
        detail["chaos"] = {"error": repr(e)[:300]}

    # churn rung: mid-query membership change (worker joins + graceful
    # drain of a serving worker) — the planned-drain counterpart of the
    # chaos kill; must hold query_attempts == 1 (reported, never gated)
    try:
        detail["churn"] = bench_churn()
    except Exception as e:
        detail["churn"] = {"error": repr(e)[:300]}

    # spill rung: Q1+Q3 under a memory cap must complete via the disk tier
    # with identical rows — capped walls and spill traffic ride along with
    # every bench run (reported in --compare, never gated)
    try:
        detail["spill"] = bench_spill(quick=args.quick)
    except Exception as e:
        detail["spill"] = {"error": repr(e)[:300]}

    # Pallas hash kernels: sorted-vs-pallas build/probe + Q3 walls, plus the
    # skew-aware 99%-one-key join spread (VERDICT #6's measured verdict)
    try:
        detail["hash_kernels"] = bench_hash_kernels(quick=args.quick)
    except Exception as e:
        detail["hash_kernels"] = {"error": repr(e)[:300]}

    # streaming mesh exchange: chunk/compile/overlap accounting on a small
    # virtual mesh (subprocess — must not disturb this process's backend)
    if not args.quick:
        detail["multichip_exchange"] = bench_multichip_exchange()

    baseline = cpu_baseline_rows_per_sec()
    rps, batch_rows, step_ms, stream = bench_q1_kernel(
        sf, seconds_budget=15.0 if args.quick else 45.0, quick=args.quick)
    detail.update({
        "q1_warm_rows_per_sec": round(rps),
        "q1_vs_numpy_baseline": round(rps / baseline, 3),
        "resident_batch_rows": batch_rows,
        "resident_step_ms": round(step_ms, 2),
        "stream": stream,
        "cpu_baseline_rows_per_sec": round(baseline),
    })

    # headline: the ENGINE path (round-5 contract) — Q3 SF1 through the full
    # parse/plan/optimize/driver stack, vs the same engine pinned to the CPU
    # backend. Falls back to the Q1 kernel metric if the rung errored.
    q3 = detail.get("q3", {})
    q3_rps = q3.get("rows_per_sec") if q3.get("schema") == "sf1" else None
    if q3_rps and platform != "cpu":
        cpu_engine = _cpu_engine_q3_baseline()
        detail["cpu_engine_q3_sf1_rows_per_sec"] = cpu_engine
        result = {
            "metric": "tpch_q3_sf1_engine_rows_per_sec",
            "value": round(q3_rps),
            "unit": "rows/s",
            "vs_baseline": round(q3_rps / max(cpu_engine, 1), 3),
            "detail": detail,
        }
    else:
        result = {
            "metric": "tpch_q1_warm_rows_per_sec",
            "value": round(rps),
            "unit": "rows/s",
            "vs_baseline": round(rps / baseline, 3),
            "detail": detail,
        }
    print(json.dumps(result))

    if args.compare:
        # regression gate: the result line above already went out (the
        # round driver always gets its JSON), THEN the comparison verdict
        with open(args.compare) as f:
            prev = json.load(f)
        cmp_result = compare_benches(prev, result)
        print("BENCH_COMPARE=" + json.dumps(cmp_result))
        if cmp_result["regressions"]:
            print(f"bench: wall regression >"
                  f"{int(WALL_REGRESSION_THRESHOLD * 100)}% on "
                  f"{', '.join(cmp_result['regressions'])}",
                  file=sys.stderr)
            sys.exit(3)

    failed = [k for k, v in detail.items()
              if isinstance(v, dict) and "error" in v]
    if failed:
        print(f"bench: rungs failed: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    try:
        main()
    except Exception:
        # the driver must always get one parseable JSON line
        print(json.dumps({"metric": "bench_error", "value": 0, "unit": "error",
                          "vs_baseline": 0,
                          "detail": {**DETAIL,
                                     "error": traceback.format_exc()[-1500:]}}))
        sys.exit(1)


