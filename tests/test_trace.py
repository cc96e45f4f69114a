"""Query flight recorder (utils/trace.py) + distributed EXPLAIN ANALYZE.

Coverage per the observability contract:
- recorder mechanics: ring bound + drop accounting, span helpers, PER-QUERY
  recorder scoping (thread-local install + bound() propagation, global
  fallback for ambient threads);
- tracing OFF is a no-op differential: identical results and zero recorded
  events on a TPC-H Q3 run;
- tracing ON exports valid Chrome trace-event JSON (pid/tid/ts/dur/ph)
  with spans from every instrumented subsystem — lifecycle, driver,
  scan, segment locally; exchange on the 2-device mesh;
- histogram plumbing: query wall + exchange chunk latency percentiles
  reach /v1/metrics;
- distributed EXPLAIN ANALYZE on a 2-device mesh rolls per-operator
  rows/wall/peak-mem up per fragment (the cluster tier's roll-up is
  exercised in tests/test_cluster.py over real worker HTTP).
"""
import json
import threading
import time

import pytest

from presto_tpu.metadata import Session
from presto_tpu.models.tpch_sql import QUERIES
from presto_tpu.runner import LocalQueryRunner
from presto_tpu.utils import trace
from presto_tpu.utils.metrics import METRICS
from presto_tpu.utils.testing import assert_rows_equal


# ---------------------------------------------------------------- recorder

def test_ring_buffer_bounds_and_drop_count():
    rec = trace.TraceRecorder("t", max_events=16)
    for i in range(40):
        rec.record("driver", f"e{i}", i, 1)
    events = rec.events()
    assert len(events) == 16
    assert rec.dropped == 24
    # oldest overwritten: the surviving events are the most recent ones
    assert [e[1] for e in events] == [f"e{i}" for i in range(24, 40)]


def test_span_context_manager_and_module_helpers():
    rec = trace.TraceRecorder("t")
    with rec.span("scan", "read", reader=3):
        pass
    (cat, name, t0, dur, tid, tname, args), = rec.events()
    assert cat == "scan" and name == "read" and args == {"reader": 3}
    assert tid == threading.get_ident() and dur >= 0

    # module-level helpers are no-ops until a recorder is installed
    assert trace.active() is None
    trace.record("driver", "ghost", 0, 1)
    trace.instant("driver", "ghost2")
    with trace.span("driver", "ghost3"):
        pass
    assert rec.count() == 1

    assert trace.install(rec)
    try:
        trace.record("driver", "real", 0, 1)
        with trace.span("kernel", "build"):
            pass
    finally:
        trace.uninstall(rec)
    assert trace.active() is None
    cats = {e[0] for e in rec.events()}
    assert cats == {"scan", "driver", "kernel"}


def test_per_query_scoping_threads_record_separately():
    """Concurrent traced queries no longer collide: each thread's install
    binds its own recorder (thread-local), bound() propagates it to worker
    threads, and unbound threads fall back to the first-installed global."""
    rec_a = trace.TraceRecorder("a")
    rec_b = trace.TraceRecorder("b")
    ready = threading.Barrier(2)
    done = threading.Barrier(2)

    def query(rec, name):
        assert trace.install(rec)
        try:
            ready.wait(timeout=10)
            trace.record("driver", name, 0, 1)
            done.wait(timeout=10)
        finally:
            trace.uninstall(rec)

    t = threading.Thread(target=query, args=(rec_b, "from-b"))
    t.start()
    query(rec_a, "from-a")
    t.join(timeout=10)
    assert [e[1] for e in rec_a.events()] == ["from-a"]
    assert [e[1] for e in rec_b.events()] == ["from-b"]

    # bound() hands a query's recorder to a worker thread and restores
    rec = trace.TraceRecorder("w")
    def worker():
        with trace.bound(rec):
            trace.record("scan", "bound-span", 0, 1)
        assert trace.active() is None
    w = threading.Thread(target=worker)
    w.start()
    w.join(timeout=10)
    assert [e[1] for e in rec.events()] == ["bound-span"]


def test_chrome_trace_schema(tmp_path):
    rec = trace.TraceRecorder("q42")
    rec.record("exchange", "chunk_dispatch f1", rec.t0_ns + 5_000, 2_000,
               {"chunk": 1})
    path = rec.write(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    assert doc["otherData"]["query_id"] == "q42"
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(spans) == 1 and len(metas) >= 2  # process + thread names
    e = spans[0]
    assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid"}
    assert e["ts"] == pytest.approx(5.0) and e["dur"] == pytest.approx(2.0)
    assert all(isinstance(e[k], (int, float)) for k in ("ts", "dur", "pid"))


def test_overlap_ratio_math():
    doc = {"traceEvents": [
        {"ph": "X", "cat": "a", "ts": 0.0, "dur": 10.0},
        {"ph": "X", "cat": "a", "ts": 20.0, "dur": 10.0},
        {"ph": "X", "cat": "b", "ts": 5.0, "dur": 10.0},   # covers a[5..10]
        {"ph": "X", "cat": "b", "ts": 25.0, "dur": 100.0},  # covers a[25..30]
    ]}
    assert trace.overlap_ratio(doc, "a", "b") == pytest.approx(0.5)
    assert trace.overlap_ratio(doc, "a", "missing") == 0.0
    assert trace.overlap_ratio({"traceEvents": []}, "a", "b") == 0.0


# ------------------------------------------------------- engine integration

@pytest.fixture()
def q3_runner():
    return LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))


def test_tracing_off_is_a_noop_differential(q3_runner):
    traced = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny", properties={"query_trace": True}))
    plain = q3_runner.execute(QUERIES[3])
    on = traced.execute(QUERIES[3])
    assert_rows_equal(plain.rows, on.rows, ordered=True)
    assert plain.trace_path is None
    assert on.trace_path is not None
    # the recorder never leaks past its query
    assert trace.active() is None


def test_local_trace_export_has_subsystem_spans(q3_runner):
    from presto_tpu.ops.scan import RESIDENT_CACHE

    # warm scans replay device-resident pages and skip the scan pipeline
    # entirely; a COLD scan is what exercises the read/decode/upload spans
    RESIDENT_CACHE.clear()
    traced = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny", properties={"query_trace": True}))
    res = traced.execute(QUERIES[3])
    doc = json.load(open(res.trace_path))
    cats = trace.span_categories(doc)
    # lifecycle phases, driver quanta, scan-pipeline stages and fused-
    # segment dispatches must all be on the timeline for a Q3 run
    for want in ("lifecycle", "driver", "scan", "segment"):
        assert cats.get(want, 0) > 0, (want, cats)
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"parse", "plan", "local_plan", "execute"} <= names
    # query-wall histogram percentiles reach the metrics snapshot
    snap = METRICS.snapshot("query.wall_s")
    assert snap["query.wall_s.count"] >= 1
    assert snap["query.wall_s.p99"] >= snap["query.wall_s.p50"] > 0


def test_distributed_trace_has_exchange_spans(eight_devices):
    from presto_tpu.parallel.mesh import MeshContext
    from presto_tpu.parallel.runner import DistributedQueryRunner

    mesh = MeshContext(eight_devices[:2])
    runner = DistributedQueryRunner(mesh, session=Session(
        catalog="tpch", schema="tiny",
        properties={"exchange_chunk_rows": 256, "query_trace": True}))
    res = runner.execute("select o_custkey % 5, count(*) "
                         "from orders group by 1 order by 1")
    assert res.trace_path is not None
    doc = json.load(open(res.trace_path))
    cats = trace.span_categories(doc)
    assert cats.get("exchange", 0) > 0, cats
    assert cats.get("driver", 0) > 0, cats
    dispatches = [e for e in doc["traceEvents"]
                  if e.get("cat") == "exchange"
                  and e["name"].startswith("chunk_dispatch")]
    assert dispatches and all(e["dur"] > 0 for e in dispatches)
    # per-chunk exchange latency percentiles reach /v1/metrics
    snap = METRICS.snapshot("exchange.chunk_latency_s")
    assert snap["exchange.chunk_latency_s.count"] >= 1
    assert snap["exchange.chunk_latency_s.p95"] > 0


def test_distributed_explain_analyze_rolls_up_per_fragment(eight_devices):
    from presto_tpu.parallel.mesh import MeshContext
    from presto_tpu.parallel.runner import DistributedQueryRunner

    mesh = MeshContext(eight_devices[:2])
    runner = DistributedQueryRunner(mesh, session=Session(
        catalog="tpch", schema="tiny"))
    res = runner.execute("explain analyze select o_custkey % 5, count(*) "
                         "from orders group by 1")
    text = "\n".join(r[0] for r in res.rows)
    # per-fragment sections with the shared stats table
    assert "Fragment 0 [source]" in text
    assert "Operator" in text and "Wall ms" in text and "Peak MB" in text
    assert "Blk ms" in text  # blocked-time enrichment
    # worker roll-up: fragment 0 runs on BOTH workers; the TableScan row
    # aggregates their input rows (orders tiny = 15000 rows, padded pages)
    scan_line = next(line for line in text.splitlines()
                     if line.strip().startswith("TableScan"))
    assert int(scan_line.split()[1]) >= 15000
    # exchange enrichment per fragment: chunk/carry counts
    assert "exchange [repartition]" in text and "chunks=" in text \
        and "carry_rows=" in text


def test_trace_http_endpoint(tmp_path):
    import urllib.request

    from presto_tpu.server import PrestoTpuServer

    runner = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny",
        properties={"query_trace": True,
                    "query_trace_dir": str(tmp_path)}))
    server = PrestoTpuServer(runner, port=0)
    server.start()
    try:
        base = f"http://127.0.0.1:{server.port}"
        req = urllib.request.Request(
            f"{base}/v1/statement", data=b"select count(*) from region",
            headers={"X-Presto-User": "test"})
        resp = json.loads(urllib.request.urlopen(req, timeout=10).read())
        qid = resp["id"]
        next_uri = resp.get("nextUri")
        for _ in range(200):
            if next_uri is None:
                break
            if resp["stats"]["state"] in ("QUEUED", "RUNNING"):
                time.sleep(0.05)  # pace the nextUri poll while it runs
            resp = json.loads(urllib.request.urlopen(
                urllib.request.Request(
                    next_uri, headers={"X-Presto-User": "test"}),
                timeout=10).read())
            next_uri = resp.get("nextUri")
        assert resp["stats"]["state"] == "FINISHED", resp
        doc = json.loads(urllib.request.urlopen(
            urllib.request.Request(
                f"{base}/v1/query/{qid}/trace",
                headers={"X-Presto-User": "test"}),
            timeout=10).read())
        assert trace.span_categories(doc).get("lifecycle", 0) > 0
        info = json.loads(urllib.request.urlopen(
            urllib.request.Request(
                f"{base}/v1/query/{qid}",
                headers={"X-Presto-User": "test"}),
            timeout=10).read())
        assert info["hasTrace"] is True
        assert info["elapsedMillis"] >= 0
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# always-on black-box mode (observability PR)
# ---------------------------------------------------------------------------

def test_maybe_recorder_modes():
    from presto_tpu.utils.trace import BLACKBOX_MAX_EVENTS, TraceRecorder

    coarse = trace.maybe_recorder(Session(catalog="tpch", schema="tiny"))
    assert isinstance(coarse, TraceRecorder)
    assert coarse.coarse and coarse.max_events == BLACKBOX_MAX_EVENTS

    full = trace.maybe_recorder(Session(
        catalog="tpch", schema="tiny", properties={"query_trace": True}))
    assert not full.coarse

    off = trace.maybe_recorder(Session(
        catalog="tpch", schema="tiny",
        properties={"query_blackbox": False}))
    assert off is None


def test_coarse_recorder_drops_per_page_categories():
    rec = trace.TraceRecorder("q", max_events=64, coarse=True)
    rec.record(trace.OPERATOR, "op.add_input", 0, 100)
    rec.record(trace.SEGMENT, "page", 0, 100)
    rec.record(trace.DRIVER, "scan->sink", 0, 100)
    rec.record(trace.EXCHANGE, "chunk_dispatch", 0, 100)
    rec.record(trace.POOL, "scan_step", 0, 100)
    cats = {e[0] for e in rec.events()}
    assert cats == {trace.DRIVER, trace.EXCHANGE, trace.POOL}


def test_blackbox_success_exports_nothing_failure_dumps_forensic(tmp_path):
    import json as _json

    runner = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny",
        properties={"query_trace_dir": str(tmp_path)}))
    ok = runner.execute(QUERIES[6])
    assert ok.trace_path is None and ok.failure_trace_path is None
    assert trace.active() is None
    assert list(tmp_path.iterdir()) == []  # success writes no files

    with pytest.raises(Exception) as ei:
        runner.execute("select definitely_missing from lineitem")
    path = getattr(ei.value, "failure_trace_path", None)
    assert path and path.startswith(str(tmp_path))
    doc = _json.load(open(path))
    assert doc["otherData"]["coarse"] is True
    assert trace.active() is None  # recorder never leaks past its query


def test_blackbox_off_is_off():
    runner = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny",
        properties={"query_blackbox": False}))
    with pytest.raises(Exception) as ei:
        runner.execute("select definitely_missing from lineitem")
    assert getattr(ei.value, "failure_trace_path", None) is None


def test_full_trace_still_wins_for_failed_queries(tmp_path):
    """query_trace=on + failure: the forensic rides the exception AND the
    ring has the full (non-coarse) detail."""
    import json as _json

    runner = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny",
        properties={"query_trace": True,
                    "query_trace_dir": str(tmp_path)}))
    with pytest.raises(Exception) as ei:
        runner.execute("select definitely_missing from lineitem")
    path = getattr(ei.value, "failure_trace_path", None)
    assert path
    assert _json.load(open(path))["otherData"]["coarse"] is False


# ---------------------------------------------------------------------------
# one trace, not three (PR 27): the engine's spans in the profiler's own
# trace, the phase histograms beside them, and what stays as it was
# ---------------------------------------------------------------------------

PHASE_EVENTS = ("presto.protocol.queued", "presto.lifecycle.parse",
                "presto.lifecycle.plan", "presto.lifecycle.local_plan",
                "presto.lifecycle.execute", "presto.protocol.serialize",
                "presto.protocol.result_wait")
PHASE_HISTOGRAMS = ("query.queued_s", "query.parse_s", "query.plan_s",
                    "query.local_plan_s", "query.execute_s",
                    "query.serialize_s", "query.result_wait_s")


@pytest.fixture()
def tiny_server():
    from presto_tpu.server import PrestoTpuServer

    server = PrestoTpuServer(LocalQueryRunner(
        session=Session(catalog="tpch", schema="tiny")), port=0)
    server.start()
    yield server
    server.stop()


def _ask(server, sql):
    """One dbapi query (both catalog headers, 50 ms poll) -> (rows, wall)."""
    import presto_tpu.client.dbapi as dbapi

    with dbapi.connect(host="127.0.0.1", port=server.port, user="t",
                       catalog="tpch", schema="tiny") as conn:
        cur = conn.cursor()
        t0 = time.perf_counter()
        try:
            cur.execute(sql)
            rows = cur.fetchall()
            return rows, time.perf_counter() - t0
        finally:
            # the handler ends the root span AFTER it has written the final
            # response: the client can be back before that
            deadline = time.monotonic() + 5.0
            while any(q.stages for q in server.manager.list_queries()) \
                    and time.monotonic() < deadline:
                time.sleep(0.002)


def _profiled(tmp_path, work):
    """Run `work()` under a jax.profiler trace (python tracer off, as the
    benchmark takes it) -> {qid: [(name, start_ns, end_ns, stats)]} of the
    host plane's presto.* events."""
    import glob

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    by_qid = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("presto."):
                    stats = dict(e.stats)
                    by_qid.setdefault(stats.get("qid"), []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         stats))
    return by_qid


def test_live_profile_holds_the_engines_spans_of_a_served_q6(
        tmp_path, tiny_server):
    assert not trace.profile_live()
    events = _profiled(tmp_path, lambda: _ask(tiny_server, QUERIES[6]))
    assert not trace.profile_live()
    assert None not in events          # every presto.* event carries a qid
    (qid, spans), = events.items()     # ... and it is the one query's
    assert tiny_server.manager.get(qid) is not None   # the client-visible id
    by_name = {}
    for name, s, e, stats in spans:
        by_name.setdefault(name, []).append((s, e, stats))
    (r0, r1, _), = by_name["presto.query"]
    for name in PHASE_EVENTS + ("presto.lifecycle.query",
                                "presto.http.POST /v1/statement"):
        assert len(by_name[name]) == 1, name
    drivers = [n for n in by_name if n.startswith("presto.driver.")]
    assert drivers
    # a driver quantum runs on the executor's thread on behalf of `execute`
    assert all(st.get("parent") == "execute"
               for n in drivers for _s, _e, st in by_name[n])
    # the full recorder, not the coarse one: operator spans exist
    assert any(n.startswith("presto.operator.") for n in by_name)
    # children lie inside the root in time, and the phases (sequential by
    # construction) add up to no more than it
    # (but for the POST's handler, which is what calls submit: it reads the
    # request before the root begins and ends inside it)
    (p0, p1, _), = by_name["presto.http.POST /v1/statement"]
    assert p0 <= r0 <= p1 <= r1
    for name, s, e, _stats in spans:
        if name != "presto.http.POST /v1/statement":
            assert r0 <= s and e <= r1, (name, s - r0, e - r1)
    assert sum(by_name[n][0][1] - by_name[n][0][0]
               for n in PHASE_EVENTS) <= r1 - r0
    (x0, x1, _), = by_name["presto.lifecycle.execute"]
    assert all(x0 <= s and e <= x1
               for n in drivers for s, e, _st in by_name[n])


def test_no_profile_emits_nothing_and_keeps_the_coarse_ring(
        monkeypatch, tiny_server):
    built = []

    class Forbidden:
        def __init__(self, *a, **kw):
            built.append(a)
            raise AssertionError("a TraceMe was built with no profile live")

        is_enabled = staticmethod(lambda: False)

    monkeypatch.setattr(trace, "TraceAnnotation", Forbidden)
    rec = trace.maybe_recorder(Session(catalog="tpch", schema="tiny"))
    assert rec.coarse and not rec.profiled
    assert rec.max_events == trace.BLACKBOX_MAX_EVENTS
    rows, _wall = _ask(tiny_server, QUERIES[6])
    assert len(rows) == 1 and built == []
    info = tiny_server.manager.list_queries()[-1]
    assert info.state == "FINISHED" and not info.profiled
    assert info.stages == {}           # every stage was ended, none leaked


def test_profiler_metadata_is_escaped(monkeypatch):
    made = []

    class Fake:
        def __init__(self, name, **meta):
            made.append((name, meta))

        def __enter__(self):
            return self

    monkeypatch.setattr(trace, "TraceAnnotation", Fake)
    trace._annotation("driver.Scan->Agg#1", "q7", parent="a,b=c#d",
                      program="seg,x")
    assert made == [("presto.driver.Scan->Agg_1",
                     {"qid": "q7", "parent": "a;b:c_d", "program": "seg;x"})]


def test_each_phase_histogram_gains_one_observation_a_query(tiny_server):
    before = METRICS.raw_snapshot("query.")["histograms"]
    walls = [_ask(tiny_server, QUERIES[6])[1] for _ in range(2)]
    after = METRICS.raw_snapshot("query.")["histograms"]

    def gained(name, key):
        return after[name][key] - before.get(name, {"n": 0, "total": 0.0})[key]

    for name in PHASE_HISTOGRAMS + ("query.wall_s",):
        assert gained(name, "n") == 2, name
    # the phases follow one another inside what the client waited for
    assert 0 < sum(gained(n, "total") for n in PHASE_HISTOGRAMS) <= sum(walls)
    # the runner's four lie inside its own wall
    assert sum(gained(f"query.{p}_s", "total") for p in trace.PHASES) \
        <= gained("query.wall_s", "total")


def test_failed_query_under_a_live_profile_still_dumps_its_forensic(
        tmp_path, tiny_server):
    import urllib.request

    import presto_tpu.client.dbapi as dbapi

    before = METRICS.raw_snapshot("query.")["histograms"]

    def fail():
        with pytest.raises(dbapi.Error):
            _ask(tiny_server, "select definitely_missing from lineitem")

    events = _profiled(tmp_path / "profile", fail)
    (qid, spans), = events.items()
    names = {n for n, _s, _e, _st in spans}
    assert {"presto.query", "presto.protocol.queued",
            "presto.protocol.result_wait", "presto.lifecycle.parse"} <= names
    assert "presto.protocol.serialize" not in names   # it never got there
    info = tiny_server.manager.get(qid)
    assert info.state == "FAILED" and info.failure_trace_path
    doc = json.loads(urllib.request.urlopen(urllib.request.Request(
        f"http://127.0.0.1:{tiny_server.port}/v1/query/{qid}/trace",
        headers={"X-Presto-User": "t"}), timeout=10).read())
    # the full ring (a profile was live), with the failing phase on it
    assert doc["otherData"]["coarse"] is False
    assert "parse" in {e["name"] for e in doc["traceEvents"]}
    after = METRICS.raw_snapshot("query.")["histograms"]
    for name in ("query.queued_s", "query.result_wait_s"):
        assert after[name]["n"] - before.get(name, {"n": 0})["n"] == 1
    # like query.wall_s, the runner's phases count statements that succeed
    assert after.get("query.plan_s", {"n": 0})["n"] == \
        before.get("query.plan_s", {"n": 0})["n"]


def test_window_deltas_of_two_queries_give_plan_s_equal_to_the_hand_sum(
        tmp_path):
    from benchmark.harness import engine_spans

    runner = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny",
        properties={"query_trace": True, "query_trace_dir": str(tmp_path)}))
    window = {"before": METRICS.raw_snapshot()}
    paths = [runner.execute(QUERIES[6]).trace_path for _ in range(2)]
    window["after"] = METRICS.raw_snapshot()
    by_hand = 0.0
    for path in paths:           # the ring's spans: the same clock reads
        by_hand += sum(e["dur"] for e in json.load(open(path))["traceEvents"]
                       if e.get("cat") == "lifecycle"
                       and e["name"] in ("parse", "plan", "local_plan"))
    plan_s = engine_spans.histogram_mean_gain(
        window, ["query.parse_s", "query.plan_s", "query.local_plan_s"])
    assert plan_s == pytest.approx(by_hand / 1e6 / 2, rel=1e-6)
    assert plan_s > 0
    # a histogram that gained nothing is no reading, never a made-up 0
    assert engine_spans.histogram_mean_gain(
        {"before": window["after"], "after": window["after"]},
        ["query.parse_s"]) is None
    assert engine_spans.histogram_mean_gain(window, ["no.such_s"]) is None


def test_ring_size_knobs_are_gone_and_nothing_reads_them():
    import pathlib

    import presto_tpu

    gone = ("query_trace_max_events", "query_blackbox_max_events")
    for knob in gone:
        assert knob not in Session.DEFAULTS
    package = pathlib.Path(presto_tpu.__file__).parent
    readers = [str(p) for p in package.rglob("*.py")
               if any(k in p.read_text() for k in gone)]
    assert readers == []
    # the two sizes are the constants they always were
    full = trace.maybe_recorder(Session(
        catalog="tpch", schema="tiny", properties={"query_trace": True}))
    assert full.max_events == trace.DEFAULT_MAX_EVENTS


def test_dbapi_headers_are_served_by_the_mesh_runner(eight_devices):
    """Every dbapi/JDBC client sends X-Presto-Catalog/Schema; the protocol
    layer scopes a copy of the engine to them, which the mesh runner had no
    setter for ("property 'session' ... has no setter" on every query)."""
    from presto_tpu.parallel.mesh import MeshContext
    from presto_tpu.parallel.runner import DistributedQueryRunner
    from presto_tpu.server import PrestoTpuServer

    sql = ("select o_orderpriority, count(*) from orders "
           "group by o_orderpriority order by o_orderpriority")
    session = Session(catalog="tpch", schema="sf1")   # the headers override
    mesh = DistributedQueryRunner(MeshContext(eight_devices[:2]),
                                  session=session)
    server = PrestoTpuServer(mesh, port=0)
    server.start()
    try:
        rows, _wall = _ask(server, sql)
    finally:
        server.stop()
    want = LocalQueryRunner(
        session=Session(catalog="tpch", schema="tiny")).execute(sql).rows
    assert_rows_equal([tuple(r) for r in rows], [tuple(r) for r in want],
                      ordered=True)
    assert mesh.session.schema == "sf1"   # the shared engine is as it was
