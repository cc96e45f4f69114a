"""TPC-H Q3 served by the mesh runner on four virtual devices (PR 29): the
deployment `tpch-sf1-mesh4` of the benchmark, at schema `tiny` on the CPU.

A `PrestoTpuServer` over `DistributedQueryRunner(MeshContext(n_workers=4))`,
as `python -m presto_tpu.server --distributed` builds it, answers Q3 from
`benchmark/queries/q3.sql` through `client.dbapi` for five seeded
substitution sets. Every answer is held, row for row and in order, to the
local runner on one device and to the benchmark's plain numpy reference
(`benchmark/queries/q3.py`, which imports nothing of the program). Beside it:
the tables stay on the device that scanned them, a warm query builds nothing,
and `exchange.live_bytes` is what the width helper says of the rows that
crossed.
"""
import os
import random
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from presto_tpu.metadata import Session  # noqa: E402
from presto_tpu.runner import LocalQueryRunner  # noqa: E402
from presto_tpu.utils.metrics import METRICS  # noqa: E402

WORKERS = 4
TINY_SF = 0.01


def _parameter_sets(n=5, seed=29):
    """Five (segment, day) draws from the traffic file's own ranges, each
    segment once: what five seeds of the cell would send."""
    from benchmark.harness import cells
    from benchmark.harness.traffic import draw

    spec = cells.load_json(cells.BENCH_DIR, "traffic", "q3.json")
    params = spec["queries"][0]["parameters"]
    rng = random.Random(seed)
    segments = list(params["segment"]["values"])
    rng.shuffle(segments)
    return [(s, draw(params["day"], rng)) for s in segments[:n]]


PARAMETER_SETS = _parameter_sets()


@pytest.fixture(scope="module")
def q3():
    from benchmark.harness import cells

    return cells.Query("q3")


def _mesh_runner(devices, **properties):
    from presto_tpu.parallel.mesh import MeshContext
    from presto_tpu.parallel.runner import DistributedQueryRunner

    return DistributedQueryRunner(
        MeshContext(devices, n_workers=WORKERS),
        session=Session(catalog="tpch", schema="tiny", properties=properties))


@pytest.fixture(scope="module")
def mesh_server(eight_devices):
    from presto_tpu.server import PrestoTpuServer

    server = PrestoTpuServer(_mesh_runner(eight_devices), port=0)
    server.start()
    yield server
    server.stop()


def _ask(server, sql, schema="tiny"):
    """-> the answer as the benchmark types it (decimals exact text, dates)."""
    import presto_tpu.client.dbapi as dbapi
    from benchmark.harness.compare import typed

    with dbapi.connect(host="127.0.0.1", port=server.port, user="t",
                       catalog="tpch", schema=schema) as conn:
        cur = conn.cursor()
        cur.execute(sql)
        return typed(cur.fetchall(), cur.description)


def _exchange_counters():
    return {k: v for k, v in METRICS.raw_snapshot()["counters"].items()
            if k.startswith(("exchange.", "kernel_cache."))}


def _gained(before, name):
    return _exchange_counters().get(name, 0) - before.get(name, 0)


@pytest.mark.parametrize("segment,day", PARAMETER_SETS)
def test_served_mesh_q3_equals_local_runner_and_plain_reference(
        mesh_server, q3, segment, day):
    from benchmark.harness.compare import canon, compare_rows

    sql = q3.template.format(segment=segment, day=day)
    got = _ask(mesh_server, sql)
    assert got, "Q3 at tiny returns rows for every segment"

    local = LocalQueryRunner(
        session=Session(catalog="tpch", schema="tiny")).execute(sql).rows
    on_one_device = [(int(key), canon(revenue), date, int(priority))
                     for key, revenue, date, priority in local]
    assert got == on_one_device

    want = q3.reference(TINY_SF, {"segment": segment, "day": day})
    assert compare_rows(got, want) == (0, 0.0)
    assert got == want


def test_a_numbered_schema_serves_the_listed_schemas_tables(mesh_server, q3):
    """The cell's configuration names its schema `sf1.0` (presto-tpch's
    `sf<number>` form): over the wire such a name gives the tables of the
    listed schema with that scale factor, `sf0.01` those of `tiny`."""
    segment, day = PARAMETER_SETS[0]
    sql = q3.template.format(segment=segment, day=day)
    got = _ask(mesh_server, sql, schema="sf0.01")
    assert got and got == _ask(mesh_server, sql)
    assert got == q3.reference(TINY_SF, {"segment": segment, "day": day})


@pytest.mark.parametrize("segment,day", PARAMETER_SETS[:2])
def test_second_run_builds_nothing_and_uploads_nothing(
        mesh_server, q3, segment, day):
    """What the cell's warm-up relies on: the second run of a parameter set
    compiles no kernel and no collective, and no page crosses host->device
    inside an exchange (the tables are resident, fragment chains stay on
    their chips)."""
    sql = q3.template.format(segment=segment, day=day)
    first = _ask(mesh_server, sql)
    before = _exchange_counters()
    again = _ask(mesh_server, sql)
    assert again == first
    assert _gained(before, "kernel_cache.misses") == 0
    assert _gained(before, "exchange.collective_compiles") == 0
    assert _gained(before, "exchange.exchanges") == 6
    # every size derived (PR 37): at `tiny` each exchange sends ONE chunk,
    # but for lineitem's, whose two 8192-row pages a worker are about 54%
    # live and fit one 8192-row chunk together on some days and not on
    # others (at 4096-row chunks it sent three, when this read "at least 6")
    assert _gained(before, "exchange.chunks") in (6, 7)
    assert _gained(before, "exchange.refills") == 0
    assert _gained(before, "exchange.fills") >= \
        _gained(before, "exchange.chunks") + 6 * WORKERS
    assert _exchange_counters().get("exchange.host_uploads", 0) == 0


@pytest.mark.parametrize("segment,day", PARAMETER_SETS[2:4])
def test_every_size_derived_equals_the_references_and_builds_nothing_twice(
        eight_devices, q3, segment, day):
    """Mesh Q3 at `tiny` with no size named (the mesh's page, each
    exchange's chunk derived from it when the exchange is built): the rows
    are the local runner's and the plain reference's, no fill leaves a
    leftover, and the second run derives the same chunks and builds
    nothing."""
    from benchmark.harness.compare import canon
    from presto_tpu.metadata import default_page_capacity
    from presto_tpu.parallel.streaming_exchange import (MESH_PAGE_ROWS,
                                                        derive_chunk_rows,
                                                        exchange_row_bytes)

    runner = _mesh_runner(eight_devices)
    assert runner.session.get("page_capacity") is None
    assert not runner.session.get("exchange_chunk_rows")
    sql = q3.template.format(segment=segment, day=day)
    first = runner.execute(sql)
    got = [(int(key), canon(revenue), date, int(priority))
           for key, revenue, date, priority in first.rows]
    local = LocalQueryRunner(
        session=Session(catalog="tpch", schema="tiny")).execute(sql).rows
    assert got == [(int(key), canon(revenue), date, int(priority))
                   for key, revenue, date, priority in local]
    assert got == q3.reference(TINY_SF, {"segment": segment, "day": day})

    def derived(result):
        per_exchange = result.stats["exchange"]["per_exchange"]
        assert all(e["refills"] == 0 and e["fills"] >= e["chunks"] >= 1
                   for e in per_exchange), per_exchange
        return {e["fragment"]: e["chunk_rows"] for e in per_exchange}

    chunk_rows = derived(first)
    # decided before a page flows: the page every fragment was planned with,
    # whatever each table's splits and each aggregation's groups come to
    page = min(default_page_capacity(), MESH_PAGE_ROWS)
    widths = {f.id: exchange_row_bytes([s.type for s in f.root.outputs()])
              for f in runner.plan_sql(sql).fragments}
    assert chunk_rows == {
        fid: derive_chunk_rows(page, widths[fid], WORKERS, 1 << 28)
        for fid in range(6)}
    assert set(chunk_rows.values()) == {page}

    before = _exchange_counters()
    again = runner.execute(sql)
    assert again.rows == first.rows
    assert derived(again) == chunk_rows
    assert _gained(before, "kernel_cache.misses") == 0
    assert _gained(before, "exchange.collective_compiles") == 0


def test_resident_cache_holds_one_stream_a_table_and_device(
        mesh_server, eight_devices, q3):
    """Each chip keeps its own quarter of every table, once: one resident
    stream a (table, device), whose splits together cover the table exactly
    once, and a second query adds nothing."""
    from benchmark.harness import tpch_data
    from presto_tpu.ops.scan import RESIDENT_CACHE

    devices = eight_devices[:WORKERS]
    sql = q3.template.format(segment="BUILDING", day=15)
    RESIDENT_CACHE.clear()      # other tests' streams lie on these devices
    _ask(mesh_server, sql)
    with RESIDENT_CACHE._lock:
        tokens = set(RESIDENT_CACHE._pages)
    held = {}
    for source, device in tokens:
        # ("concat", split, split, ...) or one split: ("tpch", (table, sf,
        # first row, end row), columns, page rows)
        for _c, (table, _sf, lo, hi), columns, _rows in \
                (source[1:] if source[0] == "concat" else [source]):
            assert set(columns) == set(q3.scans[table])
            held.setdefault((table, device), []).append((source, lo, hi))
    assert set(held) == {(t, d) for t in q3.scans for d in devices}
    for table in q3.scans:
        streams = {src for d in devices for src, _lo, _hi in held[table, d]}
        assert len(streams) == WORKERS          # one stream a device
        ranges = sorted((lo, hi) for d in devices
                        for _src, lo, hi in held[table, d])
        assert ranges[0][0] == 0
        assert all(a[1] == b[0] for a, b in zip(ranges, ranges[1:]))
        unit = "orders" if table == "lineitem" else table   # split by order
        assert ranges[-1][1] == tpch_data.row_count(unit, TINY_SF)
    _ask(mesh_server, sql)
    with RESIDENT_CACHE._lock:
        assert set(RESIDENT_CACHE._pages) == tokens


def test_live_bytes_are_the_width_helpers_sum_for_each_exchange(eight_devices,
                                                                q3):
    """`exchange.live_bytes` = delivered live rows x the exchange's logical
    row width, for each of Q3's six exchanges and for the query's counter."""
    from presto_tpu.parallel.streaming_exchange import exchange_row_bytes

    runner = _mesh_runner(eight_devices)
    sql = q3.template.format(segment="MACHINERY", day=7)
    sub = runner.plan_sql(sql)
    widths = {f.id: exchange_row_bytes([s.type for s in f.root.outputs()])
              for f in sub.fragments if f is not sub.root_fragment}
    # by hand: bigint and decimal 8, date, integer and a varchar's code 4, no
    # null mask (inner joins over NOT NULL columns). In the plan's order:
    # lineitem (key, price, discount, shipdate), orders (key, custkey, date,
    # priority), the first join's output, customer (key, segment), the
    # partial aggregation (3 keys, sum, count), the TopN's rows
    assert widths == {0: 28, 1: 24, 2: 40, 3: 12, 4: 32, 5: 24}

    before = _exchange_counters()
    result = runner.execute(sql)
    per_exchange = result.stats["exchange"]["per_exchange"]
    assert sorted(e["fragment"] for e in per_exchange) == sorted(widths)
    for e in per_exchange:
        assert e["rows_out"] > 0
        assert e["live_bytes"] == e["rows_out"] * widths[e["fragment"]], e
    assert _gained(before, "exchange.live_bytes") == \
        sum(e["live_bytes"] for e in per_exchange)
    assert _gained(before, "exchange.rows") == \
        sum(e["rows_out"] for e in per_exchange)


SMALL_SQL = ("select o_custkey, count(*), sum(o_totalprice) from orders "
             "group by o_custkey")


def test_a_consumer_sees_a_page_count_that_timing_cannot_move(eight_devices):
    """Received rows are packed: an exchange hands each consumer
    ceil(rows / page) pages however many chunks carried them (pages cut at
    chunk boundaries made every join build and fold above an exchange trace
    a new shape, and compile, in a warm query)."""
    result = _mesh_runner(eight_devices,
                          exchange_chunk_rows=256).execute(SMALL_SQL)
    seen = 0
    for e in result.stats["exchange"]["per_exchange"]:
        # a consumer's shard of one chunk: a slice from each of its peers
        page = WORKERS * e["out_cap"]
        assert e["pages_out"] == sum(-(-rows // page)
                                     for rows in e["partition_rows"]), e
        seen += e["chunks"] > e["pages_out"]
    assert seen, "no exchange moved more chunks than pages: nothing packed"


def test_exchange_row_bytes_is_the_types_logical_width():
    from presto_tpu import types as T
    from presto_tpu.parallel.streaming_exchange import exchange_row_bytes

    assert exchange_row_bytes([]) == 0
    assert exchange_row_bytes([T.BIGINT]) == 8
    assert exchange_row_bytes([T.BIGINT, T.DATE, T.INTEGER, T.DOUBLE]) == 24
    assert exchange_row_bytes([T.DecimalType(12, 2), T.BOOLEAN]) == 9
    # a varchar crosses as its dictionary code, not as its text
    assert exchange_row_bytes([T.VarcharType()]) == 4
    # one byte of null mask where the column carries one, and only there
    assert exchange_row_bytes([T.BIGINT, T.DATE], [True, False]) == 13
    assert exchange_row_bytes([T.BIGINT, T.DATE],
                              np.array([True, True])) == 14
    assert exchange_row_bytes([T.BIGINT, T.DATE], [False, False]) == 12


def test_exchange_spans_reach_the_profilers_host_plane(tmp_path, mesh_server,
                                                       q3):
    """While a jax.profiler trace is live the pumps' spans lie in its host
    plane with the query's id: `chunk_dispatch` names the kernel-cache
    family it dispatched (`program`), and a span opened on a pump's thread
    names the span its work was handed over in (`parent`)."""
    import glob

    import jax

    sql = q3.template.format(segment="BUILDING", day=15)
    _ask(mesh_server, sql)     # warm: the traced run compiles nothing
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _ask(mesh_server, sql)
        # the handler ends the root span AFTER it has written the final
        # response: the client can be back before that (as test_trace waits)
        deadline = time.monotonic() + 5.0
        while any(q.stages for q in mesh_server.manager.list_queries()) \
                and time.monotonic() < deadline:
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("presto.")]
    (root,) = [e for e in events if e[0] == "presto.query"]
    qid = root[3]["qid"]
    dispatches = [e for e in events
                  if e[0].startswith("presto.exchange.chunk_dispatch f")]
    stalls = [e for e in events
              if e[0].startswith("presto.exchange.pump_stall f")]
    assert len({e[0] for e in dispatches}) == 6     # one name an exchange
    assert stalls
    steps = [e for e in events if e[0] == "presto.pool.exchange_step"]
    for name, start, end, stats in dispatches + stalls:
        assert stats.get("qid") == qid, (name, stats)
        assert root[1] <= start and end <= root[2], name
        # opened inside a pool step on the pump's thread: that step names
        # the span the pump was started in, the span itself needs none
        inside = [s for s in steps if s[1] <= start and end <= s[2]]
        assert inside or stats.get("parent"), (name, stats)
        assert all(s[3].get("parent") == "local_plan" and
                   s[3].get("qid") == qid for s in inside), name
    assert all(stats.get("program") == "exchange-stream"
               for _n, _s, _e, stats in dispatches)
    # every state of a pump but `queued` is a span (PR 38): the blocked
    # device_get, the send side's fills and the receive side's packs of all
    # six exchanges lie there too, under the same rules
    from presto_tpu.parallel.streaming_exchange import _STATE_SPANS

    by_exchange = {}
    for e in events:
        kind, _, fragment = e[0][len("presto.exchange."):].partition(" f")
        if e[0].startswith("presto.exchange.") and \
                kind in _STATE_SPANS.values():
            by_exchange.setdefault(fragment, {}).setdefault(kind, []).append(e)
    assert sorted(by_exchange) == [str(f) for f in range(6)]
    for fragment, kinds in by_exchange.items():
        # (a pump whose producers never leave it waiting has no pump_stall)
        assert {"pump_sync", "pump_fill", "chunk_deliver",
                "chunk_dispatch"} <= set(kinds), (fragment, sorted(kinds))
        spans = sorted(e for es in kinds.values() for e in es)
        for name, start, end, stats in spans:
            assert stats.get("qid") == qid, (name, stats)
            # none is left open when the root ends
            assert root[1] <= start <= end <= root[2], name
            assert stats.get("parent") or any(
                s[1] <= start and end <= s[2] for s in steps), (name, stats)
        # ONE state at a moment: no two state spans of an exchange overlap
        spans.sort(key=lambda e: e[1])
        for a, b in zip(spans, spans[1:]):
            assert a[2] <= b[1], (a, b)
