"""TPC-H Q5 served on one device (PR 36): the deployment
`tpch-sf1-q5-1chip` of the benchmark, at schema `tiny` on the CPU.

A `PrestoTpuServer` over a `LocalQueryRunner`, as `python -m presto_tpu.server`
builds it, answers Q5 from `benchmark/queries/q5.sql` through `client.dbapi`
for the five regions and three of the five years; every answer is held, row
for row and in order, to the benchmark's plain numpy reference
(`benchmark/queries/q5.py`, which imports nothing of the program). Beside it,
what the cell's per-layer metrics read: the query's five join builds all take
the direct-address table, customer's too: its join on (nation, customer key)
is keyed on the customer key alone, on which the build is unique, and checks
the nation behind the probe (`join.builds.narrowed`); no probe page takes the
expansion path a fan-out takes; every lineitem page is counted once for each
of the five probes, which fuse into one segment with no `CoalesceOperator`
behind them; the aggregation on the dictionary key `n_name` is the dense
(direct) one, behind a join; the join order has a span and a histogram.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from presto_tpu.metadata import Session  # noqa: E402
from presto_tpu.runner import LocalQueryRunner  # noqa: E402
from presto_tpu.utils.metrics import METRICS  # noqa: E402

TINY_SF = 0.01
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
YEARS = [1993, 1994, 1995, 1996, 1997]     # qgen's whole range
PROBES = 5


@pytest.fixture(scope="module")
def q5():
    from benchmark.harness import cells

    return cells.Query("q5")


@pytest.fixture(scope="module")
def server():
    from presto_tpu.server import PrestoTpuServer

    srv = PrestoTpuServer(LocalQueryRunner(
        session=Session(catalog="tpch", schema="tiny")), port=0)
    srv.start()
    yield srv
    srv.stop()


def _ask(server, sql):
    """-> the answer as the benchmark types it (decimals exact text)."""
    import presto_tpu.client.dbapi as dbapi
    from benchmark.harness.compare import typed

    with dbapi.connect(host="127.0.0.1", port=server.port, user="t",
                       catalog="tpch", schema="tiny") as conn:
        cur = conn.cursor()
        cur.execute(sql)
        return typed(cur.fetchall(), cur.description)


def _numbers():
    numbers = {}
    for prefix in ("join.", "coalesce.", "agg.direct.", "segments."):
        numbers.update(METRICS.raw_snapshot(prefix)["counters"])
    for name in ("join.build_s", "planner.reorder_joins_s"):
        prefix = name.split(".")[0] + "."
        numbers[name + ".n"] = METRICS.raw_snapshot(prefix)[
            "histograms"].get(name, {"n": 0})["n"]
    return numbers


def _run(sql, **properties):
    """-> (result, what each counter gained over the query)"""
    runner = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny", properties=properties))
    before = _numbers()
    result = runner.execute(sql)
    after = _numbers()
    return result, {k: v - before.get(k, 0) for k, v in after.items()}


@pytest.mark.parametrize("year", YEARS)
@pytest.mark.parametrize("region", REGIONS)
def test_served_q5_equals_the_plain_reference(server, q5, region, year):
    from benchmark.harness.compare import compare_rows

    params = {"region": region, "year": year}
    got = _ask(server, q5.template.format(**params))
    want = q5.reference(TINY_SF, params)
    # a region has five nations; tiny's 100 suppliers leave one out now and then
    assert 3 <= len(want) <= 5
    assert compare_rows(got, want) == (0, 0.0)
    assert got == want
    # rows in the ORDER BY's order: the revenue downwards
    revenues = [float(r[1]) for r in got]
    assert revenues == sorted(revenues, reverse=True)


def test_the_reference_imports_nothing_of_the_program():
    import subprocess

    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.harness import cells; "
            "q = cells.Query('q5'); "
            "q.reference(0.01, {'region': 'ASIA', 'year': 1994}); "
            "bad = [m for m in sys.modules if m.split('.')[0] == "
            "'presto_tpu']; sys.exit(1 if bad else 0)" % ROOT)
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0


@pytest.mark.parametrize("fused", [True, False])
def test_one_query_counts_its_builds_and_probe_pages(q5, fused):
    """Five builds, all direct-address on one key column: customer's join
    on two clauses is narrowed to its customer key, and no page goes
    through `_emit_expanded` or a binary search. Every lineitem page is
    counted once for each of the five probes: the same whether the probes
    ran inside a fused segment or as `LookupJoinOperator`s (session property
    `segment_fusion`)."""
    result, gained = _run(q5.template.format(region="ASIA", year=1994),
                          segment_fusion=fused)
    assert len(result.rows) == 5
    segments = (result.stats or {}).get("segments") or {"count": 0}
    assert segments["count"] == (1 if fused else 0)
    assert gained["join.builds"] == PROBES
    assert gained["join.builds.dense"] == PROBES
    assert gained.get("join.builds.sorted", 0) == 0
    assert gained.get("join.builds.multikey", 0) == 0
    assert gained["join.builds.narrowed"] == 1
    assert gained["join.build_s.n"] == PROBES
    assert gained.get("join.probe.expanded_pages", 0) == 0
    assert gained.get("join.probe.sorted_pages", 0) == 0
    scanned = gained["join.probe.pages"] // PROBES
    assert scanned >= 1
    assert gained["join.probe.pages"] == PROBES * scanned
    if fused:
        (segment,) = result.stats["segments"]["segments"]
        assert segment["operators"] == ["LookupJoin(inner)"] * PROBES + [
            "FilterProject", "FilterProject", "HashAggregation(single)"]
        assert segment["dispatches"] == scanned
    # the filtered builds' pages (region, orders); nothing packs lineitem's
    # (the plan's test below)
    assert gained["coalesce.packed_pages"] == gained["coalesce.pages"] >= 1
    # n_name is a dictionary of 25: the dense aggregation, behind a join
    assert gained["agg.direct.dense_pages"] >= 1
    assert gained["agg.direct.dense_pages"] == gained["agg.direct.pages"]


def test_a_join_that_fans_out_is_counted_as_expanded_pages():
    """The order Q5 had before PR 36 joined customer on the nation alone:
    such a probe goes through `_emit_expanded`, once a page."""
    result, gained = _run("select count(*) from supplier, customer "
                          "where s_nationkey = c_nationkey")
    assert result.rows[0][0] > 1500 * 100 / 25 / 2
    assert gained["join.probe.expanded_pages"] == gained["join.probe.pages"] >= 1


def test_the_two_column_probe_stands_alone_between_two_fused_segments(q5):
    """It no longer does: the customer join is keyed on `c_custkey` alone,
    so its probe fuses with the other four and the nation is checked by a
    `FilterProject` in the same segment; no `Coalesce` stands behind
    lineitem's probes, and EXPLAIN ANALYZE names the one key and the
    filter."""
    runner = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    plan = "\n".join(str(r[0]) for r in runner.execute(
        "explain analyze " + q5.template.format(region="ASIA", year=1994)).rows)
    probe = plan[plan.index("pipeline 5:"):plan.index("fused segments:")]
    operators = [line.split()[0] for line in probe.splitlines()[1:] if line]
    assert operators == ["TableScan", "FusedSegment[LookupJoin(in",
                         "OrderBy", "PageConsumer"]
    assert "pipeline 5: " + "+".join(
        ["LookupJoin(inner)"] * PROBES +
        ["FilterProject", "FilterProject", "HashAggregation(single)"]) in plan
    assert "narrowed joins: 1\n  LookupJoin(inner) on o_custkey = " \
        "c_custkey, filter equal(s_nationkey, c_nationkey)" in plan


def test_the_order_and_the_builds_have_their_spans(q5):
    result, gained = _run(q5.template.format(region="ASIA", year=1994),
                          query_trace=True)
    assert gained["planner.reorder_joins_s.n"] == 1
    with open(result.trace_path) as f:
        events = json.load(f)["traceEvents"]
    ordered = [e["args"] for e in events
               if e.get("cat") == "planner" and e["name"] == "reorder_joins"]
    assert ordered == [{"relations": 6, "joins": 5, "widest_rows": 60_032.0}]
    builds = [e["args"] for e in events
              if e.get("cat") == "join" and e["name"] == "build"]
    assert sorted((b["kind"], b["keys"]) for b in builds) == \
        [("dense", 1)] * PROBES
