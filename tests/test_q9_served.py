"""TPC-H Q9 served on one device (PR 34): the deployment
`tpch-sf1-6table-1chip` of the benchmark, at schema `tiny` on the CPU.

A `PrestoTpuServer` over a `LocalQueryRunner`, as `python -m presto_tpu.server`
builds it, answers Q9 from `benchmark/queries/q9.sql` through `client.dbapi`
for three of the 92 colours; every answer is held, row for row and in order,
to the benchmark's plain numpy reference (`benchmark/queries/q9.py`, which
imports nothing of the program). Beside it, what the cell's per-layer metrics
read: of the query's five join builds four take the direct-address table and
ONE, partsupp's on two columns, the sorted form; every lineitem page is
counted once for each of the three probes ahead of the partsupp probe; a
`CoalesceOperator` then packs what the part join kept (PR 35), and each page
it emits is counted once for the partsupp probe, once more as a sorted page
(the binary search) and once for the orders probe, the same whether the
single-key probes ran fused or alone; the build has a span and a histogram.
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
COLOURS = ["green", "almond", "yellow"]   # qgen's default, the first, the last
PROBES = 5


@pytest.fixture(scope="module")
def q9():
    from benchmark.harness import cells

    return cells.Query("q9")


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


def _join_numbers():
    raw = METRICS.raw_snapshot("join.")
    numbers = dict(raw["counters"])
    numbers["join.build_s.n"] = raw["histograms"].get(
        "join.build_s", {"n": 0})["n"]
    numbers.update(METRICS.raw_snapshot("coalesce.")["counters"])
    return numbers


def _run(sql, **properties):
    """-> (result, what each `join.*` and `coalesce.*` number gained over the
    query)"""
    runner = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny", properties=properties))
    before = _join_numbers()
    result = runner.execute(sql)
    after = _join_numbers()
    return result, {k: v - before.get(k, 0) for k, v in after.items()}


@pytest.fixture(scope="module")
def probe_pages(q9):
    """-> (pages of lineitem a query at `tiny` scans, pages the partsupp
    probe sees): what each of the two fused segments of the probe pipeline
    says it dispatched, one a page. A `CoalesceOperator` packs the part
    join's survivors between them, so the second sees fewer."""
    result, _gained = _run(q9.template.format(color="green"))
    first, second = result.stats["segments"]["segments"]
    assert first["operators"] == ["LookupJoin(inner)"] * 3
    assert second["operators"][-1] == "HashAggregation(single)"
    assert first["dispatches"] > second["dispatches"] >= 1
    return first["dispatches"], second["dispatches"]


@pytest.mark.parametrize("colour", COLOURS)
def test_served_q9_equals_the_plain_reference(server, q9, colour):
    from benchmark.harness.compare import compare_rows

    got = _ask(server, q9.template.format(color=colour))
    want = q9.reference(TINY_SF, {"color": colour})
    assert len(want) > 25, "a colour keeps several years of every nation"
    assert compare_rows(got, want) == (0, 0.0)
    assert got == want
    # rows in the ORDER BY's order: nation, then the year downwards
    assert got == sorted(got, key=lambda r: (r[0], -r[1]))


def test_the_reference_imports_nothing_of_the_program():
    import subprocess

    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.harness import cells; "
            "q = cells.Query('q9'); q.reference(0.01, {'color': 'green'}); "
            "bad = [m for m in sys.modules if m.split('.')[0] == "
            "'presto_tpu']; sys.exit(1 if bad else 0)" % ROOT)
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0


@pytest.mark.parametrize("fused", [True, False])
def test_one_query_counts_its_builds_and_probe_pages(q9, probe_pages, fused):
    """Five builds, four direct-address and one sorted on two key columns.
    Every lineitem page is counted once for each of the three probes ahead
    of the Coalesce; every packed page once for the partsupp probe, under
    `join.probe.sorted_pages` for it alone, and once for the orders probe:
    the same whether the single-key probes ran inside fused segments or as
    `LookupJoinOperator`s (session property `segment_fusion`)."""
    result, gained = _run(q9.template.format(color="green"),
                          segment_fusion=fused)
    assert len(result.rows) > 25
    segments = (result.stats or {}).get("segments") or {"count": 0}
    assert segments["count"] == (2 if fused else 0)
    assert gained["join.builds"] == PROBES
    assert gained["join.builds.dense"] == 4
    assert gained["join.builds.sorted"] == 1
    assert gained["join.builds.multikey"] == 1
    scanned, packed = probe_pages
    assert gained["join.probe.pages"] == 3 * scanned + (PROBES - 3) * packed
    assert gained["join.probe.sorted_pages"] == packed
    assert gained["join.build_s.n"] == PROBES


@pytest.mark.parametrize("fused", [True, False])
def test_lineitems_pages_go_through_a_coalesce_in_pack_mode(q9, probe_pages,
                                                            fused):
    """Two Coalesces a Q9: one behind part's filtered scan, as in any join
    on the filtered part, and one behind the part join. The colour keeps a
    twentieth of the parts and so of the lines: both decide for pack mode on
    their first page, and every page of lineitem goes through
    `block._compact`, fused or not."""
    scanned, _packed = probe_pages
    _result, parts = _run("select count(*) from partsupp, part where "
                          "ps_partkey = p_partkey and p_name like '%green%'")
    assert parts["coalesce.packed_pages"] == parts["coalesce.pages"] >= 1
    _result, gained = _run(q9.template.format(color="green"),
                           segment_fusion=fused)
    assert gained["coalesce.pages"] == parts["coalesce.pages"] + scanned
    assert gained["coalesce.packed_pages"] == gained["coalesce.pages"]


def test_the_two_column_probe_stands_alone_between_two_fused_segments(q9):
    """`probe_plan_fusible` refuses a probe on more than one key column, so
    Q9's probe pipeline is three fused probes, a Coalesce that packs what
    the part join kept, the partsupp probe as a standalone
    `LookupJoinOperator`, then the orders probe fused with the
    aggregation."""
    runner = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    plan = "\n".join(str(r[0]) for r in runner.execute(
        "explain analyze " + q9.template.format(color="green")).rows)
    probe = plan[plan.index("pipeline 5:"):plan.index("fused segments:")]
    operators = [line.split()[0] for line in probe.splitlines()[1:] if line]
    assert operators == ["TableScan", "FusedSegment[LookupJoin(in",
                         "Coalesce", "LookupJoin(inner)",
                         "FusedSegment[LookupJoin(in", "OrderBy",
                         "PageConsumer"]


def test_the_build_has_a_span_with_its_kind_keys_and_pages(q9):
    result, _gained = _run(q9.template.format(color="green"), query_trace=True)
    with open(result.trace_path) as f:
        events = json.load(f)["traceEvents"]
    builds = [e["args"] for e in events
              if e.get("cat") == "join" and e["name"] == "build"]
    assert sorted((b["kind"], b["keys"]) for b in builds) == \
        [("dense", 1)] * 4 + [("sorted", 2)]
    assert all(b["pages"] >= 1 for b in builds)


def test_probe_pages_are_counted_by_the_sources_kind():
    from presto_tpu.ops.hash_join import count_probe_pages

    class Source:
        def __init__(self, kind):
            self.kind = kind

    before = _join_numbers()
    count_probe_pages([Source("dense"), Source("sorted"), Source("dense")], 4)
    count_probe_pages((Source("sorted"),))
    count_probe_pages([], 7)
    after = _join_numbers()
    assert after["join.probe.pages"] - before.get("join.probe.pages", 0) == 13
    assert after["join.probe.sorted_pages"] - \
        before.get("join.probe.sorted_pages", 0) == 5
