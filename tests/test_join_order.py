"""The join order is priced from key cardinalities (PR 36).

What the tpch connector says of a foreign key's distinct count, what
`cost.join_output_rows` makes of it, the estimate against the repo's own
selectivity table (`BASELINE.md`) and against rows counted at `tiny`, the
shape of Q5's plan under schema `sf1`, and the `EXPLAIN` text of every query a
benchmark cell runs, held letter for letter to the text the tree before this
PR printed (`tests/golden_plans/`, written from that tree).
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from presto_tpu.connectors.tpch import generator  # noqa: E402
from presto_tpu.metadata import Session  # noqa: E402
from presto_tpu.models.tpch_sql import QUERIES  # noqa: E402
from presto_tpu.runner import LocalQueryRunner  # noqa: E402
from presto_tpu.sql.planner import optimizer  # noqa: E402
from presto_tpu.sql.planner.cost import join_output_rows, join_step_cost  # noqa: E402
from presto_tpu.sql.planner.plan import (FilterNode, JoinNode,  # noqa: E402
                                         TableScanNode)

GOLDEN = os.path.join(ROOT, "tests", "golden_plans")

# clause 1.4.2's key relations: (table, foreign key) -> the table it references
FOREIGN_KEYS = {("nation", "n_regionkey"): "region",
                ("supplier", "s_nationkey"): "nation",
                ("customer", "c_nationkey"): "nation",
                ("partsupp", "ps_partkey"): "part",
                ("lineitem", "l_partkey"): "part",
                ("partsupp", "ps_suppkey"): "supplier",
                ("lineitem", "l_suppkey"): "supplier",
                ("orders", "o_custkey"): "customer"}
PRIMARY_KEYS = {"region": "r_regionkey", "nation": "n_nationkey",
                "supplier": "s_suppkey", "part": "p_partkey",
                "customer": "c_custkey", "orders": "o_orderkey"}


def _runner(schema):
    return LocalQueryRunner(session=Session(catalog="tpch", schema=schema))


@pytest.fixture(scope="module")
def sf1():
    return _runner("sf1")


def _plan(runner, sql):
    """-> the optimized plan's root (what EXPLAIN prints)."""
    return runner.plan_sql(sql)


def _first(node, kind):
    """-> the first node of `kind` down the leftmost path."""
    while not isinstance(node, kind):
        node = node.children()[0]
    return node


def _scan(runner, table, columns):
    """-> a TableScanNode of `table` over `columns`, as the planner builds it"""
    return _first(_plan(runner, f"select {', '.join(columns)} from {table}"),
                  TableScanNode)


def _joins(node, out=None):
    out = [] if out is None else out
    if isinstance(node, JoinNode):
        out.append(node)
    for child in node.children():
        _joins(child, out)
    return out


def _leaf(node):
    return _first(node, TableScanNode)


# ------------------------------------------------------------ the statistics

@pytest.mark.parametrize("schema, sf", [("tiny", 0.01), ("sf1", 1.0),
                                        ("sf10", 10.0)])
def test_a_foreign_key_has_its_referenced_tables_distinct_count(schema, sf):
    runner = _runner(schema)
    for (table, column), referenced in FOREIGN_KEYS.items():
        scan = _scan(runner, table, [column])
        stats = runner.metadata.get_table_statistics(scan.table)
        assert stats.columns[column].distinct_count == \
            generator.table_row_count(referenced, sf), (table, column)
    for table, column in PRIMARY_KEYS.items():
        scan = _scan(runner, table, [column])
        stats = runner.metadata.get_table_statistics(scan.table)
        assert stats.columns[column].distinct_count == stats.row_count == \
            generator.table_row_count(table, sf), (table, column)


def test_the_one_key_left_at_its_own_tables_rows_is_l_orderkey(sf1):
    """`l_orderkey` references orders (1,500,000 at SF1) and still reads
    lineitem's 6,001,112: `estimate_rows`' JoinNode arm, which
    `add_exchanges`' broadcast choice reads, divides by it, and with the
    true count `tpch-sf1-mesh4`'s Q3 replicates customer (4 exchanges for 6):
    a plan no PR may change without measuring that cell (PERF.md, section 7).
    The join ORDER does not read it: orders' unique key prices that join."""
    scan = _scan(sf1, "lineitem", ["l_orderkey"])
    stats = sf1.metadata.get_table_statistics(scan.table)
    assert stats.columns["l_orderkey"].distinct_count == stats.row_count


# --------------------------------------------------------------- the estimate

def test_a_join_on_a_25_value_key_fans_out_and_a_primary_key_cannot():
    # 1.2M lines of a region against 150,000 customers on the nation alone
    assert join_output_rows(1.2e6, 150_000, [25.0]) == 1.2e6 * 150_000 / 25
    # the same customers on their primary key: at most a row a line
    assert join_output_rows(1.2e6, 150_000, [], 150_000) == 1.2e6
    # ... of which the nation clause keeps a twenty-fifth
    assert join_output_rows(1.2e6, 150_000, [25.0], 150_000) == 1.2e6 / 25
    # a filtered build keeps its share of the probe
    assert join_output_rows(6e6, 375_000, [], 1.5e6) == 6e6 * 0.25
    # no clause: a cross product; clauses nobody knows: the larger input
    assert join_output_rows(100.0, 7.0, []) == 700.0
    assert join_output_rows(100.0, 7.0, [None]) == 100.0
    assert join_output_rows(3.0, 2.0, [1e9]) == 1.0


def test_a_step_that_fans_out_is_priced_above_one_that_cannot():
    stream = 6e6
    fan_out = join_output_rows(1.2e6, 150_000, [25.0])
    unique = join_output_rows(1.2e6, 93_750, [], 1.5e6)
    assert join_step_cost(stream, 150_000, max(stream, fan_out)).total() > \
        100 * join_step_cost(stream, 93_750, max(stream, unique)).total()


def test_the_estimate_reads_the_selectivity_tables_rows(sf1):
    """`BASELINE.md`'s table: `q3_customer` 0.2 (one market segment of five),
    `q5_region_customers` 0.2 (the customers of one region's nations)."""
    customer = _scan(sf1, "customer", ["c_custkey", "c_nationkey",
                                       "c_mktsegment"])
    rows = optimizer.estimate_rows(customer, sf1.metadata)
    assert rows == 150_000
    filt = _first(_plan(sf1, "select c_custkey from customer "
                             "where c_mktsegment = 'BUILDING'"), FilterNode)
    assert optimizer.estimate_rows(filt, sf1.metadata) / rows == \
        pytest.approx(0.2)
    # customer -> nation -> the one region kept
    nation = _scan(sf1, "nation", ["n_nationkey", "n_regionkey"])
    n_key, n_region = (s for s, _c in nation.assignments)
    c_nation = [s for s, c in customer.assignments
                if c.name == "c_nationkey"][0]
    with_nation = optimizer.join_rows(
        rows, nation, 25.0,
        [(optimizer._join_key_ndv(customer, c_nation, sf1.metadata), n_key)],
        sf1.metadata)
    assert with_nation == rows
    region = _first(_plan(sf1, "select r_regionkey from region "
                               "where r_name = 'ASIA'"), FilterNode)
    r_key = region.outputs()[0]
    in_region = optimizer.join_rows(
        with_nation, region, optimizer.estimate_rows(region, sf1.metadata),
        [(optimizer._join_key_ndv(nation, n_region, sf1.metadata), r_key)],
        sf1.metadata)
    assert in_region / rows == pytest.approx(0.2)


def _step_estimates(runner, root):
    """-> [(the build's table, the greedy's estimate of the step's output)]
    bottom-up over a left-deep join tree, recomputed the way `_greedy_join`
    does: the probe key's distinct count from the scan that owns it."""
    spine = _joins(root)[::-1]                        # innermost first
    leaves = [_leaf(spine[0].left)] + [_leaf(j.right) for j in spine]

    def probe_ndv(symbol):
        for leaf in leaves:
            if symbol.name in {s.name for s in leaf.outputs()}:
                return optimizer._join_key_ndv(leaf, symbol, runner.metadata)
        raise AssertionError(symbol)

    rows = optimizer.estimate_rows(spine[0].left, runner.metadata)
    out = []
    for join in spine:
        rows = optimizer.join_rows(
            rows, join.right,
            optimizer.estimate_rows(join.right, runner.metadata),
            [(probe_ndv(l), r) for l, r in join.criteria], runner.metadata)
        out.append((_leaf(join.right).table.schema_table.table, rows))
    return out


def test_q5s_five_steps_are_estimated_within_four_times_the_counted_rows():
    """At `tiny`, each step's estimate beside `count(*)` of the same joins:
    supplier 60,032 / 60,032, nation 60,032 / 60,032, region 12,006 / 14,989
    (a fifth of the nations hold a quarter of tiny's 100 suppliers), orders
    750 / 2,208 and customer 30 / 90: the connector has no minimum and
    maximum for a date, so a year of o_orderdate is two FILTER_SELECTIVITY
    guesses (0.0625) where 0.147 of the lines' orders lie, and the two
    misses multiply to 2.9. Within a factor of 4, and never a fan-out."""
    tiny = _runner("tiny")
    steps = _step_estimates(tiny, _plan(tiny, QUERIES[5]))
    assert [t for t, _rows in steps] == ["supplier", "nation", "region",
                                         "orders", "customer"]
    froms = ["lineitem, supplier", "nation", "region", "orders", "customer"]
    wheres = ["l_suppkey = s_suppkey", "s_nationkey = n_nationkey",
              "n_regionkey = r_regionkey and r_name = 'ASIA'",
              "l_orderkey = o_orderkey and o_orderdate >= date '1994-01-01' "
              "and o_orderdate < date '1995-01-01'",
              "c_custkey = o_custkey and c_nationkey = s_nationkey"]
    for i, (table, estimate) in enumerate(steps):
        counted = tiny.execute(
            f"select count(*) from {', '.join(froms[:i + 1])} "
            f"where {' and '.join(wheres[:i + 1])}").rows[0][0]
        assert counted > 0, table
        assert counted / 4 <= estimate <= counted * 4, (table, estimate,
                                                        counted)


# ------------------------------------------------------------- Q5's plan, sf1

def test_q5_under_sf1_has_no_join_that_can_fan_out(sf1):
    root = _plan(sf1, QUERIES[5])
    joins = _joins(root)
    assert len(joins) == 5
    for join in joins:
        cover = optimizer._unique_cover(
            join.right, {r.name for _l, r in join.criteria}, sf1.metadata)
        assert cover is not None, join.criteria
    customer = [j for j in joins
                if _leaf(j.right).table.schema_table.table == "customer"][0]
    assert {(l.name, r.name) for l, r in customer.criteria} == \
        {("s_nationkey", "c_nationkey"), ("o_custkey", "c_custkey")}
    below = {_leaf(j.right).table.schema_table.table
             for j in _joins(customer.left)}
    assert "orders" in below and customer is joins[0]
    with open(os.path.join(GOLDEN, "q5_sf1.txt")) as f:
        assert sf1.explain(QUERIES[5]) + "\n" == f.read()


def test_a_composite_key_join_keeps_the_builds_share_of_its_probe(sf1):
    """Q9's partsupp on (ps_partkey, ps_suppkey): taken as independent the
    two clauses would say 6M x 800k / (200k x 10k) = 2,400 rows and move the
    join to the front; they cover partsupp's primary key, so every line finds
    its one row, and a filtered partsupp keeps its share of the lines."""
    lineitem = _scan(sf1, "lineitem", ["l_partkey", "l_suppkey"])
    l_part, l_supp = (s for s, _c in lineitem.assignments)
    lines = optimizer.estimate_rows(lineitem, sf1.metadata)

    def estimate(build):
        keys = {c.name: s for s, c in _leaf(build).assignments}
        return optimizer.join_rows(
            lines, build, optimizer.estimate_rows(build, sf1.metadata),
            [(optimizer._join_key_ndv(lineitem, l_part, sf1.metadata),
              keys["ps_partkey"]),
             (optimizer._join_key_ndv(lineitem, l_supp, sf1.metadata),
              keys["ps_suppkey"])], sf1.metadata)

    partsupp = _scan(sf1, "partsupp", ["ps_partkey", "ps_suppkey"])
    assert estimate(partsupp) == lines
    filtered = _first(_plan(sf1, "select ps_partkey, ps_suppkey from partsupp "
                                 "where ps_availqty < 100"), FilterNode)
    kept = optimizer.estimate_rows(filtered, sf1.metadata) / 800_000
    assert 0 < kept < 1
    assert estimate(filtered) == pytest.approx(lines * kept)
    naive = join_output_rows(lines, 800_000, [200_000.0, 10_000.0])
    assert naive < lines / 1000


# ------------------------------------------------- the cells' plans, unchanged

@pytest.mark.parametrize("schema, query", [
    ("sf1", 1), ("sf1", 3), ("sf1", 6), ("sf1", 9), ("sf10", 1), ("sf10", 6)])
def test_a_cells_plan_is_the_text_the_tree_before_pr36_printed(schema, query):
    with open(os.path.join(GOLDEN, f"q{query}_{schema}.txt")) as f:
        golden = f.read()
    assert _runner(schema).explain(QUERIES[query]) + "\n" == golden


def _exchange_kinds(text):
    """-> {kind: fragments whose output exchange is of it}, and the key
    lists of the repartitions, from `explain`'s fragment heads"""
    kinds, keys = {}, []
    for line in text.splitlines():
        if line.startswith("Fragment ") and " output=" in line:
            kind = line.split(" output=")[1].split(" ")[0]
            kinds[kind] = kinds.get(kind, 0) + 1
            if kind == "repartition":
                keys.append(line.split(" keys=")[1])
    return kinds, keys


@pytest.mark.parametrize("query, golden, kinds, two_column", [
    (3, "q3_sf1_mesh4.txt", {"repartition": 5, "gather": 1}, 0),
    (5, "q5_sf1_mesh4.txt",
     {"broadcast": 3, "repartition": 5, "merge": 1, "gather": 1}, 2)],
    ids=["q3_sf1_mesh4", "q5_sf1_mesh4"])
def test_the_mesh_cells_q3_plans_the_same_fragments_and_exchanges(
        eight_devices, query, golden, kinds, two_column):
    """As `tpch-sf1-mesh4` and `tpch-sf1-q5-mesh4` plan it: schema `sf1.0`,
    four workers, no session property (`add_exchanges` chooses by cost:
    Q3's joins PARTITIONED; Q5's supplier, nation and region replicated,
    its cyclic edge on two columns, its ORDER BY merged)."""
    from presto_tpu.parallel.mesh import MeshContext
    from presto_tpu.parallel.runner import DistributedQueryRunner

    runner = DistributedQueryRunner(
        MeshContext(eight_devices[:4], n_workers=4),
        session=Session(catalog="tpch", schema="sf1.0"))
    with open(os.path.join(GOLDEN, golden)) as f:
        expected = f.read()
    text = runner.explain(QUERIES[query])
    assert text + "\n" == expected
    if query == 3:
        assert text.count("output=repartition") == 5 and \
            "broadcast" not in text
    found, keys = _exchange_kinds(text)
    assert found == kinds
    assert sum(k.count(",") == 1 for k in keys) == two_column


def test_the_order_is_timed_as_a_span_and_a_histogram():
    import json

    from presto_tpu.utils.metrics import METRICS

    def observed():
        return METRICS.raw_snapshot("planner.")["histograms"].get(
            "planner.reorder_joins_s", {"n": 0, "total": 0.0})

    runner = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny", properties={"query_trace": True}))
    before = observed()
    result = runner.execute(QUERIES[5])
    after = observed()
    assert after["n"] == before["n"] + 1
    assert 0 < after["total"] - before["total"] < 1.0
    with open(result.trace_path) as f:
        events = json.load(f)["traceEvents"]
    spans = [e for e in events
             if e.get("cat") == "planner" and e["name"] == "reorder_joins"]
    assert len(spans) == 1     # its args: tests/test_q5_served.py
    plans = [e for e in events
             if e.get("cat") == "lifecycle" and e["name"] == "plan"]
    assert plans and plans[0]["ts"] <= spans[0]["ts"] and \
        spans[0]["ts"] + spans[0]["dur"] <= plans[0]["ts"] + plans[0]["dur"]
