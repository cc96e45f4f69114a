"""An INNER join of several equi-clauses is keyed on ONE clause when the build
is unique on that clause's integer column alone
(`LocalExecutionPlanner._narrowed_clause`): the build is a single-key unique
one (a direct-address table, a probe that fuses) and the other clauses are
checked as SQL equalities by a `FilterProject` behind the probe. Counted as
`join.builds.narrowed`, shown by EXPLAIN ANALYZE.

Where it engages and where it does not (LEFT joins, builds unique only on
the whole tuple, single-clause joins, a unique column that is not an
integer), and that the narrowed plan returns the rows of the multi-key
plan, of the per-operator pipeline and of a plain Python join, with keys
that match where a checked clause does not and NULLs on either side of it.
"""
import pytest

from presto_tpu.connectors.memory import MemoryMetadata
from presto_tpu.metadata import Session
from presto_tpu.models.tpch_sql import QUERIES
from presto_tpu.runner import LocalQueryRunner
from presto_tpu.utils.metrics import METRICS

Q5_SUPPLIER_CUSTOMER = (
    "select count(*), sum(l_quantity) from lineitem, supplier, orders, customer "
    "where l_suppkey = s_suppkey and l_orderkey = o_orderkey "
    "and o_custkey = c_custkey and s_nationkey = c_nationkey")

# (build rows, probe rows) as (k, n, v) / (k, n, x): keys that match where
# the nation does not, NULL nations on both sides, NULL and absent keys
BUILD = [(1, 10, 100), (2, 20, 200), (3, None, 300), (4, 40, 400),
         (5, 50, 500), (None, 10, 600)]
PROBE = [(1, 10, 1), (1, 11, 2), (2, 20, 3), (2, None, 4), (3, None, 5),
         (3, 30, 6), (4, 40, 7), (4, 40, 8), (None, 10, 9), (6, 60, 10),
         (5, 51, 11), (5, 50, 12)]
TWO_CLAUSES = ("select p.x, b.v from memory.default.nj_probe p "
               "join memory.default.nj_build b on p.k = b.k and p.n = b.n")


def _runner(**properties):
    return LocalQueryRunner(session=Session(catalog="tpch", schema="tiny",
                                            properties=properties))


def _narrowed_builds(runner, sql):
    """-> (result, what `join.builds.narrowed` and `.multikey` gained)"""
    def counts():
        c = METRICS.raw_snapshot("join.")["counters"]
        return c.get("join.builds.narrowed", 0), c.get("join.builds.multikey", 0)

    before = counts()
    result = runner.execute(sql)
    after = counts()
    return result, (after[0] - before[0], after[1] - before[1])


@pytest.fixture
def memory_tables(monkeypatch):
    """`nj_build` (k bigint, n bigint, v bigint, d double) and `nj_probe`
    (k, n, x, d); the test declares which of the build's columns is unique
    through `unique(...)`."""
    declared = {}
    monkeypatch.setattr(
        MemoryMetadata, "get_unique_column_sets",
        lambda self, table: list(declared.get(table.schema_table.table, [])))
    runner = _runner()
    for name, rows, third in (("nj_build", BUILD, "v"), ("nj_probe", PROBE, "x")):
        runner.execute(f"create table memory.default.{name} as select "
                       f"o_custkey as k, o_custkey as n, o_custkey as {third}, "
                       f"o_totalprice * 1e0 as d from orders limit 0")
        values = ", ".join(
            "(" + ", ".join("null" if c is None else str(c) for c in r) +
            f", {r[0] or 0}e0)" for r in rows)
        runner.execute(f"insert into memory.default.{name} values {values}")

    def unique(*columns):
        declared["nj_build"] = [tuple(columns)] if columns else []
        return runner
    yield unique
    for name in ("nj_build", "nj_probe"):
        runner.execute(f"drop table memory.default.{name}")


def _python_join():
    """SQL semantics by hand: a NULL on either side of a clause never matches."""
    out = []
    for k, n, x in PROBE:
        for bk, bn, v in BUILD:
            if None not in (k, n, bk, bn) and (k, n) == (bk, bn):
                out.append((x, v))
    return sorted(out)


CASES = {
    # (sql, what the memory tables declare unique or None, narrowed builds)
    "q5_customer": (QUERIES[5], None, 1),
    "q5_supplier_customer": (Q5_SUPPLIER_CUSTOMER, None, 1),
    "memory_integer_unique": (TWO_CLAUSES, ("k",), 1),
    "left_join": (
        "select count(*), count(c_name) from "
        "(select o_custkey, s_nationkey from lineitem, supplier, orders "
        " where l_suppkey = s_suppkey and l_orderkey = o_orderkey) t "
        "left join customer on o_custkey = c_custkey and s_nationkey = c_nationkey",
        None, 0),
    "q9_partsupp_pair": (QUERIES[9], None, 0),
    "single_clause": ("select count(*) from orders, customer "
                      "where o_custkey = c_custkey", None, 0),
    "memory_double_unique": (
        "select p.x, b.v from memory.default.nj_probe p "
        "join memory.default.nj_build b on p.d = b.d and p.k = b.k",
        ("d",), 0),
}


@pytest.mark.parametrize("case", list(CASES))
def test_where_the_rule_narrows_a_join(case, memory_tables):
    sql, declared, narrowed = CASES[case]
    runner = memory_tables(*declared) if declared else _runner()
    result, (got, multikey) = _narrowed_builds(runner, sql)
    assert result.rows
    assert got == narrowed
    # a join it leaves alone keeps its build on every clause
    assert multikey == (0 if narrowed else int(case != "single_clause"))
    plan = "\n".join(str(r[0]) for r in
                     runner.execute("explain analyze " + sql).rows)
    assert ("narrowed joins: 1\n" in plan) == bool(narrowed)
    if case.startswith("q5"):
        assert "on o_custkey = c_custkey, filter " \
            "equal(s_nationkey, c_nationkey)" in plan


@pytest.mark.parametrize("fused", [True, False])
def test_the_narrowed_join_returns_the_rows_of_every_clause(memory_tables,
                                                             fused):
    """The narrowed plan (unique on `k`) against the multi-key plan (nothing
    declared unique: a sorted build on both columns) and a Python join;
    fused into a segment and as one operator at a time."""
    want = _python_join()
    assert len(want) == 5      # 1/10, 2/20, 4/40 twice, 5/50
    runner = memory_tables("k")
    runner.session.properties["segment_fusion"] = fused
    narrowed, (count, _) = _narrowed_builds(runner, TWO_CLAUSES)
    assert count == 1
    plain = memory_tables()
    both, (count, multikey) = _narrowed_builds(plain, TWO_CLAUSES)
    assert (count, multikey) == (0, 1)
    assert sorted(map(tuple, narrowed.rows)) == want
    assert sorted(map(tuple, both.rows)) == want


def test_q5_narrowed_equals_the_per_operator_pipeline():
    """Q5 for every region at one year: the fused narrowed plan against
    `segment_fusion = False`, row for row."""
    fused, plain = _runner(), _runner(segment_fusion=False)
    for region in ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"):
        sql = QUERIES[5].replace("'ASIA'", f"'{region}'")
        assert fused.execute(sql).rows == plain.execute(sql).rows
