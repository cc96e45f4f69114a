"""Ring-2 SQL end-to-end tests: full parse->plan->optimize->execute against the
sqlite oracle (the reference's AbstractTestQueries + H2QueryRunner pattern,
presto-tests/.../QueryAssertions.java:97). Runs the TPC-H north-star queries
(BASELINE Q1/Q3/Q5/Q6/Q9) plus coverage queries at schema `tiny`.
"""
import datetime
import re

import pytest

from presto_tpu.runner import LocalQueryRunner
from presto_tpu.models.tpch_sql import QUERIES
from presto_tpu.utils.testing import (TPCH_KEY_SUFFIXES, SqliteOracle,
                                      assert_rows_equal)


def to_sqlite(sql: str) -> str:
    """Translate engine SQL to the oracle dialect: dates are stored as
    days-since-epoch ints, decimals as floats."""
    def days(y, m, d):
        return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days

    def date_arith(m):
        y, mo, d = int(m.group(1)), int(m.group(2)), int(m.group(3))
        base = datetime.date(y, mo, d)
        op, n, unit = m.group(4), int(m.group(5)), m.group(6).lower()
        n = n if op == "+" else -n
        if unit == "day":
            out = base + datetime.timedelta(days=n)
        elif unit == "month":
            k = base.month - 1 + n
            out = base.replace(year=base.year + k // 12, month=k % 12 + 1)
        else:
            out = base.replace(year=base.year + n)
        return str((out - datetime.date(1970, 1, 1)).days)

    sql = re.sub(r"date\s+'(\d+)-(\d+)-(\d+)'\s*([+-])\s*interval\s+'(\d+)'"
                 r"\s+(day|month|year)", date_arith, sql, flags=re.I)
    sql = re.sub(r"date\s+'(\d+)-(\d+)-(\d+)'",
                 lambda m: str(days(int(m.group(1)), int(m.group(2)),
                                    int(m.group(3)))), sql, flags=re.I)
    sql = re.sub(r"extract\s*\(\s*year\s+from\s+([a-z_][a-z0-9_.]*)\s*\)",
                 r"CAST(strftime('%Y', (\1)*86400.0, 'unixepoch') AS INTEGER)",
                 sql, flags=re.I)

    # decimal-literal arithmetic folded exactly: sqlite's float 0.06 + 0.01 is
    # 0.069999..., which would wrongly exclude the 0.07 bucket our exact decimal
    # engine includes
    from decimal import Decimal

    def dec_fold(m):
        a, op, b = Decimal(m.group(1)), m.group(2), Decimal(m.group(3))
        return str(a + b if op == "+" else a - b)
    sql = re.sub(r"(\d+\.\d+)\s*([+-])\s*(\d+\.\d+)", dec_fold, sql)
    return sql


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner()


@pytest.fixture(scope="module")
def oracle():
    o = SqliteOracle()
    o.load_tpch(0.01, ["region", "nation", "supplier", "part", "partsupp",
                       "customer", "orders", "lineitem"])
    return o


def check(runner, oracle, sql, ordered=False, rel_tol=1e-6):
    res = runner.execute(sql)
    exp = oracle.query(to_sqlite(sql))

    def norm(row):
        out = []
        for v in row:
            if isinstance(v, datetime.date):
                out.append((v - datetime.date(1970, 1, 1)).days)
            else:
                out.append(v)
        return out
    assert_rows_equal([norm(r) for r in res.rows], exp, ordered=ordered,
                      rel_tol=rel_tol)
    return res


# ---------------------------------------------------------------------------
# basic SQL coverage
# ---------------------------------------------------------------------------

def test_select_filter_project(runner, oracle):
    check(runner, oracle,
          "select n_name, n_nationkey + 100 from nation where n_regionkey = 2")


def test_order_by_limit(runner, oracle):
    check(runner, oracle,
          "select c_custkey, c_acctbal from customer "
          "order by c_acctbal desc, c_custkey limit 7", ordered=True)


def test_distinct(runner, oracle):
    check(runner, oracle, "select distinct o_orderpriority from orders")


def test_in_list_and_between(runner, oracle):
    check(runner, oracle,
          "select count(*) from orders where o_orderpriority in "
          "('1-URGENT', '3-MEDIUM') and o_totalprice between 1000 and 2000")


def test_global_agg(runner, oracle):
    check(runner, oracle,
          "select count(*), sum(o_totalprice), min(o_orderdate), "
          "max(o_orderdate), avg(o_totalprice) from orders")


def test_group_by_having(runner, oracle):
    check(runner, oracle,
          "select o_custkey, count(*) c from orders group by o_custkey "
          "having count(*) > 25")


def test_explicit_join_on(runner, oracle):
    check(runner, oracle,
          "select n_name, r_name from nation join region "
          "on n_regionkey = r_regionkey where r_name <> 'ASIA'")


def test_left_join(runner, oracle):
    check(runner, oracle,
          "select c.c_custkey, o.o_orderkey from customer c "
          "left join orders o on c.c_custkey = o.o_custkey "
          "where c.c_custkey < 50")


def test_right_join(runner, oracle):
    check(runner, oracle,
          "select n_name, c_name from customer "
          "right join nation on c_nationkey = n_nationkey "
          "and c_acctbal > 9000")


def test_full_join(runner, oracle):
    # orders 1..6 vs a filtered customer set: unmatched rows on both sides
    check(runner, oracle,
          "select c_name, o_orderkey from "
          "(select * from customer where c_custkey < 30) c full join "
          "(select * from orders where o_orderkey < 7) o "
          "on c_custkey = o_custkey")


def test_full_join_duplicates(runner, oracle):
    # non-unique build keys exercise the range-expansion + visited marking path
    check(runner, oracle,
          "select n.n_regionkey, r_name from "
          "(select * from nation where n_nationkey < 12) n full join region "
          "on n.n_regionkey = r_regionkey")


def test_in_subquery_semijoin(runner, oracle):
    check(runner, oracle,
          "select count(*) from orders where o_custkey in "
          "(select c_custkey from customer where c_mktsegment = 'BUILDING')")


def test_not_in_subquery(runner, oracle):
    check(runner, oracle,
          "select count(*) from customer where c_custkey not in "
          "(select o_custkey from orders)")


def test_scalar_subquery(runner, oracle):
    check(runner, oracle,
          "select count(*) from orders where o_totalprice > "
          "(select avg(o_totalprice) from orders)")


def test_case_expression(runner, oracle):
    check(runner, oracle,
          "select sum(case when o_orderstatus = 'F' then o_totalprice else 0 end),"
          " count(case when o_orderpriority = '1-URGENT' then 1 end) from orders")


def test_cte(runner, oracle):
    check(runner, oracle,
          "with big as (select * from orders where o_totalprice > 100000) "
          "select count(*) from big")


def test_union_all_and_distinct(runner, oracle):
    check(runner, oracle,
          "select n_regionkey from nation union all select r_regionkey from region")
    check(runner, oracle,
          "select n_regionkey from nation union select r_regionkey from region")


def test_cross_join_small(runner, oracle):
    check(runner, oracle,
          "select count(*) from nation, region "
          "where n_regionkey = r_regionkey and r_name = 'AFRICA'")


# ---------------------------------------------------------------------------
# TPC-H: the full 22-query suite vs the oracle (AbstractTestQueries pattern)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q", sorted(QUERIES))
def test_tpch(runner, oracle, q):
    check(runner, oracle, QUERIES[q], ordered=True, rel_tol=1e-9)


def test_oracle_indexes_the_tpch_keys(oracle):
    """Every key column of the loaded tables carries an index, so that sqlite
    plans Q21's correlated NOT EXISTS as an index search of lineitem, not a
    scan of it per outer row."""
    keys = {col for t, in oracle.query(
                "select name from sqlite_master where type = 'table'")
            for _, col, *_ in oracle.query(f"pragma table_info({t})")
            if col.endswith(TPCH_KEY_SUFFIXES)}
    assert len(keys) == 15, keys
    indexed = {col for col, in oracle.query(
        "select ii.name from sqlite_master m, pragma_index_info(m.name) ii "
        "where m.type = 'index'")}
    assert keys <= indexed, keys - indexed
    plan = [detail for _, _, _, detail in oracle.query(
        "explain query plan " + to_sqlite(QUERIES[21]))]
    assert any(d.startswith("SEARCH l3 USING INDEX") for d in plan), plan
