"""Distributed SQL end-to-end: the full planner-driven multi-worker path.

parse -> plan -> AddExchanges -> fragment -> per-worker drivers + collective
exchanges over the virtual 8-device CPU mesh, checked against the single-chip
LocalQueryRunner (itself oracle-checked in test_sql_e2e.py). The reference
pattern is AbstractTestDistributedQueries running the same AbstractTestQueries
suite through DistributedQueryRunner.java:77.

Covers the BASELINE north-star queries (Q1/Q3/Q5/Q9) plus exchange-shape
coverage: global agg (GATHER), distinct agg (input repartition), semi join
(repartition both sides), NOT IN (broadcast of the filtering side), cross-join
scalar subquery (BROADCAST), and UNION.

Every distinct query shape compiles its own 8-way shard_map collectives
(minutes of XLA time per fresh process), so tier-1 keeps one representative
test per exchange kind and the exhaustive ladder runs under `-m slow`
(tests/test_streaming_exchange.py adds the streaming-vs-barrier differentials
on a cheaper 2-device mesh).
"""
import pytest

from presto_tpu.models.tpch_sql import QUERIES
from presto_tpu.parallel.runner import DistributedQueryRunner
from presto_tpu.runner import LocalQueryRunner
from presto_tpu.utils.testing import assert_rows_equal


@pytest.fixture(scope="module")
def dist():
    return DistributedQueryRunner()


@pytest.fixture(scope="module")
def local():
    return LocalQueryRunner()


def check(dist, local, sql, ordered=True):
    d = dist.execute(sql)
    l = local.execute(sql)
    assert_rows_equal(d.rows, l.rows, ordered=ordered)
    return d


def test_dist_group_by(dist, local):
    check(dist, local,
          "select n_regionkey, count(*), min(n_name), max(n_nationkey) "
          "from nation group by n_regionkey order by n_regionkey")


@pytest.mark.slow
def test_dist_global_agg(dist, local):
    check(dist, local,
          "select count(*), sum(o_totalprice), avg(o_totalprice) from orders")


@pytest.mark.slow
def test_dist_distinct_agg(dist, local):
    check(dist, local,
          "select count(distinct o_custkey) from orders")


def test_dist_join(dist, local):
    check(dist, local,
          "select n_name, r_name from nation join region "
          "on n_regionkey = r_regionkey order by n_name")


@pytest.mark.slow
def test_dist_semijoin(dist, local):
    check(dist, local,
          "select c_name from customer where c_nationkey in "
          "(select n_nationkey from nation where n_regionkey = 1) "
          "order by c_name limit 20")


@pytest.mark.slow
def test_dist_not_in(dist, local):
    check(dist, local,
          "select n_name from nation where n_regionkey not in "
          "(select r_regionkey from region where r_name like 'A%') "
          "order by n_name")


@pytest.mark.slow
def test_dist_scalar_subquery(dist, local):
    check(dist, local,
          "select o_orderkey from orders "
          "where o_totalprice > (select avg(o_totalprice) from orders) "
          "order by o_orderkey limit 10")


@pytest.mark.slow
def test_dist_union(dist, local):
    check(dist, local,
          "select n_name from nation where n_regionkey = 0 union all "
          "select n_name from nation where n_nationkey < 5 order by 1")


def test_dist_union_with_values(dist, local):
    # a SINGLE-distribution union child (VALUES) must not be rematerialized on
    # every worker of the SOURCE-partitioned union fragment
    check(dist, local,
          "select n_nationkey from nation where n_regionkey = 0 "
          "union all select 999 order by 1")
    check(dist, local,
          "select count(*) from (select 1 as x union all select 2) t")


@pytest.mark.slow
@pytest.mark.parametrize("q", [1, 3, 5, 9])
def test_dist_tpch(dist, local, q):
    check(dist, local, QUERIES[q])


def test_cbo_broadcasts_small_builds(dist):
    # DetermineJoinDistributionType: Q5's dimension builds (nation/region/...)
    # are under the broadcast threshold -> replicated, so the lineitem probe
    # never repartitions for the joins
    plan = dist.explain(QUERIES[5])
    assert "output=broadcast" in plan
    frags = plan.split("Fragment")
    lineitem_frag = next(f for f in frags if "tiny.lineitem" in f)
    assert "RemoteSource" in lineitem_frag  # joins happen at the probe


def test_broadcast_joins_pack_survivors_ahead_of_a_two_column_probe(local):
    """Both builds replicated, so one fragment holds the whole probe chain:
    the s1 join keeps a third of partsupp's rows (INNER, one unique key) and
    feeds a probe on two columns, which cannot fuse, and the local planner
    puts a Coalesce between them (PR 35). Every worker's stream packs. The
    s2 join is a LEFT one: an INNER join on these two clauses is keyed on
    s2's unique `s_suppkey` alone and fuses (`_narrowed_clause`)."""
    from presto_tpu.metadata import Session
    from presto_tpu.utils.metrics import METRICS

    bcast = DistributedQueryRunner(
        session=Session(catalog="tpch", schema="tiny",
                        properties={"join_distribution_type": "BROADCAST"}))
    sql = ("select count(*), sum(ps_supplycost), max(s2.s_acctbal), "
           "count(s2.s_suppkey) "
           "from partsupp join supplier s1 on ps_suppkey = s1.s_suppkey "
           "and s1.s_nationkey < 8 "
           "left join supplier s2 on s2.s_suppkey = ps_suppkey "
           "and s2.s_nationkey = s1.s_nationkey")
    d = check(bcast, local, sql)
    assert d.rows[0][0] > 0
    # again on the mesh alone: its one Coalesce is the new one (s1's filtered
    # scan is a fragment of its own and feeds an exchange, not a join)
    names = ("coalesce.pages", "coalesce.packed_pages")
    before = [METRICS.counter_value(n) for n in names]
    bcast.execute(sql)
    seen, packed = (METRICS.counter_value(n) - b
                    for n, b in zip(names, before))
    assert seen == packed >= 1


@pytest.mark.slow
def test_forced_partitioned_matches_broadcast(local):
    from presto_tpu.metadata import Session
    from presto_tpu.parallel.runner import DistributedQueryRunner

    part = DistributedQueryRunner(
        session=Session(catalog="tpch", schema="tiny",
                        properties={"join_distribution_type": "PARTITIONED"}))
    plan = part.explain(QUERIES[5])
    assert "output=broadcast" not in plan
    check(part, local, QUERIES[5])


@pytest.mark.slow
def test_dist_full_join(dist, local):
    # FULL joins repartition both sides (broadcast would duplicate unmatched
    # build rows); per-worker unmatched emission composes to the global result
    check(dist, local,
          "select c_name, o_orderkey from "
          "(select * from customer where c_custkey < 30) c full join "
          "(select * from orders where o_orderkey < 7) o "
          "on c_custkey = o_custkey order by 1, 2")


@pytest.mark.slow
def test_skewed_join_key(dist, local):
    # hot-key stress: ~90% of orders land on one custkey partition via the
    # modulo classes; exchange capacity scales to the live rows, no drops
    sql = ("select o_custkey % 3, count(*), sum(o_totalprice) from orders "
           "where o_custkey % 10 < 9 group by 1 order by 1")
    check(dist, local, sql)


def test_dist_order_by_no_limit(dist, local):
    # full ORDER BY without LIMIT: MERGE (range) exchange + per-worker sort —
    # worker-order concatenation must equal the global order (the engine's
    # distributed-sort answer to operator/MergeOperator.java). Secondary key
    # makes the expected order fully determined.
    check(dist, local,
          "select c_custkey, c_acctbal from customer "
          "order by c_acctbal, c_custkey")


@pytest.mark.slow
def test_dist_order_by_desc_varchar(dist, local):
    check(dist, local,
          "select c_name, c_custkey from customer "
          "order by c_name desc, c_custkey")


@pytest.mark.slow
def test_dist_order_by_multi_key(dist, local):
    check(dist, local,
          "select o_orderkey, o_orderdate, o_totalprice from orders "
          "order by o_orderdate desc, o_totalprice, o_orderkey")
