"""Fused pipeline segments (ops/fused_segment.py + the local planner's
segment compiler): differential tests against the unfused oracle, segment
boundary decisions, and observability plumbing.

The fused path (`segment_fusion = True`, the default) must be ROW-IDENTICAL
to the per-operator pipeline (`segment_fusion = False`) — the unfused path
is kept precisely to be this oracle.
"""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from presto_tpu.exec.local_planner import LocalExecutionPlanner  # noqa: E402
from presto_tpu.metadata import Session  # noqa: E402
from presto_tpu.models.tpch_sql import QUERIES  # noqa: E402
from presto_tpu.ops.fused_segment import (  # noqa: E402
    FusedSegmentOperatorFactory)
from presto_tpu.runner import LocalQueryRunner  # noqa: E402


def _runner(**props):
    return LocalQueryRunner(
        session=Session(catalog="tpch", schema="tiny", properties=props))


def _segments(runner, sql):
    """Plan `sql` and return every FusedSegmentOperatorFactory in it."""
    plan = runner.plan_sql(sql)
    local = LocalExecutionPlanner(runner.metadata, runner.session)
    exec_plan = local.plan(plan)
    return [f for chain in exec_plan.pipelines for f in chain
            if isinstance(f, FusedSegmentOperatorFactory)], exec_plan


# ------------------------------------------------------------- differential

@pytest.mark.parametrize("qid", [1, 3, 6])
def test_fused_equals_unfused_tpch(qid):
    fused = _runner().execute(QUERIES[qid])
    oracle = _runner(segment_fusion=False).execute(QUERIES[qid])
    assert fused.rows == oracle.rows
    assert fused.column_names == oracle.column_names


def test_fused_equals_unfused_topn_over_join():
    sql = ("select o_orderkey, c_name from orders, customer "
           "where o_custkey = c_custkey order by o_orderkey limit 5")
    fused = _runner().execute(sql)
    oracle = _runner(segment_fusion=False).execute(sql)
    assert fused.rows == oracle.rows


def test_fused_equals_unfused_semi_join():
    sql = ("select count(*) from orders where o_custkey in "
           "(select c_custkey from customer where c_acctbal > 0)")
    fused = _runner().execute(sql)
    oracle = _runner(segment_fusion=False).execute(sql)
    assert fused.rows == oracle.rows


def test_fused_equals_unfused_dict_encoded_group_keys():
    # group keys are dictionary-coded varchars (Q1's shape): the segment's
    # kernel key includes dictionary versions, so dict-encoded inputs must
    # never fuse wrong
    sql = ("select l_returnflag, l_linestatus, count(*) c, sum(l_quantity) q "
           "from lineitem group by l_returnflag, l_linestatus "
           "order by l_returnflag, l_linestatus")
    fused = _runner().execute(sql)
    oracle = _runner(segment_fusion=False).execute(sql)
    assert fused.rows == oracle.rows


# ------------------------------------------------------ boundary decisions

def test_q3_fuses_probe_chain_into_agg_terminal():
    segs, _plan = _segments(_runner(), QUERIES[3])
    assert len(segs) == 1
    names = segs[0].member_names
    # probe -> probe -> project -> partial-agg contribution, one dispatch
    assert any("LookupJoin" in n for n in names)
    assert "HashAggregation" in " ".join(names)
    # the blocking aggregation TERMINATES the segment: TopN stays outside
    assert not any("TopN" in n for n in names)


def test_topn_terminates_a_probe_segment():
    sql = ("select o_orderkey, c_name from orders, customer "
           "where o_custkey = c_custkey order by o_orderkey limit 5")
    segs, _plan = _segments(_runner(), sql)
    assert any("TopN" in segs_i.member_names[-1] for segs_i in segs)


def test_join_build_pipelines_never_fuse():
    segs, exec_plan = _segments(_runner(), QUERIES[3])
    for chain in exec_plan.pipelines:
        for i, f in enumerate(chain):
            if "JoinBuild" in getattr(f, "name", ""):
                # the build sink is a barrier: never inside a segment
                assert not isinstance(f, FusedSegmentOperatorFactory)


def test_full_join_probe_is_a_barrier():
    sql = ("select c_custkey, o_orderkey from customer "
           "full join orders on c_custkey = o_custkey")
    segs, exec_plan = _segments(_runner(), sql)
    for s in segs:
        assert not any("LookupJoin" in n for n in s.member_names)
    fused = _runner().execute(sql + " order by 1, 2 limit 50")
    oracle = _runner(segment_fusion=False).execute(sql + " order by 1, 2 limit 50")
    assert fused.rows == oracle.rows


def test_order_by_is_a_barrier():
    sql = "select l_orderkey from lineitem order by l_orderkey"
    segs, exec_plan = _segments(_runner(), sql)
    for s in segs:
        assert not any("OrderBy" in n for n in s.member_names)


def test_knob_off_plans_no_segments():
    segs, exec_plan = _segments(_runner(segment_fusion=False), QUERIES[3])
    assert segs == []
    assert exec_plan.segment_decisions == []


def test_single_operator_runs_stay_unfused():
    # Q6: the filter fuses into the scan, the aggregation stands alone —
    # a one-operator run must not be wrapped (nothing to merge)
    segs, exec_plan = _segments(_runner(), QUERIES[6])
    assert segs == []
    reasons = [d for d in exec_plan.segment_decisions if not d["fused"]]
    assert any(d["reason"] == "single-operator run" for d in reasons)


# ------------------------------------------- where a Coalesce is placed

# lineitem probes the filtered part (INNER, single unique key: it drops the
# lines of every other part), then one more table
_GREEN_LINES = ("select count(*), sum({value}) from lineitem, part, {table} "
                "where l_partkey = p_partkey and p_name like '%green%' "
                "and {on}")
_THEN_PARTSUPP = _GREEN_LINES.format(
    value="ps_supplycost", table="partsupp",
    on="ps_partkey = l_partkey and ps_suppkey = l_suppkey")
_THEN_ORDERS = _GREEN_LINES.format(
    value="o_totalprice", table="orders", on="o_orderkey = l_orderkey")


def _pipelines(runner, sql):
    _segs, exec_plan = _segments(runner, sql)
    return [[f.name for f in chain] for chain in exec_plan.pipelines]


@pytest.mark.parametrize("fusion", [True, False])
def test_a_join_that_feeds_a_two_column_probe_gets_a_coalesce(fusion):
    """The partsupp probe has two key columns: it cannot fuse and pays for
    every page at full capacity, so what the part join kept is packed."""
    *builds, probe = _pipelines(_runner(segment_fusion=fusion), _THEN_PARTSUPP)
    assert builds == [["TableScan", "Coalesce", "JoinBuild"],
                      ["TableScan", "JoinBuild"]]
    assert probe == ["TableScan", "LookupJoin(inner)", "Coalesce",
                     "LookupJoin(inner)", "HashAggregation(single)",
                     "PageConsumer"]


def test_a_join_that_feeds_a_fusible_probe_gets_none():
    """The orders probe has one unique key: a Coalesce would cut the fused
    segment in two, so the probe side gets none and stays one segment."""
    segs, exec_plan = _segments(_runner(), _THEN_ORDERS)
    assert [s.member_names for s in segs] == [
        ["LookupJoin(inner)", "LookupJoin(inner)", "HashAggregation(single)"]]
    assert [f.name for f in exec_plan.pipelines[-1]] == \
        ["TableScan", segs[0].name, "PageConsumer"]
    assert _pipelines(_runner(segment_fusion=False), _THEN_ORDERS)[-1] == \
        ["TableScan", "LookupJoin(inner)", "LookupJoin(inner)",
         "HashAggregation(single)", "PageConsumer"]
    # a green line has one partsupp row and one order: the packed plan and
    # the fused one count the same lines
    assert _runner().execute(_THEN_PARTSUPP).rows[0][0] == \
        _runner().execute(_THEN_ORDERS).rows[0][0] > 0


def test_q3_plan_is_what_it_was_before_joins_were_packed():
    """Both of Q3's probes are fusible: its only Coalesces are the ones
    behind the three filtered scans, and its probe pipeline is one segment."""
    assert _pipelines(_runner(), QUERIES[3]) == [
        ["TableScan", "Coalesce", "JoinBuild"],
        ["TableScan", "Coalesce", "JoinBuild"],
        ["TableScan", "Coalesce",
         "FusedSegment[LookupJoin(inner)+LookupJoin(inner)+FilterProject"
         "+HashAggregation(single)]",
         "TopN", "FilterProject", "PageConsumer"]]


# ------------------------------------------------------------ observability

def test_segment_stats_flow_into_query_result():
    res = _runner().execute(QUERIES[3])
    seg = (res.stats or {}).get("segments")
    assert seg is not None
    assert seg["count"] >= 1
    assert seg["dispatches"] > 0
    assert seg["segments"][0]["operators"]
    assert any(d.get("fused") for d in seg["decisions"])


def test_segment_metrics_counters():
    from presto_tpu.utils.metrics import METRICS

    before = METRICS.counter_value("segments.dispatches")
    _runner().execute(QUERIES[3])
    assert METRICS.counter_value("segments.dispatches") > before
