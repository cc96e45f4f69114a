"""The yardstick's own parts, on the CPU: the copied column formulas against
the program's generator, the comparison, the traffic generator, the trace
reduction on hand-built intervals and on the recorded fixture.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import datetime
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, compare, tpch_data, trace_reduce  # noqa: E402
from benchmark.harness.traffic import Plan  # noqa: E402


def test_copied_columns_equal_the_programs_generator_at_tiny():
    from presto_tpu.connectors.tpch import generator as g

    sf = 0.01
    n = g.table_row_count("orders", sf)
    mine = tpch_data.lineitem(0, n, sf, [
        "l_orderkey", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate"])
    theirs = g.lineitem_for_orders(0, n, sf, list(mine))
    for c in mine:
        assert (mine[c] == theirs[c].astype(np.int64)).all(), c
    for table, gen, cols in (
            ("orders", tpch_data.orders,
             ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]),
            ("customer", tpch_data.customer, ["c_custkey", "c_mktsegment"])):
        rows = g.table_row_count(table, sf)
        mine, theirs = gen(0, rows, sf, cols), g.generate_rows(
            table, 0, rows, sf, cols)
        for c in cols:
            assert (mine[c] == theirs[c]).all(), c
    for table in ("lineitem", "orders", "customer"):
        for s in (0.01, 1.0):
            assert tpch_data.row_count(table, s) == g.table_row_count(table, s)
    assert tpch_data.SEGMENTS == list(g.DICT_SEGMENT.lookup(np.arange(5)))
    assert tpch_data.RETURNFLAGS == list(g.DICT_RETURNFLAG.lookup(np.arange(3)))


def test_compare_counts_cells_and_gaps():
    want = [("A", "12.5", 3, 1.0, datetime.date(1995, 3, 1))]
    assert compare.compare_rows(list(want), want) == (0, 0.0)
    got = [("A", "12.50001", 3, 1.0 + 1e-12, datetime.date(1995, 3, 1))]
    wrong, gap = compare.compare_rows(got, want)
    assert wrong == 1 and 0.5e-12 < gap < 2e-12
    assert compare.compare_rows([], want)[0] >= 1
    assert compare.compare_rows([("A", "12.5", 3.0, 1.0, None)], want)[0] == 2
    numbers, failed = compare.judge(
        [("q", list(want)), ("q", got), ("q", None)], {"q": want}, 1e-9)
    assert failed == 2 and not compare.within(numbers)
    numbers, failed = compare.judge([("q", list(want))], {"q": want}, 1e-9)
    assert failed == 0 and compare.within(numbers)
    assert not compare.within(compare.judge([], {"q": want}, 1e-9)[0])


def test_every_seed_sends_the_same_work_and_large_seeds_draw():
    cell = cells.Cell("q6_sf1")
    a = Plan(cell.traffic, cell.queries, 2**31 + 7)
    b = Plan(cell.traffic, cell.queries, 2**31 + 7)
    assert a.params == b.params and a.sql == b.sql
    seen = {json.dumps(Plan(cell.traffic, cell.queries, s).params)
            for s in range(40)}
    assert len(seen) > 10
    p = a.params["q6"]
    assert 1993 <= p["year"] <= 1997 and p["quantity"] in (24, 25)
    assert p["discount"] in {f"0.0{d}" for d in range(2, 10)}
    mix = {"loop": "open", "clients": 2, "rate_per_s": 5.0, "queries": [
        {"query": "q6", "weight": 3, "parameters": {}},
        {"query": "q1", "weight": 1, "parameters": {
            "delta": {"type": "int", "lo": 60, "hi": 120}}}]}
    both = {"q6": cells.Query("q6"), "q1": cells.Query("q1")}
    with pytest.raises(KeyError):
        Plan(mix, both, 1)      # q6's template needs its parameters
    mix["queries"][0]["parameters"] = cell.traffic["queries"][0]["parameters"]
    for seed in (1, 2, 3):
        plan = Plan(mix, both, seed)
        assert sorted(plan.block) == ["q1", "q6", "q6", "q6"]


def test_reduction_of_hand_built_intervals():
    ms = 1_000_000
    trace = {"spans": [(0, 100 * ms), (100 * ms, 200 * ms)],
             "devices": {"/device:TPU:0": [
                 (10 * ms, 30 * ms, "fusion.1"), (20 * ms, 25 * ms, "nested"),
                 (40 * ms, 50 * ms, "fusion.2"), (150 * ms, 160 * ms, "fusion.1"),
                 (500 * ms, 600 * ms, "after the window")]}}
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(0.2)
    assert r["busy_s"] == pytest.approx(0.040)      # nested op counted once
    assert r["queries"] == 2 and r["programs"] == 0
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.030)]
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["in_query.before_first_op"] == pytest.approx(0.010 + 0.050)
    assert gaps["in_query.between_ops"] == pytest.approx(0.010)
    assert gaps["in_query.after_last_op"] == pytest.approx(0.050 + 0.040)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    assert trace_reduce.reduce({"spans": [], "devices": {}}) is None
    assert trace_reduce.reduce({"spans": [(0, 10)], "devices": {"d": []}}) is None


def test_reduction_of_the_recorded_trace_gives_the_recorded_numbers():
    from benchmark.check_trace_reduction import differences

    assert differences() == []
