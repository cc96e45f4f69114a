"""The reader of the exchange pumps' state spans (harness/pump_spans.py), on
the CPU: the labels and the three shares on hand-built intervals reckoned by
hand, None on every trace without the spans, the eight entries the mesh cell
gained, and one traced run of the cell at schema `tiny` on four virtual
devices.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_pump_spans.py -q
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, engine_spans, pump_spans  # noqa: E402
from benchmark.tools import pump_gaps  # noqa: E402

CELL = "q3_sf1_mesh4"
FIXTURES = os.path.join(cells.BENCH_DIR, "fixtures")
COUNTERS = {"exchange_sync_s": ["exchange.sync_s"],
            "exchange_fill_s": ["exchange.fill_s", "exchange.deliver_s"],
            "exchange_lock_wait_s": ["exchange.lock_wait_s"],
            "exchange_backpressure_s": ["exchange.backpressure_s"],
            "exchange_queued_s": ["exchange.queued_s"]}
TRACED = ("idle_host_working_pct", "idle_all_waiting_pct",
          "all_chips_idle_pct")
MS = 1_000_000


def read_metric(name, window):
    spec = cells.load_json(cells.BENCH_DIR, "layer_metrics", name + ".json")
    assert spec["reader"] == "file"
    return cells.load_module(
        os.path.join(cells.BENCH_DIR, "layer_metrics", name + ".py"),
        "test_metric_" + name).read(spec, window)


def ms(*intervals):
    return [tuple(x * MS if isinstance(x, int) else x for x in iv)
            for iv in intervals]


def hand_built():
    """One query whose execute is 100 ms; one driver quantum; two pumps; two
    chips. Reckoned by hand in the test below."""
    query = {"execute": ms((0, 100)), "drivers": ms((0, 10)),
             "pumps": {0: ms((10, 20, "fill"), (20, 25, "dispatch"),
                             (25, 40, "sync"), (40, 45, "deliver"),
                             (50, 70, "starved")),
                       1: ms((10, 30, "starved"), (35, 55, "sync"),
                             (55, 60, "fill"), (60, 80, "backpressure"))}}
    return {"queries": {"q_hand": query},
            "busy": {"/device:TPU:0": ms((5, 15), (30, 50), (90, 100)),
                     "/device:TPU:1": ms((0, 20), (58, 62))}}


def test_the_labels_and_the_three_shares_of_hand_built_intervals():
    trace = hand_built()
    pieces = pump_spans.partition(trace["queries"]["q_hand"])
    # driver > pump_host (fill, dispatch, deliver) > pump_sync > waiting
    assert pieces == ms((0, 10, "driver"), (10, 25, "pump_host"),
                        (25, 40, "pump_sync"), (40, 45, "pump_host"),
                        (45, 55, "pump_sync"), (55, 60, "pump_host"),
                        (60, 100, "waiting"))
    got = pump_spans.summarize(trace)
    (q,) = got["queries"]
    assert q["execute_ns"] == 100 * MS
    assert q["wall"] == {"driver": 10 * MS, "pump_host": 25 * MS,
                         "pump_sync": 25 * MS, "waiting": 40 * MS}
    # chip 0 is idle over (0,5) (15,30) (50,90), chip 1 over (20,58) (62,100)
    assert q["idle"]["/device:TPU:0"] == {
        "driver": 5 * MS, "pump_host": 15 * MS, "pump_sync": 10 * MS,
        "waiting": 30 * MS}
    assert q["idle"]["/device:TPU:1"] == {
        "pump_host": 13 * MS, "pump_sync": 25 * MS, "waiting": 38 * MS}
    assert q["busy"]["/device:TPU:0"] == {
        "driver": 5 * MS, "pump_host": 10 * MS, "pump_sync": 15 * MS,
        "waiting": 10 * MS}
    # both at once over (20,30) (50,58) (62,90)
    assert q["all_idle"] == {"pump_host": 8 * MS, "pump_sync": 10 * MS,
                             "waiting": 28 * MS}
    assert got["idle"] == {"driver": 5 * MS, "pump_host": 28 * MS,
                           "pump_sync": 35 * MS, "waiting": 68 * MS}
    assert pump_spans.idle_host_working_pct(got) == \
        pytest.approx(100 * 33 / 136)
    assert pump_spans.idle_all_waiting_pct(got) == pytest.approx(50.0)
    assert pump_spans.idle_share(got, ("pump_sync",)) == \
        pytest.approx(100 * 35 / 136)
    assert pump_spans.all_chips_idle_pct(got) == pytest.approx(46.0)
    # a pump's `queued` is what lies between its spans
    assert q["states"][0] == dict(
        dict.fromkeys(pump_spans.STATES, 0), fill=10 * MS, dispatch=5 * MS,
        sync=15 * MS, deliver=5 * MS, starved=20 * MS, queued=5 * MS)
    assert q["states"][1] == dict(
        dict.fromkeys(pump_spans.STATES, 0), starved=20 * MS, sync=20 * MS,
        fill=5 * MS, backpressure=20 * MS, queued=5 * MS)
    # the table, as the tool prints it
    rep = pump_gaps.report(got)
    json.dumps(rep)
    assert rep["queries"][0]["state_s"]["f1"]["backpressure"] == \
        pytest.approx(0.020)
    assert rep["idle_host_working_pct"] + rep["idle_all_waiting_pct"] + \
        rep["idle_pump_sync_pct"] == pytest.approx(100.0)
    pump_gaps.show(rep["queries"][0], rep["planes"])


def test_chips_that_never_sat_idle_read_none_not_zero():
    trace = hand_built()
    trace["busy"] = {"/device:TPU:0": ms((0, 100))}
    got = pump_spans.summarize(trace)
    assert pump_spans.idle_host_working_pct(got) is None
    assert pump_spans.idle_all_waiting_pct(got) is None
    assert pump_spans.all_chips_idle_pct(got) == 0.0


def test_a_trace_without_the_pumps_states_reads_none(monkeypatch):
    # the parent of PR 38 has `pump_stall` and `chunk_dispatch` alone
    parent = hand_built()
    parent["queries"]["q_hand"]["pumps"] = {
        0: ms((20, 25, "dispatch"), (50, 70, "starved"))}
    assert pump_spans.summarize(parent) is None
    assert pump_spans.summarize({"queries": {}, "busy": parent["busy"]}) is None
    assert pump_spans.summarize(dict(hand_built(), busy={})) is None
    empty = {"counters": {}, "gauges": {}, "histograms": {}}
    window = {"before": empty, "after": empty, "trace": {"queries": 18},
              "walls": [0.05], "completed": 1}
    # recorded on the chip: PR 27's trace holds the engine's spans and no
    # exchange, PR 26's no engine span at all
    for name in ("q6_sf1_engine_spans.xplane.pb", "q6_sf1_18_queries.xplane.pb"):
        old = os.path.join(FIXTURES, name)
        assert pump_spans.read(old) == {}
        assert pump_spans.summarize(pump_spans.gather(old)) is None
        monkeypatch.setattr(engine_spans, "newest", lambda: old)
        assert [read_metric(m, window) for m in TRACED] == [None] * 3
    # an untraced run never reads a trace an older run left behind
    assert [read_metric(m, dict(window, trace=None)) for m in TRACED] == \
        [None] * 3
    # and no trace kept at all
    monkeypatch.setattr(engine_spans, "newest", lambda: None)
    assert [read_metric(m, window) for m in TRACED] == [None] * 3


def test_a_counter_that_is_not_there_counts_zero():
    """On the parent the five counter-backed metrics read 0.0, as
    `dense_join_builds_per_query` does on a tree before PR 30."""
    from benchmark.harness import readers

    window = {"completed": 3, "before": {"counters": {}},
              "after": {"counters": {"exchange.stall_s": 12.0}}}
    for name, counters in COUNTERS.items():
        spec = cells.load_json(cells.BENCH_DIR, "layer_metrics", name + ".json")
        assert spec["reader"] == "counter_per_query"
        assert spec["counters"] == counters
        assert readers.READERS[spec["reader"]](spec, window) == 0.0
    window["after"]["counters"].update(
        {"exchange.fill_s": 1.5, "exchange.deliver_s": 3.0})
    spec = cells.load_json(cells.BENCH_DIR, "layer_metrics",
                           "exchange_fill_s.json")
    assert readers.counter_per_query(spec, window) == pytest.approx(1.5)


def test_the_eight_new_entries_name_the_mesh_cell_and_no_shared_one_does():
    bench = cells.load_json(cells.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-8:]] == \
        list(COUNTERS) + list(TRACED)
    for name in list(COUNTERS) + list(TRACED):
        entry = by_name[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "rows_per_s"
        assert entry["better"] == "lower"
        assert (entry["unit"], entry["source"], entry["layer"]) == (
            ("s", "program_counter", "mesh exchange") if name in COUNTERS
            else ("%", "device_trace", "device")), name
    # the rule test_mesh_cell.py holds: an entry without `workloads` is every
    # cell's, and no other cell gained a metric
    every = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    for w in bench["workloads"]:
        names = {m["name"] for m in cells.Cell(w["name"]).metrics("per_layer")}
        assert every <= names
        assert bool(names & (set(COUNTERS) | set(TRACED))) == \
            (w["name"] == CELL), w["name"]


def test_a_traced_mesh_run_prints_the_eight_and_they_are_consistent():
    """CPU, four virtual devices, schema `tiny`: the paths, no device number
    (a CPU trace's stand-in events are one plane)."""
    import jax

    from benchmark.rehearse import TINY
    from benchmark.run import run_cell

    assert jax.device_count() >= 4
    r = run_cell(CELL, 2**31 + 38, 0.5, True, need_chips=False, scale=TINY)
    assert r["correct"]
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(COUNTERS) | set(TRACED) <= set(m)
    assert m["exchange_sync_s"] > 0 and m["exchange_fill_s"] > 0
    assert 0 <= m["exchange_lock_wait_s"] <= m["exchange_dispatch_s"]
    assert m["exchange_backpressure_s"] >= 0 and m["exchange_queued_s"] > 0
    assert 0 <= m["idle_host_working_pct"] <= 100
    assert 0 <= m["idle_all_waiting_pct"] <= 100
    assert m["idle_host_working_pct"] + m["idle_all_waiting_pct"] <= 100 + 1e-9
    assert 0 <= m["all_chips_idle_pct"] <= 100
    # the kept trace, by the tool: every exchange's spans, one state a moment
    summary = pump_spans.summarize(pump_spans.gather(engine_spans.newest()))
    for q in summary["queries"]:
        assert sorted(q["states"]) == list(range(6))
        assert sum(q["wall"].values()) == q["execute_ns"]
    for qid, q in pump_spans.gather(engine_spans.newest())["queries"].items():
        for fragment, spans in q["pumps"].items():
            for a, b in zip(spans, spans[1:]):
                assert a[1] <= b[0], (qid, fragment, a, b)
    e2e = run_cell(CELL, 2**31 + 38, 0.5, False, need_chips=False, scale=TINY)
    assert e2e["correct"] and set(e2e["metrics"]) == {"rows_per_s", "setup_s"}
