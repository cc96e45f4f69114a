"""The reader of the engine's spans (harness/engine_spans.py), on the CPU: the
partition on hand-built spans, and the whole reduction on a trace recorded on
the chip (fixtures/q6_sf1_engine_spans.xplane.pb: `run.py --workload q6_sf1
--seconds 0.3 --trace 1` on a v5e, PR 27).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_engine_spans.py -q

To replace the fixture: copy the new run's .xplane.pb over it, put what
`python3 benchmark/tools/engine_gaps.py <file> --json` prints under "report"
in fixtures/expected_engine_spans.json, and read both before committing.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, engine_spans, trace_reduce  # noqa: E402
from benchmark.tools import engine_gaps  # noqa: E402

FIXTURES = os.path.join(cells.BENCH_DIR, "fixtures")
METRICS = ("queue_wait_s", "result_wait_s", "plan_s", "driver_idle_s",
           "idle_unattributed_pct")


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(FIXTURES, "expected_engine_spans.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded(expected):
    return engine_spans.read(os.path.join(FIXTURES, expected["file"]))


def assert_close(got, want, where):
    if isinstance(want, dict):
        assert set(got) == set(want), where
        for key in want:
            assert_close(got[key], want[key], f"{where}.{key}")
    else:
        assert got == pytest.approx(want, rel=1e-9), where


def read_metric(name, window):
    spec = cells.load_json(cells.BENCH_DIR, "layer_metrics", name + ".json")
    assert spec["reader"] == "file"
    return cells.load_module(
        os.path.join(cells.BENCH_DIR, "layer_metrics", name + ".py"),
        "benchmark_metric_" + name).read(spec, window)


def test_the_labels_add_up_to_every_root_exactly(recorded):
    assert len(recorded["queries"]) >= 5
    for qid, query in recorded["queries"].items():
        pieces = engine_spans.partition(query)
        r0, r1 = query["root"]
        assert pieces[0][0] == r0 and pieces[-1][1] == r1, qid
        assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:])), qid
        assert sum(b - a for a, b, _l in pieces) == r1 - r0, qid
        assert {lab for _a, _b, lab in pieces} <= set(engine_spans.LABELS), qid
        # a served Q6 went through every stage
        assert {lab for _a, _b, lab in pieces} >= {
            "queued", "parse", "plan", "local_plan", "execute.driver",
            "result", "serialize", "result_wait"}, qid


def test_a_gap_over_two_overlapping_quanta_is_execute_driver_once():
    query = {"root": (0, 100),
             "phases": [(5, 10, "queued"), (10, 90, "execute")],
             "drivers": [(20, 60), (40, 80)],     # two threads, overlapping
             "http": [(0, 12), (95, 100)]}
    pieces = engine_spans.partition(query)
    assert pieces == [(0, 5, "http"), (5, 10, "queued"),
                      (10, 20, "execute.no_driver"),
                      (20, 80, "execute.driver"),
                      (80, 90, "execute.no_driver"),
                      (90, 95, "unattributed"), (95, 100, "http")]
    # one idle gap of the chip across both quanta: counted once
    assert engine_spans.overlay(pieces, [(30, 70)]) == {"execute.driver": 40}
    assert engine_spans.overlay(pieces, [(0, 100)]) == {
        "http": 10, "queued": 5, "execute.no_driver": 20,
        "execute.driver": 60, "unattributed": 5}
    assert engine_spans.idle_of([(10, 20), (30, 40)], 0, 50) == [
        (0, 10), (20, 30), (40, 50)]
    # the innermost phase wins where phases nest (a subquery run while planning)
    nested = {"root": (0, 10), "phases": [(0, 10, "plan"), (2, 4, "execute")],
              "drivers": [], "http": []}
    assert engine_spans.partition(nested) == [
        (0, 2, "plan"), (2, 4, "execute.no_driver"), (4, 10, "plan")]


def test_the_five_readers_return_the_fixtures_numbers(
        expected, recorded, monkeypatch):
    path = os.path.join(FIXTURES, expected["file"])
    assert os.path.getsize(path) < 1_000_000
    monkeypatch.setattr(engine_spans, "newest", lambda: path)
    window = dict(expected["window"], trace={"queries": 1})
    for name in METRICS:
        assert read_metric(name, window) == pytest.approx(
            expected["metrics"][name], rel=1e-9), name
    # an untraced run never reads a trace an older run left behind
    untraced = dict(window, trace=None)
    assert read_metric("driver_idle_s", untraced) is None
    assert read_metric("idle_unattributed_pct", untraced) is None
    # the whole table, as engine_gaps.py prints it
    got = engine_gaps.report(engine_spans.summarize(recorded))
    want = expected["report"]
    assert_close(got, want, "report")
    # what the chip run of PR 27 has to show, held on the recorded trace
    assert got["programs_in_execute_pct"] >= 99.0
    assert got["median_query_covered_pct"] >= 95.0
    assert got["idle_unattributed_pct"] < 15.0


def test_engine_labels_split_exactly_what_the_benchmarks_labels_hold(
        expected, recorded):
    """The cross table's columns are the benchmark's own gap labels: each
    adds up to what trace_reduce reports for it on the same file."""
    reduced = trace_reduce.reduce(trace_reduce.read(
        os.path.join(FIXTURES, expected["file"])))
    by_bench = engine_spans.summarize(recorded)["by_bench"]
    for label, seconds in reduced["idle_gaps"]:
        if label.startswith("in_query."):
            assert sum(by_bench[label].values()) / 1e9 == pytest.approx(
                seconds, rel=1e-9), label


def test_a_trace_without_engine_spans_reads_none_five_times(monkeypatch):
    old = os.path.join(FIXTURES, "q6_sf1_18_queries.xplane.pb")   # PR 26's
    trace = engine_spans.read(old)
    assert trace["queries"] == {} and trace["busy"] and trace["bench"]
    assert engine_spans.summarize(trace) is None
    monkeypatch.setattr(engine_spans, "newest", lambda: old)
    empty = {"counters": {}, "gauges": {},
             "histograms": {"query.wall_s": {"n": 3, "total": 0.06}}}
    window = {"before": empty, "after": empty, "trace": {"queries": 18},
              "walls": [0.05], "completed": 1}
    assert [read_metric(name, window) for name in METRICS] == [None] * 5
    # and no trace kept at all
    monkeypatch.setattr(engine_spans, "newest", lambda: None)
    assert read_metric("driver_idle_s", window) is None
