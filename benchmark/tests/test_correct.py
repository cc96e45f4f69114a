"""`correct` has been seen to fail: the control (the reference in float32, in
the program's place) on three seeds a query, and a whole run of the harness on
the CPU at schema `tiny` with an answer altered where the program produces it.
The sound run beside it comes out correct. The chip look is skipped here and
nowhere else (`need_chips=False`).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys
from decimal import Decimal

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import control  # noqa: E402
from benchmark.harness import cells, compare  # noqa: E402
from benchmark.rehearse import TINY  # noqa: E402
from benchmark.run import run_cell  # noqa: E402

CELLS = ["q1_sf1", "q6_sf1", "q3_sf1"]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 77])
def test_the_control_comes_out_not_correct(workload, seed):
    cell = cells.Cell(workload)
    # a tenth of SF1: large enough that float32 loses digits in every sum
    _params, ctl, sound = control.control_numbers(cell, seed, 0.1, 1e-9)
    assert compare.within(sound)
    assert not compare.within(ctl), ctl
    assert ctl["cells_unequal"]["value"] >= 1


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct_and_an_altered_answer_is_not(workload, monkeypatch):
    from presto_tpu.runner import LocalQueryRunner

    sound = run_cell(workload, 2**31 + 99, 0.5, False, need_chips=False,
                     scale=TINY)
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"] >= 1
    assert "rows_per_s" in sound["metrics"] and "setup_s" in sound["metrics"]

    execute = LocalQueryRunner.execute

    def altered(self, sql, *a, **kw):
        """The last decimal of the first row off by one unit in its last place:
        the answer is altered where it is produced, before the wire."""
        result = execute(self, sql, *a, **kw)
        row = list(result.rows[0])
        at = max(i for i, v in enumerate(row) if isinstance(v, Decimal))
        row[at] = row[at] + Decimal(1).scaleb(row[at].as_tuple().exponent)
        result.rows[0] = tuple(row)
        return result

    monkeypatch.setattr(LocalQueryRunner, "execute", altered)
    broken = run_cell(workload, 2**31 + 99, 0.5, False, need_chips=False,
                      scale=TINY)
    assert not broken["correct"]
    assert broken["failed"] == broken["attempted"] >= 1
    assert broken["compared"]["cells_unequal"]["value"] >= broken["attempted"]


@pytest.mark.parametrize("workload", CELLS)
def test_a_part_of_the_table_left_out_is_not_correct(workload, monkeypatch):
    """The scan drops its last split: the analogue of half a batch left out."""
    from presto_tpu.connectors.tpch import connector

    get_splits = connector.TpchSplitManager.get_splits

    def fewer(self, table, constraint, desired_splits):
        splits = get_splits(self, table, constraint, desired_splits)
        return splits[:-1] if len(splits) > 1 else splits

    monkeypatch.setattr(connector.TpchSplitManager, "get_splits", fewer)
    r = run_cell(workload, 2**31 + 99, 0.5, False, need_chips=False, scale=TINY)
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1


def test_a_cell_that_never_warms_is_not_measured(monkeypatch, capsys):
    """The last warm-up run still built a program: the window would be timed
    with compiles in it, so the run ends non-zero and prints no result."""
    from benchmark import run
    from benchmark.harness import served

    log = [{"query": "q6", "wall_s": 1.0, "built": 3},
           {"query": "q6", "wall_s": 0.9, "built": 1}]
    assert run.never_warmed(log) == ["q6"]
    assert run.never_warmed(log + [{"query": "q6", "wall_s": 0.1, "built": 0}]) == []
    assert run.never_warmed([]) == []
    monkeypatch.setattr(run, "warm_up", lambda served, plan, watch: log)

    def no_window(*a, **kw):
        raise AssertionError("the window was opened")

    monkeypatch.setattr(served, "run_window", no_window)
    with pytest.raises(SystemExit) as e:
        run.run_cell("q6_sf1", 5, 0.5, False, need_chips=False, scale=TINY)
    assert e.value.code not in (0, None) and "still built" in str(e.value.code)
    assert '"built": 1' in str(e.value.code)       # the log goes with it
    assert capsys.readouterr().out == ""


def test_a_query_that_raises_counts_as_failed(monkeypatch):
    from presto_tpu.client import dbapi

    fetchall = dbapi.Cursor.fetchall
    calls = {"n": 0}

    def flaky(self):
        calls["n"] += 1
        if calls["n"] == 4:      # after the warm-up's two, inside the window
            raise dbapi.Error("dropped on the way")
        return fetchall(self)

    monkeypatch.setattr(dbapi.Cursor, "fetchall", flaky)
    r = run_cell("q6_sf1", 5, 0.5, False, need_chips=False, scale=TINY)
    assert not r["correct"] and r["failed"] == 1
    assert r["compared"]["answers_wrong"]["value"] == 1


def test_an_open_loop_of_two_queries_and_two_clients_keeps_its_rate():
    """The generator's other branches, which no cell uses yet: a weighted mix,
    several clients, arrivals due at a fixed rate."""
    import presto_tpu  # noqa: F401
    from benchmark.harness import served as sv
    from benchmark.harness.traffic import Plan

    cell = cells.Cell("q6_sf1")
    mix = {"loop": "open", "clients": 2, "rate_per_s": 8.0, "queries": [
        dict(cell.traffic["queries"][0], weight=3),
        {"query": "q1", "weight": 1, "parameters": {
            "delta": {"type": "int", "lo": 60, "hi": 120}}}]}
    queries = {"q6": cells.Query("q6"), "q1": cells.Query("q1")}
    plan = Plan(mix, queries, 2**31 + 1)
    served = sv.Served(dict(cell.config, **TINY))
    try:
        win = sv.run_window(served, plan, 2.0)
    finally:
        served.stop()
    assert win["attempted"] == 16 and not win["errors"]
    assert sorted({q for q, _rows in win["answers"]}) == ["q1", "q6"]
    assert sum(q == "q1" for q, _rows in win["answers"]) == 4
    expected = {q: queries[q].reference(0.01, plan.params[q]) for q in queries}
    numbers, failed = compare.judge(win["answers"], expected, 1e-9)
    assert failed == 0 and compare.within(numbers)
    assert all(w > 0 for w in win["walls"]) and len(win["late_s"]) == 16
