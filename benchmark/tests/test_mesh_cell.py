"""The mesh cell `q3_sf1_mesh4` and its readers, on the CPU: the two
`"reader": "file"` metrics of the layer "mesh exchange" against hand-built
event lists, and whole runs of the harness at schema `tiny` on the virtual
devices conftest.py asks for, sound and with one worker's split dropped.
Beside them, which per-layer metrics each cell reads: the shared ones name no
cell, so `q1_sf10` and every later cell read them without an edit.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, collectives  # noqa: E402
from benchmark.rehearse import TINY  # noqa: E402
from benchmark.run import run_cell  # noqa: E402

CELL = "q3_sf1_mesh4"
MS = 1_000_000
EXCHANGE = {"exchange_chunks_per_query", "exchange_stall_s",
            "exchange_dispatch_s", "collective_s_per_query",
            "exchange_ici_roofline"}
SHARED = {"device_idle_pct", "window_compiles", "device_programs_per_query",
          "driver_idle_s", "peak_hbm_bytes", "scan_roofline",
          "idle_unattributed_pct", "protocol_overhead_s", "queue_wait_s",
          "result_wait_s", "plan_s"}

# what a v5e trace names its `XLA Ops` events: the HLO instruction's text
ALL_TO_ALL = ("%all_to_all.73 = u32[4,1,2048]{2,1,0:T(1,128)S(1)} all-to-all("
              "%bitcast.30), channel_id=1, replica_groups={{0,1,2,3}}, "
              "dimensions={0}")
GATHER_START = ("%all-gather-start.5 = (u32[4096]{0}, u32[16384]{0}) "
                "all-gather-start(%arrays_2_.2), channel_id=1")
GATHER_DONE = ("%all-gather-done.5 = u32[16384]{0:T(1024)S(1)} "
               "all-gather-done(%all-gather-start.5)")
NAMED_AFTER_ONE = ("%broadcast_and_fusion = pred[16384]{0} fusion("
                   "%all-gather.48, %eq.3), kind=kLoop")
SORT = "%sort.18 = (s64[8192]{0}, s32[8192]{0}) sort(%a, %b), dimensions={0}"


def test_collective_opcodes_are_told_from_names_that_only_mention_them():
    assert collectives.opcode(ALL_TO_ALL) == "all-to-all"
    assert collectives.opcode(GATHER_START) == "all-gather"
    assert collectives.opcode(GATHER_DONE) == "all-gather"
    assert collectives.opcode(NAMED_AFTER_ONE) is None
    assert collectives.opcode(SORT) is None
    assert collectives.opcode("%all-reduce.1 = f32[] all-reduce(%x)") == \
        "all-reduce"
    assert collectives.opcode(
        "%cp = u32[8]{0} collective-permute(%x), source_target_pairs={{0,1}}"
    ) == "collective-permute"
    # a CPU trace's stand-in events carry the instruction's name alone
    assert collectives.opcode("all-to-all") == "all-to-all"
    assert collectives.opcode("all-to-all.8") == "all-to-all"
    assert collectives.opcode("wrapped_broadcast") is None
    assert collectives.opcode("all-to-all_fusion.1") is None


def hand_built_trace():
    """Two queries of 100 ms; two planes; on each one all-to-all of 10 ms in
    the first query, an all-gather pair of 1 + 3 ms in the second, a fusion
    that only names a collective, a sort, and an all-to-all after the
    window."""
    plane = [(10 * MS, 20 * MS, ALL_TO_ALL), (30 * MS, 50 * MS, SORT),
             (120 * MS, 121 * MS, GATHER_START),
             (130 * MS, 140 * MS, NAMED_AFTER_ONE),
             (150 * MS, 153 * MS, GATHER_DONE),
             (500 * MS, 600 * MS, ALL_TO_ALL)]
    return {"spans": [(0, 100 * MS), (100 * MS, 200 * MS)],
            "devices": {"/device:TPU:0": list(plane),
                        "/device:TPU:1": list(plane)},
            "programs": []}


def test_collective_seconds_of_hand_built_events():
    got = collectives.seconds_per_query(hand_built_trace())
    # (10 + 1 + 3) ms a chip over 2 queries
    assert got["total"] == pytest.approx(0.007)
    assert got["all-to-all"] == pytest.approx(0.005)
    assert got["all-gather"] == pytest.approx(0.002)
    assert set(got) == {"total", "all-to-all", "all-gather"}
    # a plane that sat out counts in the mean over the chips
    trace = hand_built_trace()
    trace["devices"]["/device:TPU:1"] = [(30 * MS, 50 * MS, SORT)]
    assert collectives.seconds_per_query(trace)["total"] == \
        pytest.approx(0.0035)
    # an op that straddles the window's end counts as far as the window goes
    trace = hand_built_trace()
    trace["devices"] = {"/device:TPU:0": [(195 * MS, 215 * MS, ALL_TO_ALL)]}
    assert collectives.seconds_per_query(trace)["total"] == \
        pytest.approx(0.0025)
    # nothing to read is None, never 0
    trace["devices"] = {"/device:TPU:0": [(30 * MS, 50 * MS, SORT)]}
    assert collectives.seconds_per_query(trace) is None
    assert collectives.seconds_per_query(
        {"spans": [], "devices": {"d": []}}) is None
    assert collectives.seconds_per_query(
        {"spans": [(0, 10)], "devices": {}}) is None


def test_ici_roofline_share_is_least_wire_time_over_collective_time():
    # 8 GB cross in a query on 4 chips: a chip holds 2 GB and sends 3/4 of
    # it, 1.5 GB at 2e11 B/s = 7.5 ms; the collectives took 100 ms
    assert collectives.ici_roofline_pct(8e9, 4, 2.0e11, 0.1) == \
        pytest.approx(7.5)
    # over 100 is reported as it reads: a fault of the count, not clipped
    assert collectives.ici_roofline_pct(8e9, 4, 2.0e11, 0.005) == \
        pytest.approx(150.0)
    assert collectives.ici_roofline_pct(0, 4, 2.0e11, 0.1) is None
    assert collectives.ici_roofline_pct(8e9, 4, 2.0e11, None) is None
    assert collectives.ici_roofline_pct(8e9, 1, 2.0e11, 0.1) is None


def _reader(name):
    return cells.load_module(
        os.path.join(cells.BENCH_DIR, "layer_metrics", name + ".py"),
        "test_metric_" + name).read


def _spec(name):
    return cells.load_json(cells.BENCH_DIR, "layer_metrics", name + ".json")


def test_file_readers_read_the_windows_trace_and_nothing_else(monkeypatch):
    window = {"trace": None, "completed": 2,
              "before": {"counters": {"exchange.live_bytes": 1e9}},
              "after": {"counters": {"exchange.live_bytes": 17e9}}}
    for name in ("collective_s_per_query", "exchange_ici_roofline"):
        assert _reader(name)(_spec(name), window) is None   # untraced run
    window["trace"] = {"queries": 2}
    monkeypatch.setattr(collectives, "of_window",
                        lambda w: {"total": 0.1, "all-to-all": 0.1})
    assert _reader("collective_s_per_query")(
        _spec("collective_s_per_query"), window) == 0.1
    spec = _spec("exchange_ici_roofline")
    assert spec["ici_bytes_per_s"] == 2.0e11 and "1,600 Gbit/s" in spec["source"]
    # a CPU has no ICI: the share is left out of the line there
    assert _reader("exchange_ici_roofline")(spec, window) is None
    import jax

    class Chip:
        device_kind = spec["device_kind"]

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()] * 4)
    monkeypatch.setattr(jax, "device_count", lambda *a: 4)
    # 8e9 bytes a query, as above
    assert _reader("exchange_ici_roofline")(spec, window) == \
        pytest.approx(7.5)
    monkeypatch.setattr(collectives, "of_window", lambda w: None)
    assert _reader("exchange_ici_roofline")(spec, window) is None
    assert _reader("collective_s_per_query")(
        _spec("collective_s_per_query"), window) is None


def test_the_mesh_cell_resolves_to_the_distributed_runner_on_four_chips():
    cell = cells.Cell(CELL)
    assert cell.chips == 4 and cell.config["runner"] == "distributed"
    assert cell.config["chips"] == 4
    # the schema is named in presto-tpch's sf<number> form, which the commit
    # before the cell does not read (it refuses the cell at once): it has to
    # be the scale the reference computes at
    from presto_tpu.connectors.tpch.connector import SCHEMAS, schema_scale_factor
    assert cell.config["schema"] not in SCHEMAS
    assert schema_scale_factor(cell.config["schema"]) == \
        cell.config["scale_factor"] == cells.Cell("q3_sf1").config["scale_factor"]
    assert cell.traffic == cells.Cell("q3_sf1").traffic
    assert cell.config["guarantees"] == cells.Cell("q3_sf1").config["guarantees"]
    assert sorted(m["name"] for m in cell.metrics("end_to_end")) == \
        ["rows_per_s", "setup_s"]
    assert {m["name"] for m in cell.metrics("per_layer")} == EXCHANGE | SHARED | {
        "segment_dispatches_per_query", "dense_join_builds_per_query"}
    # not `coalesce_packed_pages_per_query`: a PARTITIONED join's children are
    # exchange sources, so no CoalesceOperator is planned and nothing counts


def test_the_shared_metrics_name_no_cell_so_a_later_cell_edits_no_entry():
    """A per-layer entry without a `workloads` key is every cell's
    (`cells.Cell.metrics`), those of later PRs too: a new cell brings its own
    `workloads` entry and touches none that is there."""
    bench = cells.load_json(cells.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in SHARED:
        assert "workloads" not in by_name[name], name
    known = {w["name"] for w in bench["workloads"]}
    for name in set(by_name) - SHARED:     # the others say where they read
        assert set(by_name[name]["workloads"]) <= known and \
            by_name[name]["workloads"], name
    for w in known:
        assert SHARED <= {m["name"] for m in
                          cells.Cell(w).metrics("per_layer")}, w


def test_q1_sf10_is_q1_sf1s_traffic_under_the_sf10_deployment():
    cell, pair = cells.Cell("q1_sf10"), cells.Cell("q1_sf1")
    assert cell.chips == 1 and cell.config["name"] == "tpch-sf10-1chip"
    assert cell.config["scale_factor"] == 10.0
    assert cell.traffic == pair.traffic and set(cell.queries) == {"q1"}
    # the deployment's file names the part of the source that defines the cell
    assert "2.4.1.3" in cell.config["source"]
    assert sorted(m["name"] for m in cell.metrics("end_to_end")) == \
        ["rows_per_s", "setup_s"]
    assert {m["name"] for m in cell.metrics("per_layer")} == \
        SHARED | {"dense_reduce_pages_per_query"} == \
        {m["name"] for m in pair.metrics("per_layer")}


def test_the_scan_roofline_of_four_chips_stands_on_four_chips_peak():
    from benchmark import run
    from benchmark.harness import readers

    one = run.least_seconds(819e9, "TPU v5 lite", 1)
    assert one == pytest.approx(1.0)
    assert run.least_seconds(819e9, "TPU v5 lite", 4) == pytest.approx(one / 4)
    with pytest.raises(KeyError):
        run.least_seconds(1e9, "cpu", 4)
    # busy_s is a chip's mean: four chips that each read their quarter at the
    # peak are at 100%, not at 400
    window = {"trace": {"busy_s": one / 4}, "least_s": run.least_seconds(
        819e9, "TPU v5 lite", 4)}
    assert readers.scan_roofline({}, window) == pytest.approx(100.0)


def test_a_sound_mesh_run_is_correct_and_prints_the_exchange_metrics():
    import jax

    assert jax.device_count() >= 4
    r = run_cell(CELL, 2**31 + 99, 0.5, True, need_chips=False, scale=TINY)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert r["device"]["count"] == jax.device_count()
    m = {k: v["value"] for k, v in r["metrics"].items()}
    # six exchanges, each at least its flush chunk
    assert m["exchange_chunks_per_query"] >= 6
    assert m["exchange_stall_s"] > 0 and m["exchange_dispatch_s"] > 0
    assert m["collective_s_per_query"] > 0     # CPU stand-in events: a path
    assert "exchange_ici_roofline" not in m    # no ICI on a CPU
    # the shared readings and the operators' counters, since PR 33; a CPU has
    # no peak to stand on, no `XLA Modules` line and no memory_stats
    on_chip_only = {"scan_roofline", "device_programs_per_query",
                    "peak_hbm_bytes"}
    assert SHARED - on_chip_only <= set(m) and not on_chip_only & set(m)
    assert m["window_compiles"] == 0
    assert m["dense_join_builds_per_query"] == 8     # 2 joins x 4 workers
    assert m["segment_dispatches_per_query"] >= 4
    assert m["driver_idle_s"] > 0 and m["plan_s"] > 0
    assert any(collectives.opcode(name)
               for name, _s in r["breakdown"]["device_ops"])
    e2e = run_cell(CELL, 2**31 + 99, 0.5, False, need_chips=False, scale=TINY)
    assert e2e["correct"] and set(e2e["metrics"]) == {"rows_per_s", "setup_s"}
    # the warm-up reached a query that built nothing, and so did the window
    assert e2e["run"]["warm_up"][-1]["built"] == 0


def test_one_workers_split_dropped_is_not_correct(monkeypatch):
    from presto_tpu.connectors.tpch import connector

    get_splits = connector.TpchSplitManager.get_splits

    def fewer(self, table, constraint, desired_splits):
        splits = get_splits(self, table, constraint, desired_splits)
        return splits[:-1] if len(splits) > 1 else splits

    monkeypatch.setattr(connector.TpchSplitManager, "get_splits", fewer)
    r = run_cell(CELL, 2**31 + 99, 0.5, False, need_chips=False, scale=TINY)
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1
    assert r["compared"]["cells_unequal"]["value"] >= 1
