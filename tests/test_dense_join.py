"""A unique single-key integer join build picks its lookup structure from the
keys it sees (ops/hash_join.py, `JoinBuildOperator._dense_plan`): a
direct-address table over [min, min + pow2 bucket of the range) while the
table stays within DENSE_JOIN_MAX_TABLE_BYTES, the sorted form otherwise.

The sorted form is the reference (the constant patched to 0 refuses every
table), beside a plain dictionary join written here. SQL level: Q3 at SF0.1
is row-identical both ways, and the mesh runner's second Q3 builds nothing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.block import page_from_pylists
from presto_tpu.metadata import Session
from presto_tpu.models.tpch_sql import QUERIES
from presto_tpu.ops import hash_join
from presto_tpu.ops.hash_join import (FULL, INNER, LEFT,
                                      JoinBuildOperatorFactory,
                                      LookupJoinOperatorFactory)
from presto_tpu.runner import LocalQueryRunner
from presto_tpu.types import BIGINT, BOOLEAN, DOUBLE, INTEGER
from presto_tpu.utils import kernel_cache
from presto_tpu.utils.metrics import METRICS

BASE = 10 ** 12          # a smallest key far beyond 32 bits
WRAP = 1 << 32


def _drain(op):
    rows = []
    while True:
        page = op.get_output()
        if page is None:
            return rows
        rows.extend(page.to_pylists())


def _join(build_rows, probe_keys, join_type=INNER, key_type=BIGINT,
          capacity=16, **build_options):
    """(key, value) build rows x probe keys through the two operators.
    -> (the published lookup source, output rows [probe key, build value])"""
    build_options.setdefault("unique", True)
    bf = JoinBuildOperatorFactory(0, [0], [1], [(BIGINT, None)],
                                  **build_options)
    pf = LookupJoinOperatorFactory(1, bf.lookup_factory, [0], [0],
                                   [(key_type, None)], [0], [(BIGINT, None)],
                                   join_type,
                                   unique_build=build_options["unique"])
    build = bf.create_operator()
    build.add_input(page_from_pylists([key_type, BIGINT], build_rows,
                                      capacity=capacity))
    build.finish()
    probe = pf.create_operator()
    probe.add_input(page_from_pylists([key_type], [[k] for k in probe_keys],
                                      capacity=capacity))
    rows = _drain(probe)
    probe.finish()
    return bf.lookup_factory.get(), rows + _drain(probe)


def _dictionary_join(build_rows, probe_keys, join_type):
    table = {k: v for k, v in build_rows if k is not None}
    out = []
    for k in probe_keys:
        if k is not None and k in table:
            out.append([k, table[k]])
        elif join_type == LEFT:
            out.append([k, None])
    return out


CASES = {
    "plain": ([[BASE + 1, 10], [BASE + 3, 30], [BASE + 7, 70]],
              [BASE + 3, BASE + 2, BASE + 7, BASE + 7, BASE + 1]),
    "null_probe_keys": ([[5, 50], [6, 60]], [None, 5, None, 6]),
    "null_build_keys": ([[5, 50], [None, 99], [9, 90]], [5, 9, 0, None]),
    "negative_keys": ([[-7, 1], [-3, 2], [4, 3]], [-7, -8, -3, 0, 4, 5]),
    "probe_outside_the_range": ([[100, 1], [131, 2]],
                                [99, 100, 131, 132, -1, 10 ** 15]),
    # 64-bit exactness of the address: base + 2^32 + 5 is not slot 5
    "beyond_plus_2_32": ([[BASE, 1], [BASE + 5, 2], [BASE + 9, 3]],
                         [BASE + WRAP + 5, BASE + WRAP, BASE + 5,
                          BASE + 3 * WRAP + 9]),
    "beyond_minus_2_32": ([[BASE, 1], [BASE + 5, 2], [BASE + 9, 3]],
                          [BASE - WRAP + 5, BASE - WRAP, BASE,
                           BASE - 2 * WRAP + 9]),
    "int64_extremes": ([[-2, 1], [3, 2]],
                       [np.iinfo(np.int64).min, np.iinfo(np.int64).max, 3,
                        np.iinfo(np.int64).min + 5]),
    "one_row_build": ([[42, 420]], [41, 42, 43]),
    "no_live_build_row": ([[None, 1], [None, 2]], [0, 1, None]),
}


@pytest.mark.parametrize("join_type", [INNER, LEFT])
@pytest.mark.parametrize("case", list(CASES))
def test_dense_rows_equal_the_sorted_form(case, join_type, monkeypatch):
    build_rows, probe_keys = CASES[case]
    source, dense = _join(build_rows, probe_keys, join_type)
    assert source.kind == "dense" and source.sorted_key is None
    assert source.table.shape[0] & (source.table.shape[0] - 1) == 0
    monkeypatch.setattr(hash_join, "DENSE_JOIN_MAX_TABLE_BYTES", 0)
    source, want = _join(build_rows, probe_keys, join_type)
    assert source.kind == "sorted" and source.table is None
    assert dense == want == _dictionary_join(build_rows, probe_keys, join_type)


@pytest.mark.parametrize("key_type,build_rows,probe_keys", [
    (INTEGER, [[-5, 1], [2 ** 31 - 1, 2]], [2 ** 31 - 1, -5, 0, -2 ** 31]),
    (BOOLEAN, [[True, 1], [False, 0]], [False, True, None, True]),
], ids=["integer", "boolean"])
def test_narrow_integer_keys_take_the_table(key_type, build_rows, probe_keys):
    if key_type is INTEGER:
        # the whole int32 range: a 2^32-slot table is refused by its bytes
        assert _join(build_rows, probe_keys, key_type=key_type)[0].kind == \
            "sorted"
        build_rows = [[-5, 1], [70_000, 2]]
        probe_keys = [70_000, -5, 0, -2 ** 31]
    source, got = _join(build_rows, probe_keys, key_type=key_type)
    assert source.kind == "dense"
    assert got == _dictionary_join(build_rows, probe_keys, INNER)


def test_the_choice_is_the_tables_bytes(monkeypatch):
    """A range of 2^k + 1 keys takes the 2^(k+1) bucket: 4 bytes a slot
    against the bound, no row count and nothing else."""
    monkeypatch.setattr(hash_join, "DENSE_JOIN_MAX_TABLE_BYTES", 4 * 64)
    assert _join([[1, 1], [64, 2]], [64])[0].table.shape == (64,)
    assert _join([[1001, 1], [1064, 2]], [1064])[0].table.shape == (64,)
    source, rows = _join([[1, 1], [65, 2]], [65])  # 65 keys: 128 slots
    assert source.kind == "sorted" and rows == [[65, 2]]
    monkeypatch.undo()
    # SF10's orderkeys on one chip are admitted, SF100's refused
    assert 4 << 26 <= hash_join.DENSE_JOIN_MAX_TABLE_BYTES < 4 << 30


KEEPS_SORTED = {
    "non_unique": dict(unique=False),
    "full": dict(unique=True, track_unmatched=True),
}


@pytest.mark.parametrize("why", list(KEEPS_SORTED))
def test_builds_the_table_cannot_serve_keep_the_sorted_form(why):
    options = KEEPS_SORTED[why]
    join_type = FULL if options.get("track_unmatched") else INNER
    build_rows = [[3, 30], [4, 40], [4, 41]] if why == "non_unique" \
        else [[3, 30], [4, 40], [6, 60]]
    source, rows = _join(build_rows, [4, 5, 3], join_type, **options)
    assert source.kind == "sorted"
    want = {"non_unique": [[4, 40], [4, 41], [3, 30]],
            "full": [[4, 40], [5, None], [3, 30], [None, 60]]}[why]
    assert sorted(rows, key=repr) == sorted(want, key=repr)


def test_float_key_keeps_the_sorted_form():
    bf = JoinBuildOperatorFactory(0, [0], [1], [(BIGINT, None)], unique=True)
    build = bf.create_operator()
    build.add_input(page_from_pylists([DOUBLE, BIGINT],
                                      [[1.2, 12], [1.5, 15], [3.0, 30]]))
    build.finish()
    assert bf.lookup_factory.get().kind == "sorted"


def test_multi_key_keeps_the_sorted_form():
    bf = JoinBuildOperatorFactory(0, [0, 1], [2], [(BIGINT, None)],
                                  unique=True)
    build = bf.create_operator()
    build.add_input(page_from_pylists([BIGINT, BIGINT, BIGINT],
                                      [[1, 1, 11], [1, 2, 12], [2, 1, 21]]))
    build.finish()
    source = bf.lookup_factory.get()
    assert source.kind == "sorted" and source.pack_offsets is not None


def _counters():
    counters = METRICS.raw_snapshot()["counters"]
    return {name: counters.get(name, 0)
            for name in ("join.builds", "join.builds.dense",
                         "kernel_cache.misses")}


def _gained(before):
    return {name: value - before[name] for name, value in _counters().items()}


def test_semi_join_keeps_the_sorted_form():
    """The planner declares no semi-join build unique."""
    runner = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    before = _counters()
    rows = runner.execute(
        "select count(*) from orders where o_custkey in "
        "(select c_custkey from customer where c_nationkey = 3)").rows
    assert rows[0][0] > 0
    gained = _gained(before)
    assert gained["join.builds"] == 1 and gained["join.builds.dense"] == 0


def test_the_tables_bytes_are_charged_to_the_build_operator():
    bf = JoinBuildOperatorFactory(0, [0], [1], [(BIGINT, None)], unique=True)
    build = bf.create_operator()
    build.add_input(page_from_pylists([BIGINT, BIGINT],
                                      [[1, 1], [1000, 2]], capacity=8))
    build.finish()
    table = bf.lookup_factory.get().table
    assert table.shape == (1024,)
    assert build.context.user_memory.get_bytes() == table.nbytes == 4096
    assert build.context.stats.peak_memory_bytes >= 4096
    build.close()
    assert build.context.memory.total_bytes() == 0


def _probe_stage_hlo(source):
    pf = LookupJoinOperatorFactory(1, None, [0], [0], [(BIGINT, None)], [0],
                                   [(BIGINT, None)], INNER, unique_build=True)
    cfg = hash_join.probe_stage_cfg(pf, source)
    page = page_from_pylists([BIGINT], [[k] for k in range(8)])
    return jax.jit(hash_join.apply_probe_stage, static_argnames=("cfg",)) \
        .lower(page, hash_join.probe_stage_aux(source), cfg=cfg).as_text()


def test_a_dense_probe_stage_has_no_loop(monkeypatch):
    build_rows = [[k, k] for k in range(3, 11)]
    source, _ = _join(build_rows, [3])
    assert source.kind == "dense"
    hlo = _probe_stage_hlo(source)
    assert "while" not in hlo and "gather" in hlo
    monkeypatch.setattr(hash_join, "DENSE_JOIN_MAX_TABLE_BYTES", 0)
    assert "while" in _probe_stage_hlo(_join(build_rows, [3])[0])


def test_another_base_in_the_same_bucket_compiles_nothing():
    """`base` is traced and `domain` a pow2 bucket: a new substitution set
    whose smallest live key moved replays both build programs."""
    rows = [[1000 + 3 * i, i] for i in range(10)]       # range 28: 32 slots
    first, _ = _join(rows, [1003])
    sizes = (hash_join._fused_build_dense._cache_size(),
             hash_join._live_key_range._cache_size())
    before = _counters()
    moved = [[5017 + 2 * i, i] for i in range(10)]      # range 19: 32 slots
    second, got = _join(moved, [5019, 5020])
    assert (first.base, second.base) == (1000, 5017)
    assert first.table.shape == second.table.shape == (32,)
    assert got == [[5019, 1]]
    assert (hash_join._fused_build_dense._cache_size(),
            hash_join._live_key_range._cache_size()) == sizes
    assert _gained(before)["kernel_cache.misses"] == 0


def test_join_build_counters_are_served():
    import json
    import urllib.request

    from presto_tpu.server.http_server import PrestoTpuServer

    before = _counters()
    _join([[1, 1], [2, 2]], [1])
    _join([[1, 1], [2, 2]], [1], unique=False)
    server = PrestoTpuServer(port=0)
    server.start()
    try:
        served = json.loads(urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/metrics/join.builds",
            headers={"X-Presto-User": "test"}), timeout=10).read())
    finally:
        server.stop()
    assert served["join.builds"] == before["join.builds"] + 2
    assert served["join.builds.dense"] == before["join.builds.dense"] + 1


# ------------------------------------------------------------------ SQL level

def _q3_at_sf01():
    kernel_cache.clear()  # the constant is read at build time, the probe's
    runner = LocalQueryRunner(       # kind is part of every segment's key
        session=Session(catalog="tpch", schema="sf0.1"))
    before = _counters()
    rows = runner.execute(QUERIES[3]).rows
    return rows, _gained(before)


def test_q3_rows_equal_the_sorted_form(monkeypatch):
    dense, gained = _q3_at_sf01()
    assert gained["join.builds"] == gained["join.builds.dense"] == 2
    monkeypatch.setattr(hash_join, "DENSE_JOIN_MAX_TABLE_BYTES", 0)
    try:
        want, gained = _q3_at_sf01()
    finally:
        kernel_cache.clear()
    assert gained["join.builds"] == 2 and gained["join.builds.dense"] == 0
    assert len(dense) == 10 and dense == want


def test_mesh_q3_second_query_builds_nothing(eight_devices):
    """What the four-chip cell's warm-up relies on: every worker builds both
    tables (the same 2^k bucket whatever rows its partition holds), and the
    second run of a query compiles no program for them."""
    from presto_tpu.parallel.mesh import MeshContext
    from presto_tpu.parallel.runner import DistributedQueryRunner

    runner = DistributedQueryRunner(
        MeshContext(eight_devices, n_workers=4),
        session=Session(catalog="tpch", schema="tiny"))
    first = runner.execute(QUERIES[3]).rows
    sizes = (hash_join._fused_build_dense._cache_size(),
             hash_join._live_key_range._cache_size())
    before = _counters()
    assert runner.execute(QUERIES[3]).rows == first and len(first) == 10
    gained = _gained(before)
    assert gained["join.builds"] == gained["join.builds.dense"] == 8
    assert gained["kernel_cache.misses"] == 0
    assert (hash_join._fused_build_dense._cache_size(),
            hash_join._live_key_range._cache_size()) == sizes
