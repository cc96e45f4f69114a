"""The scalar segment reduce of ops/hash_agg.py picks its form from the
segment count: a masked reduction per segment up to
DENSE_REDUCE_MAX_SEGMENTS, the scatter (jax.ops.segment_*) above it.

The scatter is the reference: integers and MIN/MAX bit-equal, float SUM to
1e-12 relative (the association changes), the scatter's identities in empty
segments, out-of-range ids dropped. SQL level: Q1 and a GROUP BY over a
nullable flag column are row-identical with the constant patched to 0.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.metadata import Session
from presto_tpu.models.tpch_sql import QUERIES
from presto_tpu.ops import hash_agg
from presto_tpu.ops.aggregates import MAX, MIN, SUM
from presto_tpu.runner import LocalQueryRunner
from presto_tpu.utils import kernel_cache
from presto_tpu.utils.metrics import METRICS

LIMIT = hash_agg.DENSE_REDUCE_MAX_SEGMENTS
SCATTER = {SUM: jax.ops.segment_sum, MIN: jax.ops.segment_min,
           MAX: jax.ops.segment_max}
DTYPES = ("int64", "int32", "float64", "bool_as_int")


def _column(dtype: str, rows: int, rng):
    if dtype == "bool_as_int":  # the `seen` count, bool_or/bool_and states
        return jnp.asarray(rng.random(rows) < 0.5).astype(jnp.int32)
    raw = rng.integers(-10 ** 9, 10 ** 9, rows)
    if dtype == "float64":
        return jnp.asarray(raw / 1000.0, dtype=np.float64)
    return jnp.asarray(raw, dtype=np.dtype(dtype))


def _ids(segments: int, rows: int, rng):
    """Ids in [0, segments]: `segments` itself is out of range (dropped), and
    from three segments up segment 1 stays empty."""
    ids = rng.integers(0, segments + 1, rows)
    if segments >= 3:
        ids = np.where(ids == 1, 0, ids)
    return jnp.asarray(ids, dtype=jnp.int32)


def _assert_same(kind, got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.asarray(got), np.asarray(want)
    if kind == SUM and want.dtype.kind == "f":
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=0)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("segments", [1, 2, 13, LIMIT, LIMIT + 1])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kind", [SUM, MIN, MAX])
def test_matches_the_scatter(kind, dtype, segments):
    rng = np.random.default_rng(segments * 7 + len(dtype))
    values, ids = _column(dtype, 3001, rng), _ids(segments, 3001, rng)
    got = jax.jit(hash_agg._segment_reduce, static_argnums=(0, 3))(
        kind, values, ids, segments)
    want = SCATTER[kind](values, ids, num_segments=segments)
    _assert_same(kind, got, want)
    if segments >= 3:  # the empty segment holds the scatter's identity
        assert np.asarray(got)[1] == hash_agg._reduce_identity(
            kind, values.dtype)


@pytest.mark.parametrize("kind", [SUM, MIN, MAX])
def test_trash_segment_is_dropped(kind):
    """_reduce_all's contract: rows with gid == out_groups reach no state."""
    values = jnp.asarray([5, 7, 11, 13], dtype=np.int64)
    gid = jnp.asarray([0, 2, 2, 1], dtype=jnp.int32)  # out_groups = 2
    (state,) = hash_agg._reduce_all((values,), (kind,), (0,), (1,), gid, 2)
    assert state.tolist() == [5, 13]


@pytest.mark.parametrize("kind", [MIN, MAX])
def test_nan_in_a_min_max_column(kind):
    values = jnp.asarray([1.0, np.nan, 3.0, -2.0, 8.0], dtype=np.float64)
    ids = jnp.asarray([0, 0, 1, 1, 3], dtype=jnp.int32)
    got = hash_agg._segment_reduce(kind, values, ids, 4)
    _assert_same(kind, got, SCATTER[kind](values, ids, num_segments=4))
    assert np.isnan(np.asarray(got)[0])


@pytest.mark.parametrize("dtype", ["int64", "float64"])
@pytest.mark.parametrize("kind", [SUM, MIN, MAX])
def test_zero_row_page(kind, dtype):
    values = jnp.zeros(0, dtype=np.dtype(dtype))
    ids = jnp.zeros(0, dtype=jnp.int32)
    got = hash_agg._segment_reduce(kind, values, ids, 13)
    _assert_same(kind, got, SCATTER[kind](values, ids, num_segments=13))


def test_bool_states_keep_the_scatter():
    """arbitrary(boolean) holds a bool MAX state: no arithmetic identity."""
    values = jnp.asarray([True, False, False, True, False])
    ids = jnp.asarray([0, 0, 1, 3, 3], dtype=jnp.int32)
    got = hash_agg._segment_reduce(MAX, values, ids, 4)
    _assert_same(MAX, got, jax.ops.segment_max(values, ids, num_segments=4))


def test_wide_states_keep_the_scatter():
    """(rows, width) states being re-grouped are not scalar columns."""
    values = jnp.arange(12, dtype=np.int64).reshape(6, 2)
    ids = jnp.asarray([0, 1, 0, 2, 2, 2], dtype=jnp.int32)
    got = hash_agg._segment_reduce(SUM, values, ids, 3)
    _assert_same(SUM, got, jax.ops.segment_sum(values, ids, num_segments=3))


@pytest.mark.parametrize("kind", [SUM, MIN, MAX])
def test_the_choice_is_the_segment_count(kind):
    """LIMIT segments lower without a scatter, LIMIT + 1 with one."""
    def lowered(segments):
        fn = jax.jit(lambda v, i: hash_agg._segment_reduce(
            kind, v, i, segments))
        return fn.lower(jax.ShapeDtypeStruct((4096,), np.int64),
                        jax.ShapeDtypeStruct((4096,), np.int32)).as_text()

    assert "scatter" not in lowered(LIMIT)
    assert "scatter" in lowered(LIMIT + 1)


# ------------------------------------------------------------------ SQL level

NULLABLE_FLAG_SQL = (
    # NULL group present (every seventh line), 'N' in the dictionary but
    # filtered out: a group that never occurs
    "select f, s, count(*), sum(q), avg(p), min(q), max(p), count(q) from ("
    "select case when l_linenumber = 7 then null else l_returnflag end as f, "
    "l_linestatus as s, l_quantity as q, l_extendedprice as p "
    "from lineitem where l_returnflag <> 'N') group by f, s order by f, s")


def _run(sql):
    kernel_cache.clear()  # the constant is read at trace time
    runner = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    before = METRICS.counter_value("agg.direct.pages")
    rows = runner.execute(sql).rows
    assert METRICS.counter_value("agg.direct.pages") > before, \
        "not planned through DirectAggregationBuilder"
    return rows


@pytest.mark.parametrize("sql", [QUERIES[1], NULLABLE_FLAG_SQL],
                         ids=["q1", "nullable_flag"])
def test_sql_rows_equal_the_scatter_form(sql, monkeypatch):
    dense = _run(sql)
    monkeypatch.setattr(hash_agg, "DENSE_REDUCE_MAX_SEGMENTS", 0)
    try:
        scatter = _run(sql)
    finally:
        kernel_cache.clear()
    assert len(dense) == len(scatter) >= 3
    if sql is NULLABLE_FLAG_SQL:
        assert [r[0] for r in dense] == ["A", "R", None]
    for got, want in zip(dense, scatter):
        for a, b in zip(got, want):
            if isinstance(a, float):  # avg: a double, summed in another order
                assert a == pytest.approx(b, rel=1e-12)
            else:
                assert a == b


# -------------------------------------------------------------------- counters

def _direct_builder(domain: int):
    from presto_tpu.block import Block, Page
    from presto_tpu.ops.aggregates import AggregateCall, resolve_aggregate
    from presto_tpu.types import BIGINT

    call = AggregateCall(resolve_aggregate("sum", [BIGINT]), [1])
    builder = hash_agg.make_builder([BIGINT], [None], [domain], [call],
                                    page_capacity=64).set_channels([0])
    assert isinstance(builder, hash_agg.DirectAggregationBuilder)
    keys = jnp.arange(64, dtype=np.int64) % domain
    page = Page((Block(BIGINT, keys), Block(BIGINT, jnp.ones(64, np.int64))),
                jnp.ones(64, dtype=jnp.bool_))
    return builder, page


@pytest.mark.parametrize("domain,dense", [(LIMIT - 2, True), (LIMIT, False)],
                         ids=["small_domain", "over_the_constant"])
def test_direct_page_counters(domain, dense):
    """D + 1 counts the NULL slot and the trash segment: a domain of
    LIMIT - 2 is the widest that is still dense."""
    builder, page = _direct_builder(domain)
    pages = METRICS.counter_value("agg.direct.pages")
    dense_pages = METRICS.counter_value("agg.direct.dense_pages")
    for _ in range(3):
        builder.add_page(page)
    _keys, states, seen = builder.finish()
    assert int(np.asarray(states[0]).sum()) == 3 * 64 and bool(seen[0])
    assert METRICS.counter_value("agg.direct.pages") == pages + 3
    assert METRICS.counter_value("agg.direct.dense_pages") == \
        dense_pages + (3 if dense else 0)


def test_direct_page_counters_are_served():
    import json
    import urllib.request

    from presto_tpu.server.http_server import PrestoTpuServer

    _run(NULLABLE_FLAG_SQL)
    server = PrestoTpuServer(port=0)
    server.start()
    try:
        served = json.loads(urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/metrics/agg.direct",
            headers={"X-Presto-User": "test"}), timeout=10).read())
    finally:
        server.stop()
    assert served["agg.direct.pages"] >= 1
    assert served["agg.direct.dense_pages"] >= 1
