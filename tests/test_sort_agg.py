"""The sort aggregation builder (ops/hash_agg.GroupedAggregationBuilder),
page level, against a plain Python dict group-by written here.

Key distributions: a few groups (shrunken partials), one group, groups ~
rows (the defer-raw path), NULL keys, two keys, float keys. Then the
kernel's sorted-segment reduction against jax.ops.segment_*, what it lowers
to, and the counters of its column forms.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from presto_tpu.block import Block, Page, page_from_arrays
from presto_tpu.ops import hash_agg
from presto_tpu.ops.aggregates import (MAX, MIN, SUM, AggregateCall,
                                       resolve_aggregate)
from presto_tpu.ops.hash_agg import GroupedAggregationBuilder
from presto_tpu.types import BIGINT, DOUBLE, decimal_type


def _agg_pages(rng, npages, cap, dist, with_nulls=False):
    pages = []
    for _ in range(npages):
        if dist == "few":
            keys = rng.randint(0, 17, cap).astype(np.int64) * 3 - 7
        elif dist == "one":
            keys = np.full(cap, 42, dtype=np.int64)
        elif dist == "many":  # groups ~ rows: the defer path
            keys = rng.randint(0, 10 ** 9, cap).astype(np.int64)
        else:
            raise AssertionError(dist)
        vals = rng.randint(-50, 100, cap).astype(np.int64)
        p = page_from_arrays([BIGINT, BIGINT], [keys, vals],
                             count=cap, capacity=cap)
        if with_nulls:
            nulls = rng.rand(cap) < 0.15
            p = Page((Block(BIGINT, p.blocks[0].data, jnp.asarray(nulls),
                            None), p.blocks[1]), p.mask)
        pages.append(p)
    return pages


def _agg_result(pages, key_types):
    """sum, min and count(*) of the last channel by the leading ones ->
    {key tuple (None = NULL): (sum, min, count)}."""
    nkeys = len(key_types)
    calls = [AggregateCall(resolve_aggregate("sum", [BIGINT], False, ()),
                           [nkeys], None),
             AggregateCall(resolve_aggregate("min", [BIGINT], False, ()),
                           [nkeys], None),
             AggregateCall(resolve_aggregate("count", [], False, ()),
                           [], None)]
    b = GroupedAggregationBuilder(
        key_types, [None] * nkeys, calls,
        pages[0].capacity).set_channels(list(range(nkeys)))
    for p in pages:
        b.add_page(p)
    keys, states, valid = b.finish()
    keys = [np.asarray(k) for k in keys]
    # states: (sum, non-null inputs), (min, non-null inputs), (count)
    total, _, least, _, count = (np.asarray(s) for s in states)
    out = {}
    for i in np.flatnonzero(np.asarray(valid)):
        k = tuple(None if keys[j + 1][i] else keys[j][i].item()
                  for j in range(0, 2 * nkeys, 2))
        assert k not in out, f"group {k} came out twice"
        out[k] = (int(total[i]), int(least[i]), int(count[i]))
    return out


def _plain_group_by(pages, nkeys):
    out = {}
    for p in pages:
        cols = [(np.asarray(b.data),
                 np.asarray(b.nulls) if b.nulls is not None else None)
                for b in p.blocks]
        for i in np.flatnonzero(np.asarray(p.mask)):
            k = tuple(None if n is not None and n[i] else d[i].item()
                      for d, n in cols[:nkeys])
            v = int(cols[nkeys][0][i])
            s, m, c = out.get(k, (0, v, 0))
            out[k] = (s + v, min(m, v), c + 1)
    return out


@pytest.mark.parametrize("dist", ["few", "one", "many"])
@pytest.mark.parametrize("with_nulls", [False, True])
def test_fuzz_grouped_agg_equals_plain_reference(dist, with_nulls):
    rng = np.random.RandomState(13)
    pages = _agg_pages(rng, 5, 256, dist, with_nulls)
    want = _plain_group_by(pages, 1)
    assert _agg_result(pages, [BIGINT]) == want
    assert (None,) in want or not with_nulls


def test_multi_key_groups():
    rng = np.random.RandomState(23)
    pages = []
    for _ in range(4):
        k1 = rng.randint(0, 5, 256).astype(np.int64)
        k2 = rng.randint(0, 4, 256).astype(np.int64) * 11
        vals = rng.randint(0, 100, 256).astype(np.int64)
        pages.append(page_from_arrays([BIGINT, BIGINT, BIGINT],
                                      [k1, k2, vals], count=256,
                                      capacity=256))
    want = _plain_group_by(pages, 2)
    assert len(want) == 20
    assert _agg_result(pages, [BIGINT, BIGINT]) == want


def test_float_keys():
    rng = np.random.RandomState(2)
    pages = []
    for _ in range(3):
        keys = rng.randint(0, 9, 128).astype(np.float64) / 2
        vals = rng.randint(0, 50, 128).astype(np.int64)
        pages.append(page_from_arrays([DOUBLE, BIGINT], [keys, vals],
                                      count=128, capacity=128))
    want = _plain_group_by(pages, 1)
    assert (0.5,) in want and (1.0,) in want  # halves stay apart
    assert _agg_result(pages, [DOUBLE]) == want


# ------------------------------------------- the sorted-segment reduction

SCATTER = {SUM: jax.ops.segment_sum, MIN: jax.ops.segment_min,
           MAX: jax.ops.segment_max}
ROWS = 300        # not a whole number of the scan's blocks: a padded tail
OVERFLOW_GROUPS = 8


def _sorted_case(case, dist, dtype, rng):
    """(keys, mask, values, out_groups) of one page of the parametrised
    case."""
    n = 0 if case == "zero_row" else ROWS
    if dist == "one":
        keys = np.full(n, 42, dtype=np.int64)
    elif dist == "few":
        keys = rng.integers(0, 17, n).astype(np.int64) * 3 - 7
    else:  # every row its own group
        keys = rng.permutation(n).astype(np.int64) * 5
    dtype = np.dtype(dtype)
    if case == "wrap":  # sums past 2^63 (2^31 for int32) wrap as the scatter's
        hi = np.iinfo(dtype).max if dtype.kind == "i" else 2.0 ** 62
        values = rng.integers(int(hi) // 4, int(hi) // 2, n).astype(dtype)
    else:
        values = rng.integers(-1000, 1000, n).astype(dtype)
        if dtype.kind == "f":
            values = values / 8
    if case == "nan":  # NaN in a float column, the extremes in an int one
        odd = rng.random(n) < 0.1
        if dtype.kind == "f":
            values[odd] = np.nan
        else:
            info = np.iinfo(dtype)
            values[odd] = np.where(rng.random(n) < 0.5, info.min,
                                   info.max)[odd]
    invalid = {"trash": 0.3, "overflow": 0.1}.get(case, 0.0)
    mask = rng.random(n) >= invalid
    out_groups = OVERFLOW_GROUPS if case == "overflow" else max(n, 16)
    return keys, mask, values, out_groups


@pytest.mark.parametrize("case", ["trash", "overflow", "zero_row", "wrap",
                                  "nan"])
@pytest.mark.parametrize("dist", ["one", "few", "own"])
@pytest.mark.parametrize("dtype", ["int32", "int64", "float64"])
@pytest.mark.parametrize("kind", [SUM, MIN, MAX])
def test_sorted_reduction_equals_the_scatter(kind, dtype, dist, case):
    """sort_group_reduce's segmented scan over the sorted rows, read at each
    group's last row, against jax.ops.segment_* over the same groups: ints
    and MIN/MAX bit-equal, float sums to 1e-12 (another association); the
    trash rows reach no group, groups past out_groups are dropped, empty
    slots hold the identity."""
    rng = np.random.default_rng(
        [SUM, MIN, MAX].index(kind) * 100 + len(dtype) * 10 + len(dist))
    keys, mask, values, out_groups = _sorted_case(case, dist, dtype, rng)
    ident = hash_agg._reduce_identity(kind, values.dtype).item()
    gkeys, (state,), gvalid, num_groups = hash_agg._combine_kernel(
        (jnp.asarray(keys),), jnp.asarray(mask), (jnp.asarray(values),),
        (kind,), (ident,), out_groups, (1,))
    uniq, group = np.unique(keys[mask], return_inverse=True)
    want = SCATTER[kind](jnp.asarray(values[mask]),
                         jnp.asarray(group, dtype=np.int32),
                         num_segments=out_groups)
    live = min(len(uniq), out_groups)
    assert int(num_groups) == len(uniq)
    assert np.asarray(gvalid).tolist() == [True] * live + \
        [False] * (out_groups - live)
    assert np.asarray(gkeys[0])[:live].tolist() == uniq[:live].tolist()
    got = np.asarray(state)
    assert got.dtype == values.dtype and got.shape == (out_groups,)
    if kind == SUM and values.dtype.kind == "f":
        np.testing.assert_allclose(got[:live], np.asarray(want)[:live],
                                   rtol=1e-12, atol=0)
    else:
        np.testing.assert_array_equal(got[:live], np.asarray(want)[:live])
    np.testing.assert_array_equal(got[live:], np.full(out_groups - live,
                                                      ident, values.dtype))


def test_the_fold_kernel_keeps_one_scatter_the_first_index():
    """sum(decimal) + min + count(*) over more segments than the masked form
    takes: the five state columns lower without a scatter; the one left is
    the int32 segment_min that finds each group's first row."""
    calls = [resolve_aggregate("sum", [decimal_type(12, 2)], False, ()),
             resolve_aggregate("min", [BIGINT], False, ()),
             resolve_aggregate("count", [], False, ())]
    cols = [col for f in calls for col in f.state]
    rows, groups = 16384, hash_agg.DENSE_REDUCE_MAX_SEGMENTS * 2
    spec = jax.ShapeDtypeStruct
    text = hash_agg._combine_kernel.lower(
        (spec((rows,), np.int64), spec((rows,), np.bool_)),
        spec((rows,), np.bool_), tuple(spec((rows,), c.dtype) for c in cols),
        tuple(c.reduce for c in cols), tuple(c.identity for c in cols),
        groups, (1,) * len(cols)).as_text()
    lines = text.splitlines()
    scatters = [i for i, line in enumerate(lines)
                if '"stablehlo.scatter"(' in line]
    assert len(cols) == 5 and len(scatters) == 1, scatters
    # its signature closes the op's region a few lines down
    op = "\n".join(lines[scatters[0]:scatters[0] + 8])
    assert f"-> tensor<{groups + 1}xi32>" in op, op


def test_a_q3_counts_its_columns_by_form():
    """A local Q3 reduces sum(decimal)'s two states by the scan in every
    partial and fold, and scatters none."""
    from presto_tpu.metadata import Session
    from presto_tpu.models.tpch_sql import QUERIES
    from presto_tpu.runner import LocalQueryRunner
    from presto_tpu.utils.metrics import METRICS

    scan = METRICS.counter_value("agg.sort.scan_columns")
    scatter = METRICS.counter_value("agg.sort.scatter_columns")
    rows = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny")).execute(QUERIES[3]).rows
    assert len(rows) == 10
    assert METRICS.counter_value("agg.sort.scan_columns") > scan
    assert METRICS.counter_value("agg.sort.scatter_columns") == scatter
