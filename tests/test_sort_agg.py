"""The sort aggregation builder (ops/hash_agg.GroupedAggregationBuilder),
page level, against a plain Python dict group-by written here.

Key distributions: a few groups (shrunken partials), one group, groups ~
rows (the defer-raw path), NULL keys, two keys, float keys.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from presto_tpu.block import Block, Page, page_from_arrays
from presto_tpu.ops.aggregates import AggregateCall, resolve_aggregate
from presto_tpu.ops.hash_agg import GroupedAggregationBuilder
from presto_tpu.types import BIGINT, DOUBLE


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
