"""Hash join tests (reference: TestHashJoinOperator.java patterns, page-level)."""
import numpy as np
import pytest

from presto_tpu.types import BIGINT, DOUBLE, VARCHAR
from presto_tpu.block import Block, Page, page_from_arrays
from presto_tpu.ops import hash_join
from presto_tpu.ops.hash_join import (ANTI, INNER, LEFT, SEMI, JoinBuildOperatorFactory,
                                      LookupJoinOperatorFactory)
from presto_tpu.utils.testing import assert_rows_equal


def run_join(build_pages, probe_pages, build_fac, probe_fac):
    b = build_fac.create_operator()
    for p in build_pages:
        b.add_input(p)
    b.finish()
    j = probe_fac.create_operator()
    rows = []
    for p in probe_pages:
        j.add_input(p)
        while True:
            o = j.get_output()
            if o is None:
                break
            rows.extend(o.to_pylists())
    j.finish()
    while True:
        o = j.get_output()
        if o is None:
            break
        rows.extend(o.to_pylists())
    return rows


@pytest.mark.parametrize("form", ["dense", "sorted"])
def test_inner_unique_join(form, monkeypatch):
    # the build picks the direct-address table from the keys it sees; a byte
    # bound of 0 refuses every table and keeps the sorted form
    if form == "sorted":
        monkeypatch.setattr(hash_join, "DENSE_JOIN_MAX_TABLE_BYTES", 0)
    # build: (key, value); probe: (key, weight)
    bkeys = np.asarray([1, 3, 5, 7], dtype=np.int64)
    bvals = np.asarray([10, 30, 50, 70], dtype=np.int64)
    build = page_from_arrays([BIGINT, BIGINT], [bkeys, bvals], count=4, capacity=8)
    pkeys = np.asarray([5, 1, 2, 7, 7, 9], dtype=np.int64)
    pw = np.asarray([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    probe = page_from_arrays([BIGINT, DOUBLE], [pkeys, pw], count=6, capacity=8)
    bf = JoinBuildOperatorFactory(0, [0], [1], [(BIGINT, None)],
                                  strategy="sorted", unique=True)
    pf = LookupJoinOperatorFactory(1, bf.lookup_factory, [0], [0, 1],
                                   [(BIGINT, None), (DOUBLE, None)],
                                   [0], [(BIGINT, None)], INNER)
    rows = run_join([build], [probe], bf, pf)
    assert bf.lookup_factory.get().kind == form
    exp = [[5, 1.0, 50], [1, 2.0, 10], [7, 4.0, 70], [7, 5.0, 70]]
    assert_rows_equal(rows, exp)


def test_left_outer_join():
    bkeys = np.asarray([1, 3], dtype=np.int64)
    bvals = np.asarray([10, 30], dtype=np.int64)
    build = page_from_arrays([BIGINT, BIGINT], [bkeys, bvals], count=2, capacity=4)
    pkeys = np.asarray([1, 2, 3], dtype=np.int64)
    probe = page_from_arrays([BIGINT], [pkeys], count=3, capacity=4)
    bf = JoinBuildOperatorFactory(0, [0], [1], [(BIGINT, None)],
                                  strategy="sorted", unique=True)
    pf = LookupJoinOperatorFactory(1, bf.lookup_factory, [0], [0],
                                   [(BIGINT, None)], [0], [(BIGINT, None)], LEFT)
    rows = run_join([build], [probe], bf, pf)
    assert_rows_equal(rows, [[1, 10], [2, None], [3, 30]])


def test_duplicate_build_expansion():
    # build has duplicate keys -> output fanout > 1 per probe row
    bkeys = np.asarray([1, 1, 1, 2, 2], dtype=np.int64)
    bvals = np.asarray([11, 12, 13, 21, 22], dtype=np.int64)
    build = page_from_arrays([BIGINT, BIGINT], [bkeys, bvals], count=5, capacity=8)
    pkeys = np.asarray([1, 2, 3], dtype=np.int64)
    pvals = np.asarray([100, 200, 300], dtype=np.int64)
    probe = page_from_arrays([BIGINT, BIGINT], [pkeys, pvals], count=3, capacity=4)
    bf = JoinBuildOperatorFactory(0, [0], [1], [(BIGINT, None)],
                                  strategy="sorted", unique=False)
    pf = LookupJoinOperatorFactory(1, bf.lookup_factory, [0], [0, 1],
                                   [(BIGINT, None), (BIGINT, None)],
                                   [0], [(BIGINT, None)], INNER)
    rows = run_join([build], [probe], bf, pf)
    exp = [[1, 100, 11], [1, 100, 12], [1, 100, 13], [2, 200, 21], [2, 200, 22]]
    assert_rows_equal(rows, exp)


def test_expansion_exceeds_page_capacity():
    # fanout makes output bigger than one page -> chunked emission
    bkeys = np.repeat(np.arange(1, 4, dtype=np.int64), 4)  # 1x4, 2x4, 3x4
    bvals = np.arange(12, dtype=np.int64)
    build = page_from_arrays([BIGINT, BIGINT], [bkeys, bvals], count=12, capacity=16)
    pkeys = np.asarray([1, 2, 3, 1], dtype=np.int64)
    probe = page_from_arrays([BIGINT], [pkeys], count=4, capacity=4)  # cap 4 < 16 outputs
    bf = JoinBuildOperatorFactory(0, [0], [1], [(BIGINT, None)],
                                  strategy="sorted", unique=False)
    pf = LookupJoinOperatorFactory(1, bf.lookup_factory, [0], [0],
                                   [(BIGINT, None)], [0], [(BIGINT, None)], INNER)
    rows = run_join([build], [probe], bf, pf)
    assert len(rows) == 16
    got = sorted((r[0], r[1]) for r in rows)
    # each probe key k matches the 4 build rows with that key; probe has 1,2,3,1
    expect = []
    for pk in pkeys:
        for v in bvals[bkeys == pk]:
            expect.append((int(pk), int(v)))
    assert got == sorted(expect)


def test_multi_key_join():
    b1 = np.asarray([1, 1, 2], dtype=np.int64)
    b2 = np.asarray([10, 20, 10], dtype=np.int64)
    bv = np.asarray([110, 120, 210], dtype=np.int64)
    build = page_from_arrays([BIGINT, BIGINT, BIGINT], [b1, b2, bv], count=3, capacity=4)
    p1 = np.asarray([1, 1, 2, 2], dtype=np.int64)
    p2 = np.asarray([10, 20, 10, 20], dtype=np.int64)
    probe = page_from_arrays([BIGINT, BIGINT], [p1, p2], count=4, capacity=4)
    bf = JoinBuildOperatorFactory(0, [0, 1], [2], [(BIGINT, None)],
                                  strategy="sorted", unique=True)
    pf = LookupJoinOperatorFactory(1, bf.lookup_factory, [0, 1], [0, 1],
                                   [(BIGINT, None), (BIGINT, None)],
                                   [0], [(BIGINT, None)], INNER)
    rows = run_join([build], [probe], bf, pf)
    assert_rows_equal(rows, [[1, 10, 110], [1, 20, 120], [2, 10, 210]])


def test_semi_and_anti_join():
    bkeys = np.asarray([2, 4], dtype=np.int64)
    build = page_from_arrays([BIGINT], [bkeys], count=2, capacity=4)
    pkeys = np.asarray([1, 2, 3, 4], dtype=np.int64)
    probe = page_from_arrays([BIGINT], [pkeys], count=4, capacity=4)
    for jt, expect in [(SEMI, [[2], [4]]), (ANTI, [[1], [3]])]:
        bf = JoinBuildOperatorFactory(0, [0], [], [], strategy="sorted", unique=False)
        pf = LookupJoinOperatorFactory(1, bf.lookup_factory, [0], [0],
                                       [(BIGINT, None)], [], [], jt)
        rows = run_join([build], [probe], bf, pf)
        assert_rows_equal(rows, expect)


def test_null_keys_never_match():
    bkeys = np.asarray([1, 2], dtype=np.int64)
    build = Page((Block(BIGINT, bkeys, np.asarray([False, True])),
                  Block(BIGINT, np.asarray([10, 20], dtype=np.int64))),
                 np.ones(2, dtype=bool))
    pkeys = np.asarray([1, 2], dtype=np.int64)
    probe = Page((Block(BIGINT, pkeys, np.asarray([False, True])),),
                 np.ones(2, dtype=bool))
    bf = JoinBuildOperatorFactory(0, [0], [1], [(BIGINT, None)],
                                  strategy="sorted", unique=True)
    pf = LookupJoinOperatorFactory(1, bf.lookup_factory, [0], [0],
                                   [(BIGINT, None)], [0], [(BIGINT, None)], INNER)
    rows = run_join([build], [probe], bf, pf)
    # only the non-null key 1 on both sides matches
    assert_rows_equal(rows, [[1, 10]])


def test_empty_build():
    build = page_from_arrays([BIGINT, BIGINT], [np.zeros(0, np.int64), np.zeros(0, np.int64)],
                             count=0, capacity=4)
    probe = page_from_arrays([BIGINT], [np.asarray([1, 2], dtype=np.int64)],
                             count=2, capacity=4)
    bf = JoinBuildOperatorFactory(0, [0], [1], [(BIGINT, None)],
                                  strategy="sorted", unique=True)
    pf = LookupJoinOperatorFactory(1, bf.lookup_factory, [0], [0],
                                   [(BIGINT, None)], [0], [(BIGINT, None)], INNER)
    rows = run_join([build], [probe], bf, pf)
    assert rows == []
