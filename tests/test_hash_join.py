"""Hash join tests (reference: TestHashJoinOperator.java patterns, page-level)."""
import numpy as np
import pytest

from presto_tpu.types import BIGINT, DOUBLE, VARCHAR
from presto_tpu.block import (Block, Dictionary, Page, page_from_arrays,
                              page_from_pylists)
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
                                  unique=True)
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
                                  unique=True)
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
                                  unique=False)
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
                                  unique=False)
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
                                  unique=True)
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
        bf = JoinBuildOperatorFactory(0, [0], [], [], unique=False)
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
                                  unique=True)
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
                                  unique=True)
    pf = LookupJoinOperatorFactory(1, bf.lookup_factory, [0], [0],
                                   [(BIGINT, None)], [0], [(BIGINT, None)], INNER)
    rows = run_join([build], [probe], bf, pf)
    assert rows == []


# ------------------------------------------------ fuzz against a plain join
#
# The reference is a Python dict join written here: it shares nothing with
# the engine, so a fault in a lookup structure, in _range_kernel or in
# _expand_kernel cannot hide behind another engine path making it too.

def _page(rows):
    """[[key | None, payload]] -> one page at the rows' pow2 capacity."""
    return page_from_pylists(
        [BIGINT, BIGINT], rows,
        capacity=1 << max(3, (len(rows) - 1).bit_length()))


def _rows(keys, payload, nulls=None):
    return [[None if nulls is not None and nulls[i] else int(keys[i]),
             int(payload[i])] for i in range(len(keys))]


def _join_factories(jt, unique):
    # as the planner builds them: a semi join's build is never declared unique
    bf = JoinBuildOperatorFactory(0, [0], [1], [(BIGINT, None)],
                                  unique=unique and jt in (INNER, LEFT))
    if jt in (SEMI, ANTI):
        pf = LookupJoinOperatorFactory(
            1, bf.lookup_factory, [0], [0, 1],
            [(BIGINT, None), (BIGINT, None)], [], [], jt)
    else:
        pf = LookupJoinOperatorFactory(
            1, bf.lookup_factory, [0], [0, 1],
            [(BIGINT, None), (BIGINT, None)], [0], [(BIGINT, None)],
            jt, unique_build=unique)
    return bf, pf


def _probe_keys(rng, build_keys, n):
    """Mixture of hits, misses and repeats."""
    pool = np.concatenate([build_keys, build_keys,
                           rng.randint(-10 ** 6, 10 ** 6, max(n, 1))])
    return rng.choice(pool, n).astype(np.int64)


def _plain_join(jt, build, probe):
    """build, probe: [[key | None, payload]] -> the rows SQL asks for
    (EXISTS semantics for SEMI/ANTI: a NULL key never matches)."""
    table = {}
    for k, v in build:
        if k is not None:
            table.setdefault(k, []).append(v)
    out = []
    for k, v in probe:
        hits = table.get(k, []) if k is not None else []
        if jt == SEMI:
            out += [[k, v]] if hits else []
        elif jt == ANTI:
            out += [] if hits else [[k, v]]
        else:
            out += [[k, v, h] for h in hits]
            if jt == LEFT and not hits:
                out.append([k, v, None])
    return out


@pytest.mark.parametrize("jt", [INNER, LEFT, SEMI, ANTI])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("keys", ["unique", "duplicate"])
def test_fuzz_join_equals_plain_reference(keys, seed, jt):
    rng = np.random.RandomState(seed)
    n_build = rng.randint(50, 400)
    if keys == "unique":
        build_keys = rng.permutation(4000)[:n_build].astype(np.int64)
        build_nulls = None
    else:
        # each key 1-8 times, some build keys NULL (they never join)
        build_keys = rng.choice(rng.permutation(4000)[:max(n_build // 4, 1)],
                                n_build).astype(np.int64)
        build_nulls = rng.rand(n_build) < 0.05
    build = _rows(build_keys, rng.randint(0, 10 ** 6, n_build), build_nulls)
    probe_keys = _probe_keys(rng, build_keys, rng.randint(10, 500))
    probe = _rows(probe_keys, rng.randint(0, 10 ** 6, len(probe_keys)),
                  rng.rand(len(probe_keys)) < 0.1)
    bf, pf = _join_factories(jt, unique=keys == "unique")
    rows = run_join([_page(build)], [_page(probe)], bf, pf)
    assert_rows_equal(rows, _plain_join(jt, build, probe), ordered=False)
    # the structure is the build's own choice: one row per key slot and a
    # unique claim -> the direct-address table; everything else sorted
    kind = bf.lookup_factory.get(0).kind
    assert kind == ("dense" if keys == "unique" and jt in (INNER, LEFT)
                    else "sorted")


@pytest.mark.parametrize("case", ["empty_build", "all_misses",
                                  "null_build_keys", "multi_page"])
def test_join_edge_cases_equal_plain_reference(case):
    rng = np.random.RandomState(7)
    if case == "empty_build":
        build = []
    elif case == "null_build_keys":
        keys = np.arange(20)
        build = [_rows(keys, keys * 10, keys % 3 == 0)]
    elif case == "multi_page":
        build = [_rows(np.arange(w * 50, w * 50 + 50), np.arange(50))
                 for w in range(3)]
    else:
        build = [_rows(np.arange(30), np.arange(30))]
    probe_keys = np.arange(10 ** 6, 10 ** 6 + 40) \
        if case == "all_misses" else _probe_keys(rng, np.arange(60), 80)
    probe = _rows(probe_keys, np.arange(len(probe_keys)))
    for jt in (INNER, LEFT, SEMI, ANTI):
        bf, pf = _join_factories(jt, unique=True)
        rows = run_join([_page(p) for p in build], [_page(probe)], bf, pf)
        assert_rows_equal(
            rows, _plain_join(jt, [r for p in build for r in p], probe),
            ordered=False)


def test_dict_encoded_keys_and_payload():
    d = Dictionary([f"v{i}" for i in range(40)])
    build_keys = np.arange(40, dtype=np.int64)
    probe_keys = np.random.RandomState(3).randint(0, 80, 100)
    bf = JoinBuildOperatorFactory(0, [0], [1], [(VARCHAR, d)], unique=True)
    pf = LookupJoinOperatorFactory(
        1, bf.lookup_factory, [0], [0, 1], [(BIGINT, None), (BIGINT, None)],
        [0], [(VARCHAR, d)], INNER, unique_build=True)
    build = Page((Block(VARCHAR, build_keys, None, d),
                  Block(VARCHAR, build_keys.copy(), None, d)),
                 np.ones(40, bool))
    rows = run_join([build], [_page(_rows(probe_keys, probe_keys * 2))],
                    bf, pf)
    # codes join as integers, the payload comes back decoded; half miss
    want = [[int(k), int(k) * 2, f"v{k}"] for k in probe_keys if k < 40]
    assert 0 < len(want) < 100
    assert_rows_equal(rows, want, ordered=False)
