"""CoalesceOperator against a plain reference: what comes out is the stream's
live rows in order, full pages then one tail in pack mode, the pages as they
came in pass mode, and `coalesce.pages` / `coalesce.packed_pages` say which;
the same decision where the planner puts it behind a join (PR 35).
"""
import itertools

import numpy as np
import pytest

from presto_tpu.block import Block, Dictionary, Page
from presto_tpu.ops.coalesce import CoalesceOperatorFactory
from presto_tpu.types import BIGINT, DOUBLE, INTEGER, VARCHAR
from presto_tpu.utils.metrics import METRICS

DICT = Dictionary([f"s{i}" for i in range(5)])
TYPES = [BIGINT, DOUBLE, INTEGER, VARCHAR]


def _page(rng, cap, share, first):
    """`cap` rows numbered from `first`, about `share` of them live; every
    column nullable so a null travels with its row."""
    ids = np.arange(first, first + cap, dtype=np.int64)
    blocks = (
        Block(BIGINT, ids * (1 << 34) + 1, rng.random(cap) < 0.2),
        Block(DOUBLE, ids * 0.25 + 0.5, None),
        Block(INTEGER, (ids % 1000 + 1).astype(np.int32),
              rng.random(cap) < 0.2),
        Block(VARCHAR, (ids % 5).astype(np.int32), rng.random(cap) < 0.2,
              DICT))
    return Page(blocks, rng.random(cap) < share)


def _stream(seed, caps, share):
    rng = np.random.default_rng(seed)
    pages, first = [], 0
    for cap in caps:
        pages.append(_page(rng, cap, share, first))
        first += cap
    return pages


def _run(pages):
    """Drive the operator the way a driver does: output drained after every
    input, then finish and drain."""
    op = CoalesceOperatorFactory(0, TYPES, [None, None, None, DICT]) \
        .create_operator()
    out = []

    def drain():
        while (p := op.get_output()) is not None:
            out.append(p)

    for page in pages:
        assert op.needs_input()
        op.add_input(page)
        drain()
    op.finish()
    drain()
    assert op.is_finished()
    return out


def _counted(fn):
    names = ("coalesce.pages", "coalesce.packed_pages")
    before = [METRICS.counter_value(n) for n in names]
    result = fn()
    return result, tuple(METRICS.counter_value(n) - b
                         for n, b in zip(names, before))


def _live_rows(pages):
    return [row for p in pages for row in p.to_pylists()]


def _assert_prefix_with_zero_tail(page):
    """A packed page: the mask a prefix, nothing left in a dead slot."""
    mask = np.asarray(page.mask)
    n = int(mask.sum())
    assert np.array_equal(mask, np.arange(len(mask)) < n)
    for b in page.blocks:
        assert not np.asarray(b.data)[n:].any()
        assert b.nulls is None or not np.asarray(b.nulls)[n:].any()


@pytest.mark.parametrize("share", [0.05, 0.3, 0.5])
def test_pack_mode_full_pages_then_one_tail(share):
    cap = 64
    pages = _stream(7, [cap] * 9, share)
    # the decision is the first page's: make it one that packs
    assert np.asarray(pages[0].mask).mean() <= 0.5
    out, (seen, packed) = _counted(lambda: _run(pages))
    rows = _live_rows(pages)
    assert _live_rows(out) == rows  # every live row, in order, nulls kept
    assert len(out) == -(-len(rows) // cap)
    for p in out[:-1]:
        assert p.capacity == cap and np.asarray(p.mask).all()
    for p in out:
        _assert_prefix_with_zero_tail(p)
        assert [b.type for b in p.blocks] == TYPES
        assert p.blocks[3].dictionary is DICT
    assert (seen, packed) == (9, 9)


def test_pass_mode_hands_pages_on_untouched():
    pages = _stream(11, [64] * 5, 0.9)
    assert np.asarray(pages[0].mask).mean() > 0.5
    out, (seen, packed) = _counted(lambda: _run(pages))
    assert len(out) == len(pages)
    assert all(o is p for o, p in zip(out, pages))
    assert (seen, packed) == (5, 0)


def test_mode_is_the_first_pages_decision():
    """A dense first page passes the whole stream; a sparse first page packs
    it, dense pages after it included."""
    dense, sparse = _stream(3, [32] * 2, 0.9), _stream(5, [32] * 3, 0.1)
    out, counts = _counted(lambda: _run(dense + sparse))
    assert all(o is p for o, p in zip(out, dense + sparse))
    assert counts == (5, 0)
    out, counts = _counted(lambda: _run(sparse + dense))
    assert _live_rows(out) == _live_rows(sparse + dense)
    for p in out[:-1]:
        assert np.asarray(p.mask).all()
    assert counts == (5, 5)


def test_capacity_change_mid_stream_flushes_and_restarts():
    pages = _stream(13, [32, 32, 32, 128, 128, 16], 0.3)
    out, counts = _counted(lambda: _run(pages))
    assert _live_rows(out) == _live_rows(pages)
    # the accumulator of each capacity leaves as a partial page of its own
    # capacity before the next capacity starts
    assert [c for c, _ in itertools.groupby(p.capacity for p in out)] == \
        [32, 128, 16]
    for p in out:
        _assert_prefix_with_zero_tail(p)
    assert counts == (6, 6)


def test_empty_pages_and_empty_stream():
    assert _run([]) == []
    rng = np.random.default_rng(17)
    pages = [_page(rng, 32, 0.0, 0), _page(rng, 32, 0.4, 32),
             _page(rng, 32, 0.0, 64)]
    out, counts = _counted(lambda: _run(pages))
    assert _live_rows(out) == _live_rows(pages)
    assert len(out) == 1 and counts == (3, 3)


def test_behind_a_join_that_keeps_its_rows_it_passes_every_page():
    """The planner packs an INNER join's survivors ahead of a probe that
    cannot fuse (two key columns). Whether that pays is the operator's to see:
    every line has its part, the first page arrives more than half live, and
    the stream goes through untouched."""
    from presto_tpu.metadata import Session
    from presto_tpu.runner import LocalQueryRunner

    runner = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    lines = runner.execute("select count(*) from lineitem").rows[0][0]
    result, (seen, packed) = _counted(lambda: runner.execute(
        "select count(*) from lineitem, part, partsupp "
        "where l_partkey = p_partkey "
        "and ps_partkey = l_partkey and ps_suppkey = l_suppkey"))
    assert result.rows == [[lines]]
    assert seen >= 1 and packed == 0
