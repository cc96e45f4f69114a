"""The aggregation's once-a-query work as compiled programs (ops/hash_agg.py).

A builder's state is made by ONE cached program, and its result finished by
ONE program where its size is static (the direct builder's D slots, the
global builder's one row); where the size is data (the sort and collect
builders) one program finishes the columns and counts, and the host reads
them once. Held here: (a) the program census of a warm Q1 and Q6
(tools/program_census.py), (b) the fused result equal to the eager one it
replaced, for every aggregate of ops/aggregates.py, (c) the two counters.
"""
import json
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu.block import Block, Dictionary, Page
from presto_tpu.metadata import Session
from presto_tpu.models.tpch_sql import QUERIES
from presto_tpu.ops.aggregates import AggregateCall, resolve_aggregate
from presto_tpu.ops.collect_agg import COLLECT_NAMES
from presto_tpu.ops.hash_agg import (FINAL, PARTIAL, SINGLE,
                                     HashAggregationOperatorFactory)
from presto_tpu.runner import LocalQueryRunner
from presto_tpu.types import (BIGINT, BOOLEAN, DOUBLE, VARCHAR, DecimalType)
from presto_tpu.utils.metrics import METRICS
from tools.program_census import Census


@pytest.fixture(scope="module")
def runner():
    return LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny", properties={"page_capacity": 1 << 14}))


# ------------------------------------------------------------------ (a) census

def _warm_census(runner, sql):
    """-> (page count, program runs of the warm query)."""
    runner.execute(sql)
    with Census() as census:
        runner.execute(sql)  # the fast path is off: programs compile anew
        with census.counting() as runs:
            runner.execute(sql)
    pages = sum(1 for _n, line in runs if line.startswith("presto_tpu/ops/scan.py"))
    return pages, runs


@pytest.mark.parametrize("query,once", [(1, 3), (6, 2)], ids=["q1", "q6"])
def test_warm_query_runs_two_programs_a_page_and_few_once(runner, query, once):
    """Q1: the scan's and the aggregation's program a page, then the state's
    init, the result and OrderBy's sort (76 with 8 pages before: 60 + 2 a
    page). Q6: the same without the sort (25 before: 9 + 2 a page)."""
    pages, runs = _warm_census(runner, QUERIES[query])
    agg_pages = sum(1 for _n, line in runs if line.endswith(" add_page"))
    assert pages >= 2 and agg_pages == pages, runs
    assert len(runs) - 2 * pages <= once, runs


# ------------------------------------------------------- (b) fused == eager

DEC = DecimalType(12, 2)
SORTED = Dictionary(["apple", "banana", "cherry", "date", "elder"])
UNSORTED = Dictionary(["pear", "fig", "kiwi", "apple", "lime"])
# key, x (NULLs), d, dec, b, varchar (sorted dictionary), varchar (unsorted)
TYPES = [BIGINT, BIGINT, DOUBLE, DEC, BOOLEAN, VARCHAR, VARCHAR]
DICTS = [None, None, None, None, None, SORTED, UNSORTED]
CAP = 128

# (id, function, input channels, params): every aggregate of ops/aggregates
AGGS = [
    ("count_star", "count", [], ()), ("count", "count", [1], ()),
    ("sum_bigint", "sum", [1], ()), ("sum_double", "sum", [2], ()),
    ("sum_decimal", "sum", [3], ()), ("avg_bigint", "avg", [1], ()),
    ("avg_decimal", "avg", [3], ()), ("min_bigint", "min", [1], ()),
    ("max_double", "max", [2], ()), ("min_varchar", "min", [5], ()),
    ("max_varchar", "max", [5], ()), ("min_varchar_unsorted", "min", [6], ()),
    ("max_varchar_unsorted", "max", [6], ()), ("min_by", "min_by", [1, 2], ()),
    ("max_by_varchar", "max_by", [6, 1], ()), ("stddev", "stddev", [2], ()),
    ("stddev_samp", "stddev_samp", [1], ()), ("stddev_pop", "stddev_pop", [3], ()),
    ("variance", "variance", [2], ()), ("var_samp", "var_samp", [1], ()),
    ("var_pop", "var_pop", [2], ()), ("bool_or", "bool_or", [4], ()),
    ("bool_and", "bool_and", [4], ()), ("every", "every", [4], ()),
    ("count_if", "count_if", [4], ()), ("arbitrary", "arbitrary", [1], ()),
    ("any_value", "any_value", [5], ()), ("covar_samp", "covar_samp", [2, 1], ()),
    ("covar_pop", "covar_pop", [2, 3], ()), ("corr", "corr", [2, 1], ()),
    ("approx_distinct", "approx_distinct", [1], ()),
    ("approx_percentile", "approx_percentile", [2], (0.5,)),
    ("array_agg", "array_agg", [1], ()), ("map_agg", "map_agg", [5, 1], ()),
    ("histogram", "histogram", [5], ()),
    # plug-in aggregates (presto_tpu/functions/ml.py): one traced final map,
    # one that runs on the host and fills its dictionary
    ("regr_slope", "regr_slope", [2, 1], ()),
    ("learn_regressor", "learn_linear_regressor", [2, 1], ()),
]
BUILDERS = ["direct", "global", "sort", "collect"]
_DICT_OUT = ("min", "max", "arbitrary", "any_value", "min_by", "max_by")


def _pages(n_keys: int, seed: int):
    """Two pages of CAP slots, 100 and 70 live rows; NULL keys and x."""
    rng = np.random.RandomState(seed)
    out = []
    for live in (100, 70):
        k = rng.randint(0, n_keys, CAP).astype(np.int64)
        cols = [k, rng.randint(-50, 1000, CAP).astype(np.int64),
                rng.rand(CAP) * 100.0 - 20.0,
                rng.randint(0, 10 ** 6, CAP).astype(np.int64),
                rng.rand(CAP) < 0.4,
                rng.randint(0, len(SORTED), CAP).astype(np.int32),
                rng.randint(0, len(UNSORTED), CAP).astype(np.int32)]
        nulls = {0: rng.rand(CAP) < 0.15, 1: rng.rand(CAP) < 0.1}
        blocks = tuple(Block(t, jnp.asarray(c), None if i not in nulls
                             else jnp.asarray(nulls[i]), d)
                       for i, (t, c, d) in enumerate(zip(TYPES, cols, DICTS)))
        out.append(Page(blocks, jnp.asarray(np.arange(CAP) < live)))
    return out


def _call(name, chans, params, intermediate=None):
    fn = resolve_aggregate(name, [TYPES[c] for c in chans], False, params)
    d = DICTS[chans[0]] if name in _DICT_OUT and chans else None
    if fn.output_dict is not None:
        d = fn.output_dict
    if intermediate is not None:
        return AggregateCall(fn, [], None, intermediate_channels=intermediate,
                             output_dictionary=d)
    return AggregateCall(fn, list(chans), None, output_dictionary=d)


def _factory(builder, calls, step, n_keys, out_cap=CAP):
    keyed = builder != "global"
    return HashAggregationOperatorFactory(
        0, [0] if keyed else [], [BIGINT] if keyed else [],
        [None] if keyed else [], [n_keys] if builder == "direct" else None,
        calls, step, out_cap)


def _eager_rows(op):
    """The result as `_build_result` made it before the fused programs: the
    builder's finish, then each final map, rank map and cast as its own
    eager step, and a host read of each key's NULL flags."""
    keys, states, valid = op.builder.finish()
    cols = []
    for i, (t, d) in enumerate(zip(op.key_types, op.key_dicts)):
        kv, kf = keys[2 * i], keys[2 * i + 1]
        cols.append((t, kv, d, kf if bool(np.asarray(kf).any()) else None))
    si = 0
    for call in op.calls:
        n = len(call.function.state)
        group_states = states[si: si + n]
        si += n
        if op.step == PARTIAL:
            cols.extend((it, s, None, None) for it, s in
                        zip(call.function.intermediate_types, group_states))
            continue
        out = call.function.final_map(group_states)
        nulls = None
        if isinstance(out, tuple):
            out, nulls = out
        d = call.output_dictionary
        if call.function.name in ("min", "max") and d is not None \
                and not d.is_sorted():
            order = jnp.asarray(d.sort_order())
            out = order[jnp.clip(out, 0, len(order) - 1).astype(jnp.int32)]
        cols.append((call.function.output_type, out, d, nulls))
    page = Page(tuple(Block(t, jnp.asarray(a, dtype=t.np_dtype),
                            None if n is None else jnp.asarray(n), d)
                      for t, a, d, n in cols), jnp.asarray(valid, bool))
    return _rows(page)


def _rows(page):
    """Live rows as Python values; a NULL cell is None before any dictionary
    is asked (an empty group's min over varchar holds the identity code)."""
    live = np.flatnonzero(np.asarray(page.mask))
    cols = []
    for b in page.blocks:
        data = np.asarray(b.data)[live]
        nulls = (np.zeros(len(live), bool) if b.nulls is None
                 else np.asarray(b.nulls)[live])
        d = b.dictionary
        cols.append([None if n else
                     d.lookup(np.asarray([v], np.int64))[0] if d is not None
                     else b.type.to_python(v) for v, n in zip(data, nulls)])
    return [list(r) for r in zip(*cols)]


def _drain(op):
    op.finish()
    pages = []
    while True:
        p = op.get_output()
        if p is None:
            return pages
        pages.append(p)


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for a, b in zip(g, w):
            if isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-12, abs=1e-300), (g, w)
            else:
                assert a == b, (g, w)


def _fused_and_eager(factory, pages):
    """-> (fused result pages, fused rows, eager rows) of two operators of
    one factory fed the same pages."""
    fused, eager = factory.create_operator(), factory.create_operator()
    for p in pages:
        fused.add_input(p)
        eager.add_input(p)
    out = _drain(fused)
    return out, [r for p in out for r in _rows(p)], _eager_rows(eager)


def _cases():
    for aid, name, chans, params in AGGS:
        collect = name in COLLECT_NAMES
        for builder in BUILDERS:
            if collect and builder not in ("collect", "global"):
                continue  # a collect call always plans the collect builder
            yield pytest.param(builder, name, chans, params,
                               id=f"{builder}-{aid}")


@pytest.mark.parametrize("builder,name,chans,params", list(_cases()))
def test_fused_result_equals_eager(builder, name, chans, params):
    """SINGLE over two pages (NULL keys and inputs), over no page at all,
    and where the function is splittable PARTIAL then FINAL; each result of
    the fused programs equals the eager one, row for row."""
    calls = [_call(name, chans, params)]
    if builder == "collect" and name not in COLLECT_NAMES:
        calls.insert(0, _call("array_agg", [1], ()))
    pages = _pages(4, seed=len(name) + len(builder))
    single = _factory(builder, calls, SINGLE, 5)
    _p, got, want = _fused_and_eager(single, pages)
    assert got  # every builder has a live group here
    _same(got, want)
    _p, got, want = _fused_and_eager(single, [])
    _same(got, want)
    if builder == "collect" or not all(c.function.splittable for c in calls):
        return
    mid, got, want = _fused_and_eager(
        _factory(builder, calls, PARTIAL, 5), pages)
    _same(got, want)
    nk = 0 if builder == "global" else 1
    widths = [len(c.function.intermediate_types) for c in calls]
    final_calls, ch = [], nk
    for (c, w) in zip(calls, widths):
        final_calls.append(_call(name, chans, params,
                                 intermediate=list(range(ch, ch + w))))
        ch += w
    _p, got, want = _fused_and_eager(
        _factory(builder, final_calls, FINAL, 5), mid)
    _same(got, want)


def test_sort_result_over_several_output_pages():
    """Over a hundred groups into 32-slot output pages: the sort builder's
    result is cut into pages at host offsets after its one read."""
    calls = [_call("sum", [1], ()), _call("min", [6], ()),
             _call("avg", [3], ())]
    pages = _pages(300, seed=7)
    out, got, want = _fused_and_eager(
        _factory("sort", calls, SINGLE, 300, out_cap=32), pages)
    assert len(out) > 2 and all(p.capacity == 32 for p in out)
    assert len(got) > 64
    _same(got, want)


# ------------------------------------------------------------- (c) counters

def _counters():
    return (METRICS.counter_value("agg.result.fused"),
            METRICS.counter_value("agg.result.host_reads"))


@pytest.mark.parametrize("query,fused,reads", [(1, 1, 0), (6, 1, 0), (3, 0, 1)],
                         ids=["q1", "q6", "q3"])
def test_result_counters(runner, query, fused, reads):
    """A warm Q1 and Q6 finish their result in one program and read nothing
    back; Q3's sort builder reads its count and key NULL flags once (four
    reads before)."""
    runner.execute(QUERIES[query])
    f0, r0 = _counters()
    runner.execute(QUERIES[query])
    f1, r1 = _counters()
    assert (f1 - f0, r1 - r0) == (fused, reads)


def test_result_counters_are_served(runner):
    from presto_tpu.server.http_server import PrestoTpuServer

    runner.execute(QUERIES[6])
    server = PrestoTpuServer(port=0)
    server.start()
    try:
        served = json.loads(urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/metrics/agg.result",
            headers={"X-Presto-User": "test"}), timeout=10).read())
    finally:
        server.stop()
    text = json.dumps(served)
    assert "agg.result.fused" in text and "agg.result.host_reads" in text
