"""Streaming mesh exchange (parallel/streaming_exchange.py).

Differential: the mesh == the single-chip LocalQueryRunner over the same
catalog, on every exchange kind — REPARTITION, BROADCAST, GATHER, MERGE
(global order, dict-encoded columns). Mechanism: the send chunk derived from
the fragment's page and the in-flight bound (PR 37), pages that fit a chunk never
split, overflow carry-over under total key skew,
producer backpressure on the in-flight byte budget (no deadlock with a slow
consumer), clean close-while-blocked teardown, stats plumbing.

Most SQL differentials run on a 2-device mesh: the collective programs are
per-(mesh, shape), so the small mesh keeps compile cost out of tier-1; skew
needs out_cap < chunk (only true for W >= 4), so it uses the 8-device mesh.
"""
import threading
import time

import numpy as np
import pytest

from presto_tpu.metadata import Session
from presto_tpu.parallel.mesh import MeshContext
from presto_tpu.parallel.runner import DistributedQueryRunner
from presto_tpu.runner import LocalQueryRunner
from presto_tpu.utils.testing import assert_rows_equal


@pytest.fixture(scope="module")
def mesh2(eight_devices):
    return MeshContext(eight_devices[:2])


def _session(**props):
    return Session(catalog="tpch", schema="tiny", properties=props)


@pytest.fixture(scope="module")
def streaming(mesh2):
    return DistributedQueryRunner(mesh2, session=_session())


@pytest.fixture(scope="module")
def local():
    """The reference: one chip, no fragment, no exchange."""
    return LocalQueryRunner(session=_session())


def check(streaming, local, sql, ordered=True):
    s = streaming.execute(sql)
    assert_rows_equal(s.rows, local.execute(sql).rows, ordered=ordered)
    assert (s.stats or {}).get("exchange", {}).get("chunks", 0) > 0
    return s


# ------------------------------------------------------------- differential

def test_repartition_group_by(streaming, local):
    s = check(streaming, local,
              "select o_custkey % 7, count(*), sum(o_totalprice) "
              "from orders group by 1 order by 1")
    ex = s.stats["exchange"]
    assert ex["chunks"] >= 1
    assert ex["exchanges"] >= 1


def test_gather_global_agg(streaming, local):
    check(streaming, local,
          "select count(*), sum(o_totalprice), min(o_orderdate) from orders")


def test_broadcast_join(streaming, local):
    check(streaming, local,
          "select n_name, r_name from nation join region "
          "on n_regionkey = r_regionkey order by n_name")


def test_merge_global_order(streaming, local):
    # MERGE (range) exchange: worker-order concatenation must equal the
    # global order even though rows now arrive in per-chunk interleavings
    check(streaming, local,
          "select c_custkey, c_acctbal from customer "
          "order by c_acctbal, c_custkey")


def test_merge_desc_dict_encoded(streaming, local):
    # primary sort key is a dict-encoded varchar: range routing goes through
    # the dictionary's sort keys, chunk by chunk
    check(streaming, local,
          "select c_name, c_custkey from customer "
          "order by c_name desc, c_custkey")


def test_dict_encoded_agg_outputs(streaming, local):
    # min/max over dict columns carry dictionary codes through the exchange
    check(streaming, local,
          "select n_regionkey, min(n_name), max(n_name) from nation "
          "group by n_regionkey order by n_regionkey")


def test_join_repartitioned(local, mesh2):
    forced = DistributedQueryRunner(
        mesh2, session=_session(join_distribution_type="PARTITIONED"))
    check(forced, local,
          "select c_name, o_orderkey from customer join orders "
          "on c_custkey = o_custkey order by o_orderkey limit 50")


def test_small_chunks_match(mesh2, local):
    # tiny chunks force many dispatches per exchange (and leftover splits of
    # single pages) — results must not depend on the chunking
    s = DistributedQueryRunner(
        mesh2, session=_session(exchange_chunk_rows=128))
    r = check(s, local,
              "select o_orderpriority, count(*) from orders "
              "group by o_orderpriority order by 1")
    assert r.stats["exchange"]["chunks"] > 1


# ---------------------------------------------------- skew / carry-over

def test_skew_carryover(eight_devices, local):
    # EVERY probe row keys to one partition (a partitioned join on a
    # constant key — RAW rows cross the exchange, unlike a group-by whose
    # partial agg collapses the skew before routing): each 512-row chunk
    # overflows its 128-slot peer slice and the overflow must carry into
    # later dispatches instead of dropping.
    # skew_aware_exchange=False: this test exercises the CARRY correctness
    # backstop; with spreading on, hot rows never overflow a peer slice
    # (that path is covered by test_skew_spreads_hot_key below)
    mesh = MeshContext(eight_devices[:8])
    sql = ("select count(*) from (select o_custkey * 0 as k from orders) o "
           "join (select r_regionkey * 0 as k from region "
           "where r_regionkey = 0) r on o.k = r.k")
    s = DistributedQueryRunner(
        mesh, session=_session(exchange_chunk_rows=512,
                               skew_aware_exchange=False,
                               join_distribution_type="PARTITIONED"))
    rs = s.execute(sql)
    assert_rows_equal(rs.rows, local.execute(sql).rows)
    assert rs.stats["exchange"]["carry_rows"] > 0, \
        "total skew must exercise the overflow carry-over path"


# -------------------------------------------------- skew-aware spreading

SKEWED_JOIN = (
    # ~99% of the probe rows share key 7; the build side (customer) is
    # unique per key — the probe exchange must detect the heavy hitter,
    # spray its rows round-robin, and the build exchange must replicate
    # key 7's single build row to every partition
    "select count(*), sum(o.k) from "
    "(select case when o_orderkey % 100 = 0 then o_custkey else 7 end as k "
    " from orders) o "
    "join (select c_custkey as k from customer) c on o.k = c.k")


def _skewed_runner(eight_devices, n=4, **props):
    mesh = MeshContext(eight_devices[:n])
    return DistributedQueryRunner(
        mesh, session=_session(exchange_chunk_rows=512,
                               join_distribution_type="PARTITIONED",
                               **props))


def test_skew_spreads_hot_key(eight_devices):
    # acceptance: the 99%-one-key partitioned join spreads the hot key
    # across >= 2 partitions (per-partition exchange stats) and stays
    # row-identical to the non-skew-aware path
    oracle = _skewed_runner(eight_devices,
                            skew_aware_exchange=False).execute(SKEWED_JOIN)
    skewed = _skewed_runner(eight_devices).execute(SKEWED_JOIN)
    assert_rows_equal(skewed.rows, oracle.rows)
    per_ex = {e.get("skew_role"): e
              for e in skewed.stats["exchange"]["per_exchange"]}
    probe = per_ex.get("probe")
    build = per_ex.get("build")
    assert probe is not None and build is not None, per_ex.keys()
    assert probe["hot_keys"] >= 1, probe
    # the heavy side's rows landed on >= 2 partitions, and no partition
    # holds more than ~half the stream (the old behavior: ~99% on one)
    parts = probe["partition_rows"]
    assert sum(p > 0 for p in parts) >= 2, parts
    assert max(parts) < 0.6 * sum(parts), parts
    # the peer replicated the hot key's build rows to every partition
    assert build["replicated_rows"] > 0, build
    # the oracle run concentrated the same stream on one partition
    op = {e.get("fragment"): e
          for e in oracle.stats["exchange"]["per_exchange"]}
    oparts = op[probe["fragment"]]["partition_rows"]
    assert max(oparts) > 0.9 * sum(oparts), oparts


def test_skew_build_side_hot(eight_devices):
    # the mirrored case: duplicate hot keys on the BUILD side split, and
    # the probe side replicates its matching rows
    sql = ("select count(*) from "
           "(select o_custkey as k from orders where o_custkey <= 50) o "
           "join (select case when c_custkey % 50 = 0 then c_custkey "
           "             else 13 end as k from customer) c on o.k = c.k")
    oracle = _skewed_runner(eight_devices,
                            skew_aware_exchange=False).execute(sql)
    skewed = _skewed_runner(eight_devices).execute(sql)
    assert_rows_equal(skewed.rows, oracle.rows)
    per_ex = {e.get("skew_role"): e
              for e in skewed.stats["exchange"]["per_exchange"]}
    assert per_ex["build"]["hot_keys"] >= 1, per_ex["build"]
    parts = per_ex["build"]["partition_rows"]
    assert sum(p > 0 for p in parts) >= 2, parts


def test_skew_off_knob(eight_devices):
    # skew_aware_exchange=False must leave every exchange unwired
    r = _skewed_runner(eight_devices,
                       skew_aware_exchange=False).execute(SKEWED_JOIN)
    for e in r.stats["exchange"]["per_exchange"]:
        assert "skew_role" not in e, e


def test_skew_declines_when_downstream_needs_copartitioning(eight_devices):
    # GROUP BY on the join key AFTER the join: the planner elides the
    # re-exchange (join output is "partitioned" on k), so spraying the hot
    # key would split one group across partitions and emit duplicate group
    # rows. _skew_pair_safe must DECLINE the wiring (a non-PARTIAL agg
    # downstream of the probe) — concentrated but correct, and the skew
    # stats must show no roles were attached.
    sql = (SKEWED_JOIN.replace("select count(*), sum(o.k)",
                               "select o.k, count(*)")
           + " group by o.k order by 2 desc, 1 limit 5")
    oracle = _skewed_runner(eight_devices,
                            skew_aware_exchange=False).execute(sql)
    skewed = _skewed_runner(eight_devices).execute(sql)
    assert_rows_equal(skewed.rows, oracle.rows)
    assert not any("skew_role" in e
                   for e in skewed.stats["exchange"]["per_exchange"]), \
        skewed.stats["exchange"]["per_exchange"]


# ------------------------------------------------ the derived send chunk

@pytest.mark.parametrize("page_rows,row_bytes,workers,inflight,override,want", [
    # at the floor: a short page still sends MIN_CHUNK_ROWS-row chunks
    (64, 8, 4, 1 << 28, 0, 1 << 12),
    (4096, 8, 4, 1 << 28, 0, 1 << 12),
    # at the page: the pow2 of the producing fragment's page
    (1 << 18, 28, 4, 1 << 28, 0, 1 << 18),
    (200_000, 28, 4, 1 << 28, 0, 1 << 18),
    # capped by the bound: two chunks a side must fit exchange_inflight_bytes
    # (2 x 40 B x 4 workers x 2^19 rows = 160 MiB fits 256 MiB, 2^20 not)
    (1 << 22, 40, 4, 1 << 28, 0, 1 << 19),
    (1 << 20, 28, 4, 1 << 28, 0, 1 << 20),
    (1 << 20, 28, 8, 1 << 28, 0, 1 << 19),
    (1 << 18, 8, 2, 1 << 20, 0, 1 << 15),
    # a bound under the floor does not go under it
    (1 << 18, 8, 2, 1 << 10, 0, 1 << 12),
    # exchange_chunk_rows set: the override wins (pow2-rounded, floor 64)
    (1 << 18, 28, 4, 1 << 28, 256, 256),
    (1 << 18, 28, 4, 1 << 28, 300, 512),
    (64, 8, 4, 1 << 10, 1, 64),
])
def test_derive_chunk_rows(page_rows, row_bytes, workers, inflight, override,
                           want):
    from presto_tpu.parallel.streaming_exchange import derive_chunk_rows

    assert derive_chunk_rows(page_rows, row_bytes, workers, inflight,
                             override) == want


def _keyed_page(keys, live=None):
    import jax.numpy as jnp

    from presto_tpu.block import Block, Page
    from presto_tpu.types import BIGINT

    keys = np.asarray(keys, dtype=np.int64)
    mask = np.ones(len(keys), dtype=bool) if live is None else \
        np.asarray(live, dtype=bool)
    return Page((Block(BIGINT, jnp.asarray(keys)),), jnp.asarray(mask))


class _PeakMemory:
    """Stands where the query's memory context stands: keeps the largest
    reservation the exchange ever published."""

    def __init__(self):
        self.peak = 0

    def set_bytes(self, n):
        self.peak = max(self.peak, n)

    def close(self):
        pass


def _run_exchange(mesh, kind, pages_by_worker, lengths=None, **kw):
    """Feed the pages as one well-behaved producer a worker would (an add
    only while the exchange has capacity), drain every consumer, and return
    (the exchange, {consumer: [values received]}); `lengths` (a dict) takes
    {consumer: [length of each page received]}."""
    from presto_tpu.parallel.streaming_exchange import (ExchangeStatsBook,
                                                        StreamingExchange)
    from presto_tpu.sql.planner.plan import MERGE, REPARTITION
    from presto_tpu.types import BIGINT

    ex = StreamingExchange(
        mesh, 7, kind, [0] if kind == REPARTITION else None, [BIGINT],
        [None], orderings=((0, False, False),) if kind == MERGE else None,
        book=ExchangeStatsBook(), **kw)
    got = {w: [] for w in range(mesh.n_workers)}
    failed = []

    def consume(w):
        buf = ex.out_buffer(w)
        try:
            while True:
                page = buf.poll()
                if page is not None:
                    if lengths is not None:
                        lengths.setdefault(w, []).append(page.capacity)
                    vals = np.asarray(page.blocks[0].data)
                    got[w].extend(vals[np.asarray(page.mask)].tolist())
                elif buf.is_done(None):
                    return
                else:
                    time.sleep(0.002)
        except BaseException as e:  # noqa: BLE001 - surfaced by the caller
            failed.append(e)

    ex.start(n_producers=1)
    consumers = [threading.Thread(target=consume, args=(w,), daemon=True)
                 for w in got]
    for t in consumers:
        t.start()
    try:
        for w, pages in enumerate(pages_by_worker):
            for page in pages:
                deadline = time.time() + 30
                while not ex.has_capacity() and time.time() < deadline:
                    time.sleep(0.002)
                assert ex.has_capacity(), "the producer stayed parked"
                ex.add_page(w, page)
        ex.producer_finished()
        for t in consumers:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in consumers), \
            "the pump did not end the stream: a consumer still waits"
        assert not failed, failed
    finally:
        ex.close()
    return ex, got


PAGE = 1 << 12   # the derived chunk's floor: such a page is one chunk's worth


@pytest.mark.parametrize("kind", ["REPARTITION", "BROADCAST", "GATHER",
                                  "MERGE"])
def test_pages_no_longer_than_the_chunk_are_never_split(eight_devices, kind):
    """Every size derived: the chunk is the pow2 of the page the fragment
    was planned with, a page that
    does not fit what is left of a chunk opens the next one, so no fill
    leaves a leftover and a fill is one program a live page sent and one a
    shard received."""
    from presto_tpu.sql.planner import plan

    mesh = MeshContext(eight_devices[:4])
    W = mesh.n_workers
    rng = np.random.default_rng(37)
    pages, sent, live_pages = [], [], 0
    for w in range(W):
        mine = []
        for i in range(3):
            keys = rng.integers(0, 1 << 40, PAGE)
            live = rng.random(PAGE) < (0.7, 0.5, 0.0)[i]   # the third: dead
            mine.append(_keyed_page(keys, live))
            sent.extend(keys[live].tolist())
            live_pages += bool(live.any())
        pages.append(mine)
    ex, got = _run_exchange(mesh, getattr(plan, kind), pages,
                            page_capacity=PAGE)
    st = ex.stats
    assert st["chunk_rows"] == PAGE
    assert st["refills"] == 0
    assert st["carry_rows"] == 0
    # 0.7 + 0.5 of a page do not fit one chunk: the second page opened its own
    assert st["chunks"] == 2
    assert st["rows_in"] == len(sent)
    copies = W if kind == "BROADCAST" else 1
    receivers = 1 if kind == "GATHER" else W
    assert st["rows_out"] == copies * len(sent)
    assert st["fills"] == live_pages + st["chunks"] * receivers
    everything = sorted(v for vals in got.values() for v in vals)
    assert everything == sorted(sent * copies)
    if kind == "GATHER":
        assert not any(got[w] for w in range(1, W))
    if kind == "MERGE":     # worker order is value order
        tops = [max(got[w]) for w in range(W) if got[w]]
        lows = [min(got[w]) for w in range(W) if got[w]]
        assert all(t <= lo for t, lo in zip(tops, lows[1:]))
    snap = ex.book.snapshot()
    assert snap["fills"] == st["fills"] and snap["refills"] == 0


@pytest.mark.parametrize("first", ["short_page_first", "long_page_first"])
def test_the_chunk_does_not_depend_on_which_page_comes_first(eight_devices,
                                                             first):
    """Splits of uneven length hand over pages of different pow2 lengths and
    which reaches the pump first is timing: the chunk is fixed when the
    exchange is built, so either order runs the same shapes, splits no page
    and builds the collective once."""
    from presto_tpu.sql.planner.plan import REPARTITION

    mesh = MeshContext(eight_devices[:4])
    rng = np.random.default_rng(5)
    short, long_ = rng.integers(0, 1 << 40, 512), \
        rng.integers(0, 1 << 40, PAGE)
    order = (short, long_) if first == "short_page_first" else (long_, short)
    pages = [[_keyed_page(v) for v in order]] + [[] for _ in range(3)]
    ex, got = _run_exchange(mesh, REPARTITION, pages, page_capacity=PAGE)
    st = ex.stats
    assert (st["chunk_rows"], st["out_cap"]) == (PAGE, PAGE // 2)
    assert st["refills"] == 0 and st["compiles"] <= 1
    assert st["rows_in"] == st["rows_out"] == PAGE + 512
    assert sorted(v for vals in got.values() for v in vals) == \
        sorted(short.tolist() + long_.tolist())


@pytest.mark.parametrize("kind,rows,want", [
    # the whole stream fits one receive page: cut to the pow2 of its rows
    ("GATHER", 40, [1 << 12]),
    ("GATHER", 5000, [1 << 13]),
    ("REPARTITION", 40, [1 << 12]),
    # more than a page: the pages keep the length the fragment was planned at
    ("GATHER", 3 * (1 << 14), [1 << 14, 1 << 14, 1 << 14]),
    # MERGE's splitters sample the first chunk: its pages keep their length
    ("MERGE", 40, [1 << 14]),
])
def test_a_stream_of_one_page_is_cut_to_its_rows(eight_devices, kind, rows,
                                                 want):
    """The chunk comes from the fragment's page, not from the rows that came:
    a consumer whose whole stream is one page still traces a shape of its
    input's size, and a longer stream the planned one."""
    from presto_tpu.sql.planner import plan

    mesh = MeshContext(eight_devices[:4])
    keys = np.arange(rows) * 4
    pages = [[_keyed_page(keys[i:i + (1 << 14)])
              for i in range(0, rows, 1 << 14)]] + [[] for _ in range(3)]
    lengths = {}
    ex, got = _run_exchange(mesh, getattr(plan, kind), pages,
                            lengths=lengths, page_capacity=1 << 14)
    assert ex.stats["chunk_rows"] == 1 << 14
    assert sorted(v for vals in got.values() for v in vals) == keys.tolist()
    busiest = max(lengths, key=lambda w: len(got[w]))
    assert len(got[busiest]) == rows or kind != "GATHER"
    assert lengths[busiest] == want, lengths


@pytest.mark.parametrize("keys", ["spread", "one_key"])
def test_a_page_longer_than_its_chunk_arrives_whole(eight_devices, keys):
    """The override path: 1024-row pages into 256-row chunks go through the
    brim fill and its leftover; with every row on one key each chunk
    overflows its 128-slot peer slice and the rest rides the carry."""
    from presto_tpu.sql.planner.plan import REPARTITION

    mesh = MeshContext(eight_devices[:4])
    rng = np.random.default_rng(3)
    pages, sent = [], []
    for w in range(4):
        vals = rng.integers(0, 1 << 40, 1024) if keys == "spread" \
            else np.full(1024, 11)
        live = rng.random(1024) < 0.9
        pages.append([_keyed_page(vals, live)])
        sent.extend(vals[live].tolist())
    ex, got = _run_exchange(mesh, REPARTITION, pages, chunk_rows=256)
    st = ex.stats
    assert (st["chunk_rows"], st["out_cap"]) == (256, 128)
    assert st["refills"] >= 3 * 4         # a page is four chunks' worth
    assert st["fills"] > st["refills"]
    assert st["rows_in"] == st["rows_out"] == len(sent)
    assert sorted(v for vals in got.values() for v in vals) == sorted(sent)
    if keys == "one_key":
        assert st["carry_rows"] > 0
        assert sorted(len(v) for v in got.values())[:3] == [0, 0, 0]
    else:
        assert all(got.values())


def test_a_page_past_a_consumer_queues_bound_is_delivered(mesh2):
    """A receive page whose bytes pass its consumer queue's bound still goes
    in (an empty queue admits any page) and the pump ends; the reservation
    the exchange publishes never passes exchange_inflight_bytes by more than
    one chunk."""
    from presto_tpu.ops.scan_pipeline import page_nbytes
    from presto_tpu.sql.planner.plan import GATHER

    rows, inflight = 1 << 15, 1 << 20
    pages = [[_keyed_page(np.arange(rows) + (w * 8 + i) * rows)
              for i in range(6)] for w in range(2)]
    one_page = page_nbytes(pages[0][0])
    memory = _PeakMemory()
    ex, got = _run_exchange(mesh2, GATHER, pages, inflight_bytes=inflight,
                            page_capacity=rows, memory=memory)
    queue_bound = ex.out_buffer(0).max_bytes
    assert one_page > queue_bound, (one_page, queue_bound)
    assert ex.stats["chunk_rows"] == rows
    assert ex.stats["refills"] == 0
    assert ex.stats["rows_out"] == 12 * rows
    assert sorted(got[0]) == sorted(
        v for w in range(2) for p in pages[w]
        for v in np.asarray(p.blocks[0].data).tolist())
    one_chunk = 2 * one_page            # a chunk is a page a worker here
    assert 0 < memory.peak <= inflight + one_chunk, memory.peak


# ------------------------------------------------- backpressure / teardown

def _exchange(mesh, **kw):
    from presto_tpu.parallel.streaming_exchange import (ExchangeStatsBook,
                                                        StreamingExchange)
    from presto_tpu.sql.planner.plan import GATHER
    from presto_tpu.types import BIGINT

    defaults = dict(chunk_rows=64, inflight_bytes=1 << 20,
                    page_capacity=256, book=ExchangeStatsBook())
    defaults.update(kw)
    return StreamingExchange(mesh, 99, GATHER, None, [BIGINT], [None],
                             **defaults)


def _page(n=256, fill=1):
    return _keyed_page(np.full(n, fill))


def test_backpressure_blocks_and_releases(mesh2):
    ex = _exchange(mesh2, inflight_bytes=2048)
    ex.start(n_producers=1)
    try:
        ex.add_page(0, _page())
        # staged + undelivered bytes exceed the budget: producers must park
        deadline = time.time() + 10
        while ex.has_capacity() and time.time() < deadline:
            time.sleep(0.01)
        assert not ex.has_capacity()
        ex.producer_finished()
        # a consumer draining worker 0 releases the budget and unblocks
        buf = ex.out_buffer(0)
        got = 0
        deadline = time.time() + 20
        while time.time() < deadline:
            page = buf.poll()
            if page is not None:
                got += int(np.asarray(page.mask).sum())
            elif buf.is_done(None):
                break
            else:
                time.sleep(0.005)
        assert got == 256
        deadline = time.time() + 10
        while not ex.has_capacity() and time.time() < deadline:
            time.sleep(0.01)
        assert ex.has_capacity()
    finally:
        ex.close()


def test_no_deadlock_with_slow_consumer(mesh2, local):
    # a byte budget far below the intermediate volume: producers park, the
    # pump trickles chunks, the consumer drains — and the query still
    # completes with the reference's rows
    s = DistributedQueryRunner(
        mesh2, session=_session(exchange_chunk_rows=128,
                                exchange_inflight_bytes=1 << 14))
    check(s, local,
          "select o_orderstatus, count(*) from orders "
          "group by o_orderstatus order by 1")


def test_close_while_blocked(mesh2):
    ex = _exchange(mesh2, inflight_bytes=1)
    ex.start(n_producers=1)
    ex.add_page(0, _page())
    # producer view: budget exhausted
    deadline = time.time() + 10
    while ex.has_capacity() and time.time() < deadline:
        time.sleep(0.01)
    # consumer blocked mid-stream on another worker's empty queue
    poll_error = {}

    def consume():
        buf = ex.out_buffer(1)
        try:
            while True:
                if buf.poll() is None:
                    if buf.is_done(None):
                        poll_error["done"] = True
                        return
                    time.sleep(0.005)
        except RuntimeError as e:
            poll_error["error"] = e

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    ex.close()  # tear down with the producer parked and a consumer blocked
    t.join(timeout=10)
    assert not t.is_alive(), "blocked consumer must wake on close"
    assert "error" in poll_error, \
        "a consumer cut off mid-stream must fail loudly, not see EOF"
    with pytest.raises(RuntimeError):
        ex.add_page(0, _page())
    # idempotent
    ex.close()


def test_limit_abandons_undrained_stream(mesh2, local):
    # a satisfied LIMIT above the exchange closes its consumer with rows
    # still buffered and producers still streaming under a tiny byte budget
    # — the abandoned queue must discard instead of wedging the pump (and,
    # through the budget, every producer driver)
    s = DistributedQueryRunner(
        mesh2, session=_session(exchange_chunk_rows=128,
                                exchange_inflight_bytes=1 << 14))
    check(s, local,
          "select o_orderkey from orders order by o_orderkey limit 7")


def test_abandoned_buffer_discards_puts(mesh2):
    from presto_tpu.ops.local_exchange import LocalExchangeBuffer

    buf = LocalExchangeBuffer(n_producers=1, max_bytes=1)
    buf.put(_page())          # fills past the bound
    buf.abandon()
    buf.put(_page(), block=True)  # would deadlock without the abandon
    assert buf.poll() is None and buf.buffered_bytes() == 0


def test_error_poisons_consumers(mesh2):
    ex = _exchange(mesh2)
    ex.start(n_producers=1)
    boom = ValueError("producer exploded")
    ex.close(error=boom)
    with pytest.raises(RuntimeError):
        ex.out_buffer(0).poll()


# ------------------------------------------------------------------ stats

def test_stats_and_metrics_plumbing(mesh2):
    from presto_tpu.utils.metrics import METRICS

    s = DistributedQueryRunner(mesh2, session=_session())
    before = METRICS.counter_value("exchange.chunks")
    r = s.execute("select n_regionkey, count(*) from nation "
                  "group by n_regionkey order by 1")
    ex = r.stats["exchange"]
    assert "mode" not in ex  # one data plane: nothing to tell apart
    assert ex["exchanges"] >= 1
    assert ex["chunks"] >= 1
    assert "per_exchange" in ex
    entry = ex["per_exchange"][0]
    for key in ("fragment", "kind", "chunk_rows", "out_cap", "chunks",
                "dispatch_s", "overlap_s", "stall_s", "compiles"):
        assert key in entry, key
    assert METRICS.counter_value("exchange.chunks") > before
    # compile discipline: at most one collective program per (kind, shape)
    # per query — warm caches can make it zero, never more than exchanges
    assert ex.get("collective_compiles", 0) <= ex["exchanges"]


# ------------------------------------------------------------- pump states
#
# Every pump carries ONE state at every moment of its life (PUMP_STATES,
# StreamingExchange._enter): the states' seconds add up to `pump_s`, under
# both schedulers, after an early close too, and with no recorder bound
# entering a state builds no span.

STATES_SQL = ("select c_mktsegment, count(*), sum(o_totalprice) "
              "from customer join orders on c_custkey = o_custkey "
              "group by c_mktsegment order by 1")


def _assert_states_add_up(entry):
    from presto_tpu.parallel.streaming_exchange import PUMP_STATES

    assert tuple(entry["state_s"]) == PUMP_STATES, entry
    assert all(v >= 0 for v in entry["state_s"].values()), entry
    total = sum(entry["state_s"].values())
    assert abs(total - entry["pump_s"]) <= max(0.02 * entry["pump_s"],
                                               0.002), entry
    assert entry["pump_s"] > 0
    # the lock wait is a part OF the dispatch (rounded to 1 us each)
    assert 0 <= entry["lock_wait_s"] <= entry["dispatch_s"] + 2e-6, entry
    assert entry["dispatch_s"] == entry["state_s"]["dispatch"]
    assert entry["stall_s"] == entry["state_s"]["starved"]


@pytest.mark.parametrize("shared_pools", [True, False],
                         ids=["pool", "dedicated_thread"])
def test_pump_states_add_up_to_the_pumps_life(eight_devices, local,
                                              shared_pools):
    from presto_tpu.parallel.streaming_exchange import PUMP_STATES
    from presto_tpu.utils.metrics import METRICS

    # the name a state's seconds reach /v1/metrics and the query's stats by
    keys = {"stall_s" if s == "starved" else f"{s}_s": s for s in PUMP_STATES}
    names = ["exchange." + k for k in list(keys) + ["lock_wait_s"]]
    runner = DistributedQueryRunner(
        MeshContext(eight_devices[:4]),
        session=_session(join_distribution_type="PARTITIONED",
                         shared_pools=shared_pools))
    # on its own thread a pump is never queued for a worker: what it reads
    # there is its thread's start and the resume after each yield, some
    # hundred microseconds. Under six test workers the machine held single
    # thread starts up past 1 ms in three attempts running, so the bound is
    # held by the promptest pump of an attempt, and in one of three
    for _attempt in range(3):
        before = {n: METRICS.counter_value(n) for n in names}
        r = check(runner, local, STATES_SQL)
        ex = r.stats["exchange"]
        assert len(ex["per_exchange"]) == ex["exchanges"] >= 3
        for entry in ex["per_exchange"]:
            _assert_states_add_up(entry)
        # the query's seconds are its exchanges', under the names they reach
        # /v1/metrics by (one count_many a query)
        for n in names:
            key = n[len("exchange."):]
            per = [e["state_s"][keys[key]] if key in keys else e[key]
                   for e in ex["per_exchange"]]
            assert ex[key] == pytest.approx(sum(per), abs=1e-5), key
            assert METRICS.counter_value(n) - before[n] == \
                pytest.approx(ex[key], abs=1e-5), n
        assert ex["fill_s"] > 0 and ex["deliver_s"] > 0 and ex["sync_s"] > 0
        queued = min(e["state_s"]["queued"] for e in ex["per_exchange"])
        if shared_pools or queued < 0.001:
            break
    assert shared_pools or queued < 0.001, ex["per_exchange"]


@pytest.mark.parametrize("pool_key", [None, "states-closed-early"],
                         ids=["dedicated_thread", "pool"])
def test_a_stream_closed_early_still_publishes_states_that_add_up(mesh2,
                                                                  pool_key):
    ex = _exchange(mesh2, pool_key=pool_key)
    ex.start(n_producers=1)
    ex.add_page(0, _page())
    time.sleep(0.05)       # the pump is somewhere in the page, or starved
    ex.close(error=ValueError("producer exploded"))
    assert ex._pump_done.is_set()
    (entry,) = ex.book.per_exchange
    _assert_states_add_up(entry)
    # nothing is left open: the clock has stopped and the span is closed
    assert ex._state is None and ex._span is None


@pytest.mark.parametrize("shared_pools", [True, False],
                         ids=["pool", "dedicated_thread"])
def test_with_no_recorder_a_pump_builds_no_span(mesh2, local, monkeypatch,
                                                shared_pools):
    from presto_tpu.utils import trace

    built = []

    class Counted(trace._Span):
        __slots__ = ()

        def __init__(self, rec, cat, name, args, min_ns=0):
            built.append((cat, name, args))
            super().__init__(rec, cat, name, args, min_ns)

    monkeypatch.setattr(trace, "_Span", Counted)
    sql = ("select o_orderstatus, count(*) from orders "
           "group by o_orderstatus order by 1")

    def pump_spans(**props):
        del built[:]
        r = check(DistributedQueryRunner(
            mesh2, session=_session(shared_pools=shared_pools, **props)),
            local, sql)
        for entry in r.stats["exchange"]["per_exchange"]:
            _assert_states_add_up(entry)      # timed all the same
        return [(name.split(" ")[0], args) for cat, name, args in built
                if cat == trace.EXCHANGE]

    # the always-on black-box ring: every state but `queued` is a span,
    # and says what it waited for or worked on
    spans = pump_spans()
    assert {"pump_fill", "pump_sync", "chunk_dispatch",
            "chunk_deliver"} <= {name for name, _args in spans}, spans
    assert {args["of"] for name, args in spans if name == "pump_sync"} <= \
        {"live", "carry", "deliver", "hot", "splitters"}
    # the ORDER BY's MERGE froze its splitters once, inside such a sync
    (froze,) = [args for name, args in spans if name == "merge_splitters"]
    assert froze["splitters"] == mesh2.n_workers - 1 and froze["rows"] >= 1
    assert [args for name, args in spans
            if name == "pump_sync" and args["of"] == "splitters"]
    assert all(args["chunk"] >= 1 for name, args in spans
               if name == "chunk_deliver")
    assert pump_spans(query_blackbox=False) == []
