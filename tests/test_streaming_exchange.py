"""Streaming mesh exchange (parallel/streaming_exchange.py).

Differential: the mesh == the single-chip LocalQueryRunner over the same
catalog, on every exchange kind — REPARTITION, BROADCAST, GATHER, MERGE
(global order, dict-encoded columns). Mechanism: overflow carry-over under total key skew,
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
    import jax.numpy as jnp

    from presto_tpu.block import Block, Page
    from presto_tpu.types import BIGINT

    return Page((Block(BIGINT, jnp.full((n,), fill, dtype=jnp.int64)),),
                jnp.ones((n,), dtype=jnp.bool_))


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
