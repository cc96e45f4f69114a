"""Multi-tenant serving: shared worker pools, unified memory accounting,
and concurrent-load correctness.

The serving contract (ROADMAP "concurrent query serving"):
- N concurrent queries share the process-wide scan/exchange pools (O(pool)
  threads, round-robin fairness per query) and produce rows identical to
  their serial runs — with `shared_pools=False` (per-query stage threads)
  as the differential oracle;
- scan prefetch and exchange in-flight bytes reserve in the per-query
  memory accounting, so the pool (and through it admission + the OOM
  killer) sees the WHOLE footprint, and an over-budget query is killed
  (limit exception), not wedged;
- the kernel cache is single-flight under concurrent misses.
"""
import threading
import time

import numpy as np
import pytest

from presto_tpu.exec.shared_pools import (SCAN_POOL, SharedWorkerPool,
                                          next_query_key)
from presto_tpu.memory import (ExceededMemoryLimitException, MemoryPool,
                               QueryContextMemory)
from presto_tpu.metadata import Session
from presto_tpu.models.tpch_sql import QUERIES
from presto_tpu.ops.scan_pipeline import HostChunk, ScanPipeline
from presto_tpu.runner import LocalQueryRunner
from presto_tpu.types import BIGINT
from presto_tpu.utils.testing import assert_no_residue

MIX = [1, 3, 6]


# ---------------------------------------------------------------------------
# shared pool unit behavior
# ---------------------------------------------------------------------------

class TestSharedWorkerPool:
    def test_round_robin_fairness_across_clients(self):
        """Two clients' steps interleave: neither drains fully before the
        other starts (single-worker pool makes the order deterministic
        enough to assert interleaving)."""
        pool = SharedWorkerPool("t-fair", 1)
        order = []
        done = threading.Event()

        def gen(tag, n):
            for i in range(n):
                order.append(tag)
                yield "again"
            if tag == "b":
                done.set()

        a = pool.client("qa")
        b = pool.client("qb")
        a.submit(gen("a", 20))
        b.submit(gen("b", 20))
        assert done.wait(timeout=10)
        assert a.wait_idle(10) and b.wait_idle(10)
        a.release()
        b.release()
        # strict alternation from the point both are runnable: the first 10
        # entries must contain both tags several times (no monopolization)
        head = order[:10]
        assert head.count("a") >= 3 and head.count("b") >= 3, order[:10]

    def test_thread_count_bounded_across_many_clients(self):
        """50 clients x 2 generators cost at most `size` threads."""
        pool = SharedWorkerPool("t-bound", 3)
        clients = [pool.client(f"q{i}") for i in range(50)]
        for c in clients:
            for _ in range(2):
                c.submit(iter([]))  # empty gen: finishes on first step
        for c in clients:
            assert c.wait_idle(10)
            c.release()
        assert pool.stats()["threads"] <= 3
        # released + drained clients are dropped (no growth with history)
        assert pool.stats()["clients"] == 0

    def test_client_refcounted_by_key(self):
        pool = SharedWorkerPool("t-ref", 1)
        c1 = pool.client("q")
        c2 = pool.client("q")
        assert c1 is c2
        c1.release()
        assert pool.stats()["clients"] == 1  # second ref still held
        c2.release()
        assert pool.stats()["clients"] == 0


# ---------------------------------------------------------------------------
# scan pipeline on the shared pool
# ---------------------------------------------------------------------------

class _SplitSource:
    """Deterministic split-parallel source (the dryrun's fixture shape)."""

    def __init__(self, n_readers=4, chunks=4, rows=64):
        self.spec = [[np.arange(r * chunks * rows + c * rows,
                                r * chunks * rows + (c + 1) * rows,
                                dtype=np.int64)
                      for c in range(chunks)]
                     for r in range(n_readers)]

    def close(self):
        pass

    def split_readers(self, target_rows):
        def reader(i):
            def read():
                for arr in self.spec[i]:
                    yield HostChunk.build([arr], [None], [BIGINT], [None])
            return read
        return [reader(i) for i in range(len(self.spec))]


def _drain_rows(pipe: ScanPipeline):
    got = []
    deadline = time.monotonic() + 60
    while True:
        page = pipe.next()
        if page is None:
            break
        got.append(np.asarray(page.blocks[0].data)[np.asarray(page.mask)])
        assert time.monotonic() < deadline, "the pipeline never ended"
    pipe.close()
    return np.concatenate(got).tolist() if got else []


class TestPooledScanPipeline:
    def test_pooled_rows_identical_to_threaded(self):
        src = _SplitSource()
        expect = np.concatenate(
            [a for row in src.spec for a in row]).tolist()
        threaded = _drain_rows(ScanPipeline(_SplitSource(), reader_threads=4,
                                            target_rows=64,
                                            prefetch_bytes=1024))
        pooled = _drain_rows(ScanPipeline(_SplitSource(), reader_threads=4,
                                          target_rows=64,
                                          prefetch_bytes=1024,
                                          pool_key=next_query_key("t")))
        assert threaded == expect
        assert pooled == expect

    def test_concurrent_pooled_pipelines_under_one_key(self):
        """Several pipelines of one query share a fairness slot and still
        stream correct, complete rows concurrently."""
        key = next_query_key("t")
        results = {}
        errors = []

        def run(i):
            try:
                src = _SplitSource(n_readers=2, chunks=3, rows=32)
                expect = np.concatenate(
                    [a for row in src.spec for a in row]).tolist()
                rows = _drain_rows(ScanPipeline(src, reader_threads=2,
                                                target_rows=32,
                                                prefetch_bytes=512,
                                                pool_key=key))
                results[i] = (rows == expect)
            except BaseException as e:  # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert all(results.get(i) for i in range(3)), results
        assert SCAN_POOL.stats()["clients"] == 0  # key released by closes

    def test_external_wait_source_never_pools(self):
        """A source that blocks indefinitely on external progress (cluster
        remote exchange streams) is exempt from the shared pool even when a
        pool key is passed — a wedged read would hold a pool worker and
        starve every other query's stages, circularly including the very
        upstream producers it waits for (the cluster-tier deadlock this
        guards against)."""
        src = _SplitSource()
        src.external_wait = True
        expect = np.concatenate(
            [a for row in src.spec for a in row]).tolist()
        pipe = ScanPipeline(src, reader_threads=2, target_rows=64,
                            prefetch_bytes=1024,
                            pool_key=next_query_key("t"))
        assert pipe._pool is None  # dedicated threads despite the pool key
        assert _drain_rows(pipe) == expect

    def test_close_mid_stream_releases_pool_client(self):
        pipe = ScanPipeline(_SplitSource(), reader_threads=4, target_rows=64,
                            prefetch_bytes=256,
                            pool_key=next_query_key("t"))
        assert pipe.next() is not None  # started
        pipe.close()
        deadline = time.monotonic() + 5
        while SCAN_POOL.stats()["clients"] and time.monotonic() < deadline:
            time.sleep(0.02)
        assert SCAN_POOL.stats()["clients"] == 0


# ---------------------------------------------------------------------------
# unified memory accounting
# ---------------------------------------------------------------------------

class TestMemoryAccounting:
    def test_scan_prefetch_bytes_reserved_in_query_pool(self):
        """While the pipeline streams, its staged/uploaded bytes appear as
        the query's pool reservation; after close the reservation is 0."""
        pool = MemoryPool("test-general", 1 << 30)
        qmem = QueryContextMemory("q-prefetch", pool, 1 << 30)
        mem = qmem.memory.user.new_local_memory_context("scan_prefetch")
        src = _SplitSource(n_readers=2, chunks=8, rows=256)
        pipe = ScanPipeline(src, reader_threads=2, target_rows=256,
                            prefetch_bytes=1 << 20, memory=mem)
        assert pipe.next() is not None
        # prefetch runs ahead of the consumer: reservation must be visible
        deadline = time.monotonic() + 5
        seen = 0
        while time.monotonic() < deadline:
            seen = max(seen, pool.query_bytes("q-prefetch"))
            if seen > 0:
                break
            time.sleep(0.005)
        assert seen > 0, "prefetch bytes never appeared in the pool"
        pipe.close()
        assert_no_residue(pool, "q-prefetch")

    def test_exchange_inflight_bytes_reserved_in_query_pool(self):
        jax = pytest.importorskip("jax")
        if len(jax.devices()) < 1:
            pytest.skip("no devices")
        from presto_tpu.parallel.mesh import MeshContext
        from presto_tpu.parallel.streaming_exchange import StreamingExchange
        from presto_tpu.sql.planner.plan import GATHER
        from presto_tpu.block import Block, Page

        pool = MemoryPool("test-general", 1 << 30)
        qmem = QueryContextMemory("q-exchange", pool, 1 << 30)
        mem = qmem.memory.user.new_local_memory_context("exchange_inflight")
        mesh = MeshContext(jax.devices()[:1])
        ex = StreamingExchange(mesh, 0, GATHER, None, [BIGINT], [None],
                               chunk_rows=64, memory=mem)
        data = np.arange(64, dtype=np.int64)
        page = Page((Block(BIGINT, data, None, None),),
                    np.ones(64, dtype=bool))
        ex.add_page(0, page)  # staged, pump not started: bytes stay in-flight
        assert pool.query_bytes("q-exchange") > 0
        ex.close()
        assert_no_residue(pool, "q-exchange")

    def test_over_budget_query_killed_not_wedged(self):
        """A query whose scan prefetch blows its per-query budget FAILS with
        the memory-limit error (surfaced through the pipeline) instead of
        wedging a stage thread."""
        pool = MemoryPool("test-general", 1 << 30)
        qmem = QueryContextMemory("q-oom", pool, max_user_bytes=1024)
        mem = qmem.memory.user.new_local_memory_context("scan_prefetch")
        src = _SplitSource(n_readers=2, chunks=8, rows=1024)
        pipe = ScanPipeline(src, reader_threads=2, target_rows=1024,
                            prefetch_bytes=64 << 20, memory=mem)
        with pytest.raises(ExceededMemoryLimitException):
            while pipe.next() is not None:
                pass
        pipe.close()
        assert_no_residue(pool, "q-oom")

    def test_shared_pool_release_clears_query(self):
        from presto_tpu.memory import shared_general_pool

        pool = shared_general_pool()
        pool.reserve("q-leak-test", 12345)
        assert pool.query_bytes("q-leak-test") == 12345
        pool.clear_query("q-leak-test")
        assert_no_residue(pool, "q-leak-test")


# ---------------------------------------------------------------------------
# resource-group admission consults memory
# ---------------------------------------------------------------------------

class TestMemoryAwareAdmission:
    def test_admission_gated_on_memory_then_promotes(self):
        from presto_tpu.server.resource_groups import (GroupSpec,
                                                       ResourceGroupManager)

        usage = {"bytes": 10 << 20}
        mgr = ResourceGroupManager(GroupSpec("root", 10, 10),
                                   memory_limit_bytes=1 << 20,
                                   memory_fn=lambda: usage["bytes"])
        admitted = []

        def submit():
            t = mgr.submit("q2", timeout_s=10.0)
            admitted.append(t)

        th = threading.Thread(target=submit)
        th.start()
        time.sleep(0.3)
        assert not admitted, "admitted while pool was over the memory limit"
        usage["bytes"] = 0  # tenants released: next promotion tick admits
        th.join(timeout=15)
        assert admitted, "queued query never promoted after memory freed"
        mgr.finish(admitted[0])

    def test_memory_ok_defaults_to_shared_pool(self):
        from presto_tpu.memory import shared_general_pool
        from presto_tpu.server.resource_groups import (GroupSpec,
                                                       ResourceGroupManager)

        pool = shared_general_pool()
        mgr = ResourceGroupManager(GroupSpec("root", 10, 10),
                                   memory_limit_bytes=1 << 60)
        ticket = mgr.submit("q1")
        mgr.finish(ticket)
        assert_no_residue(pool)  # probe wired without residue


# ---------------------------------------------------------------------------
# kernel cache single-flight
# ---------------------------------------------------------------------------

class TestKernelCacheSingleFlight:
    def test_concurrent_misses_build_once(self):
        from presto_tpu.utils import kernel_cache as kc

        key = ("test-single-flight", time.monotonic_ns())
        builds = []
        barrier = threading.Barrier(6)
        results = []

        def make():
            builds.append(1)
            time.sleep(0.2)  # a slow "compile" — the herd must wait, not build
            return object()

        def worker():
            barrier.wait(timeout=10)
            results.append(kc.get_or_install(key, make))

        threads = [threading.Thread(target=worker) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(builds) == 1, f"{len(builds)} duplicate builds"
        assert len(set(id(r) for r in results)) == 1, "callers got different kernels"

    def test_failed_build_retried_by_waiter(self):
        from presto_tpu.utils import kernel_cache as kc

        key = ("test-build-fail", time.monotonic_ns())
        attempts = []

        def flaky():
            attempts.append(1)
            if len(attempts) == 1:
                time.sleep(0.05)
                raise RuntimeError("first build fails")
            return "kernel"

        errors = []
        results = []

        def worker():
            try:
                results.append(kc.get_or_install(key, flaky))
            except RuntimeError as e:
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        # one caller saw the failure, the other (waiter) retried and built
        assert results == ["kernel"], (results, errors)
        assert len(errors) == 1


# ---------------------------------------------------------------------------
# the concurrent differential: K queries through QueryManager
# ---------------------------------------------------------------------------

def _wait_done(manager, info, timeout_s=300.0):
    deadline = time.monotonic() + timeout_s
    while not info.done() and time.monotonic() < deadline:
        time.sleep(0.02)
    return info.done()


@pytest.mark.parametrize("shared", [True, False],
                         ids=["shared-pools", "thread-oracle"])
def test_concurrent_queries_row_identical_to_serial(shared):
    """K>=4 mixed TPC-H queries concurrently through QueryManager: every
    result row-identical to its serial run — with the shared pools on, and
    with `shared_pools=False` as the differential oracle."""
    from presto_tpu.server.protocol import FINISHED, QueryManager

    runner = LocalQueryRunner(session=Session(
        catalog="tpch", schema="tiny",
        properties={"shared_pools": shared}))
    manager = QueryManager(runner)
    try:
        serial = {qid: runner.execute(QUERIES[qid]).rows for qid in MIX}
        # K = 6 concurrent queries (2 waves of the mix, offset per client)
        infos = [manager.submit(QUERIES[MIX[i % len(MIX)]])
                 for i in range(6)]
        for i, info in enumerate(infos):
            assert _wait_done(manager, info), f"query {i} never finished"
            assert info.state == FINISHED, \
                f"query {i} failed: {info.error}"
        for i, info in enumerate(infos):
            qid = MIX[i % len(MIX)]
            expect = [manager._to_json_row(r) for r in serial[qid]]
            assert info.rows == expect, \
                f"query {i} (q{qid}) diverged under concurrent load"
    finally:
        manager.close()


def test_concurrent_traced_queries_each_export_complete_traces(tmp_path):
    """Per-query trace scoping (PR 6 follow-up): two traced queries running
    concurrently BOTH export valid Chrome traces with their own driver
    spans — previously the second ran silently untraced."""
    import json

    props = {"query_trace": True, "query_trace_dir": str(tmp_path)}
    runner = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny",
                                              properties=props))
    results = {}
    errors = []
    barrier = threading.Barrier(2)

    def run(i, qid):
        try:
            barrier.wait(timeout=30)
            results[i] = runner.execute(QUERIES[qid])
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=run, args=(i, MIX[i]))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    paths = {i: results[i].trace_path for i in results}
    assert all(paths.values()), f"missing trace export: {paths}"
    assert paths[0] != paths[1], "both queries wrote one trace file"
    from presto_tpu.utils import trace as trace_mod
    for i, path in paths.items():
        with open(path) as f:
            doc = json.load(f)
        cats = trace_mod.span_categories(doc)
        assert cats.get("driver", 0) > 0, \
            f"query {i} trace has no driver spans: {cats}"
        assert cats.get("lifecycle", 0) > 0, \
            f"query {i} trace has no lifecycle spans: {cats}"
