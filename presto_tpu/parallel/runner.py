"""DistributedQueryRunner: planner-driven SQL execution over the device mesh.

The multi-chip analogue of presto-tests DistributedQueryRunner.java:77 — but
where the reference boots N HTTP servers, here "workers" are mesh devices:

  parse -> analyze/plan -> optimize -> AddExchanges -> PlanFragmenter
  -> per fragment (bottom-up): drive each worker's operator pipeline over its
     shard (worker-scoped splits or exchange-output pages)
  -> route the fragment's output through shard_map collectives over the ICI
     mesh (all_to_all repartition / all_gather broadcast / gather-to-root)

The data plane between fragments is the real XLA collective — the engine's
answer to the reference's HTTP+LZ4 shuffle (PartitionedOutputOperator.java:380,
ExchangeClient.java). Two modes:

- STREAMING (default, `streaming_exchange=True`): every fragment's drivers
  run concurrently on ONE task executor; fragment boundaries are
  StreamingExchange instances (parallel/streaming_exchange.py) moving
  fixed-capacity chunks through one compiled collective per chunk while
  producers still run — the ExchangeClient pull-while-producing shape, with
  byte-bounded backpressure on both sides.
- BARRIER (`streaming_exchange=False`, the differential oracle): fragments
  execute bottom-up, each draining fully before `run_exchange` routes ALL of
  its output in one variable-shape collective — the pre-streaming data plane,
  kept bit-for-bit for A/B testing exactly like `segment_fusion=False`.

Within a fragment, EVERY worker's drivers are enqueued on one shared
TaskExecutor and time-slice across its runner threads (so 8 virtual workers
never host-serialize; build/probe pipelines of different workers overlap);
the collective itself always runs as one SPMD program over all workers.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..block import Block, Dictionary, Page
from ..exec.local_planner import LocalExecutionPlanner
from ..exec.shared_pools import next_query_key
from ..exec.task_executor import TaskExecutor
from ..metadata import CatalogManager, Session
from ..runner import LocalQueryRunner, QueryResult
from ..sql import tree as t
from ..sql.planner.add_exchanges import add_exchanges
from ..sql.planner.fragmenter import (Fragment, SINGLE_PART, SubPlan,
                                      fragment_plan)
from ..sql.planner.optimizer import optimize
from ..sql.planner.plan import (BROADCAST, GATHER, MERGE, OutputNode,
                                REPARTITION, RemoteSourceNode, plan_to_text)
from ..sql.planner.planner import LogicalPlanner
from ..types import Type
from ..utils import trace
from ..utils.metrics import METRICS
from .mesh import MeshContext, WORKER_AXIS
# shared exchange plumbing (one accounting + device-helper set for both data
# planes); EXCHANGE_STATS re-exported here because the multichip dryrun (and
# history) imports it from this module
from .streaming_exchange import (EXCHANGE_STATS, ExchangeSinkOperatorFactory,  # noqa: F401
                                 ExchangeStatsBook, StreamingExchange,
                                 _compact_pad_jit, _range_key_for,
                                 _zeros_shard, exchange_row_bytes,
                                 record_exchange_stat)

# (pages for each worker, shared column dictionaries)
RemoteInput = Tuple[List[Page], List[Optional[Dictionary]]]


class DistributedQueryRunner:
    """In-process multi-worker engine over a jax.sharding.Mesh."""

    def __init__(self, mesh: Optional[MeshContext] = None,
                 session: Optional[Session] = None,
                 catalogs: Optional[CatalogManager] = None,
                 page_capacity: int = 1 << 14):
        self.local = LocalQueryRunner(session, catalogs, page_capacity)
        self.mesh = mesh if mesh is not None else MeshContext()

    @property
    def metadata(self):
        return self.local.metadata

    @property
    def session(self):
        return self.local.session

    @session.setter
    def session(self, session: Session) -> None:
        # the protocol layer scopes a shallow copy of the engine to one
        # query's X-Presto-Catalog/Schema: the copy needs a local runner of
        # its own to carry that session (the mesh and the catalogs are shared)
        import copy

        self.local = copy.copy(self.local)
        self.local.session = session

    # ------------------------------------------------------------------ api

    def plan_sql(self, sql: str) -> SubPlan:
        stmt = self.local.parser.parse(sql)
        if not isinstance(stmt, t.Query):
            raise ValueError(f"cannot plan {type(stmt).__name__}")
        return self.plan_statement(stmt)

    def plan_statement(self, stmt: t.Query) -> SubPlan:
        planner = LogicalPlanner(self.metadata, self.session)
        plan = planner.plan(stmt)
        plan = optimize(plan, self.metadata, self.session)
        plan = add_exchanges(plan, planner.symbols, self.metadata, self.session,
                             n_workers=self.mesh.n_workers)
        return fragment_plan(plan)

    def explain(self, sql: str) -> str:
        sub = self.plan_sql(sql)
        parts = []
        for f in sub.fragments:
            head = f"Fragment {f.id} [{f.partitioning}]"
            if f.output_kind:
                keys = f" keys={[k.name for k in f.output_keys]}" \
                    if f.output_keys else ""
                head += f" output={f.output_kind}{keys}"
            parts.append(head + "\n" + plan_to_text(f.root, indent=1))
        return "\n".join(parts)

    def execute(self, sql: str) -> QueryResult:
        """One statement under the scope every runner tier shares
        (trace.QueryScope: recorder, forensic, wall and phase histograms)."""
        with trace.QueryScope(self.session) as scope:
            with trace.phase("parse"):
                stmt = self.local.parser.parse(sql)
            if isinstance(stmt, t.Explain) and stmt.analyze and \
                    isinstance(stmt.statement, t.Query):
                # distributed EXPLAIN ANALYZE: execute over the mesh and
                # render per-fragment per-operator stats rolled up across
                # workers — before this, ANALYZE silently profiled the
                # single-node path
                result = self._explain_analyze(stmt.statement)
            elif not isinstance(stmt, t.Query):
                # EXPLAIN/SHOW et al stay local, inside this scope
                result = self.local._execute_statement(sql)
            else:
                with trace.phase("plan"):
                    sub = self.plan_statement(stmt)
                result = self._execute_subplan(sub)
        return scope.finish(result)

    # ------------------------------------------------------------ execution

    def _execute_subplan(self, sub: SubPlan,
                         frag_drivers: Optional[Dict[int, List[list]]] = None
                         ) -> QueryResult:
        """`frag_drivers`, when given, collects each fragment's per-worker
        driver lists for EXPLAIN ANALYZE's stats roll-up."""
        book = ExchangeStatsBook()
        if bool(self.session.get("streaming_exchange", True)):
            result = self._execute_streaming(sub, book, frag_drivers)
        else:
            result = self._execute_barrier(sub, book, frag_drivers)
        snap = book.snapshot()
        if snap:
            snap["mode"] = "streaming" \
                if bool(self.session.get("streaming_exchange", True)) \
                else "barrier"
            result.stats = dict(result.stats or {}, exchange=snap)
            METRICS.count_many(
                {k: v for k, v in snap.items()
                 if isinstance(v, (int, float))}, prefix="exchange.")
        return result

    def _fragment_root(self, sub: SubPlan, frag: Fragment) -> OutputNode:
        if frag is sub.root_fragment:
            return OutputNode(frag.root, sub.column_names, sub.output_symbols)
        syms = frag.root.outputs()
        return OutputNode(frag.root, [s.name for s in syms], syms)

    def _routing_spec(self, frag: Fragment):
        """-> (key_idx, orderings) for the fragment's output exchange."""
        names = [s.name for s in frag.root.outputs()]
        key_idx = None
        orderings = None
        if frag.output_kind == REPARTITION:
            key_idx = [names.index(k.name) for k in frag.output_keys]
        elif frag.output_kind == MERGE:
            orderings = tuple(
                (names.index(o.symbol.name), o.descending, o.nulls_first)
                for o in frag.output_orderings)
        return key_idx, orderings

    def _execute_streaming(self, sub: SubPlan, book: ExchangeStatsBook,
                           frag_drivers: Optional[dict] = None) \
            -> QueryResult:
        """Plan every fragment, connect them with StreamingExchanges, then
        run ALL fragments' drivers in ONE task-executor pass: producer and
        consumer fragments time-slice on the same runner threads while the
        exchange pumps move chunks between them."""
        W = self.mesh.n_workers
        frag_dicts: Dict[int, List[Optional[Dictionary]]] = {}
        exchanges: Dict[int, StreamingExchange] = {}
        sink_facs: Dict[int, ExchangeSinkOperatorFactory] = {}
        mem_ctx, over_target, mem_release = self.local._query_memory()
        chunk_rows = int(self.session.get("exchange_chunk_rows") or 0)
        inflight = int(self.session.get("exchange_inflight_bytes") or 0)
        page_cap = int(self.session.get("page_capacity") or (1 << 14))
        # ONE shared-pool fairness slot per query: every fragment's scan
        # stages and every exchange pump of this query share it
        pool_key = next_query_key("mesh-q") \
            if bool(self.session.get("shared_pools", True)) else None
        drivers = []
        root_ep = None
        planned = []  # (fragment, local plan) — skew wiring scans consumers
        remote_roots = {f.id: f.root for f in sub.fragments}
        try:
            with trace.phase("local_plan"):
                for frag in sub.fragments:
                    is_root = frag is sub.root_fragment
                    root = self._fragment_root(sub, frag)
                    workers = [0] if frag.partitioning == SINGLE_PART \
                        else list(range(W))
                    lp = LocalExecutionPlanner(self.metadata, self.session,
                                               n_workers=W,
                                               remote_dicts=frag_dicts,
                                               devices=self.mesh.devices,
                                               pool_key=pool_key,
                                               remote_roots=remote_roots)
                    lp.attach_memory(mem_ctx, over_target)
                    if is_root:
                        ep = lp.plan(root)
                    else:
                        key_idx, orderings = self._routing_spec(frag)
                        holder: dict = {}

                        def sink_factory(types, dicts, _frag=frag,
                                         _key=key_idx, _ord=orderings,
                                         _holder=holder, _lp=lp):
                            ex = StreamingExchange(
                                self.mesh, _frag.id, _frag.output_kind, _key,
                                types, dicts, orderings=_ord,
                                chunk_rows=chunk_rows, inflight_bytes=inflight,
                                page_capacity=page_cap, book=book,
                                pool_key=pool_key,
                                # in-flight exchange bytes reserve as the
                                # query's user memory (unified accounting)
                                memory=mem_ctx.user.new_local_memory_context(
                                    f"exchange_inflight_f{_frag.id}"))
                            fac = ExchangeSinkOperatorFactory(
                                next(_lp._ids), ex, types)
                            _holder["exchange"] = ex
                            _holder["factory"] = fac
                            return fac

                        ep = lp.plan(root, sink_factory=sink_factory)
                        exchanges[frag.id] = holder["exchange"]
                        sink_facs[frag.id] = holder["factory"]
                        frag_dicts[frag.id] = ep.output_dicts
                    # consumer endpoints: attach the producers' streams
                    # (created in fragment order, so every referenced
                    # exchange exists)
                    for fid, slot in ep.remote_slots.items():
                        slot.stream = exchanges[fid]
                    planned.append(ep)
                    for w in workers:
                        worker_drivers = ep.create_drivers(w)
                        drivers.extend(worker_drivers)
                        if frag_drivers is not None:
                            # per-worker lists: driver ordering is
                            # deterministic per plan, so EXPLAIN ANALYZE's
                            # roll-up can line operator instances up across
                            # workers
                            frag_drivers.setdefault(frag.id, []).append(
                                worker_drivers)
                    if is_root:
                        root_ep = ep
                # skew-aware routing: pair each INNER join's build-side and
                # probe-side REPARTITION exchanges BEFORE any pump runs (the
                # roles change the compiled routing program for the stream)
                if bool(self.session.get("skew_aware_exchange", True)):
                    _wire_skew(planned, exchanges)
                # all drivers exist: producer counts are exact — start the
                # pumps
                for fid, ex in exchanges.items():
                    ex.start(sink_facs[fid].created)
            # live progress across ALL fragments' drivers (exec/progress.py;
            # no-op outside a protocol-layer query scope)
            from ..exec import progress as _progress
            from ..exec.explain import driver_stats as _dstats
            from ..runner import _pool_steps

            unregister = _progress.register(lambda: {
                "operators": _dstats(drivers),
                "memory_reserved_bytes": mem_ctx.total_bytes(),
                "pool_steps": _pool_steps(pool_key)})
            try:
                with trace.phase("execute"):
                    TaskExecutor(
                        int(self.session.get("task_concurrency"))
                    ).execute(drivers)
            finally:
                unregister()
            with trace.span(trace.LIFECYCLE, "result"):
                rows = root_ep.sink.rows()
            return QueryResult(rows, sub.column_names, root_ep.output_types)
        finally:
            err = sys.exc_info()[1]
            for ex in exchanges.values():
                ex.close(error=err)
            if err is not None:
                for d in drivers:
                    try:
                        d.close()
                    except Exception:  # noqa: BLE001 - teardown best effort
                        pass
            # after every pipeline/exchange tore down: clear this query's
            # reservations from the process-shared pool
            mem_release()

    def _execute_barrier(self, sub: SubPlan, book: ExchangeStatsBook,
                         frag_drivers: Optional[dict] = None) \
            -> QueryResult:
        """The pre-streaming stage-barrier loop, kept as the differential
        oracle: each fragment drains fully, then ONE variable-shape
        collective routes all of its output."""
        # ONE memory pool + query context + task executor for the whole
        # query: every fragment's operators draw on the same budget and the
        # runner threads are reused across stages instead of rebuilt
        mem_ctx, over_target, mem_release = self.local._query_memory()
        executor = TaskExecutor(int(self.session.get("task_concurrency")),
                                persistent=True)
        try:
            return self._run_barrier_stages(sub, executor,
                                            (mem_ctx, over_target),
                                            book, frag_drivers)
        finally:
            executor.close()
            mem_release()

    def _run_barrier_stages(self, sub: SubPlan, executor: TaskExecutor,
                            query_memory, book: ExchangeStatsBook,
                            frag_drivers: Optional[dict] = None) \
            -> QueryResult:
        W = self.mesh.n_workers
        frag_dicts: Dict[int, List[Optional[Dictionary]]] = {}
        routed: Dict[int, List[List[Page]]] = {}  # fid -> per-worker pages
        # one shared-pool fairness slot per QUERY (not per fragment) — the
        # same invariant the streaming path and the cluster tier keep
        pool_key = next_query_key("mesh-q") \
            if bool(self.session.get("shared_pools", True)) else None
        remote_roots = {f.id: f.root for f in sub.fragments}
        for frag in sub.fragments:
            is_root = frag is sub.root_fragment
            root = self._fragment_root(sub, frag)
            workers = [0] if frag.partitioning == SINGLE_PART else list(range(W))
            # plan ONCE per fragment: every worker shares the factories (and so
            # the jit-compiled kernels); only splits/exchange pages differ
            lp = LocalExecutionPlanner(self.metadata, self.session,
                                       n_workers=W, remote_dicts=frag_dicts,
                                       devices=self.mesh.devices,
                                       pool_key=pool_key,
                                       remote_roots=remote_roots)
            lp.attach_memory(*query_memory)
            with trace.phase("local_plan"):
                ep = lp.plan(root)
                for fid, slot in ep.remote_slots.items():
                    for w in range(W):
                        slot.set_pages(w, routed[fid][w])
                # all workers' drivers share one executor: worker tasks and
                # their build/probe pipelines time-slice across runner threads
                per_worker_drivers = [ep.create_drivers(w) for w in workers]
            if frag_drivers is not None:
                frag_drivers[frag.id] = per_worker_drivers
            drivers = [d for wd in per_worker_drivers for d in wd]
            with trace.phase("execute"):
                executor.execute(drivers)
            if is_root:
                return QueryResult(ep.sink.rows(), sub.column_names,
                                   ep.output_types)
            per_worker = [ep.sink.pages_for(w) for w in range(W)]
            key_idx, orderings = self._routing_spec(frag)
            routed[frag.id] = run_exchange(
                self.mesh, frag.output_kind, key_idx, per_worker,
                ep.output_types, ep.output_dicts,
                page_capacity=int(self.session.get("page_capacity")
                                  or (1 << 14)),
                orderings=orderings, book=book)
            frag_dicts[frag.id] = ep.output_dicts
        raise AssertionError("root fragment must terminate execution")

    # ------------------------------------------------- EXPLAIN ANALYZE

    def _explain_analyze(self, stmt: t.Query) -> QueryResult:
        """Execute over the mesh, then render per-fragment per-operator
        stats ROLLED UP across workers (rows / wall / blocked / peak-mem,
        via exec/explain.py — the same table the local runner prints),
        plus each fragment boundary's exchange chunk/carry counts."""
        import time as _time

        from ..exec.explain import driver_stats, rollup, table

        with trace.phase("plan"):
            sub = self.plan_statement(stmt)
        frag_drivers: Dict[int, List[list]] = {}
        t0 = _time.perf_counter()
        result = self._execute_subplan(sub, frag_drivers)
        wall = _time.perf_counter() - t0
        ex = (result.stats or {}).get("exchange", {})
        per_exchange = {e.get("fragment"): e
                        for e in ex.get("per_exchange", [])}
        lines = [f"Query: {wall * 1000:.0f}ms wall, "
                 f"{len(sub.fragments)} fragments, "
                 f"{self.mesh.n_workers} workers, "
                 f"exchange={ex.get('mode', 'none')}", ""]
        for frag in sub.fragments:
            head = f"Fragment {frag.id} [{frag.partitioning}]"
            if frag.output_kind:
                head += f" output={frag.output_kind}"
            per_worker = frag_drivers.get(frag.id, [])
            head += f" workers={len(per_worker)}"
            lines.append(head)
            stats = [s for wd in per_worker for s in driver_stats(wd)]
            lines += table(rollup(stats), indent="  ")
            exch = per_exchange.get(frag.id)
            if exch:
                lines.append(
                    f"  exchange [{exch.get('kind')}]: "
                    f"chunks={exch.get('chunks', 0)} "
                    f"carry_rows={exch.get('carry_rows', 0)} "
                    f"rows_out={exch.get('rows_out', 0)} "
                    f"compiles={exch.get('compiles', 0)} "
                    f"overlap_s={exch.get('overlap_s', 0)}")
            lines.append("")
        return QueryResult([[line] for line in lines], ["Query Plan"],
                           stats=result.stats,
                           trace_path=result.trace_path)


# ---------------------------------------------------------------------------
# skew wiring: pair each INNER join's build/probe exchanges for heavy-hitter
# handling (parallel/streaming_exchange.py SkewCoordinator)
# ---------------------------------------------------------------------------

def _pipeline_members(chain) -> list:
    """Factory chain with fused segments expanded back to their members —
    the join build/probe factories the skew wiring looks for may sit inside
    a FusedSegmentOperatorFactory."""
    from ..ops.fused_segment import FusedSegmentOperatorFactory

    members = []
    for f in chain:
        if isinstance(f, FusedSegmentOperatorFactory):
            members.extend(f.mid_factories)
            if f.terminal_factory is not None:
                members.append(f.terminal_factory)
        else:
            members.append(f)
    return members


def _skew_pair_safe(build_members, probe_members, probe_join,
                    build_src, exchanges) -> bool:
    """Is spraying/replicating this join's hot keys invisible to everything
    else in the consumer fragment? Skew routing breaks the "all rows of key
    k on one partition" invariant that add_exchanges may have RELIED on
    when it elided downstream exchanges (a SINGLE-step aggregation on the
    join key, a second same-key partitioned join) — so the pair only wires
    when the build pipeline is exactly remote-source -> row-local* -> build,
    and everything downstream of the probe join is partition-AGNOSTIC:
    row-local operators, PARTIAL aggregations (re-exchanged by key later),
    TopN/sort/limit (order-based), sinks, and further joins only when their
    build side arrived by BROADCAST (location-independent by construction).
    Anything else keeps plain hash routing — correct, just concentrated."""
    from ..ops.coalesce import CoalesceOperatorFactory
    from ..ops.filter_project import FilterProjectOperatorFactory
    from ..ops.hash_agg import PARTIAL, HashAggregationOperatorFactory
    from ..ops.hash_join import LookupJoinOperatorFactory
    from ..ops.topn import (LimitOperatorFactory, OrderByOperatorFactory,
                            TopNOperatorFactory)
    from ..utils.testing import PageConsumerFactory

    row_local = (FilterProjectOperatorFactory, CoalesceOperatorFactory)
    if any(not isinstance(f, row_local) for f in build_members[:-1]):
        return False
    ji = probe_members.index(probe_join)
    if any(not isinstance(f, row_local) for f in probe_members[:ji]):
        return False
    for f in probe_members[ji + 1:]:
        if isinstance(f, row_local + (TopNOperatorFactory,
                                      OrderByOperatorFactory,
                                      LimitOperatorFactory,
                                      ExchangeSinkOperatorFactory,
                                      PageConsumerFactory)):
            continue
        if isinstance(f, HashAggregationOperatorFactory) and \
                f.step == PARTIAL:
            continue
        if isinstance(f, LookupJoinOperatorFactory):
            bfid = build_src.get(id(f.lookup_factory))
            bex = exchanges.get(bfid) if bfid is not None else None
            if bex is not None and bex.kind == BROADCAST:
                continue
            return False
        return False
    return True


def _wire_skew(planned, exchanges) -> None:
    """Scan every consumer fragment's pipelines for partitioned joins and
    pair the REPARTITION exchange feeding each JoinBuildOperatorFactory
    ("build" side) with the one feeding the matching LookupJoin probe
    ("probe" side) on one SkewCoordinator: both sample their first chunk,
    and a heavy-hitter key splits round-robin on its own side while the
    peer replicates it. INNER joins only — a replicated row would emit
    spurious unmatched rows under LEFT/FULL/semi semantics — and only
    unambiguous 1:1 pairs whose consumer fragment is provably partition-
    agnostic downstream of the join (:func:`_skew_pair_safe`)."""
    from ..ops.hash_join import INNER, JoinBuildOperatorFactory, \
        LookupJoinOperatorFactory
    from .streaming_exchange import SkewCoordinator

    build_src = {}   # id(lookup_factory) -> producer fragment id
    build_info = {}  # id(lookup_factory) -> build pipeline members
    probe_src = {}   # id(lf) -> (fid, join factory, members) | None
    for ep in planned:
        for chain in ep.pipelines:
            fid = getattr(getattr(chain[0], "slot", None),
                          "fragment_id", None)
            if fid is None:
                continue
            members = _pipeline_members(chain[1:])
            if members and isinstance(members[-1], JoinBuildOperatorFactory):
                build_src[id(members[-1].lookup_factory)] = fid
                build_info[id(members[-1].lookup_factory)] = members
            for f in members:
                if isinstance(f, LookupJoinOperatorFactory):
                    key = id(f.lookup_factory)
                    if key in probe_src:
                        probe_src[key] = None  # ambiguous: two probe feeds
                    else:
                        probe_src[key] = (fid, f, members)
    for key, bfid in build_src.items():
        pair = probe_src.get(key)
        if not pair:
            continue
        pfid, join_fac, probe_members = pair
        if join_fac.join_type != INNER or pfid == bfid:
            continue
        bex, pex = exchanges.get(bfid), exchanges.get(pfid)
        if bex is None or pex is None or \
                bex.kind != REPARTITION or pex.kind != REPARTITION or \
                bex._skew is not None or pex._skew is not None:
            continue
        if not _skew_pair_safe(build_info[key], probe_members, join_fac,
                               build_src, exchanges):
            continue
        coord = SkewCoordinator()
        bex.set_skew("build", coord)
        pex.set_skew("probe", coord)


# ---------------------------------------------------------------------------
# the barrier exchange bridge: per-worker page lists -> one collective ->
# per-worker page lists (the oracle data plane; the streaming plane lives in
# parallel/streaming_exchange.py and shares this module's device helpers)
# ---------------------------------------------------------------------------

# shape floor for exchange buffers: below this, padding is free but every
# distinct capacity would compile (and cache) another XLA collective
_MIN_EXCHANGE_CAP = 1 << 9


def _worker_device_columns(pages: List[Page], types: Sequence[Type],
                           book: Optional[ExchangeStatsBook] = None):
    """Concat+widen one worker's pages ON ITS DEVICE -> (datas, nulls, mask,
    live_count). Eager jnp ops follow the pages' committed device, so a worker
    whose pipeline ran on mesh device w compacts on device w."""
    import jax.numpy as jnp

    # host-sourced pages (numpy blocks — VALUES rows, or a regression that
    # re-materialized exchange output host-side) are what the multichip
    # dryrun's device-residency assertion exists to catch: count them
    for p in pages:
        if isinstance(p.mask, np.ndarray) or \
                any(isinstance(b.data, np.ndarray) for b in p.blocks):
            record_exchange_stat("host_uploads", 1, book)

    ncols = len(types)
    masks = [jnp.asarray(p.mask) for p in pages]
    mask = masks[0] if len(masks) == 1 else jnp.concatenate(masks)
    datas, nulls = [], []
    for c in range(ncols):
        dt = np.dtype(types[c].np_dtype)
        parts = [jnp.asarray(p.blocks[c].data).astype(dt) for p in pages]
        datas.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts))
        nparts = [jnp.asarray(p.blocks[c].nulls)
                  if p.blocks[c].nulls is not None
                  else jnp.zeros(p.capacity, dtype=jnp.bool_) for p in pages]
        nulls.append(nparts[0] if len(nparts) == 1 else jnp.concatenate(nparts))
    # live count stays a DEVICE scalar: the caller batches all workers'
    # counts into one host transfer instead of W serialized syncs
    return datas, nulls, mask, jnp.sum(mask.astype(jnp.int32))


def _exchange_program(mesh, kind: str, key_idx: Optional[Tuple[int, ...]],
                      ncols: int, W: int, L: int, out_cap: int,
                      range_dtype: Optional[str] = None):
    """-> (program, compiled_now). Build + jit the exchange collective ONCE
    per (mesh, kind, keys, shape) signature — repeated exchanges of the same
    shape reuse the compiled XLA program via the global LRU kernel cache
    (the reference reuses its HTTP buffer machinery similarly).
    `compiled_now` feeds the per-query compile counter race-free (a global
    cache-stats diff would misattribute compiles between concurrently
    executing queries).

    `out_cap` is the per-peer receive capacity. For REPARTITION the caller
    sizes it from the measured max (worker, peer) send count — sizing it to L
    (the worst case) would make every downstream page W/occupancy times
    padding, which on an 8-way mesh was a ~10x compute blowup."""
    from ..utils import kernel_cache as kc

    key = ("exchange-barrier", mesh, kind, key_idx, ncols, W, L, out_cap,
           range_dtype)
    return kc.get_or_build(
        key, lambda: _build_exchange_program(mesh, kind, key_idx, ncols, W,
                                             L, out_cap))


def _build_exchange_program(mesh, kind: str,
                            key_idx: Optional[Tuple[int, ...]],
                            ncols: int, W: int, L: int, out_cap: int):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..ops.hash_join import combined_key
    from .exchange import (broadcast_gather, gather_to_single,
                           range_partition_ids, repartition,
                           repartition_by_pid)

    n_arrays = 2 * ncols

    if kind == MERGE:
        def merge_stage(arrays, mask, range_key, splitters):
            pid = range_partition_ids(range_key, splitters, mask, W)
            out, m, dropped = repartition_by_pid(
                list(arrays) + [range_key], mask, pid, W, out_cap)
            return tuple(out[:-1]), m, dropped.reshape(1)

        smapped = shard_map(
            merge_stage, mesh=mesh,
            in_specs=(tuple(P(WORKER_AXIS) for _ in range(n_arrays)),
                      P(WORKER_AXIS), P(WORKER_AXIS), P()),
            out_specs=(tuple(P(WORKER_AXIS) for _ in range(n_arrays)),
                       P(WORKER_AXIS), P(WORKER_AXIS)))
        return jax.jit(smapped)

    def stage(arrays, mask):
        if kind == REPARTITION:
            keys = [jnp.where(arrays[ncols + i], 0, arrays[i]).astype(jnp.int64)
                    for i in key_idx]
            out, m, dropped = repartition(list(arrays), mask,
                                          combined_key(keys), W, out_cap)
            return tuple(out), m, dropped.reshape(1)
        if kind == BROADCAST:
            out, m = broadcast_gather(list(arrays), mask)
        elif kind == GATHER:
            out, m = gather_to_single(list(arrays), mask)
        else:
            raise AssertionError(kind)
        return tuple(out), m, jnp.zeros(1, dtype=jnp.int32)

    smapped = shard_map(
        stage, mesh=mesh,
        in_specs=(tuple(P(WORKER_AXIS) for _ in range(n_arrays)), P(WORKER_AXIS)),
        out_specs=(tuple(P(WORKER_AXIS) for _ in range(n_arrays)),
                   P(WORKER_AXIS), P(WORKER_AXIS)))
    return jax.jit(smapped)


def run_exchange(mesh: MeshContext, kind: str, key_idx: Optional[List[int]],
                 per_worker_pages: List[List[Page]], types: Sequence[Type],
                 dicts: Sequence[Optional[Dictionary]],
                 page_capacity: int = 1 << 14,
                 orderings=None,
                 book: Optional[ExchangeStatsBook] = None) -> List[List[Page]]:
    """Route every worker's output pages to their consumers with ONE shard_map
    collective over the mesh (REPARTITION=all_to_all, BROADCAST=all_gather,
    GATHER=all_gather masked to worker 0).

    DEVICE-RESIDENT end to end: each worker's pages compact on their own
    device, the global sharded array is assembled from those per-device
    shards (jax.make_array_from_single_device_arrays — no host gather), the
    collective runs, and the output shards are handed to the next fragment as
    device pages. The only host->device uploads are zero backfills for
    workers that produced nothing (counted in EXCHANGE_STATS). The reference
    never re-materializes pages host-side mid-query either — its data plane
    streams serialized pages process-to-process (ExchangeClient.java)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    W = mesh.n_workers
    ncols = len(types)
    record_exchange_stat("exchanges", 1, book)

    compacted = [None] * W
    for w, pages in enumerate(per_worker_pages):
        if pages:
            compacted[w] = _worker_device_columns(pages, types, book)
    # ONE batched host transfer for all workers' live counts (device_get on
    # the list issues every d2h together, not W serialized blocking syncs)
    live_devs = [c[3] for c in compacted if c is not None]
    live_np = iter(jax.device_get(live_devs))
    live = [int(next(live_np)) if compacted[w] is not None else 0
            for w in range(W)]
    # bucket L (live rows of the fullest worker) to powers of two — with a
    # floor — so repeated exchanges of similar volume reuse one compiled
    # collective; every distinct (L, out_cap) is a separate XLA program, and
    # distinct-program count is worth bounding (compile time, code memory)
    L = max(1 << (max(max(live), 1) - 1).bit_length(), _MIN_EXCHANGE_CAP)

    compact = _compact_pad_jit()
    shard_datas: List[List] = [None] * W  # per worker: ncols data arrays
    shard_nulls: List[List] = [None] * W
    shard_masks: List = [None] * W
    for w in range(W):
        dev = mesh.devices[w]
        if compacted[w] is None:
            # no output on this worker: cached constant zero shards
            shard_datas[w] = [_zeros_shard(dev, types[c].np_dtype, L, book)
                              for c in range(ncols)]
            shard_nulls[w] = [_zeros_shard(dev, bool, L, book)
                              for _ in range(ncols)]
            shard_masks[w] = _zeros_shard(dev, bool, L, book)
            continue
        datas, nulls, mask, _ = compacted[w]
        out_d, out_n, out_m = compact(tuple(datas), tuple(nulls), mask, L)
        # device_put to the worker's own device is a no-op when the pipeline
        # already ran there; otherwise a direct device-to-device move
        shard_datas[w] = [jax.device_put(a, dev) for a in out_d]
        shard_nulls[w] = [jax.device_put(a, dev) for a in out_n]
        shard_masks[w] = jax.device_put(out_m, dev)

    sharding = NamedSharding(mesh.mesh, P(WORKER_AXIS))

    def assemble(shards):
        return jax.make_array_from_single_device_arrays(
            (W * L,), sharding, shards)

    dev_arrays = [assemble([shard_datas[w][c] for w in range(W)])
                  for c in range(ncols)]
    dev_arrays += [assemble([shard_nulls[w][c] for w in range(W)])
                   for c in range(ncols)]
    dev_mask = assemble([shard_masks[w] for w in range(W)])

    # per-peer receive capacity: worst case (L) for gather/broadcast; for
    # REPARTITION/MERGE measure the true max (worker, peer) send count so
    # output pages are sized to the data, not to the theoretical skew bound
    out_cap = L
    range_keys = splitters = None
    if kind == REPARTITION:
        from ..ops.hash_join import combined_key
        from .exchange import partition_ids

        maxes = []
        for w in range(W):
            if compacted[w] is None:
                continue
            datas, nulls_w, mask, _ = compacted[w]
            keys = [jnp.where(nulls_w[i], 0, datas[i]).astype(jnp.int64)
                    for i in key_idx]
            pid = jnp.where(mask, partition_ids(combined_key(keys), W), W)
            counts = jax.ops.segment_sum(
                jnp.ones_like(pid), pid, num_segments=W + 1)[:W]
            maxes.append(jnp.max(counts))
        max_count = int(max(jax.device_get(maxes))) if maxes else 1
        out_cap = max(1 << (max(max_count, 1) - 1).bit_length(),
                      _MIN_EXCHANGE_CAP)
        out_cap = min(out_cap, L)
    elif kind == MERGE:
        # range routing for distributed ORDER BY: per-worker routing key on
        # each worker's device, splitters from pooled samples (control-plane
        # scalars — the reference samples the same way for bucketed sorts)
        from .exchange import range_partition_ids

        ch, desc, nf = orderings[0]
        range_keys = [None] * W
        samples = []
        for w in range(W):
            key_w = _range_key_for(
                jax.device_put(shard_datas[w][ch], mesh.devices[w]),
                shard_nulls[w][ch], types[ch], dicts[ch], desc, nf)
            range_keys[w] = jax.device_put(key_w, mesh.devices[w])
            lw = live[w]
            if lw:
                stride = max(1, lw // 128)
                samples.append(np.asarray(key_w[:lw:stride][:128]))
        pooled = np.sort(np.concatenate(samples)) if samples else \
            np.zeros(1, dtype=range_keys[0].dtype)
        splitters = np.asarray(
            [pooled[len(pooled) * i // W] for i in range(1, W)],
            dtype=pooled.dtype)
        maxes = []
        for w in range(W):
            if compacted[w] is None:
                continue
            pid = range_partition_ids(range_keys[w],
                                      jax.device_put(splitters,
                                                     mesh.devices[w]),
                                      shard_masks[w], W)
            counts = jax.ops.segment_sum(
                jnp.ones_like(pid), pid, num_segments=W + 1)[:W]
            maxes.append(jnp.max(counts))
        max_count = int(max(jax.device_get(maxes))) if maxes else 1
        out_cap = max(1 << (max(max_count, 1) - 1).bit_length(),
                      _MIN_EXCHANGE_CAP)
        out_cap = min(out_cap, L)

    # jax.sharding.Mesh is hashable and value-equal: safe as the cache key
    program, compiled_now = _exchange_program(
        mesh.mesh, kind, tuple(key_idx) if key_idx is not None else None,
        ncols, W, L, out_cap,
        str(range_keys[0].dtype) if kind == MERGE else None)
    if book is not None and compiled_now:
        book.bump("collective_compiles")
    from .streaming_exchange import COLLECTIVE_DISPATCH_LOCK
    with COLLECTIVE_DISPATCH_LOCK:
        if kind == MERGE:
            g_rangekey = assemble([range_keys[w] for w in range(W)])
            out_arrays, out_mask, dropped = program(
                tuple(dev_arrays), dev_mask, g_rangekey, splitters)
        else:
            out_arrays, out_mask, dropped = program(tuple(dev_arrays),
                                                    dev_mask)
    n_dropped = int(np.asarray(dropped).sum())
    if n_dropped:
        # the send buffers are sized to the fullest worker's live rows, so a
        # drop means a sizing bug upstream — corrupt results must fail loudly
        # (the streaming exchange carries overflow over to the next chunk
        # instead; see parallel/streaming_exchange.py)
        raise RuntimeError(
            f"repartition exchange dropped {n_dropped} rows "
            f"(capacity {L} per peer, {W} workers)")

    # hand each worker its output shard as DEVICE pages (no host round trip):
    # prefix-compact the shard on its device, then slice into STANDARD pow2
    # page capacities — downstream operators then reuse the kernels already
    # compiled for scan pages instead of tracing one variant per shard length
    # (capacity diversity compiles programs; program count is a real cost)
    out_len = out_mask.shape[0] // W
    # one host sync per column to decide null-mask presence (downstream
    # kernels skip null arithmetic entirely for all-non-null columns)
    null_cols = out_arrays[ncols:]
    has_nulls = np.asarray(jnp.stack([jnp.any(a) for a in null_cols])) \
        if ncols else np.zeros(0, dtype=bool)

    def shards_by_worker(arr):
        out = [None] * W
        for sh in arr.addressable_shards:
            start = sh.index[0].start or 0  # W=1: index is slice(None)
            out[start // out_len] = sh.data
        return out

    data_shards = [shards_by_worker(out_arrays[c]) for c in range(ncols)]
    nulls_shards = [shards_by_worker(null_cols[c]) for c in range(ncols)]
    mask_shards = shards_by_worker(out_mask)
    cap = min(max(page_capacity, _MIN_EXCHANGE_CAP), out_len)
    out_compact = []
    for w in range(W):
        out_compact.append(compact(
            tuple(data_shards[c][w] for c in range(ncols)),
            tuple(nulls_shards[c][w] for c in range(ncols)),
            mask_shards[w], out_len))
    out_live = jax.device_get(
        [jnp.sum(m.astype(jnp.int32)) for _, _, m in out_compact])
    if book is not None:
        rows = sum(int(n) for n in out_live)
        book.bump("rows", rows)
        book.bump("live_bytes", rows * exchange_row_bytes(types, has_nulls))
    routed: List[List[Page]] = []
    for w in range(W):
        out_d, out_n, out_m = out_compact[w]
        live_w = int(out_live[w])
        n_pages = max(1, -(-live_w // cap))
        pages: List[Page] = []
        for off in range(0, n_pages * cap, cap):
            blocks = []
            for c in range(ncols):
                nm = out_n[c][off:off + cap] if has_nulls[c] else None
                blocks.append(Block(types[c], out_d[c][off:off + cap],
                                    nm, dicts[c]))
            pages.append(Page(tuple(blocks), out_m[off:off + cap]))
        routed.append(pages if live_w else [])
    return routed
