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
ExchangeClient.java). It is ONE plane: every fragment's drivers run
concurrently on one task executor; fragment boundaries are StreamingExchange
instances (parallel/streaming_exchange.py) moving fixed-capacity chunks
through one compiled collective per chunk while producers still run — the
ExchangeClient pull-while-producing shape, with byte-bounded backpressure on
both sides.

EVERY worker's drivers are enqueued on that one TaskExecutor and time-slice
across its runner threads (so 8 virtual workers never host-serialize;
build/probe pipelines of different workers overlap); the collective itself
always runs as one SPMD program over all workers.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Optional

from ..block import Dictionary
from ..exec.local_planner import LocalExecutionPlanner
from ..exec.shared_pools import next_query_key
from ..exec.task_executor import TaskExecutor
from ..metadata import CatalogManager, Session, default_page_capacity
from ..runner import LocalQueryRunner, QueryResult
from ..sql import tree as t
from ..sql.planner.add_exchanges import add_exchanges
from ..sql.planner.fragmenter import (Fragment, SINGLE_PART, SubPlan,
                                      fragment_plan)
from ..sql.planner.optimizer import optimize
from ..sql.planner.plan import (BROADCAST, MERGE, OutputNode, REPARTITION,
                                plan_to_text)
from ..sql.planner.planner import LogicalPlanner
from ..utils import trace
from ..utils.metrics import METRICS
from .mesh import MeshContext
# EXCHANGE_STATS re-exported here because the multichip dryrun (and history)
# imports it from this module
from .streaming_exchange import (EXCHANGE_STATS, ExchangeSinkOperatorFactory,  # noqa: F401
                                 ExchangeStatsBook, MESH_PAGE_ROWS,
                                 StreamingExchange)


class DistributedQueryRunner:
    """In-process multi-worker engine over a jax.sharding.Mesh."""

    def __init__(self, mesh: Optional[MeshContext] = None,
                 session: Optional[Session] = None,
                 catalogs: Optional[CatalogManager] = None,
                 page_capacity: Optional[int] = None):
        # page_capacity None = the platform's page, resolved at execution
        # (_execute_streaming) as the local runner resolves its own
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
        result = self._execute_streaming(sub, book, frag_drivers)
        snap = book.snapshot()
        if snap:
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
        session = self.session
        chunk_rows = int(session.get("exchange_chunk_rows") or 0)
        inflight = int(session.get("exchange_inflight_bytes") or 0)
        page_cap = int(session.get("page_capacity") or 0)
        if not page_cap:
            # the platform's page, as the local runner reads it, held to the
            # grain the mesh's data plane runs at; every fragment plans with it
            page_cap = min(default_page_capacity(), MESH_PAGE_ROWS)
            session = session.with_properties(page_capacity=page_cap)
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
                    lp = LocalExecutionPlanner(self.metadata, session,
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
                 f"{self.mesh.n_workers} workers", ""]
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
                    f"chunk_rows={exch.get('chunk_rows', 0)} "
                    f"fills={exch.get('fills', 0)} "
                    f"refills={exch.get('refills', 0)} "
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
