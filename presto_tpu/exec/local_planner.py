"""Local execution planner: logical plan -> driver pipelines.

Analogue of presto-main sql/planner/LocalExecutionPlanner.java:282,356 — the switch
point where physical operators are chosen (visitTableScan :1276, visitFilter :1135,
visitAggregation :1098, visitJoin :1570 -> HashBuilderOperatorFactory :1990,
visitTopN :963). Differences, TPU-first:

- Filter/Project chains are FUSED into one PageProcessor (one XLA kernel) and, when
  they sit directly on a scan, into the scan itself — the
  ScanFilterAndProjectOperator analogue, but the fusion is done by inlining
  RowExpressions and letting XLA compile the whole stage.
- Join build sides become their own pipelines ending in a JoinBuildOperatorFactory;
  probe pipelines block on the lookup-source future exactly like the reference's
  LookupSourceFactory handoff.
- Symbols resolve to channels here (SymbolRef -> InputRef), the same
  symbol->channel translation the reference does via its source layouts.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..block import Dictionary, Page
from ..metadata import MetadataManager, Session
from ..ops.aggregates import AggregateCall, resolve_aggregate
from ..ops.expressions import (Constant, InputLayout, RowExpression, SymbolRef,
                               call, input_ref, resolve_symbols, symbol_ref)
from ..ops.filter_project import FilterProjectOperatorFactory, PageProcessor
from ..ops.hash_agg import SINGLE, HashAggregationOperatorFactory
from ..ops.hash_join import (ANTI, FULL, INNER, LEFT, SEMI, JoinBuildOperatorFactory,
                             LookupJoinOperatorFactory, probe_plan_fusible)
from ..ops.scan import TableScanOperatorFactory
from ..ops.single_row import EnforceSingleRowOperatorFactory
from ..ops.topn import (LimitOperatorFactory, OrderByOperatorFactory, SortOrder,
                        TopNOperatorFactory)
from ..spi.connector import ConnectorPageSource, Constraint
from ..sql.planner.optimizer import and_all, split_and, substitute
from ..sql.planner.plan import (AggregationNode, EnforceSingleRowNode, FilterNode,
                                JoinNode, LimitNode, OutputNode, PlanNode,
                                ProjectNode, RemoteSourceNode, SemiJoinNode,
                                SortNode, Symbol,
                                TableScanNode, TopNNode, UnionNode, ValuesNode)
from ..types import BIGINT, BOOLEAN, Type, is_integral, is_string
from ..utils.testing import PageConsumerFactory
from ..exec.driver import Driver


# union dictionaries interned by VALUE so re-planning the same query
# yields the same Dictionary object (stable token -> kernel caches hit)
_UNION_DICTS: Dict[tuple, Dictionary] = {}


def _with_symbols(symbols: List[Symbol], more: List[Symbol]) -> List[Symbol]:
    """`symbols`, then each of `more` that is not among them yet."""
    out = list(symbols)
    for s in more:
        if all(s.name != o.name for o in out):
            out.append(s)
    return out


def _intern_union_dict(values: List[str]) -> Dictionary:
    key = tuple(values)
    d = _UNION_DICTS.get(key)
    if d is None:
        if len(_UNION_DICTS) > 256:
            _UNION_DICTS.clear()
        d = _UNION_DICTS[key] = Dictionary(values)
    return d


def _extract_constraint(filter_parts, scan: TableScanNode) -> Constraint:
    """Scan-filter conjuncts -> per-column [lo, hi] domains (TupleDomain
    extraction, narrowed to constant comparisons — what file/split pruning
    needs). Values are the engine's substrate ints (scaled decimals, date
    days, dictionary codes for equality on sorted dictionaries are NOT
    extracted — only numeric columns)."""
    import math

    from ..ops.expressions import Call, Constant, InputRef
    from ..types import DecimalType

    cols = {i: col for i, (_s, col) in enumerate(scan.assignments)}
    domains: Dict[str, List] = {}

    def bound(v, vt, ct, kind: str):
        """Constant in ITS representation (scaled decimal int, date days,
        float) -> a domain bound in the COLUMN's substrate units.

        kind: lo_ge | lo_gt | hi_le | hi_lt — strict bounds tighten AFTER
        the exact conversion (tightening in the constant's coarser scale
        then upscaling would narrow the domain and drop satisfying rows).
        Integer paths use exact integer arithmetic — float round-trips
        above 2^53 could likewise narrow a domain."""
        if is_string(ct):
            fl = cl = v  # dictionary code compare: units already match
            exact = True
        else:
            s_from = vt.scale if isinstance(vt, DecimalType) else 0
            s_to = ct.scale if isinstance(ct, DecimalType) else 0
            if ct.name in ("double", "real"):
                # continuous substrate: strict bounds stay inclusive
                # (over-approximation, the engine filter refines)
                return float(v) / (10 ** s_from) if s_from else float(v)
            if isinstance(v, int):
                if s_to >= s_from:
                    fl = cl = v * 10 ** (s_to - s_from)
                    exact = True
                else:
                    q, r = divmod(v, 10 ** (s_from - s_to))  # // floors
                    fl, cl, exact = q, q + (1 if r else 0), r == 0
            else:
                x = v * (10 ** (s_to - s_from)) if s_to != s_from else v
                fl, cl = math.floor(x), math.ceil(x)
                exact = fl == cl
        if kind == "hi_le":
            return fl
        if kind == "hi_lt":
            return fl - 1 if exact else fl
        if kind == "lo_ge":
            return cl
        return cl + 1 if exact else cl  # lo_gt

    def note(ch: int, kind: str, v, vt):
        col = cols.get(ch)
        if col is None:
            return
        cur = domains.setdefault(col.name, [None, None])
        b = bound(v, vt, col.type, kind)
        if kind.startswith("lo"):
            cur[0] = b if cur[0] is None else max(cur[0], b)
        else:
            cur[1] = b if cur[1] is None else min(cur[1], b)

    # flatten AND trees first: a multi-conjunct WHERE arrives as ONE nested
    # conjunction, and every conjunct may contribute a domain bound (Q6's
    # shipdate/discount/quantity ranges drive both split pruning and the
    # scan pipeline's native pre-filter compaction)
    for part in (c for fp in filter_parts for c in split_and(fp)):
        if not isinstance(part, Call) or len(part.args) != 2:
            continue
        a, b = part.args
        if isinstance(a, Constant) and isinstance(b, InputRef):
            flip = {"less_than": "greater_than",
                    "less_than_or_equal": "greater_than_or_equal",
                    "greater_than": "less_than",
                    "greater_than_or_equal": "less_than_or_equal",
                    "equal": "equal"}.get(part.name)
            if flip is None:
                continue
            a, b, name = b, a, flip
        elif isinstance(a, InputRef) and isinstance(b, Constant):
            name = part.name
        else:
            continue
        v = b.value
        if v is None or isinstance(v, str):
            continue
        if name == "equal":
            note(a.channel, "lo_ge", v, b.type)
            note(a.channel, "hi_le", v, b.type)
        elif name == "less_than":
            note(a.channel, "hi_lt", v, b.type)
        elif name == "less_than_or_equal":
            note(a.channel, "hi_le", v, b.type)
        elif name == "greater_than":
            note(a.channel, "lo_gt", v, b.type)
        elif name == "greater_than_or_equal":
            note(a.channel, "lo_ge", v, b.type)
    return Constraint({k: tuple(v) for k, v in domains.items()}) \
        if domains else Constraint.all()


class _ConcatPageSource(ConnectorPageSource):
    def __init__(self, sources):
        self.sources = list(sources)

    @property
    def external_wait(self):
        """One externally-blocking child (a remote-connector source) makes
        the whole concat ineligible for the shared scan pool."""
        return any(getattr(s, "external_wait", False) for s in self.sources)

    def __iter__(self):
        for s in self.sources:
            yield from s

    @property
    def cache_token(self):
        """Deterministic iff every child is; token = tuple of child tokens."""
        toks = tuple(getattr(s, "cache_token", None) for s in self.sources)
        if any(t is None for t in toks):
            return None
        return ("concat",) + toks

    def split_readers(self, target_rows: int):
        """Concatenated split decomposition (scan-pipeline SPI): the child
        streams' range readers in stream order — re-batching then fills
        device-shaped pages ACROSS file boundaries. All-or-nothing: one
        child without split support keeps the whole concat serial, so
        output order always matches serial iteration."""
        out = []
        for s in self.sources:
            rs = s.split_readers(target_rows)
            if rs is None:
                return None
            out.extend(rs)
        return out

    def close(self) -> None:
        # best-effort per source: a raising close must not skip the rest
        for s in self.sources:
            try:
                s.close()
            except Exception:
                pass  # close of the remaining sources is best-effort


@dataclasses.dataclass
class Chain:
    """A pipeline under construction + its output layout."""
    factories: List
    symbols: List[Symbol]
    dicts: List[Optional[Dictionary]]

    def channel(self, name: str) -> int:
        for i, s in enumerate(self.symbols):
            if s.name == name:
                return i
        raise KeyError(f"symbol {name} not in layout "
                       f"{[s.name for s in self.symbols]}")

    def channel_map(self) -> Dict[str, int]:
        return {s.name: i for i, s in enumerate(self.symbols)}

    def layout(self) -> InputLayout:
        return InputLayout([s.type for s in self.symbols], list(self.dicts))

    def meta(self, names: Sequence[str]) -> List[Tuple[Type, Optional[Dictionary]]]:
        idx = self.channel_map()
        return [(self.symbols[idx[n]].type, self.dicts[idx[n]]) for n in names]


class RemoteSourceSlot:
    """Per-fragment exchange endpoint: the runner wires the producer's
    stream in here after planning (the consumer half of the reference's
    OutputBuffer -> ExchangeClient pair)."""

    def __init__(self, fragment_id: int):
        self.fragment_id = fragment_id
        # cluster mode plugs a streaming HTTP source in here (callable
        # worker -> ConnectorPageSource)
        self.source_factory = None
        # set by plan_subplan for MERGE inputs: [(channel, desc, nulls_first)]
        # — the cluster task wires a MergingRemoteSource instead of the
        # interleaving StreamingRemoteSource
        self.merge_orderings = None
        # the mesh runner: a parallel/streaming_exchange.StreamingExchange
        # attached after planning and before driver creation — consumers
        # block on chunk arrival
        self.stream = None


class RemoteSourceOperatorFactory(TableScanOperatorFactory):
    """Exchange endpoint factory (ExchangeOperator.java:35 analogue).

    The mode is decided at DRIVER-CREATION time, after the runner has wired
    the slot: with a StreamingExchange attached, consumers are
    LocalExchangeSources over the exchange's per-worker chunk queue —
    blocking on chunk arrival while the producer fragment still runs; the
    cluster keeps the inherited TableScanOperator over its streaming HTTP
    source_factory."""

    def __init__(self, operator_id: int, slot: RemoteSourceSlot,
                 types: List[Type]):
        super().__init__(operator_id, lambda w: [slot.source_factory(w)],
                         types, None)
        self.name = "RemoteSource"
        self.slot = slot

    def create_operator(self, worker: int = 0):
        stream = self.slot.stream
        if stream is not None:
            from ..parallel.streaming_exchange import StreamingExchangeSource
            return StreamingExchangeSource(self.context(worker),
                                           stream.out_buffer(worker),
                                           list(self._types))
        return super().create_operator(worker)


@dataclasses.dataclass
class LocalExecutionPlan:
    pipelines: List[List[object]]   # factory chains, dependency order
    sink: PageConsumerFactory
    output_names: List[str]
    output_types: List[Type] = dataclasses.field(default_factory=list)
    output_dicts: List[Optional[Dictionary]] = dataclasses.field(default_factory=list)
    remote_slots: Dict[int, RemoteSourceSlot] = dataclasses.field(default_factory=dict)
    # segment-compiler fusion decisions (exec/fused_segment): one entry per
    # candidate run of page-local operators, fused or not, with the reason
    segment_decisions: List[dict] = dataclasses.field(default_factory=list)
    narrowed_joins: List[str] = dataclasses.field(default_factory=list)

    def create_drivers(self, worker: int = 0) -> List[Driver]:
        """Instantiate one driver set for `worker`. The factory list is planned
        ONCE per fragment and shared by every worker, so jitted kernels compile
        once; per-worker state (splits, lookup slots, sinks) is keyed off the
        worker index."""
        drivers = []
        for chain in self.pipelines:
            k = getattr(chain[0], "parallel_drivers", 1)
            for _ in range(k):
                drivers.append(
                    Driver([f.create_operator(worker) for f in chain]))
        return drivers


class LocalExecutionPlanner:
    """One instance per query fragment (shared by all its worker tasks).

    `n_workers` scopes table scans: worker w of n reads splits w, w+n, ...
    (SOURCE distribution: SqlStageExecution split assignment analogue).
    RemoteSourceNodes plan into RemoteSourceSlots exposed on the plan; the
    distributed runner fills them per worker after each exchange collective."""

    def __init__(self, metadata: MetadataManager, session: Session,
                 n_workers: int = 1,
                 remote_dicts: Optional[Dict[int, List[Optional[Dictionary]]]] = None,
                 devices=None, bucket_filter: Optional[int] = None,
                 pool_key: Optional[str] = None,
                 remote_roots: Optional[Dict[int, PlanNode]] = None):
        self.metadata = metadata
        self.session = session
        from ..metadata import default_page_capacity
        cap = session.get("page_capacity")
        self.page_capacity = int(cap) if cap else default_page_capacity()
        # streaming scan pipeline knobs (ops/scan_pipeline.py), resolved once
        # per fragment. target rows default to the canonical page capacity so
        # every scan feeds kernels ONE shape; 0/None knobs fall through to
        # ScanPipeline's engine defaults (single source of truth)
        threads = session.get("scan_reader_threads")
        rows = session.get("scan_target_page_rows")
        # shared_pools: scan stages run on the process-wide SCAN_POOL under
        # ONE fairness slot per query (callers planning several fragments of
        # one query pass the same pool_key); False = per-query stage threads,
        # the differential oracle
        if bool(session.get("shared_pools", True)):
            from .shared_pools import next_query_key
            pool_key = pool_key or next_query_key()
        else:
            pool_key = None
        self.pool_key = pool_key
        self.scan_options = {
            "rebatch": bool(session.get("scan_pipeline", True)),
            "reader_threads": int(threads) if threads else None,
            "target_rows": int(rows) if rows else self.page_capacity,
            "prefetch_bytes": int(session.get("scan_prefetch_bytes") or 0)
            or None,
            "pool_key": pool_key,
        }
        self.n_workers = n_workers
        # grouped (lifespan) execution: restrict every scan to this bucket's
        # splits (exec/grouped.py drives one planner per lifespan)
        self.bucket_filter = bucket_filter
        # worker -> device placement (distributed mode): scans upload worker
        # w's pages to mesh device w so fragment chains stay device-resident
        self.devices = devices
        # producer fragment id -> its output dictionaries (a plan-time property:
        # the runner plans fragments bottom-up and feeds each consumer the dicts
        # of its already-planned producers)
        self.remote_dicts = remote_dicts or {}
        # producer fragment id -> its plan: lets _keys_unique carry a build
        # side's key uniqueness across the exchange that feeds it
        self.remote_roots = remote_roots or {}
        self.remote_slots: Dict[int, RemoteSourceSlot] = {}
        self._ids = itertools.count()
        self.pipelines: List[List[object]] = []
        # joins keyed on one unique clause with the others as a filter
        # (_narrowed_clause), one line each for EXPLAIN ANALYZE
        self.narrowed_joins: List[str] = []

    # ------------------------------------------------------------------ api

    def attach_memory(self, memory, revoke_check=None, spill=None) -> None:
        """Wire a query-level MemoryTrackingContext (+ pressure probe, + the
        query's disk-tier SpillManager) into every planned factory —
        operators then account bytes into the query's pool and self-revoke
        under pressure, escalating host state to disk when `spill` is set.
        The runner hangs the manager off the memory context (`memory.spill`)
        so existing call sites that splat (memory, revoke_check) pick up the
        disk tier without a signature change."""
        self._memory_ctx = memory
        self._revoke_check = revoke_check
        self._spill = spill if spill is not None \
            else getattr(memory, "spill", None)

    def plan(self, root: OutputNode, sink_factory=None) -> LocalExecutionPlan:
        """`sink_factory`: optional callable (types, dicts) -> OperatorFactory
        replacing the default page-buffer sink (cluster tasks sink into their
        partitioned output buffers instead)."""
        chain = self.visit(root.source)
        # final projection into the user's column order
        want = [s.name for s in root.symbols]
        have = [s.name for s in chain.symbols]
        if want != have:
            chain = self._append_project(
                chain, [(s, symbol_ref(s.name, s.type)) for s in root.symbols])
        if sink_factory is not None:
            sink = sink_factory([s.type for s in chain.symbols],
                                list(chain.dicts))
        else:
            sink = PageConsumerFactory(next(self._ids),
                                       [s.type for s in chain.symbols])
        self._add_pipeline(chain.factories + [sink])
        # segment fusion BEFORE memory wiring: fused factories must receive
        # the query memory context too (they forward it to their terminal)
        decisions = self._fuse_pipelines()
        mem = getattr(self, "_memory_ctx", None)
        if mem is not None:
            check = getattr(self, "_revoke_check", None)
            spill = getattr(self, "_spill", None)
            for pipeline in self.pipelines:
                for fac in pipeline:
                    fac.memory_ctx = mem
                    fac.revoke_check = check
                    fac.spill_manager = spill
        for pipeline in self.pipelines:
            for fac in pipeline:
                if isinstance(fac, TableScanOperatorFactory):
                    if self.devices is not None:
                        fac.devices = self.devices
                    if fac.scan_options is None:
                        fac.scan_options = self.scan_options
        return LocalExecutionPlan(self.pipelines, sink, root.column_names,
                                  [s.type for s in chain.symbols],
                                  list(chain.dicts), self.remote_slots,
                                  decisions, self.narrowed_joins)

    # ------------------------------------------------------ segment fusion

    def _fuse_pipelines(self) -> List[dict]:
        """Pipeline-segment compiler: replace each maximal run of fusible
        page-local operator factories (filter/project -> page-local join
        probe -> partial hash-agg / TopN contribution) with ONE
        FusedSegmentOperatorFactory whose whole chain traces into a single
        jitted dispatch per page (ops/fused_segment.py). Single-operator
        runs stay unfused (nothing to merge); blocking operators, join
        builds, exchanges and sorts are barriers. `segment_fusion = False`
        keeps the per-operator pipeline as the differential-testing oracle."""
        from ..ops.fused_segment import (FusedSegmentOperatorFactory,
                                         mid_stage_fusible,
                                         terminal_stage_fusible)

        decisions: List[dict] = []
        if not self.session.get("segment_fusion", True):
            return decisions
        for pi, chain in enumerate(self.pipelines):
            out = [chain[0]]  # the source operator never fuses
            i = 1
            while i < len(chain):
                if not (mid_stage_fusible(chain[i]) or
                        terminal_stage_fusible(chain[i])):
                    out.append(chain[i])
                    i += 1
                    continue
                run: List[object] = []
                while i < len(chain) and mid_stage_fusible(chain[i]):
                    run.append(chain[i])
                    i += 1
                terminal = None
                if i < len(chain) and terminal_stage_fusible(chain[i]):
                    terminal = chain[i]
                    i += 1
                members = run + ([terminal] if terminal is not None else [])
                entry = {"pipeline": pi,
                         "operators": [m.name for m in members]}
                if len(members) >= 2:
                    types, dicts = self._segment_output_meta(members[-1])
                    out.append(FusedSegmentOperatorFactory(
                        next(self._ids), run, terminal, types, dicts))
                    entry["fused"] = True
                else:
                    out.extend(members)
                    entry["fused"] = False
                    entry["reason"] = "single-operator run"
                decisions.append(entry)
            self.pipelines[pi] = out  # prestocheck: ignore[shared-state-race] - planner instance is per-task: built and read on the one thread planning that task, never shared
        return decisions

    @staticmethod
    def _segment_output_meta(last) -> Tuple[List[Type], List]:
        """Output (types, dicts) of a segment = those of its last member."""
        if isinstance(last, HashAggregationOperatorFactory):
            out = list(last.key_types)
            dicts = list(last.key_dicts)
            for c in last.calls:
                if last.step == "partial":
                    out.extend(c.function.intermediate_types)
                    dicts.extend([None] * len(c.function.intermediate_types))
                else:
                    out.append(c.function.output_type)
                    dicts.append(c.output_dictionary)
            return out, dicts
        if isinstance(last, TopNOperatorFactory):
            return list(last.types), list(last.dicts)
        if isinstance(last, FilterProjectOperatorFactory):
            return list(last.processor.output_types), \
                list(last.processor.output_dicts)
        assert isinstance(last, LookupJoinOperatorFactory), type(last)
        return list(last.output_types), \
            [d for _, d in last.probe_output_meta] + \
            [d for _, d in last.build_output_meta]

    # --------------------------------------------------- driver parallelism

    def _add_pipeline(self, factories: List) -> None:
        """Append a pipeline, splitting its stateless scan prefix into N
        parallel drivers behind a local exchange when profitable
        (reference parallelism axis #4: N Drivers per pipeline, fed by split
        assignment; AddLocalExchanges + LocalExchange.java:52).

        Split rule: the chain starts with a multi-split table scan, the
        prefix of {scan, filter/project, lookup-join probe} is followed by at
        least one stateful operator, and task_concurrency allows > 1 driver.
        Producers run the prefix per split-group; the stateful tail runs as
        ONE consumer driver downstream of the exchange."""
        from ..ops.filter_project import FilterProjectOperatorFactory
        from ..ops.hash_join import LookupJoinOperatorFactory
        from ..ops.local_exchange import (LocalExchangeFactory,
                                          LocalExchangeSinkFactory,
                                          LocalExchangeSourceFactory)
        from ..ops.scan import TableScanOperatorFactory

        # driver_parallelism AUTO engages only off-CPU: XLA-CPU kernels already
        # use every host core, so extra driver threads just contend; on TPU the
        # extra drivers overlap host generation/upload with device compute
        setting = self.session.get("driver_parallelism")
        if setting in (None, "AUTO", "auto"):
            import jax

            conc = int(self.session.get("task_concurrency")) \
                if jax.default_backend() != "cpu" else 1
        else:
            conc = int(setting)
        head = factories[0]
        n_sources = getattr(getattr(head, "_sources_fn", None),
                            "sources_per_worker", 1)
        n = min(conc, n_sources)
        if n <= 1 or not isinstance(head, TableScanOperatorFactory) or \
                getattr(head, "_prefetch", True) is False:
            self.pipelines.append(factories)
            return
        def prefix_safe(f) -> bool:
            if isinstance(f, FilterProjectOperatorFactory):
                return True
            if isinstance(f, LookupJoinOperatorFactory):
                # FULL joins emit unmatched BUILD rows at probe finish — that
                # pass must run exactly once, so such probes stay single-driver
                return f.join_type != FULL
            return False

        from ..ops.hash_join import JoinBuildOperatorFactory

        cut = 1
        while cut < len(factories) and prefix_safe(factories[cut]):
            cut += 1
        if cut == len(factories) - 1 and \
                isinstance(factories[-1], JoinBuildOperatorFactory):
            # partitioned parallel hash build: the whole chain runs as n
            # drivers, each with its OWN build accumulator; the last to
            # finish merges and publishes the lookup source
            # (PartitionedLookupSourceFactory, reference parallelism axis #5)
            head.set_parallelism(n)
            head.parallel_drivers = n
            self.pipelines.append(factories)
            return
        if cut >= len(factories) - 1:
            self.pipelines.append(factories)   # nothing stateful before sink
            return
        head.set_parallelism(n)
        head.parallel_drivers = n
        # bounded: these pipelines always run under the task executor, so a
        # full buffer parks producers (BLOCKED) instead of growing HBM
        lx = LocalExchangeFactory(n_producers=n, max_pages=2 * n + 2)
        sink = LocalExchangeSinkFactory(next(self._ids), lx, [])
        source = LocalExchangeSourceFactory(next(self._ids), lx, [])
        self.pipelines.append(factories[:cut] + [sink])
        self.pipelines.append([source] + factories[cut:])

    # ------------------------------------------------------------ dispatch

    def visit(self, node: PlanNode) -> Chain:
        if isinstance(node, (FilterNode, ProjectNode)):
            return self.visit_fused_stage(node)
        m = getattr(self, f"visit_{type(node).__name__}", None)
        if m is None:
            raise NotImplementedError(
                f"local planning for {type(node).__name__}")
        return m(node)

    # ------------------------------------------------- scan + fused stages

    def visit_fused_stage(self, node: PlanNode) -> Chain:
        """Collapse a Filter/Project chain into one PageProcessor; fuse into the
        scan when the chain bottoms out at a TableScanNode."""
        stack: List[PlanNode] = []
        cur = node
        while isinstance(cur, (FilterNode, ProjectNode)):
            stack.append(cur)
            cur = cur.children()[0]

        if isinstance(cur, TableScanNode):
            base = self._scan_layout(cur)
            mapping = {s.name: input_ref(i, s.type)
                       for i, (s, _) in enumerate(cur.assignments)}
        else:
            base = self.visit(cur)
            mapping = {s.name: input_ref(i, s.type)
                       for i, s in enumerate(base.symbols)}

        filter_parts: List[RowExpression] = []
        out_symbols = cur.outputs() if isinstance(cur, TableScanNode) else base.symbols
        for n in reversed(stack):
            if isinstance(n, FilterNode):
                filter_parts.append(substitute(n.predicate, mapping))
            else:
                mapping = {s.name: substitute(e, mapping)
                           for s, e in n.assignments}
                out_symbols = [s for s, _ in n.assignments]

        projections = [mapping[s.name] for s in out_symbols]
        processor = PageProcessor(base.layout() if isinstance(base, Chain)
                                  else base, and_all(filter_parts), projections)
        if isinstance(cur, TableScanNode):
            constraint = _extract_constraint(filter_parts, cur)
            sources = self._page_sources(cur, constraint)
            fac = TableScanOperatorFactory(next(self._ids), sources,
                                           processor.output_types, processor)
            fac.has_filter = processor.filter is not None
            return Chain([fac], list(out_symbols), processor.output_dicts)
        fac = FilterProjectOperatorFactory(next(self._ids), processor=processor)
        return Chain(base.factories + [fac], list(out_symbols),
                     processor.output_dicts)

    def _scan_layout(self, node: TableScanNode) -> InputLayout:
        meta = self.metadata.get_table_metadata(node.table)
        dicts = []
        for sym, col in node.assignments:
            dicts.append(meta.column(col.name).dictionary)
        return InputLayout([s.type for s, _ in node.assignments], dicts)

    def _page_sources(self, node: TableScanNode,
                      constraint: Optional[Constraint] = None):
        """-> callable worker -> [page source]: splits dealt round-robin over
        the fragment's workers, one concatenated source (= one driver) each.
        `constraint` carries pushed-down column ranges so split managers can
        prune (file stats, key ranges)."""
        conn = self.metadata.connector(node.table.connector_id)
        constraint = constraint or Constraint.all()
        splits = conn.split_manager().get_splits(node.table, constraint, 8)
        if self.bucket_filter is not None:
            splits = [s for s in splits if s.bucket == self.bucket_filter]
        cols = [c for _, c in node.assignments]
        provider = conn.page_source_provider()
        count = self.n_workers

        def for_worker(w: int):
            mine = [s for i, s in enumerate(splits) if i % count == w]
            return [_ConcatPageSource(
                provider.create_page_source(s, cols, self.page_capacity,
                                            constraint)
                for s in mine)]
        for_worker.sources_per_worker = max(
            1, -(-len(splits) // max(count, 1)))
        return for_worker

    def visit_TableScanNode(self, node: TableScanNode) -> Chain:
        layout = self._scan_layout(node)
        projections = [input_ref(i, s.type)
                       for i, (s, _) in enumerate(node.assignments)]
        processor = PageProcessor(layout, None, projections)
        fac = TableScanOperatorFactory(next(self._ids), self._page_sources(node),
                                       processor.output_types, processor)
        return Chain([fac], [s for s, _ in node.assignments],
                     processor.output_dicts)

    def visit_RemoteSourceNode(self, node) -> Chain:
        """Replay each worker's exchange-output pages (ExchangeOperator.java:35
        analogue — the collective already ran; this is the local endpoint). The
        slot is filled by the runner between fragment executions."""
        slot = self.remote_slots.get(node.fragment_id)
        if slot is None:
            slot = self.remote_slots[node.fragment_id] = \
                RemoteSourceSlot(node.fragment_id)
        fac = RemoteSourceOperatorFactory(
            next(self._ids), slot, [s.type for s in node.symbols])
        dicts = self.remote_dicts.get(node.fragment_id,
                                      [None] * len(node.symbols))
        out = Chain([fac], list(node.symbols), list(dicts))
        if node.fragment_id not in self.remote_dicts:
            # unknown producer dicts: None entries may hide LIVE codes
            out.unreliable_dicts = True
        return out

    def visit_ValuesNode(self, node: ValuesNode) -> Chain:
        cap = max(len(node.rows), 1)
        blocks = []
        dicts: List[Optional[Dictionary]] = []
        for i, sym in enumerate(node.symbols):
            vals = [r[i] for r in node.rows]
            if is_string(sym.type):
                from ..block import block_from_strings
                b = block_from_strings(vals, sym.type)
            else:
                arr = np.zeros(cap, dtype=sym.type.np_dtype)
                nulls = np.zeros(cap, dtype=np.bool_)
                for j, v in enumerate(vals):
                    if v is None:
                        nulls[j] = True
                    else:
                        arr[j] = v
                from ..block import Block
                b = Block(sym.type, arr, nulls if nulls.any() else None, None)
            blocks.append(b)
            dicts.append(b.dictionary)
        mask = np.arange(cap) < len(node.rows)
        page = Page(tuple(blocks), mask)
        from ..spi.connector import FixedPageSource
        # literal rows exist ONCE globally: only worker 0 materializes them
        # (a SOURCE-partitioned fragment runs on every worker — emitting the
        # page on each would multiply VALUES rows by the worker count)
        fac = TableScanOperatorFactory(
            next(self._ids),
            lambda w: [FixedPageSource([page] if w == 0 else [])],
            [s.type for s in node.symbols], None)
        return Chain([fac], list(node.symbols), dicts)

    # ------------------------------------------------------------- joins

    def _maybe_coalesce(self, chain: Chain,
                        feeds_unfused_probe: bool = False) -> Chain:
        """Insert a page-coalescing stage where a chain that feeds a join
        DROPS rows at its end: the join's per-page kernel work (and its
        per-page dispatches) then scales with the survivors instead of the
        scanned capacity. Two endings drop rows: a FILTERED scan, on either
        side of the join; and, with `feeds_unfused_probe` (the join being
        planned probes alone, so it is a segment barrier anyway), an
        INNER/SEMI/ANTI probe. A fusible probe gets none behind another
        probe: it would cut their fused segment in two. The operator itself
        adapts at runtime — a stream whose first page is mostly live
        switches it to permanent pass-through (ops/coalesce.py)."""
        if not chain.factories:
            return chain
        last = chain.factories[-1]
        drops_rows = getattr(last, "has_filter", False) or (
            feeds_unfused_probe
            and isinstance(last, LookupJoinOperatorFactory)
            and last.join_type in (INNER, SEMI, ANTI))
        if not drops_rows:
            return chain
        from ..ops.coalesce import CoalesceOperatorFactory

        fac = CoalesceOperatorFactory(
            next(self._ids), [s.type for s in chain.symbols],
            list(chain.dicts))
        return Chain(chain.factories + [fac], chain.symbols, chain.dicts)

    def visit_JoinNode(self, node: JoinNode) -> Chain:
        if not node.criteria:
            return self._plan_cross_join(node)
        jt = self._join_type(node)
        kept = self._narrowed_clause(node, jt)
        criteria = [kept] if kept is not None else list(node.criteria)
        checked = [c for c in node.criteria if c not in criteria]
        left_keys = [l for l, _ in criteria]
        right_keys = [r for _, r in criteria]
        unique = kept is not None or self._keys_unique(node.right, right_keys)
        probe_chain = self._maybe_coalesce(
            self.visit(node.left),
            feeds_unfused_probe=not probe_plan_fusible(jt, left_keys, unique))
        build_chain = self._maybe_coalesce(self.visit(node.right))

        build_key_ch = [build_chain.channel(r.name) for r in right_keys]
        probe_key_ch = [probe_chain.channel(l.name) for l in left_keys]

        out_syms = node.outputs()
        probe_names = {s.name for s in probe_chain.symbols}
        probe_out = [s for s in out_syms if s.name in probe_names]
        build_out = [s for s in out_syms if s.name not in probe_names]
        # the clauses checked behind the probe read both sides' columns
        probe_cols = _with_symbols(probe_out, [l for l, _ in checked])
        build_cols = _with_symbols(build_out, [r for _, r in checked])

        payload_names = [s.name for s in build_cols]
        payload_ch = [build_chain.channel(n) for n in payload_names]
        payload_meta = build_chain.meta(payload_names)

        build_fac = JoinBuildOperatorFactory(
            next(self._ids), build_key_ch, payload_ch, payload_meta,
            unique=unique,
            track_unmatched=node.type == "full",
            narrowed=kept is not None)
        self._add_pipeline(build_chain.factories + [build_fac])

        probe_out_ch = [probe_chain.channel(s.name) for s in probe_cols]
        probe_meta = probe_chain.meta([s.name for s in probe_cols])
        probe_fac = LookupJoinOperatorFactory(
            next(self._ids), build_fac.lookup_factory, probe_key_ch,
            probe_out_ch, probe_meta, list(range(len(payload_ch))),
            payload_meta, jt, unique_build=unique)
        out_dicts = [probe_chain.dicts[c] for c in probe_out_ch] + \
                    [d for _, d in payload_meta]
        joined = Chain(probe_chain.factories + [probe_fac],
                       probe_cols + build_cols, out_dicts)
        if not checked:
            return joined
        condition = and_all([call("equal", BOOLEAN,
                                  symbol_ref(l.name, l.type),
                                  symbol_ref(r.name, r.type))
                             for l, r in checked])
        self.narrowed_joins.append(
            f"{probe_fac.name} on {kept[0]} = {kept[1]}, filter {condition}")
        return self._append_project(
            joined, [(s, symbol_ref(s.name, s.type))
                     for s in probe_out + build_out], condition)

    def _narrowed_clause(self, node: JoinNode,
                         jt: str) -> Optional[Tuple[Symbol, Symbol]]:
        """The one clause an INNER join of several clauses is keyed on when
        its build column alone is a unique integer: the build is then a
        single-key unique one (a direct-address table, a probe that fuses),
        and the other clauses, checked as SQL equalities on the joined row,
        drop exactly the rows they would have failed to match, NULLs
        included, since at most one build row matches a probe row. LEFT and
        FULL joins keep every clause (a filter behind the probe would drop
        rows they must emit with NULL build columns); so does a clause over
        strings, whose dictionaries an equality does not reconcile."""
        if jt != INNER or len(node.criteria) < 2:
            return None
        if any(l.type != r.type or is_string(l.type)
               for l, r in node.criteria):
            return None
        for l, r in node.criteria:
            if is_integral(r.type) and self._keys_unique(node.right, [r]):
                return l, r
        return None

    def _plan_cross_join(self, node: JoinNode) -> Chain:
        """Cross join via constant-key lookup join: both sides project a literal 0
        key; the build side is expected to be tiny (scalar subqueries)."""
        zero = Constant(BIGINT, 0)
        left = self.visit(node.left)
        right = self.visit(node.right)
        ck_l = Symbol("$xkey_probe", BIGINT)
        ck_r = Symbol("$xkey_build", BIGINT)
        left = self._append_project(
            left, [(s, symbol_ref(s.name, s.type)) for s in left.symbols] +
            [(ck_l, zero)])
        right = self._append_project(
            right, [(s, symbol_ref(s.name, s.type)) for s in right.symbols] +
            [(ck_r, zero)])

        out_syms = node.outputs()
        right_names = {s.name for s in node.right.outputs()}
        probe_out = [s for s in out_syms if s.name not in right_names]
        build_out = [s for s in out_syms if s.name in right_names]
        payload_ch = [right.channel(s.name) for s in build_out]
        payload_meta = right.meta([s.name for s in build_out])
        build_fac = JoinBuildOperatorFactory(
            next(self._ids), [right.channel(ck_r.name)], payload_ch,
            payload_meta,
            unique=isinstance(node.right, EnforceSingleRowNode))
        self._add_pipeline(right.factories + [build_fac])
        probe_out_ch = [left.channel(s.name) for s in probe_out]
        probe_meta = left.meta([s.name for s in probe_out])
        probe_fac = LookupJoinOperatorFactory(
            next(self._ids), build_fac.lookup_factory,
            [left.channel(ck_l.name)], probe_out_ch, probe_meta,
            list(range(len(payload_ch))), payload_meta, self._join_type(node),
            unique_build=build_fac.unique)
        out_dicts = [left.dicts[c] for c in probe_out_ch] + \
                    [d for _, d in payload_meta]
        return Chain(left.factories + [probe_fac], probe_out + build_out,
                     out_dicts)

    def visit_SemiJoinNode(self, node: SemiJoinNode) -> Chain:
        src = self.visit(node.source)
        filt = self.visit(node.filtering_source)

        # residual filter (decorrelated EXISTS with non-equi correlated
        # conjuncts, Q21): compile over [probe residual cols..., build residual
        # cols...] and evaluate per candidate (source,filtering) pair — the
        # JoinFilterFunctionCompiler analogue wired into _emit_semi_expanded
        filter_fn = None
        filter_key = None
        filter_probe_ch: List[int] = []
        filter_build_ch: List[int] = []
        payload_ch: List[int] = []
        payload_meta: List[Tuple[Type, Optional[Dictionary]]] = []
        if node.residual is not None:
            from ..ops.expressions import ExpressionCompiler
            from ..sql.planner.optimizer import symbols_in
            rsyms = symbols_in(node.residual)
            src_names = {s.name for s in src.symbols}
            probe_list = sorted(n for n in rsyms if n in src_names)
            build_list = sorted(n for n in rsyms if n not in src_names)
            filter_probe_ch = [src.channel(n) for n in probe_list]
            payload_ch = [filt.channel(n) for n in build_list]
            payload_meta = filt.meta(build_list)
            filter_build_ch = list(range(len(build_list)))
            mapping = {n: i for i, n in enumerate(probe_list)}
            mapping.update({n: len(probe_list) + i
                            for i, n in enumerate(build_list)})
            layout = InputLayout(
                [src.symbols[c].type for c in filter_probe_ch] +
                [t for t, _ in payload_meta],
                [src.dicts[c] for c in filter_probe_ch] +
                [d for _, d in payload_meta])
            resolved = resolve_symbols(node.residual, mapping)
            filter_fn = ExpressionCompiler(layout).compile(resolved)
            from ..utils import kernel_cache as kc
            filter_key = (kc.expr_key(resolved),
                          kc.layout_key(layout.types, layout.dictionaries))

        build_fac = JoinBuildOperatorFactory(
            next(self._ids), [filt.channel(node.filtering_key.name)],
            payload_ch, payload_meta, unique=False)
        self._add_pipeline(filt.factories + [build_fac])
        out_ch = list(range(len(src.symbols)))
        meta = src.meta([s.name for s in src.symbols])
        jt = ANTI if node.negated else SEMI
        semi_mark = None
        if node.mark is not None:
            raise NotImplementedError("mark semi join arrives with the "
                                      "subquery-expression rev")
        fac = LookupJoinOperatorFactory(
            next(self._ids), build_fac.lookup_factory,
            [src.channel(node.source_key.name)], out_ch, meta, [], [], jt,
            semi_output_channel=semi_mark, null_aware=node.null_aware,
            filter_fn=filter_fn, filter_probe_channels=filter_probe_ch,
            filter_build_channels=filter_build_ch, filter_key=filter_key)
        return Chain(src.factories + [fac], list(src.symbols), list(src.dicts))

    @staticmethod
    def _join_type(node: JoinNode) -> str:
        if node.type == "inner":
            return INNER
        if node.type == "left":  # RIGHT was flipped to LEFT by the planner
            return LEFT
        if node.type == "full":
            return FULL
        raise NotImplementedError(f"{node.type} join")

    def _keys_unique(self, node: PlanNode, keys: List[Symbol],
                     remote: bool = False) -> bool:
        """Conservative uniqueness proof for the build keys. `remote`: the
        walk has crossed an exchange, and only what holds over ALL of the
        producing fragment's tasks together still counts (a scan's splits
        are disjoint; a partial aggregation's groups repeat across tasks)."""
        names = {k.name for k in keys}
        if isinstance(node, TableScanNode):
            by_symbol = {s.name: c.name for s, c in node.assignments}
            cols = {by_symbol[n] for n in names if n in by_symbol}
            if len(cols) != len(names):
                return False
            conn_meta = self.metadata.connector(
                node.table.connector_id).metadata()
            for uset in conn_meta.get_unique_column_sets(node.table):
                if set(uset) <= cols:
                    return True
            return False
        if isinstance(node, FilterNode):
            return self._keys_unique(node.source, keys, remote)
        if isinstance(node, ProjectNode):
            inner = []
            for k in keys:
                e = dict((s.name, x) for s, x in node.assignments).get(k.name)
                if not isinstance(e, SymbolRef):
                    return False
                inner.append(Symbol(e.name, e.type))
            return self._keys_unique(node.source, inner, remote)
        if isinstance(node, SemiJoinNode):
            return self._keys_unique(node.source, keys, remote)
        if isinstance(node, RemoteSourceNode):
            # an exchange moves rows, and hands a worker no row twice
            root = self.remote_roots.get(node.fragment_id)
            return root is not None and self._keys_unique(root, keys, True)
        if remote:
            return False
        if isinstance(node, AggregationNode):
            return {k.name for k in node.keys} <= names
        if isinstance(node, EnforceSingleRowNode):
            return True
        return False

    # ------------------------------------------------------- aggregation

    def visit_AggregationNode(self, node: AggregationNode) -> Chain:
        src = self.visit(node.source)
        key_ch = [src.channel(k.name) for k in node.keys]
        key_types = [k.type for k in node.keys]
        key_dicts = [src.dicts[c] for c in key_ch]
        domains = []
        for tt, d in zip(key_types, key_dicts):
            if d is not None and type(d).__name__ == "Dictionary":
                domains.append(len(d))
            elif tt is BOOLEAN:
                domains.append(2)
            else:
                domains.append(None)
        key_domains = domains if domains and all(x is not None for x in domains) \
            else None

        from ..sql.planner.plan import FINAL as P_FINAL, PARTIAL as P_PARTIAL
        from ..ops.hash_agg import FINAL as OP_FINAL, PARTIAL as OP_PARTIAL

        step = node.step
        calls = []
        out_dicts = list(key_dicts)
        out_syms = list(node.keys)
        for i, (sym, ac) in enumerate(node.aggregations):
            arg_types = [a.type for a in ac.args]
            fn = resolve_aggregate(ac.name, arg_types, ac.distinct,
                                   getattr(ac, "params", ()))
            if step == P_FINAL:
                # inputs are the partial state columns named by the exchange plan
                isyms = node.intermediate_symbols[i]
                inter_ch = [src.channel(s.name) for s in isyms]
                out_dict = src.dicts[inter_ch[0]] \
                    if ac.name in ("min", "max", "arbitrary", "any_value") and \
                    inter_ch and src.dicts[inter_ch[0]] is not None else None
                if fn.output_dict is not None:  # string-producing aggregates
                    out_dict = fn.output_dict
                calls.append(AggregateCall(fn, [], None,
                                           intermediate_channels=inter_ch,
                                           output_dictionary=out_dict))
                out_dicts.append(out_dict)
                out_syms.append(sym)
                continue
            arg_ch = [src.channel(a.name) for a in ac.args]
            mask_ch = src.channel(ac.filter.name) if ac.filter is not None else None
            out_dict = None
            if ac.name in ("min", "max", "arbitrary", "any_value",
                           "min_by", "max_by") and arg_ch \
                    and src.dicts[arg_ch[0]] is not None:
                out_dict = src.dicts[arg_ch[0]]
            if fn.output_dict is not None:  # string-producing aggregates
                out_dict = fn.output_dict
            calls.append(AggregateCall(fn, arg_ch, mask_ch,
                                       output_dictionary=out_dict))
            if step == P_PARTIAL:
                isyms = node.intermediate_symbols[i]
                out_syms.extend(isyms)
                # min/max state over a dict column carries codes: keep the dict
                # on the first state column so the exchange + FINAL can decode
                for j, s in enumerate(isyms):
                    out_dicts.append(out_dict if j == 0 else None)
            else:
                out_syms.append(sym)
                out_dicts.append(out_dict)

        op_step = {P_PARTIAL: OP_PARTIAL, P_FINAL: OP_FINAL}.get(step, SINGLE)
        fac = HashAggregationOperatorFactory(
            next(self._ids), key_ch, key_types, key_dicts, key_domains, calls,
            op_step, self.page_capacity,
            max_groups=int(self.session.get("max_groups")))
        return Chain(src.factories + [fac], out_syms, out_dicts)

    def visit_WindowNode(self, node) -> Chain:
        from ..ops.window import WindowOperatorFactory
        from ..types import DecimalType

        src = self.visit(node.source)
        part_ch = [src.channel(k.name) for k in node.partition_keys]
        orders = self._orders(src, node.orderings)
        call_channels = []
        call_meta = []
        for sym, call in node.calls:
            arg_chs = [src.channel(a.name) for a in call.args]
            scale_div = 1
            if call.name == "avg" and arg_chs:
                at = src.symbols[arg_chs[0]].type
                if isinstance(at, DecimalType):
                    scale_div = 10 ** at.scale
            out_dict = None
            if call.name in ("min", "max", "lag", "lead", "first_value",
                             "last_value", "nth_value") and arg_chs and \
                    src.dicts[arg_chs[0]] is not None:
                out_dict = src.dicts[arg_chs[0]]
            call_channels.append((call.name, arg_chs, call.frame_mode,
                                  scale_div, call.offset))
            call_meta.append((sym.type, out_dict))
        fac = WindowOperatorFactory(
            next(self._ids), part_ch, orders, call_channels, call_meta,
            [s.type for s in src.symbols])
        out_syms = src.symbols + [s for s, _ in node.calls]
        out_dicts = list(src.dicts) + [d for _, d in call_meta]
        return Chain(src.factories + [fac], out_syms, out_dicts)

    def visit_UnionNode(self, node: UnionNode) -> Chain:
        """Materialized concatenation: each child pipeline drains into a page
        buffer; the union 'scan' replays the buffers (plan/UnionNode; the
        reference streams through an exchange — the local-exchange rev will)."""
        chains: List[Chain] = []
        for child, mapping in zip(node.sources, node.symbol_mappings):
            chain = self.visit(child)
            if [s.name for s in chain.symbols] != [m.name for m in mapping]:
                chain = self._append_project(
                    chain, [(m, symbol_ref(m.name, m.type)) for m in mapping])
            chains.append(chain)
        # dictionary unification across branches (the re-encode pass):
        # - a branch whose column carries NO dictionary (e.g. a GROUPING
        #   SETS null branch: all-NULL constants) adopts the other
        #   branches' dictionary — its codes are dead under the null mask;
        # - two DIFFERENT real dictionaries union their values and the
        #   minority branches re-encode codes on device;
        # - virtual (formatted) dictionaries can't union — same object only.
        ncols = len(node.symbol_mappings[0])
        # a dict-less varchar column is only safe to ADOPT a sibling's
        # dictionary when its codes are provably dead (NULL constants from
        # GROUPING SETS); remote-source chains fall back to unknown dicts
        # with LIVE codes — adopting would decode them through the wrong
        # dictionary, so keep the loud error for those
        for ch in chains:
            if getattr(ch, "unreliable_dicts", False) and any(
                    ch.dicts[c] is None and any(
                        other.dicts[c] is not None for other in chains)
                    for c in range(len(node.symbol_mappings[0]))):
                raise NotImplementedError(
                    "UNION dictionary unification over a remote source "
                    "with unknown dictionaries")
        dicts: List[Optional[Dictionary]] = []
        remaps: List[List[Optional[np.ndarray]]] = [
            [None] * ncols for _ in chains]
        for c in range(ncols):
            branch_dicts = [ch.dicts[c] for ch in chains]
            real = [d for d in branch_dicts if d is not None]
            if not real:
                dicts.append(None)
                continue
            if all(d is real[0] for d in real):
                dicts.append(real[0])
                continue
            if any(not hasattr(d, "values") for d in real):
                raise NotImplementedError(
                    "UNION across distinct VIRTUAL dictionaries has no "
                    "re-encode (formatted columns must share one source)")
            seen: Dict[str, int] = {}
            values: List[str] = []
            for d in real:
                for v in d.values:
                    if v not in seen:
                        seen[v] = len(values)
                        values.append(v)
            union = _intern_union_dict(values)
            for bi, d in enumerate(branch_dicts):
                if d is not None and list(d.values) != values:
                    remap = np.asarray([seen[v] for v in d.values],
                                       dtype=np.int32)
                    # the prefix-majority branch gets an identity mapping:
                    # a dictionary REBIND suffices, skip the device gather
                    if not np.array_equal(remap,
                                          np.arange(len(remap),
                                                    dtype=np.int32)):
                        remaps[bi][c] = remap
                    else:
                        branch_dicts[bi] = None  # force rebind-only below
            dicts.append(union)
        buffers: List[PageConsumerFactory] = []
        for bi, (chain, mapping) in enumerate(
                zip(chains, node.symbol_mappings)):
            facs = list(chain.factories)
            needs_rebind = any(
                dicts[c] is not None and chain.dicts[c] is not dicts[c]
                for c in range(ncols))
            if needs_rebind or any(r is not None for r in remaps[bi]):
                from ..ops.coalesce import DictionaryRemapOperatorFactory

                facs.append(DictionaryRemapOperatorFactory(
                    next(self._ids), [m.type for m in mapping], remaps[bi],
                    target_dicts=dicts))
            buf = PageConsumerFactory(next(self._ids), [m.type for m in mapping])
            self.pipelines.append(facs + [buf])  # union: keep 1 driver (replay ordering)
            buffers.append(buf)

        class _ReplaySource(ConnectorPageSource):
            def __init__(self, bufs, worker):
                self.bufs = bufs
                self.worker = worker

            def __iter__(self):
                for b in self.bufs:
                    yield from b.pages_for(self.worker)

        def ready(w):
            def all_children_done():
                return all(len(b.consumers_by_worker.get(w, [])) > 0 and
                           all(c.is_finished()
                               for c in b.consumers_by_worker[w])
                           for b in buffers)
            return all_children_done

        fac = TableScanOperatorFactory(
            next(self._ids), lambda w: [_ReplaySource(buffers, w)],
            [s.type for s in node.symbols], None, ready=ready)
        return Chain([fac], list(node.symbols), dicts or [])

    # ------------------------------------------------- sort / limit / misc

    def _orders(self, chain: Chain, orderings) -> List[SortOrder]:
        return [SortOrder(chain.channel(o.symbol.name), o.descending,
                          o.nulls_first) for o in orderings]

    def visit_TopNNode(self, node: TopNNode) -> Chain:
        src = self.visit(node.source)
        fac = TopNOperatorFactory(next(self._ids), node.count,
                                  self._orders(src, node.orderings),
                                  [s.type for s in src.symbols], list(src.dicts))
        return Chain(src.factories + [fac], list(src.symbols), list(src.dicts))

    def visit_SortNode(self, node: SortNode) -> Chain:
        src = self.visit(node.source)
        fac = OrderByOperatorFactory(next(self._ids),
                                     self._orders(src, node.orderings),
                                     [s.type for s in src.symbols],
                                     list(src.dicts))
        return Chain(src.factories + [fac], list(src.symbols), list(src.dicts))

    def visit_LimitNode(self, node: LimitNode) -> Chain:
        src = self.visit(node.source)
        fac = LimitOperatorFactory(next(self._ids), node.count,
                                   [s.type for s in src.symbols])
        return Chain(src.factories + [fac], list(src.symbols), list(src.dicts))

    def visit_EnforceSingleRowNode(self, node: EnforceSingleRowNode) -> Chain:
        src = self.visit(node.source)
        fac = EnforceSingleRowOperatorFactory(next(self._ids),
                                              [s.type for s in src.symbols],
                                              list(src.dicts))
        return Chain(src.factories + [fac], list(src.symbols), list(src.dicts))

    # ---------------------------------------------------------- helpers

    def _append_project(self, chain: Chain,
                        assignments: List[Tuple[Symbol, RowExpression]],
                        predicate: Optional[RowExpression] = None) -> Chain:
        channels = chain.channel_map()
        projections = [resolve_symbols(e, channels) for _, e in assignments]
        condition = resolve_symbols(predicate, channels) \
            if predicate is not None else None
        processor = PageProcessor(chain.layout(), condition, projections)
        fac = FilterProjectOperatorFactory(next(self._ids), processor=processor)
        return Chain(chain.factories + [fac], [s for s, _ in assignments],
                     processor.output_dicts)
