"""Fused pipeline segments: one device dispatch per page through a chain of
page-local operators.

The driver (exec/driver.py, the Driver.processInternal analogue) moves each
page through N separate jitted dispatches with a host round-trip at every
operator boundary. For chains of PAGE-LOCAL operators — filter/project, the
unique/exact join probe, the per-page partial of a hash aggregation, a TopN
buffer merge — those boundaries are pure overhead: every stage is a pure
``page -> page`` (or ``page -> contribution``) function, so the whole chain
can trace into ONE jitted kernel. XLA then fuses across the old operator
boundaries (a join's gathered payload column feeding only a SUM never
materializes), and per-page host work drops to a single dispatch. This is
the per-operator kernel-launch fusion "Accelerating Presto with GPUs"
(PAPERS.md) identifies as the first structural win, applied to the engine's
jitted-operator design.

Shape of the thing:

- The segment compiler (exec/local_planner.LocalExecutionPlanner, knob
  ``segment_fusion``) groups maximal runs of fusible operator factories into
  one :class:`FusedSegmentOperatorFactory`. Mid stages are
  ``FilterProjectOperatorFactory`` (PageProcessor._process) and plan-time
  page-local ``LookupJoinOperatorFactory`` probes
  (hash_join.apply_probe_stage); an optional TERMINAL stage absorbs a
  ``HashAggregationOperatorFactory`` (the builder's per-page partial) or a
  ``TopNOperatorFactory`` (the buffer merge). Blocking operators, join
  builds, exchanges, sorts and expansion-path probes are fusion barriers.
- Join lookup-source arrays and aggregation/TopN accumulator state thread
  through the fused function as JIT ARGUMENTS, never trace constants — a
  rebuilt build side or a growing accumulator replays the compiled kernel.
- Compiled segments live in the global ``utils/kernel_cache`` keyed on every
  stage's config fingerprint plus the input layout's dictionary versions
  (the hash_agg ``share_kernels`` pattern, generalized): workers, drivers
  and repeated queries share one compile per distinct segment.
- The unfused path (``segment_fusion = False``) keeps the exact per-operator
  pipeline and serves as the differential-testing oracle
  (tests/test_fused_segment.py asserts row-identical output).

Per-segment dispatch and compile counts surface in
``QueryResult.stats["segments"]`` and as ``segments.*`` counters on
``/v1/metrics``.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

import jax

from ..block import Page
from ..utils import kernel_cache as kc
from ..utils import trace
from ..utils.metrics import METRICS
from .filter_project import FilterProjectOperatorFactory
from .hash_agg import (DirectAggregationBuilder, GlobalAggregationBuilder,
                       GroupedAggregationBuilder,
                       HashAggregationOperatorFactory, _builder_key)
from .hash_join import (LookupJoinOperatorFactory, apply_probe_stage,
                        count_probe_pages, probe_plan_fusible,
                        probe_stage_aux, probe_stage_cfg, probe_stage_key)
from .operator import Operator, OperatorContext, OperatorFactory, timed
from .topn import TopNOperatorFactory, topn_merge_stage


def mid_stage_fusible(f) -> bool:
    """Plan-time: can `f` run as a page-local mid stage of a segment?"""
    if isinstance(f, FilterProjectOperatorFactory):
        return True
    if isinstance(f, LookupJoinOperatorFactory):
        return probe_plan_fusible(f.join_type, f.probe_key_channels,
                                  f.unique_build, f.filter_fn,
                                  f.semi_output_channel)
    return False


def terminal_stage_fusible(f) -> bool:
    """Plan-time: can `f` terminate a segment (per-page contribution)?"""
    if isinstance(f, HashAggregationOperatorFactory):
        from .collect_agg import COLLECT_NAMES
        # collect builders keep raw rows (no per-page partial); ragged
        # handles cannot thread through the fused kernel
        return not any(c.function.name in COLLECT_NAMES for c in f.calls)
    return isinstance(f, TopNOperatorFactory)


class FusedSegmentOperatorFactory(OperatorFactory):
    """One factory per fused segment; holds the member factories in chain
    order plus the segment-level dispatch/compile counters the runner rolls
    into ``QueryResult.stats["segments"]``."""

    def __init__(self, operator_id: int, mid_factories: List,
                 terminal_factory=None,
                 output_types: Optional[List] = None,
                 output_dicts: Optional[List] = None):
        members = list(mid_factories) + (
            [terminal_factory] if terminal_factory is not None else [])
        names = "+".join(m.name for m in members)
        super().__init__(operator_id, f"FusedSegment[{names}]")
        self.mid_factories = list(mid_factories)
        self.terminal_factory = terminal_factory
        self.member_names = [m.name for m in members]
        self.output_types = list(output_types or [])
        self.output_dicts = list(output_dicts or [])
        self._lock = threading.Lock()
        self.pages = 0      # fused dispatches (one per input page)
        self.compiles = 0   # kernel-cache misses this factory triggered

    def create_operator(self, worker: int = 0) -> "FusedSegmentOperator":
        tf = self.terminal_factory
        if tf is not None:
            # forward the query's memory wiring: the terminal's builder is
            # the segment's only revocable state
            tf.memory_ctx = self.memory_ctx
            tf.revoke_check = self.revoke_check
            tf.spill_manager = self.spill_manager
        return FusedSegmentOperator(self.context(worker), self, worker)

    def note_pages(self, n: int) -> None:
        with self._lock:
            self.pages += n
        METRICS.count_many({"dispatches": n}, prefix="segments.")

    def note_compile(self) -> None:
        with self._lock:
            self.compiles += 1
        METRICS.count("segments.compiles")
        trace.instant(trace.SEGMENT, f"compile {self.name}")

    def describe(self) -> dict:
        with self._lock:
            return {"operators": list(self.member_names),
                    "dispatches": self.pages, "compiles": self.compiles}


class _AggTerminal:
    """Terminal adapter around a real HashAggregationOperator: the fused
    kernel computes the per-page contribution; this absorbs it into the
    inner operator's builder (state, spill and result building unchanged)."""

    def __init__(self, factory: HashAggregationOperatorFactory, worker: int):
        self.op = factory.create_operator(worker)
        self.builder = self.op.builder
        if isinstance(self.builder, GroupedAggregationBuilder):
            self.mode = "grouped"
        elif isinstance(self.builder, DirectAggregationBuilder):
            self.mode = "direct"
        else:
            assert isinstance(self.builder, GlobalAggregationBuilder), \
                type(self.builder)
            self.mode = "global"

    def variant(self):
        """Changes when the builder's adaptive per-page strategy flips
        (partial -> raw defer): the operator recomposes its fused kernel."""
        if self.mode == "grouped" and self.builder.defer_raw():
            return "raw"
        return "partial"

    def cache_key(self, input_dicts) -> tuple:
        tag = {"grouped": "sort", "direct": "direct",
               "global": "global"}[self.mode]
        return _builder_key(tag, self.builder,
                            input_dicts=tuple(input_dicts)) + (self.variant(),)

    def stage_plan(self):
        b = self.builder
        if self.mode == "grouped":
            if b.defer_raw():
                return ("agg_raw", b._page_raw)
            return ("agg_partial", b._page_partial)
        if self.mode == "direct":
            return ("agg_state", lambda page, st: b._accumulate(page, *st))
        return ("agg_state", lambda page, st: b._accumulate(page, st))

    def state(self):
        if self.mode == "grouped":
            return ()
        return self.builder.init_state()

    def out_groups(self, capacity: int) -> int:
        if self.mode == "grouped" and not self.builder.defer_raw():
            return self.builder.page_out_groups(capacity)
        return 0

    def absorb(self, result, capacity: int, out_groups: int) -> bool:
        b = self.builder
        if self.mode == "grouped":
            if b.defer_raw() and out_groups == 0:
                b.absorb_raw(result, capacity)
                ok = True
            else:
                ok = b.absorb_partial(result, capacity, out_groups)
        else:
            b.absorb_state(result)
            ok = True
        mem = getattr(b, "memory_bytes", None)
        if mem is not None:
            self.op.context.update_revocable(mem(),
                                             self.op.start_memory_revoke)
        return ok


class _TopNTerminal:
    """Terminal adapter around a real TopNOperator: the fused kernel merges
    the page into the N-row buffer, threaded through as a jit argument."""

    mode = "topn"

    def __init__(self, factory: TopNOperatorFactory, worker: int):
        self.op = factory.create_operator(worker)

    def variant(self):
        return "topn"

    def cache_key(self, input_dicts) -> tuple:
        f = self.op
        return ("topn", tuple(f.orders), f.n,
                tuple(t.name for t in f.output_types),
                tuple(kc.dict_key(d) for d in input_dicts))

    def stage_plan(self):
        orders, n = self.op.orders, self.op.n
        return ("topn", lambda page, st: topn_merge_stage(page, st, orders, n))

    def state(self):
        return self.op._buffer  # None before the first page (one retrace)

    def out_groups(self, capacity: int) -> int:
        return 0

    def absorb(self, result, capacity: int, out_groups: int) -> bool:
        self.op._buffer = result
        return True


def _compose(mid_plan, terminal_plan):
    """-> f(page, auxes, state, out_groups): the whole segment, traceable."""

    def run_mid(page, auxes):
        ai = 0
        for kind, obj in mid_plan:
            if kind == "proc":
                page = obj._process(page)
            else:  # probe
                page = apply_probe_stage(page, auxes[ai], obj)
                ai += 1
        return page

    tkind = terminal_plan[0]

    def fn(page, auxes, state, out_groups):
        page = run_mid(page, auxes)
        if tkind == "none":
            return page
        if tkind == "agg_partial":
            return terminal_plan[1](page, out_groups)
        if tkind == "agg_raw":
            return terminal_plan[1](page)
        return terminal_plan[1](page, state)  # agg_state | topn

    return fn


class FusedSegmentOperator(Operator):
    """Runs the whole segment chain as one jitted dispatch per input page."""

    def __init__(self, context: OperatorContext,
                 factory: FusedSegmentOperatorFactory, worker: int):
        super().__init__(context)
        self.f = factory
        self.worker = worker
        # per-stage runtime slots, chain order (probe stages resolve their
        # lookup source through is_blocked, exactly like LookupJoinOperator)
        self._stages = [{"factory": mf, "source": None, "aux": None}
                        for mf in factory.mid_factories]
        self._terminal = None
        tf = factory.terminal_factory
        if isinstance(tf, HashAggregationOperatorFactory):
            self._terminal = _AggTerminal(tf, worker)
        elif isinstance(tf, TopNOperatorFactory):
            self._terminal = _TopNTerminal(tf, worker)
        self._pending: Optional[Page] = None
        self._fused = None
        self._in_key = None
        self._tvariant = None
        self._pages = 0

    @property
    def output_types(self) -> List:
        return self.f.output_types

    # ------------------------------------------------------------- blocking

    def is_blocked(self):
        for st in self._stages:
            mf = st["factory"]
            if not isinstance(mf, LookupJoinOperatorFactory) or \
                    st["source"] is not None:
                continue
            lf = mf.lookup_factory
            w = self.worker
            if lf.done(w):
                st["source"] = lf.get(w)
                continue
            return lambda: lf.done(w)
        return None

    # ------------------------------------------------------------ execution

    def needs_input(self) -> bool:
        if self._finishing:
            return False
        if self._terminal is None:
            return self._pending is None
        return True

    def _install(self, page: Page, in_key) -> None:
        """(Re)compose + fetch the segment kernel for the live input layout.
        Mirrors PageProcessor.__call__'s rebuild-on-layout-drift: dictionary
        versions are part of the key, so an INSERT-extended dictionary can
        never replay a stale kernel."""
        from .expressions import InputLayout

        self._in_key = in_key
        cur_types = [b.type for b in page.blocks]
        cur_dicts = [b.dictionary for b in page.blocks]
        mid_plan = []
        keys = []
        for st in self._stages:
            mf = st["factory"]
            if isinstance(mf, FilterProjectOperatorFactory):
                proc = mf.processor
                live = kc.layout_key(cur_types, cur_dicts)
                if proc._layout_key != live:
                    proc._build(InputLayout(cur_types, cur_dicts))
                mid_plan.append(("proc", proc))
                keys.append(proc.cache_key)
                cur_types = list(proc.output_types_)
                cur_dicts = list(proc.output_dicts)
            else:
                src = st["source"]
                assert src is not None, \
                    "probe stage traced before its build finished"
                assert src.exact_keys, "fused probe needs exact keys"
                cfg = probe_stage_cfg(mf, src)
                st["aux"] = probe_stage_aux(src)
                mid_plan.append(("probe", cfg))
                keys.append(probe_stage_key(cfg))
                cur_types = [cur_types[c] for c in cfg.probe_output_channels] \
                    + [t for t, _ in cfg.payload_meta]
                cur_dicts = [cur_dicts[c] for c in cfg.probe_output_channels] \
                    + [d for _, d in cfg.payload_meta]
        if self._terminal is None:
            terminal_plan = ("none",)
            tkey = ("none",)
            self._tvariant = None
        else:
            terminal_plan = self._terminal.stage_plan()
            tkey = self._terminal.cache_key(cur_dicts)
            self._tvariant = self._terminal.variant()
        key = ("fused-segment", in_key, tuple(keys), tkey)

        def make():
            self.f.note_compile()
            return jax.jit(_compose(mid_plan, terminal_plan),
                           static_argnames=("out_groups",))

        self._fused = kc.get_or_install(key, make)

    def add_input(self, page: Page) -> None:
        # timed by hand instead of @timed: ONE clock pair feeds the stats
        # accumulator and the per-page dispatch histogram (the decorator
        # would add a duplicate `operator` span per page beside the
        # `segment` span of the dispatch)
        t0 = time.perf_counter_ns()
        try:
            # `program` = the kernel-cache key's first field: what a reader
            # of the profiler's trace groups the dispatched programs by
            with trace.span(trace.SEGMENT, self.f.name, rows=page.capacity,
                            program="fused-segment"):
                self._add_page(page)
        finally:
            # per-page dispatch latency: one histogram observation per page
            # (pages are large, so this is per-dispatch, not per-row)
            dt = time.perf_counter_ns() - t0
            self.context.stats.add_input_ns += dt
            METRICS.histogram("segments.page_dispatch_s", dt / 1e9)

    def _add_page(self, page: Page) -> None:
        self.context.record_input(page, page.capacity)
        in_key = kc.layout_key([b.type for b in page.blocks],
                               [b.dictionary for b in page.blocks])
        t = self._terminal
        if self._fused is None or in_key != self._in_key or \
                (t is not None and t.variant() != self._tvariant):
            self._install(page, in_key)
        auxes = tuple(st["aux"] for st in self._stages
                      if st["aux"] is not None)
        self._pages += 1
        if t is None:
            self._pending = self._fused(page, auxes, None, out_groups=0)
            return
        og = t.out_groups(page.capacity)
        result = self._fused(page, auxes, t.state(), out_groups=og)
        if not t.absorb(result, page.capacity, og):
            # the builder's shrunken partial table overflowed on this
            # page and reset to full size: recompute at the new size
            og = t.out_groups(page.capacity)
            ok = t.absorb(
                self._fused(page, auxes, t.state(), out_groups=og),
                page.capacity, og)
            assert ok, "full-size partial cannot overflow"

    @timed("get_output_ns")
    def get_output(self) -> Optional[Page]:
        if self._terminal is None:
            out, self._pending = self._pending, None
        else:
            out = self._terminal.op.get_output()
        if out is not None:
            self.context.record_output(out, out.capacity)
        return out

    def finish(self) -> None:
        super().finish()
        if self._terminal is not None:
            self._terminal.op.finish()

    def is_finished(self) -> bool:
        if self._terminal is not None:
            return self._terminal.op.is_finished()
        return self._finishing and self._pending is None

    def close(self) -> None:
        if self._pages:
            self.f.note_pages(self._pages)
            # every page went through every probe stage, whose `kind` is
            # that of the live build
            count_probe_pages([st["source"] for st in self._stages
                               if st["source"] is not None], self._pages)
            self._pages = 0
        if self._terminal is not None:
            self._terminal.op.close()
        super().close()
