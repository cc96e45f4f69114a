"""Physical operator protocol and operator context.

Analogue of operator/Operator.java:20 (needsInput/addInput/getOutput/isBlocked/finish)
and operator/OperatorContext.java. The protocol is kept — it is what lets the Driver
pipeline arbitrary operator chains and lets blocking (join build, exchange) propagate —
but operators here hold *device arrays* and their compute methods are jitted closures,
so one addInput/getOutput hop is one fused XLA kernel launch, not a virtual call per row.

Stats: every operator records wall time + rows/pages in/out, rolled up by the driver
into pipeline/task stats (OperatorStats analogue for EXPLAIN ANALYZE).
"""
from __future__ import annotations

import abc
import dataclasses
import time
from typing import Any, Callable, List, Optional, Sequence

from ..block import Page
from ..memory import AggregatedMemoryContext, MemoryTrackingContext
from ..types import Type
from ..utils import trace


@dataclasses.dataclass
class OperatorStats:
    """operator/OperatorStats.java (narrowed)."""
    operator_id: int = 0
    name: str = ""
    add_input_calls: int = 0
    get_output_calls: int = 0
    input_rows: int = 0
    input_pages: int = 0
    output_rows: int = 0
    output_pages: int = 0
    add_input_ns: int = 0
    get_output_ns: int = 0
    finish_ns: int = 0
    # time this operator held its driver BLOCKED (build wait, backpressure),
    # attributed by the Driver when the parked driver next runs
    blocked_ns: int = 0
    peak_memory_bytes: int = 0

    def total_ns(self) -> int:
        return self.add_input_ns + self.get_output_ns + self.finish_ns

    def to_dict(self) -> dict:
        """JSON-safe form for the cluster control plane: each worker ships
        its task's operator stats inside TaskInfo so the coordinator's
        distributed EXPLAIN ANALYZE can roll them up (the reference ships
        OperatorStats inside TaskStatus the same way)."""
        return {"operator_id": self.operator_id, "name": self.name,
                "input_rows": self.input_rows,
                "output_rows": self.output_rows,
                "total_ns": self.total_ns(), "blocked_ns": self.blocked_ns,
                "peak_memory_bytes": self.peak_memory_bytes,
                "input_pages": self.input_pages,
                "output_pages": self.output_pages}


class OperatorContext:
    def __init__(self, operator_id: int, name: str,
                 memory: Optional[MemoryTrackingContext] = None,
                 worker: int = 0,
                 revoke_check: Optional[Callable[[], bool]] = None,
                 spill=None):
        self.worker = worker
        self.stats = OperatorStats(operator_id, name)
        self.memory = memory or MemoryTrackingContext(
            AggregatedMemoryContext(), AggregatedMemoryContext(), AggregatedMemoryContext())
        # memory-pressure probe: operators self-revoke (spill device state to
        # host, then host to disk when `spill` is attached) from their own
        # thread when this fires — thread-safe where an external revoker
        # thread mutating operator state would not be
        self._revoke_check = revoke_check
        # the query's disk tier (exec/spill.SpillManager) or None: operators
        # that can persist host-resident state use it as the ladder's last
        # revocation rung before the OOM killer would fire
        self.spill = spill
        self.user_memory = self.memory.user.new_local_memory_context(name)
        self.revocable_memory = self.memory.revocable.new_local_memory_context(name)

    def should_revoke(self) -> bool:
        return self._revoke_check is not None and self._revoke_check()

    def update_revocable(self, used: int, on_revoke: Callable[[], None]) -> None:
        """Account the operator's revocable device bytes; spill (on the calling
        thread) when the pool is over the revoke target."""
        self.revocable_memory.set_bytes(used)
        self.stats.peak_memory_bytes = max(self.stats.peak_memory_bytes, used)
        if used and self.should_revoke():
            # only fires under memory pressure (rare): the spill decision is
            # exactly what a post-mortem needs to see in the journal
            from ..utils import events
            events.emit("memory.spill", severity=events.WARN,
                        operator=self.stats.name, revocable_bytes=used)
            on_revoke()

    def release_memory(self) -> None:
        self.user_memory.close()
        self.revocable_memory.close()

    def record_input(self, page: Page, rows: int) -> None:
        self.stats.add_input_calls += 1
        self.stats.input_pages += 1
        self.stats.input_rows += rows

    def record_output(self, page: Page, rows: int) -> None:
        self.stats.output_pages += 1
        self.stats.output_rows += rows


class Operator(abc.ABC):
    """operator/Operator.java:20 — page-at-a-time pull/push protocol.

    Lifecycle: while not finished: if needs_input and input available: add_input(page);
    out = get_output(); finish() when upstream exhausted. is_blocked() returns a
    callable/future-like or None (blocking drives yield, like ListenableFuture in the
    reference)."""

    def __init__(self, context: OperatorContext):
        self.context = context
        self._finishing = False

    @property
    @abc.abstractmethod
    def output_types(self) -> List[Type]:
        ...

    def needs_input(self) -> bool:
        return not self._finishing

    @abc.abstractmethod
    def add_input(self, page: Page) -> None:
        ...

    @abc.abstractmethod
    def get_output(self) -> Optional[Page]:
        ...

    def finish(self) -> None:
        self._finishing = True

    def is_finished(self) -> bool:
        return self._finishing

    def is_blocked(self) -> Optional[Callable[[], bool]]:
        """None = not blocked; else a poll-able 'done?' callable."""
        return None

    def close(self) -> None:
        # drop this operator's reservations so pool pressure subsides as
        # operators retire (otherwise should_revoke stays latched and every
        # later operator spills on every page)
        self.context.release_memory()

    # spill protocol (operator/Operator.java:68 startMemoryRevoke/finishMemoryRevoke)
    def revocable_bytes(self) -> int:
        return 0

    def start_memory_revoke(self) -> None:
        pass

    def finish_memory_revoke(self) -> None:
        pass


class OperatorFactory(abc.ABC):
    """operator/OperatorFactory — one per plan node, creates per-driver instances.

    ONE factory serves every worker task of its fragment (the reference ships the
    factory list to each worker; here workers share the process, so sharing the
    factory also shares its jit-compiled kernels — each kernel traces once, not
    once per worker). `worker` selects worker-scoped state (splits, exchange
    pages, lookup-source slots)."""

    def __init__(self, operator_id: int, name: str):
        self.operator_id = operator_id
        self.name = name
        # wired by the local planner when the query has a memory context:
        self.memory_ctx = None        # MemoryTrackingContext (query-level)
        self.revoke_check = None      # () -> bool: pool over revoke target?
        self.spill_manager = None     # exec/spill.SpillManager (disk tier)

    @abc.abstractmethod
    def create_operator(self, worker: int = 0) -> Operator:
        ...

    def context(self, worker: int = 0) -> "OperatorContext":
        mem = self.memory_ctx.fork() if self.memory_ctx is not None else None
        return OperatorContext(self.operator_id, self.name, memory=mem,
                               worker=worker, revoke_check=self.revoke_check,
                               spill=self.spill_manager)

    def no_more_operators(self) -> None:
        pass


def timed(stats_field: str):
    """Decorator: accumulate wall-clock ns of an operator method into stats.

    Doubles as the flight recorder's operator tap: when a query trace is
    active, every call above the noise floor becomes an `operator` span —
    the stats and the timeline are measured by the same clock read."""
    method = stats_field.rsplit("_", 1)[0]  # "add_input_ns" -> "add_input"

    def deco(fn):
        def wrapper(self, *a, **kw):
            stats = self.context.stats
            t0 = time.perf_counter_ns()
            try:
                with trace.span(trace.OPERATOR, f"{stats.name}.{method}",
                                min_ns=trace.MIN_OPERATOR_SPAN_NS):
                    return fn(self, *a, **kw)
            finally:
                dt = time.perf_counter_ns() - t0
                setattr(stats, stats_field, getattr(stats, stats_field) + dt)
        return wrapper
    return deco
