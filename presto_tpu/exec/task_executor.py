"""Task executor: time-sliced multi-driver scheduling on a thread pool.

Analogue of execution/executor/TaskExecutor.java:78 (runner threads pulling
prioritized splits), PrioritizedSplitRunner.java:42 (the quantum + accumulated
CPU-time priority), and MultilevelSplitQueue.java:43 (flattened here to one
priority heap ordered by consumed time — the lowest-consumption driver runs
next, which is what the reference's multilevel queue converges to under its
level thresholds).

TPU fit: a "driver slice" is Python pumping pages between jitted kernels; XLA
releases the GIL during compute and compilation, so runner threads genuinely
overlap build and probe pipelines, device compute with host page generation,
and different workers' fragments. Blocked drivers (probe waiting on a build's
LookupSourceFactory slot) park in a blocked list polled between slices —
the moral equivalent of the reference's ListenableFuture wake-ups.
"""
from __future__ import annotations

import heapq
import itertools
import threading
from typing import Callable, List, Optional, Sequence

from ..utils import trace
from .driver import Driver, ProcessState

_DEFAULT_QUANTUM_NS = 200_000_000


class TaskExecutor:
    """Run many drivers to completion on `n_threads` runner threads.

    execute(drivers) blocks until every driver finishes or any driver raises
    (first exception propagates, remaining drivers are abandoned). Driver
    ownership is exclusive: a driver is held by at most one runner thread at
    a time (the heap hands it out, the thread returns it). Threads are
    spawned per call: one query = one execute (every fragment's drivers go
    through a single call), so nothing outlives the executor."""

    def __init__(self, n_threads: int = 4,
                 quantum_ns: int = _DEFAULT_QUANTUM_NS):
        self.n_threads = max(1, int(n_threads))
        self.quantum_ns = quantum_ns

    def execute(self, drivers: Sequence[Driver]) -> None:
        if not drivers:
            return
        run = _Run(list(drivers), self.quantum_ns)
        n = min(self.n_threads, len(drivers))
        if n == 1:
            # single runner: same parking scheduler, on the calling thread
            # (a blocked driver must still defer to later drivers in the list)
            run.runner_loop()
        else:
            threads = [threading.Thread(target=run.runner_loop,
                                        name=f"task-runner-{i}", daemon=True)
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        if run.error is not None:
            raise run.error
        if run.outstanding:
            raise RuntimeError(
                f"task executor finished with {run.outstanding} unfinished "
                "drivers (scheduler invariant violated)")


class _Run:
    """State of one execute() call (SqlTaskExecution's driver bookkeeping)."""

    def __init__(self, drivers: List[Driver], quantum_ns: int):
        self.quantum_ns = quantum_ns
        self.lock = threading.Lock()
        self.cv = threading.Condition(self.lock)
        self.ready: List = []            # heap of (consumed_ns, seq, driver)
        self.blocked: List = []          # [(driver, consumed_ns, unblock_cb)]
        self.outstanding = len(drivers)  # unfinished drivers
        self.error: Optional[BaseException] = None
        self._seq = itertools.count()
        # the submitting (query) thread's flight recorder rides with the run
        # so runner threads attribute driver spans to the right query even
        # when several traced queries share the process
        self.traced = trace.capture()
        for d in drivers:
            heapq.heappush(self.ready, (0, next(self._seq), d))

    # ------------------------------------------------------------- scheduling

    def _next_driver(self):
        """Pop the least-consumed ready driver; promote any unblocked parked
        drivers first. Returns (driver, consumed) or None when all work is done
        (or an error aborted the run)."""
        with self.cv:
            while True:
                if self.error is not None or self.outstanding == 0:
                    self.cv.notify_all()
                    return None
                still = []
                for d, consumed, cb in self.blocked:
                    try:
                        unblocked = cb()
                    except BaseException as e:  # noqa: BLE001
                        self.error = self.error or e
                        self.cv.notify_all()
                        return None
                    if unblocked:
                        heapq.heappush(self.ready,
                                       (consumed, next(self._seq), d))
                    else:
                        still.append((d, consumed, cb))
                self.blocked = still
                if self.ready:
                    consumed, _, d = heapq.heappop(self.ready)
                    return d, consumed
                # nothing ready: wait for an unblock / finish, re-polling the
                # blocked callbacks at a modest cadence
                self.cv.wait(timeout=0.001)

    def runner_loop(self) -> None:
        with trace.bound(*self.traced):
            self._runner_loop()

    def _runner_loop(self) -> None:
        import time
        while True:
            nxt = self._next_driver()
            if nxt is None:
                return
            driver, consumed = nxt
            t0 = time.perf_counter_ns()
            try:
                # one span per driver slice: the flight recorder's timeline
                # of which pipelines ran when (and why they stopped)
                with trace.span(trace.DRIVER, driver.trace_label) as sp:
                    state = driver.process(self.quantum_ns)
                    cb = driver.blocked_on() \
                        if state == ProcessState.BLOCKED else None
                    sp.note(state=state.name)
            except BaseException as e:  # noqa: BLE001 - propagated to caller
                with self.cv:
                    if self.error is None:
                        self.error = e
                    self.cv.notify_all()
                return
            spent = time.perf_counter_ns() - t0
            with self.cv:
                if state == ProcessState.FINISHED:
                    self.outstanding -= 1
                elif state == ProcessState.BLOCKED:
                    self.blocked.append((driver, consumed + spent,
                                         cb or (lambda: True)))
                else:  # YIELDED / MADE_PROGRESS
                    heapq.heappush(self.ready,
                                   (consumed + spent, next(self._seq), driver))
                self.cv.notify_all()
