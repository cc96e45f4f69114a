"""Process-wide shared worker pools with per-query fair scheduling.

Before this module, every query spun up its own thread armies: N scan reader
threads + a decode thread + an upload thread per scan pipeline, and one pump
thread per streaming exchange — so N concurrent queries cost O(N * stages)
OS threads, and nothing arbitrated between them. The reference never works
that way: ALL queries time-slice on one TaskExecutor pool
(execution/executor/TaskExecutor.java:78), and that is what makes it a
multi-tenant service rather than a per-query batch engine.

This module is that shape for the engine's background stages:

- :data:`SCAN_POOL` runs every scan pipeline's reader/decode/upload stages;
  :data:`EXCHANGE_POOL` runs every streaming exchange's pump. Each pool is
  sized ONCE per process (env knobs below) and its threads are reused across
  ``execute()`` calls — N concurrent queries cost O(pool) threads.
- Work is submitted as **generators**: each ``next()`` advances the stage by
  one bounded step (one chunk read, one pump sweep). A stage that cannot
  progress waits a short bounded interval *inside* its step and then yields,
  so no step ever parks a pool worker indefinitely — the pool stays
  deadlock-free by construction (every worker frees within
  :data:`STEP_WAIT_S`). Work that CANNOT honor that contract — reads that
  block on progress the engine does not control (``ConnectorPageSource.
  external_wait``, e.g. the cluster tier's remote exchange streams) — must
  stay on dedicated threads; the scan pipeline enforces the exemption.
- Fairness is **round-robin across clients** (one client per live query):
  a worker picks the next client with runnable work and advances ONE step
  of ONE of its generators. A query streaming a huge table cannot starve a
  point query — they interleave at step granularity, the moral equivalent of
  the reference's split quanta.
- Clients are refcounted by key (the per-query pool key), so every pipeline
  and exchange of one query shares one fairness slot and the client
  disappears when the last owner releases it — the pool's client map cannot
  grow with query history.

The per-query dedicated-thread mode (``shared_pools=False``) drives the very
same generators on private threads — one stage logic, two schedulers — and
is kept as the differential-testing oracle, exactly like ``segment_fusion``
and ``streaming_exchange``.

The pools are constructed at module import (not first use) so their internal
locks are allocated while the lock sanitizer's import-time hook is already
installed — ``__graft_entry__.dryrun_locksan`` asserts they really are
instrumented (see :func:`pool_locks`).
"""
from __future__ import annotations

import itertools
import os
import sys
import threading
from collections import OrderedDict, deque
from typing import Dict, Iterator, List, Optional

from ..utils import trace

# status values generators may yield; the pool treats every yield as a
# fairness checkpoint, the names just document intent at the yield site
AGAIN = "again"   # made progress, more work pending
WAIT = "wait"     # could not progress; the step already waited its bound

# the bounded wait a blocked step performs before yielding: long enough to
# catch a notify (no busy spin), short enough that a parked step frees its
# pool worker promptly for other queries' work
STEP_WAIT_S = 0.02

_IDLE_WAIT_S = 0.05   # worker park time when no client has runnable work


class PoolClient:
    """One query's fairness slot in a pool. Refcounted: every pipeline /
    exchange of the query acquires the same client (by pool key) and
    releases it on close; the pool drops the client when the last reference
    is gone and its generators have drained."""

    def __init__(self, pool: "SharedWorkerPool", key: str):
        self.pool = pool
        self.key = key
        self.refs = 0
        self.gens: deque = deque()   # runnable (generator, trace.capture())
        self.live = 0                # submitted, not yet finished
        self.steps = 0

    def submit(self, gen: Iterator) -> None:
        """Enqueue a stage generator. The submitting thread's active trace
        recorder rides along so pool workers attribute the stage's spans to
        the owning query (per-query trace scoping under shared threads)."""
        self.pool._submit(self, gen, trace.capture())

    def release(self) -> None:
        self.pool._release(self)

    def wait_idle(self, timeout_s: float = 5.0) -> bool:
        """Block until every generator submitted through this client has
        finished (bounded). Owners stop their machinery first (stop flags),
        then wait here so no step is mid-flight when they tear state down."""
        return self.pool._wait_idle(self, timeout_s)


class SharedWorkerPool:
    """Fixed-size worker pool stepping client generators round-robin."""

    def __init__(self, name: str, size: int):
        self.name = name
        self.size = max(1, int(size))
        self._cv = threading.Condition()
        self._clients: "OrderedDict[str, PoolClient]" = OrderedDict()
        self._threads: List[threading.Thread] = []
        self._rr = 0
        self.total_steps = 0

    # ------------------------------------------------------------------ api

    def client(self, key: str) -> PoolClient:
        """Acquire (refcounted) the client for `key`, creating it on first
        use. Threads start lazily on the first acquire."""
        with self._cv:
            c = self._clients.get(key)
            if c is None:
                c = self._clients[key] = PoolClient(self, key)
            c.refs += 1
            self._ensure_threads_locked()
        return c

    def stats(self) -> dict:
        with self._cv:
            return {"threads": len(self._threads),
                    "clients": len(self._clients),
                    "steps": self.total_steps}

    # ------------------------------------------------------------- internals

    def _ensure_threads_locked(self) -> None:
        while len(self._threads) < self.size:
            t = threading.Thread(target=self._worker,
                                 name=f"{self.name}-pool-"
                                      f"{len(self._threads)}",
                                 daemon=True)
            self._threads.append(t)
            t.start()

    def _submit(self, client: PoolClient, gen: Iterator, traced) -> None:
        with self._cv:
            client.gens.append((gen, traced))
            client.live += 1
            self._cv.notify_all()

    def _release(self, client: PoolClient) -> None:
        with self._cv:
            client.refs -= 1
            self._maybe_drop_locked(client)

    def _maybe_drop_locked(self, client: PoolClient) -> None:
        # every caller holds self._cv (the _locked suffix contract); the
        # static pass cannot propagate held locks across the call
        if client.refs <= 0 and client.live <= 0 and not client.gens:
            self._clients.pop(client.key, None)  # prestocheck: ignore[shared-state-race]

    def _wait_idle(self, client: PoolClient, timeout_s: float) -> bool:
        import time
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while client.live > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(min(left, _IDLE_WAIT_S))
        return True

    def _next_work(self):
        """Round-robin pick: the next client (from the rotation cursor) with
        a runnable generator. Returns (client, gen, traced) or None."""
        with self._cv:
            keys = list(self._clients)
            n = len(keys)
            for i in range(n):
                c = self._clients[keys[(self._rr + i) % n]]
                if c.gens:
                    self._rr = (self._rr + i + 1) % max(n, 1)
                    gen, traced = c.gens.popleft()
                    return c, gen, traced
            self._cv.wait(_IDLE_WAIT_S)
            return None

    def _worker(self) -> None:
        while True:
            work = self._next_work()
            if work is None:
                continue
            client, gen, traced = work
            finished = False
            try:
                if traced[0] is not None:
                    # one coarse span per step: the black-box / flight
                    # recorder timeline shows WHEN each query's stages got
                    # pool service (category `pool`)
                    with trace.bound(*traced):
                        with trace.span(trace.POOL, f"{self.name}_step",
                                        query=client.key):
                            next(gen)
                else:
                    next(gen)
            except StopIteration:
                finished = True
            except BaseException as e:  # noqa: BLE001 - stage gens guard their
                # own errors into their pipelines; anything escaping here is a
                # pool-level bug — keep the worker alive, drop the generator
                finished = True
                from ..utils import events
                events.emit("pool.step_error", severity=events.ERROR,
                            pool=self.name, client=client.key,
                            error=repr(e)[:300])
                print(f"shared pool {self.name}: worker step failed: {e!r}",
                      file=sys.stderr)
            with self._cv:
                client.steps += 1
                self.total_steps += 1
                if finished:
                    client.live -= 1
                    self._maybe_drop_locked(client)
                else:
                    client.gens.append((gen, traced))
                self._cv.notify_all()


def _pool_size(env: str, default: int) -> int:
    try:
        n = int(os.environ.get(env) or 0)
    except ValueError:
        n = 0
    return n if n > 0 else default


# process-wide pools, sized once at import (env knobs for operators):
#   PRESTO_TPU_SCAN_POOL_THREADS      scan reader/decode/upload stages
#   PRESTO_TPU_EXCHANGE_POOL_THREADS  streaming-exchange pumps
SCAN_POOL = SharedWorkerPool(
    "scan", _pool_size("PRESTO_TPU_SCAN_POOL_THREADS",
                       max(4, min(8, os.cpu_count() or 4))))
EXCHANGE_POOL = SharedWorkerPool(
    "exchange", _pool_size("PRESTO_TPU_EXCHANGE_POOL_THREADS", 4))

_QUERY_KEYS = itertools.count(1)


def next_query_key(prefix: str = "q") -> str:
    """Fresh per-query pool key: every pipeline/exchange of one query
    acquires the pool client under the same key, giving the query ONE
    fairness slot per pool."""
    return f"{prefix}{next(_QUERY_KEYS)}"


def pool_locks() -> Dict[str, object]:
    """The pools' internal condition variables, by pool name — what
    ``dryrun_locksan`` asserts are sanitizer-instrumented (pools allocate
    their locks at module import, AFTER the sanitizer's import-time install;
    this hook keeps that ordering honest)."""
    return {SCAN_POOL.name: SCAN_POOL._cv,
            EXCHANGE_POOL.name: EXCHANGE_POOL._cv}


from ..utils.metrics import METRICS as _METRICS  # noqa: E402

_METRICS.set_gauge("pool.scan.clients", lambda: len(SCAN_POOL._clients))
_METRICS.set_gauge("pool.scan.steps", lambda: SCAN_POOL.total_steps)
_METRICS.set_gauge("pool.exchange.clients",
                   lambda: len(EXCHANGE_POOL._clients))
_METRICS.set_gauge("pool.exchange.steps", lambda: EXCHANGE_POOL.total_steps)
