"""Runtime lock sanitizer: the dynamic half of the concurrency checks.

`tools/prestocheck`'s `lock-discipline` / `shared-state-race` passes reason
about locks *statically*; this module observes the real thing. Under
``PRESTO_TPU_LOCKSAN=1`` (or an explicit :func:`install`), every
``threading.Lock`` / ``RLock`` / ``Condition`` allocated from this repo's
code is replaced by an instrumented wrapper that records:

- the **live acquisition-order graph**: an edge ``held -> acquired`` for
  every lock taken while another is held. A new edge that closes a cycle is
  a deadlock in waiting, reported *at the acquire attempt, before blocking*
  — a real inverted-order deadlock produces a finding, not a hang. The
  runtime graph also validates the static ``lock-order-cycle`` pass: edges
  the static resolver missed (dynamic dispatch, callbacks) show up in
  :func:`order_graph` / :func:`dump` and become static-pass fixtures.
- **blocking waits while holding a lock**: ``Condition.wait`` while the
  thread still holds another instrumented lock serializes every other
  holder behind the wait (the dynamic twin of lock-discipline's
  blocking-under-lock check).
- **per-lock hold-time and contention-wait histograms**, exported through
  the process :data:`~presto_tpu.utils.metrics.METRICS` registry as
  ``locksan.hold_s`` / ``locksan.wait_s`` (aggregate) and per lock via
  :meth:`LockSanitizer.lock_stats`; contended waits >= 1ms additionally
  land as flight-recorder spans (category ``locksan``) so a traced query
  shows lock convoys on its timeline.

Only locks allocated from files under this repository are instrumented —
stdlib internals (queue mutexes, Event conditions) pass through untouched,
so the overhead and the graph stay scoped to engine locking. Never
benchmark with the sanitizer installed.

Locks are named by their allocation site (``presto_tpu/ops/scan.py:52``);
tests can name them explicitly via the always-instrumenting module
factories :func:`Lock` / :func:`RLock` / :func:`Condition`.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Set, Tuple

from .metrics import METRICS, Histogram
from . import trace

# raw primitives captured before any monkeypatching — the sanitizer's own
# bookkeeping must never instrument itself
_RAW_LOCK = threading.Lock
_RAW_RLOCK = threading.RLock
_RAW_CONDITION = threading.Condition

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TRACE_CATEGORY = "locksan"
_TRACE_WAIT_NS = 1_000_000       # contended waits >= 1ms become trace spans
_MAX_FINDINGS = 256
_MAX_EDGES = 8192


def _site(depth: int = 2) -> str:
    """'relpath:lineno' of the caller `depth` frames up."""
    f = sys._getframe(depth)
    path = f.f_code.co_filename
    try:
        rel = os.path.relpath(path, REPO_ROOT)
    except ValueError:
        rel = path
    if rel.startswith(".."):
        rel = path
    return f"{rel.replace(os.sep, '/')}:{f.f_lineno}"


def _in_repo(depth: int = 2) -> bool:
    path = os.path.abspath(sys._getframe(depth).f_code.co_filename)
    return path.startswith(REPO_ROOT + os.sep)


class LockSanitizer:
    """Process-wide recorder shared by every instrumented lock."""

    def __init__(self):
        self._meta = _RAW_LOCK()
        self._tls = threading.local()
        # (held_name, acquired_name) -> first site string
        self._edges: Dict[Tuple[str, str], str] = {}
        self._succ: Dict[str, Set[str]] = {}
        self._findings: List[dict] = []
        self._reported: Set[tuple] = set()
        self._hold: Dict[str, Histogram] = {}
        self._wait: Dict[str, Histogram] = {}
        self.n_locks = 0

    # ------------------------------------------------------------- held stack

    def _held(self) -> list:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def _busy(self) -> bool:
        return getattr(self._tls, "busy", False)

    class _Quiet:
        """Reentrancy guard: metrics/trace calls made *by* the sanitizer go
        through instrumented locks raw instead of recording recursively."""

        __slots__ = ("tls",)

        def __init__(self, tls):
            self.tls = tls

        def __enter__(self):
            self.tls.busy = True

        def __exit__(self, *exc):
            self.tls.busy = False
            return False

    # ------------------------------------------------------------- recording

    def note_attempt(self, lock: "_SanLock") -> None:
        """Order-graph edges for an acquire attempt — recorded BEFORE any
        blocking so an actual deadlock still yields its cycle finding."""
        held = self._held()
        if not held or self._busy():
            return
        with self._Quiet(self._tls):
            site = _site(3)
            for h, _t0 in held:
                if h.name == lock.name:
                    continue
                self._add_edge(h.name, lock.name, site)

    def _add_edge(self, a: str, b: str, site: str) -> None:
        with self._meta:
            if (a, b) in self._edges:
                return
            if len(self._edges) >= _MAX_EDGES:
                return
            self._edges[(a, b)] = site
            self._succ.setdefault(a, set()).add(b)
            self._succ.setdefault(b, set())
            path = self._path(b, a)
        if path is not None:
            nodes = [a, b] + path[1:]
            self._report("order-cycle", tuple(sorted(set(nodes))), site,
                         "lock-order cycle (deadlock potential): "
                         + " -> ".join(nodes + [a]),
                         locks=sorted(set(nodes)))

    def _path(self, src: str, dst: str) -> Optional[List[str]]:
        """DFS path src -> dst in the edge graph (meta lock held)."""
        if src == dst:
            return [src]
        stack = [(src, [src])]
        seen = {src}
        while stack:
            node, trail = stack.pop()
            for nxt in self._succ.get(node, ()):
                if nxt == dst:
                    return trail  # trail excludes dst; caller appends
                if nxt not in seen and len(trail) < 16:
                    seen.add(nxt)
                    stack.append((nxt, trail + [nxt]))
        return None

    def _report(self, kind: str, key: tuple, site: str, message: str,
                locks: List[str]) -> None:
        t = threading.current_thread()
        with self._meta:
            if (kind, key) in self._reported:
                return
            self._reported.add((kind, key))
            if len(self._findings) >= _MAX_FINDINGS:
                return
            self._findings.append({
                "kind": kind, "message": message, "site": site,
                "locks": locks, "thread": t.name,
            })

    def note_acquired(self, lock: "_SanLock", waited_ns: int,
                      contended: bool) -> None:
        self._held().append((lock, time.perf_counter_ns()))
        if not contended or self._busy():
            return
        with self._Quiet(self._tls):
            waited_s = waited_ns / 1e9
            with self._meta:
                h = self._wait.get(lock.name)
                if h is None:
                    h = self._wait[lock.name] = Histogram()
                h.add(waited_s)
            METRICS.histogram("locksan.wait_s", waited_s)
            if waited_ns >= _TRACE_WAIT_NS:
                trace.record(TRACE_CATEGORY, f"wait {lock.name}",
                             time.perf_counter_ns() - waited_ns, waited_ns)

    def note_released(self, lock: "_SanLock") -> None:
        held = self._held()
        t0 = None
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] is lock:
                t0 = held[i][1]
                del held[i]
                break
        if t0 is None or self._busy():
            return
        with self._Quiet(self._tls):
            dt_ns = time.perf_counter_ns() - t0
            hold_s = dt_ns / 1e9
            with self._meta:
                h = self._hold.get(lock.name)
                if h is None:
                    h = self._hold[lock.name] = Histogram()
                h.add(hold_s)
            METRICS.histogram("locksan.hold_s", hold_s)
            if dt_ns >= _TRACE_WAIT_NS:
                trace.record(TRACE_CATEGORY, f"hold {lock.name}",
                             time.perf_counter_ns() - dt_ns, dt_ns)

    def note_cond_wait(self, cond_lock: "_SanLock") -> None:
        """Condition.wait parks the thread; any OTHER lock still held
        serializes its every other would-be holder behind this wait."""
        if self._busy():
            return
        others = [h.name for h, _ in self._held() if h is not cond_lock]
        if not others:
            return
        with self._Quiet(self._tls):
            site = _site(3)
            self._report(
                "wait-while-held", (cond_lock.name, tuple(sorted(others))),
                site,
                f"Condition.wait on `{cond_lock.name}` while holding "
                f"{', '.join('`%s`' % o for o in others)} — every other "
                "holder is blocked for the whole wait",
                locks=others + [cond_lock.name])

    def suspend_for_wait(self, lock: "_SanLock") -> Optional[int]:
        """Condition.wait releases its lock for the duration: close the
        hold-time segment and pop it so held-stack checks stay truthful.
        Returns the acquire timestamp to restore, or None if untracked."""
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] is lock:
                t0 = held[i][1]
                del held[i]
                if not self._busy():
                    with self._Quiet(self._tls):
                        hold_s = (time.perf_counter_ns() - t0) / 1e9
                        with self._meta:
                            h = self._hold.get(lock.name)
                            if h is None:
                                h = self._hold[lock.name] = Histogram()
                            h.add(hold_s)
                        METRICS.histogram("locksan.hold_s", hold_s)
                return t0
        return None

    def resume_after_wait(self, lock: "_SanLock") -> None:
        self._held().append((lock, time.perf_counter_ns()))

    # --------------------------------------------------------------- reading

    def findings(self) -> List[dict]:
        with self._meta:
            return [dict(f) for f in self._findings]

    def order_graph(self) -> Dict[str, List[str]]:
        with self._meta:
            return {a: sorted(bs) for a, bs in self._succ.items()}

    def edges(self) -> List[dict]:
        with self._meta:
            return [{"held": a, "acquired": b, "site": s}
                    for (a, b), s in sorted(self._edges.items())]

    def lock_stats(self) -> Dict[str, dict]:
        """{lock name: {hold: {count,p50,p95,p99}, wait: {...}}}."""
        with self._meta:
            names = set(self._hold) | set(self._wait)
            out = {}
            for n in sorted(names):
                entry = {}
                if n in self._hold:
                    entry["hold"] = self._hold[n].summary()
                if n in self._wait:
                    entry["wait"] = self._wait[n].summary()
                out[n] = entry
            return out

    def report(self) -> str:
        fs = self.findings()
        if not fs:
            return ("locksan: clean "
                    f"({self.n_locks} locks, {len(self.edges())} order "
                    "edges, 0 findings)")
        lines = [f"locksan: {len(fs)} finding(s):"]
        for f in fs:
            lines.append(f"  [{f['kind']}] {f['message']} "
                         f"(thread {f['thread']}, at {f['site']})")
        return "\n".join(lines)

    def assert_clean(self) -> None:
        fs = self.findings()
        assert not fs, self.report()

    def dump(self, path: str) -> str:
        """Order-graph + findings JSON — the runtime half a developer diffs
        against the static `lock-order-cycle` graph (a runtime edge the
        static pass missed becomes a fixture for it)."""
        doc = {"locks": self.n_locks, "edges": self.edges(),
               "findings": self.findings(), "lock_stats": self.lock_stats()}
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
        return path

    def absorb(self, findings: List[dict]) -> None:
        """Re-inject findings captured before a reset() — the test harness
        isolates deliberate-violation fixtures without losing real engine
        findings a sanitized tier-1 run accumulated earlier."""
        with self._meta:
            for f in findings:
                if len(self._findings) < _MAX_FINDINGS:
                    self._findings.append(dict(f))

    def reset(self) -> None:
        with self._meta:
            self._edges.clear()
            self._succ.clear()
            self._findings.clear()
            self._reported.clear()
            self._hold.clear()
            self._wait.clear()


SANITIZER = LockSanitizer()


# ---------------------------------------------------------------------------
# instrumented primitives
# ---------------------------------------------------------------------------

class _SanLock:
    """threading.Lock with order/hold/wait bookkeeping."""

    _reentrant = False

    def __init__(self, name: str):
        self._inner = _RAW_LOCK()
        self.name = name
        with SANITIZER._meta:
            SANITIZER.n_locks += 1

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        san = SANITIZER
        if san._busy():
            return self._inner.acquire(blocking, timeout)
        san.note_attempt(self)
        got = self._inner.acquire(False)
        if got:
            san.note_acquired(self, 0, contended=False)
            return True
        if not blocking:
            return False
        t0 = time.perf_counter_ns()
        got = self._inner.acquire(True, timeout)
        if got:
            san.note_acquired(self, time.perf_counter_ns() - t0,
                              contended=True)
        return got

    def release(self) -> None:
        san = SANITIZER
        if san._busy():
            self._inner.release()
            return
        san.note_released(self)
        self._inner.release()

    def locked(self) -> bool:
        return self._inner.locked()

    __enter__ = acquire

    def __exit__(self, *exc) -> bool:
        self.release()
        return False

    # Condition-protocol hooks (a RAW threading.Condition built over this
    # wrapper — e.g. allocated from stdlib code — still bookkeeps correctly)
    def _release_save(self):
        SANITIZER.note_released(self)
        self._inner.release()

    def _acquire_restore(self, _state) -> None:
        self.acquire()

    def _is_owned(self) -> bool:
        return any(h is self for h, _ in SANITIZER._held())

    def __repr__(self) -> str:
        return f"<{type(self).__name__} {self.name} {self._inner!r}>"


class _SanRLock(_SanLock):
    """threading.RLock wrapper: reentrant acquires neither re-push the held
    stack nor add order edges (same lock, same thread)."""

    _reentrant = True

    def __init__(self, name: str):
        self._inner = _RAW_RLOCK()
        self.name = name
        self._owner: Optional[int] = None
        self._depth = 0
        with SANITIZER._meta:
            SANITIZER.n_locks += 1

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        san = SANITIZER
        if san._busy():
            return self._inner.acquire(blocking, timeout)
        me = threading.get_ident()
        if self._owner == me:
            self._inner.acquire()
            self._depth += 1
            return True
        san.note_attempt(self)
        got = self._inner.acquire(False)
        contended = False
        waited = 0
        if not got:
            if not blocking:
                return False
            t0 = time.perf_counter_ns()
            got = self._inner.acquire(True, timeout)
            waited = time.perf_counter_ns() - t0
            contended = True
        if got:
            self._owner = me
            self._depth = 1
            san.note_acquired(self, waited, contended)
        return got

    def release(self) -> None:
        san = SANITIZER
        if san._busy():
            self._inner.release()
            return
        if self._owner == threading.get_ident() and self._depth > 1:
            self._depth -= 1
            self._inner.release()
            return
        self._owner = None
        self._depth = 0
        san.note_released(self)
        self._inner.release()

    __enter__ = acquire

    def _release_save(self):
        # Condition.wait over an RLock drops the WHOLE recursion count
        state = self._inner._release_save()
        depth, self._depth = self._depth, 0
        self._owner = None
        SANITIZER.note_released(self)
        return (state, depth)

    def _acquire_restore(self, state) -> None:
        inner_state, depth = state
        self._inner._acquire_restore(inner_state)
        self._owner = threading.get_ident()
        self._depth = depth
        SANITIZER.resume_after_wait(self)

    def _is_owned(self) -> bool:
        return self._owner == threading.get_ident()


class _SanCondition:
    """threading.Condition over an instrumented lock. `wait` while holding
    any OTHER instrumented lock is a finding; the condition's own lock is
    correctly modeled as released for the duration of the wait."""

    def __init__(self, lock=None, name: str = ""):
        self.name = name or _site()
        if lock is None:
            lock = _SanRLock(self.name)
        if isinstance(lock, _SanLock):
            self._san_lock: Optional[_SanLock] = lock
        else:
            self._san_lock = None  # foreign/raw lock: no bookkeeping
        self._cond = _RAW_CONDITION(lock if self._san_lock is None
                                    else lock._inner)

    # lock protocol -------------------------------------------------------
    def acquire(self, *a, **kw) -> bool:
        if self._san_lock is not None:
            return self._san_lock.acquire(*a, **kw)
        return self._cond.acquire(*a, **kw)

    def release(self) -> None:
        if self._san_lock is not None:
            self._san_lock.release()
        else:
            self._cond.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> bool:
        self.release()
        return False

    # condition protocol --------------------------------------------------
    def wait(self, timeout: Optional[float] = None) -> bool:
        lk = self._san_lock
        if lk is None:
            return self._cond.wait(timeout)
        SANITIZER.note_cond_wait(lk)
        saved_depth = None
        if lk._reentrant:
            # the raw wait fully releases the inner RLock; clear ownership
            # NOW so another thread acquiring during our park sees a clean
            # wrapper, and restore after the inner lock is ours again
            saved_depth = lk._depth
            lk._owner = None
            lk._depth = 0
        t0 = SANITIZER.suspend_for_wait(lk)
        try:
            return self._cond.wait(timeout)
        finally:
            if lk._reentrant:
                lk._owner = threading.get_ident()
                lk._depth = saved_depth or 1
            if t0 is not None:
                SANITIZER.resume_after_wait(lk)

    def wait_for(self, predicate, timeout: Optional[float] = None):
        endtime = None
        waittime = timeout
        result = predicate()
        while not result:
            if waittime is not None:
                if endtime is None:
                    endtime = time.monotonic() + waittime
                else:
                    waittime = endtime - time.monotonic()
                    if waittime <= 0:
                        break
            self.wait(waittime)
            result = predicate()
        return result

    def notify(self, n: int = 1) -> None:
        self._cond.notify(n)

    def notify_all(self) -> None:
        self._cond.notify_all()

    notifyAll = notify_all

    def __repr__(self) -> str:
        return f"<_SanCondition {self.name}>"


# ---------------------------------------------------------------------------
# factories + install
# ---------------------------------------------------------------------------

def Lock(name: Optional[str] = None) -> _SanLock:
    """Always-instrumented Lock (tests; engine code just uses threading)."""
    return _SanLock(name or _site())


def RLock(name: Optional[str] = None) -> _SanRLock:
    return _SanRLock(name or _site())


def Condition(lock=None, name: Optional[str] = None) -> _SanCondition:
    return _SanCondition(lock, name or _site())


def _lock_factory():
    if _in_repo():
        return _SanLock(_site())
    return _RAW_LOCK()


def _rlock_factory():
    if _in_repo():
        return _SanRLock(_site())
    return _RAW_RLOCK()


def _condition_factory(lock=None):
    if _in_repo():
        return _SanCondition(lock, _site())
    return _RAW_CONDITION(lock)


_installed = False


def install() -> LockSanitizer:
    """Monkeypatch threading so repo-allocated locks are instrumented.
    Idempotent. Locks created BEFORE install stay raw — install as early as
    possible (PRESTO_TPU_LOCKSAN=1 installs at package import)."""
    global _installed
    if not _installed:
        threading.Lock = _lock_factory
        threading.RLock = _rlock_factory
        threading.Condition = _condition_factory
        _installed = True
    return SANITIZER


def uninstall() -> None:
    """Restore the raw primitives (existing instrumented locks keep working
    — they wrap real primitives — but new allocations are raw again)."""
    global _installed
    if _installed:
        threading.Lock = _RAW_LOCK
        threading.RLock = _RAW_RLOCK
        threading.Condition = _RAW_CONDITION
        _installed = False


def enabled() -> bool:
    return _installed


def install_from_env() -> bool:
    """The PRESTO_TPU_LOCKSAN=1 hook (called from presto_tpu.__init__)."""
    if os.environ.get("PRESTO_TPU_LOCKSAN") in ("1", "true", "on"):
        install()
        return True
    return False
