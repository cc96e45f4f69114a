"""Per-query flight recorder: engine-wide span tracing with Chrome-trace
export.

The reference rolls per-operator wall/row stats up to the coordinator
(operator/OperatorStats.java -> QueryStats) but those are AGGREGATES —
they say how much time a stage consumed, never WHEN. Everything PRs 3-5
built (prefetch vs compute, double-buffered exchange chunks, concurrent
fragments) is valuable precisely for when things happen, so this module
records the timeline itself:

- :class:`TraceRecorder` is a thread-safe ring buffer of spans stamped with
  ``time.perf_counter_ns``. Producers on any engine thread (drivers, scan
  readers, exchange pumps, HTTP clients) append; the ring bound makes the
  recorder safe to leave on under heavy traffic (oldest spans overwrite,
  the drop count is exported).
- Recorders are PER-QUERY: :func:`install` binds the query's recorder to
  its submitting thread, and every component that fans work out to other
  threads (task-executor runs, scan-pipeline stages, exchange pumps,
  shared-pool steps) captures :func:`active` at hand-off and re-binds it
  with :func:`bound` — so concurrently traced queries each export their own
  complete timeline. A process-global fallback covers ambient threads.
  Every instrumentation site goes through the module-level
  :func:`record`/:func:`span` helpers, which are a single thread-local load
  + ``None`` check when tracing is off — the hot paths pay nothing.
- Export is Chrome trace-event JSON (the ``{"traceEvents": [...]}`` shape
  that loads directly in Perfetto / ``chrome://tracing``), reachable as
  ``QueryResult.trace_path`` and over ``GET /v1/query/{id}/trace``.
- **Black-box mode (always on)**: production failures happen on queries
  nobody opted into tracing. Every query therefore gets a COARSE recorder
  (small ring, operator/segment per-page spans dropped at the source) unless
  the ``query_blackbox`` session knob turns it off; when the query fails, is
  OOM-killed or exhausts its retries, the ring is exported as a forensic
  Chrome trace attached to the failure (``QueryResult.failure_trace_path``,
  the exception's ``failure_trace_path`` attribute, and
  ``GET /v1/query/{id}/trace`` — which now answers for FAILED queries).
  A query that succeeds pays only the ring appends and drops the recorder.
- **One trace, not three**: while a ``jax.profiler`` trace is live in the
  process, every span site ALSO opens a ``jax.profiler.TraceAnnotation``
  named ``presto.<category>.<name>`` that carries the client-visible query
  id (``qid``), so the engine's spans lie in the profiler's own
  ``.xplane.pb`` host plane, on the clock of the device's ``XLA Ops``.
  :func:`maybe_recorder` finds that out once a query (and then hands out the
  full recorder, so operator and segment spans exist); with no profile live
  a span site builds no annotation. A span opened on a thread the work was
  handed to (:func:`capture` / :func:`bound`) names, as ``parent``, the span
  that was open where the work was handed over.

Categories — one per instrumented subsystem:
  lifecycle  parse / plan / local-plan / execute phases, result fetch
  driver     TaskExecutor quanta (one span per driver slice)
  operator   Operator add_input/get_output (via ops.operator.timed)
  segment    fused-segment page dispatches + compile markers
  scan       scan-pipeline read/decode/upload stage work + compute stalls
  exchange   streaming-exchange pump states: stall, sync, fill, chunk
             dispatch/delivery, back-pressure, skew wait
  kernel     kernel-cache misses (jit closure builds)
  join       a join build publishing its lookup source (ops/hash_join.py)
  planner    the join order (sql/planner/optimizer.py reorder_joins)
  tpch       a stored tpch catalog writing a table's files, once a table
             (connectors/tpch/connector.py `store`)
  http       cluster task create/poll and exchange pulls
  pool       shared-pool generator steps (exec/shared_pools.py)
  protocol   queued / serialize / result_wait, and long_poll: a GET parked
             until its query ends (server/protocol.py; profiler trace and
             /v1/metrics histograms only: the ring is the runner's)
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from .metrics import METRICS

LIFECYCLE = "lifecycle"
DRIVER = "driver"
OPERATOR = "operator"
SEGMENT = "segment"
SCAN = "scan"
EXCHANGE = "exchange"
KERNEL = "kernel"
JOIN = "join"
PLANNER = "planner"
TPCH = "tpch"
HTTP = "http"
POOL = "pool"

# the runner's phases of a query, each a `lifecycle` span and a
# `query.<phase>_s` histogram at /v1/metrics (one observation a query)
PHASES = ("parse", "plan", "local_plan", "execute")

DEFAULT_MAX_EVENTS = 1 << 16

# always-on black-box ring: small enough to be free, large enough that the
# last seconds of a failing query's coarse timeline survive to the dump
BLACKBOX_MAX_EVENTS = 1 << 13

# per-page categories a coarse (black-box) recorder drops at the source —
# everything else (driver quanta, exchange chunks, scan stage work/stalls,
# pool steps, kernel builds, cluster HTTP) is coarse by construction
_COARSE_DROP = frozenset((OPERATOR, SEGMENT))

# operator add_input/get_output fire constantly (get_output polls return
# None most slices); spans shorter than this are noise that would churn the
# ring — they are dropped at the source, not recorded-then-evicted
MIN_OPERATOR_SPAN_NS = 20_000

_TRACE_SEQ = itertools.count(1)


class TraceRecorder:
    """Ring buffer of (category, name, t0_ns, dur_ns, tid, tname, args)."""

    def __init__(self, query_id: str = "", max_events: int = 0,
                 coarse: bool = False, profiled: bool = False):
        self.query_id = query_id or f"trace-{next(_TRACE_SEQ)}"
        # profiled = a jax.profiler trace was live when the query began:
        # its spans also go into the profiler's host plane
        self.profiled = profiled
        self.max_events = max(int(max_events or DEFAULT_MAX_EVENTS), 16)
        # coarse = the always-on black-box mode: per-page operator/segment
        # spans are dropped before the tuple is even built, so the hot paths
        # pay one frozenset lookup — the ring holds only coarse spans
        self.coarse = coarse
        self._drop = _COARSE_DROP if coarse else frozenset()
        self._lock = threading.Lock()
        self._events: List[tuple] = []
        self._next = 0           # overwrite cursor once the ring is full
        self.dropped = 0
        self.t0_ns = time.perf_counter_ns()   # trace epoch (ts origin)

    # ------------------------------------------------------------ recording

    def record(self, cat: str, name: str, t0_ns: int, dur_ns: int,
               args: Optional[dict] = None) -> None:
        if cat in self._drop:
            return
        t = threading.current_thread()
        evt = (cat, name, t0_ns, dur_ns, t.ident, t.name, args)
        with self._lock:
            if len(self._events) < self.max_events:
                self._events.append(evt)
            else:
                self._events[self._next] = evt
                self._next = (self._next + 1) % self.max_events
                self.dropped += 1

    def instant(self, cat: str, name: str,
                args: Optional[dict] = None) -> None:
        self.record(cat, name, time.perf_counter_ns(), 0, args)

    def span(self, cat: str, name: str, **args) -> "_Span":
        return _Span(self, cat, name, args or None)

    # ------------------------------------------------------------- reading

    def events(self) -> List[tuple]:
        """Events in recording order (ring rotated so oldest comes first)."""
        with self._lock:
            return self._events[self._next:] + self._events[:self._next]

    def count(self, cat: Optional[str] = None) -> int:
        if cat is None:
            with self._lock:
                return len(self._events)
        return sum(1 for e in self.events() if e[0] == cat)

    # -------------------------------------------------------------- export

    def to_chrome_trace(self) -> dict:
        """The Chrome trace-event document (ph="X" complete events, ts/dur
        in MICROseconds — the unit the format specifies)."""
        pid = os.getpid()
        spans = []
        threads: Dict[int, str] = {}
        for cat, name, t0, dur, tid, tname, args in self.events():
            e = {"name": name, "cat": cat, "ph": "X",
                 "ts": (t0 - self.t0_ns) / 1e3, "dur": dur / 1e3,
                 "pid": pid, "tid": tid}
            if args:
                e["args"] = args
            spans.append(e)
            threads.setdefault(tid, tname)
        meta = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                 "args": {"name": f"presto-tpu {self.query_id}"}}]
        meta += [{"name": "thread_name", "ph": "M", "pid": pid, "tid": t,
                  "args": {"name": n}} for t, n in sorted(threads.items())]
        return {"traceEvents": meta + spans, "displayTimeUnit": "ms",
                "otherData": {"query_id": self.query_id,
                              "dropped_events": self.dropped,
                              "coarse": self.coarse}}

    def write(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


class _Span:
    """One span: opened BEFORE the work (the profiler's events take no
    explicit stamps), closed after it. `min_ns` is a noise floor: a shorter
    span is not worth a slot of the ring."""

    __slots__ = ("rec", "cat", "name", "args", "min_ns", "t0", "dur",
                 "_ann", "_outer")

    def __init__(self, rec: Optional[TraceRecorder], cat: str, name: str,
                 args: Optional[dict], min_ns: int = 0):
        self.rec = rec
        self.cat = cat
        self.name = name
        self.args = args
        self.min_ns = min_ns
        self._ann = None

    def __enter__(self):
        rec = self.rec
        if rec is not None and rec.profiled:
            self._annotate(rec.query_id)
        self.t0 = time.perf_counter_ns()
        return self

    def _annotate(self, qid: str) -> None:
        outer = getattr(_TLS, "open", None)
        self._ann = _annotation(
            f"{self.cat}.{self.name}", qid,
            None if outer else getattr(_TLS, "parent", None),
            self.args.get("program") if self.args else None)
        if not self.min_ns:
            # a span with a noise floor may end on another thread than it
            # began on (the scan stalls wait across generator yields) and
            # may not be kept: it is never the span work is handed over in
            self._outer = outer
            _TLS.open = self.name

    def note(self, **args) -> None:
        """What is known only after the work (a driver's end state)."""
        if self.rec is not None:
            self.args = dict(self.args or (), **args)

    def __exit__(self, *exc):
        self.dur = time.perf_counter_ns() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
            if not self.min_ns:
                _TLS.open = self._outer
        if self.rec is not None and self.dur >= self.min_ns:
            self.rec.record(self.cat, self.name, self.t0, self.dur,
                            self.args)
        return False


def profile_live() -> bool:
    """Is a jax.profiler trace being taken in this process? Asked once a
    query (and once a request by the HTTP handler): ~20 ns."""
    return TraceAnnotation.is_enabled()


def _annotation(name: str, qid: str, parent: Optional[str] = None,
                program: Optional[str] = None):
    """An ENTERED profiler event `presto.<name>`. The profiler's encoding
    `name#key=value,...#` gives `#`, `,` and `=` a meaning: they are
    written as `_`, `;` and `:` inside a name or a value."""
    meta = {"qid": qid} if qid else {}
    if parent:
        meta["parent"] = parent.translate(_META_SAFE)
    if program:
        meta["program"] = program.translate(_META_SAFE)
    ann = TraceAnnotation("presto." + name.replace("#", "_"), **meta)
    ann.__enter__()
    return ann


_META_SAFE = str.maketrans("#,=", "_;:")
_NULL_SPAN = _Span(None, "", "", None)


# ---------------------------------------------------------------------------
# the installed recorder: PER-QUERY scoping. A query's recorder binds to the
# threads doing its work — install() binds the calling (query) thread, and
# every engine component that hands work to other threads (TaskExecutor
# runs, scan-pipeline stages, exchange pumps, shared-pool steps) re-binds
# the recorder it captured from its submitting thread via bound(). The
# process-global slot remains only as a FALLBACK for ambient threads with no
# query affiliation, so the single-traced-query case keeps recording exactly
# what it did before — while a second traced query under concurrent load now
# exports its own complete timeline instead of silently running untraced.
# ---------------------------------------------------------------------------

_ACTIVE: Optional[TraceRecorder] = None
_ACTIVE_LOCK = threading.Lock()
_TLS = threading.local()


def active() -> Optional[TraceRecorder]:
    r = getattr(_TLS, "recorder", None)
    return r if r is not None else _ACTIVE


def install(recorder: TraceRecorder) -> bool:
    """Make `recorder` the calling thread's trace sink (and the process
    fallback, first-installed wins). Always succeeds: concurrent traced
    queries no longer collide — each query's threads are bound to its own
    recorder, so the timelines stay separate."""
    global _ACTIVE
    _TLS.recorder = recorder
    with _ACTIVE_LOCK:
        if _ACTIVE is None:
            _ACTIVE = recorder
    return True


def uninstall(recorder: TraceRecorder) -> None:
    global _ACTIVE
    if getattr(_TLS, "recorder", None) is recorder:
        _TLS.recorder = None
    with _ACTIVE_LOCK:
        if _ACTIVE is recorder:
            _ACTIVE = None


class _Bound:
    """Context manager binding a recorder to the current thread (and
    restoring whatever was bound before). Worker threads stepping another
    query's work wrap each step so spans land on the owning query."""

    __slots__ = ("rec", "parent", "prev")

    def __init__(self, rec: Optional[TraceRecorder], parent: Optional[str]):
        self.rec = rec
        self.parent = parent

    def __enter__(self):
        tls = _TLS
        self.prev = (getattr(tls, "recorder", None),
                     getattr(tls, "parent", None), getattr(tls, "open", None))
        tls.recorder, tls.parent, tls.open = self.rec, self.parent, None
        return self.rec

    def __exit__(self, *exc):
        _TLS.recorder, _TLS.parent, _TLS.open = self.prev
        return False


def capture() -> tuple:
    """(recorder, parent) for :func:`bound`, taken on the thread that hands
    work to another: the recorder in force here and, under a live profile,
    the name of the span open here (else of the one this thread itself
    works for), which the other thread's spans will name as `parent`."""
    return active(), (getattr(_TLS, "open", None)
                      or getattr(_TLS, "parent", None))


def bound(recorder: Optional[TraceRecorder],
          parent: Optional[str] = None) -> _Bound:
    """Bind what :func:`capture` took on the submitting thread around work
    executed on a different thread: `with trace.bound(*captured)`."""
    return _Bound(recorder, parent)


def record(cat: str, name: str, t0_ns: int, dur_ns: int,
           args: Optional[dict] = None) -> None:
    """Hot-path append: one thread-local load + None check when tracing is
    off."""
    r = active()
    if r is not None:
        r.record(cat, name, t0_ns, dur_ns, args)


def instant(cat: str, name: str, args: Optional[dict] = None) -> None:
    r = active()
    if r is not None:
        r.instant(cat, name, args)


def span(cat: str, name: str, min_ns: int = 0, **args) -> _Span:
    """The one helper of every span site: `with trace.span(...)` around the
    work. With no recorder, or a coarse one that drops `cat`, it is the
    shared no-op span: one thread-local load and two checks."""
    r = active()
    if r is None or cat in r._drop:
        return _NULL_SPAN
    return _Span(r, cat, name, args or None, min_ns)


# ---------------------------------------------------------------------------
# session wiring (runner entry points call these two)
# ---------------------------------------------------------------------------

def maybe_recorder(session, query_id: str = "") -> Optional[TraceRecorder]:
    """The query's recorder: a FULL one when the session's `query_trace`
    knob is on or a jax.profiler trace is live in the process (the engine
    finds that out here, once a query: no knob says so), else the always-on
    coarse black-box ring (disable with `query_blackbox=False` — what the
    bench's overhead rung compares against). None only when all are off.

    The recorder's query_id defaults to the CANONICAL client-visible id the
    protocol layer bound via exec.progress.query_scope — so forensic dumps,
    `query.forensic_dumped` events, trace filenames and the profiler's
    `qid` correlate with the id the client knows, instead of a synthetic
    trace-N counter."""
    if not query_id:
        from ..exec import progress
        query_id = progress.current_query_id() or ""
    live = profile_live()
    if live or session.get("query_trace"):
        return TraceRecorder(query_id, profiled=live)
    if not session.get("query_blackbox", True):
        return None
    return TraceRecorder(query_id, BLACKBOX_MAX_EVENTS, coarse=True)


class _Phase:
    __slots__ = ("span",)

    def __init__(self, name: str):
        self.span = _Span(active(), LIFECYCLE, name, None)

    def __enter__(self):
        self.span.__enter__()

    def __exit__(self, *exc):
        self.span.__exit__(*exc)
        scope = getattr(_TLS, "scope", None)
        if scope is not None:
            name = self.span.name
            scope.phases[name] = scope.phases.get(name, 0) + self.span.dur
        return False


def phase(name: str) -> _Phase:
    """`with trace.phase("parse")` on the query's thread: a `lifecycle` span
    that is ALWAYS timed, recorder or not, because the enclosing
    :class:`QueryScope` histograms the phase from the same clock reads."""
    return _Phase(name)


class QueryScope:
    """What every runner tier wraps one statement in, so that they cannot
    drift: the query's recorder installed on the calling thread, the root
    `lifecycle.query` span, the forensic dump pinned to a failure, and for a
    statement that succeeds the histograms `query.wall_s` and
    `query.<phase>_s` (p50/p95/p99 at /v1/metrics; one observation each, 0
    for a phase the statement did not have)."""

    def __init__(self, session):
        self.session = session
        self.phases: Dict[str, int] = {}
        self.rec: Optional[TraceRecorder] = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._outer = getattr(_TLS, "scope", None)
        _TLS.scope = self
        self.rec = maybe_recorder(self.session)
        if self.rec is not None:
            install(self.rec)
            # on THIS query's recorder only: an untraced query running
            # beside a traced one must not write into the other's timeline
            self._root = self.rec.span(LIFECYCLE, "query")
            self._root.__enter__()
        return self

    def __exit__(self, etype, exc, tb):
        _TLS.scope = self._outer
        rec = self.rec
        if rec is not None:
            self._root.__exit__(etype, exc, tb)
            if exc is not None:
                attach_failure(exc, rec, self.session)
            uninstall(rec)
        if exc is None:
            METRICS.histogram("query.wall_s",
                              time.perf_counter() - self._t0)
            for name in PHASES:
                METRICS.histogram(f"query.{name}_s",
                                  self.phases.get(name, 0) / 1e9)
        return False

    def finish(self, result):
        """After the scope: the opted-in Chrome export rides the result."""
        if self.session.get("query_trace"):
            result.trace_path = export(self.rec, self.session)
        return result


class Stage:
    """A span of the protocol layer, which may end on another thread than
    it began on (a request is queued on an HTTP thread and starts running on
    the query's; an answer is finished on the query's thread and fetched on
    an HTTP thread). Always timed, for the `query.<stage>_s` histograms; an
    event of the profiler's trace when `live`."""

    __slots__ = ("t0", "_ann")

    def __init__(self, name: str, qid: str, live: bool):
        self._ann = _annotation(name, qid) if live else None
        self.t0 = time.perf_counter_ns()

    def end(self, **meta) -> float:
        """-> seconds since the start. Once: the caller takes the stage out
        of where it keeps it before it ends it. `meta`: what is known only
        now, as args of the profiler's event."""
        dur = time.perf_counter_ns() - self.t0
        if self._ann is not None:
            if meta:
                self._ann.set_metadata(**meta)
            self._ann.__exit__(None, None, None)
        return dur / 1e9


class _Request:
    __slots__ = ("_ann",)

    def __init__(self, name: str, qid: str):
        self._ann = _annotation(f"{HTTP}.{name}", qid)

    def __enter__(self):
        return self

    def note(self, **meta) -> None:
        self._ann.set_metadata(**meta)

    def __exit__(self, *exc):
        self._ann.__exit__(None, None, None)
        return False


def request(name: str, qid: str = ""):
    """`with trace.request("POST /v1/statement") as r:` in an HTTP handler:
    the event `presto.http.<name>` of the profiler's trace while a profile
    is live (`r.note(qid=...)` once the id is known), else the no-op span.
    The ring holds no such span: no query's recorder is bound there."""
    return _Request(name, qid) if profile_live() else _NULL_SPAN


def export(recorder: TraceRecorder, session, suffix: str = "") -> str:
    """Write the Chrome trace JSON under `query_trace_dir` (tempdir default)
    and return the path (what QueryResult.trace_path carries).

    The filename carries the CLIENT-VISIBLE query id whenever one is known:
    when the recorder was created before the protocol layer bound its scope,
    its own id is a synthetic trace-N counter — useless for correlating a
    forensic dump with a cluster query — so the ambient corr_id from
    exec.progress is appended alongside it."""
    import tempfile

    directory = str(session.get("query_trace_dir") or "") or \
        tempfile.gettempdir()
    os.makedirs(directory, exist_ok=True)
    from ..exec import progress
    corr = progress.current_query_id() or ""
    qid = recorder.query_id
    if corr and corr != qid:
        qid = f"{qid}-{corr}"
    path = os.path.join(
        directory,
        f"presto-trace-{os.getpid()}-{qid}{suffix}.json")
    return recorder.write(path)


def attach_failure(exc: BaseException, recorder: TraceRecorder,
                   session) -> Optional[str]:
    """Failure forensics: dump `recorder`'s ring (scoped to this query) as a
    Chrome trace and pin the path onto the exception — the protocol layer
    ships it as `QueryInfo.failure_trace_path` so `GET /v1/query/{id}/trace`
    answers for FAILED queries. First writer wins (the innermost engine tier
    saw the most detail); the dump itself must never mask the real error."""
    if getattr(exc, "failure_trace_path", None):
        return exc.failure_trace_path
    try:
        path = export(recorder, session, suffix="-forensic")
        exc.failure_trace_path = path
        from . import events
        events.emit("query.forensic_dumped", severity="error",
                    query_id=recorder.query_id, path=path,
                    error=type(exc).__name__)
        return path
    except Exception:  # noqa: BLE001 - forensics are best-effort
        return None


# ---------------------------------------------------------------------------
# analysis helpers (bench rungs + tests read exported documents)
# ---------------------------------------------------------------------------

def _merged_intervals(doc: dict, cat: str) -> List[tuple]:
    ivals = sorted((e["ts"], e["ts"] + e.get("dur", 0))
                   for e in doc.get("traceEvents", [])
                   if e.get("ph") == "X" and e.get("cat") == cat)
    merged: List[list] = []
    for lo, hi in ivals:
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [tuple(m) for m in merged]


def overlap_ratio(doc: dict, cat_a: str, cat_b: str) -> float:
    """Fraction of `cat_a` span time that overlaps some `cat_b` span —
    the proof-of-overlap number (e.g. exchange dispatches vs driver compute)
    the GPU-Presto paper argues accelerator engines must report."""
    a = _merged_intervals(doc, cat_a)
    b = _merged_intervals(doc, cat_b)
    total = sum(hi - lo for lo, hi in a)
    if total <= 0:
        return 0.0
    inter = 0.0
    bi = 0
    for lo, hi in a:
        while bi < len(b) and b[bi][1] <= lo:
            bi += 1
        j = bi
        while j < len(b) and b[j][0] < hi:
            inter += max(0.0, min(hi, b[j][1]) - max(lo, b[j][0]))
            j += 1
    return inter / total


def span_categories(doc: dict) -> Dict[str, int]:
    """{category: span count} of an exported document (schema validation)."""
    out: Dict[str, int] = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") == "X":
            out[e.get("cat", "")] = out.get(e.get("cat", ""), 0) + 1
    return out
