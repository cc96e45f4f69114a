"""Client protocol types + query manager: the /v1/statement contract.

Analogues: server/protocol/StatementResource.java:88,134 (POST creates a
query, GET pages results via nextUri, DELETE cancels),
execution/SqlQueryManager.java:300 + QueryStateMachine (state transitions),
client/QueryResults.java (the wire shape: id/columns/data/nextUri/error/stats).

The wire format is JSON with the reference's field names so a reference-style
client maps 1:1: {"id", "infoUri", "nextUri", "columns":[{"name","type"}],
"data":[[...]], "stats":{"state", ...}, "error":{...}}.
"""
from __future__ import annotations

import dataclasses
import datetime
import decimal
import itertools
import threading
import time
import traceback
from typing import Dict, List, Optional

import numpy as np

from ..utils import trace
from ..utils.metrics import METRICS

# QueryState.java vocabulary (narrowed to the states this engine reaches)
QUEUED = "QUEUED"
RUNNING = "RUNNING"
FINISHED = "FINISHED"
FAILED = "FAILED"
CANCELED = "CANCELED"

_DONE = {FINISHED, FAILED, CANCELED}

# the longest a GET is held for a query that is not done before it is
# answered as it stands (StatementResource.java's MAX_WAIT_TIME)
MAX_WAIT_S = 1.0


@dataclasses.dataclass
class QueryInfo:
    query_id: str
    sql: str
    state: str = QUEUED
    rows: Optional[List[list]] = None
    columns: Optional[List[Dict[str, str]]] = None
    error: Optional[Dict] = None
    # wall-clock timestamps (what clients display); duration math uses the
    # monotonic pair below — wall deltas jump under NTP steps
    create_time: float = dataclasses.field(default_factory=time.time)
    end_time: Optional[float] = None
    create_mono: float = dataclasses.field(default_factory=time.monotonic)
    end_mono: Optional[float] = None
    row_count: int = 0
    user: str = ""
    source: str = ""
    catalog: str = ""    # per-query default-catalog override (JDBC/DBAPI)
    schema: str = ""
    trace_token: str = ""   # X-Presto-Trace-Token correlation id
    # flight-recorder export (query_trace session knob): local path of the
    # Chrome trace JSON, served at GET /v1/query/{id}/trace
    trace_path: Optional[str] = None
    # black-box forensic dump (always-on coarse ring, utils/trace.py): set
    # when the query FAILED (from the exception's failure_trace_path) or
    # survived a failed attempt; /v1/query/{id}/trace serves it when no
    # opted-in trace exists — failed queries are debuggable after the fact
    failure_trace_path: Optional[str] = None
    # the protocol layer's open spans by name (utils/trace.Stage): `query`
    # (submit -> the GET that serves the final state), `queued` (submit ->
    # RUNNING), `result_wait` (the final state -> the first fetch of it: the
    # time a GET parked in `await_done` takes to wake and answer).
    # Whoever ends one takes it out under the manager's lock first.
    stages: Dict = dataclasses.field(default_factory=dict, repr=False)
    profiled: bool = False   # a jax.profiler trace was live at submit

    def done(self) -> bool:
        return self.state in _DONE

    def elapsed_millis(self) -> int:
        return int(((self.end_mono if self.end_mono is not None
                     else time.monotonic()) - self.create_mono) * 1000)


class QueryManager:
    """Owns query lifecycle: submit -> background execute -> paged fetch.

    One engine (LocalQueryRunner or DistributedQueryRunner) serves every query;
    queries run on daemon threads (the HTTP layer must never block on the
    engine — StatementResource's async pattern)."""

    def __init__(self, runner, page_rows: int = 1000,
                 max_done_queries: int = 100,
                 resource_groups=None, monitor=None, access_control=None,
                 transactions=None):
        self.runner = runner
        self.page_rows = page_rows
        # completed-query history is bounded (SqlQueryManager's expiration):
        # oldest done queries are evicted, their materialized rows with them
        self.max_done_queries = max_done_queries
        # service subsystems, all optional (None = allow-all / no-op):
        self.resource_groups = resource_groups   # ResourceGroupManager
        self.monitor = monitor                   # QueryMonitor (events)
        self.access_control = access_control     # AccessControl
        self.transactions = transactions         # TransactionManager
        self._queries: Dict[str, QueryInfo] = {}
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        # what a parked GET waits on (await_done): notified by _finished,
        # the one place every final state passes, and by close()
        self._done_cv = threading.Condition(self._lock)
        # live execution threads by query id (removed by _run on exit):
        # close() joins them so shutdown never abandons a query mid-write
        # and tests never leak engine threads across cases
        self._run_threads: Dict[str, threading.Thread] = {}
        self._closed = False
        import inspect

        try:
            self._execute_takes_user = "user" in inspect.signature(
                runner.execute).parameters
        except (TypeError, ValueError):
            self._execute_takes_user = False

    # ----------------------------------------------------------------- api

    def submit(self, sql: str, user: str = "", source: str = "",
               catalog: str = "", schema: str = "",
               trace_token: str = "") -> QueryInfo:
        with self._lock:
            qid = f"q{next(self._ids)}_{int(time.time())}"
            info = QueryInfo(qid, sql, user=user, source=source,
                             catalog=catalog, schema=schema,
                             trace_token=trace_token,
                             profiled=trace.profile_live())
            info.stages["query"] = trace.Stage("query", qid, info.profiled)
            info.stages["queued"] = trace.Stage("protocol.queued", qid,
                                                info.profiled)
            self._queries[qid] = info
            self._expire_locked()
        if self.monitor is not None:
            from ..spi.eventlistener import QueryCreatedEvent

            self.monitor.query_created(
                QueryCreatedEvent(qid, sql, user=user, source=source,
                                  trace_token=trace_token))
        from ..utils import events
        METRICS.count("query_manager.submitted")
        events.emit("query.submitted", query_id=qid, user=user, source=source)
        # daemon (a wedged kernel must not block interpreter exit) but
        # REGISTERED: close() joins every live one, bounded
        t = threading.Thread(target=self._run, args=(info,),
                             name=f"query-{qid}", daemon=True)
        with self._lock:
            if self._closed:
                info.state = FAILED
                info.error = {"message": "server is shutting down",
                              "errorType": "ServerShuttingDown"}
                info.stages.pop("queued").end()
                self._finished(info)
                return info
            self._run_threads[qid] = t
            # start INSIDE the lock: a concurrent close() must never snapshot
            # (and try to join) a registered-but-unstarted thread
            t.start()
        return info

    def _expire_locked(self) -> None:
        done = [q for q in self._queries.values() if q.done()]
        if len(done) <= self.max_done_queries:
            return
        done.sort(key=lambda q: q.end_time or 0)
        for q in done[:len(done) - self.max_done_queries]:
            self._queries.pop(q.query_id, None)

    def get(self, query_id: str) -> Optional[QueryInfo]:
        return self._queries.get(query_id)

    def cancel(self, query_id: str) -> bool:
        info = self._queries.get(query_id)
        if info is None:
            return False
        canceled = False
        with self._lock:
            if not info.done():
                # engine slices are not interruptible mid-kernel; the query is
                # marked canceled and its results are dropped on completion
                info.state = CANCELED
                self._finished(info)
                canceled = True
        if canceled:
            from ..utils import events
            events.emit("query.canceled", severity=events.WARN,
                        query_id=query_id)
        return True

    def _finished(self, info: QueryInfo) -> None:
        """Under the lock, with the final state set: stamp the end, and from
        here the answer lies ready until somebody asks (`result_wait`)."""
        info.end_time = time.time()
        info.end_mono = time.monotonic()
        if "result_wait" not in info.stages:   # a canceled query that fails
            info.stages["result_wait"] = trace.Stage(
                "protocol.result_wait", info.query_id, info.profiled)
        self._done_cv.notify_all()

    def await_done(self, info: QueryInfo,
                   max_wait_s: float = MAX_WAIT_S) -> bool:
        """The long-poll (Query.waitForResults): park the calling HTTP
        thread until `info` reaches a final state, the manager closes or
        `max_wait_s` have passed; the caller then answers with the state as
        it stands. -> whether the final state ended the wait."""
        METRICS.count("protocol.long_poll.parked")
        stage = trace.Stage("protocol.long_poll", info.query_id,
                            info.profiled)
        with self._done_cv:
            self._done_cv.wait_for(lambda: info.done() or self._closed,
                                   max_wait_s)
            woken = info.done()
        if woken:
            METRICS.count("protocol.long_poll.woken")
        METRICS.histogram("protocol.long_poll_s", stage.end(woken=int(woken)))
        return woken

    def _end_stage(self, info: QueryInfo, name: str) -> None:
        """End the named open span, once: its `query.<name>_s` observation
        and its event of the profiler's trace come from one clock read."""
        with self._lock:
            stage = info.stages.pop(name, None)
        if stage is not None:
            seconds = stage.end()
            if name != "query":   # the root is a span, not a phase
                METRICS.histogram(f"query.{name}_s", seconds)

    def served(self, info: QueryInfo) -> None:
        """The HTTP handler, after it has written a response for `info`:
        the one that carried the final state ends the root span."""
        if info.done() and "result_wait" not in info.stages:
            self._end_stage(info, "query")

    def list_queries(self) -> List[QueryInfo]:
        return list(self._queries.values())

    def close(self, timeout_s: float = 10.0) -> None:
        """Join every live query thread (bounded on the WHOLE close): new
        submissions are refused, running queries get `timeout_s` to finish.
        A thread still alive after the deadline is abandoned (daemon) rather
        than hanging shutdown."""
        with self._lock:
            self._closed = True
            live = list(self._run_threads.values())
            self._done_cv.notify_all()   # no parked GET outlives the server
        deadline = time.monotonic() + timeout_s
        for t in live:
            t.join(timeout=max(0.0, deadline - time.monotonic()))

    def _scoped_runner(self, info: QueryInfo):
        """Shallow-copy the engine with the query's catalog/schema defaults
        (the X-Presto-Catalog/Schema headers a JDBC/DBAPI client sends).
        Kernel caches are process-global, so scoped copies cost nothing."""
        if not (info.catalog or info.schema):
            return self.runner
        import copy
        import dataclasses as _dc

        runner = copy.copy(self.runner)
        runner.session = _dc.replace(
            runner.session,
            catalog=info.catalog or runner.session.catalog,
            schema=info.schema or runner.session.schema)
        return runner

    # ------------------------------------------------------------- execute

    def _run(self, info: QueryInfo) -> None:
        ticket = None
        tx = None
        t0 = time.monotonic()
        t_run = t0
        try:
            with self._lock:
                if info.state != QUEUED:  # canceled before the thread started
                    return
            if self.access_control is not None:
                self.access_control.check_can_execute_query(info.user)
            if self.resource_groups is not None:
                # may QUEUE the query (blocks this thread) or reject
                ticket = self.resource_groups.submit(
                    info.query_id, info.user, info.source)
            t_run = time.monotonic()  # cpu charge excludes queue wait
            with self._lock:
                if info.state != QUEUED:  # canceled while queued
                    return
                info.state = RUNNING
            self._end_stage(info, "queued")
            if self.transactions is not None:
                tx = self.transactions.begin(info.query_id)
                # conservative join: every registered catalog (hooks are
                # no-ops for connectors without transaction support), so any
                # connector the query touches gets its commit/rollback —
                # qualified cross-catalog writes included
                for cat in self.transactions.catalog_names():
                    self.transactions.join(tx, cat)
            runner = self._scoped_runner(info)
            # live progress: the engine's _run_plan / schedulers register
            # their per-operator providers under THIS query id while the
            # query runs (served at GET /v1/query/{id})
            from ..exec import progress as _progress
            with _progress.query_scope(info.query_id):
                if self._execute_takes_user:
                    result = runner.execute(info.sql, user=info.user)
                else:
                    result = runner.execute(info.sql)
            serialize = trace.Stage("protocol.serialize", info.query_id,
                                    info.profiled)
            rows = [self._to_json_row(r) for r in result.rows]
            if tx is not None:
                self.transactions.commit(tx)
                tx = None
            with self._lock:
                if info.state == CANCELED:
                    return
                info.rows = rows
                info.trace_path = getattr(result, "trace_path", None)
                info.failure_trace_path = getattr(
                    result, "failure_trace_path", None)
                info.row_count = len(rows)
                info.columns = [{"name": n, "type": self._type_name(result, i)}
                                for i, n in enumerate(result.column_names)]
                info.state = FINISHED
                serialize_s = serialize.end()
                self._finished(info)
            from ..utils import events
            METRICS.histogram("query.serialize_s", serialize_s)
            METRICS.count("query_manager.completed")
            METRICS.count("query_manager.output_rows", len(rows))
            events.emit("query.finished", query_id=info.query_id,
                        rows=len(rows),
                        wall_s=round(time.monotonic() - t_run, 4))
        except Exception as e:  # noqa: BLE001 - reported through the protocol
            with self._lock:
                info.error = {
                    "message": str(e),
                    "errorType": type(e).__name__,
                    "stack": traceback.format_exc()[-2000:],
                }
                # the engine's failure forensic (always-on black-box ring)
                # rides the exception; GET /v1/query/{id}/trace serves it
                info.failure_trace_path = getattr(e, "failure_trace_path",
                                                  None)
                info.state = FAILED
                self._finished(info)
            from ..utils import events
            METRICS.count("query_manager.failed")
            events.emit("query.failed", severity=events.ERROR,
                        query_id=info.query_id, error=type(e).__name__,
                        message=str(e)[:500],
                        forensic=bool(info.failure_trace_path))
        finally:
            with self._lock:
                self._run_threads.pop(info.query_id, None)
            self._end_stage(info, "queued")   # never ran: canceled, refused
            if tx is not None:
                self.transactions.abort(tx)
            if ticket is not None:
                self.resource_groups.finish(
                    ticket, cpu_seconds=time.monotonic() - t_run)
            if self.monitor is not None:
                from ..spi.eventlistener import QueryCompletedEvent

                self.monitor.query_completed(QueryCompletedEvent(
                    info.query_id, info.sql, state=info.state, user=info.user,
                    trace_token=info.trace_token,
                    row_count=info.row_count,
                    wall_seconds=time.monotonic() - t0, error=info.error))

    @staticmethod
    def _type_name(result, i: int) -> str:
        types = getattr(result, "types", None)
        if types and i < len(types):
            return getattr(types[i], "name", "unknown")
        return "unknown"

    @staticmethod
    def _to_json_row(row) -> list:
        out = []
        for v in row:
            if isinstance(v, decimal.Decimal):
                out.append(str(v))
            elif isinstance(v, datetime.date):
                out.append(v.isoformat())
            elif isinstance(v, np.generic):
                out.append(v.item())
            else:
                out.append(v)
        return out

    # ------------------------------------------------------------ protocol

    def results_payload(self, info: QueryInfo, token: int,
                        base_uri: str) -> Dict:
        """QueryResults wire shape for page `token` (nextUri paging:
        StatementClientV1.java:86 advances until nextUri is absent)."""
        if info.done():
            # the first fetch of the final state: the answer stops waiting
            self._end_stage(info, "result_wait")
        payload: Dict = {
            "id": info.query_id,
            "infoUri": f"{base_uri}/v1/query/{info.query_id}",
            "stats": {
                "state": info.state,
                "elapsedTimeMillis": info.elapsed_millis(),
                "processedRows": info.row_count,
            },
        }
        if info.state == FAILED:
            payload["error"] = info.error
            return payload
        if info.state in (QUEUED, RUNNING):
            # not ready, after the handler held a GET for MAX_WAIT_S (the
            # POST's answer is not held): the client asks for the same token
            payload["nextUri"] = \
                f"{base_uri}/v1/statement/{info.query_id}/{token}"
            return payload
        if info.state == CANCELED:
            # surface cancellation as an error: a client mid-pagination must
            # raise, not mistake the truncated rows for a complete result
            payload["error"] = {"message": "Query was canceled",
                                "errorType": "QueryCanceled"}
            return payload
        # FINISHED: serve page `token`, advance nextUri while rows remain
        lo = token * self.page_rows
        hi = lo + self.page_rows
        payload["columns"] = info.columns
        if lo < info.row_count:
            payload["data"] = info.rows[lo:hi]
        if hi < info.row_count:
            payload["nextUri"] = \
                f"{base_uri}/v1/statement/{info.query_id}/{token + 1}"
        return payload
