"""The system under test, started the way `python -m presto_tpu.server` starts
it, and the clients that drive it over /v1/statement. From the program this
takes only the server, the client library and its counters."""
import threading
import time

from .compare import typed


class CompileWatch:
    """XLA backend compiles and persistent-cache hits, through jax.monitoring
    (a copy of chip_smoke.CompileWatch): compiles are seen where they happen."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Served:
    """An in-process server on a free port over the configuration's runner."""

    def __init__(self, config):
        from presto_tpu.metadata import Session
        from presto_tpu.server.http_server import PrestoTpuServer

        self.config = config
        session = Session(catalog=config["catalog"], schema=config["schema"])
        if config["runner"] == "local":
            from presto_tpu.runner import LocalQueryRunner
            self.runner = LocalQueryRunner(session=session)
        elif config["runner"] == "distributed":
            from presto_tpu.parallel.runner import DistributedQueryRunner
            self.runner = DistributedQueryRunner(session=session)
        else:
            raise ValueError(f"unknown runner {config['runner']!r}")
        self.server = PrestoTpuServer(self.runner, port=0)
        self.thread = self.server.start()

    def connect(self, user):
        from presto_tpu.client import dbapi

        return dbapi.connect(host="127.0.0.1", port=self.server.port,
                             catalog=self.config["catalog"],
                             schema=self.config["schema"], user=user)

    def stop(self):
        self.server.stop()
        self.thread.join(timeout=30.0)
        if self.thread.is_alive():
            raise RuntimeError("the server thread did not stop")
        self.runner = self.server = None


def counters():
    """The program's own counters and histograms, as raw numbers."""
    from presto_tpu.utils.metrics import METRICS

    return METRICS.raw_snapshot()


def ask(conn, sql, query="", seq=0):
    """One client call: the wall every latency is taken around."""
    import jax.profiler

    with jax.profiler.TraceAnnotation("bench.query", query=query, seq=seq):
        t0 = time.perf_counter()
        cur = conn.cursor()
        cur.execute(sql)
        rows = cur.fetchall()
        wall = time.perf_counter() - t0
    return typed(rows, cur.description), wall


def run_window(served, plan, seconds, min_queries=0):
    """Drive the plan for `seconds`: no query starts later (but at least
    `min_queries` do), and the window closes when the last in flight returns.
    -> {"answers": [(query, rows or None)], "walls", "errors", "late_s",
        "attempted", "seconds": the window's real length}"""
    conns = [served.connect(f"bench-{c}") for c in range(plan.clients)]
    lock = threading.Lock()
    out = {"answers": [], "walls": [], "errors": [], "late_s": [],
           "attempted": 0}
    ticket = [0]
    t0 = time.perf_counter()

    def next_index():
        with lock:
            now = time.perf_counter() - t0
            i = ticket[0]
            if plan.loop == "open":
                due = i / plan.rate_per_s
                if due >= seconds and i >= min_queries:
                    return None, 0.0
            else:
                due = now
                if now >= seconds and i >= min_queries:
                    return None, 0.0
            ticket[0] += 1
            out["attempted"] += 1
            return i, due

    def client(conn):
        while True:
            i, due = next_index()
            if i is None:
                return
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late = max(0.0, time.perf_counter() - t0 - due)
            query = plan.query_at(i)
            try:
                rows, wall = ask(conn, plan.sql[query], query, i)
            except Exception as e:  # noqa: BLE001 - a failed query is counted, not fatal
                with lock:
                    out["answers"].append((query, None))
                    out["errors"].append(f"{query}: {type(e).__name__}: {e}")
                continue
            with lock:
                out["answers"].append((query, rows))
                out["walls"].append(wall + late)
                out["late_s"].append(late)

    threads = [threading.Thread(target=client, args=(c,), name=f"bench-client-{i}")
               for i, c in enumerate(conns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    out["seconds"] = time.perf_counter() - t0
    for c in conns:
        c.close()
    return out
