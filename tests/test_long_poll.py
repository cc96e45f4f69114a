"""The result GET as a long-poll: /v1/statement/{id}/{token} parks on the
server until the query ends (protocol.MAX_WAIT_S at the most), and the client
never sleeps in front of a server that parks.

Reference pattern: TestStatementResource's waitForResults cases. The runner
is a stub whose `execute` blocks on an Event, so no test waits on the
engine."""
import functools
import glob
import json
import threading
import time
import types
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

import presto_tpu.client as client_module
from presto_tpu.client import QueryError, StatementClient
from presto_tpu.server import PrestoTpuServer, protocol
from presto_tpu.server.protocol import QueryManager
from presto_tpu.utils.metrics import METRICS

PARKED = "protocol.long_poll.parked"
WOKEN = "protocol.long_poll.woken"


class _Result:
    rows = [(1, "a"), (2, "b")]
    column_names = ["k", "v"]
    types = None


class GatedRunner:
    """`execute` waits for `gate`, then answers two rows or raises `error`."""

    def __init__(self):
        self.gate = threading.Event()
        self.error = None

    def execute(self, sql):
        if not self.gate.wait(30.0):
            raise AssertionError("the test never opened the gate")
        if self.error is not None:
            raise self.error
        return _Result()


@pytest.fixture()
def served():
    runner = GatedRunner()
    server = PrestoTpuServer(runner, port=0)
    server.start()
    yield server, runner, f"http://127.0.0.1:{server.port}"
    runner.gate.set()      # a test that failed half way leaves nothing parked
    server.stop()


def _post(base, sql="select 1"):
    req = urllib.request.Request(f"{base}/v1/statement", data=sql.encode(),
                                 method="POST")
    return json.loads(urllib.request.urlopen(req, timeout=10).read())


class _Get(threading.Thread):
    """One GET on its own thread: `payload` and when it came back."""

    def __init__(self, url):
        super().__init__(daemon=True)
        self.url = url
        self.payload = None
        self.sent = time.monotonic()
        self.back = None
        self.start()

    def run(self):
        self.payload = json.loads(
            urllib.request.urlopen(self.url, timeout=60).read())
        self.back = time.monotonic()

    def result(self, timeout=10.0):
        self.join(timeout)
        assert not self.is_alive(), "the GET never came back"
        return self.payload


def _park(url, more=1):
    """A GET that is parked on the server: the handler counts `parked` before
    it waits, so the counter says when it is there."""
    before = METRICS.counter_value(PARKED)
    get = _Get(url)
    deadline = time.monotonic() + 5.0
    while METRICS.counter_value(PARKED) < before + more:
        assert time.monotonic() < deadline, "the GET never reached the park"
        time.sleep(0.002)
    time.sleep(0.02)   # from the count into the condition's wait
    assert get.is_alive(), "the server answered a query that is not done"
    return get


def _gained(name, before):
    after = METRICS.raw_snapshot(name)["histograms"].get(
        name, {"n": 0, "total": 0.0})
    was = before["histograms"].get(name, {"n": 0, "total": 0.0})
    return after["n"] - was["n"], after["total"] - was["total"]


def test_a_parked_get_comes_back_with_the_final_state_and_the_data(served):
    server, runner, base = served
    lags = []
    for _ in range(3):      # the quickest of three: the machine may stall one
        runner.gate.clear()
        first = _post(base)
        assert first["stats"]["state"] in ("QUEUED", "RUNNING")
        get = _park(first["nextUri"])
        runner.gate.set()
        payload = get.result()
        assert payload["stats"]["state"] == "FINISHED"
        assert payload["data"] == [[1, "a"], [2, "b"]]
        assert [c["name"] for c in payload["columns"]] == ["k", "v"]
        assert "nextUri" not in payload
        lags.append(get.back - server.manager.get(first["id"]).end_mono)
    assert min(lags) < 0.05, lags


def test_a_get_on_a_query_that_does_not_end_comes_back_after_the_limit(
        served, monkeypatch):
    _server, _runner, base = served
    monkeypatch.setattr(QueryManager, "await_done", functools.partialmethod(
        QueryManager.await_done, max_wait_s=0.2))
    parked, woken = (METRICS.counter_value(n) for n in (PARKED, WOKEN))
    first = _post(base)
    get = _Get(first["nextUri"])
    payload = get.result()
    assert 0.2 <= get.back - get.sent < 1.0
    assert payload["stats"]["state"] == "RUNNING"
    assert payload["nextUri"] == first["nextUri"]
    assert "data" not in payload and "error" not in payload
    assert METRICS.counter_value(PARKED) == parked + 1
    assert METRICS.counter_value(WOKEN) == woken     # the limit ended it


def test_the_limit_is_the_references_second():
    assert protocol.MAX_WAIT_S == 1.0
    assert QueryManager.await_done.__defaults__ == (protocol.MAX_WAIT_S,)


def test_delete_wakes_a_parked_get_with_the_canceled_error(served):
    _server, runner, base = served
    first = _post(base)
    get = _park(first["nextUri"])
    req = urllib.request.Request(first["nextUri"], method="DELETE")
    assert urllib.request.urlopen(req, timeout=10).status == 204
    payload = get.result()     # woken by the final state, or it reads RUNNING
    assert payload["stats"]["state"] == "CANCELED"
    assert payload["error"]["errorType"] == "QueryCanceled"
    assert "nextUri" not in payload
    runner.gate.set()


def test_a_raising_runner_wakes_a_parked_get_with_the_failure(served):
    _server, runner, base = served
    runner.error = ValueError("no such thing")
    first = _post(base)
    get = _park(first["nextUri"])
    runner.gate.set()
    payload = get.result()
    assert payload["stats"]["state"] == "FAILED"
    assert payload["error"]["errorType"] == "ValueError"
    assert payload["error"]["message"] == "no such thing"
    assert "nextUri" not in payload


def test_close_wakes_a_parked_get(served, monkeypatch):
    server, runner, base = served
    # no limit to fall back on: only close() can end this park
    monkeypatch.setattr(QueryManager, "await_done", functools.partialmethod(
        QueryManager.await_done, max_wait_s=60.0))
    first = _post(base)
    get = _park(first["nextUri"])
    server.manager.close(timeout_s=0.05)    # the query itself is still gated
    payload = get.result(timeout=5.0)
    assert payload["stats"]["state"] == "RUNNING"
    # and a GET after the close is answered at once, parked or not
    again = _Get(first["nextUri"])
    assert again.result(timeout=5.0)["stats"]["state"] == "RUNNING"
    runner.gate.set()


def test_a_served_short_query_parks_once_and_its_answer_does_not_wait(served):
    _server, runner, base = served
    waits = []
    for _ in range(3):
        runner.gate.clear()
        before = METRICS.raw_snapshot("query.result_wait_s")
        parked, woken = (METRICS.counter_value(n) for n in (PARKED, WOKEN))
        first = _post(base)
        get = _park(first["nextUri"])
        runner.gate.set()
        assert get.result()["stats"]["state"] == "FINISHED"
        assert METRICS.counter_value(PARKED) == parked + 1
        assert METRICS.counter_value(WOKEN) == woken + 1
        n, total = _gained("query.result_wait_s", before)
        assert n == 1
        waits.append(total)
    assert min(waits) < 0.010, waits
    summary = METRICS.histogram_summary("protocol.long_poll_s")
    assert summary["count"] >= 3


def test_a_get_on_a_finished_query_does_not_park(served):
    _server, runner, base = served
    runner.gate.set()
    first = _post(base)
    assert _Get(first["nextUri"]).result()["stats"]["state"] == "FINISHED"
    parked = METRICS.counter_value(PARKED)
    again = _Get(first["nextUri"])
    assert again.result()["data"] == [[1, "a"], [2, "b"]]
    assert METRICS.counter_value(PARKED) == parked
    assert again.back - again.sent < 0.5


def test_every_parked_get_wakes_with_its_own_querys_state(served):
    """Eight queries parked at once on one condition: one notify_all wakes
    them all, and each answers for its own query."""
    _server, runner, base = served
    parked, woken = (METRICS.counter_value(n) for n in (PARKED, WOKEN))
    firsts = [_post(base, f"select {i}") for i in range(8)]
    gets = [_park(f["nextUri"]) for f in firsts]
    req = urllib.request.Request(firsts[0]["nextUri"], method="DELETE")
    urllib.request.urlopen(req, timeout=10)
    assert gets[0].result(timeout=5.0)["stats"]["state"] == "CANCELED"
    time.sleep(0.05)
    assert all(g.is_alive() for g in gets[1:])   # woken, not done: parked on
    runner.gate.set()
    for first, get in zip(firsts[1:], gets[1:]):
        payload = get.result(timeout=5.0)
        assert payload["id"] == first["id"]
        assert payload["stats"]["state"] == "FINISHED"
    assert METRICS.counter_value(PARKED) == parked + 8
    assert METRICS.counter_value(WOKEN) == woken + 8


def _clock(sleeps):
    """What the client module sees as `time`: the real clocks, and a `sleep`
    that is recorded before it is taken."""
    def sleep(seconds):
        sleeps.append(seconds)
        time.sleep(seconds)

    return types.SimpleNamespace(time=time.time, monotonic=time.monotonic,
                                 sleep=sleep)


def _counting(client):
    """-> [monotonic start of each GET `client` makes]."""
    starts, request = [], client._request

    def counted(method, url, body=None):
        if method == "GET":
            starts.append(time.monotonic())
        return request(method, url, body)

    client._request = counted
    return starts


def test_the_client_makes_one_get_and_never_sleeps_before_a_parking_server(
        served, monkeypatch):
    _server, runner, base = served
    sleeps = []
    monkeypatch.setattr(client_module, "time", _clock(sleeps))
    client = StatementClient(base, "select 1")
    gets = _counting(client)
    threading.Timer(0.15, runner.gate.set).start()   # three polls of old
    t0 = time.monotonic()
    assert list(client.rows()) == [[1, "a"], [2, "b"]]
    assert time.monotonic() - t0 >= 0.15
    assert len(gets) == 1
    assert sleeps == []
    assert client.stats["state"] == "FINISHED"


def test_the_client_raises_what_woke_its_parked_get(served):
    _server, runner, base = served
    runner.error = RuntimeError("boom")
    threading.Timer(0.05, runner.gate.set).start()
    with pytest.raises(QueryError, match="boom"):
        list(StatementClient(base, "select 1").rows())


class _AtOnce(BaseHTTPRequestHandler):
    """A server that does not hold a GET (an older one, a proxy that does
    not): RUNNING at once until `done_at`, then one row."""
    done_at = 0.0

    def log_message(self, fmt, *args):  # noqa: A003
        pass

    def _answer(self):
        uri = f"http://127.0.0.1:{self.server.server_address[1]}" \
              "/v1/statement/q1/0"
        if time.monotonic() < self.done_at:
            payload = {"id": "q1", "stats": {"state": "RUNNING"},
                       "nextUri": uri}
        else:
            payload = {"id": "q1", "stats": {"state": "FINISHED"},
                       "columns": [{"name": "k", "type": "bigint"}],
                       "data": [[7]]}
        body = json.dumps(payload).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers.get("Content-Length", 0)))
        self._answer()

    do_GET = _answer  # noqa: N815


def test_the_client_keeps_its_cadence_before_a_server_that_answers_at_once(
        monkeypatch):
    handler = type("AtOnce", (_AtOnce,), {"done_at": time.monotonic() + 0.4})
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        sleeps = []
        monkeypatch.setattr(client_module, "time", _clock(sleeps))
        client = StatementClient(
            f"http://127.0.0.1:{httpd.server_address[1]}", "select 1",
            poll_interval_s=0.05)
        gets = _counting(client)
        t0 = time.monotonic()
        assert list(client.rows()) == [[7]]
        seconds = time.monotonic() - t0
    finally:
        httpd.shutdown()
        httpd.server_close()
        thread.join(5.0)
    # no more than 1 / poll_interval_s GETs a second: two GETs of a query
    # that is not done start at least the interval apart
    assert 3 <= len(gets) <= seconds / 0.05 + 1
    gaps = [b - a for a, b in zip(gets, gets[1:])]
    assert min(gaps) >= 0.05 - 1e-3, gaps
    # the first GET follows the POST at once; each sleep is what the GET left
    assert len(sleeps) == len(gets) - 1
    assert all(0 < s <= 0.05 for s in sleeps)


def test_the_parks_span_lies_outside_the_gets_http_span(served, tmp_path):
    """engine_spans.partition labels a piece `http` where a handler's span
    lies over it: a presto.http.GET as long as the query would name every
    unattributed second `http`."""
    import jax

    _server, runner, base = served
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        first = _post(base)
        get = _park(first["nextUri"])
        time.sleep(0.1)
        runner.gate.set()
        assert get.result()["stats"]["state"] == "FINISHED"
        deadline = time.monotonic() + 5.0     # the root ends after the answer
        while _server.manager.get(first["id"]).stages \
                and time.monotonic() < deadline:
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if e.name.startswith("presto.") \
                        and stats.get("qid") == first["id"]:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns, stats))
    (p0, p1, stats), = spans["presto.protocol.long_poll"]
    (g0, g1, _), = spans["presto.http.GET /v1/statement/{id}/{token}"]
    (r0, r1, _), = spans["presto.query"]
    assert int(stats["woken"]) == 1
    assert p1 - p0 >= 0.1e9             # the park is as long as the query
    assert p1 <= g0                      # ... and over before the GET's span
    assert g1 - g0 < 0.05e9
    assert r0 <= p0 and g1 <= r1         # both inside the query's root
