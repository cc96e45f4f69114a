"""Service-layer subsystems: resource groups (admission control), event
listeners, transactions, access control, cluster memory manager.

Reference analogues: execution/resourceGroups/InternalResourceGroup.java,
spi/eventlistener/ + event/QueryMonitor.java, transaction/
InMemoryTransactionManager.java, security/AccessControlManager.java +
FileBasedSystemAccessControl, memory/ClusterMemoryManager.java +
TotalReservationLowMemoryKiller."""
import threading
import time

import pytest

from presto_tpu.cluster.memory_manager import ClusterMemoryManager
from presto_tpu.runner import LocalQueryRunner
from presto_tpu.security import (AccessDeniedException, AccessRule,
                                 FileBasedAccessControl)
from presto_tpu.server.protocol import QueryManager
from presto_tpu.server.resource_groups import (GroupSpec, QueryRejected,
                                               ResourceGroupManager,
                                               SelectorSpec)
from presto_tpu.spi.eventlistener import (EventListener, QueryMonitor)
from presto_tpu.transaction import TransactionManager


# ------------------------------------------------------------ resource groups

def test_concurrency_limit_queues_then_admits():
    rg = ResourceGroupManager(GroupSpec("root", hard_concurrency_limit=1,
                                        max_queued=10))
    t1 = rg.submit("q1")
    assert t1.admitted.is_set()
    admitted = []

    def second():
        t2 = rg.submit("q2", timeout_s=10)
        admitted.append(t2)

    th = threading.Thread(target=second)
    th.start()
    time.sleep(0.1)
    assert not admitted  # queued behind q1
    rg.finish(t1)
    th.join(5)
    assert admitted and admitted[0].admitted.is_set()
    rg.finish(admitted[0])
    assert rg.stats()["root"] == (0, 0)


def test_queue_full_rejects():
    rg = ResourceGroupManager(GroupSpec("root", hard_concurrency_limit=1,
                                        max_queued=0))
    t1 = rg.submit("q1")
    with pytest.raises(QueryRejected, match="Too many queued"):
        rg.submit("q2")
    rg.finish(t1)


def test_selectors_route_to_subgroups():
    spec = GroupSpec("root", hard_concurrency_limit=10, sub_groups=[
        GroupSpec("etl", hard_concurrency_limit=1, max_queued=5),
        GroupSpec("adhoc", hard_concurrency_limit=5),
    ])
    rg = ResourceGroupManager(spec, selectors=[
        SelectorSpec(group="root.etl", source_regex="etl-.*"),
        SelectorSpec(group="root.adhoc"),
    ])
    a = rg.submit("q1", user="u", source="etl-nightly")
    assert a.group.name == "root.etl"
    b = rg.submit("q2", user="u", source="cli")
    assert b.group.name == "root.adhoc"
    # etl is at its limit of 1; adhoc still admits
    c = rg.submit("q3", user="u", source="cli")
    assert c.admitted.is_set()
    for tk in (a, b, c):
        rg.finish(tk)


def test_cpu_quota_blocks_admission():
    rg = ResourceGroupManager(GroupSpec("root", cpu_quota_per_s=0.5))
    t1 = rg.submit("q1")
    rg.finish(t1, cpu_seconds=100.0)  # burn far past the quota
    t_start = time.monotonic()
    with pytest.raises(QueryRejected):
        rg.submit("q2", timeout_s=0.3)
    assert time.monotonic() - t_start >= 0.25  # waited, then timed out


# ------------------------------------------------------- events + transactions

class _Recorder(EventListener):
    def __init__(self):
        self.created = []
        self.completed = []

    def query_created(self, e):
        self.created.append(e)

    def query_completed(self, e):
        self.completed.append(e)


class _Exploder(EventListener):
    def query_created(self, e):
        raise RuntimeError("bad listener")


def _wait_done(mgr, info, timeout=60):
    deadline = time.time() + timeout
    while not info.done() and time.time() < deadline:
        time.sleep(0.02)
    assert info.done()


def test_query_manager_emits_events_and_isolates_listener_errors():
    rec = _Recorder()
    mgr = QueryManager(LocalQueryRunner(),
                       monitor=QueryMonitor([_Exploder(), rec]))
    info = mgr.submit("select 1", user="alice")
    _wait_done(mgr, info)
    assert info.state == "FINISHED"
    assert [e.query_id for e in rec.created] == [info.query_id]
    assert rec.completed[0].state == "FINISHED"
    assert rec.completed[0].user == "alice"
    assert rec.completed[0].row_count == 1

    info2 = mgr.submit("select bogus_column from nation")
    _wait_done(mgr, info2)
    assert rec.completed[1].state == "FAILED"
    assert rec.completed[1].error is not None


class _TxConnector:
    """Connector with transaction hooks (records the calls)."""

    def __init__(self):
        self.calls = []

    def begin_transaction(self, tid):
        self.calls.append(("begin", tid))

    def commit_transaction(self, tid):
        self.calls.append(("commit", tid))

    def rollback_transaction(self, tid):
        self.calls.append(("rollback", tid))


class _Catalogs:
    def __init__(self, conn):
        self._conn = conn

    def connector(self, name):
        return self._conn


def test_transaction_commit_and_abort():
    conn = _TxConnector()
    tm = TransactionManager(_Catalogs(conn))
    tx = tm.begin("q1")
    tm.join(tx, "memory")
    tm.join(tx, "memory")  # idempotent
    tm.commit(tx)
    assert conn.calls == [("begin", tx.transaction_id),
                          ("commit", tx.transaction_id)]
    tx2 = tm.begin("q2")
    tm.join(tx2, "memory")
    tm.abort(tx2)
    assert conn.calls[-1] == ("rollback", tx2.transaction_id)
    assert tm.active_transactions() == []


# ------------------------------------------------------------- access control

def test_file_based_access_control():
    ac = FileBasedAccessControl([
        AccessRule(user_regex="bob", table_regex="nation",
                   privileges=("select", "execute")),
        AccessRule(user_regex="admin.*"),
        AccessRule(user_regex=".*", privileges=("execute",)),
    ])
    ac.check_can_execute_query("bob")
    ac.check_can_select("bob", "tpch", "tiny", "nation")
    with pytest.raises(AccessDeniedException):
        ac.check_can_select("bob", "tpch", "tiny", "orders")
    ac.check_can_select("admin1", "tpch", "tiny", "orders")
    with pytest.raises(AccessDeniedException):
        ac.check_can_select("eve", "tpch", "tiny", "nation")


def test_runner_enforces_table_access():
    r = LocalQueryRunner()
    r.session = r.session.with_user("bob") if hasattr(r.session, "with_user") \
        else r.session
    r.session.user = "bob"
    r.access_control = FileBasedAccessControl([
        AccessRule(user_regex="bob", table_regex="nation",
                   privileges=("select", "execute"))])
    assert r.execute("select count(*) from nation").rows == [[25]]
    with pytest.raises(AccessDeniedException):
        r.execute("select count(*) from orders")
    with pytest.raises(AccessDeniedException):
        r.execute("create table memory.default.x as select 1 as a")


# -------------------------------------------------------- cluster memory mgr

class _Node:
    def __init__(self, uri):
        self.uri = uri


class _Nodes:
    def __init__(self, uris):
        self._nodes = [_Node(u) for u in uris]

    def active_nodes(self):
        return self._nodes


def test_cluster_memory_manager_kills_biggest_query():
    statuses = {
        "w1": {"queryMemory": {"q1": 10 << 20, "q2": 50 << 20}},
        "w2": {"queryMemory": {"q1": 15 << 20, "q2": 30 << 20}},
    }
    killed = []
    mgr = ClusterMemoryManager(
        _Nodes(["w1", "w2"]), kill_query=killed.append,
        limit_bytes=64 << 20, grace_polls=2,
        fetch_status=lambda uri: statuses[uri])
    assert mgr.poll_once() is None          # first over-limit poll: grace
    assert mgr.poll_once() == "q2"          # q2 holds 80MB total -> victim
    assert killed == ["q2"]
    assert mgr.last_total == 105 << 20
    # under the limit: counter resets, nothing killed
    statuses["w1"] = {"queryMemory": {"q1": 1 << 20}}
    statuses["w2"] = {"queryMemory": {}}
    assert mgr.poll_once() is None
    assert killed == ["q2"]


def test_memory_manager_revoke_beat_before_kill():
    """Kill ordering regression: a revocable-heavy cluster first gets a
    `memory.revoke` journal + revoke request and ONE more poll for spilling
    to land; when the spill relieves the pressure, nothing is killed."""
    from presto_tpu.utils.events import JOURNAL

    state = {"spilled": False}

    def fetch(uri):
        if not state["spilled"]:
            return {"queryMemory": {"q1": 100 << 20},
                    "queryRevocable": {"q1": 90 << 20}}
        # post-revoke: state moved to the disk ledger, RAM pressure gone
        return {"queryMemory": {"q1": 10 << 20},
                "querySpill": {"q1": 90 << 20}}

    killed, revoke_calls = [], []
    mgr = ClusterMemoryManager(
        _Nodes(["w1"]), kill_query=killed.append, limit_bytes=50 << 20,
        grace_polls=2, fetch_status=fetch,
        request_revoke=lambda: revoke_calls.append(1))
    assert mgr.poll_once() is None              # over, inside grace
    seq_before = JOURNAL.last_seq()
    assert mgr.poll_once() is None              # grace up -> revoke beat
    assert revoke_calls == [1]
    revokes = [e for e in JOURNAL.events(since=seq_before,
                                         kind="memory.revoke")]
    assert revokes and revokes[-1]["requested_bytes"] == 90 << 20
    state["spilled"] = True                     # the beat let spilling land
    assert mgr.poll_once() is None
    assert killed == [], "revocable-heavy query was killed instead of spilled"


def test_memory_manager_kills_after_unhelpful_revoke_with_evidence():
    """When the revoke beat does NOT relieve pressure, the NEXT poll kills —
    and the `query.oom_killed` record says revocation was attempted and how
    many revocable bytes remained (post-mortem: 'killed too eagerly' vs
    'nothing left to spill')."""
    from presto_tpu.utils.events import JOURNAL

    def fetch(uri):
        return {"queryMemory": {"q1": 100 << 20, "q2": 30 << 20},
                "queryRevocable": {"q1": 40 << 20}}

    killed = []
    mgr = ClusterMemoryManager(
        _Nodes(["w1"]), kill_query=killed.append, limit_bytes=50 << 20,
        grace_polls=2, fetch_status=fetch)
    assert mgr.poll_once() is None              # grace
    assert mgr.poll_once() is None              # revoke beat (no killer yet)
    assert killed == []
    assert mgr.poll_once() == "q1"              # still over -> largest dies
    assert killed == ["q1"]
    kill = JOURNAL.events(kind="query.oom_killed")[-1]
    assert kill["revoke_attempted"] is True
    assert kill["revocable_bytes"] == 40 << 20


def test_worker_status_ships_spill_ledgers_and_gcs_residue():
    """/v1/status carries the queryRevocable + querySpill ledgers (the
    revoke-before-kill evidence and the disk rung), and its GC sweep walks
    the UNION of the pool's ledgers — spill-only residue of a dead query is
    cleared on the next poll."""
    import json as _json
    import urllib.request as _rq

    from presto_tpu.cluster.worker import WorkerServer
    from presto_tpu.memory import shared_general_pool

    w = WorkerServer(port=0).start()
    try:
        pool = shared_general_pool()
        pool.reserve_spill("q_dead_spill", 4096)  # no live task owns this
        with _rq.urlopen(f"{w.uri}/v1/status", timeout=2.0) as resp:
            st = _json.loads(resp.read())
        assert "querySpill" in st and "queryRevocable" in st
        assert "q_dead_spill" not in st["querySpill"]
        assert pool.spill_bytes("q_dead_spill") == 0, \
            "spill-only residue survived the status-poll GC"
    finally:
        w.stop()


def test_memory_manager_legacy_status_kills_at_grace():
    """Workers that report no queryRevocable (or none left) keep the
    original policy: kill as soon as grace expires — no wasted beat."""
    killed = []
    mgr = ClusterMemoryManager(
        _Nodes(["w1"]), kill_query=killed.append, limit_bytes=50,
        grace_polls=2,
        fetch_status=lambda uri: {"queryMemory": {"q1": 100}})
    assert mgr.poll_once() is None
    assert mgr.poll_once() == "q1"
    assert killed == ["q1"]


def test_memory_manager_tolerates_dead_worker():
    def fetch(uri):
        if uri == "dead":
            raise OSError("unreachable")
        return {"queryMemory": {"q1": 10}}

    mgr = ClusterMemoryManager(_Nodes(["dead", "ok"]), kill_query=lambda q: None,
                               limit_bytes=1 << 30, fetch_status=fetch)
    assert mgr.poll_once() is None
    assert mgr.last_total == 10


# ------------------------------------------------------------- config system

def test_etc_config_and_catalog_loading(tmp_path):
    """etc/config.properties + catalog/*.properties (airlift bootstrap +
    CatalogManager/PluginManager analogue)."""
    from presto_tpu.server.config import (load_catalogs, load_config,
                                          parse_properties,
                                          session_from_config)

    etc = tmp_path / "etc"
    (etc / "catalog").mkdir(parents=True)
    (etc / "config.properties").write_text(
        "# the coordinator\n"
        "http-server.http.port=9090\n"
        "session.catalog=gen\n"
        "session.schema=tiny\n"
        "session.task-concurrency=2\n")
    (etc / "catalog" / "gen.properties").write_text(
        "connector.name=tpch\ntpch.splits-per-node=4\n")
    (etc / "catalog" / "store.properties").write_text(
        f"connector.name=file\nfile.base-dir={tmp_path}/warehouse\n")

    conf = load_config(str(etc))
    assert conf["http-server.http.port"] == "9090"
    catalogs = load_catalogs(str(etc))
    assert sorted(catalogs.names()) == ["gen", "store"]
    session = session_from_config(conf)
    assert session.catalog == "gen" and session.schema == "tiny"
    assert session.properties["task_concurrency"] == 2

    r = LocalQueryRunner(session=session, catalogs=catalogs)
    assert r.execute("select count(*) from nation").rows == [[25]]

    with pytest.raises(ValueError, match="unknown connector"):
        (etc / "catalog" / "bad.properties").write_text("connector.name=nope\n")
        load_catalogs(str(etc))


def test_register_connector_factory(tmp_path):
    from presto_tpu.server import config as C

    calls = []

    def factory(name, props):
        calls.append((name, dict(props)))
        from presto_tpu.connectors.blackhole import BlackholeConnector
        return BlackholeConnector(name)

    C.register_connector_factory("custom", factory)
    try:
        etc = tmp_path / "etc"
        (etc / "catalog").mkdir(parents=True)
        (etc / "catalog" / "c1.properties").write_text(
            "connector.name=custom\nmy.flag=on\n")
        cats = C.load_catalogs(str(etc))
        assert cats.names() == ["c1"]
        assert calls == [("c1", {"my.flag": "on"})]
    finally:
        C.FACTORIES.pop("custom", None)


def test_every_session_default_has_a_reader():
    """A property nothing reads is a switch wired to nothing: every key of
    Session.DEFAULTS is named, quoted, somewhere in the engine besides the
    table that declares it."""
    import pathlib
    import re

    import presto_tpu
    from presto_tpu.metadata import Session

    root = pathlib.Path(presto_tpu.__file__).parent
    source = "\n".join(p.read_text() for p in sorted(root.rglob("*.py"))
                       if p.name != "metadata.py")
    quoted = set(re.findall(r"""["']([a-z][a-z0-9_]*)["']""", source))
    assert sorted(set(Session.DEFAULTS) - quoted) == []
