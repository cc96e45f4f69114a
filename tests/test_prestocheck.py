"""tools/prestocheck: the multi-pass static analysis suite gating tier-1.

Each pass gets synthetic fixture modules: a positive case (deliberately
seeded violation detected), a suppressed case (`# prestocheck: ignore[...]`
honored) and a clean/negative case. The whole-tree test is the tier-1 wiring
(successor to test_check_imports.test_whole_tree_is_clean): every `pytest
tests/` run fails on any new (non-baselined, non-suppressed) finding in
presto_tpu/ or tools/.
"""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.prestocheck import (all_pass_ids, load_baseline, run,  # noqa: E402
                               save_baseline)

EXPECTED_PASSES = {"undefined-name", "tracer-safety", "lock-discipline",
                   "exception-hygiene", "retry-discipline",
                   "mutable-default-args", "sleep-poll", "host-sync",
                   "unbounded-cache", "wallclock-duration",
                   "shared-state-race", "thread-lifecycle",
                   "print-hygiene", "tempfile-hygiene",
                   "resource-discipline", "close-propagation",
                   "retrace-risk", "cache-key-hygiene"}


def _scan(tmp_path, source, select=None, name="mod.py"):
    path = tmp_path / name
    path.write_text(textwrap.dedent(source))
    return run([str(path)], select=select, baseline_path=None).new_findings


def _messages(findings):
    return [f"{f.pass_id}: {f.message}" for f in findings]


def test_registry_has_all_six_passes():
    assert EXPECTED_PASSES <= set(all_pass_ids())


# ------------------------------------------------------------- tracer-safety

def test_tracer_safety_flags_side_effects_in_jit(tmp_path):
    findings = _scan(tmp_path, """
        import time
        import random
        import numpy as np
        import jax
        import jax.numpy as jnp

        COUNT = 0

        @jax.jit
        def kernel(x):
            global COUNT
            COUNT = COUNT + 1
            print("tracing", x)
            t = time.time()
            r = random.random()
            v = x.sum().item()
            h = np.asarray(x)
            return jnp.sum(x) + t + r + v
        """, select=["tracer-safety"])
    msgs = "\n".join(_messages(findings))
    assert "mutates global `COUNT`" in msgs
    assert "print()" in msgs
    assert "time.time()" in msgs
    assert "random.random()" in msgs
    assert ".item()" in msgs
    assert "host-numpy call np.asarray" in msgs


def test_tracer_safety_partial_jit_respects_static_argnames(tmp_path):
    findings = _scan(tmp_path, """
        import functools
        import numpy as np
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("shape",))
        def make(x, shape):
            pad = np.prod(shape)     # shape is static: concrete by contract
            return jnp.resize(x, shape) + pad

        @functools.partial(jax.jit, static_argnames=("n",))
        def bad(x, n):
            return jnp.sum(np.asarray(x)) + n   # x is traced: flagged
        """, select=["tracer-safety"])
    msgs = "\n".join(_messages(findings))
    assert "np.prod" not in msgs
    assert "np.asarray" in msgs and "`x`" in msgs


def test_tracer_safety_reaches_helpers_and_jit_call_roots(tmp_path):
    findings = _scan(tmp_path, """
        import jax

        def helper(x):
            print("helper side effect")
            return x

        class Op:
            def _process(self, page):
                return helper(page)

            def compiled(self):
                return jax.jit(self._process)
        """, select=["tracer-safety"])
    msgs = "\n".join(_messages(findings))
    assert "in jit-traced `helper`" in msgs and "print()" in msgs


def test_tracer_safety_suppression_and_clean_module(tmp_path):
    findings = _scan(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def noisy(x):
            print("debug", x)  # prestocheck: ignore[tracer-safety]
            return jnp.sum(x)

        @jax.jit
        def clean(x):
            return jnp.sum(x) * 2

        def untraced(x):
            print(x)           # not reachable from any jit root: fine
            return x
        """, select=["tracer-safety"])
    assert findings == []


# ----------------------------------------------------------- lock-discipline

def test_lock_discipline_flags_blocking_calls_under_lock(tmp_path):
    findings = _scan(tmp_path, """
        import threading
        import time
        import urllib.request

        _LOCK = threading.Lock()

        class Client:
            def __init__(self):
                self._lock = threading.Lock()

            def bad_sleep(self):
                with self._lock:
                    time.sleep(0.1)

            def bad_io(self):
                with _LOCK:
                    return urllib.request.urlopen("http://x").read()

            def fine(self):
                with self._lock:
                    snapshot = dict(self.__dict__)
                    hit = snapshot.get("k")   # dict.get: not blocking
                time.sleep(0.1)               # outside the lock
                return hit
        """, select=["lock-discipline"])
    msgs = _messages(findings)
    assert len(msgs) == 2, msgs
    assert any("time.sleep()" in m and "Client._lock" in m for m in msgs)
    assert any("urlopen()" in m and "mod._LOCK" in m for m in msgs)


def test_lock_discipline_two_module_order_cycle(tmp_path):
    """The deadlock detector: module a takes A_LOCK then calls into b (which
    takes B_LOCK); module b takes B_LOCK then calls back into a (which takes
    A_LOCK). Opposite acquisition orders = a cycle in the global graph."""
    (tmp_path / "locka.py").write_text(textwrap.dedent("""
        import threading
        from lockb import enter_b

        A_LOCK = threading.Lock()

        def refresh_a():
            with A_LOCK:
                enter_b()

        def poke_a():
            with A_LOCK:
                return 1
        """))
    (tmp_path / "lockb.py").write_text(textwrap.dedent("""
        import threading
        from locka import poke_a

        B_LOCK = threading.Lock()

        def enter_b():
            with B_LOCK:
                return 2

        def refresh_b():
            with B_LOCK:
                poke_a()
        """))
    result = run([str(tmp_path)], select=["lock-discipline"],
                 baseline_path=None)
    cycles = [f for f in result.new_findings
              if "lock-order cycle" in f.message]
    assert len(cycles) == 1, _messages(result.new_findings)
    assert "locka.A_LOCK" in cycles[0].message
    assert "lockb.B_LOCK" in cycles[0].message


def test_lock_discipline_consistent_order_is_clean(tmp_path):
    """Same two locks, both paths take A then B: no cycle, no finding."""
    (tmp_path / "orda.py").write_text(textwrap.dedent("""
        import threading

        A_LOCK = threading.Lock()
        B_LOCK = threading.Lock()

        def path1():
            with A_LOCK:
                with B_LOCK:
                    return 1

        def path2():
            with A_LOCK:
                with B_LOCK:
                    return 2
        """))
    result = run([str(tmp_path)], select=["lock-discipline"],
                 baseline_path=None)
    assert result.new_findings == [], _messages(result.new_findings)


# --------------------------------------------------------- exception-hygiene

def test_exception_hygiene_positive_suppressed_and_justified(tmp_path):
    findings = _scan(tmp_path, """
        def silent():
            try:
                risky()
            except Exception:
                pass

        def bare_continue(items):
            for i in items:
                try:
                    risky(i)
                except:
                    continue

        def justified():
            try:
                risky()
            except Exception:
                pass  # best-effort cleanup; teardown also frees it

        def narrow():
            try:
                risky()
            except KeyError:
                pass

        def logged():
            try:
                risky()
            except Exception as e:
                print(e)

        def risky(i=0):
            return i
        """, select=["exception-hygiene"])
    assert len(findings) == 2, _messages(findings)
    assert findings[0].message.startswith("except Exception")
    assert findings[1].message.startswith("bare except")


def test_exception_hygiene_inline_suppression(tmp_path):
    findings = _scan(tmp_path, """
        def f():
            try:
                g()
            except Exception:  # prestocheck: ignore[exception-hygiene]
                pass

        def g():
            return 1
        """, select=["exception-hygiene"])
    assert findings == []


# --------------------------------------------------------- retry-discipline

def test_retry_discipline_flags_adhoc_loop_not_backoff(tmp_path):
    findings = _scan(tmp_path, """
        import time
        import urllib.request

        def adhoc(url):
            while True:
                try:
                    return urllib.request.urlopen(url).read()
                except OSError:
                    time.sleep(1.0)

        def bounded(url):
            for _ in range(5):
                try:
                    return urllib.request.urlopen(url).read()
                except OSError:
                    time.sleep(0.5)

        def disciplined(url, backoff):
            while True:
                try:
                    return urllib.request.urlopen(url).read()
                except OSError:
                    if backoff.failure():
                        raise
                    backoff.wait()

        def plain_poll(flag):
            while not flag.is_set():
                time.sleep(0.01)   # no I/O try/except: not a retry loop
        """, select=["retry-discipline"])
    assert len(findings) == 2, _messages(findings)
    assert {f.line for f in findings} == {6, 13}


_BOUNDARY_SOURCE = """
    import urllib.request

    def one_shot(url):
        return urllib.request.urlopen(url, timeout=5.0).read()

    def classified(url):
        try:
            return urllib.request.urlopen(url, timeout=5.0).read()
        except OSError:
            return None

    def disciplined(url, backoff):
        while True:
            try:
                return urllib.request.urlopen(url).read()
            except OSError:
                if backoff.failure():
                    raise
                backoff.wait()
"""


def test_retry_discipline_flags_raw_urlopen_on_cluster_boundary(tmp_path):
    # the boundary check applies to files under presto_tpu/cluster/: a raw
    # urlopen with no try and no backoff is a one-shot RPC whose transport
    # failure propagates unclassified
    mod = tmp_path / "presto_tpu" / "cluster" / "boundary.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(textwrap.dedent(_BOUNDARY_SOURCE))
    findings = run([str(mod)], select=["retry-discipline"],
                   baseline_path=None).new_findings
    assert len(findings) == 1, _messages(findings)
    assert findings[0].line == 5
    assert "raw urlopen" in findings[0].message


def test_retry_discipline_boundary_scope_and_suppression(tmp_path):
    # the same module OUTSIDE presto_tpu/cluster/ is not on the
    # coordinator<->worker boundary: no findings
    outside = tmp_path / "elsewhere" / "boundary.py"
    outside.parent.mkdir(parents=True)
    outside.write_text(textwrap.dedent(_BOUNDARY_SOURCE))
    assert run([str(outside)], select=["retry-discipline"],
               baseline_path=None).new_findings == []
    # an inline justification suppresses the boundary finding
    mod = tmp_path / "presto_tpu" / "cluster" / "probe.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(textwrap.dedent("""
        import urllib.request

        def probe(url):
            # raise-through by design: the caller classifies
            return urllib.request.urlopen(url, timeout=2.0).read()  # prestocheck: ignore[retry-discipline]
        """))
    assert run([str(mod)], select=["retry-discipline"],
               baseline_path=None).new_findings == []


# ----------------------------------------------------------------- sleep-poll

def test_sleep_poll_flags_fixed_interval_polling_loop(tmp_path):
    findings = _scan(tmp_path, """
        import time

        def busy_poll(blocked_on):
            b = blocked_on()
            while b is not None and not b():
                time.sleep(0.001)      # the driver.run_to_completion bug

        def backed_off(blocked_on, backoff):
            b = blocked_on()
            while b is not None and not b():
                backoff.failure()
                backoff.wait()

        def parked(event):
            while not event.is_set():
                event.wait(0.1)        # sanctioned: condition/event wait
        """, select=["sleep-poll"])
    assert len(findings) == 1, _messages(findings)
    assert findings[0].line == 6


def test_sleep_poll_exempts_retry_streaming_and_inner_loops(tmp_path):
    findings = _scan(tmp_path, """
        import time
        import urllib.request

        def retry(url):
            while True:
                try:                   # retry-discipline's domain, not ours
                    return urllib.request.urlopen(url).read()
                except OSError:
                    time.sleep(1.0)

        def stream(client):
            while True:
                yield client.poll()    # pacing an external peer
                time.sleep(0.5)

        def nested(jobs):
            for j in jobs:             # only the INNER loop is the poll site
                while not j.done():
                    time.sleep(0.01)

        def inner_wait_no_excuse(jobs, flag):
            while not flag:            # OUTER sleep still flagged: the
                for j in jobs:         # inner loop's wait() is not ITS wait
                    j.cond.wait(0.1)
                time.sleep(0.5)
        """, select=["sleep-poll"])
    assert len(findings) == 2, _messages(findings)
    # the nested inner while, and the outer loop whose own sleep is not
    # excused by a sanctioned wait inside a nested loop
    assert {f.line for f in findings} == {19, 23}


def test_sleep_poll_suppression(tmp_path):
    findings = _scan(tmp_path, """
        import time

        def poll(flag):
            while not flag:  # prestocheck: ignore[sleep-poll]
                time.sleep(0.5)
        """, select=["sleep-poll"])
    assert findings == []


# ----------------------------------------------------------------- host-sync

def test_host_sync_flags_syncs_in_operator_hot_methods(tmp_path):
    findings = _scan(tmp_path, """
        import numpy as np
        import jax

        class FancyOperator:
            def add_input(self, page):
                n = int(np.asarray(page.mask).sum())
                v = page.blocks[0].data.sum().item()
                host = jax.device_get(page)
                self._n = n + v

            def get_output(self):
                if self._pending is not None:
                    self._pending.mask.block_until_ready()
                return self._pending
        """, select=["host-sync"])
    msgs = "\n".join(_messages(findings))
    assert len(findings) == 4, msgs
    assert "np.asarray(...)" in msgs
    assert ".item()" in msgs
    assert "jax.device_get(...)" in msgs
    assert ".block_until_ready()" in msgs


def test_host_sync_ignores_non_operators_and_cold_methods(tmp_path):
    findings = _scan(tmp_path, """
        import numpy as np

        class PageCodec:                  # not an operator class
            def add_input(self, page):
                return np.asarray(page)

        class SinkOperator:
            def finish(self):             # not a per-page hot method
                return np.asarray(self._acc)

            def add_input(self, page):
                self._acc = page          # no sync: clean
        """, select=["host-sync"])
    assert findings == [], _messages(findings)


def test_host_sync_detects_operator_by_base_class(tmp_path):
    findings = _scan(tmp_path, """
        import numpy as np
        from presto_tpu.ops.operator import Operator

        class Passthrough(Operator):
            def add_input(self, page):
                self._rows += int(np.asarray(page.mask).sum())
        """, select=["host-sync"])
    assert len(findings) == 1, _messages(findings)


def test_host_sync_suppression(tmp_path):
    findings = _scan(tmp_path, """
        import numpy as np

        class AdaptiveOperator:
            def add_input(self, page):
                if self._mode is None:  # once per stream, not per page
                    frac = np.asarray(page.mask).mean()  # prestocheck: ignore[host-sync]
                    self._mode = "pack" if frac < 0.5 else "pass"
        """, select=["host-sync"])
    assert findings == [], _messages(findings)


# ------------------------------------------ pallas kernel bodies as jit roots

def test_tracer_safety_pallas_kernel_is_a_root(tmp_path):
    findings = _scan(tmp_path, """
        import time
        import jax
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            t = time.time()              # freezes at trace time: flagged
            n = x_ref[0].item()          # concretizes a Ref: flagged
            o_ref[:] = x_ref[:] * 2

        def launch(x):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
        """, select=["tracer-safety"])
    msgs = "\n".join(_messages(findings))
    assert "time.time()" in msgs and "`kernel`" in msgs
    assert ".item()" in msgs


def test_tracer_safety_pallas_flags_python_control_flow_on_refs(tmp_path):
    findings = _scan(tmp_path, """
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        TRIPS = 8

        def kernel(x_ref, mask_ref, o_ref):
            if mask_ref[0]:              # python branch on a Ref: flagged
                o_ref[:] = x_ref[:]
            while x_ref[0] > 0:          # python loop on a Ref: flagged
                pass
            for _d in range(TRIPS):      # static python loop: fine
                o_ref[:] = o_ref[:] + 1

        def launch(x, mask):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x, mask)
        """, select=["tracer-safety"])
    msgs = _messages(findings)
    assert any("`if` on kernel parameter `mask_ref`" in m for m in msgs), msgs
    assert any("`while` on kernel parameter `x_ref`" in m for m in msgs), msgs
    assert len(msgs) == 2, msgs


def test_tracer_safety_pallas_clean_kernel(tmp_path):
    findings = _scan(tmp_path, """
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            def trip(d, acc):
                return acc + x_ref[d]
            o_ref[:] = lax.fori_loop(0, 8, trip, jnp.zeros_like(o_ref[:]))

        def launch(x):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
        """, select=["tracer-safety"])
    assert findings == [], _messages(findings)


def test_pallas_roots_resolve_factory_made_kernels(tmp_path):
    # the repo's real kernels come from builder factories:
    # pl.pallas_call(_make_body(n, s), ...) — the closure defined inside
    # the factory must be treated as the kernel body by BOTH passes
    src = """
        import time
        import numpy as np
        import jax
        from jax.experimental import pallas as pl

        def _make_body(slots):
            def kernel(x_ref, o_ref):
                t = time.time()                 # tracer-safety: flagged
                _h = np.asarray(x_ref[:])       # host-sync: flagged
                o_ref[:] = x_ref[:] * slots
            return kernel

        def launch(x):
            return pl.pallas_call(
                _make_body(8),
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
        """
    ts = _scan(tmp_path, src, select=["tracer-safety"])
    assert any("time.time()" in m and "`kernel`" in m
               for m in _messages(ts)), _messages(ts)
    hs = _scan(tmp_path, src, select=["host-sync"], name="mod2.py")
    assert any("np.asarray" in m and "pallas kernel `kernel`" in m
               for m in _messages(hs)), _messages(hs)


def test_host_sync_flags_syncs_in_pallas_kernels(tmp_path):
    findings = _scan(tmp_path, """
        import numpy as np
        import jax
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            host = np.asarray(x_ref[:])          # host sync in a kernel
            o_ref[:] = x_ref[:].block_until_ready()

        def launch(x):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
        """, select=["host-sync"])
    msgs = _messages(findings)
    assert any("np.asarray" in m and "pallas kernel `kernel`" in m
               for m in msgs), msgs
    assert any(".block_until_ready()" in m for m in msgs), msgs


def test_host_sync_pallas_clean_and_suppressed(tmp_path):
    findings = _scan(tmp_path, """
        import numpy as np
        import jax
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:] * 2

        def debug_kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:]
            _peek = np.asarray(o_ref[:])  # prestocheck: ignore[host-sync]

        def launch(x):
            a = pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
            return pl.pallas_call(
                debug_kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(a)

        def host_helper(x):
            return np.asarray(x)  # NOT a kernel: out of this pass's scope
        """, select=["host-sync"])
    assert findings == [], _messages(findings)


# ------------------------------------------------------- mutable-default-args

def test_mutable_defaults_flagged_and_none_is_fine(tmp_path):
    findings = _scan(tmp_path, """
        def f(a, xs=[], *, opts={}):
            return a, xs, opts

        def g(a, xs=None, n=3, s="x", t=()):
            return a, xs, n, s, t

        def h(m=dict()):
            return m
        """, select=["mutable-default-args"])
    msgs = _messages(findings)
    assert len(msgs) == 3, msgs
    assert any("xs=[]" in m for m in msgs)
    assert any("opts={}" in m for m in msgs)
    assert any("m=dict()" in m for m in msgs)


# ----------------------------------------------------------- undefined-name

def test_undefined_name_pass_via_suite(tmp_path):
    findings = _scan(tmp_path, """
        from typing import List

        class C:
            def __init__(self):
                self._m: Dict[str, int] = {}
                self.ok: List[int] = []
        """, select=["undefined-name"])
    assert len(findings) == 1 and "'Dict'" in findings[0].message


# ------------------------------------------------- baseline + suppressions

def test_baseline_grandfathers_old_findings_only(tmp_path):
    mod = tmp_path / "legacy.py"
    mod.write_text("def f(xs=[]):\n    return xs\n")
    baseline_path = str(tmp_path / "baseline.json")

    first = run([str(mod)], baseline_path=None)
    assert len(first.new_findings) == 1
    save_baseline(first.findings, baseline_path)
    assert load_baseline(baseline_path)

    grandfathered = run([str(mod)], baseline_path=baseline_path)
    assert grandfathered.new_findings == []
    assert len(grandfathered.baselined) == 1
    assert grandfathered.exit_code == 0

    # a NEW violation in the same file still fails the run
    mod.write_text("def f(xs=[]):\n    return xs\n\ndef g(m={}):\n"
                   "    return m\n")
    after = run([str(mod)], baseline_path=baseline_path)
    assert len(after.new_findings) == 1 and "m={}" in after.new_findings[0].message
    assert after.exit_code == 1


def test_bare_ignore_suppresses_every_pass(tmp_path):
    findings = _scan(tmp_path, """
        def f(xs=[]):  # prestocheck: ignore
            return undefined_thing
        """)
    # the default-arg finding sits on the annotated line; the undefined
    # name on the next line still fires
    assert len(findings) == 1, _messages(findings)
    assert findings[0].pass_id == "undefined-name"


def test_suppression_inside_string_literal_is_not_honored(tmp_path):
    """Only real COMMENT tokens suppress — the directive quoted in a
    docstring (e.g. documentation of the syntax itself) must not."""
    findings = _scan(tmp_path, '''
        DOC = "use `# prestocheck: ignore[mutable-default-args]` to silence"

        def f(xs=[]):
            return xs, DOC
        ''', select=["mutable-default-args"])
    assert len(findings) == 1


def test_malformed_suppression_fails_closed(tmp_path):
    """A typo'd pass id must suppress NOTHING, not everything."""
    findings = _scan(tmp_path, """
        def f(xs=[]):  # prestocheck: ignore[mutable.default.args]
            return xs
        """, select=["mutable-default-args"])
    assert len(findings) == 1


def test_suppression_space_before_bracket_stays_targeted(tmp_path):
    """`ignore [pass-id]` (space before bracket) must suppress exactly that
    pass — not degrade to a bare suppress-all."""
    findings = _scan(tmp_path, """
        def f(xs=[]):  # prestocheck: ignore [mutable-default-args]
            return missing_name
        """)
    assert len(findings) == 1, _messages(findings)
    assert findings[0].pass_id == "undefined-name"


def test_lock_discipline_same_basename_modules_not_conflated(tmp_path):
    """Two unrelated util.py files in different dirs, each internally
    consistent, must stay distinct graph nodes (no phantom cycle)."""
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    (tmp_path / "a" / "util.py").write_text(textwrap.dedent("""
        import threading
        A_LOCK = threading.Lock()
        def helper():
            with A_LOCK:
                return 1
        def outer():
            with A_LOCK:
                helper2()
        def helper2():
            return 2
        """))
    (tmp_path / "b" / "util.py").write_text(textwrap.dedent("""
        import threading
        B_LOCK = threading.Lock()
        def helper2():
            with B_LOCK:
                return 1
        def outer2():
            with B_LOCK:
                helper()
        def helper():
            return 2
        """))
    result = run([str(tmp_path)], select=["lock-discipline"],
                 baseline_path=None)
    assert result.new_findings == [], _messages(result.new_findings)


def test_check_imports_shim_honors_suppressions(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import check_imports
    finally:
        sys.path.pop(0)
    path = tmp_path / "mod.py"
    path.write_text(
        "x = silenced_name  # prestocheck: ignore[undefined-name]\n"
        "y = loud_name\n")
    problems = check_imports.check_file(str(path))
    assert len(problems) == 1 and "loud_name" in problems[0]


# ------------------------------------------------------------ unbounded-cache

def test_unbounded_cache_flags_growing_module_dict(tmp_path):
    findings = _scan(tmp_path, """
        _CACHE = {}
        _LOG = []

        def get(key):
            v = _CACHE.get(key)
            if v is None:
                v = _CACHE[key] = expensive(key)
            _LOG.append(key)
            return v
        """, select=["unbounded-cache"])
    msgs = "\n".join(_messages(findings))
    assert "`_CACHE`" in msgs and "never" in msgs
    assert "`_LOG`" in msgs
    assert len(findings) == 2


def test_unbounded_cache_accepts_bounds_and_eviction(tmp_path):
    findings = _scan(tmp_path, """
        _SIZE_GUARDED = {}
        _EVICTED = {}
        _CLEARED = []
        _REBOUND = {}

        def put(key, v):
            if len(_SIZE_GUARDED) > 256:
                _SIZE_GUARDED.clear()
            _SIZE_GUARDED[key] = v
            _EVICTED[key] = v
            _EVICTED.pop(next(iter(_EVICTED)))
            _CLEARED.append(v)

        def reset():
            global _REBOUND
            _CLEARED.clear()
            _REBOUND = {}

        def grow_rebound(key, v):
            _REBOUND[key] = v
        """, select=["unbounded-cache"])
    assert findings == [], _messages(findings)


def test_unbounded_cache_ignores_import_time_fills_and_locals(tmp_path):
    findings = _scan(tmp_path, """
        TABLES = {}
        TABLES["nation"] = 25      # module-body fill: a constant, not a cache
        for _name in ("region", "part"):
            TABLES[_name] = 5

        def lookup(key):
            local = {}
            local[key] = 1          # function-local: dies with the frame
            return TABLES.get(key), local
        """, select=["unbounded-cache"])
    assert findings == [], _messages(findings)


def test_unbounded_cache_suppression_honored(tmp_path):
    findings = _scan(tmp_path, """
        _REGISTRY = {}

        def register(cls):
            _REGISTRY[cls.__name__] = cls  # prestocheck: ignore[unbounded-cache] - one per class
            return cls
        """, select=["unbounded-cache"])
    assert findings == [], _messages(findings)


# -------------------------------------------------------- shared-state-race

def test_shared_state_race_thread_vs_main_unguarded(tmp_path):
    findings = _scan(tmp_path, """
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0        # __init__ write: construction, exempt

            def start(self):
                t = threading.Thread(target=self._loop, daemon=True)
                t.start()
                self._t = t

            def _loop(self):
                self.total += 1       # thread side, no lock

            def bump(self):
                self.total += 1       # main side, no lock -> race
        """, select=["shared-state-race"])
    msgs = _messages(findings)
    assert len(msgs) == 1, msgs
    assert "Pump.total" in msgs[0] and "no common lock" in msgs[0]


def test_shared_state_race_common_lock_is_clean(tmp_path):
    findings = _scan(tmp_path, """
        import threading

        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
                self.total = 0

            def start(self):
                threading.Thread(target=self._loop, daemon=True).start()  # prestocheck: ignore[thread-lifecycle]

            def _loop(self):
                with self._lock:
                    self.total += 1

            def bump(self):
                with self._lock:
                    self.total += 1
        """, select=["shared-state-race"])
    assert findings == [], _messages(findings)


def test_shared_state_race_guarded_by_inference(tmp_path):
    """All writes are thread-side (no main/thread pair exists), but two of
    three hold the same lock: the third is flagged against the inferred
    guard — the author knew the state was shared."""
    findings = _scan(tmp_path, """
        import threading

        class Book:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0
                self.ts = []

            def start(self):
                a = threading.Thread(target=self._loop, daemon=True)
                b = threading.Thread(target=self._drain, daemon=True)
                c = threading.Thread(target=self._tick, daemon=True)
                for t in (a, b, c):
                    t.start()
                    self.ts.append(t)

            def _loop(self):
                with self._lock:
                    self.n += 1

            def _drain(self):
                with self._lock:
                    self.n = 0

            def _tick(self):
                self.n += 1       # outside the guard the others respect
        """, select=["shared-state-race"])
    msgs = _messages(findings)
    assert len(msgs) == 1, msgs
    assert "inferred guard" in msgs[0] and "Book._lock" in msgs[0]
    assert "held at 2 of 3" in msgs[0]


def test_shared_state_race_cross_module_thread_target(tmp_path):
    """Thread target resolved across modules: wa spawns wb.work; wb's
    global is written by the thread AND by a main-side setter, unguarded."""
    (tmp_path / "wa.py").write_text(textwrap.dedent("""
        import threading
        from wb import work

        def boot():
            t = threading.Thread(target=work, daemon=True)
            t.start()
            return t
        """))
    (tmp_path / "wb.py").write_text(textwrap.dedent("""
        TOTAL = 0

        def work():
            global TOTAL
            TOTAL = TOTAL + 1

        def set_total(v):
            global TOTAL
            TOTAL = v
        """))
    result = run([str(tmp_path)], select=["shared-state-race"],
                 baseline_path=None)
    msgs = _messages(result.new_findings)
    assert len(msgs) == 1, msgs
    assert "TOTAL" in msgs[0] and "no common lock" in msgs[0]
    assert result.new_findings[0].file.endswith("wb.py")


def test_shared_state_race_module_list_mutation_without_global(tmp_path):
    """Mutation-method calls on a module-level container need no `global`
    declaration — ITEMS.append from thread and main is still the race."""
    findings = _scan(tmp_path, """
        import threading

        ITEMS = []

        def work():
            ITEMS.append(1)         # thread side

        def flush(v):
            ITEMS.append(v)         # main side, no lock -> race

        def boot():
            t = threading.Thread(target=work, daemon=True)
            t.start()
            return t
        """, select=["shared-state-race"])
    msgs = _messages(findings)
    assert len(msgs) == 1, msgs
    assert "ITEMS" in msgs[0] and "no common lock" in msgs[0]


def test_shared_state_race_aliased_import_target_resolved(tmp_path):
    """`from wc import work as pump` must resolve to wc.work — the alias is
    local, the function's identity is not."""
    (tmp_path / "wal.py").write_text(textwrap.dedent("""
        import threading
        from wc import work as pump

        def boot():
            t = threading.Thread(target=pump, daemon=True)
            t.start()
            return t
        """))
    (tmp_path / "wc.py").write_text(textwrap.dedent("""
        TOTAL = 0

        def work():
            global TOTAL
            TOTAL = TOTAL + 1

        def set_total(v):
            global TOTAL
            TOTAL = v
        """))
    result = run([str(tmp_path)], select=["shared-state-race"],
                 baseline_path=None)
    msgs = _messages(result.new_findings)
    assert len(msgs) == 1 and "TOTAL" in msgs[0], msgs


def test_shared_state_race_annotation_only_is_not_a_write(tmp_path):
    findings = _scan(tmp_path, """
        import threading
        from typing import Optional

        class Box:
            def start(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()

            def _loop(self):
                self.buf: Optional[list] = None  # real write: counted
                self.tag: str                    # annotation only: not one

            def untag(self):
                self.tag: str                    # would pair with _loop's
        """, select=["shared-state-race"])
    assert findings == [], _messages(findings)


def test_shared_state_race_suppression(tmp_path):
    findings = _scan(tmp_path, """
        import threading

        class Flag:
            def start(self):
                self._t = threading.Thread(target=self._loop, daemon=True)
                self._t.start()

            def _loop(self):
                self.done = True  # prestocheck: ignore[shared-state-race] - monotonic one-way flag

            def reset(self):
                self.done = False  # prestocheck: ignore[shared-state-race] - test-only reset
        """, select=["shared-state-race"])
    assert findings == [], _messages(findings)


# --------------------------------------------------------- thread-lifecycle

def test_thread_lifecycle_fire_and_forget(tmp_path):
    findings = _scan(tmp_path, """
        import threading

        def handle(req):
            threading.Thread(target=req.run, daemon=True).start()
        """, select=["thread-lifecycle"])
    msgs = _messages(findings)
    assert len(msgs) == 1, msgs
    assert "without retaining a reference" in msgs[0]


def test_thread_lifecycle_non_daemon_never_joined(tmp_path):
    findings = _scan(tmp_path, """
        import threading

        class Server:
            def start(self):
                self._t = threading.Thread(target=self._loop)
                self._t.start()

            def _loop(self):
                pass
        """, select=["thread-lifecycle"])
    msgs = _messages(findings)
    assert len(msgs) == 1, msgs
    assert "never joined" in msgs[0]


def test_thread_lifecycle_joined_in_close_is_clean(tmp_path):
    findings = _scan(tmp_path, """
        import threading

        class Server:
            def start(self):
                self._t = threading.Thread(target=self._loop)
                self._t.start()

            def close(self):
                self._t.join(timeout=5.0)

            def _loop(self):
                pass
        """, select=["thread-lifecycle"])
    assert findings == [], _messages(findings)


def test_thread_lifecycle_join_on_one_thread_does_not_clear_another(tmp_path):
    """A .join() on an unrelated thread must not suppress the finding for
    a second non-daemon thread that is never joined."""
    findings = _scan(tmp_path, """
        import threading

        class Server:
            def start(self):
                self._serve = threading.Thread(target=self._loop)
                self._serve.start()
                self._pump = threading.Thread(target=self._loop)
                self._pump.start()

            def stop(self):
                self._serve.join(timeout=5.0)   # _pump is never joined

            def _loop(self):
                pass
        """, select=["thread-lifecycle"])
    msgs = _messages(findings)
    assert len(msgs) == 1, msgs
    assert findings[0].line == 8  # the _pump creation


def test_thread_lifecycle_daemon_file_writer(tmp_path):
    findings = _scan(tmp_path, """
        import threading

        def writer():
            with open("out.json", "w") as f:
                f.write("{}")

        def boot():
            t = threading.Thread(target=writer, daemon=True)
            t.start()
            return t
        """, select=["thread-lifecycle"])
    msgs = _messages(findings)
    assert len(msgs) == 1, msgs
    assert "mutates files" in msgs[0] and "`writer`" in msgs[0]


def test_thread_lifecycle_daemon_reader_is_clean_and_suppression(tmp_path):
    findings = _scan(tmp_path, """
        import threading

        def reader():
            with open("in.json") as f:
                return f.read()

        def boot(req):
            t = threading.Thread(target=reader, daemon=True)
            t.start()
            threading.Thread(target=req.run, daemon=True).start()  # prestocheck: ignore[thread-lifecycle] - request-scoped, bounded by the pool
            return t
        """, select=["thread-lifecycle"])
    assert findings == [], _messages(findings)


# -------------------------------------------------------- wallclock-duration

def test_wallclock_duration_flags_time_time_deltas(tmp_path):
    findings = _scan(tmp_path, """
        import time

        def measure(work):
            t0 = time.time()
            work()
            return time.time() - t0          # the classic duration idiom

        def elapsed(info):
            end = info.end or time.time()
            return (info.end or time.time()) - info.create

        def accumulate(stats, t0):
            stats.stall -= 1
            stats.stall += time.time() - t0
        """, select=["wallclock-duration"])
    assert len(findings) == 3, _messages(findings)
    assert {f.line for f in findings} == {7, 11, 15}


def test_wallclock_duration_clean_uses_not_flagged(tmp_path):
    findings = _scan(tmp_path, """
        import time

        def good(work):
            t0 = time.perf_counter()
            work()
            return time.perf_counter() - t0   # monotonic interval: fine

        def uptime(start_mono):
            return time.monotonic() - start_mono

        def timestamp():
            created = time.time()             # plain timestamp: fine
            deadline = time.time() + 30.0     # deadline addition: fine
            return created, deadline
        """, select=["wallclock-duration"])
    assert findings == [], _messages(findings)


def test_wallclock_duration_suppression(tmp_path):
    findings = _scan(tmp_path, """
        import time

        def purge_cutoff(grace_s):
            # epoch cutoff vs persisted wall timestamps: wall on purpose
            return time.time() - grace_s  # prestocheck: ignore[wallclock-duration]
        """, select=["wallclock-duration"])
    assert findings == [], _messages(findings)


# ------------------------------------------------------------- print-hygiene

def test_print_hygiene_flags_bare_print(tmp_path):
    findings = _scan(tmp_path, """
        def report(state):
            print("engine state:", state)
        """, select=["print-hygiene"])
    assert len(findings) == 1
    assert "events.emit" in findings[0].message


def test_print_hygiene_allows_stderr_and_suppression(tmp_path):
    findings = _scan(tmp_path, """
        import sys

        def diag(e):
            print(f"probe failed: {e!r}", file=sys.stderr)

        def banner(port):
            print(f"listening on :{port}")  # prestocheck: ignore[print-hygiene] - CLI banner

        def journaled(qid):
            from presto_tpu.utils import events
            events.emit("query.finished", query_id=qid)
        """, select=["print-hygiene"])
    assert findings == [], _messages(findings)


def test_print_hygiene_exempts_cli_tools_and_main(tmp_path):
    src = """
        def main():
            print("interactive output")
        """
    for rel in ("cli/repl.py", "tools/sweep.py", "tests/test_x.py",
                "__main__.py"):
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
        findings = run([str(path)], select=["print-hygiene"],
                       baseline_path=None).new_findings
        assert findings == [], (rel, _messages(findings))
    # the same module OUTSIDE an exempt segment is flagged
    flagged = tmp_path / "engine.py"
    flagged.write_text(textwrap.dedent(src))
    findings = run([str(flagged)], select=["print-hygiene"],
                   baseline_path=None).new_findings
    assert len(findings) == 1


# -------------------------------------------------------- tempfile-hygiene

def test_tempfile_hygiene_flags_unowned_creation(tmp_path):
    findings = _scan(tmp_path, """
        import os
        import tempfile

        def leak_file():
            fd, path = tempfile.mkstemp()
            return path

        def leak_dir():
            return tempfile.mkdtemp()

        def leak_named():
            return tempfile.NamedTemporaryFile(delete=False)

        def leak_open():
            fh = open(os.path.join(tempfile.gettempdir(), "x.tmp"), "wb")
            fh.write(b"x")
        """, select=["tempfile-hygiene"])
    assert len(findings) == 4
    assert all(f.pass_id == "tempfile-hygiene" for f in findings)


def test_tempfile_hygiene_accepts_cleanup_owners(tmp_path):
    # finally-cleanup (acquire-before-try included), owner class with
    # close(), with-managed NamedTemporaryFile: all sanctioned shapes
    findings = _scan(tmp_path, """
        import os
        import tempfile

        def finally_guarded():
            fd, path = tempfile.mkstemp()
            try:
                os.write(fd, b"x")
            finally:
                os.close(fd)
                os.remove(path)

        class Owner:
            def make(self):
                self.path = tempfile.mkdtemp()

            def close(self):
                import shutil
                shutil.rmtree(self.path)

        def managed():
            with tempfile.NamedTemporaryFile() as f:
                f.write(b"x")
        """, select=["tempfile-hygiene"])
    assert findings == []


def test_tempfile_hygiene_suppression(tmp_path):
    findings = _scan(tmp_path, """
        import tempfile

        def forensic_dump():
            fd, path = tempfile.mkstemp()  # prestocheck: ignore[tempfile-hygiene] - user-facing artifact
            return path
        """, select=["tempfile-hygiene"])
    assert findings == []


# ------------------------------------------------------- resource-discipline

def test_resource_discipline_flags_happy_path_only_release(tmp_path):
    findings = _scan(tmp_path, """
        class Conn:
            def close(self):
                pass

        def happy_path_only():
            c = Conn()
            c.execute("select 1")   # can raise: the close below never runs
            c.close()
        """, select=["resource-discipline"])
    msgs = "\n".join(_messages(findings))
    assert len(findings) == 1
    assert "`c` (Conn) is released only on the happy path" in msgs


def test_resource_discipline_flags_unreleased_and_discarded(tmp_path):
    findings = _scan(tmp_path, """
        class Conn:
            def close(self):
                pass

        def never_released():
            c = Conn()
            c.execute("select 1")

        def discarded():
            Conn()
        """, select=["resource-discipline"])
    msgs = "\n".join(_messages(findings))
    assert "`c` (Conn) is acquired but never released on any path" in msgs
    assert "result of Conn acquire is discarded" in msgs
    assert len(findings) == 2


def test_resource_discipline_learns_producers_through_singletons(tmp_path):
    # Pool.client() returns a fresh Conn, so a POOL.client() call is an
    # acquire even though no constructor appears at the call site.
    findings = _scan(tmp_path, """
        class Conn:
            def close(self):
                pass

        class Pool:
            def client(self):
                return Conn()

        POOL = Pool()

        def leaky_client():
            h = POOL.client()
            h.execute("select 1")
        """, select=["resource-discipline"])
    msgs = "\n".join(_messages(findings))
    assert "`h` (Conn) is acquired but never released" in msgs


def test_resource_discipline_clean_shapes(tmp_path):
    # finally-release, with-managed, ownership transfer by return, and a
    # one-level helper that releases its parameter: all sanctioned.
    findings = _scan(tmp_path, """
        class Conn:
            def close(self):
                pass

        def _shutdown(conn):
            conn.close()

        def finally_guarded():
            c = Conn()
            try:
                c.execute("select 1")
            finally:
                c.close()

        def with_managed():
            c = Conn()
            with c:
                c.execute("select 1")

        def transferred():
            c = Conn()
            c.prepare()
            return c            # ownership moves to the caller

        def helper_released():
            c = Conn()
            try:
                c.execute("select 1")
            finally:
                _shutdown(c)
        """, select=["resource-discipline"])
    assert findings == []


def test_resource_discipline_ledger_pair_needs_finally(tmp_path):
    findings = _scan(tmp_path, """
        def ledger_unprotected(pool, qid):
            pool.reserve(qid, 4096)
            run_query(qid)
            pool.clear_query(qid)

        def ledger_guarded(pool, qid):
            pool.reserve(qid, 4096)
            try:
                run_query(qid)
            finally:
                pool.clear_query(qid)
        """, select=["resource-discipline"])
    msgs = "\n".join(_messages(findings))
    assert len(findings) == 1
    assert "`pool.clear_query()` paired with `pool.reserve()`" in msgs
    assert findings[0].line == 5    # anchored at the unprotected release


def test_resource_discipline_suppression(tmp_path):
    findings = _scan(tmp_path, """
        class Conn:
            def close(self):
                pass

        def deliberate():
            c = Conn()  # prestocheck: ignore[resource-discipline] - process-lifetime handle
            c.execute("select 1")
        """, select=["resource-discipline"])
    assert findings == []


# -------------------------------------------------------- close-propagation

def test_close_propagation_flags_owner_without_teardown(tmp_path):
    findings = _scan(tmp_path, """
        class Conn:
            def close(self):
                pass

        class NoTeardown:
            def __init__(self):
                self._conn = Conn()
        """, select=["close-propagation"])
    msgs = "\n".join(_messages(findings))
    assert len(findings) == 1
    assert "class `NoTeardown` acquires closeable `self._conn` (Conn)" in msgs
    assert "defines no close()/teardown method" in msgs


def test_close_propagation_flags_attr_missed_by_teardown(tmp_path):
    findings = _scan(tmp_path, """
        class Conn:
            def close(self):
                pass

        class Forgetful:
            def __init__(self):
                self._conn = Conn()
                self._log = Conn()

            def close(self):
                try:
                    self._conn.close()
                except Exception:
                    pass
        """, select=["close-propagation"])
    msgs = "\n".join(_messages(findings))
    assert len(findings) == 1
    assert "`self._log` (Conn) acquired by `Forgetful` is never closed" in msgs


def test_close_propagation_flags_sibling_and_loop_skips(tmp_path):
    findings = _scan(tmp_path, """
        class Conn:
            def close(self):
                pass

        class TwoHandles:
            def __init__(self):
                self._a = Conn()
                self._b = Conn()

            def close(self):
                self._a.close()
                self._b.close()

        class Many:
            def __init__(self):
                self._conns = []

            def close(self):
                for c in self._conns:
                    c.close()
        """, select=["close-propagation"])
    msgs = "\n".join(_messages(findings))
    assert "close of `_b` in close() is skipped when the earlier close " \
           "of `_a` raises" in msgs
    assert "close of `c` inside a loop in close()" in msgs
    assert len(findings) == 2


def test_close_propagation_clean_owners(tmp_path):
    # protected sibling closes, delegation to a helper call, a borrowed
    # (parameter-bound) attribute, and a one-level self-helper: all clean.
    findings = _scan(tmp_path, """
        class Conn:
            def close(self):
                pass

        class Careful:
            def __init__(self, outer):
                self._borrowed = outer      # borrowed: caller releases
                self._a = Conn()
                self._b = Conn()

            def close(self):
                try:
                    self._a.close()
                except Exception:
                    pass
                self._b.close()

        class Delegating:
            def __init__(self):
                self._tmp = Conn()

            def close(self):
                dispose(self._tmp)

        class Indirect:
            def __init__(self):
                self._conn = Conn()

            def _teardown_conn(self):
                self._conn.close()

            def close(self):
                self._teardown_conn()
        """, select=["close-propagation"])
    assert findings == []


def test_close_propagation_suppression(tmp_path):
    findings = _scan(tmp_path, """
        class Conn:
            def close(self):
                pass

        class Pinned:
            def __init__(self):
                self._conn = Conn()  # prestocheck: ignore[close-propagation] - released by registry atexit
        """, select=["close-propagation"])
    assert findings == []


# ------------------------------------------------------------- tier-1 gate

def test_whole_tree_has_no_new_findings():
    """Tier-1 wiring (successor of test_check_imports.test_whole_tree_is_clean
    for the full suite): all six passes over presto_tpu/ + tools/ must report
    nothing beyond the committed baseline."""
    result = run([os.path.join(REPO, "presto_tpu"),
                  os.path.join(REPO, "tools")])
    assert result.n_files > 100, f"scan looks wrong: {result.n_files} files"
    rendered = "\n".join(f.render() for f in result.new_findings)
    assert result.new_findings == [], (
        "new prestocheck findings (fix, suppress with a justified "
        "`# prestocheck: ignore[pass-id]`, or re-baseline):\n" + rendered)


# ------------------------------------------------------------------- CLI

def test_cli_list_passes_json_and_exit_codes(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO)

    out = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck", "--list-passes"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert out.returncode == 0
    assert EXPECTED_PASSES <= set(out.stdout.split())

    bad = tmp_path / "bad.py"
    bad.write_text("def f(xs=[]):\n    return unknown_name\n")
    fail = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck", "--json", str(bad)],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert fail.returncode == 1
    doc = json.loads(fail.stdout)
    assert {f["pass"] for f in doc["new"]} == {"mutable-default-args",
                                              "undefined-name"}

    only_defaults = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck",
         "--select", "mutable-default-args", str(bad)],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert only_defaults.returncode == 1
    assert "undefined name" not in only_defaults.stdout

    clean = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck",
         os.path.join(REPO, "presto_tpu", "cluster")],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert clean.returncode == 0, clean.stdout + clean.stderr

    unknown = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck", "--select", "nope"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert unknown.returncode == 2
    # fail fast AND name the valid ids — "see --list-passes" alone was a
    # second round trip for every typo
    assert "valid pass ids:" in unknown.stderr
    assert "cache-key-hygiene" in unknown.stderr
    assert "retrace-risk" in unknown.stderr

    # a nonexistent path must be a hard error, not a silent 0-file pass
    nopath = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck", "no/such/dir"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert nopath.returncode == 2
    assert "no such path" in nopath.stderr

    # default paths anchor to the repo root, not the cwd
    from_elsewhere = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck"],
        capture_output=True, text=True, cwd=str(tmp_path), env=env)
    assert from_elsewhere.returncode == 0, from_elsewhere.stderr
    assert " 0 files" not in from_elsewhere.stderr  # "160 files" is some


def test_module_cache_shared_across_select_invocations(tmp_path):
    """load_modules parses once per (path, mtime, size): a second run —
    e.g. another --select over the same tree — reuses the Module object;
    an edit invalidates it."""
    from tools.prestocheck.core import load_modules

    mod = tmp_path / "cached.py"
    mod.write_text("X = 1\n")
    first = load_modules([str(mod)])
    second = load_modules([str(mod)])
    assert first[0] is second[0]

    os.utime(str(mod), ns=(1, 1))  # force a different mtime signature
    third = load_modules([str(mod)])
    assert third[0] is not first[0]


def test_run_reports_per_pass_wall_times(tmp_path):
    mod = tmp_path / "m.py"
    mod.write_text("def f(xs=[]):\n    return xs\n")
    result = run([str(mod)], select=["mutable-default-args"],
                 baseline_path=None)
    assert "parse" in result.pass_wall_s
    assert "mutable-default-args" in result.pass_wall_s
    assert all(v >= 0 for v in result.pass_wall_s.values())


def test_git_changed_files_lists_dirty_and_untracked(tmp_path):
    from tools.prestocheck.core import git_changed_files

    repo = tmp_path / "r"
    repo.mkdir()
    sp = lambda *args: subprocess.run(  # noqa: E731
        ["git", "-C", str(repo)] + list(args), check=True,
        capture_output=True)
    sp("init", "-q")
    sp("config", "user.email", "t@example.com")
    sp("config", "user.name", "t")
    (repo / "clean.py").write_text("A = 1\n")
    (repo / "stale.py").write_text("B = 1\n")
    sp("add", ".")
    sp("commit", "-qm", "init")
    (repo / "clean.py").write_text("A = 2\n")      # modified vs HEAD
    (repo / "fresh.py").write_text("C = 1\n")      # untracked
    names = {os.path.basename(p)
             for p in git_changed_files(str(repo))}
    assert names == {"clean.py", "fresh.py"}


def test_cli_changed_only_scopes_to_git_diff(tmp_path):
    """--changed-only with the real repo: the scan set is the dirty files
    (a strict subset of the tree), and a path covering none of them scans
    nothing and still exits 0."""
    env = dict(os.environ, PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck", "--changed-only",
         "--json", "--select", "mutable-default-args",
         os.path.join(REPO, "presto_tpu"), os.path.join(REPO, "tools")],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert out.returncode in (0, 1), out.stderr
    doc = json.loads(out.stdout)
    assert "pass_wall_s" in doc

    # scoping: a path that excludes every changed file scans nothing
    empty_dir = tmp_path / "empty"
    empty_dir.mkdir()
    none = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck", "--changed-only",
         str(empty_dir)],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert none.returncode == 0
    assert "no changed .py files" in none.stderr


def test_cli_update_baseline_roundtrip(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO)
    bad = tmp_path / "bad.py"
    bad.write_text("def f(xs=[]):\n    return xs\n")
    baseline = tmp_path / "base.json"

    upd = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck",
         "--update-baseline", "--baseline", str(baseline), str(bad)],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert upd.returncode == 0 and baseline.exists()

    rerun = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck",
         "--baseline", str(baseline), str(bad)],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert rerun.returncode == 0, rerun.stdout + rerun.stderr
    assert "1 baselined" in rerun.stderr


def test_cli_partial_update_baseline_keeps_other_passes(tmp_path):
    """--update-baseline --select must not discard grandfathered findings
    of the passes that did not run."""
    env = dict(os.environ, PYTHONPATH=REPO)
    bad = tmp_path / "bad.py"
    bad.write_text("def f(xs=[]):\n    return unknown_name\n")
    baseline = tmp_path / "base.json"

    subprocess.run(
        [sys.executable, "-m", "tools.prestocheck",
         "--update-baseline", "--baseline", str(baseline), str(bad)],
        capture_output=True, text=True, cwd=REPO, env=env, check=True)
    before = load_baseline(str(baseline))
    assert len(before) == 2  # one per pass

    subprocess.run(
        [sys.executable, "-m", "tools.prestocheck",
         "--update-baseline", "--select", "undefined-name",
         "--baseline", str(baseline), str(bad)],
        capture_output=True, text=True, cwd=REPO, env=env, check=True)
    assert load_baseline(str(baseline)) == before

    full = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck",
         "--baseline", str(baseline), str(bad)],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert full.returncode == 0, full.stdout + full.stderr


def test_cli_sarif_round_trips_with_json(tmp_path):
    """--format sarif carries exactly the findings --format json reports,
    with 1-based columns, rule metadata for every pass, and baselineState."""
    env = dict(os.environ, PYTHONPATH=REPO)
    bad = tmp_path / "bad.py"
    bad.write_text("def f(xs=[]):\n    return unknown_name\n")

    jout = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck",
         "--format", "json", str(bad)],
        capture_output=True, text=True, cwd=REPO, env=env)
    sout = subprocess.run(
        [sys.executable, "-m", "tools.prestocheck",
         "--format", "sarif", str(bad)],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert jout.returncode == 1 and sout.returncode == 1

    jdoc = json.loads(jout.stdout)
    sdoc = json.loads(sout.stdout)
    assert sdoc["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in sdoc["$schema"]
    (run_,) = sdoc["runs"]
    rules = {r["id"] for r in run_["tool"]["driver"]["rules"]}
    assert EXPECTED_PASSES <= rules
    assert "SRCROOT" in run_["originalUriBaseIds"]

    jkeys = {(f["pass"], f["file"], f["line"], f["col"], f["message"])
             for f in jdoc["new"]}
    skeys = set()
    for r in run_["results"]:
        assert r["level"] == "warning"
        assert r["baselineState"] == "new"
        (loc,) = r["locations"]
        phys = loc["physicalLocation"]
        skeys.add((r["ruleId"], phys["artifactLocation"]["uri"],
                   phys["region"]["startLine"],
                   phys["region"]["startColumn"],
                   r["message"]["text"]))
    assert skeys == jkeys and len(skeys) == 2


# --------------------------------------------------------------- retrace-risk

def test_retrace_risk_flags_data_derived_static_args(tmp_path):
    msgs = _messages(_scan(tmp_path, """
        import jax
        import functools

        def kernel(x, n):
            return x

        step = jax.jit(kernel, static_argnames=("n",))

        @functools.partial(jax.jit, static_argnums=(1,))
        def kern2(x, trips):
            return x

        def run(page):
            return step(page.data, n=len(page.rows))

        def probe(arr):
            return kern2(arr, trips=int(arr.max()))
        """, select=["retrace-risk"]))
    assert len(msgs) == 2, msgs
    assert any("`n`" in m and "len(...)" in m for m in msgs)
    assert any("`trips`" in m and "int(...)" in m for m in msgs)


def test_retrace_risk_canonicalized_and_bounded_are_clean(tmp_path):
    assert _scan(tmp_path, """
        import jax

        def kernel(x, n):
            return x

        step = jax.jit(kernel, static_argnames=("n",))

        def run(page, _pow2):
            return step(page.data, n=_pow2(len(page.rows)))

        def run2(page):
            return step(page.data, n=clamp_capacity(page.rows.shape[0], 64))

        def run3(page):
            return step(page.data, n=8)
        """, select=["retrace-risk"]) == []


def test_retrace_risk_sees_kernel_cache_bindings(tmp_path):
    msgs = _messages(_scan(tmp_path, """
        import jax
        from utils import kernel_cache as kc

        def body(x, slots):
            return x

        class Op:
            def install(self):
                self._k = kc.get_or_install(
                    ("op", 1),
                    lambda: jax.jit(body, static_argnames=("slots",)))

            def run(self, x):
                return self._k(x, slots=x.shape[0])
        """, select=["retrace-risk"]))
    assert len(msgs) == 1 and ".shape" in msgs[0], msgs


def test_retrace_risk_unbounded_domain_and_suppression(tmp_path):
    src = """
        import jax

        def kernel(x, tag):
            return x

        step = jax.jit(kernel, static_argnames=("tag",))

        def run(x, name):
            return step(x, tag=f"v-{name}")

        def run2(x, a, b):
            return step(x, tag=a / b)  # prestocheck: ignore[retrace-risk]
        """
    msgs = _messages(_scan(tmp_path, src, select=["retrace-risk"]))
    assert len(msgs) == 1 and "f-string" in msgs[0], msgs


# ----------------------------------------------------------- cache-key-hygiene

def test_cache_key_hygiene_flags_jit_built_outside_funnel(tmp_path):
    msgs = _messages(_scan(tmp_path, """
        import jax
        from jax.experimental import pallas as pl

        def hot(fn, x):
            step = jax.jit(fn)
            return step(x)

        def hot_pallas(body, shape, x):
            return pl.pallas_call(body, out_shape=shape)(x)
        """, select=["cache-key-hygiene"]))
    assert len(msgs) == 2, msgs
    assert any("jax.jit callable built inside `hot`" in m for m in msgs)
    assert any("pl.pallas_call callable built inside `hot_pallas`" in m
               for m in msgs)


def test_cache_key_hygiene_funnel_lru_and_module_scope_are_clean(tmp_path):
    assert _scan(tmp_path, """
        import functools
        import jax
        from utils.kernel_cache import get_or_build, get_or_install

        def body(x):
            return x

        step = jax.jit(body)                      # module scope: once ever

        def cached(fn, x):
            k, _ = get_or_build(("k", 1), lambda: jax.jit(fn))
            return k(x)

        def _build_program(fn):
            return jax.jit(fn)                    # builder passed to funnel

        def install(fn):
            return get_or_install(("p", 2), lambda: _build_program(fn))

        @functools.lru_cache(maxsize=8)
        def make_step(n):
            return jax.jit(lambda x: x + n)       # memoized factory
        """, select=["cache-key-hygiene"]) == []


def test_cache_key_hygiene_audits_key_components(tmp_path):
    msgs = _messages(_scan(tmp_path, """
        import time
        from utils.kernel_cache import get_or_build

        def install(page, make):
            key = ("k", f"v{page.n}", float(page.x), [1, 2],
                   id(page), time.time(), len(page.rows))
            return get_or_build(key, make)
        """, select=["cache-key-hygiene"]))
    assert len(msgs) == 6, msgs
    for needle in ("f-string", "float()", "unhashable", "id(...)",
                   "`time.time()`", "raw len(...)"):
        assert any(needle in m for m in msgs), (needle, msgs)


def test_cache_key_hygiene_canonicalized_key_and_helper_returns(tmp_path):
    msgs = _messages(_scan(tmp_path, """
        from utils.kernel_cache import get_or_build

        def _mk_key(page):
            return ("k", f"layout-{page.n}")

        def install_bad(page, make):
            return get_or_build(_mk_key(page), make)

        def install_ok(page, make, _pow2):
            key = ("k", _pow2(len(page.rows)), page.data.shape)
            return get_or_build(key, make)
        """, select=["cache-key-hygiene"]))
    # the helper's f-string return is found; the pow2-canonicalized key
    # vouches for its len/.shape components
    assert len(msgs) == 1 and "f-string" in msgs[0], msgs


def test_cache_key_hygiene_suppression(tmp_path):
    assert _scan(tmp_path, """
        import jax

        def fallback(fn, x):
            step = jax.jit(fn)  # prestocheck: ignore[cache-key-hygiene]
            return step(x)
        """, select=["cache-key-hygiene"]) == []


# ------------------------------------------- --changed-only / --format compose

def test_changed_only_composes_with_sarif(tmp_path, monkeypatch, capsys):
    """Regression: --changed-only must compose with --format sarif — both
    when changed files have findings and when the changed set is empty
    (an empty run is still a well-formed SARIF document)."""
    import tools.prestocheck.__main__ as cli

    bad = tmp_path / "bad.py"
    bad.write_text("def f(xs=[]):\n    return unknown_name\n")

    monkeypatch.setattr(cli, "git_changed_files", lambda: [str(bad)])
    rc = cli.main(["--changed-only", "--format", "sarif", str(tmp_path)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    (run_,) = doc["runs"]
    assert {r["ruleId"] for r in run_["results"]} == \
        {"mutable-default-args", "undefined-name"}
    assert all(r["baselineState"] == "new" for r in run_["results"])

    monkeypatch.setattr(cli, "git_changed_files", lambda: [])
    rc = cli.main(["--changed-only", "--format", "sarif", str(tmp_path)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    (run_,) = doc["runs"]
    assert run_["results"] == []
    rules = {r["id"] for r in run_["tool"]["driver"]["rules"]}
    assert EXPECTED_PASSES <= rules
