"""The per-test time limit of tests/conftest.py, seen from a child pytest.

Each case writes a tiny test file and a conftest.py that loads this
directory's conftest.py as a plugin with its LIMIT_S set small, runs them in
a child `python -m pytest -p no:xdist`, and reads what the child reports.
"""
import pathlib
import subprocess
import sys
import textwrap

TESTS_DIR = pathlib.Path(__file__).resolve().parent

CHILD_CONFTEST = """
import importlib.util
import pathlib
import signal
import sys

sys.path.insert(0, {repo!r})
spec = importlib.util.spec_from_file_location("limited_conftest", {path!r})
limited = importlib.util.module_from_spec(spec)
spec.loader.exec_module(limited)
limited.LIMIT_S = {limit}
sys.modules["limited_conftest"] = limited
pytest_plugins = ["limited_conftest"]


def pytest_sessionfinish(session):
    after = (signal.getitimer(signal.ITIMER_REAL),
             signal.getsignal(signal.SIGALRM) == signal.SIG_DFL)
    pathlib.Path(__file__).with_name("after.txt").write_text(repr(after))
"""


def _run_child(tmp_path, limit, source):
    (tmp_path / "conftest.py").write_text(CHILD_CONFTEST.format(
        repo=str(TESTS_DIR.parent), path=str(TESTS_DIR / "conftest.py"),
        limit=limit))
    (tmp_path / "test_child.py").write_text(textwrap.dedent(source))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "no:xdist", "-p",
         "no:cacheprovider", "-p", "no:randomly", "-q", "-rfE",
         "-W", "ignore::pytest.PytestAssertRewriteWarning",
         "--rootdir", str(tmp_path), "test_child.py"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    return proc, (tmp_path / "after.txt").read_text()


def test_a_test_past_the_limit_fails_by_name_and_the_next_one_runs(tmp_path):
    proc, _ = _run_child(tmp_path, 1, """
        import time


        def test_overruns():
            time.sleep(60)


        def test_after():
            assert True
        """)
    out = proc.stdout
    assert proc.returncode == 1, out + proc.stderr
    assert "FAILED test_child.py::test_overruns" in out, out
    assert "TimeLimitExceeded" in out, out
    # faulthandler's dump of every thread, in the test's captured stderr
    assert "most recent call first" in out, out
    assert "in test_overruns" in out, out
    assert "1 failed, 1 passed" in out, out


def test_a_test_under_the_limit_leaves_no_timer_armed(tmp_path):
    proc, after = _run_child(tmp_path, 30, """
        def test_quick():
            assert True
        """)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "1 passed" in proc.stdout, proc.stdout
    assert after == repr(((0.0, 0.0), True))
