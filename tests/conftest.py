"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (the DistributedQueryRunner pattern of the
reference test suite — presto-tests/.../DistributedQueryRunner.java:77 boots N servers
in one JVM; here N XLA host devices stand in for N TPU chips). Must set flags before
jax initializes its backends.
"""
import os

# tests never touch an accelerator: force the CPU backend with eight host devices
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import faulthandler  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# seconds one test may take, the setup and teardown of its fixtures included
LIMIT_S = 150

# the real stderr, kept before capture takes fd 2 over: where the last stage
# writes the stacks of a worker it ends
_STDERR_FD = None
# the highest /proc/self/maps count the compile valve read in this process,
# and under xdist each worker's, as it reported it at its end
_MAPS_PEAK = [0]
_WORKER_MAPS_PEAKS = {}


class TimeLimitExceeded(BaseException):
    """A test ran past LIMIT_S. A BaseException, so that no engine
    `except Exception` in the test's path swallows it."""


def _over_the_limit(signum, frame):
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    raise TimeLimitExceeded(f"the test ran past LIMIT_S = {LIMIT_S} s; "
                            "every thread's stack is in its stderr")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """Bound a test, its fixtures' setup and teardown included: at LIMIT_S
    an interval timer fails it in the main thread with every thread's stack
    dumped, and 60 s later, for a wait no signal breaks (a C call),
    faulthandler ends the process, so that xdist reports that one test and
    starts a new worker."""
    previous = signal.signal(signal.SIGALRM, _over_the_limit)
    signal.setitimer(signal.ITIMER_REAL, LIMIT_S)
    faulthandler.dump_traceback_later(LIMIT_S + 60, exit=True,
                                      file=_STDERR_FD)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, previous)


def pytest_configure(config):
    global _STDERR_FD
    _STDERR_FD = os.dup(2)
    _record_maps_peak()
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy tests excluded from the tier-1 '-m not slow' "
        "budget (full distributed TPC-H ladder, exhaustive exchange shapes)")


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {devs}"
    return devs


def _record_maps_peak():
    """Keep the highest map count the valve of `utils/compile_lock` reads
    (it reads one before every CPU compile), for the summary below."""
    from presto_tpu.utils import compile_lock

    read = compile_lock._map_count

    def counted():
        n = read()
        _MAPS_PEAK[0] = max(_MAPS_PEAK[0], n)
        return n

    compile_lock._map_count = counted


@pytest.fixture(scope="module", autouse=True)
def _unload_between_modules():
    """Drop every jit cache after a module whose worker holds more than half
    of vm.max_map_count. No engine thread runs here, so the valve in
    `utils/compile_lock`, which does the same at 85% under the compile lock
    while other threads trace and compile, is never reached by a test
    worker, which lives for the whole suite."""
    yield
    from presto_tpu.utils import compile_lock, kernel_cache

    if compile_lock._map_count() > compile_lock._map_limit() // 2:
        import jax

        from presto_tpu.ops import scan

        kernel_cache.clear()
        scan.RESIDENT_CACHE.clear()
        jax.clear_caches()


@pytest.hookimpl(optionalhook=True)
def pytest_testnodedown(node, error):
    peak = getattr(node, "workeroutput", {}).get("maps_peak")
    if peak is not None:
        _WORKER_MAPS_PEAKS[node.gateway.id] = peak


def pytest_terminal_summary(terminalreporter, config):
    from presto_tpu.utils import compile_lock

    peaks = _WORKER_MAPS_PEAKS or {"main": _MAPS_PEAK[0]}
    limit = compile_lock._map_limit()
    terminalreporter.write_line(
        "highest map count at a compile, of vm.max_map_count "
        f"{limit} (the valve unloads at {int(limit * 0.85)}): "
        + ", ".join(f"{k} {v}" for k, v in sorted(peaks.items())))


def pytest_sessionfinish(session, exitstatus):
    """Tier-1 under PRESTO_TPU_LOCKSAN=1 is the dynamic concurrency gate:
    the whole suite must produce ZERO runtime order-cycle /
    wait-while-held findings. (test_locksan's own fixtures reset the
    sanitizer around each deliberate-violation case, so anything left here
    came from real engine code.)"""
    if hasattr(session.config, "workeroutput"):
        session.config.workeroutput["maps_peak"] = _MAPS_PEAK[0]
    if os.environ.get("PRESTO_TPU_LOCKSAN") not in ("1", "true", "on"):
        return
    from presto_tpu.utils import locksan

    report = locksan.SANITIZER.report()
    print("\n" + report)
    if locksan.SANITIZER.findings():
        session.exitstatus = 1
