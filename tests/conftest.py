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

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
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


def pytest_sessionfinish(session, exitstatus):
    """Tier-1 under PRESTO_TPU_LOCKSAN=1 is the dynamic concurrency gate:
    the whole suite must produce ZERO runtime order-cycle /
    wait-while-held findings. (test_locksan's own fixtures reset the
    sanitizer around each deliberate-violation case, so anything left here
    came from real engine code.)"""
    if os.environ.get("PRESTO_TPU_LOCKSAN") not in ("1", "true", "on"):
        return
    from presto_tpu.utils import locksan

    report = locksan.SANITIZER.report()
    print("\n" + report)
    if locksan.SANITIZER.findings():
        session.exitstatus = 1
