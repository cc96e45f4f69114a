"""The benchmark's own tests run on the CPU. The mesh cell needs more than
one device there: four virtual host devices, asked for before JAX starts its
backends (a run under tests/conftest.py has asked for eight already)."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        (flags + " --xla_force_host_platform_device_count=4").strip()
