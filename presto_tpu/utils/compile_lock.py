"""XLA CPU compile guard: serialization + memory-mapping safety valve.

Two protections around jax's `backend_compile_and_load`, both CPU-only:

1. A process-wide lock — concurrent LLVM codegen from executor threads is a
   crash risk, and serializing one-time compiles costs nothing.

2. A `vm.max_map_count` valve. Every loaded CPU executable costs ~18 mmap
   regions (measured: jax 0.9.0); a long SQL session compiles thousands of
   kernel/exchange variants, and when the process crosses the kernel's map
   limit (default 65530) LLVM segfaults on the failed mmap — this was root-
   caused from deterministic suite crashes at ~3.6k loaded executables. When
   the map count nears the limit, every jit cache (jax's and the engine's)
   is dropped so executables unload; affected kernels recompile on demand.
   Raising the sysctl (vm.max_map_count) is the better fix where permitted;
   the valve keeps the engine alive where it is not.
"""
from __future__ import annotations

import threading

_LOCK = threading.Lock()
_INSTALLED = False


def _map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def _map_limit() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530


def _maybe_unload(log) -> None:
    limit = _map_limit()
    if _map_count() < limit * 0.85:
        return
    import jax

    from . import kernel_cache

    log(f"presto_tpu: process near vm.max_map_count ({limit}); "
        "dropping jit caches to unload executables")
    kernel_cache.clear()
    try:
        from ..ops import scan
        scan.RESIDENT_CACHE.clear()
    except (ImportError, AttributeError):
        pass  # scan not loaded (CLI tools) — nothing resident to drop
    jax.clear_caches()


def install() -> None:
    global _INSTALLED
    if _INSTALLED:
        return
    _INSTALLED = True
    from jax._src import compiler as _compiler

    # a missing hook raises here: failing open would re-expose the
    # concurrent-LLVM segfault on every runner thread that compiles
    # mid-execution
    attr = "backend_compile_and_load"
    inner = getattr(_compiler, attr)
    if getattr(inner, "_presto_tpu_locked", False):
        return

    import itertools
    import os
    import sys
    counter = itertools.count(1)
    trace = os.environ.get("PRESTO_TPU_TRACE_COMPILES") == "1"

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    def locked(backend, *args, **kwargs):
        platform = getattr(backend, "platform", "")
        if trace:
            n = next(counter)
            try:
                name = str(args[0].operation.attributes["sym_name"])
            except Exception:
                name = "?"
            log(f"[compile {n}] {name}")
        if platform == "cpu":
            with _LOCK:
                _maybe_unload(log)
                return inner(backend, *args, **kwargs)
        return inner(backend, *args, **kwargs)

    locked._presto_tpu_locked = True
    setattr(_compiler, attr, locked)
