"""presto_tpu — a TPU-native distributed SQL query engine.

A ground-up re-design of the reference engine (frankzye/presto, Presto 0.220) for TPU
hardware: columnar pages as dense JAX arrays, physical operators as jitted XLA
kernels, distributed exchange as ICI-mesh collectives under shard_map, and a Python
control plane (parser/analyzer/planner/scheduler) where the reference uses latency-
tolerant Java coordinator code.

Layer map (mirrors SURVEY.md §1):
  types/block/memory      — data substrate (Page/Block/Type, memory accounting)
  spi/                    — connector plugin boundary
  sql/                    — parser, analyzer, logical planner, optimizer, fragmenter
  ops/                    — physical TPU operators (filter/project, hash agg, join, ...)
  exec/                   — driver loop, task executor, local planner, scheduler
  parallel/               — device mesh, partitioning, collective exchange
  connectors/             — tpch, tpcds, memory, blackhole
  server/                 — client protocol, REST server, CLI
"""
import jax as _jax

# Exact BIGINT/DECIMAL arithmetic needs 64-bit lanes (XLA emulates them on TPU; hot
# kernels deliberately stay in 32-bit — see ops/).
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache, so a second process on the same machine
# replays compiles from disk. Where JAX_COMPILATION_CACHE_DIR is set JAX reads
# it itself and nothing is set here; otherwise the cache lives at one fixed
# path inside the checkout (the path is part of the cache key, so it must not
# move between processes). An unwritable directory is an error, not a reason
# to run uncached.
import os as _os

COMPILE_CACHE_DEFAULT = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache and return the directory in force."""
    from_env = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    _os.makedirs(COMPILE_CACHE_DEFAULT, exist_ok=True)
    if not _os.access(COMPILE_CACHE_DEFAULT, _os.W_OK | _os.X_OK):
        raise PermissionError(
            f"compile cache directory {COMPILE_CACHE_DEFAULT} is not "
            "writable; set JAX_COMPILATION_CACHE_DIR to one that is")
    _jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DEFAULT)
    return COMPILE_CACHE_DEFAULT


configure_compile_cache()
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

# Runtime lock sanitizer: PRESTO_TPU_LOCKSAN=1 swaps threading.Lock/RLock/
# Condition for instrumented wrappers (acquisition-order graph, deadlock +
# wait-while-held findings, locksan.* hold/wait histograms). Installed
# BEFORE any engine module allocates a lock so the whole tree is covered.
from .utils import locksan as _locksan  # noqa: E402

_locksan.install_from_env()

# CPU-backend compiles are serialized process-wide: concurrent LLVM codegen
# from executor threads intermittently segfaults (see utils/compile_lock.py)
from .utils import compile_lock as _compile_lock  # noqa: E402

_compile_lock.install()

# Page-sized host arrays (a scan's chunks and pages) come from the malloc
# heap, not from an mmap each (utils/hostmem.py)
from .utils import hostmem as _hostmem  # noqa: E402

_hostmem.install()

from .types import (BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, REAL, SMALLINT,  # noqa: E402,F401
                    TIMESTAMP, VARCHAR, DecimalType, Type, parse_type)
from .block import Block, Dictionary, Page, page_from_arrays, page_from_pylists  # noqa: E402,F401

# pluggable function libraries (geospatial / teradata / ml) self-register
# into the analyzer + expression-compiler registries on import
from . import functions as _functions  # noqa: E402,F401

# Runtime leak sanitizer: PRESTO_TPU_LEAKSAN=1 instruments pool
# reservations, shared-pool clients, spill managers, trace recorders and
# repo-started threads with allocation-site capture; residue at query
# release / process exit becomes findings. Installed LAST: leaksan
# patches engine classes, so they must be importable first — and unlike
# locksan nothing it tracks can exist before the first query runs.
from .utils import leaksan as _leaksan  # noqa: E402

_leaksan.install_from_env()

# Runtime recompile sanitizer: PRESTO_TPU_COMPILESAN=1 wraps the kernel-cache
# compile funnel (fused segments, exchange programs, every cached jit
# closure) with per-call-site distinct-key tracking; a site compiling past
# its pow2-shape-bucket budget becomes a compile-storm finding. Installed
# with leaksan's timing: nothing compiles before the first query.
from .utils import compilesan as _compilesan  # noqa: E402

_compilesan.install_from_env()

__version__ = "0.1.0"
