"""The v5e compiler on the programs chip_smoke.py dispatches.

No chip is attached in the test sandbox, but the TPU compiler is installed
and compiles for a DESCRIBED topology. The `programs` fixture runs TPC-H
Q6/Q1/Q3 at SF1 on the CPU while the engine is told it is on an accelerator
(so it takes the accelerator's page capacity and driver parallelism), records
the jitted callables it dispatches with their argument shapes, and each test
hands a few of them to the chip's compiler. A compile that passes is not a
chip run: it says the compiler accepts the program and how much device
memory it plans, nothing about results or times.

Everything that touches libtpu lives in module-scoped fixtures of this one
file: one process at a time may load the library, and under xdist every
worker imports every test file.
"""
import pathlib
import threading

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import presto_tpu

HBM_BYTES = 16 * 10 ** 9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: the next run would warn and compile again
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


class _Recorder:
    """Wraps jitted callables so that each top-level call leaves
    (callable, abstract args) behind."""

    def __init__(self):
        self.calls = []
        self._lock = threading.Lock()

    def wrap(self, fn):
        def recorded(*args, **kwargs):
            leaves = jax.tree_util.tree_leaves((args, kwargs))
            if not any(isinstance(x, jax.core.Tracer) for x in leaves):
                shapes = jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                    if isinstance(x, (jax.Array, np.ndarray)) else x,
                    (args, kwargs))
                with self._lock:
                    self.calls.append((fn, shapes))
            return fn(*args, **kwargs)
        return recorded

    def named(self, part: str):
        """Distinct recorded programs whose function name contains `part`."""
        seen, out = set(), []
        for fn, (args, kwargs) in self.calls:
            name = getattr(fn, "__qualname__", "") or repr(fn)
            sig = (name, str(jax.tree_util.tree_structure((args, kwargs))),
                   tuple(str(x) for x in
                         jax.tree_util.tree_leaves((args, kwargs))))
            if part in name and sig not in seen:
                seen.add(sig)
                out.append((fn, args, kwargs))
        return out


@pytest.fixture(scope="module")
def programs(one_chip):
    """Q6, Q1 and Q3 at SF1 through LocalQueryRunner, recorded."""
    from presto_tpu.metadata import Session
    from presto_tpu.models.tpch_sql import QUERIES
    from presto_tpu.ops import hash_join, scan, topn
    from presto_tpu.runner import LocalQueryRunner
    from presto_tpu.utils import kernel_cache

    rec = _Recorder()
    mp = pytest.MonkeyPatch()
    real_build = kernel_cache.get_or_build

    def recording_build(key, make):
        fn, built = real_build(key, make)
        return (rec.wrap(fn) if callable(fn) else fn), built

    # steer here, not through an option of the program: the engine asks
    # jax.default_backend() for its page capacity and driver parallelism
    mp.setattr(jax, "default_backend", lambda: "tpu")
    mp.setattr(kernel_cache, "get_or_build", recording_build)
    for mod, name in ((hash_join, "_fused_build_dense"),
                      (hash_join, "_live_key_range"),
                      (topn, "_topn_merge")):
        mp.setattr(mod, name, rec.wrap(getattr(mod, name)))
    try:
        runner = LocalQueryRunner(
            session=Session(catalog="tpch", schema="sf1"))
        for qid in (6, 1, 3):
            assert runner.execute(QUERIES[qid]).rows
    finally:
        mp.undo()
        scan.RESIDENT_CACHE.clear()
    return rec


def _compile(one_chip, fn, args, kwargs):
    args, kwargs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        if isinstance(x, jax.ShapeDtypeStruct) else x, (args, kwargs))
    compiled = fn.lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes)
    assert planned < HBM_BYTES, f"{fn}: plans {planned} bytes of 16 GB"
    return compiled


def _rows(args, kwargs):
    return max((x.shape[0] for x in jax.tree_util.tree_leaves((args, kwargs))
                if isinstance(x, jax.ShapeDtypeStruct) and x.shape),
               default=0)


@pytest.mark.parametrize("name", [
    "TableScanOperatorFactory",               # every scan: widen+filter+project
    "GlobalAggregationBuilder._accumulate",   # Q6
    "DirectAggregationBuilder._accumulate"])  # Q1
def test_scan_and_aggregate_compile(one_chip, programs, name):
    """Q6 and Q1 are one scan program feeding one accumulate program per
    2^20-row page."""
    found = programs.named(name)
    assert any(_rows(a, k) == 1 << 20 for _, a, k in found)
    for fn, args, kwargs in found:
        _compile(one_chip, fn, args, kwargs)


@pytest.mark.parametrize("name,count", [("_static_result", 2),
                                        ("_result_epilogue", 1)])
def test_result_program_compiles(one_chip, programs, name, count):
    """The aggregation's result is one program (PR 41): Q6's one row and
    Q1's direct table decoded, finished, cast and cut into its page; Q3's
    sort-builder columns finished with their live count and NULL flags."""
    found = programs.named(name)
    assert len(found) == count
    for fn, args, kwargs in found:
        _compile(one_chip, fn, args, kwargs)


@pytest.mark.parametrize("name", ["_live_key_range", "_fused_build_dense"])
def test_join_build_compiles(one_chip, programs, name):
    """Q3's two builds take the direct-address table (PR 30): the range read
    and the scatter into a table of the key range's pow2 bucket, 2^18 slots
    for customer and 2^23 for orders. No sort program is left in a build."""
    found = programs.named(name)
    assert len(found) == 2
    for fn, args, kwargs in found:
        _compile(one_chip, fn, args, kwargs)


def test_probe_and_partial_agg_segment_compiles(one_chip, programs):
    """Q3's fused segment: two direct-address probes (one gather each from
    the s32 tables), the filter/project and the sort-based per-page partial
    aggregation in one program."""
    segments = programs.named("_compose")
    assert len(segments) == 1
    fn, args, kwargs = segments[0]
    assert _rows(args[:1], {}) == 1 << 20          # the probe page
    int32_lengths = {x.shape[0] for x in jax.tree_util.tree_leaves(args[1])
                     if x.dtype == np.int32 and x.shape}
    assert {1 << 18, 1 << 23} <= int32_lengths     # the two tables
    _compile(one_chip, fn, args, kwargs)


def test_sorted_two_key_probe_compiles(one_chip):
    """Q9's partsupp probe (PR 34), the one join no direct-address table
    serves: lineitem's two key columns packed into the build's 64-bit key
    space, then a binary search of a 2^20-row page in the build's 2^20 sorted
    keys and the check against the true key columns. Shapes as the cell
    `q9_sf1` dispatches them; the programs are the operator's own."""
    from presto_tpu.ops import hash_join

    n = 1 << 20
    i64 = jax.ShapeDtypeStruct((n,), np.int64)
    plan = jax.ShapeDtypeStruct((2,), np.int64)
    _compile(one_chip, hash_join._pack_key, ((i64, i64), plan, plan, plan), {})
    _compile(one_chip, hash_join._probe_match_sorted_unique,
             (i64, jax.ShapeDtypeStruct((n,), np.int32), i64, (i64, i64),
              jax.ShapeDtypeStruct((n,), np.bool_), (i64, i64)), {})


def test_topn_compiles(one_chip, programs):
    merges = programs.named("topn_merge_stage")
    assert merges
    for fn, args, kwargs in merges:
        _compile(one_chip, fn, args, kwargs)


def test_hand_q1_step_compiles(one_chip):
    from __graft_entry__ import entry

    fn, args = entry()
    _compile(one_chip, jax.jit(fn),
             tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args), {})


# where the persistent compile cache lives (no chip, no child process)

def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        # JAX reads JAX_COMPILATION_CACHE_DIR itself: the package must leave
        # whatever JAX holds alone
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert presto_tpu.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        # unset: the one fixed path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert presto_tpu.configure_compile_cache() == \
            presto_tpu.COMPILE_CACHE_DEFAULT
        assert jax.config.jax_compilation_cache_dir == \
            presto_tpu.COMPILE_CACHE_DEFAULT
        assert presto_tpu.COMPILE_CACHE_DEFAULT.endswith("/.jax_cache")
        assert presto_tpu.COMPILE_CACHE_DEFAULT.startswith(
            str(pathlib.Path(presto_tpu.__file__).parents[1]))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
