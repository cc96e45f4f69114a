"""The sweep that sets `hash_join.DENSE_JOIN_MAX_TABLE_BYTES`.

Times the two forms of a unique single-key join's lookup structure on
whatever device JAX gives (run it on the chip: `chiprun -- python3 -m
tools.dense_join_sweep`): the sorted form (`_fused_build_sorted`, probed by
`probe_match_sorted`'s binary search) and the direct-address table (the
build's range read `_live_key_range` + `_fused_build_dense`, probed by
`probe_match_dense`'s one gather). For each build size and each range/rows
ratio: the build, and the probe of one 2^20-row page of int64 keys drawn
evenly over the range. One JSON line per (build rows, ratio) with the four
medians, the table's bytes and whether both probes found the same rows; the
constant is the largest table of the sweep that still probes faster than the
sorted form and fits a chip beside the data. A CPU run says nothing about
the chip.
"""
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

import presto_tpu  # noqa: F401  (enables 64-bit types)
from presto_tpu.block import Block, Page
from presto_tpu.ops import hash_join
from presto_tpu.types import BIGINT
from tools.dense_reduce_sweep import _median_seconds

PROBE_ROWS = 1 << 20
BUILD_ROWS = (1 << 15, 1 << 18, 1 << 20, 1 << 23)
RATIOS = (1, 4, 16, 64)
BASE = 1_000_003  # a smallest key that is not 0: the offset is exercised


def _build_sorted(pages):
    return hash_join._fused_build_sorted(pages, 1, ())


def _build_dense(pages):
    """What JoinBuildOperator._dense_plan and _build do: the range read
    (one host sync), then the table."""
    base, domain = hash_join.dense_table_range(pages)
    return hash_join._fused_build_dense(pages, 1, (), np.int64(base), domain)


def main(build_rows=BUILD_ROWS, ratios=RATIOS, probe_rows=PROBE_ROWS) -> int:
    dev = jax.devices()[0]
    rng = np.random.default_rng(30)
    for n in build_rows:
        for ratio in ratios:
            # unique keys spread over [BASE, BASE + n * ratio), shuffled
            keys = np.arange(n, dtype=np.int64) * ratio + \
                rng.integers(0, ratio, n) + BASE
            keys[0], keys[-1] = BASE, BASE + n * ratio - 1
            rng.shuffle(keys)
            pages = (Page((Block(BIGINT, jnp.asarray(keys)),),
                          jnp.ones(n, dtype=jnp.bool_)),)
            probe = jnp.asarray(
                rng.integers(BASE - 8, BASE + n * ratio + 8, probe_rows))
            live = jnp.ones(probe_rows, dtype=jnp.bool_)

            skeys, _, _, _, _, sorted_key, sorted_row = _build_sorted(pages)
            _, _, _, _, _, table = _build_dense(pages)

            def sorted_probe():
                return hash_join._probe_match_sorted_unique(
                    sorted_key, sorted_row, probe, (probe,), live, skeys)

            def dense_probe():
                return hash_join._probe_match_unique(table, np.int64(BASE),
                                                     probe, live)

            same = bool(np.array_equal(np.asarray(sorted_probe()),
                                       np.asarray(dense_probe())))
            line = {
                "device": dev.device_kind, "platform": dev.platform,
                "build_rows": n, "range_over_rows": ratio,
                "probe_rows": probe_rows,
                "table_bytes": int(table.shape[0]) * 4,
                "sorted_build_s": _median_seconds(
                    lambda: _build_sorted(pages), budget_s=0.5, most=5),
                "dense_build_s": _median_seconds(
                    lambda: _build_dense(pages), budget_s=0.5, most=5),
                "sorted_probe_s": _median_seconds(sorted_probe),
                "dense_probe_s": _median_seconds(dense_probe),
                "same_rows": same}
            line["sorted_over_dense_probe"] = \
                line["sorted_probe_s"] / line["dense_probe_s"]
            print(json.dumps(line), flush=True)
            del table, sorted_key, sorted_row, skeys, pages, probe
    return 0


if __name__ == "__main__":
    sys.exit(main())
