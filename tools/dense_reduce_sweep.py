"""The sweep that sets `hash_agg.DENSE_REDUCE_MAX_SEGMENTS`.

Times the two forms of a scalar segment reduce over one 2^20-row column on
whatever device JAX gives (run it on the chip: `chiprun -- python3 -m
tools.dense_reduce_sweep`): the scatter (`jax.ops.segment_sum`) and the
masked reduction (`hash_agg._masked_segment_reduce`), for int64 and float64
at each segment count. One JSON line per (dtype, segments) with both medians
and their ratio; the constant is the largest count where the masked form is
at least 2x faster for both dtypes. A CPU run says nothing about the chip.
"""
import functools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import presto_tpu  # noqa: F401  (enables 64-bit types)
from presto_tpu.ops import hash_agg
from presto_tpu.ops.aggregates import SUM

ROWS = 1 << 20
SEGMENTS = (13, 64, 256, 1024, 4096)


@functools.partial(jax.jit, static_argnames="segments")
def _scatter(values, ids, segments):
    return jax.ops.segment_sum(values, ids, num_segments=segments)


@functools.partial(jax.jit, static_argnames="segments")
def _masked(values, ids, segments):
    return hash_agg._masked_segment_reduce(SUM, values, ids, segments)


def _median_seconds(fn, *args, budget_s: float = 1.0, most: int = 30):
    jax.block_until_ready(fn(*args))  # compile + warm
    times = []
    spent = 0.0
    while len(times) < 3 or (spent < budget_s and len(times) < most):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
        spent += times[-1]
    return statistics.median(times)


def main(segments=SEGMENTS, rows=ROWS) -> int:
    dev = jax.devices()[0]
    rng = np.random.default_rng(28)
    for dtype in (np.int64, np.float64):
        scale = 1 if dtype is np.int64 else 0.01  # doubles with a fraction
        values = jnp.asarray(rng.integers(-10 ** 9, 10 ** 9, rows) * scale,
                             dtype=dtype)
        for s in segments:
            ids = jnp.asarray(rng.integers(0, s, rows), dtype=jnp.int32)
            a = np.asarray(_scatter(values, ids, s))
            b = np.asarray(_masked(values, ids, s))
            gap = float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1)))
            t_scatter = _median_seconds(_scatter, values, ids, s)
            t_masked = _median_seconds(_masked, values, ids, s)
            print(json.dumps({
                "device": dev.device_kind, "platform": dev.platform,
                "rows": rows, "dtype": np.dtype(dtype).name, "segments": s,
                "scatter_s": t_scatter, "masked_s": t_masked,
                "scatter_over_masked": t_scatter / t_masked,
                "rel_gap": gap}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
