"""The sweep that chose the scan form of `hash_agg.sort_group_reduce`.

The sort aggregation reduces its state columns over rows already sorted by
group, so a group is a run of rows that begins where a start flag is set.
This times, on whatever device JAX gives (run it on a TPU: `python3 -m
tools.sorted_reduce_sweep`), for int64 and float64 columns at 2^16, 2^20
and 2^22 rows:

- `cumsum`: `jnp.cumsum`, the plain prefix sum an integer sum would
  difference at group ends (a reduce-window on the TPU);
- `associative_scan`: `lax.associative_scan` over (start flag, value);
- `blocked`: `hash_agg._segmented_scan`, the two-level scan over a
  (rows / SCAN_BLOCK, SCAN_BLOCK) view;
- `blocked_gather`: `blocked` and the gather at each group's last row, the
  whole reduction the kernel runs a column;
- `scatter`: `jax.ops.segment_sum` into rows + 1 segments, what the kernel
  ran before.

`--log2-rows`, `--forms` and `--dtypes` narrow the sweep: some forms take
minutes to compile at these sizes. One JSON line per (form, dtype, rows): the seconds its compile took (the
persistent compilation cache is off) and the median milliseconds a run.
`agrees` holds each form's group sums (`cumsum`: its prefix sums) against
numpy's, int64 exactly and float64 to 1e-12. A CPU run says nothing about the
chip.
"""
import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import presto_tpu  # noqa: F401  (enables 64-bit types)
from presto_tpu.ops import hash_agg

ROWS = (1 << 16, 1 << 20, 1 << 22)
GROUP_ROWS = 4  # mean rows a group


def _seg_add(a, b):
    (fa, va), (fb, vb) = a, b
    return fa | fb, jnp.where(fb, vb, va + vb)


def _cumsum(starts, values, last):
    return jnp.cumsum(values)


def _associative(starts, values, last):
    return jax.lax.associative_scan(_seg_add, (starts, values))[1]


def _blocked(starts, values, last):
    return hash_agg._segmented_scan(jax.lax.add, starts, values)


def _blocked_gather(starts, values, last):
    return hash_agg._segmented_scan(jax.lax.add, starts, values)[last]


def _scatter(starts, values, last):
    ids = jnp.cumsum(starts.astype(jnp.int32)) - 1
    return jax.ops.segment_sum(values, ids, num_segments=values.shape[0] + 1)


FORMS = {"cumsum": _cumsum, "associative_scan": _associative,
         "blocked": _blocked, "blocked_gather": _blocked_gather,
         "scatter": _scatter}


def _median_ms(fn, *args, budget_s: float = 1.0, most: int = 30):
    jax.block_until_ready(fn(*args))  # warm
    times = []
    spent = 0.0
    while len(times) < 3 or (spent < budget_s and len(times) < most):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
        spent += times[-1]
    return 1e3 * statistics.median(times)


def _agrees(name, got, values, starts, last):
    """The form's group sums (its prefix sums for `cumsum`) against numpy's:
    int64 exactly, float64 to 1e-12."""
    got = np.asarray(got)
    if name == "cumsum":
        want = np.cumsum(values)
    else:
        want = np.add.reduceat(values, np.flatnonzero(starts))
        got = {"blocked_gather": got, "scatter": got[:len(want)]}.get(
            name, got[last] if got.shape == values.shape else got)
    if values.dtype.kind == "f":  # a prefix sum's error is its magnitude's
        return bool(np.allclose(got, want, rtol=1e-12, atol=1e-12 * np.sum(
            np.abs(values)) if name == "cumsum" else 0))
    return bool(np.array_equal(got, want))


def main(rows_list=ROWS, forms=tuple(FORMS),
         dtypes=("int64", "float64")) -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    rng = np.random.default_rng(44)
    for rows in rows_list:
        starts_np = rng.random(rows) < 1.0 / GROUP_ROWS
        starts_np[0] = True
        last_np = np.append(np.flatnonzero(starts_np)[1:] - 1, rows - 1)
        starts = jnp.asarray(starts_np)
        last = jnp.asarray(last_np.astype(np.int32))
        for dtype in map(np.dtype, dtypes):
            scale = 1 if dtype.kind == "i" else 0.01  # doubles with a fraction
            values_np = (rng.integers(-10 ** 9, 10 ** 9, rows)
                         * scale).astype(dtype)
            values = jnp.asarray(values_np)
            for name in forms:
                fn = FORMS[name]
                # a compile of its own each time: its seconds are measured
                t0 = time.perf_counter()
                compiled = jax.jit(fn).lower(  # prestocheck: ignore[cache-key-hygiene]
                    starts, values, last).compile()
                compile_s = time.perf_counter() - t0
                got = compiled(starts, values, last)
                print(json.dumps({
                    "device": dev.device_kind, "platform": dev.platform,
                    "form": name, "dtype": dtype.name,
                    "rows": rows, "compile_s": round(compile_s, 3),
                    "run_ms": _median_ms(compiled, starts, values, last),
                    "agrees": _agrees(name, got, values_np, starts_np,
                                      last_np)}),
                    flush=True)
    return 0


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--log2-rows", default="16,20,22",
                   help="row counts as powers of two, comma-separated")
    p.add_argument("--forms", default=",".join(FORMS),
                   help=f"comma-separated, of {', '.join(FORMS)}")
    p.add_argument("--dtypes", default="int64,float64")
    a = p.parse_args(argv)
    return (tuple(1 << int(x) for x in a.log2_rows.split(",")),
            tuple(a.forms.split(",")), tuple(a.dtypes.split(",")))


if __name__ == "__main__":
    sys.exit(main(*_args(sys.argv[1:])))
