"""The bare host -> HBM rate of `jax.device_put`, beside which PERF.md reads
the scan pipeline's `scan_upload_gbytes_per_s`.

A loop of `jax.device_put` over numpy columns of one TPU page (2^22 rows) of
1, 2, 4 and 8 bytes a row, on whatever device JAX gives (run it on the chip:
`chiprun -- python3 -m tools.device_put_rate`, under a minute). A CPU run
says nothing about the chip. For each width, over `REPEATS` fresh host arrays
(a page the pipeline uploads was just written by the re-batcher, so no array
is put twice): `issue_s`, the median seconds until `device_put` RETURNS, and
`ready_s`, until the array is on the device (`block_until_ready`), with the
bytes over the latter as `gbytes_per_s`. Then one Q1 page as the file
connector's narrow form sends it (2+4+1+1+1+1+2 bytes a row and the row
mask), its eight arrays issued together and waited for together, as
`ops/scan_pipeline._upload_gen` does. One JSON line each, and one that says
whether `native/pcol.cpp` built (the mmap the file connector's range readers
need).
"""
import json
import statistics
import sys
import time

import jax
import numpy as np

ROWS = 1 << 22
REPEATS = 8
Q1_PAGE = (np.int16, np.int32, np.int8, np.int8, np.int8, np.int8, np.int16,
           np.bool_)


def _fresh(dtype, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 100, ROWS).astype(dtype)


def put(arrays):
    """-> (seconds until device_put returned, seconds until all arrived)"""
    t0 = time.perf_counter()
    on_device = [jax.device_put(a) for a in arrays]
    issued = time.perf_counter() - t0
    jax.block_until_ready(on_device)
    return issued, time.perf_counter() - t0


def measure(dtypes):
    nbytes = sum(np.dtype(d).itemsize for d in dtypes) * ROWS
    put([_fresh(d, 0) for d in dtypes])   # the first transfer sets the link up
    runs = [put([_fresh(d, 1 + r * len(dtypes) + i)
                 for i, d in enumerate(dtypes)]) for r in range(REPEATS)]
    issue_s = statistics.median(r[0] for r in runs)
    ready_s = statistics.median(r[1] for r in runs)
    return {"rows": ROWS, "bytes": nbytes, "issue_s": issue_s,
            "ready_s": ready_s, "gbytes_per_s": nbytes / ready_s / 1e9}


def main():
    from presto_tpu.native import native_available

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "libpcol_built": native_available()}), flush=True)
    for dtype in (np.int8, np.int16, np.int32, np.int64):
        line = dict(measure([dtype]), column=np.dtype(dtype).name)
        print(json.dumps(line), flush=True)
    print(json.dumps(dict(measure(Q1_PAGE), column="q1_page_narrow")),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
