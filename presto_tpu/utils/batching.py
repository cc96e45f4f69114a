"""Host-side column-chunk re-batching shared by page sources and kernels.

The streaming scan and the bench kernel both need "take exactly N rows off a
pending list of column chunks" — one implementation so partial-chunk view
semantics can never diverge between them.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


def narrowest_int_dtype(lo: int, hi: int) -> Optional[np.dtype]:
    """The narrowest signed integer dtype of 1, 2 or 4 bytes that holds every
    value of [lo, hi], None where it takes 8: the host->HBM wire form of an
    integer column whose range is known (the tpch connector from its
    generator's static bounds, the file connector from its files' statistics);
    ops/scan._widen_page widens it back on the device."""
    for dt in (np.int8, np.int16, np.int32):
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return np.dtype(dt)
    return None


def take_rows(pend: List[Sequence[np.ndarray]], count: int) -> List[np.ndarray]:
    """Remove exactly `count` rows from the front of `pend` (in place).

    `pend` is a list of chunks; each chunk is an indexable sequence of
    equal-length column arrays. Returns one concatenated array per column.
    Callers must ensure `pend` holds at least `count` rows.
    """
    if not pend:
        return []
    n_cols = len(pend[0])
    taken: List[List[np.ndarray]] = [[] for _ in range(n_cols)]
    got = 0
    while got < count:
        chunk = pend[0]
        n = len(chunk[0])
        need = count - got
        if n <= need:
            pend.pop(0)
            for i in range(n_cols):
                taken[i].append(chunk[i])
            got += n
        else:
            for i in range(n_cols):
                taken[i].append(chunk[i][:need])
            pend[0] = [c[need:] for c in chunk]
            got = count
    return [parts[0] if len(parts) == 1 else np.concatenate(parts)
            for parts in taken]


def clamp_capacity(est_rows: int, page_capacity: int, floor: int = 64) -> int:
    """Clamp a page capacity to the expected row count's pow2 bucket.

    Padded rows are real upload+compute waste on small splits; pow2 bucketing
    keeps the shape set (and thus XLA recompiles) small.
    """
    if est_rows <= 0:
        return min(page_capacity, floor)
    cap = 1 << max(int(est_rows - 1).bit_length(), floor.bit_length() - 1)
    return min(page_capacity, cap)
