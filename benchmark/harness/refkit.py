"""What the plain references share: dates, the two arithmetics, block maps.

`Arith(lower=False)` is the configuration's arithmetic: exact 64-bit integers
over scaled decimals. `Arith(lower=True)` is the CONTROL's: the same formulas
in float32, the nearest precision a later PR would be tempted by on a chip
that emulates 64-bit lanes. The control has to come out as not correct.
"""
import datetime
from concurrent.futures import ThreadPoolExecutor

import numpy as np

EPOCH = datetime.date(1970, 1, 1)


def days(y, m, d):
    return (datetime.date(y, m, d) - EPOCH).days


def date_of(day):
    return EPOCH + datetime.timedelta(days=int(day))


class Arith:
    def __init__(self, lower):
        self.lower = lower
        self.dtype = np.float32 if lower else np.int64

    def num(self, a):
        return a.astype(self.dtype)

    def zero(self):
        return np.float32(0) if self.lower else 0

    def total(self, a):
        """Sum of an array, as a Python int (exact) or a float32 scalar."""
        return a.sum(dtype=np.float32) if self.lower else int(a.sum())

    def scaled_int(self, x):
        """An accumulated value as the scaled integer a decimal prints from."""
        return int(round(float(x))) if self.lower else int(x)

    def mean(self, total, scale, n):
        if self.lower:
            return float(np.float32(total) / np.float32(scale) / np.float32(n))
        return int(total) / scale / n


def map_blocks(fn, blocks, threads=6):
    """fn(lo, hi) over blocks of orders; numpy releases the GIL, so a few
    threads shorten the reference without a second process."""
    with ThreadPoolExecutor(max_workers=threads) as pool:
        return list(pool.map(lambda b: fn(*b), blocks))
