"""The host allocator, set up for arrays of a page's size.

The host side of a scan makes and drops arrays of 1 to 32 MB all the time:
a range of a file copied into its wire dtype, a re-batched page, a generated
column chunk. glibc's malloc hands each of them its own `mmap` and gives it
back with `munmap`, so every such array is page-faulted in anew, 4 KB at a
time, and whether a process settles into that or into reusing its heap
depends on the order of its first frees (the threshold adapts). On the chip
machine that was the streamed Q1's wall: 0.36-0.38 s a query, 9% from query
to query and 7.5% from process to process with the defaults, 0.31 s, 2-3%
and 3.5% with arrays of this size kept on the heap (PERF.md section 6, PR
40). So they are: `mallopt` once a process, before the first scan.
"""
import ctypes
import ctypes.util
import os

# <malloc.h>
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3

MMAP_THRESHOLD = 32 << 20     # glibc's ceiling for it: a 2^22-row int64 column
TRIM_THRESHOLD = 1 << 30      # free heap top kept for the next page, not unmapped
TOP_PAD = 64 << 20            # the heap grows by a few pages' worth at a time


def install() -> bool:
    """Keep arrays up to MMAP_THRESHOLD on the malloc heap. False where
    there is no glibc `mallopt` (the platform's allocator stays as it is) or
    the user has set `MALLOC_MMAP_THRESHOLD_` (theirs holds)."""
    if "MALLOC_MMAP_THRESHOLD_" in os.environ:
        return False
    try:
        mallopt = ctypes.CDLL(ctypes.util.find_library("c")
                              or "libc.so.6").mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    return all(mallopt(option, value) == 1 for option, value in (
        (_M_MMAP_THRESHOLD, MMAP_THRESHOLD), (_M_TRIM_THRESHOLD, TRIM_THRESHOLD),
        (_M_TOP_PAD, TOP_PAD)))
