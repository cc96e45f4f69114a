"""Page wire format for the cross-host (DCN) data plane.

Analogue of execution/buffer/PagesSerde.java:39,55 + PagesSerdeFactory.java:38
(/root/reference/presto-main): the reference ships LZ4-compressed block-encoded
pages over HTTP; here a batch of pages becomes ONE columnar frame — dead
(masked-off) rows are dropped producer-side, each column's dense little-endian
bytes are concatenated and zlib-compressed per frame (zlib level 1 plays the
LZ4 "cheap and fast" role; it is what the environment provides).

Frame layout:
    magic 'PSER1'  | u32 header_len | header json | column payload...
Header: {"rows": N, "cols": [{"dtype": str, "nbytes": int, "nulls": bool}],
         "codec": "zlib1" | "raw"}
Payload: for each column, data bytes then (if nulls) a packed null bitmap.
Compressed when that wins, raw otherwise (PagesSerde's same tradeoff).

Types/dictionaries do NOT ride the wire: both ends plan the same fragment and
already agree on the schema (the reference ships block encodings instead; our
schema is a plan-time property, see cluster/task.py).
"""
from __future__ import annotations

import json
import struct
import zlib
from typing import List, Optional, Sequence

import numpy as np

from ..block import Block, Dictionary, Page
from ..types import Type

MAGIC = b"PSER1"


def pages_to_columns(pages: Sequence[Page], types: Sequence[Type]
                     ) -> tuple:
    """Concat pages, drop masked rows -> (datas, nulls, nrows). Host side."""
    ncols = len(types)
    if not pages:
        return ([np.zeros(0, dtype=np.dtype(t.np_dtype)) for t in types],
                [None] * ncols, 0)
    mask = np.concatenate([np.asarray(p.mask) for p in pages])
    keep = np.flatnonzero(mask)
    datas: List[np.ndarray] = []
    nulls: List[Optional[np.ndarray]] = []
    for c in range(ncols):
        dt = np.dtype(types[c].np_dtype)
        col = np.concatenate([np.asarray(p.blocks[c].data) for p in pages])
        datas.append(np.ascontiguousarray(col.astype(dt, copy=False)[keep]))
        if any(p.blocks[c].nulls is not None for p in pages):
            nm = np.concatenate(
                [np.asarray(p.blocks[c].nulls) if p.blocks[c].nulls is not None
                 else np.zeros(p.capacity, dtype=bool) for p in pages])
            nm = nm[keep]
            nulls.append(nm if nm.any() else None)
        else:
            nulls.append(None)
    return datas, nulls, len(keep)


def serialize_columns(datas: Sequence[np.ndarray],
                      nulls: Sequence[Optional[np.ndarray]],
                      nrows: int, compress: bool = True) -> bytes:
    cols_meta = []
    payload = bytearray()
    for data, nm in zip(datas, nulls):
        raw = data.tobytes()
        cols_meta.append({"dtype": data.dtype.str, "nbytes": len(raw),
                          "nulls": nm is not None})
        payload += raw
        if nm is not None:
            payload += np.packbits(nm).tobytes()
    body = bytes(payload)
    codec = "raw"
    if compress and len(body) > 512:
        z = zlib.compress(body, 1)
        if len(z) < len(body):
            body, codec = z, "zlib1"
    header = json.dumps({"rows": nrows, "cols": cols_meta,
                         "codec": codec}).encode()
    frame = MAGIC + struct.pack("<I", len(header)) + header + body
    from ..utils.metrics import METRICS
    METRICS.count("exchange.frames")
    METRICS.count("exchange.bytes", len(frame))
    METRICS.count("exchange.rows", nrows)
    return frame


def serialize_pages(pages: Sequence[Page], types: Sequence[Type],
                    compress: bool = True) -> bytes:
    datas, nulls, nrows = pages_to_columns(pages, types)
    return serialize_columns(datas, nulls, nrows, compress)


def deserialize_columns(frame: bytes) -> tuple:
    """-> (datas, nulls, nrows); inverse of serialize_columns."""
    assert frame[:5] == MAGIC, "bad page frame magic"
    (hlen,) = struct.unpack_from("<I", frame, 5)
    header = json.loads(frame[9:9 + hlen].decode())
    body = frame[9 + hlen:]
    if header["codec"] == "zlib1":
        body = zlib.decompress(body)
    nrows = header["rows"]
    datas, nulls = [], []
    off = 0
    for cm in header["cols"]:
        dt = np.dtype(cm["dtype"])
        datas.append(np.frombuffer(body, dtype=dt, count=cm["nbytes"] // dt.itemsize,
                                   offset=off).copy())
        off += cm["nbytes"]
        if cm["nulls"]:
            nbytes = (nrows + 7) // 8
            bits = np.frombuffer(body, dtype=np.uint8, count=nbytes, offset=off)
            nulls.append(np.unpackbits(bits)[:nrows].astype(bool))
            off += nbytes
        else:
            nulls.append(None)
    return datas, nulls, nrows


def _pad(arr: np.ndarray, length: int) -> np.ndarray:
    if len(arr) >= length:
        return arr
    return np.concatenate([arr, np.zeros(length - len(arr), dtype=arr.dtype)])


def columns_to_pages(datas: Sequence[np.ndarray],
                     nulls: Sequence[Optional[np.ndarray]], nrows: int,
                     types: Sequence[Type],
                     dicts: Sequence[Optional[Dictionary]],
                     page_capacity: int) -> List[Page]:
    """Re-page received columns at standard capacities so downstream operators
    reuse kernels compiled for scan pages (same policy as the mesh exchange,
    parallel/streaming_exchange.py)."""
    if nrows == 0:
        return []
    cap = min(page_capacity, 1 << (nrows - 1).bit_length())
    out: List[Page] = []
    for lo in range(0, nrows, cap):
        hi = min(lo + cap, nrows)
        blocks = []
        for c, t in enumerate(types):
            nm = nulls[c]
            nm_slice = _pad(nm[lo:hi], cap) if nm is not None else None
            if nm_slice is not None and not nm_slice.any():
                nm_slice = None
            blocks.append(Block(t, _pad(datas[c][lo:hi], cap), nm_slice,
                                dicts[c] if dicts else None))
        out.append(Page(tuple(blocks),
                        _pad(np.ones(hi - lo, dtype=bool), cap)))
    return out


def deserialize_pages(frame: bytes, types: Sequence[Type],
                      dicts: Sequence[Optional[Dictionary]],
                      page_capacity: int) -> List[Page]:
    datas, nulls, nrows = deserialize_columns(frame)
    return columns_to_pages(datas, nulls, nrows, types, dicts, page_capacity)
