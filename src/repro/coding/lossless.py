"""Lossless back-end: Huffman followed by a byte-stream coder (paper: ZSTD [38]).

ZSTD is unavailable in this offline container; ``zlib`` (DEFLATE) is the
stand-in with an identical bytes->bytes interface — documented in
DESIGN.md §6.  ``codec="zlib"`` skips the explicit Huffman stage (DEFLATE
already entropy-codes) and is the fast path used by the throughput benches;
``codec="huffman+zlib"`` is the paper-faithful chain.

A body longer than ``DEFLATE_CHUNK_BYTES`` is deflated pigz-style: fixed
chunks run on a thread pool, each primed with the 32 KiB of body before it,
and are joined into one standard zlib stream (RFC 1950/1951) that
``zlib.decompress`` reads as it reads any other.  The bytes depend only on
the body.  Shorter bodies keep the single ``zlib.compress`` call.
"""

from __future__ import annotations

import os
import struct
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.coding.huffman import huffman_decode, huffman_encode

_MAGIC_HUFF = b"FH"
_MAGIC_RAW = b"FR"

#: Bytes of body a chunk of the threaded DEFLATE covers.  A body of at most
#: one chunk is one ``zlib.compress`` call, so small streams are byte for
#: byte what the serial coder wrote; szlike's int16 code stream of a 256^3
#: field (9 + 2 * 256^3 bytes) is 17 chunks, the last of 7 bytes.
DEFLATE_CHUNK_BYTES = 1 << 21

_WINDOW = 1 << 15  # DEFLATE's history: the dictionary that primes a chunk
_WBITS, _MEM_LEVEL = 15, 8  # zlib.compress's defaults


def _cores() -> int:
    if hasattr(os, "sched_getaffinity"):
        return len(os.sched_getaffinity(0))
    return os.cpu_count() or 1


def _new_pool():
    global _POOL
    _POOL = ThreadPoolExecutor(max_workers=_cores(), thread_name_prefix="ffcz-deflate")


_new_pool()
# a forked child inherits the pool object but not its threads
os.register_at_fork(after_in_child=_new_pool)


def lossless_compress(symbols: np.ndarray, codec: str = "huffman+zlib", level: int = 6) -> bytes:
    """Compress an integer symbol stream to bytes."""
    symbols = np.asarray(symbols).astype(np.int64).ravel()
    if codec == "huffman+zlib":
        return _MAGIC_HUFF + _deflate(huffman_encode(symbols), level)
    if codec == "zlib":
        # int64 is wasteful on the wire; narrow to the smallest dtype that fits.
        dtype = _narrowest_dtype(symbols)
        body = struct.pack("<cQ", dtype.char.encode(), symbols.size) + symbols.astype(dtype).tobytes()
        return _MAGIC_RAW + _deflate(body, level)
    raise ValueError(f"unknown codec {codec!r}")


def lossless_decompress(data: bytes) -> np.ndarray:
    """Inverse of :func:`lossless_compress`."""
    magic, body = data[:2], zlib.decompress(data[2:])
    if magic == _MAGIC_HUFF:
        return huffman_decode(body)
    if magic == _MAGIC_RAW:
        char, n = struct.unpack_from("<cQ", body, 0)
        dtype = np.dtype(char.decode())
        return np.frombuffer(body, dtype=dtype, count=n, offset=9).astype(np.int64)
    raise ValueError("bad magic in lossless stream")


def _deflate(body: bytes, level: int) -> bytes:
    """One zlib stream of ``body``: ``zlib.compress`` below two chunks, else
    the chunks deflated on ``_POOL``."""
    # spans -> repro.core, whose package imports this module
    from repro.core.spans import span

    n_chunks = -(-len(body) // DEFLATE_CHUNK_BYTES)
    with span("ffcz.deflate", chunks=max(n_chunks, 1), bytes=len(body)):
        if n_chunks < 2:
            return zlib.compress(body, level)
        view = memoryview(body)
        starts = range(0, len(body), DEFLATE_CHUNK_BYTES)
        # zlib releases the GIL while it deflates, so the chunks run on every core
        parts = [_POOL.submit(_deflate_chunk, view, a, level) for a in starts]
        check = zlib.adler32(view)
        head = zlib.compress(b"", level)[:2]  # CMF/FLG: the same for any body
        return b"".join([head, *(p.result() for p in parts), struct.pack(">I", check)])


def _deflate_chunk(view: memoryview, a: int, level: int) -> bytes:
    """Raw DEFLATE of ``view[a : a + DEFLATE_CHUNK_BYTES]`` primed with the
    window before ``a``; it ends on a byte boundary (``Z_SYNC_FLUSH``) so the
    next chunk's blocks can follow, and the last chunk ends the stream."""
    b = min(a + DEFLATE_CHUNK_BYTES, len(view))
    prime = {"zdict": view[max(0, a - _WINDOW) : a]} if a else {}
    c = zlib.compressobj(level, zlib.DEFLATED, -_WBITS, _MEM_LEVEL, zlib.Z_DEFAULT_STRATEGY, **prime)
    return c.compress(view[a:b]) + c.flush(zlib.Z_FINISH if b == len(view) else zlib.Z_SYNC_FLUSH)


def _narrowest_dtype(symbols: np.ndarray) -> np.dtype:
    if symbols.size == 0:
        return np.dtype(np.int8)
    lo, hi = int(symbols.min()), int(symbols.max())
    for dt in (np.int8, np.int16, np.int32):
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return np.dtype(dt)
    return np.dtype(np.int64)
