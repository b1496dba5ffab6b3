"""Thread-safe zstd helpers.

zstandard (de)compressor objects are NOT safe for concurrent use from
multiple threads, and this codebase (de)compresses from many: query
workers, the flusher, merge workers, partition-parallel scans, HTTP
handler and cluster fetch threads.  Every caller goes through these
helpers, which keep one context per (thread, level) — no per-call
allocation, no sharing.  (Observed failure mode with a shared object:
sporadic "Data corruption detected" under concurrent flush+query load.)
"""

from __future__ import annotations

import threading

import zstandard

_tls = threading.local()


def compress(data: bytes, level: int = 1) -> bytes:
    key = f"zc{level}"
    zc = getattr(_tls, key, None)
    if zc is None:
        zc = zstandard.ZstdCompressor(level=level)
        setattr(_tls, key, zc)
    return zc.compress(data)


def decompress(data: bytes, max_output_size: int = 0) -> bytes:
    """Raises ValueError on anything that is not a decodable zstd frame
    (or exceeds max_output_size): an undecodable body is the sender's
    corruption, and ValueError is what every caller already maps to a
    whole-batch reject."""
    zd = getattr(_tls, "zd", None)
    if zd is None:
        zd = _tls.zd = zstandard.ZstdDecompressor()
    try:
        return zd.decompress(data, max_output_size=max_output_size)
    except zstandard.ZstdError as e:
        raise ValueError(f"undecodable zstd frame: {e}") from None
