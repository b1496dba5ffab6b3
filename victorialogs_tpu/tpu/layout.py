"""HBM staging: storage blocks -> fixed-shape device tensors.

A string column stages as (padded uint8 arena, int32 offsets, int32 lengths);
shapes are bucketed (kernels.pad_bucket) so the jit cache stays small.  Staged
columns are LRU-cached across queries keyed by (part, block, column) — the
device-side analogue of the reference's per-block value caches
(block_search.go:411-474), and the practical expression of "decompressed
columnar blocks staged into HBM" from the north star.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import activity, tracing
from .kernels import pad_bucket


MAX_ROW_WIDTH = 2048  # values longer than W-1 overflow to the host path


@dataclass
class StagedStringColumn:
    rows: jax.Array           # uint8[rows_bucket, W]: values at col 0,
    #                           tail-padded with 0xFF
    lengths: jax.Array        # int32[rows_bucket] (tail rows: 0)
    nrows: int                # true row count
    nrows_padded: int
    width: int                # W
    overflow: np.ndarray      # int64[] row indices longer than W-1
    nbytes: int

    def device_bytes(self) -> int:
        return self.nbytes


def row_width_bucket(max_len: int) -> int:
    """Fixed row width: power of two >= max_len+1, capped at MAX_ROW_WIDTH."""
    w = 32
    while w <= max_len and w < MAX_ROW_WIDTH:
        w *= 2
    return w


def to_fixed_width(arena_np: np.ndarray, offsets_np: np.ndarray,
                   lengths_np: np.ndarray, rb: int, width: int | None = None
                   ) -> tuple[np.ndarray, int, np.ndarray]:
    """Transpose a packed string column into (rows_bucket, W) uint8.

    Returns (matrix, W, overflow_row_indices).  Overflow rows (longer than
    W-1) are truncated in the matrix; the runner re-checks them on host.
    Uses the C++ host core when available (native/vlnative.cpp); numpy
    fancy-indexing fallback otherwise.
    """
    r = int(offsets_np.shape[0])
    max_len = int(lengths_np.max()) if r else 0
    w = width if width is not None else row_width_bucket(max_len)
    from .. import native
    nat = native.to_fixed_width_native(arena_np, offsets_np, lengths_np,
                                       rb, w)
    if nat is not None:
        overflow = np.nonzero(lengths_np > w - 1)[0]
        return nat, w, overflow
    out = np.full((rb, w), 0xFF, dtype=np.uint8)
    if r:
        copy_lens = np.minimum(lengths_np, w - 1)
        idx = (np.repeat(np.arange(r, dtype=np.int64) * w, copy_lens)
               + _ranges(copy_lens))
        src = (np.repeat(offsets_np, copy_lens) + _ranges(copy_lens))
        out.reshape(-1)[idx] = arena_np[src]
    overflow = np.nonzero(lengths_np > w - 1)[0]
    return out, w, overflow


def to_lanes32(mat: np.ndarray) -> np.ndarray:
    """(R, W) uint8 staging matrix -> (W/4, R/128, 128) uint32 planes
    for the u32-chunk kernels (tpu/kernels32.py): planes[q, r // 128,
    r % 128] is the little-endian word of bytes mat[r, 4q:4q+4].  Word q
    of every row is one (R/128, 128) array whose rows fill the sublanes
    and the lanes of a TPU tile, and q is a leading, untiled index; the
    row axis is axis 1 (and shards over a mesh there).  W is always a
    multiple of 4 (row_width_bucket) and R of 128 (the row buckets)."""
    r, w = mat.shape
    assert w % 4 == 0 and r % 128 == 0
    return np.ascontiguousarray(
        mat.reshape(r, w // 4, 4).view("<u4")[:, :, 0].T
    ).reshape(w // 4, r // 128, 128)


def rows_with_multibyte(arena_np: np.ndarray, offsets_np: np.ndarray,
                        lengths_np: np.ndarray) -> np.ndarray:
    """Per-row any(byte >= 0x80) over the SOURCE values (truncated tails
    included), via prefix sums — exact even for zero-length rows.
    Returns bool[r].  Consumed by case-fold and len_range device leaves,
    whose byte-level compares are only definitive for pure-ASCII rows."""
    r = int(offsets_np.shape[0])
    if not arena_np.size or not (arena_np >= 0x80).any():
        return np.zeros(r, dtype=bool)
    cs = np.zeros(arena_np.size + 1, dtype=np.int64)
    np.cumsum(arena_np >= 0x80, out=cs[1:])
    offs = offsets_np.astype(np.int64)
    lens = lengths_np.astype(np.int64)
    return cs[offs + lens] > cs[offs]


def _ranges(lengths: np.ndarray) -> np.ndarray:
    """Concatenated [0..l) ranges for each l in lengths."""
    total = int(lengths.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    ends = np.cumsum(lengths)
    out = np.arange(total, dtype=np.int64)
    out -= np.repeat(ends - lengths, lengths)
    return out


def stage_string_column(arena_np: np.ndarray, offsets_np: np.ndarray,
                        lengths_np: np.ndarray) -> StagedStringColumn:
    r = int(offsets_np.shape[0])
    rb = pad_bucket(max(r, 1), minimum=1024)
    mat, w, overflow = to_fixed_width(arena_np, offsets_np, lengths_np, rb)
    # overflow rows carry their truncated length; the runner re-evaluates
    # them on host regardless of the device verdict
    lens = np.zeros(rb, dtype=np.int32)
    lens[:r] = np.minimum(lengths_np, w - 1).astype(np.int32)
    return StagedStringColumn(
        rows=jnp.asarray(mat), lengths=jnp.asarray(lens),
        nrows=r, nrows_padded=rb, width=w, overflow=overflow,
        nbytes=rb * w + rb * 4)


import threading as _threading
import weakref as _weakref

_caches_mu = _threading.Lock()
_caches: "_weakref.WeakSet" = _weakref.WeakSet()


def staging_caches() -> list:
    """Every live StagingCache (vlsan sweeps check_balanced on each
    after every test)."""
    with _caches_mu:
        return list(_caches)


class StagingCache:
    """LRU over staged columns, bounded by device bytes.

    Thread-safe: the prefetcher, concurrent partition scans and the query
    thread all touch it (batch.py)."""

    def __init__(self, max_bytes: int = 4 << 30):
        import threading
        with _caches_mu:
            _caches.add(self)
        self.max_bytes = max_bytes
        self._lru: OrderedDict[tuple, StagedStringColumn] = OrderedDict()
        self._bytes = 0
        self._mu = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key: tuple):
        with self._mu:
            got = self._lru.get(key)
            if got is not None:
                self._lru.move_to_end(key)
                self.hits += 1
            else:
                self.misses += 1
            return got

    @staticmethod
    def _cost(col) -> int:
        # markers without device buffers still occupy a nominal slot so the
        # LRU eventually evicts them (long-running servers mint a fresh part
        # uid every flush/merge)
        return col.device_bytes() if hasattr(col, "device_bytes") else 4096

    def put(self, key: tuple, col) -> None:
        cost = self._cost(col)
        with self._mu:
            if key in self._lru:
                return
            self._lru[key] = col
            self._bytes += cost
            while self._bytes > self.max_bytes and self._lru:
                _, old = self._lru.popitem(last=False)
                self._bytes -= self._cost(old)
        # staging attribution on the active trace (noop when off); the
        # insert above returned early on a duplicate, so this counts
        # each staged value exactly once
        sp = tracing.current_span()
        if sp.enabled:
            sp.add("staged_entries")
            sp.add("staged_bytes", cost)
        activity.current_activity().add("bytes_staged", cost)

    def put_small(self, key: tuple, marker) -> None:
        """Cache a marker (e.g. 'this column is unstageable')."""
        self.put(key, marker)

    def contains(self, key: tuple) -> bool:
        """Membership probe without touching LRU order or hit counters."""
        with self._mu:
            return key in self._lru

    def stats(self) -> dict:
        """Observability snapshot (runner stats / pipeline tests)."""
        with self._mu:
            return {"hits": self.hits, "misses": self.misses,
                    "bytes": self._bytes, "entries": len(self._lru)}

    def device_resident_bytes(self) -> dict:
        """device id -> bytes of the cached entries' arrays that sit on
        that device, read off the arrays' own shards (not the budget's
        nominal costs): the evidence that a mesh runner's staging is
        spread over its devices rather than piled on one."""
        with self._mu:
            entries = list(self._lru.values())
        out: dict[int, int] = {}
        for e in entries:
            for v in getattr(e, "__dict__", {}).values():
                if isinstance(v, jax.Array):
                    for sh in v.addressable_shards:
                        out[sh.device.id] = out.get(sh.device.id, 0) \
                            + sh.data.nbytes
        return out

    def check_balanced(self) -> bool:
        """Budget-accounting invariant: the running byte total equals
        the recomputed cost of every live entry.  The pipeline's
        cancellation tests assert this after draining an in-flight
        window (a poisoned/partial entry would break the equality)."""
        with self._mu:
            return self._bytes == sum(self._cost(c)
                                      for c in self._lru.values())

    def clear(self) -> None:
        with self._mu:
            self._lru.clear()
            self._bytes = 0
