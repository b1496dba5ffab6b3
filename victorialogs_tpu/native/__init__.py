"""Native host core loader: builds/loads libvlnative-<hash>.so via ctypes.

The shared library is compiled on first use with g++ (no pip deps, no
pybind11 — plain C ABI) and keyed on a hash of vlnative.cpp, so a copied
or freshly checked-out tree (scrambled mtimes, no .so) neither trusts a
stale binary nor rebuilds one that matches.  Every consumer has a
pure-numpy fallback, so a missing toolchain degrades performance, never
correctness — but it says so once on stderr, and available() reports it.
Set VL_NO_NATIVE=1 to force the fallbacks (used in tests to diff outputs).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sys
import threading

import numpy as np
from .. import config

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "vlnative.cpp")

_lock = threading.Lock()
_lib = None
_tried = False


def so_path() -> str:
    """The built library for THIS vlnative.cpp (Makefile `native` target
    builds to the same name)."""
    with open(_SRC, "rb") as f:
        h = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"libvlnative-{h}.so")


def _build(so: str) -> bool:
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", tmp, _SRC]
    try:
        res = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        print(f"victorialogs_tpu.native: build failed ({e}); host "
              f"staging/scans fall back to numpy", file=sys.stderr)
        return False
    if res.returncode != 0:
        print(f"victorialogs_tpu.native: g++ failed; host staging/scans "
              f"fall back to numpy:\n{res.stderr.decode()[-2000:]}",
              file=sys.stderr)
        return False
    os.replace(tmp, so)
    for old in glob.glob(os.path.join(_HERE, "libvlnative*.so")):
        if old != so:          # binaries of earlier sources
            try:
                os.remove(old)
            except FileNotFoundError:
                pass           # a concurrent builder got there first
    return True


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if config.env("VL_NO_NATIVE"):
            return None
        # vlint: allow-lock-blocking-deep(one-time lazy init — hashing the source is part of locating the artifact every contender waits for)
        so = so_path()
        # vlint: allow-lock-blocking-deep(one-time lazy init — the compile is deliberately serialized under _lock; every contender needs the artifact and must wait for it)
        if not os.path.exists(so) and not _build(so):
            return None
        lib = ctypes.CDLL(so)
        i64 = ctypes.c_int64
        u64 = ctypes.c_uint64
        i32 = ctypes.c_int32
        p_u8 = ctypes.POINTER(ctypes.c_uint8)
        p_i64 = ctypes.POINTER(ctypes.c_int64)
        p_u64 = ctypes.POINTER(ctypes.c_uint64)
        lib.vl_to_fixed_width.argtypes = [p_u8, p_i64, p_i64, i64,
                                          p_u8, i64, i64]
        lib.vl_to_fixed_width.restype = None
        lib.vl_tokenize_arena.argtypes = [p_u8, p_i64, p_i64, i64,
                                          p_i64, p_i64, p_i64, i64]
        lib.vl_tokenize_arena.restype = i64
        lib.vl_unique_token_hashes.argtypes = [p_u8, p_i64, p_i64, i64,
                                               p_u64, i64]
        lib.vl_unique_token_hashes.restype = i64
        lib.vl_xxh64.argtypes = [p_u8, i64, u64]
        lib.vl_xxh64.restype = u64
        lib.vl_phrase_scan.argtypes = [p_u8, p_i64, p_i64, i64, p_u8,
                                       i64, i32, i32, i32, p_u8]
        lib.vl_phrase_scan.restype = None
        lib.vl_ordered_pair_scan.argtypes = [p_u8, p_i64, p_i64, i64,
                                             p_u8, i64, p_u8, i64,
                                             p_u8, p_u8]
        lib.vl_ordered_pair_scan.restype = None
        p_i32 = ctypes.POINTER(ctypes.c_int32)
        lib.vl_jsonline_scan.argtypes = [p_u8, i64, p_u8, i64,
                                         p_i32, i64, p_i32, i64,
                                         p_i64, p_i64]
        lib.vl_jsonline_scan.restype = i64
        p_pp = ctypes.POINTER(ctypes.c_void_p)
        lib.vl_emit_ndjson.argtypes = [i64, i64, p_pp, p_i64,
                                       p_pp, p_pp, p_pp, p_i64,
                                       p_i64, p_u8, i64]
        lib.vl_emit_ndjson.restype = i64
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def to_fixed_width_native(arena: np.ndarray, offsets: np.ndarray,
                          lengths: np.ndarray, rb: int, w: int
                          ) -> np.ndarray | None:
    """C++ staging transpose; None when the native lib is unavailable."""
    lib = _load()
    if lib is None:
        return None
    arena = np.ascontiguousarray(arena, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    out = np.empty((rb, w), dtype=np.uint8)
    lib.vl_to_fixed_width(
        _ptr(arena, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
        _ptr(lengths, ctypes.c_int64), len(offsets),
        _ptr(out, ctypes.c_uint8), rb, w)
    return out


def phrase_scan_native(arena: np.ndarray, offsets: np.ndarray,
                       lengths: np.ndarray, pattern: bytes, mode: int,
                       starts_tok: bool, ends_tok: bool
                       ) -> np.ndarray | None:
    """Arena-level scan (host analogue of the device match_scan kernel):
    one memmem pass over the packed column instead of a Python call per
    row.  Returns a bool[nrows] bitmap, or None when the native lib is
    unavailable or the pattern is empty (Python path handles those)."""
    lib = _load()
    if lib is None or not pattern:
        return None
    arena = np.ascontiguousarray(arena, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    pat = np.frombuffer(pattern, dtype=np.uint8)
    nrows = len(offsets)
    out = np.empty(nrows, dtype=np.uint8)
    lib.vl_phrase_scan(
        _ptr(arena, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
        _ptr(lengths, ctypes.c_int64), nrows,
        _ptr(pat, ctypes.c_uint8), len(pattern),
        mode, int(starts_tok), int(ends_tok),
        _ptr(out, ctypes.c_uint8))
    return out.view(np.bool_)


def ordered_pair_scan_native(arena: np.ndarray, offsets: np.ndarray,
                             lengths: np.ndarray, pat_a: bytes,
                             pat_b: bytes
                             ) -> tuple[np.ndarray, np.ndarray] | None:
    """Per-row `A.*B` decision (host analogue of match_ordered_pair):
    (definite_match bool[n], needs_verify bool[n]) or None."""
    lib = _load()
    if lib is None or not pat_a or not pat_b:
        return None
    arena = np.ascontiguousarray(arena, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    a = np.frombuffer(pat_a, dtype=np.uint8)
    b = np.frombuffer(pat_b, dtype=np.uint8)
    nrows = len(offsets)
    out_m = np.empty(nrows, dtype=np.uint8)
    out_v = np.empty(nrows, dtype=np.uint8)
    lib.vl_ordered_pair_scan(
        _ptr(arena, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
        _ptr(lengths, ctypes.c_int64), nrows,
        _ptr(a, ctypes.c_uint8), len(pat_a),
        _ptr(b, ctypes.c_uint8), len(pat_b),
        _ptr(out_m, ctypes.c_uint8), _ptr(out_v, ctypes.c_uint8))
    return out_m.view(np.bool_), out_v.view(np.bool_)


def unique_token_hashes_native(arena: np.ndarray, offsets: np.ndarray,
                               lengths: np.ndarray) -> np.ndarray | None:
    """Tokenize+hash+dedupe in one native pass; None when unavailable."""
    lib = _load()
    if lib is None:
        return None
    arena = np.ascontiguousarray(arena, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    cap = max(64, int(arena.shape[0]) // 2 + len(offsets) + 1)
    out = np.empty(cap, dtype=np.uint64)
    n = lib.vl_unique_token_hashes(
        _ptr(arena, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
        _ptr(lengths, ctypes.c_int64), len(offsets),
        _ptr(out, ctypes.c_uint64), cap)
    if n < 0:
        return None
    return out[:n].copy()


def tokenize_arena_native(arena: np.ndarray, offsets: np.ndarray,
                          lengths: np.ndarray):
    """Native tokenizer; None when unavailable."""
    lib = _load()
    if lib is None:
        return None
    arena = np.ascontiguousarray(arena, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    cap = max(64, int(arena.shape[0]) + 1)
    ts = np.empty(cap, dtype=np.int64)
    te = np.empty(cap, dtype=np.int64)
    tr = np.empty(cap, dtype=np.int64)
    n = lib.vl_tokenize_arena(
        _ptr(arena, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
        _ptr(lengths, ctypes.c_int64), len(offsets),
        _ptr(ts, ctypes.c_int64), _ptr(te, ctypes.c_int64),
        _ptr(tr, ctypes.c_int64), cap)
    if n < 0:
        return None
    return ts[:n].copy(), te[:n].copy(), tr[:n].copy()


def xxh64_native(data: bytes, seed: int = 0) -> int | None:
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    if buf.size == 0:
        buf = np.zeros(1, dtype=np.uint8)
        return int(lib.vl_xxh64(_ptr(buf, ctypes.c_uint8), 0, seed))
    return int(lib.vl_xxh64(_ptr(buf, ctypes.c_uint8), buf.size, seed))


_EMIT_DUMMY_I64 = np.zeros(1, dtype=np.int64)


def emit_ndjson_native(key_tokens: list, cols: list, nrows: int
                       ) -> bytes | None:
    """Columnar NDJSON serializer (the query emit hot path).

    key_tokens: per column, the pre-quoted b'"key":' token (json.dumps
    of the name + colon — key escaping is Python's own by construction);
    cols: per column a kind-tagged tuple (BlockResult.emit_columns):
      (0, arena uint8[], offsets int64[n], lengths int64[n]) — bytes,
          length 0 meaning "omit this field";
      (1, ts int64[n])           — RFC3339Nano timestamps (_time);
      (2, ts int64[n], frac_w)   — ISO8601, fixed fractional width;
      (3, nums int64[n])         — signed decimal;
      (4, nums uint64[n])        — unsigned decimal.
    Returns the response bytes, or None when the native lib is missing
    or a value holds invalid UTF-8 (caller uses the per-row Python path,
    whose errors='replace' decode that case would need)."""
    lib = _load()
    if lib is None:
        return None
    ncols = len(cols)
    keys = [np.frombuffer(t, dtype=np.uint8) for t in key_tokens]
    arenas, offs, lens = [], [], []
    kinds = np.empty(ncols, dtype=np.int64)
    params = np.zeros(ncols, dtype=np.int64)
    total_val = 0
    total_typed = 0
    total_key = 0
    for ci, (col, k) in enumerate(zip(cols, keys)):
        kind = col[0]
        kinds[ci] = kind
        if kind == 0:
            _k, arena, o, ln = col
            arenas.append(np.ascontiguousarray(arena, dtype=np.uint8))
            offs.append(np.ascontiguousarray(o, dtype=np.int64))
            lens.append(np.ascontiguousarray(ln, dtype=np.int64))
            total_val += int(lens[-1].sum())
        else:
            dt = np.uint64 if kind == 4 else np.int64
            arenas.append(np.ascontiguousarray(col[1], dtype=dt))
            offs.append(_EMIT_DUMMY_I64)
            lens.append(_EMIT_DUMMY_I64)
            if kind == 2:
                params[ci] = int(col[2])
            total_typed += 34 * nrows    # ts/decimal upper bound, exact
        total_key += k.size
    pp = ctypes.c_void_p * ncols
    key_ptrs = pp(*[k.ctypes.data for k in keys])
    arena_ptrs = pp(*[a.ctypes.data for a in arenas])
    off_ptrs = pp(*[o.ctypes.data for o in offs])
    len_ptrs = pp(*[ln.ctypes.data for ln in lens])
    key_lens = np.fromiter((k.size for k in keys), dtype=np.int64,
                           count=ncols)
    cap = 6 * total_val + total_typed \
        + nrows * (total_key + 6 * ncols + 8) + 16
    out = np.empty(cap, dtype=np.uint8)
    n = lib.vl_emit_ndjson(
        ncols, nrows,
        ctypes.cast(key_ptrs, ctypes.POINTER(ctypes.c_void_p)),
        _ptr(key_lens, ctypes.c_int64),
        ctypes.cast(arena_ptrs, ctypes.POINTER(ctypes.c_void_p)),
        ctypes.cast(off_ptrs, ctypes.POINTER(ctypes.c_void_p)),
        ctypes.cast(len_ptrs, ctypes.POINTER(ctypes.c_void_p)),
        _ptr(kinds, ctypes.c_int64), _ptr(params, ctypes.c_int64),
        _ptr(out, ctypes.c_uint8), cap)
    if n < 0:
        return None
    return out[:n].tobytes()


def jsonline_scan_native(body: bytes):
    """Native strict-subset JSON-lines scan (the columnar ingest fast
    path's parser).  Returns (arena_bytes, fields int32[N,5],
    lines int32[M,5], sigs int64[M], arena_is_ascii) or None when the
    native lib is unavailable or a capacity bound trips (caller uses the
    per-line Python parser)."""
    lib = _load()
    if lib is None or not body or len(body) >= (1 << 31) - 8:
        return None    # offsets are int32; huge bodies take the py path
    blen = len(body)
    buf = np.frombuffer(body, dtype=np.uint8)
    arena = np.empty(blen, dtype=np.uint8)
    fields_cap = blen // 4 + 64
    lines_cap = blen // 3 + 64
    fields = np.empty((fields_cap, 5), dtype=np.int32)
    lines = np.empty((lines_cap, 5), dtype=np.int32)
    sigs = np.empty(lines_cap, dtype=np.int64)
    counts = np.zeros(4, dtype=np.int64)
    rc = lib.vl_jsonline_scan(
        _ptr(buf, ctypes.c_uint8), blen,
        _ptr(arena, ctypes.c_uint8), blen,
        _ptr(fields, ctypes.c_int32), fields_cap,
        _ptr(lines, ctypes.c_int32), lines_cap,
        _ptr(sigs, ctypes.c_int64), _ptr(counts, ctypes.c_int64))
    if rc != 0:
        return None
    nl, nf, used, ascii_ = int(counts[0]), int(counts[1]), \
        int(counts[2]), bool(counts[3])
    return (arena[:used].tobytes(), fields[:nf], lines[:nl], sigs[:nl],
            ascii_)
