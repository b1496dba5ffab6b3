"""u32-lane scan kernels: the bandwidth-efficient device string scan.

The round-3 kernel (kernels.match_scan) tested every window offset with
`pat_len` byte-plane compares over a uint8[R, W] matrix.  On TPU every
uint8 lane occupies a full 32-bit VPU lane, so that design pays
~2*pat_len lane-ops per byte scanned — measured at ~6% of v5e HBM
bandwidth (PERF.md round-3 dissection).  This module is the round-4
rewrite; the same semantics (bit-identical vs logsql.matchers and
kernels.match_scan, which stays as the oracle) at ~4-8x fewer lane-ops:

- **u32 chunks**: the staged column is a uint32[W/4, R] matrix (4 bytes
  per lane, transposed so the ROW axis rides the 128-wide lane
  dimension and is shardable over a mesh).  A pattern compare tests 4
  bytes per lane-op: window starts split by alignment a in 0..3, and a
  window at s=4q+a matches iff ceil(pat_len/4) masked u32 compares hit.
- **SWAR byte predicates**: word-char table, ASCII case fold and
  newline detection run as parallel-per-byte bit tricks on u32 lanes
  (4 bytes/lane-op) instead of byte-plane compares.
- **exact/exact-prefix collapse**: whole-value equality only inspects
  window 0 — ceil(L/4) compares on (R,) vectors, no window matrix.

Layout contract (tpu/layout.py to_lanes32): lanes_t[q, r] is the
little-endian uint32 of bytes rows[r, 4q:4q+4]; tail padding is 0xFF
(never valid UTF-8, so padded windows cannot match and 0xFF is not a
word char).  Pattern chunk constants are built with the SAME in-trace
bitcast as the data, so data/pattern byte order always agree; the
byte-shift helpers assume a little-endian target (every XLA backend we
run — CPU x86-64, TPU — is little-endian; tests assert it).

Reference semantics anchored at filter_phrase.go:61-111 (word/phrase
match), filter_exact.go, filter_prefix.go; the tokenizer word table at
tokenizer.go:34-148.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .kernels import (MODE_EXACT, MODE_EXACT_PREFIX, MODE_PHRASE,
                      MODE_PREFIX, MODE_SUBSTRING)

_U32 = jnp.uint32


def _c(v: int) -> jnp.ndarray:
    return _U32(v & 0xFFFFFFFF)


# ---------------- SWAR byte predicates on u32 lanes ----------------
#
# All four bytes of a lane are tested in parallel; results arrive as a
# high-bit-per-byte mask (0x80 set in byte k iff byte k satisfies the
# predicate).  Range checks clear bit 7 first (x7) so per-byte adds
# never carry across byte boundaries; bytes >= 0x80 are handled via hb.

_LO7 = 0x7F7F7F7F
_HI1 = 0x80808080
_ONES = 0x01010101


def _rng(x7: jnp.ndarray, lo: int, hi: int) -> jnp.ndarray:
    """hi-bit-per-byte mask: lo <= byte7 <= hi (byte7 = byte & 0x7F;
    lo/hi must be < 0x80).  Carry-free: byte7 + (0x80-lo) <= 0xFE and
    (0x80+hi) - byte7 >= 1."""
    ge = x7 + _c((0x80 - lo) * _ONES)
    le = _c((0x80 + hi) * _ONES) - x7
    return ge & le


def word_hibits(x: jnp.ndarray) -> jnp.ndarray:
    """hi-bit-per-byte word-char mask (tokenizer table: [A-Za-z0-9_]
    plus any byte >= 0x80 except the 0xFF padding)."""
    x7 = x & _c(_LO7)
    hb = x & _c(_HI1)
    alnum = (_rng(x7, 0x61, 0x7A) | _rng(x7, 0x41, 0x5A) |
             _rng(x7, 0x30, 0x39) | _rng(x7, 0x5F, 0x5F))
    is_ff = _rng(x7, 0x7F, 0x7F) & hb
    return ((alnum & ~hb) | (hb & ~is_ff)) & _c(_HI1)


def fold_ascii32(x: jnp.ndarray) -> jnp.ndarray:
    """Per-byte ASCII lowercase fold (A-Z -> a-z), other bytes — incl.
    0xFF padding and multibyte UTF-8 — unchanged.  Exact counterpart of
    kernels._fold_ascii: adding 0x20 to bytes <= 0x5A never carries."""
    x7 = x & _c(_LO7)
    hb = x & _c(_HI1)
    upper = _rng(x7, 0x41, 0x5A) & ~hb & _c(_HI1)
    return x + (upper >> 2)


def any_byte_eq(x: jnp.ndarray, byte: int) -> jnp.ndarray:
    """hi-bit-per-byte mask of bytes == `byte` (haszero trick on
    x ^ byte*ONES).  May set a false hi bit only when a LOWER byte of
    the same lane is a true match (borrow propagation), so any-reduced
    uses are exact."""
    y = x ^ _c(byte * _ONES)
    return (y - _c(_ONES)) & ~y & _c(_HI1)


# ---------------- pattern chunking ----------------

def _pattern_chunks(pattern: jnp.ndarray, pat_len: int):
    """(chunk u32[nc], static mask ints): chunk c covers pattern bytes
    [4c, 4c+4); the last chunk's mask zeroes bytes past pat_len.  Built
    with the same bitcast the data layout uses, so byte order agrees on
    any backend."""
    nc = (pat_len + 3) // 4
    pad = nc * 4 - pat_len
    p = pattern
    if pad:
        p = jnp.concatenate([p, jnp.zeros((pad,), jnp.uint8)])
    pc = jax.lax.bitcast_convert_type(p.reshape(nc, 4), _U32)
    rem = pat_len % 4
    masks = [0xFFFFFFFF] * nc
    if rem:
        mb = np.array([0xFF] * rem + [0] * (4 - rem), dtype=np.uint8)
        masks[-1] = int(mb.view("<u4")[0])
    return pc, masks, nc


def _shifted(ext: jnp.ndarray, a: int, n: int) -> jnp.ndarray:
    """u32 at byte offset 4q+a for lane rows q in [0, n): little-endian
    combine of ext[q] and ext[q+1].  ext: u32[>=n+1, R]."""
    if a == 0:
        return ext[:n]
    return (ext[:n] >> _U32(8 * a)) | (ext[1:n + 1] << _U32(32 - 8 * a))


# ---------------- the scan ----------------

@partial(jax.jit, static_argnames=("pat_len", "mode", "starts_tok",
                                   "ends_tok", "fold"))
@jax.named_scope("match_scan")
def match_scan_t(lanes_t: jnp.ndarray, lengths: jnp.ndarray,
                 pattern: jnp.ndarray, pat_len: int, mode: int,
                 starts_tok: bool, ends_tok: bool,
                 fold: bool = False) -> jnp.ndarray:
    """Per-row match bitmap over a lane-major staged string column.

    lanes_t: uint32[W/4, R] (layout.to_lanes32); lengths: int32[R] true
    byte lengths (truncated at W-1; overflow rows re-checked on host);
    pattern: uint8[pat_len], pre-lowered when fold=True.
    Semantics identical to kernels.match_scan (the oracle); returns
    bool[R].
    """
    nl, r = lanes_t.shape
    pc, masks, nc = _pattern_chunks(pattern, pat_len)
    if fold:
        lanes_t = fold_ascii32(lanes_t)

    if mode in (MODE_EXACT, MODE_EXACT_PREFIX):
        # window 0 only: compare the first nc lanes of each row
        acc = None
        for c in range(nc):
            lane = lanes_t[c] if c < nl else _c(0xFFFFFFFF)
            if masks[c] == 0xFFFFFFFF:
                t = lane == pc[c]
            else:
                t = ((lane ^ pc[c]) & _c(masks[c])) == 0
            acc = t if acc is None else acc & t
        if mode == MODE_EXACT:
            return acc & (lengths == pat_len)
        return acc & (lengths >= pat_len)

    # extension lanes of 0xFF padding: windows past the row width can
    # never match (patterns are UTF-8 and contain no 0xFF byte)
    ext = jnp.concatenate(
        [lanes_t, jnp.full((nc, r), 0xFFFFFFFF, _U32)], axis=0)

    need_start = starts_tok and mode in (MODE_PHRASE, MODE_PREFIX)
    need_end = ends_tok and mode == MODE_PHRASE
    wm = word_hibits(ext) if (need_start or need_end) else None
    if need_start:
        # wmp[q] = word mask of lane q-1 (lane -1 = before the string:
        # a zero row, so window 0 always has a start boundary)
        wmp = jnp.concatenate([jnp.zeros((1, r), _U32), wm], axis=0)

    hit = None
    for a in range(4):
        s = _shifted(ext, a, nl + nc - 1)
        acc = None
        for c in range(nc):
            lanes = s[c:c + nl]
            if masks[c] == 0xFFFFFFFF:
                t = lanes == pc[c]
            else:
                t = ((lanes ^ pc[c]) & _c(masks[c])) == 0
            acc = t if acc is None else acc & t
        if need_start:
            # byte before window s=4q+a is byte (a-1) of lane q, or
            # byte 3 of lane q-1 when a == 0
            if a == 0:
                pw = (wmp[:nl] >> _U32(31)) & _U32(1)
            else:
                pw = (wm[:nl] >> _U32(8 * (a - 1) + 7)) & _U32(1)
            acc = acc & (pw == 0)
        if need_end:
            # byte after window is byte offset 4q + a + pat_len
            t_off = a + pat_len
            lq, lb = t_off // 4, t_off % 4
            nw = (wm[lq:lq + nl] >> _U32(8 * lb + 7)) & _U32(1)
            acc = acc & (nw == 0)
        h = jnp.any(acc, axis=0)
        hit = h if hit is None else hit | h
    return hit & (lengths >= pat_len)


@partial(jax.jit, static_argnames=("pat_len", "mode", "starts_tok",
                                   "ends_tok", "fold"))
def match_scan_t_packed(lanes_t, lengths, pattern, pat_len, mode,
                        starts_tok, ends_tok, fold=False):
    """match_scan_t with the bitmap bit-packed on device before download
    (8x fewer bytes over the host link)."""
    return jnp.packbits(match_scan_t(lanes_t, lengths, pattern, pat_len,
                                     mode, starts_tok, ends_tok,
                                     fold).astype(jnp.uint8))


def _window_hits(ext: jnp.ndarray, nl: int, pattern: jnp.ndarray,
                 pat_len: int):
    """Per-alignment window-equality masks: list of bool[nl, R] for
    a in 0..3 (window start s = 4q + a)."""
    pc, masks, nc = _pattern_chunks(pattern, pat_len)
    out = []
    for a in range(4):
        s = _shifted(ext, a, nl + nc - 1)
        acc = None
        for c in range(nc):
            lanes = s[c:c + nl]
            if masks[c] == 0xFFFFFFFF:
                t = lanes == pc[c]
            else:
                t = ((lanes ^ pc[c]) & _c(masks[c])) == 0
            acc = t if acc is None else acc & t
        out.append(acc)
    return out


@partial(jax.jit, static_argnames=("len_a", "len_b"))
@jax.named_scope("match_pair")
def match_ordered_pair_t(lanes_t: jnp.ndarray, lengths: jnp.ndarray,
                         pat_a: jnp.ndarray, len_a: int,
                         pat_b: jnp.ndarray, len_b: int):
    """`A.*B` decomposition on the lane-major layout: matches iff the
    FIRST occurrence of A ends at or before the LAST occurrence of B.
    Rows containing a newline go to the needs-verify channel ('.' does
    not cross newlines).  Returns (definite bool[R], needs_verify
    bool[R]) — semantics identical to kernels.match_ordered_pair."""
    nl, r = lanes_t.shape
    nc_max = (max(len_a, len_b) + 3) // 4
    ext = jnp.concatenate(
        [lanes_t, jnp.full((nc_max, r), 0xFFFFFFFF, _U32)], axis=0)
    big = jnp.int32(4 * nl + 8)

    hits_a = _window_hits(ext, nl, pat_a, len_a)
    hits_b = _window_hits(ext, nl, pat_b, len_b)
    any_a = None
    first_a = big
    any_b = None
    last_b = jnp.int32(-1)
    for a in range(4):
        ha, hb = hits_a[a], hits_b[a]
        ra = jnp.any(ha, axis=0)
        rb = jnp.any(hb, axis=0)
        any_a = ra if any_a is None else any_a | ra
        any_b = rb if any_b is None else any_b | rb
        fq = jnp.argmax(ha, axis=0).astype(jnp.int32)       # first hit lane
        pa = jnp.where(ra, 4 * fq + a, big)
        first_a = jnp.minimum(first_a, pa)
        lq = (nl - 1) - jnp.argmax(hb[::-1], axis=0).astype(jnp.int32)
        pb = jnp.where(rb, 4 * lq + a, jnp.int32(-1))
        last_b = jnp.maximum(last_b, pb)
    any_a = any_a & (lengths >= len_a)
    any_b = any_b & (lengths >= len_b)
    ordered = any_a & any_b & (first_a + len_a <= last_b)
    has_nl = jnp.any(any_byte_eq(lanes_t, 0x0A) != 0, axis=0)
    return ordered & ~has_nl, ordered & has_nl


@partial(jax.jit, static_argnames=("len_a", "len_b"))
def match_ordered_pair_t_packed(lanes_t, lengths, pat_a, len_a,
                                pat_b, len_b):
    """Both result vectors packed into ONE uint8[2, R/8] download."""
    definite, needsv = match_ordered_pair_t(lanes_t, lengths, pat_a,
                                            len_a, pat_b, len_b)
    return jnp.stack([jnp.packbits(definite.astype(jnp.uint8)),
                      jnp.packbits(needsv.astype(jnp.uint8))], axis=0)
