"""Device kernels for the block runner (jnp/XLA).

The flagship kernel is the byte-arena phrase/substring scan: a column block's
string values are staged as one padded uint8 arena plus row offsets, and the
kernel tests every window position against the pattern with word-boundary
semantics bit-identical to logsql.matchers.match_phrase / match_prefix (the
correctness oracle).  All control flow is static — one compile per
(arena bucket size, rows bucket, pattern length, mode) — so XLA fuses the
whole scan into a handful of vector loops over VMEM tiles.

Semantics notes:
- arena padding bytes are 0xFF: never part of valid UTF-8, so padded windows
  can't produce false matches; padded bytes map to segment `nrows`, which is
  dropped by the segment reduction.
- word chars = ASCII alnum + '_' + any byte >= 0x80 (same table as the
  tokenizer and matchers — utils/tokenizer.py).
- patterns are capped at MAX_PATTERN_LEN bytes; longer patterns fall back to
  the CPU path (runner.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

MAX_PATTERN_LEN = 64

MODE_PHRASE = 0        # substring with word boundaries on both sides
MODE_PREFIX = 1        # substring with word boundary before only
MODE_SUBSTRING = 2     # plain substring (regex literal prefilter)
MODE_EXACT = 3         # whole value equality
MODE_EXACT_PREFIX = 4  # value startswith


def _is_word_u8(b: jnp.ndarray) -> jnp.ndarray:
    """Word-char test on uint8 bytes (VPU compares, no gather).

    0xFF is excluded: it cannot occur in UTF-8 data, and staging uses it as
    the inter-value separator (row boundary)."""
    return ((b >= ord("a")) & (b <= ord("z"))) | \
           ((b >= ord("A")) & (b <= ord("Z"))) | \
           ((b >= ord("0")) & (b <= ord("9"))) | \
           (b == ord("_")) | ((b >= 0x80) & (b != 0xFF))


def _fold_ascii(rows: jnp.ndarray) -> jnp.ndarray:
    """ASCII-lowercase fold on uint8 bytes (A-Z -> a-z; everything else —
    including the 0xFF padding and multibyte UTF-8 — unchanged).  Exact
    vs Python str.lower() for pure-ASCII values; rows containing bytes
    >= 0x80 are routed to host verification by the callers (Unicode case
    folding can map non-ASCII onto ASCII, e.g. U+212A -> 'k')."""
    return jnp.where((rows >= 0x41) & (rows <= 0x5A), rows + 0x20, rows)


@partial(jax.jit, static_argnames=("pat_len", "mode", "starts_tok",
                                   "ends_tok", "fold"))
def match_scan(rows: jnp.ndarray, lengths: jnp.ndarray,
               pattern: jnp.ndarray, pat_len: int, mode: int,
               starts_tok: bool, ends_tok: bool,
               fold: bool = False) -> jnp.ndarray:
    """Per-row match bitmap over a fixed-width staged string column.

    rows: uint8[R, W] — one value per row starting at column 0, tail-padded
          with 0xFF (which never occurs in UTF-8 data).  The fixed-width
          layout is the TPU-shaped choice: the per-row `any()` reduction is
          a pure axis reduction over (8,128) VPU tiles — no scatter/segment
          ops (~80ms/block serialized), no cumsum+gather (~210ms/batch of
          gathers) — both measured dead ends on real hardware.  Values
          longer than W-1 are truncated at staging and re-checked on the
          host (runner overflow path).
    lengths: int32[R] true value byte lengths
    pattern: uint8[pat_len]
    fold: ASCII-case-insensitive compare (pattern must arrive pre-lowered;
          the word-boundary table is case-agnostic so boundaries are
          computed on the folded bytes without semantic drift)
    returns bool[R]
    """
    if fold:
        rows = _fold_ascii(rows)
    r, w = rows.shape
    nwc = w - pat_len + 1  # window start columns

    # window equality: acc[:, i] = rows[:, i:i+pat_len] == pattern
    acc = jnp.ones((r, nwc), dtype=bool)
    for j in range(pat_len):
        acc = acc & (jax.lax.slice(rows, (0, j), (r, j + nwc))
                     == pattern[j])

    if mode in (MODE_EXACT, MODE_EXACT_PREFIX):
        hit = acc[:, 0]
        if mode == MODE_EXACT:
            return hit & (lengths == pat_len)
        return hit & (lengths >= pat_len)

    # word-boundary checks; rows start at col 0 (string start => boundary)
    # and padding bytes are 0xFF (non-word), so edges need no special data
    if starts_tok and mode in (MODE_PHRASE, MODE_PREFIX):
        prev = jax.lax.slice(rows, (0, 0), (r, nwc - 1))
        start_ok = jnp.concatenate(
            [jnp.ones((r, 1), dtype=bool), ~_is_word_u8(prev)], axis=1)
        acc = acc & start_ok
    if ends_tok and mode == MODE_PHRASE:
        nxt = jax.lax.slice(rows, (0, pat_len), (r, w))
        end_ok = jnp.concatenate(
            [~_is_word_u8(nxt), jnp.ones((r, 1), dtype=bool)], axis=1)
        acc = acc & end_ok

    return jnp.any(acc, axis=1) & (lengths >= pat_len)


@partial(jax.jit, static_argnames=("pat_len", "mode", "starts_tok",
                                   "ends_tok"))
def match_scan_batch(rows: jnp.ndarray, lengths: jnp.ndarray,
                     pattern: jnp.ndarray, pat_len: int,
                     mode: int, starts_tok: bool, ends_tok: bool
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched scan over B stacked blocks in ONE dispatch.

    rows: uint8[B, R, W]; lengths: int32[B, R].
    Every completed call pays a fixed dispatch round trip, so the
    runner amortizes by scanning many blocks per dispatch and downloading
    one (B, R) bitmap + counts.
    Returns (bool[B, R] bitmaps, int32[B] per-block match counts).
    """
    def one(rw, l):
        return match_scan(rw, l, pattern, pat_len, mode, starts_tok,
                          ends_tok)
    bms = jax.vmap(one)(rows, lengths)
    return bms, jnp.sum(bms.astype(jnp.int32), axis=1)


def _window_eq(rows: jnp.ndarray, pattern: jnp.ndarray, pat_len: int
               ) -> jnp.ndarray:
    """acc[:, i] = rows[:, i:i+pat_len] == pattern (bool[R, W-pat_len+1])."""
    r, w = rows.shape
    nwc = w - pat_len + 1
    acc = jnp.ones((r, nwc), dtype=bool)
    for j in range(pat_len):
        acc = acc & (jax.lax.slice(rows, (0, j), (r, j + nwc))
                     == pattern[j])
    return acc


@partial(jax.jit, static_argnames=("len_a", "len_b"))
def match_ordered_pair(rows: jnp.ndarray, lengths: jnp.ndarray,
                       pat_a: jnp.ndarray, len_a: int,
                       pat_b: jnp.ndarray, len_b: int):
    """Device decomposition of the `A.*B` regex family.

    A row matches /A.*B/ iff substring A ends at or before the LAST
    occurrence of B — computed from first-match(A) and last-match(B)
    positions, both pure argmax reductions over the window-equality matrix
    (no gather/scatter).  '.' does not cross newlines, so rows that contain
    a 0x0A byte are flagged for host re-verification instead of being
    decided on device.

    Returns (definite_match bool[R], needs_host_verify bool[R]).
    """
    acc_a = _window_eq(rows, pat_a, len_a)
    acc_b = _window_eq(rows, pat_b, len_b)
    any_a = jnp.any(acc_a, axis=1) & (lengths >= len_a)
    any_b = jnp.any(acc_b, axis=1) & (lengths >= len_b)
    first_a = jnp.argmax(acc_a, axis=1)
    last_b = (acc_b.shape[1] - 1) - jnp.argmax(acc_b[:, ::-1], axis=1)
    ordered = any_a & any_b & (first_a + len_a <= last_b)
    has_nl = jnp.any(rows == 0x0A, axis=1)
    return ordered & ~has_nl, ordered & has_nl


@partial(jax.jit, static_argnames=("pat_len", "mode", "starts_tok",
                                   "ends_tok", "fold"))
def match_scan_packed(rows: jnp.ndarray, lengths: jnp.ndarray,
                      pattern: jnp.ndarray, pat_len: int, mode: int,
                      starts_tok: bool, ends_tok: bool,
                      fold: bool = False) -> jnp.ndarray:
    """match_scan with the bitmap bit-packed ON DEVICE before download.

    Packed bits are 8x fewer bytes over the host link.  R is always a
    pad_bucket multiple, hence divisible by 8."""
    return jnp.packbits(match_scan(rows, lengths, pattern, pat_len, mode,
                                   starts_tok, ends_tok,
                                   fold).astype(jnp.uint8))


@partial(jax.jit, static_argnames=("len_a", "len_b"))
def match_ordered_pair_packed(rows: jnp.ndarray, lengths: jnp.ndarray,
                              pat_a: jnp.ndarray, len_a: int,
                              pat_b: jnp.ndarray, len_b: int) -> jnp.ndarray:
    """match_ordered_pair with BOTH result vectors packed into ONE
    download: uint8[2, R/8] — row 0 definite, row 1 needs-verify."""
    definite, needsv = match_ordered_pair(rows, lengths, pat_a, len_a,
                                          pat_b, len_b)
    return jnp.stack([jnp.packbits(definite.astype(jnp.uint8)),
                      jnp.packbits(needsv.astype(jnp.uint8))], axis=0)


# ---------------- bitmap combine (trivial but device-resident) ----------------

@jax.jit
def bitmap_and(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return a & b


@jax.jit
def bitmap_or(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return a | b


@jax.jit
def bitmap_not(a: jnp.ndarray) -> jnp.ndarray:
    return ~a


@jax.jit
def bitmap_count(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(a.astype(jnp.int32))


# ---------------- bucketed stats partials ----------------
#
# One-hot compare-and-reduce instead of segment_sum/min/max: scatter and
# segment ops serialize on this TPU (~80ms per 8MB block, measured round 1),
# while a (chunk, num_buckets) comparison matrix reduced along the row axis
# is pure VPU/MXU work.  The reduction runs as a lax.scan over fixed-size
# row chunks so peak memory stays bounded at any bucket count.  Sums are
# EXACT: the kernel reduces four uint8 byte-planes of the uint32 values
# (per-chunk plane sums < 2**24 stay exact in the f32 matmul; accumulation
# is uint32), and the host recombines planes with Python integers
# (tpu/stats_device.py).  This is the device half of the reference's stats
# partials contract (pipe_stats.go:354-377).

STATS_CHUNK = 8192  # rows per scan step; (chunk, buckets) tiles stay in VMEM


def stats_pad_rows(n: int) -> int:
    """Rows are staged padded to a STATS_CHUNK multiple (scan-friendly)."""
    return ((max(n, 1) + STATS_CHUNK - 1) // STATS_CHUNK) * STATS_CHUNK


def _vary(x, axes):
    """Mark a scan-carry constant as varying over shard_map manual axes
    (required so carry input/output types agree inside shard_map)."""
    if not axes:
        return x
    return jax.lax.pcast(x, axes, to="varying")


def stats_count_local(bucket_ids: jnp.ndarray, mask: jnp.ndarray,
                      num_buckets: int, vary_axes=()) -> jnp.ndarray:
    """Chunked masked-count body (also the per-shard body under
    shard_map — parallel/distributed.py reduces it with psum)."""
    b = bucket_ids.reshape(-1, STATS_CHUNK)
    m = mask.reshape(-1, STATS_CHUNK)
    buckets = jnp.arange(num_buckets, dtype=jnp.int32)

    def body(acc, xs):
        bi, mi = xs
        onehot = (bi[:, None] == buckets[None, :]) & mi[:, None]
        return acc + jnp.sum(onehot.astype(jnp.uint32), axis=0), None

    acc, _ = jax.lax.scan(
        body, _vary(jnp.zeros((num_buckets,), jnp.uint32), vary_axes),
        (b, m))
    return acc


def stats_values_local(values: jnp.ndarray, bucket_ids: jnp.ndarray,
                       mask: jnp.ndarray, num_buckets: int, vary_axes=()):
    """Chunked count/sum/min/max body; returns (cnt, sums[4,B], lo, hi)."""
    v = values.reshape(-1, STATS_CHUNK)
    b = bucket_ids.reshape(-1, STATS_CHUNK)
    m = mask.reshape(-1, STATS_CHUNK)
    buckets = jnp.arange(num_buckets, dtype=jnp.int32)
    u32max = jnp.uint32(0xFFFFFFFF)

    def body(carry, xs):
        cnt, sums, lo, hi = carry
        vi, bi, mi = xs
        onehot = (bi[:, None] == buckets[None, :]) & mi[:, None]
        cnt = cnt + jnp.sum(onehot.astype(jnp.uint32), axis=0)
        planes = jnp.stack(
            [((vi >> (8 * p)) & 0xFF).astype(jnp.float32)
             for p in range(4)], axis=1)                       # (C, 4)
        ps = jnp.einsum("cb,cp->pb", onehot.astype(jnp.float32),
                        planes)                                # exact < 2**24
        sums = sums + ps.astype(jnp.uint32)
        lo = jnp.minimum(lo, jnp.min(
            jnp.where(onehot, vi[:, None], u32max), axis=0))
        hi = jnp.maximum(hi, jnp.max(
            jnp.where(onehot, vi[:, None], jnp.uint32(0)), axis=0))
        return (cnt, sums, lo, hi), None

    init = tuple(
        _vary(a, vary_axes)
        for a in (jnp.zeros((num_buckets,), jnp.uint32),
                  jnp.zeros((4, num_buckets), jnp.uint32),
                  jnp.full((num_buckets,), u32max),
                  jnp.zeros((num_buckets,), jnp.uint32)))
    (cnt, sums, lo, hi), _ = jax.lax.scan(body, init, (v, b, m))
    return cnt, sums, lo, hi


def pack_stats(cnt, sums, lo, hi) -> jnp.ndarray:
    """One packed (7, B) result => ONE device->host download per dispatch
    (each download is its own round trip)."""
    return jnp.concatenate([cnt[None], sums, lo[None], hi[None]], axis=0)


def combine_ids(ids_tuple, strides):
    """Row-major combined bucket index from per-axis id arrays
    (time buckets x group-by dict codes x quantile histograms); computed
    INSIDE the jit so multi-axis grouping costs no extra dispatch.
    Axes arrive as int32 (dict/time codes) or uint32 (quantile axes
    reusing the value staging) — cast unifies them."""
    c = None
    for a, s in zip(ids_tuple, strides):
        t = a.astype(jnp.int32)
        if s != 1:
            t = t * jnp.int32(s)
        c = t if c is None else c + t
    return c


def pad_bucket(n: int, minimum: int = 8192) -> int:
    """Pad sizes to coarse buckets so jit caches stay small."""
    b = minimum
    while b < n:
        b *= 2
    # refine with quarter steps of the previous power to cut waste
    for frac in (b // 2 + b // 8, b // 2 + b // 4, b // 2 + 3 * b // 8,
                 b // 2 + b // 2):
        if n <= frac:
            return frac
    return b
