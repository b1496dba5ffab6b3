"""The device string scan: one sweep over a staged column's planes.

Semantics are bit-identical to logsql.matchers and to kernels.match_scan
/ match_ordered_pair (the u8 kernels, which stay as the oracle of
tests/test_kernels32.py): word and phrase match at filter_phrase.go:61-111,
filter_exact.go, filter_prefix.go, the tokenizer's word table at
tokenizer.go:34-148.  Answers are exact; nothing is sampled or skipped.

Layout contract (tpu/layout.py to_lanes32).  A staged column is
uint32[W/4, R/128, 128]: planes[q, r // 128, r % 128] is the
little-endian word of bytes 4q..4q+3 of row r.  Plane q, word q of every
row, is one (R/128, 128) array whose rows fill the sublanes AND the
lanes of the vector registers; q is a leading, untiled index, so reading
a plane is an address and never a relayout.  W is a multiple of 4, R of
1024 (whole (8, 128) tiles a plane); the mesh stripes axis 1.  Tail
padding is 0xFF: never valid UTF-8 and not a word char, so a window
that runs into it cannot match; a value is cut at W-1 bytes (longer
ones are re-checked on the host), so the last byte of a row is padding.
Patterns hold no 0xFF byte (the planner takes ASCII only).  Pattern
chunk words are built with the SAME in-trace bitcast as the data, so
their byte order agrees on any backend; the byte shifts assume a
little-endian target (x86-64 and the TPU are; a test asserts it).

The sweep.  A window at byte 4q+a matches iff its ceil(pat_len/4) chunk
words equal the words at alignment a of planes q, q+1, ...; the word at
alignment a of plane q is (p[q] >> 8a) | (p[q+1] << (32-8a)), plane to
plane.  One loop over q reads each plane ONCE, derives its aligned words
and its word-char mask (SWAR: four bytes a lane-op) once, carries them
for the few steps that use them, and keeps per-row state only: one hit
word (phrase, prefix, substring, folded or not), or the first A as a
running minimum and the last B as a running maximum plus the newline OR
(`A.*B`).  No [planes, rows] intermediate exists and nothing is written
but the per-row result.  MODE_EXACT / MODE_EXACT_PREFIX look at window 0
only.

A sweep stops at its tile's longest row (sweep_steps).  Every byte of a
row past its length is padding, which neither matches nor holds a
newline, so a tile whose rows are all shorter than the staged width
reads no plane that only padding fills: a window of a pat_len-byte scan
at byte 4q+a needs 4q+a+pat_len <= the tile's longest length, and the
pair's windows and newlines lie below it.  The Pallas launcher takes
each tile's longest length from the lengths inside the program and hands
the kernel one SMEM table block a grid step; the direct launcher sweeps
the column's whole width (it is the oracle of the bounded sweep).

Two launchers, one body (_launch).  On the TPU a Pallas call tiles the
rows through VMEM blocks and runs the body a register tile at a time; on
every other backend, and for a stripe under a mesh axis, the body runs
directly as jax.numpy over the whole column.  The choice follows what
the code can observe, never an environment switch.

Measured on a TPU v5e, R = 2M, W = 128, rows of at most 100 B
(tools/bench_kernels32.py), ns a row and share of 819 GB/s: phrase
(17 B, both token boundaries) 0.63 / 26%, prefix 0.37 / 44%, substring
0.31 / 52%, folded phrase 0.76 / 21%, `A.*B` pair 0.33 / 49%; swept over
the whole width the same kinds read 0.80, 0.44, 0.37, 0.97 and 0.40.
Every kind is bound by its lane-ops, not by memory: a plain read of the
column takes 0.21 ns a row.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .kernels import (MODE_EXACT, MODE_EXACT_PREFIX, MODE_PHRASE,
                      MODE_PREFIX, MODE_SUBSTRING)

_U32 = jnp.uint32


def _c(v: int) -> np.uint32:
    """A u32 constant of the trace: a numpy scalar, which costs the
    tracer nothing (a jnp scalar is a placed array each time)."""
    return np.uint32(v & 0xFFFFFFFF)


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
    """(chunks u32[nc], static mask ints): chunk c covers pattern bytes
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
    return pc, masks


# ---------------- planes ----------------
#
# A plane is word q of every row of a tile, a (rows/128, 128) array: the
# rows fill the sublanes and the lanes of the vector registers, and q
# indexes the column's leading, untiled axis, so reading a plane is an
# address and never a relayout.  A scan is ONE sweep over the planes
# that keeps only per-row state: the aligned words and word masks of
# the few planes a window spans, and what the question asks of each row.

_PAD = 0xFFFFFFFF


def _aligned4(p, nxt):
    """The u32 at byte offsets 0..3 of plane p: the little-endian
    combine with the next plane, plane to plane, never stored."""
    return (p,) + tuple((p >> _c(8 * a)) | (nxt << _c(32 - 8 * a))
                        for a in (1, 2, 3))


def _window_eq(al, a: int, pc, c0: int, masks):
    """Whether the window at byte a of the sweep's plane equals the
    pattern: its chunk c, pc[c0 + c], against al[c][a], the aligned
    word c planes on."""
    acc = None
    for c, m in enumerate(masks):
        # a short last chunk: its pattern word is zero past pat_len
        t = (al[c][a] == pc[c0 + c]) if m == _PAD else \
            ((al[c][a] & _c(m)) == pc[c0 + c])
        acc = t if acc is None else acc & t
    return acc


def sweep_steps(tile_max, nl: int, pat_len: int | None = None):
    """The steps of a sweep over a tile of rows whose longest row is
    `tile_max` bytes; with tile_max None, the column's whole width.

    A scan of pat_len bytes: a window at byte 4q+a can match only if
    4q+a+pat_len <= tile_max, so q runs to (tile_max - pat_len) // 4, and
    never past nl - ceil(pat_len/4), the last plane a window starts in
    and lies inside the column.  The `A.*B` pair (pat_len None): no byte
    of a row, so no window start and no newline, lies at or past
    tile_max, so q runs to ceil(tile_max / 4) - 1, and never past nl - 1.
    tile_max is the kernel's traced int32 or the host's numpy array (its
    count of the steps swept and skipped): one arithmetic for both."""
    full = nl if pat_len is None else nl - (pat_len + 3) // 4 + 1
    if tile_max is None:
        return full
    xp = jnp if isinstance(tile_max, jax.Array) else np
    if pat_len is None:
        return xp.minimum((tile_max + 3) >> 2, full)
    return xp.minimum(xp.maximum(tile_max - pat_len + 4, 0) >> 2, full)


def _sweep(load, nl: int, look: int, steps, fold: bool, words: bool,
           visit, state):
    """state = visit(q, al, wm, state) for q = 0 .. steps-1, in order;
    steps a static int or a traced int32 (sweep_steps).

    al[c] = _aligned4 of plane q+c for c < look (the planes a window of
    `look` chunks spans); wm[i] = the word mask of plane q-1+i for
    i < look+2 (None unless `words`).  load(q) reads plane q of the
    tile (q an int or a traced index).  Planes outside the column are
    0xFF padding (not a word char, never equal to a pattern byte), so
    the byte before a string and the bytes past its width need no
    special case.  The sweep rolls: a step reads ONE new plane, q+look,
    and derives its aligned words and word mask once; the loop carries
    them for the `look` steps that use them.  Needs look <= nl."""
    def plane(q):
        if isinstance(q, int):
            p = load(q)
        else:
            # only the sweep's last steps reach past the column
            p = load(jnp.minimum(q, nl - 1)) \
                | jnp.where(q < nl, _c(0), _c(_PAD))
        return fold_ascii32(p) if fold else p

    raw = [plane(q) for q in range(look)]
    al = [_aligned4(raw[c], raw[c + 1]) for c in range(look - 1)]
    # before the string: the rows' shape (and sharding), no word char
    wm = [raw[0] & _c(0)] + [word_hibits(p) for p in raw] if words else None

    def step(q, carry):
        last, al, wm, state = carry
        new = plane(q + look)
        al = al + [_aligned4(last, new)]
        if words:
            wm = wm + [word_hibits(new)]
        state = visit(q, al, wm, state)
        return new, al[1:], wm[1:] if words else None, state

    return jax.lax.fori_loop(0, steps, step,
                             (raw[-1], al, wm, state))[3]


# ---------------- the two bodies: one tile of rows ----------------

def _scan_rows(load, nl: int, lens, pc, masks, pat_len: int, mode: int,
               need_start: bool, need_end: bool, fold: bool, tile_max):
    """match_scan_t over one tile of rows: load(q) is word q of each,
    lens their lengths, pc the pattern's chunk words (indexable),
    tile_max the longest of lens (None: sweep the whole width)."""
    nc = len(masks)
    if mode in (MODE_EXACT, MODE_EXACT_PREFIX):
        # window 0 only: the first chunks of each row
        al = [(fold_ascii32(load(c)) if fold else load(c),)
              for c in range(nc)]
        hit = _window_eq(al, 0, pc, 0, masks)
        return hit & ((lens == pat_len) if mode == MODE_EXACT
                      else (lens >= pat_len))

    m, r = divmod(pat_len, 4)

    def visit(q, al, wm, hit):
        """OR the windows that start in plane q into one word a row."""
        # hi bit of byte a set: the window at byte a has a word char
        # right before it (byte a-1 of plane q, byte 3 of plane q-1) or
        # right after it (byte a+r of plane q+m, running into q+m+1)
        edge = None
        if need_start:
            edge = (wm[1] << _c(8)) | (wm[0] >> _c(24))
        if need_end:
            nxt = wm[m + 1]
            if r:
                nxt = (nxt >> _c(8 * r)) | (wm[m + 2] << _c(32 - 8 * r))
            edge = nxt if edge is None else edge | nxt
        any_a = None
        for a in range(4):
            acc = _window_eq(al, a, pc, 0, masks)
            if edge is not None:
                acc = acc & ((edge & _c(0x80 << (8 * a))) == 0)
            any_a = acc if any_a is None else any_a | acc
        # a word a row: Mosaic loops carry no booleans
        return hit | any_a.astype(jnp.int32)

    hit = _sweep(load, nl, nc, sweep_steps(tile_max, nl, pat_len), fold,
                 need_start or need_end, visit, lens & 0)
    return (hit != 0) & (lens >= pat_len)


def _pair_rows(load, nl: int, lens, pc, masks_a, masks_b, len_a: int,
               len_b: int, tile_max):
    """match_ordered_pair_t over one tile of rows: the first window
    that equals A as a running minimum, the last that equals B as a
    running maximum (byte offsets), the newline bytes OR-ed.  pc holds
    A's chunk words, then B's; tile_max as for _scan_rows."""
    nowhere = np.int32(4 * nl)         # past every window start
    nca, ncb = len(masks_a), len(masks_b)

    def visit(q, al, _wm, state):
        first_a, last_b, newline = state
        newline = newline | any_byte_eq(al[0][0], 0x0A)
        for a in range(4):
            pos = 4 * q + np.int32(a)
            first_a = jnp.minimum(first_a, jnp.where(
                _window_eq(al, a, pc, 0, masks_a), pos, nowhere))
            # offsets ascend: the last hit is the latest write
            last_b = jnp.where(_window_eq(al, a, pc, nca, masks_b), pos,
                               last_b)
        return first_a, last_b, newline

    # sweeps every plane a row's byte may sit in (a newline may sit in
    # the last); windows that run past the column compare against the
    # padding
    zero = lens & 0
    first_a, last_b, newline = _sweep(
        load, nl, max(nca, ncb), sweep_steps(tile_max, nl), False, False,
        visit,
        (zero + nowhere, zero - 1, zero.astype(_U32)))
    ordered = (first_a < nowhere) & (last_b >= 0) \
        & (lens >= max(len_a, len_b)) & (first_a + len_a <= last_b)
    has_nl = newline != 0
    return ordered & ~has_nl, ordered & has_nl


# ---------------- the two launchers ----------------

_SUBLANES = 8               # rows of 128 in one vector register
# rows of 128 a sweep holds a value of: two registers a value measured
# best on the v5e for every kind but the plain substring (PERF.md §6)
_SWEEP_ROWS = 2 * _SUBLANES
_BLOCK_BYTES = 2 << 20      # of a column, in VMEM at a time


def sweep_blocks(nl: int, s: int) -> tuple[int, int]:
    """(ts, sub): the Pallas launcher's block of ts rows of 128 (a grid
    step), swept sub rows of 128 at a time (a sweep tile), for a column
    of nl planes and s rows of 128."""
    ts = next(t for t in (256, 128, 64, 32, 16, _SUBLANES)
              if s % t == 0 and (t == _SUBLANES
                                 or nl * t * 128 * 4 <= _BLOCK_BYTES))
    return ts, min(ts, _SWEEP_ROWS)


def on_tpu() -> bool:
    """Whether this process's columns run the Pallas launcher (and so
    the bounded sweep) when not striped under a mesh axis."""
    return jax.default_backend() == "tpu"


def _on_tpu_alone(lanes_t) -> bool:
    """Whether the Pallas launcher serves this column: the TPU backend,
    whole register tiles of rows, and not a stripe under a mesh axis
    (there XLA's own partitioning runs the body)."""
    return (on_tpu()
            and not jax.typeof(lanes_t).vma
            and lanes_t.shape[1] % _SUBLANES == 0)


def _launch(body, nout: int, lanes_t, lengths, pc):
    """body(load, lens, pc, tile_max) -> nout bool arrays over a tile of
    rows; returns them over every row, bool[R] each.  On the TPU the
    tiles are register-sized slices of VMEM blocks (Pallas), each swept
    to its longest row; elsewhere the tile is the column, swept over its
    whole width (tile_max None)."""
    lens = lengths.reshape(lanes_t.shape[1:])
    if not _on_tpu_alone(lanes_t):
        out = body(lambda q: jax.lax.dynamic_index_in_dim(
            lanes_t, q, 0, keepdims=False), lens, pc, None)
    else:
        code = _launch_pallas(body, lanes_t, lens, pc)
        out = [(code >> i) & 1 != 0 for i in range(nout)]
    return [o.reshape(-1) for o in out]


def _launch_pallas(body, lanes_t, lens, pc, interpret: bool = False):
    """The body over (W/4, TS, 128) blocks of rows, _SWEEP_ROWS x 128
    rows at a time; int32[R/128, 128], bit i = the body's i-th
    result.  Each sweep tile's longest length is reduced here from
    lens, in the program, and reaches the kernel as an int32 SMEM table
    blocked by grid step: ts // sub words a step, whatever R is."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    nl, s, lanes = lanes_t.shape
    ts, sub = sweep_blocks(nl, s)
    tile_max = lens.reshape(s // sub, sub * lanes).max(axis=1) \
        .reshape(s // ts, 1, ts // sub)

    def kernel(pc_ref, lanes_ref, lens_ref, tmax_ref, out_ref):
        def tile(t, carry):
            rows = pl.ds(pl.multiple_of(t * sub, sub), sub)
            out = body(lambda q: lanes_ref[q, rows, :],
                       lens_ref[rows, :], pc_ref, tmax_ref[0, t])
            out_ref[rows, :] = sum(o.astype(jnp.int32) << i
                                   for i, o in enumerate(out))
            return carry
        jax.lax.fori_loop(0, ts // sub, tile, 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s, lanes), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s // ts,),
            in_specs=[pl.BlockSpec((nl, ts, lanes),
                                   lambda i, pc: (0, i, 0)),
                      pl.BlockSpec((ts, lanes), lambda i, pc: (i, 0)),
                      pl.BlockSpec((None, 1, ts // sub),
                                   lambda i, pc: (i, 0, 0),
                                   memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec((ts, lanes), lambda i, pc: (i, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret)(pc, lanes_t, lens, tile_max)


# ---------------- the scan ----------------

@partial(jax.jit, static_argnames=("pat_len", "mode", "starts_tok",
                                   "ends_tok", "fold"))
@jax.named_scope("match_scan")
def match_scan_t(lanes_t: jnp.ndarray, lengths: jnp.ndarray,
                 pattern: jnp.ndarray, pat_len: int, mode: int,
                 starts_tok: bool, ends_tok: bool,
                 fold: bool = False) -> jnp.ndarray:
    """Per-row match bitmap over a staged string column.

    lanes_t: uint32[W/4, R/128, 128] planes (layout.to_lanes32);
    lengths: int32[R] true byte lengths (truncated at W-1; overflow
    rows re-checked on host); pattern: uint8[pat_len], pre-lowered when
    fold=True.  Semantics identical to kernels.match_scan (the oracle);
    returns bool[R].
    """
    nl = lanes_t.shape[0]
    if pat_len > 4 * nl:
        # no window fits the column's width
        return lengths < 0
    pc, masks = _pattern_chunks(pattern, pat_len)
    need_start = starts_tok and mode in (MODE_PHRASE, MODE_PREFIX)
    need_end = ends_tok and mode == MODE_PHRASE

    def body(load, lens, pc, tile_max):
        return [_scan_rows(load, nl, lens, pc, masks, pat_len, mode,
                           need_start, need_end, fold, tile_max)]

    return _launch(body, 1, lanes_t, lengths, pc)[0]


# ---------------- the ordered pair ----------------

@partial(jax.jit, static_argnames=("len_a", "len_b"))
@jax.named_scope("match_pair")
def match_ordered_pair_t(lanes_t: jnp.ndarray, lengths: jnp.ndarray,
                         pat_a: jnp.ndarray, len_a: int,
                         pat_b: jnp.ndarray, len_b: int):
    """`A.*B` decomposition over the planes: matches iff the FIRST
    occurrence of A ends at or before the LAST occurrence of B.
    Rows containing a newline go to the needs-verify channel ('.' does
    not cross newlines).  Returns (definite bool[R], needs_verify
    bool[R]) — semantics identical to kernels.match_ordered_pair."""
    nl = lanes_t.shape[0]
    if max(len_a, len_b) > 4 * nl:
        # no window fits the column's width
        return lengths < 0, lengths < 0
    pc_a, masks_a = _pattern_chunks(pat_a, len_a)
    pc_b, masks_b = _pattern_chunks(pat_b, len_b)

    def body(load, lens, pc, tile_max):
        return _pair_rows(load, nl, lens, pc, masks_a, masks_b, len_a,
                          len_b, tile_max)

    return tuple(_launch(body, 2, lanes_t, lengths,
                         jnp.concatenate([pc_a, pc_b])))
