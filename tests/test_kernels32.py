"""Parity: the plane kernels (tpu/kernels32.py) vs the round-3 byte
kernels (tpu/kernels.py), which are themselves bit-exact vs the scalar
matchers (test_tpu_runner.py).  Any drift here breaks "identical hit
sets".  The second half pins the edges the sweep over planes creates:
every pattern length modulo 4, windows that end in or straddle the
last plane, every staged width, the first and last row of a tile, and
the ordered pair's first-A / last-B corner cases; and the Pallas
launcher against the same body in interpret mode."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from victorialogs_tpu.tpu import kernels as K
from victorialogs_tpu.tpu import kernels32 as K32
from victorialogs_tpu.tpu.layout import to_fixed_width, to_lanes32

MODES = [K.MODE_PHRASE, K.MODE_PREFIX, K.MODE_SUBSTRING, K.MODE_EXACT,
         K.MODE_EXACT_PREFIX]


def test_bitcast_little_endian():
    """The lane-combine shifts in kernels32 assume a little-endian
    backend; assert the XLA bitcast agrees with the numpy '<u4' view
    used by layout.to_lanes32."""
    x = jnp.array([[1, 2, 3, 4]], dtype=jnp.uint8)
    v = int(np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint32))[0])
    assert v == 0x04030201


def _stage(values: list[bytes], width: int | None = None):
    arena = np.frombuffer(b"".join(values), dtype=np.uint8)
    lengths = np.array([len(v) for v in values], dtype=np.int64)
    offsets = np.zeros(len(values), dtype=np.int64)
    if len(values):
        offsets[1:] = np.cumsum(lengths)[:-1]
    rb = max(128, (len(values) + 127) // 128 * 128)
    mat, w, _ovf = to_fixed_width(arena, offsets, lengths, rb, width=width)
    lens = np.zeros(rb, dtype=np.int32)
    lens[:len(values)] = np.minimum(lengths, w - 1)
    return mat, lens, w


def _rand_value(rng: random.Random) -> bytes:
    words = ["alpha", "beta", "err", "GET", "x", "_u", "123", "a1b2",
             "日本", "é", "\xff".encode("latin-1").decode("latin-1")]
    kind = rng.random()
    if kind < 0.05:
        return b""
    if kind < 0.15:  # binary-ish (but no 0xFF: staging reserves it)
        return bytes(rng.randrange(0, 255) for _ in range(rng.randrange(1, 40)))
    n = rng.randrange(1, 9)
    sep = rng.choice([" ", "", "/", "=", "-", ":", "\n"])
    return sep.join(rng.choice(words) for _ in range(n)).encode()


def _rand_pattern(rng: random.Random, values: list[bytes]) -> bytes:
    if values and rng.random() < 0.6:
        v = rng.choice([v for v in values if v] or [b"x"])
        if len(v) == 0:
            return b"x"
        i = rng.randrange(len(v))
        j = min(len(v), i + rng.randrange(1, 20))
        p = v[i:j]
        if p:
            return p
    n = rng.randrange(1, 18)
    return bytes(rng.randrange(1, 128) for _ in range(n))


@pytest.mark.parametrize("seed", range(6))
def test_match_scan_parity_random(seed):
    rng = random.Random(seed)
    values = [_rand_value(rng) for _ in range(rng.randrange(1, 300))]
    mat, lens, w = _stage(values)
    lanes = to_lanes32(mat)
    for _ in range(25):
        pat = _rand_pattern(rng, values)
        if len(pat) > w - 1:
            pat = pat[:w - 1]
        if not pat:
            continue
        mode = rng.choice(MODES)
        st, et = rng.random() < 0.5, rng.random() < 0.5
        fold = rng.random() < 0.3
        if fold:
            pat = pat.lower()
        pj = jnp.asarray(np.frombuffer(pat, dtype=np.uint8))
        want = np.asarray(K.match_scan(
            jnp.asarray(mat), jnp.asarray(lens), pj, len(pat), mode,
            st, et, fold))
        got = np.asarray(K32.match_scan_t(
            jnp.asarray(lanes), jnp.asarray(lens), pj, len(pat), mode,
            st, et, fold))
        if not np.array_equal(want, got):
            bad = np.nonzero(want != got)[0]
            raise AssertionError(
                f"mode={mode} st={st} et={et} fold={fold} pat={pat!r} "
                f"rows={bad[:5]} vals="
                f"{[values[i] if i < len(values) else None for i in bad[:5]]}")


def test_match_scan_boundaries_exhaustive():
    """Hand-picked boundary shapes: word edges, pattern at row start/end,
    pattern == value, pattern crossing the truncation width."""
    values = [b"error", b"xerror", b"error7", b"an error here",
              b"error_code", b"err", b"", b" error ", b"ERROR",
              b"e", b"errorerror", b"-error-", b"a" * 40,
              ("日本語 error 日本語").encode(), b"error\nerror"]
    mat, lens, w = _stage(values, width=32)  # force truncation of a*40
    lanes = to_lanes32(mat)
    for pat in [b"error", b"err", b"e", b"error here", b" ", b"a" * 31]:
        for mode in MODES:
            for st in (False, True):
                for et in (False, True):
                    pj = jnp.asarray(np.frombuffer(pat, dtype=np.uint8))
                    want = np.asarray(K.match_scan(
                        jnp.asarray(mat), jnp.asarray(lens), pj,
                        len(pat), mode, st, et))
                    got = np.asarray(K32.match_scan_t(
                        jnp.asarray(lanes), jnp.asarray(lens), pj,
                        len(pat), mode, st, et))
                    assert np.array_equal(want, got), (pat, mode, st, et)


@pytest.mark.parametrize("seed", range(4))
def test_ordered_pair_parity(seed):
    rng = random.Random(1000 + seed)
    values = [_rand_value(rng) for _ in range(rng.randrange(1, 200))]
    mat, lens, w = _stage(values)
    lanes = to_lanes32(mat)
    for _ in range(15):
        pa = _rand_pattern(rng, values)[:8] or b"a"
        pb = _rand_pattern(rng, values)[:8] or b"b"
        wd, wv = K.match_ordered_pair(
            jnp.asarray(mat), jnp.asarray(lens),
            jnp.asarray(np.frombuffer(pa, dtype=np.uint8)), len(pa),
            jnp.asarray(np.frombuffer(pb, dtype=np.uint8)), len(pb))
        gd, gv = K32.match_ordered_pair_t(
            jnp.asarray(lanes), jnp.asarray(lens),
            jnp.asarray(np.frombuffer(pa, dtype=np.uint8)), len(pa),
            jnp.asarray(np.frombuffer(pb, dtype=np.uint8)), len(pb))
        assert np.array_equal(np.asarray(wd), np.asarray(gd)), (pa, pb)
        assert np.array_equal(np.asarray(wv), np.asarray(gv)), (pa, pb)


def test_scan_packs_like_the_oracle():
    values = [b"hello world", b"goodbye", b"hello", b""] * 4
    mat, lens, w = _stage(values)
    lanes = to_lanes32(mat)
    pat = jnp.asarray(np.frombuffer(b"hello", dtype=np.uint8))
    want = np.asarray(K.match_scan_packed(
        jnp.asarray(mat), jnp.asarray(lens), pat, 5, K.MODE_PHRASE,
        True, True))
    got = np.packbits(np.asarray(K32.match_scan_t(
        jnp.asarray(lanes), jnp.asarray(lens), pat, 5, K.MODE_PHRASE,
        True, True)))
    assert np.array_equal(want, got)


def test_swar_word_hibits_exhaustive():
    """Every byte value 0..255 through the SWAR word-char test vs the
    byte-plane oracle."""
    b = np.arange(256, dtype=np.uint8)
    mat = b.reshape(64, 4)
    lanes = jnp.asarray(np.ascontiguousarray(mat.view("<u4")[:, 0]))
    hi = np.asarray(K32.word_hibits(lanes))
    got = np.zeros(256, dtype=bool)
    for i in range(64):
        for k in range(4):
            got[4 * i + k] = bool((int(hi[i]) >> (8 * k + 7)) & 1)
    want = np.asarray(K._is_word_u8(jnp.asarray(b)))
    assert np.array_equal(want, got)


def test_swar_fold_exhaustive():
    b = np.arange(256, dtype=np.uint8)
    mat = b.reshape(64, 4)
    lanes = jnp.asarray(np.ascontiguousarray(mat.view("<u4")[:, 0]))
    folded = np.asarray(K32.fold_ascii32(lanes))
    got = folded.view(np.uint32).astype("<u4").tobytes()
    want = np.asarray(K._fold_ascii(jnp.asarray(b))).tobytes()
    assert got == want


# ---------------- the edges of the sweep over planes ----------------

def _matrix(values: list[bytes], width: int, rows: int | None = None):
    """Stage by hand: (uint8[R, W] 0xFF-padded, int32[R] lengths), R a
    multiple of 128 (a value never reaches the last byte of its row)."""
    rows = rows or -(-len(values) // 128) * 128
    mat = np.full((rows, width), 0xFF, dtype=np.uint8)
    lens = np.zeros(rows, dtype=np.int32)
    for i, v in enumerate(values):
        v = v[:width - 1]
        mat[i, :len(v)] = np.frombuffer(v, dtype=np.uint8)
        lens[i] = len(v)
    return mat, lens


def _u8(b: bytes):
    return jnp.asarray(np.frombuffer(b, dtype=np.uint8))


def _assert_scan_parity(mat, lens, pat: bytes, mode, st, et, fold=False):
    want = np.asarray(K.match_scan(jnp.asarray(mat), jnp.asarray(lens),
                                   _u8(pat), len(pat), mode, st, et, fold))
    got = np.asarray(K32.match_scan_t(
        jnp.asarray(to_lanes32(mat)), jnp.asarray(lens), _u8(pat),
        len(pat), mode, st, et, fold))
    bad = np.nonzero(want != got)[0]
    assert not bad.size, (pat, mode, st, et, fold, bad[:8],
                          [bytes(mat[i][:lens[i]]) for i in bad[:4]])
    return want


def _edge_values(pat: bytes, width: int) -> list[bytes]:
    """Rows that place `pat` at every byte alignment, in the last
    window that fits, across the last plane's edge, next to word and
    non-word bytes, and one byte off in every chunk."""
    room = width - 1
    vals = []
    for s in range(0, 9):                      # every alignment, twice
        vals.append(b"." * s + pat + b" tail")
        vals.append(b"x" * s + pat + b"y")     # word chars on both sides
        vals.append(b"x" * s + b" " + pat)     # boundary before, end after
    last = room - len(pat)                     # the last window that fits
    for s in (last, last - 1, last - 2, last - 3, last - 4):
        if s >= 0:
            vals.append(b"-" * s + pat)
            vals.append(b"a" * s + pat)
    # straddling the edge of the last plane, then cut by the width
    for s in range(max(0, width - 4 - len(pat) + 1), width - 4 + 1):
        vals.append(b"=" * s + pat + b"=")
    for k in range(len(pat)):                  # one byte off
        near = bytearray(pat)
        near[k] ^= 0x01
        vals.append(b"  " + bytes(near) + b"  ")
    vals += [pat, pat[:-1], pat + pat, b"", b" " + pat + b" " + pat + b"z"]
    return vals


@pytest.mark.parametrize("pat_len", range(1, 21))
def test_scan_every_pattern_length(pat_len):
    """pat_len 1..20: every length modulo 4, one to five chunks."""
    pat = b"deadline_exceeded_07"[:pat_len]
    mat, lens = _matrix(_edge_values(pat, 64), 64)
    hits = 0
    for mode, st, et in [(K.MODE_PHRASE, True, True),
                         (K.MODE_SUBSTRING, False, False)]:
        hits += int(_assert_scan_parity(mat, lens, pat, mode, st, et).sum())
    assert hits


@pytest.mark.parametrize("width", [16, 64, 128, 256])
def test_scan_every_width(width):
    """Four planes (one sweep step a window) up to 64, every mode,
    folded or not; the hit in the last window that fits is found."""
    for pat in (b"err", b"Deadline", b"a1_b2/c3=d4:"):
        if len(pat) > width - 1:
            continue
        vals = _edge_values(pat, width) + _edge_values(pat.lower(), width)
        mat, lens = _matrix(vals, width)
        for mode in MODES:
            for st, et, fold in [(True, True, False), (False, True, True),
                                 (True, False, False)]:
                p = pat.lower() if fold else pat
                _assert_scan_parity(mat, lens, p, mode, st, et, fold)
        last = vals.index(b"-" * (width - 1 - len(pat)) + pat)
        assert _assert_scan_parity(mat, lens, pat, K.MODE_SUBSTRING, False,
                                   False)[last]


@pytest.mark.parametrize("k", [1, 2])
def test_scan_first_and_last_row_of_a_tile(k):
    """R = 8192 k: hits in the first and last row of every 1024-row
    tile (a register tile's corners) and of every 128-row plane row."""
    rows = 8192 * k
    pat = b"needle"
    mat, lens = _matrix([], 32, rows=rows)
    edges = sorted({r for t in range(0, rows, 1024)
                    for r in (t, t + 127, t + 128, t + 1023)})
    for i, r in enumerate(edges):
        v = b" " * (i % 7) + pat
        mat[r, :len(v)] = np.frombuffer(v, dtype=np.uint8)
        lens[r] = len(v)
    want = _assert_scan_parity(mat, lens, pat, K.MODE_PHRASE, True, True)
    assert np.array_equal(np.nonzero(want)[0], edges)
    gd, gv = K32.match_ordered_pair_t(
        jnp.asarray(to_lanes32(mat)), jnp.asarray(lens), _u8(b"nee"), 3,
        _u8(b"dle"), 3)
    assert np.array_equal(np.nonzero(np.asarray(gd))[0], edges)
    assert not np.asarray(gv).any()


PAIRS = [(b"a", b"b"), (b"ab", b"ab"), (b"GET", b"late"),
         (b"dead", b"exceeded"), (b"abcde", b"xy"), (b"0123456789", b"ab")]


@pytest.mark.parametrize("pa,pb", PAIRS)
def test_ordered_pair_edges(pa, pb):
    """First A and last B touching, overlapping, in different byte
    alignments, in the wrong order, repeated, in the last window, and
    with a newline anywhere in the row."""
    width = 64
    room = width - 1
    vals = []
    for s in range(0, 6):
        lead = b"." * s
        vals += [lead + pa + pb,                         # touch
                 lead + pa + b"-" + pb,                  # different alignment
                 lead + pa + b"--" + pb, lead + pa + b"---" + pb,
                 lead + pb + pa,                         # wrong order
                 lead + pb + pa + pb,                    # B, A, B
                 lead + pa + pb + pa,                    # A, B, A
                 lead + pa[:-1] + pb,                    # A cut short
                 lead + pa + b"\n" + pb,                 # newline between
                 b"\n" + lead + pa + pb,                 # newline before
                 lead + pa + pb + b"\n"]                 # newline after
    # overlap: B starts inside A (matches only when pb sits in pa's tail
    # AND again after it)
    for cut in range(1, len(pa)):
        vals.append(b"::" + pa[:cut] + pb)
        vals.append(b"::" + pa + pb[len(pa) - cut:])
    fill = room - len(pa) - len(pb)
    vals += [b"=" * fill + pa + pb,                      # B in the last window
             pa + b"=" * fill + pb,
             b"=" * (room - len(pa)) + pa,               # A in the last window
             pb + b"=" * (room - len(pa) - len(pb)) + pa,
             pa, pb, b"", b"\n", pa + b"\n"]
    mat, lens = _matrix(vals, width)
    wd, wv = K.match_ordered_pair(jnp.asarray(mat), jnp.asarray(lens),
                                  _u8(pa), len(pa), _u8(pb), len(pb))
    gd, gv = K32.match_ordered_pair_t(
        jnp.asarray(to_lanes32(mat)), jnp.asarray(lens), _u8(pa), len(pa),
        _u8(pb), len(pb))
    for want, got in ((wd, gd), (wv, gv)):
        bad = np.nonzero(np.asarray(want) != np.asarray(got))[0]
        assert not bad.size, (pa, pb, [vals[i] for i in bad[:4]
                                       if i < len(vals)])
    assert np.asarray(wd).any() and np.asarray(wv).any()


def test_pattern_wider_than_the_column():
    """No window fits: nothing matches, and nothing is traced."""
    mat, lens = _matrix([b"0123456789abcde"], 16)
    lanes = jnp.asarray(to_lanes32(mat))
    pat = b"0123456789abcdefg"
    assert not np.asarray(K32.match_scan_t(
        lanes, jnp.asarray(lens), _u8(pat), len(pat), K.MODE_SUBSTRING,
        False, False)).any()
    gd, gv = K32.match_ordered_pair_t(lanes, jnp.asarray(lens), _u8(b"0"),
                                      1, _u8(pat), len(pat))
    assert not np.asarray(gd).any() and not np.asarray(gv).any()


def test_to_lanes32_layout_contract():
    """planes[q, r // 128, r % 128] is the little-endian word of bytes
    mat[r, 4q:4q+4]."""
    rng = np.random.default_rng(5)
    mat = rng.integers(0, 256, size=(256, 16), dtype=np.uint8)
    planes = to_lanes32(mat)
    assert planes.shape == (4, 2, 128) and planes.dtype == np.uint32
    for r, q in [(0, 0), (127, 3), (128, 1), (255, 2)]:
        b = mat[r, 4 * q:4 * q + 4].astype(np.uint32)
        assert planes[q, r // 128, r % 128] == \
            b[0] | b[1] << 8 | b[2] << 16 | b[3] << 24


# ---------------- the Pallas launcher, interpreted ----------------
#
# The launcher sweeps each tile of rows only to its longest row
# (kernels32.sweep_steps); the direct launcher sweeps the column's whole
# width.  Both must agree bit for bit, and with the u8 oracle, on
# columns whose sweep tiles differ in their longest row.

PHRASE = b"deadline exceeded"                # 17 B, the benchmark's
LEAVES = [(PHRASE, K.MODE_PHRASE, True, True, False),
          (b"line exc", K.MODE_SUBSTRING, False, False, False),
          (b"dead", K.MODE_PREFIX, True, False, False),
          (PHRASE, K.MODE_PHRASE, True, True, True),
          (PHRASE, K.MODE_EXACT, False, False, False)]
PAIR = (b"dead", b"exceeded")


def _busy(longest: int) -> list[bytes]:
    """Rows of a tile whose longest is `longest` bytes: the phrase, the
    pair and near misses at every alignment, cut to fit."""
    vals = [b"-" * k + PHRASE + b" tail" for k in range(8)]
    vals += [b"dead " + b"x" * k + b" exceeded" for k in range(4)]
    vals += [b"exceeded dead", b"Deadline EXCEEDED now", b"dead\nexceeded",
             b"a" * longest]
    return [v[:longest] for v in vals]


def _at_the_end(longest: int) -> list[bytes]:
    """Rows of `longest` bytes that end in each leaf's pattern (the
    phrase after a space and after a word char, the substring, the pair
    with its B last), and a shorter row."""
    return [b" " * (longest - 17) + PHRASE, b"x" * (longest - 17) + PHRASE,
            b"." * (longest - 8) + b"line exc",
            b"dead" + b"=" * (longest - 12) + b"exceeded",
            (b"dead " + PHRASE)[:longest - 1]]


def _at_the_end_folded(longest: int) -> list[bytes]:
    return [b" " * (longest - 17) + b"DEADLINE EXCEEDED",
            b"-" * (longest - 17) + b"Deadline Exceeded",
            b"x" * (longest - 17) + b"DeadLine exceeded", b"dead exceeded"]


def _newline_last(longest: int) -> list[bytes]:
    """`A.*B` rows whose last live byte is a newline, beside rows
    without one and with one first."""
    return [b"dead" + b" " * (longest - 13) + b"exceeded\n",
            b"dead exceeded", b"\ndead exceeded", b"exceeded dead\n"]


# case -> (width, the values of each sweep tile, the tiles' longest rows)
TILE_CASES = {
    "empty_tile": (128, [_busy(60), [b""] * 50, _busy(30), _busy(100)],
                   [60, 0, 30, 100]),
    "tile_shorter_than_the_pattern": (
        128, [_busy(40), [PHRASE[:k] for k in range(17)] + [b"Dead"],
              _busy(127), _busy(16)], [40, 16, 127, 16]),
    "match_ends_on_the_last_live_byte": (
        128, [_at_the_end(37 + t) for t in range(4)], [37, 38, 39, 40]),
    "end_token_reads_the_first_padding_byte": (
        128, [_at_the_end(17 + t) + [b"  " + PHRASE + b"s"]
              for t in (3, 4, 5, 6)], [20, 21, 22, 23]),
    "folded": (128, [_at_the_end_folded(17 + t) for t in (0, 5, 10, 23)],
               [17, 22, 27, 40]),
    "pair_newline_in_the_last_live_byte": (
        128, [_newline_last(21 + t) for t in range(4)], [21, 22, 23, 24]),
    "one_row_at_w_minus_1": (
        512, [_busy(20), _busy(20) + [b" " * 494 + PHRASE], _busy(30),
              [b"dead" + b"=" * 499 + b"exceeded"] + _busy(9)],
        [20, 511, 30, 511]),
}


def _tiled(width: int, tiles: list[list[bytes]]):
    """One sweep tile of the Pallas launcher a list of values."""
    nl = width // 4
    tile = K32.sweep_blocks(nl, len(tiles) * 16)[1] * 128
    rows = len(tiles) * tile
    assert K32.sweep_blocks(nl, rows // 128)[1] * 128 == tile
    mat, lens = _matrix([], width, rows=rows)
    for t, vals in enumerate(tiles):
        sub, sl = _matrix(vals, width, rows=tile)
        mat[t * tile:(t + 1) * tile], lens[t * tile:(t + 1) * tile] = sub, sl
    return mat, lens, tile


def _old_column(width: int, rows: int):
    pat = b"dead line"
    vals = (_edge_values(pat, width) + _edge_values(b"EXC", width)) * 3
    mat, lens = _matrix(vals, width, rows=rows)
    mat[rows - 1, :len(pat)] = np.frombuffer(pat, dtype=np.uint8)
    lens[rows - 1] = len(pat)
    leaves = [(pat, K.MODE_PHRASE, True, True, False),
              (b"exc", K.MODE_PREFIX, True, False, True),
              (b"ad li", K.MODE_SUBSTRING, False, False, False),
              (pat, K.MODE_EXACT, False, False, False)]
    return mat, lens, leaves, (b"dead", b"ne")


@pytest.mark.parametrize("width,rows,case", [
    (16, 1024, None), (128, 2048, None), (256, 4096, None),
    *((None, None, c) for c in TILE_CASES)],
    ids=["16-1024", "128-2048", "256-4096", *TILE_CASES])
def test_pallas_launcher_matches_the_body(width, rows, case):
    """The TPU launcher (blocks of rows in VMEM, a register tile at a
    time, each swept to its longest row) runs the SAME body; interpreted
    on jax-CPU it must agree bit for bit with the direct launcher, which
    sweeps the whole width, and with the u8 oracle (as
    tests/test_pallas.py does for the other kernels)."""
    if case is None:
        mat, lens, leaves, (pa, pb) = _old_column(width, rows)
    else:
        width, tiles, longest = TILE_CASES[case]
        mat, lens, tile = _tiled(width, tiles)
        assert list(lens.reshape(-1, tile).max(axis=1)) == longest
        leaves, (pa, pb) = LEAVES, PAIR
    lanes = jnp.asarray(to_lanes32(mat))
    lens2 = jnp.asarray(lens).reshape(-1, 128)
    nl = width // 4
    hits = 0
    for p, mode, st, et, fold in leaves:
        if fold:
            p = p.lower()
        pc, masks = K32._pattern_chunks(_u8(p), len(p))
        ns = st and mode in (K.MODE_PHRASE, K.MODE_PREFIX)
        ne = et and mode == K.MODE_PHRASE

        def body(load, tile_lens, pcs, tile_max):
            return [K32._scan_rows(load, nl, tile_lens, pcs, masks, len(p),
                                   mode, ns, ne, fold, tile_max)]
        code = np.asarray(K32._launch_pallas(body, lanes, lens2, pc,
                                             interpret=True))
        want = _assert_scan_parity(mat, lens, p, mode, st, et, fold)
        assert np.array_equal(code.reshape(-1) != 0, want), (p, mode)
        if case is None:
            assert want.any()
        hits += int(want.sum())
    assert hits
    ca, ma = K32._pattern_chunks(_u8(pa), len(pa))
    cb, mb = K32._pattern_chunks(_u8(pb), len(pb))

    def pair(load, tile_lens, pcs, tile_max):
        return K32._pair_rows(load, nl, tile_lens, pcs, ma, mb, len(pa),
                              len(pb), tile_max)
    code = np.asarray(K32._launch_pallas(
        pair, lanes, lens2, jnp.concatenate([ca, cb]),
        interpret=True)).reshape(-1)
    wd, wv = K32.match_ordered_pair_t(lanes, jnp.asarray(lens), _u8(pa),
                                      len(pa), _u8(pb), len(pb))
    od, ov = K.match_ordered_pair(jnp.asarray(mat), jnp.asarray(lens),
                                  _u8(pa), len(pa), _u8(pb), len(pb))
    assert np.array_equal(np.asarray(wd), np.asarray(od))
    assert np.array_equal(np.asarray(wv), np.asarray(ov))
    assert np.array_equal(code & 1 != 0, np.asarray(wd))
    assert np.array_equal(code & 2 != 0, np.asarray(wv))
    assert np.asarray(wd).any()
    if case == "pair_newline_in_the_last_live_byte":
        assert np.asarray(wv).any()


SWEEP_CASES = [  # (tile_max, pat_len or None for `A.*B`, nl)
    (0, 1, 8), (0, None, 8), (3, 4, 8), (3, 5, 8), (4, 4, 8), (5, 4, 8),
    (16, 17, 32), (17, 17, 32), (20, 17, 32), (21, 17, 32), (73, 17, 32),
    (127, 17, 32), (127, 1, 32), (127, 4, 32), (127, 128, 32),
    (73, None, 32), (72, None, 32), (1, None, 32), (127, None, 32),
    (2047, 17, 512), (2047, None, 512), (31, 31, 8), (31, 32, 8)]


def test_the_host_count_of_sweep_steps_is_the_kernels():
    """The host's count of steps swept and skipped (FusedField) takes
    the kernel's own arithmetic (kernels32.sweep_steps, traced in the
    kernel): equal on every case, equal to the windows that fit, and
    swept + skipped is the whole width's count."""
    from victorialogs_tpu.tpu.fused import FusedField
    for tile_max, pat_len, nl in SWEEP_CASES:
        traced = int(jax.jit(lambda t: K32.sweep_steps(t, nl, pat_len))(
            jnp.int32(tile_max)))
        whole = K32.sweep_steps(None, nl, pat_len)
        if pat_len is None:      # a byte of a row in plane q
            fit = sum(4 * q < tile_max for q in range(nl))
        else:                    # a window that starts in plane q fits
            fit = sum(4 * q + pat_len <= min(tile_max, 4 * nl)
                      for q in range(whole))
        ff = FusedField(rows=None, lengths=None, width=4 * nl,
                        ovf_packed=None, ovf_np=None, has_ovf=False,
                        nbytes=0, tile_max=(np.array([tile_max, 0]),
                                            np.array([3, 2])))
        swept, skipped = ff.sweep_steps(pat_len)
        case = (tile_max, pat_len, nl)
        assert traced == fit, case
        assert swept == 3 * traced, case
        assert swept + skipped == 5 * whole, case
