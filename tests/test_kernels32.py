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

@pytest.mark.parametrize("width,rows", [(16, 1024), (128, 2048),
                                        (256, 4096)])
def test_pallas_launcher_matches_the_body(width, rows):
    """The TPU launcher (blocks of rows in VMEM, a register tile at a
    time) runs the SAME body; interpreted on jax-CPU it must agree with
    the direct launcher bit for bit (as tests/test_pallas.py does for
    the other kernels)."""
    pat = b"dead line"
    vals = (_edge_values(pat, width) + _edge_values(b"EXC", width)) * 3
    mat, lens = _matrix(vals, width, rows=rows)
    mat[rows - 1, :len(pat)] = np.frombuffer(pat, dtype=np.uint8)
    lens[rows - 1] = len(pat)
    lanes = jnp.asarray(to_lanes32(mat))
    lens2 = jnp.asarray(lens).reshape(-1, 128)
    nl = width // 4
    for p, mode, st, et, fold in [(pat, K.MODE_PHRASE, True, True, False),
                                  (b"exc", K.MODE_PREFIX, True, False, True),
                                  (b"ad li", K.MODE_SUBSTRING, False, False,
                                   False),
                                  (pat, K.MODE_EXACT, False, False, False)]:
        pc, masks = K32._pattern_chunks(_u8(p), len(p))
        ns = st and mode in (K.MODE_PHRASE, K.MODE_PREFIX)
        ne = et and mode == K.MODE_PHRASE

        def body(load, tile_lens, pcs):
            return [K32._scan_rows(load, nl, tile_lens, pcs, masks, len(p),
                                   mode, ns, ne, fold)]
        code = np.asarray(K32._launch_pallas(body, lanes, lens2, pc,
                                             interpret=True))
        want = np.asarray(K32.match_scan_t(lanes, jnp.asarray(lens), _u8(p),
                                           len(p), mode, st, et, fold))
        assert np.array_equal(code.reshape(-1) != 0, want), (p, mode)
        assert want.any()
    pa, pb = b"dead", b"ne"
    ca, ma = K32._pattern_chunks(_u8(pa), len(pa))
    cb, mb = K32._pattern_chunks(_u8(pb), len(pb))

    def pair(load, tile_lens, pcs):
        return K32._pair_rows(load, nl, tile_lens, pcs, ma, mb, len(pa),
                              len(pb))
    code = np.asarray(K32._launch_pallas(
        pair, lanes, lens2, jnp.concatenate([ca, cb]),
        interpret=True)).reshape(-1)
    wd, wv = K32.match_ordered_pair_t(lanes, jnp.asarray(lens), _u8(pa),
                                      len(pa), _u8(pb), len(pb))
    assert np.array_equal(code & 1 != 0, np.asarray(wd))
    assert np.array_equal(code & 2 != 0, np.asarray(wv))
    assert np.asarray(wd).any()
