"""Pallas-vs-XLA parity checks for the three Pallas kernels.

Two callers, one implementation:

- tests/test_pallas.py imports the check_* functions and runs them in
  interpret mode on CPU at small shapes (the tier-1 parity check);
- ``python tests/pallas_check.py --chip`` (a child of chip_smoke.py, run
  while no server holds the chip) compiles each kernel through Mosaic
  (interpret=False) at product shapes and diffs it against its XLA
  twin, then prints one JSON line naming which kernels compiled.
"""

import json
import random
import sys

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from victorialogs_tpu.storage import filterbank as FB  # noqa: E402
from victorialogs_tpu.storage.bloom import bloom_build  # noqa: E402
from victorialogs_tpu.tpu import kernels as K  # noqa: E402
from victorialogs_tpu.tpu import stats_seg as SS  # noqa: E402
from victorialogs_tpu.tpu.bloom_device import (  # noqa: E402
    pad_plane, pad_probe_args, plane_keep_pallas, probe_np)
from victorialogs_tpu.tpu.kernels_pallas import (  # noqa: E402
    match_scan_pallas, pad_for_pallas, pallas_ok)
from victorialogs_tpu.utils.hashing import hash_tokens  # noqa: E402

WORDS = ["err", "error", "GET", "a_b", "x", "", "deadline exceeded",
         "tok123", "ab/cd"]
PATTERNS = [
    ("error", K.MODE_PHRASE, True, True),
    ("err", K.MODE_PHRASE, True, True),
    ("err", K.MODE_PREFIX, True, False),
    ("error", K.MODE_SUBSTRING, False, False),
    ("GET", K.MODE_EXACT, False, False),
    ("err", K.MODE_EXACT_PREFIX, False, False),
    ("deadline exceeded", K.MODE_PHRASE, True, True),
    ("a_b", K.MODE_PHRASE, True, True),
    ("/", K.MODE_SUBSTRING, False, False),
]


def _stage(vals, width=128):
    bs = [v.encode() for v in vals]
    mat = np.full((len(bs), width), 0xFF, dtype=np.uint8)
    lens = np.zeros(len(bs), dtype=np.int32)
    for i, b in enumerate(bs):
        take = min(len(b), width - 1)
        mat[i, :take] = np.frombuffer(b[:take], dtype=np.uint8)
        lens[i] = take
    return pad_for_pallas(mat, lens)


def check_scan(interpret: bool, copies: int = 3) -> dict:
    """match_scan_pallas vs kernels.match_scan over every mode, on a
    ~1k-row corpus tiled `copies` times (multi-tile grid)."""
    rnd = random.Random(17)
    vals = [" ".join(rnd.choice(WORDS) for _ in range(rnd.randint(0, 6)))
            for _ in range(900)]
    vals += ["error", " error", "error ", "xerror", "errorx", "err or"]
    mat, lens = _stage(vals)
    mat = np.concatenate([mat] * copies)
    lens = np.concatenate([lens] * copies)
    assert pallas_ok(*mat.shape)
    for pat_s, mode, st, et in PATTERNS:
        pat = np.frombuffer(pat_s.encode(), dtype=np.uint8)
        want = np.asarray(K.match_scan(mat, lens, pat, len(pat_s), mode,
                                       st, et))
        got = np.asarray(match_scan_pallas(mat, lens, pat, len(pat_s),
                                           mode, st, et,
                                           interpret=interpret))
        assert np.array_equal(got, want), (pat_s, mode)
    return {"rows": int(mat.shape[0]), "width": int(mat.shape[1]),
            "patterns": len(PATTERNS)}


class _FakePart:
    def __init__(self, blooms):
        self._b = blooms
        self.num_blocks = len(blooms)

    def block_column_bloom(self, i, name):
        return self._b[i]


def check_bloom(interpret: bool, nblocks: int = 300,
                max_tokens: int = 250) -> dict:
    """plane_keep_pallas vs the vectorized host probe (itself pinned to
    per-block bloom_contains_all in tests/test_filterbank.py) for 1-8
    query tokens over a part-sized bloom plane."""
    rng = np.random.default_rng(29)
    universe = [f"tok{i}" for i in range(6 * max_tokens)]
    blooms = []
    for bi in range(nblocks):
        if bi % 13 == 0:
            blooms.append(None)
            continue
        n = int(rng.integers(1, max_tokens))
        toks = list(rng.choice(universe, size=n, replace=False))
        blooms.append(bloom_build(hash_tokens(toks)))
    part = _FakePart(blooms)
    plb = FB.filter_bank(part).plane(part, "f")
    plane_p, nw_p = pad_plane(plb.plane, plb.nwords)
    for t in (1, 2, 3, 8):
        qt = list(rng.choice(universe, size=t, replace=False))
        idx, shift = plb.block_probe_args(hash_tokens(qt))
        want = probe_np(plb.plane, idx, shift, plb.nwords)
        idx_p, shift_p = pad_probe_args(idx, shift, plane_p.shape[0])
        got = np.asarray(plane_keep_pallas(plane_p, idx_p, shift_p, nw_p,
                                           interpret=interpret))
        assert np.array_equal(got[:plb.plane.shape[0]], want), t
        assert got[plb.plane.shape[0]:].all()  # pad blocks: nwords=0 keeps
    return {"blocks": nblocks, "plane_lanes": int(plane_p.shape[1]),
            "tokensets": 4}


def check_seg(interpret: bool, shapes=((2, 7), (5, 64), (8, 251)),
              chunks: int = 3) -> dict:
    """stats_count_seg_pallas vs the widened-combined-id reference."""
    import jax.numpy as jnp
    rng = np.random.default_rng(31)
    r = SS.STATS_CHUNK * chunks
    for nseg, nb in shapes:
        seg = jnp.asarray(rng.integers(0, nseg, r).astype(np.int32))
        bkt = jnp.asarray(rng.integers(0, nb, r).astype(np.int32))
        m = jnp.asarray(rng.random(r) < 0.37)
        want = np.asarray(SS.stats_count_seg_reference(seg, bkt, m, nseg,
                                                       nb))
        got = np.asarray(SS.stats_count_seg_pallas(seg, bkt, m, nseg, nb,
                                                   interpret=interpret))
        assert np.array_equal(got, want), (nseg, nb)
    return {"rows": r, "shapes": [list(s) for s in shapes]}


def main() -> int:
    if sys.argv[1:] != ["--chip"]:
        print("usage: pallas_check.py --chip", file=sys.stderr)
        return 2
    import jax
    out = {"platform": jax.devices()[0].platform}
    # product shapes: the scan at 512k x 128, a >=1000-block part's
    # bloom plane, seg-major counts from a handful of buckets up to
    # MAX_BUCKETS
    out["match_scan"] = check_scan(False, copies=512)
    out["plane_keep"] = check_bloom(False, nblocks=1100, max_tokens=3000)
    out["stats_count_seg"] = check_seg(
        False, shapes=((2, 7), (8, 7), (2, 251), (8, 251), (2, 8192),
                       (8, 8192)), chunks=4)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
