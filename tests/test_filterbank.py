"""Filter-index subsystem: packed bloom planes, batched probes, and
part-level aggregate pruning (storage/filterbank.py, tpu/bloom_device.py).

The batched plane probe must be BIT-IDENTICAL to the per-block
bloom_contains_all kill-path, the host/device probe-position derivations
must never drift from bloom_contains_all's splitmix64 iteration, and the
aggregate may only kill parts whose every block the per-block path would
have killed too."""

import random

import numpy as np
import pytest

from victorialogs_tpu.storage import filterbank as FB
from victorialogs_tpu.storage.bloom import (BLOOM_HASHES, bloom_build,
                                            bloom_contains_all,
                                            bloom_num_words,
                                            bloom_probe_positions)
from victorialogs_tpu.utils.hashing import (cached_token_hashes,
                                            hash_tokens, splitmix64_np)

NS = 1_000_000_000
T0 = 1_753_660_800_000_000_000


class FakePart:
    """Minimal part-shaped object: the uniform block-access surface the
    filter bank consumes (Part and InmemoryPart both provide it)."""

    def __init__(self, blooms):
        self._b = blooms
        self.num_blocks = len(blooms)

    def block_column_bloom(self, i, name):
        return self._b[i]


def _rand_parts(rng, nparts=8, universe=None):
    universe = universe or [f"tok{i}" for i in range(3000)]
    parts = []
    for pi in range(nparts):
        blooms = []
        tokens = []
        nblocks = int(rng.integers(1, 60))
        for bi in range(nblocks):
            r = rng.random()
            if r < 0.15:
                blooms.append(None)          # missing column / no bloom
                tokens.append(None)
                continue
            if r < 0.3:
                n = 1                        # single-word (64-bit) filter
            else:
                n = int(rng.integers(1, 400))
            toks = list(rng.choice(universe, size=n, replace=False))
            blooms.append(bloom_build(hash_tokens(toks)))
            tokens.append(set(toks))
        parts.append((FakePart(blooms), blooms, tokens))
    return parts, universe


# ---------------- probe-position pinning ----------------

def test_probe_positions_match_contains_all_iteration():
    """bloom_probe_positions must replicate bloom_contains_all's
    splitmix64 probe stream exactly: setting precisely those bits makes
    contains True; clearing any single one makes it False."""
    rng = np.random.default_rng(7)
    for nwords in (1, 2, 3, 7, 64, 1000):
        hashes = rng.integers(0, 1 << 63, size=5, dtype=np.uint64)
        pos = bloom_probe_positions(hashes, nwords)
        assert pos.shape == (5, BLOOM_HASHES)
        # independent re-derivation, exactly as bloom_contains_all walks
        nbits = np.uint64(nwords * 64)
        h = hashes.copy()
        for k in range(BLOOM_HASHES):
            assert np.array_equal(pos[:, k], h % nbits)
            h = splitmix64_np(h)
        # bit-for-bit: words with exactly these bits contain the tokens
        words = np.zeros(nwords, dtype=np.uint64)
        np.bitwise_or.at(words, (pos >> np.uint64(6)).astype(np.int64),
                         np.uint64(1) << (pos & np.uint64(63)))
        assert bloom_contains_all(words, hashes)
        # clearing any probed bit of a token always breaks that token
        p0 = int(pos[2, 3])
        w2 = words.copy()
        w2[p0 >> 6] &= ~(np.uint64(1) << np.uint64(p0 & 63))
        assert not bloom_contains_all(w2, hashes[2:3])


def test_bloom_num_words_floor():
    assert bloom_num_words(0) == 1           # 64-bit minimum filter
    assert bloom_num_words(1) == 1
    assert bloom_num_words(100) == (100 * 16 + 63) // 64


# ---------------- randomized plane differential ----------------

def test_plane_probe_differential_1000_pairs():
    """Batched plane probe ≡ per-block bloom_contains_all over ≥1000
    (block, tokenset) pairs, including empty tokensets, missing columns
    (words is None) and single-word filters."""
    rng = np.random.default_rng(11)
    parts, universe = _rand_parts(rng)
    pairs = 0
    for part, blooms, tokens in parts:
        pl = FB.filter_bank(part).plane(part, "f")
        assert pl is not None or all(
            b is None or b.shape[0] == 0 for b in blooms)
        for _ in range(10):
            t = int(rng.integers(0, 5))
            if t and rng.random() < 0.5:
                # bias towards tokens present in some block
                qt = list(rng.choice(universe, size=t, replace=False))
            elif t:
                qt = [f"absent{rng.integers(1 << 30)}" for _ in range(t)]
            else:
                qt = []
            hashes = hash_tokens(qt)
            ref = np.array([
                b is None or b.shape[0] == 0
                or bloom_contains_all(b, hashes)
                for b in blooms])
            if pl is not None:
                assert np.array_equal(pl.keep_mask(hashes), ref)
                # subset form (the evaluator probes candidate blocks)
                bis = sorted(rng.choice(
                    part.num_blocks,
                    size=min(5, part.num_blocks), replace=False))
                assert np.array_equal(pl.keep_mask(hashes, bis),
                                      ref[np.asarray(bis)])
            pairs += len(blooms)
    assert pairs >= 1000, pairs


def test_plane_probe_device_matches_numpy():
    """The jitted jax probe returns the numpy probe bit-for-bit."""
    from victorialogs_tpu.tpu.bloom_device import plane_probe, probe_np
    rng = np.random.default_rng(3)
    parts, universe = _rand_parts(rng, nparts=3)
    checked = 0
    for part, blooms, _tokens in parts:
        pl = FB.filter_bank(part).plane(part, "f")
        if pl is None:
            continue
        for t in (1, 2, 4):
            qt = list(rng.choice(universe, size=t, replace=False))
            hashes = hash_tokens(qt)
            idx, shift = pl.block_probe_args(hashes)
            want = probe_np(pl.plane, idx, shift, pl.nwords)
            got = np.asarray(plane_probe(pl.plane, idx, shift,
                                         pl.nwords))
            assert np.array_equal(got, want)
            checked += 1
    assert checked


# ---------------- false-positive rate (6 probes / 16 bits per token) ----

def test_false_positive_rate_measured():
    """k=6 probes over 16 bits/token: theoretical fp ≈ (1-e^(-6/16))^6
    ≈ 9.4e-4.  Measure it: absent single tokens against a 1000-token
    filter must false-positive rarely — and the vectorized position
    math must agree with bloom_contains_all on every probe."""
    rng = np.random.default_rng(23)
    member = [f"m{i}" for i in range(1000)]
    words = bloom_build(hash_tokens(member))
    absent = hash_tokens([f"a{i}" for i in range(50000)])
    pos = bloom_probe_positions(absent, words.shape[0])
    bits = (words[(pos >> np.uint64(6)).astype(np.int64)]
            >> (pos & np.uint64(63))) & np.uint64(1)
    fp = bits.astype(bool).all(axis=1)
    rate = fp.mean()
    assert rate < 5e-3, rate          # ~5x theory: generous, not flaky
    # spot-agree with the scalar oracle on a sample (both outcomes)
    sample = list(rng.choice(50000, size=200, replace=False))
    sample += list(np.nonzero(fp)[0][:20])
    for i in sample:
        assert bool(fp[i]) == bloom_contains_all(words, absent[i:i + 1])
    # no false negatives, ever
    mh = hash_tokens(member)
    mpos = bloom_probe_positions(mh, words.shape[0])
    mbits = (words[(mpos >> np.uint64(6)).astype(np.int64)]
             >> (mpos & np.uint64(63))) & np.uint64(1)
    assert mbits.astype(bool).all()


# ---------------- aggregate: soundness + kills ----------------

def test_aggregate_soundness_and_kills():
    rng = np.random.default_rng(5)
    universe = [f"tok{i}" for i in range(2000)]
    blooms = []
    for _ in range(48):
        n = int(rng.integers(1, 200))
        toks = list(rng.choice(universe, size=n, replace=False))
        blooms.append(bloom_build(hash_tokens(toks)))
    part = FakePart(blooms)
    agg = FB.filter_bank(part).aggregate(part, "f")
    assert agg is not None and agg.all_have
    kills = 0
    for t in range(400):
        h = hash_tokens([f"absent{t}"])
        if not agg.may_contain_all(h):
            kills += 1
            # sound: every block's own filter also rejects
            for w in blooms:
                assert not bloom_contains_all(w, h)
    assert kills > 0, "aggregate never kills absent tokens"
    # no false kills for genuinely present tokens
    for tok in rng.choice(universe, size=200, replace=False):
        h = hash_tokens([tok])
        if any(bloom_contains_all(w, h) for w in blooms):
            assert agg.may_contain_all(h), tok


def test_aggregate_missing_bloom_blocks_disable_kills():
    """A block without a bloom can hide anything: never kill the part."""
    rng = np.random.default_rng(6)
    blooms = [bloom_build(hash_tokens(["alpha", "beta"])), None]
    part = FakePart(blooms)
    agg = FB.filter_bank(part).aggregate(part, "f")
    assert agg is not None and not agg.all_have
    assert agg.may_contain_all(hash_tokens([f"zz{rng.integers(1e9)}"]))


def test_filter_bank_cached_on_part():
    part = FakePart([bloom_build(hash_tokens(["a"]))])
    fb1 = FB.filter_bank(part)
    fb2 = FB.filter_bank(part)
    assert fb1 is fb2
    pl1 = fb1.plane(part, "f")
    assert fb1.plane(part, "f") is pl1
    assert fb1.aggregate(part, "f") is fb1.aggregate(part, "f")


def test_cached_token_hashes_invalidates_on_new_tokens():
    class Owner:
        pass
    o = Owner()
    h1 = cached_token_hashes(o, ["a", "b"])
    assert cached_token_hashes(o, ["a", "b"]) is h1
    h2 = cached_token_hashes(o, ["c"])
    assert h2 is not h1
    assert np.array_equal(h2, hash_tokens(["c"]))


# ---------------- end-to-end through the query engine ----------------

@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    from victorialogs_tpu.storage.log_rows import LogRows, TenantID
    from victorialogs_tpu.storage.storage import Storage
    random.seed(31)
    s = Storage(str(tmp_path_factory.mktemp("fbstore")),
                retention_days=100000, flush_interval=3600)
    lr = LogRows(stream_fields=["app"])
    for i in range(4000):
        msg = ("rareneedle present here "
               if i % 2 == 0 else "ordinary line ") + f"row{i}"
        lr.add(TenantID(0, 0), T0 + i * NS,
               [("app", f"app{i % 2}"), ("_msg", msg)])
    s.must_add_rows(lr)
    s.debug_flush()
    yield s
    s.close()


E2E_QUERIES = [
    "rareneedle | fields _time",
    "rareneedle row2 | fields _time",
    "absenttoken | fields _time",
    "rareneedle | stats count() c",
    "rareneedle | stats by (app) count() c",
    "absenttoken | stats count() c",
    "rareneedle or ordinary | stats count() c",
]


def test_plane_and_aggregate_e2e_parity(storage, monkeypatch):
    """CPU vs batched runner over queries where bloom kills some (or
    all) blocks of the part: bit-identical results, the plane probe ran
    on the batch path, the fused path emitted the in-dispatch bloom
    node, and the absent-token query pruned the part outright.

    Pinned to VL_FILTER_INDEX=v1: this suite is the CLASSIC-path
    differential (the kill-switch contract); the v2 sidecar path has
    its own e2e pins in tests/test_filterindex.py."""
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.storage.log_rows import TenantID
    from victorialogs_tpu.tpu.batch import BatchRunner
    monkeypatch.setenv("VL_FILTER_INDEX", "v1")
    ten = TenantID(0, 0)
    runner = BatchRunner()
    for q in E2E_QUERIES:
        cpu = run_query_collect(storage, [ten], q, timestamp=T0)
        dev = run_query_collect(storage, [ten], q, timestamp=T0,
                                runner=runner)
        assert cpu == dev, q
    assert runner.agg_pruned_parts >= 2      # both absent-token queries
    assert runner.bloom_plane_probes >= 1    # row-path leaf probe
    assert "bloom_device" in runner.dispatch_kinds


def test_device_bloom_disabled_still_identical(storage, monkeypatch):
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.storage.log_rows import TenantID
    from victorialogs_tpu.tpu.batch import BatchRunner
    monkeypatch.setenv("VL_DEVICE_BLOOM", "0")
    monkeypatch.setenv("VL_FILTER_INDEX", "v1")
    ten = TenantID(0, 0)
    runner = BatchRunner()
    for q in E2E_QUERIES:
        cpu = run_query_collect(storage, [ten], q, timestamp=T0)
        dev = run_query_collect(storage, [ten], q, timestamp=T0,
                                runner=runner)
        assert cpu == dev, q
    assert "bloom_device" not in runner.dispatch_kinds


def test_and_path_token_leaves_walker():
    from victorialogs_tpu.logsql.filters import iter_and_path_token_leaves
    from victorialogs_tpu.logsql.parser import parse_query
    q = parse_query('alpha path:beta (x or y) !gamma | fields _msg', T0)
    leaves = list(iter_and_path_token_leaves(q.filter))
    got = {(f, tuple(t)) for f, t, _ in leaves}
    # OR/NOT branches contribute nothing; AND-path leaves do
    assert ("_msg", ("alpha",)) in got
    assert ("path", ("beta",)) in got
    assert all("gamma" not in t and "x" not in t and "y" not in t
               for _f, t, _l in leaves)
