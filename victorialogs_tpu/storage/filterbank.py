"""Filter-index subsystem: packed per-part bloom planes + part aggregates.

Turns bloom pruning from an O(blocks) host Python loop into one dense
batched probe per (part, column), plus an O(1) part-level kill:

- **Bloom plane** (split-block layout, Lang et al. arXiv:2101.01719
  reshaped for whole-part probing): every block's bloom words for one
  column packed into a single zero-padded uint32 matrix `[B, 2*Wmax]`
  (uint64 words as 2 little-endian uint32 lanes — the same lane
  reinterpretation the device kernels use).  Probe positions are
  computed host-side ONCE PER DISTINCT FILTER SIZE with
  `bloom.bloom_probe_positions` and broadcast to per-block gather
  indices, so testing T tokens against B blocks is a single vectorized
  gather + bit-test instead of B Python calls.  The same
  (plane, idx, shift, nwords) arguments drive the device probe
  (tpu/bloom_device.py) unchanged.

- **Part aggregate** (Bloofi-style, arXiv:1501.01941): fixed-width
  OR-folds of the block filters, one fold per distinct filter size
  (probe positions of a size-w filter span only w words, so sizes must
  not share a fold).  Word i of a block filter folds into aggregate
  word ``i % width``, so a bit set by ANY block is set in its size's
  aggregate and the probe has no false negatives.  A token whose
  probes miss for EVERY distinct block-filter size present in the part
  is absent from every block — the whole part dies in O(1) before any
  block header is touched by the query.

Both are derived purely from the existing blooms.bin sidecar (no format
change) and cached on the part object (parts are immutable).
"""

from __future__ import annotations

import threading
import weakref
from dataclasses import dataclass

import numpy as np

from .. import config
from ..obs import activity, events, hist, tracing
from ..utils.hashing import cached_token_hashes
from .bloom import (BLOOM_HASHES, bloom_contains_all,
                    bloom_probe_positions_multi)

# aggregate fold width cap, in uint64 words (4096 words = 32 KiB bits);
# small parts fold at their own max filter size instead
AGG_WORDS = 4096

# planes beyond this decline to the per-block path (a pathological part
# with huge per-block filters must not balloon host memory)
_MAX_PLANE_BYTES = config.env_int("VL_BLOOM_PLANE_MAX_BYTES")

# global budget for ALL host-resident planes: planes duplicate the
# mmap'd blooms.bin data in RAM, so a long-lived server querying many
# (part, column) pairs must stay bounded — past the budget, new columns
# take the per-block fallback (identical semantics, just slower) until
# parts (and their banks) are garbage-collected
_BANK_MAX_BYTES = config.env_int("VL_BLOOM_BANK_MAX_BYTES")
_bank_mu = threading.Lock()
_bank_bytes = 0
# every live charge list registered with a _bank_release finalizer —
# the vlsan runtime sweep proves _bank_bytes == sum of live charges
# (>= 0) after every test (tools/vlint/vlsan.py)
_bank_owners: "weakref.WeakSet" = weakref.WeakSet()


def _bank_track(owner) -> None:
    """Register an object whose ._charged list was handed to a
    _bank_release weakref.finalize (FilterBank, PartFilterIndex)."""
    _bank_owners.add(owner)


def bank_check_balanced() -> tuple[bool, str]:
    """Budget-accounting invariant for the vlsan sweep: the global
    byte total equals the sum of every live owner's charges and never
    goes negative (a double release would).  Callers retry once after
    gc.collect() — a finalizer may not have run yet."""
    with _bank_mu:
        used = _bank_bytes
    live = sum(sum(o._charged) for o in list(_bank_owners))
    ok = used == live and used >= 0
    return ok, f"bank_bytes={used} sum(live charges)={live}"


def _bank_try_charge(n: int) -> bool:
    global _bank_bytes
    with _bank_mu:
        if _bank_bytes + n > _BANK_MAX_BYTES:
            return False
        _bank_bytes += n
        return True


def _bank_release(charges: list) -> None:
    """weakref.finalize callback: a collected FilterBank returns its
    planes' bytes to the budget (charges is the bank's live list)."""
    global _bank_bytes
    with _bank_mu:
        _bank_bytes -= sum(charges)
        charges.clear()


def bank_stats() -> dict:
    """Occupancy of the global host bloom-plane budget, for /metrics
    (vl_tpu_bloom_bank_used_bytes / vl_tpu_bloom_bank_max_bytes)."""
    with _bank_mu:
        return {"used_bytes": _bank_bytes, "max_bytes": _BANK_MAX_BYTES}


@dataclass
class BloomPlane:
    """All (block, column) bloom filters of one part column, packed."""
    plane: np.ndarray              # uint32[B, 2*Wmax], zero-padded
    nwords: np.ndarray             # int32[B]; 0 = block has no bloom
    sizes: tuple                   # distinct nonzero word counts, sorted
    size_id: np.ndarray            # int32[B] index into sizes (0 if none)
    nbytes: int

    # single-slot memo: the same (leaf, part) pair probes with the same
    # hashes from the planner, the evaluator and the prefetcher.  One
    # (key, value) tuple, swapped atomically (GIL) — concurrent probers
    # may duplicate work but never see a key/value mismatch.
    _memo: tuple | None = None

    def probe_tables(self, hashes: np.ndarray
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Per-size gather tables -> (idx, shift) int32[S, T*6].

        idx is the uint32-lane index of each probe bit inside a plane
        row (2*word + high-half), shift the bit position within the
        lane; both derived from bloom_probe_positions so the host and
        device probes share one position derivation.
        """
        key = hashes.tobytes()
        memo = self._memo
        if memo is not None and memo[0] == key:
            return memo[1]
        p = len(hashes) * BLOOM_HASHES
        pos = bloom_probe_positions_multi(hashes, self.sizes) \
            .reshape(len(self.sizes), p)
        idx = ((pos >> np.uint64(6)) * np.uint64(2)
               + ((pos >> np.uint64(5)) & np.uint64(1))).astype(np.int32)
        shift = (pos & np.uint64(31)).astype(np.int32)
        self._memo = (key, (idx, shift))
        return idx, shift

    def block_probe_args(self, hashes: np.ndarray
                         ) -> tuple[np.ndarray, np.ndarray]:
        """(idx, shift) int32[B, T*6] — per-block gather arguments."""
        idx_s, shift_s = self.probe_tables(hashes)
        return idx_s[self.size_id], shift_s[self.size_id]

    def keep_mask(self, hashes: np.ndarray,
                  bis=None) -> np.ndarray:
        """bool keep-mask: True where the block may contain ALL tokens
        (or has no bloom).  bis: optional block-idx list restricting the
        probe (returned mask is aligned with bis)."""
        from ..tpu.bloom_device import probe_np
        if bis is None:
            if len(hashes) == 0:
                return np.ones(self.plane.shape[0], dtype=bool)
            idx, shift = self.block_probe_args(hashes)
            return probe_np(self.plane, idx, shift, self.nwords)
        sel = np.asarray(list(bis), dtype=np.int64)
        if len(hashes) == 0:
            return np.ones(sel.shape[0], dtype=bool)
        idx_s, shift_s = self.probe_tables(hashes)
        sid = self.size_id[sel]
        # gather ONLY the probed lanes (cost scales with T*6, not Wmax;
        # plane[sel] would copy whole rows first).  Bit-test semantics
        # are probe_np's, pinned by the differential tests.
        words = self.plane[sel[:, None], idx_s[sid]]
        bits = (words >> shift_s[sid].astype(np.uint32)) & np.uint32(1)
        return (bits != 0).all(axis=1) | (self.nwords[sel] == 0)

    def device_bytes(self) -> int:
        return self.nbytes


@dataclass
class AggregateFilter:
    """Fixed-width OR-folds of the part's block filters, one per
    distinct filter size, padded into one matrix so a probe is a
    single vectorized gather over every (size, token, probe) at once.

    Probe positions of a size-w filter only span w words, so folding
    different sizes together saturates immediately; folding WITHIN a
    size is exact up to the width cap (word i ORs into i % width), and
    same-size blocks are naturally few — block filter size tracks the
    block's distinct token count."""
    mat: np.ndarray                # uint64[S, Wcap] zero-padded folds
    widths: np.ndarray             # uint64[S] fold width per size
    sizes: tuple                   # distinct filter word counts (|| mat)
    all_have: bool                 # every block has a non-empty bloom

    # small result memo: parts are immutable and a query probes the
    # same (leaf, part) pairs from the serial walk, the pipeline
    # planner AND the explain pricing pass; a DICT (not a single slot)
    # because several AND-path leaves alternate probes on one field's
    # aggregate and would thrash a one-entry memo.  Bounded: cleared
    # wholesale past _MEMO_MAX (GIL-atomic dict ops, no lock needed)
    _memo: dict | None = None
    _MEMO_MAX = 32

    def may_contain_all(self, hashes: np.ndarray) -> bool:
        """False only when some token is PROVABLY absent from every
        block (=> a filter requiring all tokens matches nothing in the
        part).  Blocks without blooms can hide anything, so a part
        where any block lacks one is never killable."""
        if not self.all_have or len(hashes) == 0:
            return True
        key = hashes.tobytes()
        memo = self._memo
        if memo is None:
            memo = self._memo = {}
        got = memo.get(key)
        if got is not None:
            return got
        pos = bloom_probe_positions_multi(hashes, self.sizes)  # [S,T,6]
        wi = (pos >> np.uint64(6)) % self.widths[:, None, None]
        bit = (self.mat[np.arange(len(self.sizes))[:, None, None],
                        wi.astype(np.int64)]
               >> (pos & np.uint64(63))) & np.uint64(1)
        # a token is possible if SOME size's fold holds all its probes
        out = bool(bit.astype(bool).all(axis=2).any(axis=0).all())
        if len(memo) >= self._MEMO_MAX:
            memo.clear()
        memo[key] = out
        return out


class FilterBank:
    """Per-part cache of bloom planes and aggregate filters.

    Attached lazily to the part object (Part and InmemoryPart both
    expose the uniform block_column_bloom API); parts are immutable so
    entries never invalidate.  Thread-safe: the evaluator, the
    prefetcher and concurrent partition workers may probe one part at
    once — builds run outside the lock and the first insert wins.
    """

    def __init__(self):
        self._mu = threading.Lock()
        self._planes: dict = {}
        self._aggs: dict = {}
        # plane byte charges against the global budget, released when
        # the bank (== its part) is garbage-collected
        self._charged: list = []
        weakref.finalize(self, _bank_release, self._charged)
        _bank_track(self)

    def plane(self, part, field: str) -> BloomPlane | None:
        with self._mu:
            got = self._planes.get(field, _MISSING)
        if got is not _MISSING:
            return got
        built = _build_plane(part, field)
        if built is not None and not _bank_try_charge(built.nbytes):
            # budget exhausted — the would-be plane is evicted before
            # it ever lands (per-block path instead).  Previously
            # invisible; now a journal event AND the decline counter.
            events.emit("bloom_bank_evict", field=field,
                        nbytes=built.nbytes,
                        part=str(getattr(part, "uid", "?")))
            built = None               # budget exhausted: per-block path
        with self._mu:
            got = self._planes.setdefault(field, built)
            if got is built and built is not None:
                self._charged.append(built.nbytes)
        if got is not built and built is not None:
            _bank_release([built.nbytes])  # lost the build race
        return got

    def cached_plane(self, field: str) -> "BloomPlane | None":
        """The plane if one was already built; never builds (the
        aggregate can fold from raw blooms directly, so a pure CPU-path
        query must not pay the plane's B x 2*Wmax host memory)."""
        with self._mu:
            got = self._planes.get(field, _MISSING)
        return None if got is _MISSING else got

    def aggregate(self, part, field: str) -> AggregateFilter | None:
        with self._mu:
            got = self._aggs.get(field, _MISSING)
        if got is not _MISSING:
            return got
        built = _build_aggregate(part, field, self.cached_plane(field))
        with self._mu:
            got = self._aggs.setdefault(field, built)
        return got

    def cached_aggregate(self, field: str) -> "AggregateFilter | None":
        with self._mu:
            got = self._aggs.get(field, _MISSING)
        return None if got is _MISSING else got


_MISSING = object()
_attach_mu = threading.Lock()


def filter_bank(part) -> FilterBank:
    """The part's FilterBank, attached on first use."""
    fb = getattr(part, "_filter_bank", None)
    if fb is None:
        with _attach_mu:
            fb = getattr(part, "_filter_bank", None)
            if fb is None:
                fb = FilterBank()
                part._filter_bank = fb
    return fb


def _build_plane(part, field: str) -> BloomPlane | None:
    """Pack every block's bloom words for `field` into one uint32 plane.

    None when no block has a bloom for the column (nothing to probe) or
    the padded plane would exceed the size cap (per-block fallback)."""
    nblocks = part.num_blocks
    words_by_block: list = [None] * nblocks
    nwords = np.zeros(nblocks, dtype=np.int32)
    wmax = 0
    for bi in range(nblocks):
        w = part.block_column_bloom(bi, field)
        if w is None or w.shape[0] == 0:
            continue
        words_by_block[bi] = w
        nwords[bi] = w.shape[0]
        if w.shape[0] > wmax:
            wmax = int(w.shape[0])
    if wmax == 0:
        return None
    if nblocks * wmax * 8 > _MAX_PLANE_BYTES:
        return None
    plane = np.zeros((nblocks, 2 * wmax), dtype=np.uint32)
    for bi, w in enumerate(words_by_block):
        if w is None:
            continue
        lanes = np.ascontiguousarray(w, dtype=np.uint64).view(np.uint32)
        plane[bi, :lanes.shape[0]] = lanes
    sizes = tuple(sorted(int(s) for s in np.unique(nwords[nwords > 0])))
    size_of = {s: i for i, s in enumerate(sizes)}
    size_id = np.zeros(nblocks, dtype=np.int32)
    for bi in range(nblocks):
        if nwords[bi]:
            size_id[bi] = size_of[int(nwords[bi])]
    return BloomPlane(plane=plane, nwords=nwords, sizes=sizes,
                      size_id=size_id, nbytes=plane.nbytes)


def _fold_into(agg: np.ndarray, words: np.ndarray) -> None:
    aw = agg.shape[0]
    for start in range(0, words.shape[0], aw):
        chunk = np.asarray(words[start:start + aw], dtype=np.uint64)
        agg[:chunk.shape[0]] |= chunk


def _pack_aggs(aggs: dict, all_have: bool) -> AggregateFilter:
    sizes = tuple(sorted(aggs))
    wcap = max(a.shape[0] for a in aggs.values())
    mat = np.zeros((len(sizes), wcap), dtype=np.uint64)
    widths = np.empty(len(sizes), dtype=np.uint64)
    for si, s in enumerate(sizes):
        a = aggs[s]
        mat[si, :a.shape[0]] = a
        widths[si] = a.shape[0]
    return AggregateFilter(mat=mat, widths=widths, sizes=sizes,
                           all_have=all_have)


def _build_aggregate(part, field: str,
                     plane: BloomPlane | None) -> AggregateFilter | None:
    """Per-size OR-folds of the block filters.

    Rides the packed plane when available (pure row reductions per size
    group); falls back to a direct per-block fold when the plane
    declined on size.  None when no block has a bloom for the column."""
    if plane is not None:
        aggs = {}
        for si, w in enumerate(plane.sizes):
            rows = plane.plane[(plane.size_id == si)
                               & (plane.nwords > 0)]
            col_or = np.bitwise_or.reduce(rows[:, :2 * w], axis=0)
            lo = col_or[0::2].astype(np.uint64)
            hi = col_or[1::2].astype(np.uint64)
            words = lo | (hi << np.uint64(32))          # uint64[w]
            agg = np.zeros(min(w, AGG_WORDS), dtype=np.uint64)
            _fold_into(agg, words)
            aggs[w] = agg
        return _pack_aggs(aggs, bool((plane.nwords > 0).all()))
    aggs = {}
    have = 0
    nblocks = part.num_blocks
    for bi in range(nblocks):
        w = part.block_column_bloom(bi, field)
        if w is None or w.shape[0] == 0:
            continue
        have += 1
        size = int(w.shape[0])
        agg = aggs.get(size)
        if agg is None:
            agg = aggs[size] = np.zeros(min(size, AGG_WORDS),
                                        dtype=np.uint64)
        _fold_into(agg, w)
    if not aggs:
        return None
    return _pack_aggs(aggs, have == nblocks)


# ---------------- query-path entry points ----------------

def bloom_keep_mask(part, field: str, hashes: np.ndarray,
                    bis=None, observe: bool = True) -> np.ndarray:
    """THE bloom kill-path: bool keep-mask over `bis` (or all blocks),
    True where the block may contain ALL tokens (or has no bloom).

    Rides the packed plane when the column has one; columns without a
    plane (no blooms anywhere, or past the size cap) fall back to a
    per-block probe with identical semantics — every caller sees one
    contract, so the evaluator, prefetcher and fused planner can never
    diverge on survivors.

    A COLD plane build reads every block's bloom (forcing all lazy
    header groups) and charges the bank budget, so it only pays when
    the probed candidate set covers a sizable fraction of the part —
    the same coverage gate the searcher applies to aggregate builds;
    narrow probes ride an already-built plane or the per-block loop.

    observe=False skips the prune-ratio histogram and trace counters:
    the prefetcher probes the same (part, field, bis) the evaluator
    will re-probe at dispatch — only the dispatch probe counts.

    Sealed parts with a valid v2 sidecar (storage/filterindex) answer
    from the token→block maplet instead: one lookup, an EXACT keep set
    (strictly fewer survivors than the probabilistic probe, never a
    false negative), and no host plane build at all.  Every caller
    still sees this one contract — VL_FILTER_INDEX=v1, a corrupt
    sidecar or an unsealed part land on the classic path below."""
    from .filterindex import part_index
    fi = part_index(part)
    if fi is not None:
        return _observe_keep(fi.keep_mask(field, hashes, bis), observe)
    fb = filter_bank(part)
    pl = fb.cached_plane(field)
    if pl is None and (bis is None
                       or len(bis) * 4 >= part.num_blocks):
        pl = fb.plane(part, field)
    if pl is not None:
        return _observe_keep(pl.keep_mask(hashes, bis), observe)
    idxs = list(bis) if bis is not None else list(range(part.num_blocks))
    keep = np.ones(len(idxs), dtype=bool)
    if len(hashes) == 0:
        return keep
    for k, bi in enumerate(idxs):
        w = part.block_column_bloom(bi, field)
        if w is not None and w.shape[0] and \
                not bloom_contains_all(w, hashes):
            keep[k] = False
    return _observe_keep(keep, observe)


def _observe_keep(keep: np.ndarray, observe: bool = True) -> np.ndarray:
    """Per-probe prune accounting: the kill fraction feeds the
    vl_tpu_bloom_prune_ratio histogram, and an active trace's ambient
    span gets blocks_probed_bloom / blocks_killed_bloom counters."""
    n = int(keep.shape[0])
    if n and observe:
        killed = n - int(keep.sum())
        hist.PRUNE_RATIO.observe(killed / n)
        sp = tracing.current_span()
        if sp.enabled:
            sp.add("blocks_probed_bloom", n)
            sp.add("blocks_killed_bloom", killed)
        if killed:
            # live-progress twin of the span counter: the active-query
            # registry record (no-op when the query isn't tracked)
            activity.current_activity().add("blocks_killed_bloom",
                                            killed)
    return keep


def aggregate_kill_leaf(part, leaves, build: bool = True):
    """The (field, tokens, owner_filter, artifact) leaf whose required
    tokens are provably absent from every block of the part, or None —
    the header walk's O(1) part-level kill (engine/planwalk.py) and the
    EXPLAIN plan's kill citation.  No trace/registry side effects: pure
    probe (planwalk.observe lands the execution's counters).

    leaves: [(field, tokens, owner_filter)] from
    logsql.filters.iter_and_path_token_leaves — owner_filter carries the
    per-filter token-hash cache so tokens hash once per query.
    build=False probes only aggregates that already exist (a cold build
    reads every block's bloom, which a time-narrow query touching few
    candidate blocks should not pay — the caller gates on candidate
    coverage).

    Sealed v2 parts probe the xor-filter aggregate first (artifact
    `xor_aggregate`: ~0.62x the bits/key and a fixed ~2^-8 fp rate, so
    it kills a superset of what the classic fold kills); classic parts
    use the Bloofi-style OR-folds (artifact `bloom_fold`)."""
    from .filterindex import part_index
    fi = part_index(part)
    fb = filter_bank(part) if build else \
        getattr(part, "_filter_bank", None)
    for field, tokens, f in leaves:
        if fi is not None:
            if fi.xor_kill(field, cached_token_hashes(f, tokens)):
                return field, tokens, f, "xor_aggregate"
            if fi.covers(field):
                # the xor aggregate is exact over the part's token set
                # (no false negatives): when it declines to kill, the
                # coarser classic fold cannot kill either
                continue
        if fb is None:
            continue
        agg = fb.aggregate(part, field) if build else \
            fb.cached_aggregate(field)
        if agg is not None and \
                not agg.may_contain_all(cached_token_hashes(f, tokens)):
            return field, tokens, f, "bloom_fold"
    return None


def maplet_leaf_keep(fi, leaves, bis):
    """THE AND-path maplet core of the header walk (engine/planwalk.py):
    ONE lookup per leaf over the sealed part's token→block maplets.
    The blocks it drops are exactly those the per-leaf kill-path would
    have zeroed (the maplet is exact on token membership), so results
    are identical — the kill only moves before any header, bloom or
    dispatch work, where the EXPLAIN planner can count it.  Returns
    (keep bool[len(bis)] | None, killing_leaf | None): keep is None
    when no leaf had maplet coverage; killing_leaf is the first leaf
    whose candidates emptied."""
    keep = None
    for field, tokens, f in leaves:
        if not fi.has(field):
            continue
        km = fi.keep_mask(field, cached_token_hashes(f, tokens), bis)
        keep = km if keep is None else keep & km
        if not keep.any():
            return keep, (field, tokens, f)
    return keep, None
