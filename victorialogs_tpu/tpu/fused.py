"""Fully-fused `filter | stats` device path: ONE dispatch per part.

Why this exists: a pipeline of steps (scan dispatch -> bitmap download
-> host slice -> mask re-upload -> stats dispatch) pays a dispatch round
trip and an R-byte transfer per step.  This module evaluates the WHOLE
filter tree and the stats partials inside a single jit: the bitmap never
leaves HBM, and the host downloads only the (7, num_buckets) partials
plus (when needed) a bit-packed "needs-host-verify" vector (R/8 bytes).

Key design points:
- Staging is in STATS-LAYOUT coordinates (every block of the part, in
  index order — tpu/batch.py part_stats_layout).  Dict/const/missing
  blocks are
  MATERIALIZED into the fixed-width matrix (a const block is one
  template row broadcast), so every filter leaf is a pure scan and the
  jitted program needs no per-block composition tables — which keeps
  the jit cache keyed on query SHAPE, not on part-specific block maps.
- Three-valued logic: each tree node evaluates to (definite, maybe)
  row vectors.  `maybe` collects truncation-overflow rows and the
  ordered-pair regex's newline rows; they are excluded from the device
  partials and settled by a host residue pass (filters' own
  apply_to_block over just those rows) whose per-row partials merge
  through the same absorb path — bit-identical to the CPU executor.
- The host-side planner simplifies the tree first: bloom kill-paths
  and block-uniform leaves (stream filters after candidate pruning)
  fold to constants, so `{app="x"} "y" | stats count()` compiles to a
  single scan + reduction.  Bloom planning probes the part's packed
  bloom plane in one batch (storage/filterbank.py); when only SOME
  candidate blocks die, the plane is staged to HBM and the keep-mask
  is re-probed INSIDE the dispatch (tpu/bloom_device.py), gathered to
  rows through a staged block-id column and ANDed with the scan tree —
  the bloom kill bitmap never crosses the host boundary.

Reference parity: this is the TPU-shaped fusion of the reference's
per-worker stats shards merged at flush (pipe_stats.go:354-377) with
its batched block scanning (storage_search.go:1035-1121); the
correctness oracle is the CPU executor (tests/test_fused.py diffs
them bit-exactly over randomized query matrices).
"""

from __future__ import annotations

import math
import struct
import threading
from dataclasses import dataclass, field as dc_field
from functools import lru_cache

import jax
import numpy as np
from .. import config, sched

from ..logsql import filters as F
from ..obs import tracing
from ..storage.filterbank import bloom_keep_mask, filter_bank
from ..storage.values_encoder import VT_DICT, VT_STRING
from ..utils.hashing import cached_token_hashes
from . import kernels as K
from . import kernels32 as K32
from .batch import device_plan, StatsLayout
from .bloom_device import (MAX_PALLAS_PROBES, pad_probe_args, pad_sb_idx,
                           plane_keep, plane_keep_sb)
from .layout import (row_width_bucket, rows_with_multibyte, to_fixed_width,
                     to_lanes32)


# ---------------- layout-coordinate string staging ----------------

@dataclass
class FusedField:
    """One column staged over EVERY block of a part, layout coords."""
    rows: object                   # jax uint32[W/4, RLp/128, 128] planes
    lengths: object                # jax int32[RLp]
    width: int
    ovf_packed: object | None      # jax uint8[RLp//8] bit-packed overflow
    ovf_np: np.ndarray             # host bool[RLp] (residue bookkeeping)
    has_ovf: bool
    nbytes: int
    # the Pallas launcher's sweep tiles by their longest length: the
    # distinct lengths and how many tiles have each
    tile_max: tuple[np.ndarray, np.ndarray]
    # pattern length (None: `A.*B`) -> (steps swept, steps skipped)
    _steps: dict = dc_field(default_factory=dict, repr=False)

    def device_bytes(self) -> int:
        return self.nbytes

    def sweep_steps(self, pat_len: int | None) -> tuple[int, int]:
        """The plane steps a scan of pat_len bytes (None: an `A.*B`
        pair) sweeps over this column's tiles, each to its longest row,
        and the steps the column's whole width would have added
        (kernels32.sweep_steps); computed once a pattern length."""
        got = self._steps.get(pat_len)
        if got is None:
            lengths, tiles = self.tile_max
            nl = self.width // 4
            swept = int((K32.sweep_steps(lengths, nl, pat_len)
                         * tiles).sum())
            whole = K32.sweep_steps(None, nl, pat_len) * int(tiles.sum())
            got = self._steps[pat_len] = (swept, whole - swept)
        return got


def stage_layout_column(part, field: str, layout: StatsLayout,
                        max_bytes: int, put) -> FusedField | None:
    """Materialize `field` for all blocks into one (RLp, W) matrix.

    String blocks ride the native fixed-width transpose; dict blocks
    are gathered per code; const/missing blocks broadcast a template
    row ('' for missing — the host's value semantics for absent
    fields).  Returns None when any block is numeric/ipv4/ts-typed
    (the planner declines and the host evaluates the part) or the
    matrix would exceed max_bytes."""
    virtual = field in ("_stream", "_stream_id")
    plans = []        # (start, n, kind, payload)
    max_len = 0
    for bi in range(part.num_blocks):
        start = layout.starts[bi]
        n = part.block_rows(bi)
        if virtual:
            v = part.block_tags(bi) if field == "_stream" else \
                part.block_stream_id(bi).as_string()
            b = v.encode("utf-8", "replace")
            max_len = max(max_len, len(b))
            plans.append((start, n, "const", b))
            continue
        meta = part.block_column_meta(bi, field)
        if meta is None:
            consts = dict(part.block_consts(bi))
            b = consts.get(field, "").encode("utf-8", "replace")
            max_len = max(max_len, len(b))
            plans.append((start, n, "const", b))
            continue
        if meta["t"] == VT_STRING:
            col = part.block_column(bi, field)
            if col.lengths.size:
                max_len = max(max_len, int(col.lengths.max()))
            plans.append((start, n, "str", col))
        elif meta["t"] == VT_DICT:
            col = part.block_column(bi, field)
            enc = [v.encode("utf-8", "replace") for v in col.dict_values]
            if enc:
                max_len = max(max_len, max(len(b) for b in enc))
            plans.append((start, n, "dict", (col.ids, enc)))
        else:
            return None  # numeric/ipv4/ts block: host path decodes these
    w = row_width_bucket(max_len)
    rlp = layout.nrows_padded
    if rlp * (w + 4) > max_bytes:
        return None
    mat = np.full((rlp, w), 0xFF, dtype=np.uint8)
    lens = np.zeros(rlp, dtype=np.int32)
    ovf = np.zeros(rlp, dtype=bool)
    for start, n, kind, payload in plans:
        if kind == "str":
            col = payload
            sub, _w, ov = to_fixed_width(col.arena, col.offsets,
                                         col.lengths, n, width=w)
            mat[start:start + n] = sub
            lens[start:start + n] = np.minimum(col.lengths, w - 1)
            if ov.size:
                ovf[start + ov] = True
        elif kind == "dict":
            ids, enc = payload
            for code, b in enumerate(enc):
                sel = np.nonzero(ids == code)[0]
                if not sel.size:
                    continue
                cl = min(len(b), w - 1)
                row = np.full(w, 0xFF, dtype=np.uint8)
                row[:cl] = np.frombuffer(b[:cl], dtype=np.uint8)
                mat[start + sel] = row
                lens[start + sel] = cl
                if len(b) > w - 1:
                    ovf[start + sel] = True
        else:  # const ('' included)
            b = payload
            cl = min(len(b), w - 1)
            row = np.full(w, 0xFF, dtype=np.uint8)
            row[:cl] = np.frombuffer(b[:cl], dtype=np.uint8)
            mat[start:start + n] = row
            lens[start:start + n] = cl
            if len(b) > w - 1:
                ovf[start:start + n] = True
    has_ovf = bool(ovf.any())
    ovp = put(np.packbits(ovf)) if has_ovf else None
    _ts, sub = K32.sweep_blocks(w // 4, rlp // 128)
    tile_max = np.unique(lens.reshape(-1, sub * 128).max(axis=1),
                         return_counts=True)
    return FusedField(rows=put(to_lanes32(mat), row_axis=1),
                      lengths=put(lens), width=w,
                      ovf_packed=ovp, ovf_np=ovf, has_ovf=has_ovf,
                      nbytes=rlp * (w + 5), tile_max=tile_max)


@dataclass
class MultibyteMask:
    """Per-row 'contains a byte >= 0x80' flags for one column, packed.
    A static property of the part, computed host-side from the SOURCE
    values (so truncated tails count) and staged lazily the first time
    a len_range leaf needs it.  any=False => the column is pure ASCII
    and len_range is fully definitive on byte lengths."""
    packed: object | None          # jax uint8[RLp/8]; None when not any
    any: bool
    nbytes: int

    def device_bytes(self) -> int:
        return self.nbytes


def stage_multibyte_mask(part, field: str, layout: StatsLayout,
                         put) -> MultibyteMask:
    virtual = field in ("_stream", "_stream_id")
    mb = np.zeros(layout.nrows_padded, dtype=bool)
    for bi in range(part.num_blocks):
        start = layout.starts[bi]
        n = part.block_rows(bi)
        if virtual:
            v = part.block_tags(bi) if field == "_stream" else \
                part.block_stream_id(bi).as_string()
            if max(v.encode("utf-8", "replace"), default=0) >= 0x80:
                mb[start:start + n] = True
            continue
        meta = part.block_column_meta(bi, field)
        if meta is None:
            consts = dict(part.block_consts(bi))
            b = consts.get(field, "").encode("utf-8", "replace")
            if b and max(b) >= 0x80:
                mb[start:start + n] = True
            continue
        if meta["t"] == VT_STRING:
            col = part.block_column(bi, field)
            mb[start:start + n] = rows_with_multibyte(
                col.arena, col.offsets, col.lengths)
        elif meta["t"] == VT_DICT:
            col = part.block_column(bi, field)
            flags = np.array([bool(v.encode("utf-8", "replace") and
                                   max(v.encode("utf-8", "replace"))
                                   >= 0x80)
                              for v in col.dict_values], dtype=bool)
            if flags.any():
                mb[start:start + n] = flags[col.ids]
        # numeric/ipv4/ts blocks: canonical decimals are pure ASCII
    has = bool(mb.any())
    return MultibyteMask(packed=put(np.packbits(mb)) if has else None,
                         any=has,
                         nbytes=layout.nrows_padded // 8 if has else 64)


@dataclass
class _CandMask:
    packed: object                 # jax uint8[RLp/8]
    nbytes: int

    def device_bytes(self) -> int:
        return self.nbytes


@dataclass
class TsPlanes:
    """Block timestamps as two int32 planes (hi = off>>16, lo = off&0xFFFF)
    of ns offsets from the part minimum — exact int64 compares without
    x64 mode (a per-day partition's offsets fit 47 bits)."""
    hi: object
    lo: object
    base: int                      # part min ts (ns)
    nbytes: int

    def device_bytes(self) -> int:
        return self.nbytes


def stage_ts_planes(part, layout: StatsLayout, put) -> TsPlanes:
    off = np.zeros(layout.nrows_padded, dtype=np.int64)
    # single decode pass per block: base comes from the header min-ts
    base = min((part.block_min_ts(bi) for bi in range(part.num_blocks)),
               default=0)
    for bi in range(part.num_blocks):
        ts = part.block_timestamps(bi).astype(np.int64)
        start = layout.starts[bi]
        off[start:start + ts.shape[0]] = ts - base
    hi = (off >> 16).astype(np.int32)
    lo = (off & 0xFFFF).astype(np.int32)
    return TsPlanes(hi=put(hi), lo=put(lo), base=base,
                    nbytes=layout.nrows_padded * 8)


def _split_bound(v: int) -> tuple[int, int]:
    return int(v) >> 16, int(v) & 0xFFFF


# ---------------- planner: filter tree -> static program ----------------

class _NoFuse(Exception):
    pass


# The operand block: every small host-side operand of a dispatch (the
# live row count, time and range bounds, scan patterns) travels in ONE
# int32 numpy array, the jitted program's single host operand, so a
# dispatch makes one host-to-device transfer whatever the query holds.
# A tree node keeps the STATIC word offset of what it registered (the
# order of registration, so a function of the query's shape alone) and
# the program reads static slices of the block; pattern lengths are
# static in the tree already.  The length is bucketed, so no literal
# and no pattern length adds a shape to a program key.
BLOCK_NROWS = 0            # word 0 of every block: the layout's live rows
_BLOCK_MIN_WORDS = 32


class _Planner:
    """Walks the filter tree, staging what it needs and emitting a
    hashable program, the parallel list of device-resident arguments
    and the operand block of the host-side ones."""

    def __init__(self, runner, part, bss, layout):
        self.runner = runner
        self.part = part
        self.bss = bss
        self.layout = layout
        self.args: list = []
        self.arg_rows: list = []
        self._block = bytearray(struct.pack("<i", layout.nrows))
        self.field_slots: dict[str, int] = {}
        self.fields: list[FusedField] = []
        self.planes: dict[int, FusedField] = {}   # planes arg -> column
        self._slot_args: list = []
        self.ts_slot: tuple | None = None
        self.has_maybe = False

    def arg(self, a, row: int = 0) -> int:
        """Register a dynamic input; row marks row-aligned arrays that a
        mesh dispatch shards — recorded explicitly so sharding never
        relies on shape coincidences.  row=1 (or True): the row axis is
        axis 0 (RLp or RLp/8 leading dim); row=2: axis 1 (the
        uint32[W/4, RLp/128, 128] planes of the string staging)."""
        self.args.append(a)
        self.arg_rows.append(int(row))
        return len(self.args) - 1

    def host_words(self, *words: int) -> int:
        """Register host scalars in the operand block, each as the low
        32 bits of its two's complement (an int32 reads back as itself,
        a uint32 bound through a bitcast); returns the static word
        offset of the first."""
        off = len(self._block) // 4
        self._block += struct.pack(f"<{len(words)}I",
                                   *(w & 0xFFFFFFFF for w in words))
        return off

    def host_bytes(self, b: bytes) -> int:
        """Register a short byte string (a scan pattern), four bytes a
        little-endian word, zero-padded to a whole word; returns its
        static word offset (_block_bytes reads it back)."""
        off = len(self._block) // 4
        self._block += b
        self._block += bytes(-len(b) % 4)
        return off

    def block(self) -> np.ndarray:
        """The dispatch's operand block: int32[bucket], the bucket the
        power of two that holds the registered words (32 at least)."""
        nwords = len(self._block) // 4
        size = max(_BLOCK_MIN_WORDS, 1 << (nwords - 1).bit_length())
        return np.frombuffer(self._block + bytes(4 * (size - nwords)),
                             dtype="<i4")

    def field_slot(self, field: str) -> tuple[int, FusedField]:
        slot = self.field_slots.get(field)
        if slot is not None:
            return slot, self.fields[slot]
        ff = self.runner._stage_fused_field(self.part, field, self.layout)
        if ff is None:
            raise _NoFuse(field)
        ri = self.arg(ff.rows, row=2)
        self.planes[ri] = ff
        li = self.arg(ff.lengths, row=True)
        oi = self.arg(ff.ovf_packed, row=True) if ff.has_ovf else -1
        slot = len(self.fields)
        self.field_slots[field] = slot
        self.fields.append(ff)
        self._slot_args.append((ri, li, oi))
        if ff.has_ovf:
            self.has_maybe = True
        return slot, ff

    def slot_args(self, slot: int) -> tuple[int, int, int]:
        return self._slot_args[slot]

    # -- tree walk --

    def plan(self, f):
        if isinstance(f, F.FilterAnd):
            return self._combine("and", [self.plan(s) for s in f.filters])
        if isinstance(f, F.FilterOr):
            return self._combine("or", [self.plan(s) for s in f.filters])
        if isinstance(f, F.FilterNot):
            inner = self.plan(f.inner)
            if inner == ("true",):
                return ("false",)
            if inner == ("false",):
                return ("true",)
            return ("not", inner)
        if isinstance(f, F.FilterNoop):
            return ("true",)
        if isinstance(f, F.FilterNone):
            return ("false",)
        if isinstance(f, F.FilterTime):
            return self._time_leaf(f)
        if isinstance(f, (F.FilterStream, F.FilterStreamID,
                          F.FilterValueType)):
            return self._block_uniform_leaf(f)
        if isinstance(f, F.FilterRange):
            return self._numrange_leaf(f)
        if isinstance(f, F.FilterIn):
            return self._in_leaf(f)
        if isinstance(f, F.FilterLenRange):
            return self._lenrange_leaf(f)
        return self._scan_leaf(f)

    @staticmethod
    def _combine(op, kids):
        flat = []
        for k in kids:
            if op == "and":
                if k == ("false",):
                    return ("false",)
                if k == ("true",):
                    continue
            else:
                if k == ("true",):
                    return ("true",)
                if k == ("false",):
                    continue
            flat.append(k)
        if not flat:
            return ("true",) if op == "and" else ("false",)
        if len(flat) == 1:
            return flat[0]
        return (op, tuple(flat))

    def _time_leaf(self, f: F.FilterTime):
        ts = self.runner._stage_ts_planes(self.part, self.layout)
        if self.part.max_ts - ts.base >= (1 << 47):
            # the (hi >> 16) int32 plane is exact only below 2**47 ns
            # of offset (~39h).  Per-day parts never exceed it and
            # iter_pack_groups splits packs at PACK_TS_SPAN_MAX, so
            # this is a defensive decline (e.g. a part from a widened
            # retention layout), never a silent wrong compare.
            raise _NoFuse("ts-span")
        if self.ts_slot is None:
            hi = self.arg(ts.hi, row=True)
            lo = self.arg(ts.lo, row=True)
            self.ts_slot = (hi, lo)
        # clamp query bounds into the part's offset space; the leaf is
        # inclusive on both ends (FilterTime semantics)
        lo_off = max(0, f.min_ts - ts.base)
        hi_off = f.max_ts - ts.base
        if hi_off < 0 or lo_off >= (1 << 47):
            return ("false",)
        bounds = self.host_words(
            *_split_bound(lo_off),
            *_split_bound(min(hi_off, (1 << 47) - 1)))
        return ("time", self.ts_slot[0], self.ts_slot[1], bounds)

    def _block_uniform_leaf(self, f):
        """Per-block-constant filters (stream filters after candidate
        pruning; value_type, which depends only on the block's column
        encoding).  Uniform over the candidates -> constant; mixed -> a
        bit-packed row mask built host-side (cheap: range fills)."""
        truths = {}
        for bi, bs in self.bss.items():
            if isinstance(f, F.FilterStream):
                ctx = getattr(bs, "ctx", None)
                if ctx is None:
                    truths[bi] = True
                    continue
                sids = f.resolve(ctx.partition, ctx.tenants)
                truths[bi] = bs.stream_id in sids
            elif isinstance(f, F.FilterValueType):
                truths[bi] = bs.value_type_name(
                    F.canonical_field(f.field)) == f.type_name
            else:
                truths[bi] = bs.stream_id.as_string() in f._set
        vals = set(truths.values())
        if vals == {True}:
            return ("true",)
        if vals == {False}:
            return ("false",)
        m = np.zeros(self.layout.nrows_padded, dtype=bool)
        for bi, t in truths.items():
            if t:
                s = self.layout.starts[bi]
                m[s:s + self.part.block_rows(bi)] = True
        return ("maskleaf",
                self.arg(self.runner._put(np.packbits(m)), row=True))

    def _scan_leaf(self, f):
        plan = device_plan(f)
        if plan is None:
            raise _NoFuse(type(f).__name__)
        if plan.verify and plan.pair is None:
            raise _NoFuse("verify")          # multi-seq / impure regex
        if plan.field == "_time":
            raise _NoFuse("_time-as-string")
        # bloom kill-path: when a required token is absent from every
        # candidate block's bloom, the leaf is constant false — no scan.
        # And when bloom + candidate pruning leave only a small row
        # fraction, the host path over those few blocks beats staging +
        # whole-part scanning.
        # The probe is the packed-plane batch probe (filterbank); when
        # only SOME blocks die, the same plane is staged to HBM and the
        # kill bitmap ANDs into the tree inside the dispatch
        # (_bloom_node) — the device result needs no host mask.
        surv_rows = 0
        bloom_node = None
        if plan.bloom_tokens:
            hashes = cached_token_hashes(plan.filter, plan.bloom_tokens)
            bis = list(self.bss)
            keep = bloom_keep_mask(self.part, plan.field, hashes, bis)
            from ..storage.filterindex import part_index
            if part_index(self.part) is not None:
                # evidence the v2 MAPLET served the probe (exact keep
                # set, no plane build at all)
                self.runner._bump("maplet_probes")
            elif filter_bank(self.part).cached_plane(plan.field) \
                    is not None:
                # evidence the PLANE path served the probe (a declined
                # column rode the per-block fallback instead)
                self.runner._bump("bloom_plane_probes")
            for i, bi in enumerate(bis):
                if keep[i]:
                    surv_rows += self.part.block_rows(bi)
            if surv_rows == 0:
                return ("false",)
            if not keep.all():
                bloom_node = self._bloom_node(plan.field, hashes)
        else:
            surv_rows = sum(self.part.block_rows(bi) for bi in self.bss)
        if surv_rows * 8 < self.part.num_rows and \
                not self.runner.cache.contains(
                    (self.part.uid, "#fl", plan.field)):
            raise _NoFuse("narrow")
        slot, ff = self.field_slot(plan.field)
        ri, li, oi = self.slot_args(slot)
        if plan.pair is not None:
            a, b = plan.pair
            if max(len(a), len(b)) >= ff.width:
                return self._with_bloom(bloom_node, self._ovf_only(oi))
            self.has_maybe = True
            pa = self.host_bytes(a)
            pb = self.host_bytes(b)
            return self._with_bloom(
                bloom_node, ("pair", ri, li, oi, pa, len(a), pb, len(b)))
        # case-fold leaves: non-ASCII rows diverge from the byte fold in
        # either direction, so they ride the maybe channel (host residue
        # settles them with the filter's own predicate)
        mb_mi = -1
        if any(op.fold for op in plan.ops):
            mbm = self.runner._stage_multibyte(self.part, plan.field,
                                               self.layout)
            if mbm.any:
                mb_mi = self.arg(mbm.packed, row=True)
                self.has_maybe = True
        kids = []
        for op in plan.ops:
            if op.match_nonempty:
                kids.append(("nonempty", li))
            elif op.match_empty:
                # truncated rows have true length > W-1 > 0: never empty,
                # so the lengths compare is definitive even for overflow
                kids.append(("empty", li))
            elif len(op.pattern) >= ff.width:
                kids.append(self._ovf_only(oi))
            else:
                pi = self.host_bytes(op.pattern)
                kids.append(("scan", ri, li, oi,
                             mb_mi if op.fold else -1, pi,
                             len(op.pattern), op.mode, op.starts_tok,
                             op.ends_tok, op.fold))
        return self._with_bloom(bloom_node,
                                self._combine(plan.combine, kids))

    @staticmethod
    def _with_bloom(bloom_node, res):
        if bloom_node is None:
            return res
        return _Planner._combine("and", [bloom_node, res])

    def _bloom_node(self, field: str, hashes):
        """Emit the in-dispatch bloom kill: the packed plane rides HBM
        (staged once per part+column), the per-block keep-mask is
        probed INSIDE the fused jit from host-computed positions, and
        gathers to rows through the staged block-id column — so the
        bloom kill bitmap ANDs against the scan tree without any host
        round-trip.  None (leaf keeps host-planning semantics only)
        when staging declines or VL_DEVICE_BLOOM=0.

        Sealed parts with a v2 filter index ship the split-block
        layout instead (storage/filterindex): all 6 probe bits of a
        token live in one 256-bit block, so the device probe is ONE
        contiguous 8-lane gather + AND-compare per (block, token)
        (`bloom_sb` node, tpu/bloom_device.plane_keep_sb) instead of 6
        scattered lane selects."""
        if not config.env_flag("VL_DEVICE_BLOOM"):
            return None
        sb_node = self._bloom_sb_node(field, hashes)
        if sb_node is not None:
            return sb_node
        sp = self.runner._stage_bloom_plane(self.part, field)
        if sp is None:
            return None
        plb = filter_bank(self.part).plane(self.part, field)
        if plb is None:
            return None
        idx, shift = plb.block_probe_args(hashes)
        idx, shift = pad_probe_args(idx, shift, sp.bp)
        # the Pallas probe replaces the gather with a VMEM lane-select;
        # gated like kernels_pallas.match_scan, never on by default
        use_pallas = (self.runner.pallas_enabled()
                      and idx.shape[1] <= MAX_PALLAS_PROBES)
        bid = self.runner._stage_block_ids(self.part, self.layout)
        self.runner._kind("bloom_device")
        return ("bloom", self.arg(sp.plane), self.arg(sp.nwords),
                self.arg(idx), self.arg(shift),
                self.arg(bid.ids, row=True), use_pallas)

    def _bloom_sb_node(self, field: str, hashes):
        """The v2 split-block variant of _bloom_node, or None when the
        part has no valid sidecar for the column (classic plane path
        serves)."""
        from ..storage.filterindex import part_index
        fi = part_index(self.part)
        if fi is None or not fi.has_sb(field):
            return None
        sp = self.runner._stage_sb_plane(self.part, field)
        if sp is None:
            return None
        sbidx = pad_sb_idx(fi.sb_probe_idx(field, hashes), sp.bp)
        mask = fi.sb_masks(hashes)
        bid = self.runner._stage_block_ids(self.part, self.layout)
        self.runner._kind("bloom_sb_device")
        return ("bloom_sb", self.arg(sp.plane), self.arg(sp.nsb),
                self.arg(sbidx), self.arg(mask),
                self.arg(bid.ids, row=True))

    def _numrange_leaf(self, f: F.FilterRange):
        """`status:>=500`-family on int-typed columns: the uint32 offset
        staging the stats path already uses doubles as the compare
        operand (host analogue: FilterRange.apply_to_block's vectorized
        numeric branch).  Declines when any candidate block is not
        int-typed (string/float/missing: host semantics differ)."""
        from .stats_device import MAX_ABS_TIMES_ROWS
        field = F.canonical_field(f.field)
        if math.isnan(f.min_value) or math.isnan(f.max_value):
            raise _NoFuse("numrange-nan")
        sn = self.runner._stage_numeric(self.part, field, self.layout,
                                        MAX_ABS_TIMES_ROWS)
        if sn is None or any(bi not in sn.eligible for bi in self.bss):
            raise _NoFuse("numrange")
        # integer-exact bounds, mirroring the host's ceil/floor treatment;
        # +-inf saturates OUTWARD (>=inf matches nothing staged, <=-inf
        # likewise) — ceil/floor of an infinity would raise OverflowError
        lo = (-(1 << 62) if f.min_value < 0 else (1 << 62)) \
            if math.isinf(f.min_value) else math.ceil(f.min_value)
        hi = ((1 << 62) if f.max_value > 0 else -(1 << 62)) \
            if math.isinf(f.max_value) else math.floor(f.max_value)
        lo_off = lo - sn.vmin
        hi_off = hi - sn.vmin
        if lo_off > hi_off or hi_off < 0 or lo_off >= (1 << 32):
            return ("false",)
        lo_off = max(0, lo_off)
        hi_off = min(hi_off, (1 << 32) - 1)
        vi = self.arg(sn.values, row=True)
        return ("numrange", vi, self.host_words(lo_off, hi_off))

    def _lenrange_leaf(self, f: F.FilterLenRange):
        """len_range(lo, hi): rune counts equal byte lengths for pure
        ASCII, so the staged lengths decide those rows.  Multibyte rows
        (precomputed packed mask, a static property of the part) are
        ambiguous only inside [lo, 4*hi] bytes (codepoints <= bytes <=
        4*codepoints); a pure-ASCII column has no maybe rows at all.
        Truncated rows join the maybe set unless even the truncation
        floor (W-1 bytes) already exceeds 4*hi."""
        if f.max_len < max(0, f.min_len):
            return ("false",)
        field = F.canonical_field(f.field)
        if field == "_time":
            raise _NoFuse("_time-as-string")
        slot, ff = self.field_slot(field)
        _ri, li, oi = self.slot_args(slot)
        mbm = self.runner._stage_multibyte(self.part, field, self.layout)
        mi = self.arg(mbm.packed, row=True) if mbm.any else -1
        imax = (1 << 31) - 1
        bounds = self.host_words(min(max(0, f.min_len), imax),
                                 min(f.max_len, imax),
                                 min(4 * f.max_len, imax))
        # overflow rows whose true length must exceed 4*hi are
        # definitively false (their staged length W-1 > hi keeps d false)
        if ff.width - 1 > min(4 * f.max_len, imax):
            oi = -1
        if mi >= 0 or oi >= 0:
            self.has_maybe = True
        return ("lenrange", li, oi, mi, bounds)

    def _in_leaf(self, f: F.FilterIn):
        """`lvl:in(a, b, ...)` = OR of exact scans over the materialized
        matrix (dict/const blocks included)."""
        if f.subquery is not None and not f.values:
            raise _NoFuse("in-subquery")
        if len(f.values) > 16:
            raise _NoFuse("in-cardinality")
        field = F.canonical_field(f.field)
        if field == "_time":
            raise _NoFuse("_time-as-string")
        slot, ff = self.field_slot(field)
        ri, li, oi = self.slot_args(slot)
        kids = []
        for v in f.values:
            if not v:
                kids.append(("empty", li))
                continue
            if not v.isascii() or len(v) > K.MAX_PATTERN_LEN:
                raise _NoFuse("in-value")
            if len(v) >= ff.width:
                kids.append(self._ovf_only(oi))
                continue
            pi = self.host_bytes(v.encode())
            kids.append(("scan", ri, li, oi, -1, pi, len(v),
                         K.MODE_EXACT, False, False, False))
        return self._combine("or", kids)

    def _ovf_only(self, oi: int):
        """Pattern wider than the staging: no staged row can match; only
        overflow rows might."""
        if oi < 0:
            return ("false",)
        self.has_maybe = True
        return ("ovfmaybe", oi)


# ---------------- program names ----------------
#
# Every device program carries the name of what it scans, so a device
# profile reads by program kind (`jit_fused_phrase__count/fusion`) and
# not as one `jit__fused_dispatch`.  A name is a pure function of the
# query's SHAPE: the leaf kinds of the planned tree and the reduction.
# Never a literal, a size, a part or an id, so two parts of different
# size run under one name and the vocabulary stays closed: a family, a
# leaf part from _PRIMARY_LEAVES | _SECONDARY_LEAVES | {all, mixed},
# and for the stats family a reduction from REDUCTIONS.

_SCAN_MODE_NAMES = {K.MODE_PHRASE: "phrase", K.MODE_PREFIX: "prefix",
                    K.MODE_SUBSTRING: "substr", K.MODE_EXACT: "exact",
                    K.MODE_EXACT_PREFIX: "startswith"}
# leaves that scan a staged column: they name the program
_PRIMARY_LEAVES = ("phrase", "prefix", "substr", "exact", "startswith",
                   "regex", "numrange", "lenrange")
# per-row predicates over small planes: they name a program only when
# it has no primary leaf
_SECONDARY_LEAVES = ("time", "bloom", "mask", "empty", "ovf")
_NODE_LEAF = {"pair": "regex", "numrange": "numrange",
              "lenrange": "lenrange", "time": "time", "bloom": "bloom",
              "bloom_sb": "bloom", "maskleaf": "mask",
              "nonempty": "empty", "empty": "empty", "ovfmaybe": "ovf"}
REDUCTIONS = ("count", "stats", "group", "bucket", "uniq", "quantile")
FAMILIES = ("fused", "filter", "topk", "topk_seg")
MAX_NAMED_LEAVES = 3


def leaf_kind(node) -> str | None:
    """The vocabulary word of one tree node; None for and/or/not and
    the constants.  Also the node's named scope in the device profile."""
    if node[0] == "scan":
        return _SCAN_MODE_NAMES[node[7]]
    return _NODE_LEAF.get(node[0])


def _tree_leaves(node, out: list) -> None:
    kind = leaf_kind(node)
    if kind is not None:
        out.append(kind)
    elif node[0] == "not":
        _tree_leaves(node[1], out)
    elif node[0] in ("and", "or"):
        for k in node[1]:
            _tree_leaves(k, out)


@lru_cache(maxsize=1024)
def program_name(family: str, tree, reduction: str = "") -> str:
    """`<family>_<leaves>[__<reduction>]`: the distinct primary leaf
    kinds in vocabulary order (the secondary ones when there is no
    primary leaf, `all` for a constant tree); a tree of more than
    MAX_NAMED_LEAVES such leaves folds to `mixed`."""
    leaves: list = []
    _tree_leaves(tree, leaves)
    for vocab in (_PRIMARY_LEAVES, _SECONDARY_LEAVES):
        mine = [k for k in leaves if k in vocab]
        if mine:
            part = "mixed" if len(mine) > MAX_NAMED_LEAVES else \
                "_".join(k for k in vocab if k in mine)
            break
    else:
        part = "all"
    name = f"{family}_{part}"
    return f"{name}__{reduction}" if reduction else name


@lru_cache(maxsize=1024)
def plane_scan_leaves(tree) -> int:
    """How many of the tree's leaves run the plane kernel
    (tpu/kernels32.py): the window scans and the `A.*B` pairs."""
    leaves: list = []
    _tree_leaves(tree, leaves)
    return sum(k == "regex" or k in _SCAN_MODE_NAMES.values()
               for k in leaves)


def _swept_leaves(node, out: list) -> list:
    """(planes arg, pattern length or None for `A.*B`) of each leaf
    whose sweep stops at its tiles' longest row: the scans but the
    window-0 modes, and the pairs."""
    if node[0] == "scan":
        if node[7] not in (K.MODE_EXACT, K.MODE_EXACT_PREFIX):
            out.append((node[1], node[6]))
    elif node[0] == "pair":
        out.append((node[1], None))
    elif node[0] == "not":
        _swept_leaves(node[1], out)
    elif node[0] in ("and", "or"):
        for k in node[1]:
            _swept_leaves(k, out)
    return out


@lru_cache(maxsize=1024)
def swept_leaves(tree) -> tuple:
    """_swept_leaves of a planned tree, once a tree."""
    return tuple(_swept_leaves(tree, []))


def _bump_plane_scans(runner, planner, tree) -> None:
    """plane_scan_leaves, and the plane steps those leaves sweep and skip
    (scan_plane_steps_swept / _skipped; FusedField.sweep_steps, a dict
    lookup a leaf once a column has seen the pattern length).  Where the
    direct launcher runs the body (off the TPU, under a mesh axis) every
    step is swept."""
    runner._bump("plane_scan_leaves", plane_scan_leaves(tree))
    swept = skipped = 0
    for ri, pat_len in swept_leaves(tree):
        sw, sk = planner.planes[ri].sweep_steps(pat_len)
        swept += sw
        skipped += sk
    if not runner.sweeps_bounded():
        swept, skipped = swept + skipped, 0
    runner._bump("scan_plane_steps_swept", swept)
    runner._bump("scan_plane_steps_skipped", skipped)


def stats_reduction(spec, n_values: int) -> str:
    """The reduction word of a fused stats dispatch, from the stats
    spec's shape (the pack's segment axis is not a grouping)."""
    if spec.uniq_fields:
        return "uniq"
    if spec.quantile_fields:
        return "quantile"
    by = [b.kind for b in spec.by if b.kind != "seg"]
    if "time" in by:
        return "bucket"
    if by:
        return "group"
    return "stats" if n_values else "count"


_programs: dict = {}
_programs_mu = threading.Lock()


def _program(name: str, body, jit):
    """The one jitted callable of a program name: `body` under that
    name, so the compiled module is `jit_<name>`.  Looked up on every
    dispatch (a dict read); built once, under the lock.  Each callable
    keeps the body's static keys, so what compiles is what compiled
    under the single name: one program per (name, static key)."""
    fn = _programs.get(name)
    if fn is None:
        with _programs_mu:
            fn = _programs.get(name)
            if fn is None:
                def call(*args):
                    return body(*args)
                call.__name__ = call.__qualname__ = name
                fn = _programs[name] = jit(call)
    return fn


def _launch(runner, dispatch, *args):
    """The jitted call, until it returns its async handles: the
    `launch` child of the pipeline's `submit` span.  The operand block
    among `args` is the call's one host operand, transferred inside it
    (as numpy, by jit's own argument path); the runner counts the call
    as one `operand_blocks`.  On a trace the span carries
    `device_queue_depth`: the scheduler's leased slots (dispatch units
    submitted and not yet harvested, process-wide) at this instant,
    this unit's own lease left out."""
    with tracing.current_span().span("launch") as sp:
        if sp.enabled:
            sp.set("device_queue_depth",
                   max(0, sched.scheduler().in_flight() - 1))
        out = dispatch(*args)
    runner._bump("operand_blocks")
    return out


# ---------------- the jitted program evaluator ----------------

def _unpack_bits(packed, n):
    import jax.numpy as jnp
    bits = jnp.unpackbits(packed)
    return bits[:n].astype(jnp.bool_)


def _block_bytes(blk, off: int, n: int):
    """The uint8[n] byte string the planner registered at word `off` of
    the operand block (_Planner.host_bytes): a static slice, the words
    bitcast back to their little-endian bytes."""
    import jax.numpy as jnp
    words = blk[off:off + (n + 3) // 4]
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(-1)[:n]


def _eval_node(node, args, blk, rlp):
    """Recursive (definite, maybe) evaluation; maybe may be None (==0).
    args: the device-resident arguments, blk: the operand block (the
    host-side scalars and patterns, at the static offsets the nodes
    carry).  Each leaf evaluates under a named scope of its kind
    (leaf_kind), so the profile's op metadata says which leaf an
    operation belongs to."""
    kind = leaf_kind(node)
    if kind is None:
        return _eval_tree_node(node, args, blk, rlp)
    with jax.named_scope(kind):
        return _eval_tree_node(node, args, blk, rlp)


def _eval_tree_node(node, args, blk, rlp):
    import jax.numpy as jnp
    kind = node[0]
    if kind == "true":
        return jnp.ones(rlp, dtype=bool), None
    if kind == "false":
        return jnp.zeros(rlp, dtype=bool), None
    if kind == "maskleaf":
        return _unpack_bits(args[node[1]], rlp), None
    if kind == "nonempty":
        return args[node[1]] > 0, None
    if kind == "empty":
        return args[node[1]] == 0, None
    if kind == "ovfmaybe":
        ov = _unpack_bits(args[node[1]], rlp)
        return jnp.zeros(rlp, dtype=bool), ov
    if kind == "bloom":
        # per-block keep-mask probed from the HBM-resident bloom plane,
        # gathered to rows via the block-id column (tpu/bloom_device.py)
        _, pi, nwi, ii, si, bidi, use_pallas = node
        keep = plane_keep(args[pi], args[ii], args[si], args[nwi],
                          use_pallas=use_pallas)
        return keep[args[bidi]], None
    if kind == "bloom_sb":
        # split-block layout (sealed-part filter index v2): one
        # contiguous 8-lane gather + AND-compare per (block, token)
        _, pi, ni, ii, mi, bidi = node
        keep = plane_keep_sb(args[pi], args[ii], args[mi], args[ni])
        return keep[args[bidi]], None
    if kind == "lenrange":
        _, li, oi, mi, bounds = node
        lens = args[li]
        lo, hi, hi4 = blk[bounds], blk[bounds + 1], blk[bounds + 2]
        d = (lens >= lo) & (lens <= hi)
        may = None
        if mi >= 0:
            multibyte = _unpack_bits(args[mi], rlp)
            may = multibyte & (lens >= lo) & (lens <= hi4)
        if oi >= 0:
            ov = _unpack_bits(args[oi], rlp)
            may = ov if may is None else may | ov
        if may is None:
            return d, None
        return d & ~may, may
    if kind == "numrange":
        _, vi, bounds = node
        v = args[vi]
        lo, hi = jax.lax.bitcast_convert_type(blk[bounds:bounds + 2],
                                              jnp.uint32)
        return (v >= lo) & (v <= hi), None
    if kind == "time":
        _, hi_i, lo_i, bounds = node
        hi, lo = args[hi_i], args[lo_i]
        lo_hi, lo_lo, hi_hi, hi_lo = blk[bounds:bounds + 4]
        ge = (hi > lo_hi) | ((hi == lo_hi) & (lo >= lo_lo))
        le = (hi < hi_hi) | ((hi == hi_hi) & (lo <= hi_lo))
        return ge & le, None
    if kind == "scan":
        _, ri, li, oi, mi, pi, plen, mode, st, et, fold = node
        m = K32.match_scan_t(args[ri], args[li],
                             _block_bytes(blk, pi, plen), plen, mode, st,
                             et, fold)
        may = None
        if oi >= 0:
            may = _unpack_bits(args[oi], rlp)
        if mi >= 0:
            mb = _unpack_bits(args[mi], rlp)
            may = mb if may is None else may | mb
        if may is None:
            return m, None
        return m & ~may, may
    if kind == "pair":
        _, ri, li, oi, pa, la, pb, lb = node
        definite, needsv = K32.match_ordered_pair_t(
            args[ri], args[li], _block_bytes(blk, pa, la), la,
            _block_bytes(blk, pb, lb), lb)
        may = needsv
        if oi >= 0:
            ov = _unpack_bits(args[oi], rlp)
            definite = definite & ~ov
            may = may | ov
        return definite, may
    if kind == "not":
        d, m = _eval_node(node[1], args, blk, rlp)
        if m is None:
            return ~d, None
        return ~(d | m), m
    # and / or
    kids = [_eval_node(k, args, blk, rlp) for k in node[1]]
    if kind == "and":
        d = kids[0][0]
        pos = d if kids[0][1] is None else d | kids[0][1]
        for kd, km in kids[1:]:
            d = d & kd
            pos = pos & (kd if km is None else kd | km)
        may = pos & ~d
        return d, (None if all(km is None for _, km in kids) else may)
    d = kids[0][0]
    pos = d if kids[0][1] is None else d | kids[0][1]
    for kd, km in kids[1:]:
        d = d | kd
        pos = pos | (kd if km is None else kd | km)
    may = pos & ~d
    return d, (None if all(km is None for _, km in kids) else may)


def _seg_base_ids(ids_tuple, strides):
    """Combined BASE bucket ids of a seg-major dispatch (everything
    after the leading segment axis; a seg-only grouping has base 0)."""
    import jax.numpy as jnp
    if len(ids_tuple) == 1:
        return jnp.zeros(ids_tuple[0].shape[0], dtype=jnp.int32)
    return K.combine_ids(ids_tuple[1:], strides[1:])


def _fused_local(prog, strides, nb, n_values, axis, blk, cand_packed,
                 seg_map, ids_tuple, values_tuple, args):
    """The fused program body, single-device or per-shard.

    axis: None for single-device execution; a mesh axis name when
    running inside shard_map — row-sized inputs arrive as this shard's
    stripe, stats reduce with psum/pmin/pmax over ICI, and the row
    index for the rows<nrows candidate form is offset by the shard's
    global position.

    Packed super-dispatches (prog carries nseg > 0): ids_tuple[0] is
    the per-row segment ids and the reduction runs SEGMENT-MAJOR
    (tpu/stats_seg.py) — the bucket one-hot stays at the base product
    nb // nseg instead of widening to the full nb, and the flattened
    [S, base] result is bit-identical to the widened combined-id form
    (the seg axis led the by order with stride == base)."""
    import jax.numpy as jnp
    tree, _rlp_global, has_maybe, has_cand = prog[:4]
    nseg = prog[5] if len(prog) > 5 else 0
    seg_pallas = prog[6] if len(prog) > 6 else False
    rl = ids_tuple[0].shape[0]         # LOCAL rows (== global w/o axis)
    d, m = _eval_node(tree, args, blk, rl)
    if has_cand:
        cand = _unpack_bits(cand_packed, rl)
    else:
        idx = jnp.arange(rl, dtype=jnp.int32)
        if axis is not None:
            idx = idx + jax.lax.axis_index(axis) * rl
        cand = idx < blk[BLOCK_NROWS]
    d = d & cand
    with jax.named_scope("stats"):
        flat = _fused_reduce(strides, nb, n_values, axis, nseg,
                             seg_pallas, seg_map, ids_tuple,
                             values_tuple, d)
    # the maybe-any flag rides INSIDE the stats download so the host can
    # skip the packed-maybe transfer entirely in the common no-maybe case
    if has_maybe and m is not None:
        mc = m & cand
        many = jnp.any(mc).astype(jnp.uint32)
        if axis is not None:
            many = jax.lax.psum(many, axis)    # nonzero iff any shard hit
        mp = jnp.packbits(mc.astype(jnp.uint8))
    else:
        many = jnp.uint32(0)
        mp = jnp.zeros(1, dtype=jnp.uint8)
        if axis is not None:
            mp = K._vary(mp, (axis,))
    return jnp.concatenate([flat, many[None]]), mp


def _fused_reduce(strides, nb, n_values, axis, nseg, seg_pallas, seg_map,
                  ids_tuple, values_tuple, d):
    """The stats reduction of _fused_local over the definite rows `d`:
    the flat partials, count-only uint32[nb] or uint32[n_values*7*nb]."""
    import jax.numpy as jnp
    vary = (axis,) if axis is not None else ()
    if nseg:
        from . import stats_seg as SS
        seg = ids_tuple[0]
        base = _seg_base_ids(ids_tuple, strides)
        nb_base = nb // nseg
        if axis is None and not seg_pallas:
            # single-device: the segment-ALIGNED slot grid — each
            # member reduces only its own padded slots (total work ~the
            # members' rows, not S * R); bit-identical to the striped
            # form below
            if n_values == 0:
                flat = SS.stats_count_slots(seg_map, base, d, nb_base)
            else:
                outs = [K.pack_stats(*SS.stats_values_slots(
                    v, seg_map, base, d, nb_base))
                    for v in values_tuple]
                flat = jnp.stack(outs, axis=0).reshape(-1)
        elif n_values == 0:
            # mesh stripes (manual shard_map rows can't gather the
            # global slot grid) and the VL_PALLAS count variant ride
            # the row-striped seg kernels
            flat = SS.stats_count_seg_local(seg, base, d, nseg, nb_base,
                                            vary_axes=vary,
                                            use_pallas=seg_pallas)
            if axis is not None:
                flat = jax.lax.psum(flat, axis)
        else:
            outs = []
            for v in values_tuple:
                cnt, sums, lo, hi = SS.stats_values_seg_local(
                    v, seg, base, d, nseg, nb_base, vary_axes=vary)
                if axis is not None:
                    cnt = jax.lax.psum(cnt, axis)
                    sums = jax.lax.psum(sums, axis)
                    lo = jax.lax.pmin(lo, axis)
                    hi = jax.lax.pmax(hi, axis)
                outs.append(K.pack_stats(cnt, sums, lo, hi))
            flat = jnp.stack(outs, axis=0).reshape(-1)
    elif n_values == 0:
        ids = K.combine_ids(ids_tuple, strides)
        flat = K.stats_count_local(ids, d, nb, vary_axes=vary)
        if axis is not None:
            flat = jax.lax.psum(flat, axis)
    else:
        ids = K.combine_ids(ids_tuple, strides)
        outs = []
        for v in values_tuple:
            cnt, sums, lo, hi = K.stats_values_local(v, ids, d, nb,
                                                     vary_axes=vary)
            if axis is not None:
                cnt = jax.lax.psum(cnt, axis)
                sums = jax.lax.psum(sums, axis)
                lo = jax.lax.pmin(lo, axis)
                hi = jax.lax.pmax(hi, axis)
            outs.append(K.pack_stats(cnt, sums, lo, hi))
        flat = jnp.stack(outs, axis=0).reshape(-1)
    return flat


def _fused_dispatch(prog, strides, nb, n_values, blk, cand_packed,
                    seg_map, ids_tuple, values_tuple, args):
    """One device call: filter tree -> stats partials (+ packed maybe).
    Jitted under its program name by fused_program().

    prog: (tree, rlp, has_maybe, has_cand, arg_rows[, nseg,
    seg_pallas]) — static, hashable; arg_rows marks which leaf args are
    row-aligned (mesh sharding); nseg > 0 marks a packed super-dispatch
    (seg-major reduction, tpu/stats_seg.py).
    blk: the operand block (_Planner.block), the call's one host
    operand: word BLOCK_NROWS is the live row count (rows below it are
    live when cand_packed is None-shaped), the rest the tree's scalars
    and patterns; cand_packed: uint8[RLp/8] or zeros(1) when unused;
    seg_map: the pack's int32[S, Lp] slot grid (zeros(1, 1) stub when
    nseg == 0).
    Returns (flat, maybe_packed): flat is uint32[nb + 1] for count-only
    or uint32[n_values*7*nb + 1] — the trailing element is the
    maybe-any flag; maybe_packed is uint8[RLp/8] (zeros(1) when the
    program proves no maybe rows exist) and is only worth downloading
    when the flag is nonzero."""
    return _fused_local(prog, strides, nb, n_values, None, blk,
                        cand_packed, seg_map, ids_tuple, values_tuple,
                        args)


def fused_program(name: str):
    return _program(name, _fused_dispatch, lambda fn: jax.jit(
        fn, static_argnums=(0, 1, 2, 3)))


def _fused_dispatch_mesh(mesh, axis, prog, strides, nb, n_values, blk,
                         cand_packed, seg_map, ids_tuple, values_tuple,
                         args):
    """The fused program under shard_map: each device evaluates the tree
    over its row stripe; stats partials psum/pmin/pmax over ICI; the
    packed maybe-vector concatenates along the row axis.  This is the
    multi-chip product form of the reference's mergeState split
    (pipe_stats.go:55-60) — one SPMD dispatch, in-network reduction.
    The seg slot grid is unused here (manual row stripes cannot gather
    global rows; the striped seg kernels serve) — it ships replicated
    as an inert operand so the submit path stays uniform."""
    from jax.sharding import PartitionSpec as P
    has_cand = prog[3]
    arg_rows = prog[4]
    # roles are explicit: the operand block is replicated; the planner
    # marked row-aligned leaf args; ids/values axes are always
    # row-aligned; cand is row-aligned only when a real candidate mask
    # was shipped (else it is a zeros(1) stub)
    in_specs = (P(), P(axis) if has_cand else P(), P(None, None),
                tuple(P(axis) for _ in ids_tuple),
                tuple(P(axis) for _ in values_tuple),
                tuple(P(None, axis) if r == 2 else
                      (P(axis) if r else P()) for r in arg_rows))

    def fn(b, cp, sm, ids, vals, leaf_args):
        return _fused_local(prog, strides, nb, n_values, axis, b,
                            cp, sm, ids, vals, leaf_args)

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=(P(), P(axis)))(
        blk, cand_packed, seg_map, ids_tuple, values_tuple, args)


def fused_mesh_program(name: str):
    return _program(name + "_mesh", _fused_dispatch_mesh, lambda fn: jax.jit(
        fn, static_argnums=(0, 1, 2, 3, 4, 5)))


# ---------------- residue: host settles the maybe rows ----------------

def _residue_partials(f, bss, spec, layout, maybe_np: np.ndarray,
                      part=None) -> list:
    """Verify maybe rows with the filters' own host path and emit one
    partial per surviving row, keyed exactly like the device cells.

    part: the dispatched part — only consulted for 'seg' by-keys, whose
    component is the packed part's member ordinal for the block
    (PackedPart.segment_of_block)."""
    from ..logsql.matchers import parse_number
    from ..logsql.stats_funcs import format_number
    from .stats_device import SYNTH_EMPTY, SYNTH_LEN
    partials = []
    for bi, bs in bss.items():
        start = layout.starts[bi]
        n = bs.nrows
        sel = maybe_np[start:start + n]
        if not sel.any():
            continue
        bm = sel.copy()
        f.apply_to_block(bs, bm)
        rows = np.nonzero(bm)[0]
        if not rows.size:
            continue
        ts = None
        val_cache: dict[str, list] = {}

        def vals(field):
            got = val_cache.get(field)
            if got is None:
                got = val_cache[field] = bs.values(field)
            return got

        for i in rows:
            key_parts = []
            uniq = {}
            for bk in spec.by:
                if bk.kind == "seg":
                    key_parts.append(("s", part.segment_of_block(bi)))
                elif bk.kind == "time":
                    if ts is None:
                        ts = bs.timestamps()
                    t = int(ts[i])
                    vb = (t - bk.offset) // bk.step * bk.step + bk.offset
                    key_parts.append(("t", vb))
                elif bk.kind == "numbucket":
                    v = parse_number(vals(bk.name)[i])
                    vb = np.floor((v - bk.foff) / bk.fstep) * bk.fstep \
                        + bk.foff
                    key_parts.append(("v", format_number(vb)))
                else:
                    key_parts.append(("v", vals(bk.name)[i]))
            for fld in spec.uniq_fields:
                uniq[fld] = vals(fld)[i]
            qv = {}
            for fld in spec.quantile_fields:
                qv[fld] = parse_number(vals(fld)[i])
            fs = {}
            for fld in spec.value_fields:
                if fld.startswith(SYNTH_LEN):
                    v = len(vals(fld[len(SYNTH_LEN):])[i])
                elif fld.startswith(SYNTH_EMPTY):
                    v = 1 if vals(fld[len(SYNTH_EMPTY):])[i] == "" else 0
                else:
                    v = int(vals(fld)[i])
                fs[fld] = (v, v, v)
            partials.append((tuple(key_parts), 1, fs, uniq, qv))
    return partials


# ---------------- entry ----------------

def _stage_cand_mask(runner, part, bss, layout):
    """Candidate-row mask for a dispatch: all-blocks-candidate uses the
    cheap rows<nrows form (no upload); partial candidate sets ship as
    packed bits, cached per (part, block-set)."""
    all_cand = len(bss) == part.num_blocks
    if all_cand:
        return runner._stub((1,), np.uint8), False
    ckey = (part.uid, "#cand", tuple(sorted(bss)))
    with runner._key_lock(ckey):
        cm = runner.cache.get(ckey)
        if cm is None:
            m = np.zeros(layout.nrows_padded, dtype=bool)
            for bi in bss:
                s = layout.starts[bi]
                m[s:s + part.block_rows(bi)] = True
            cm = _CandMask(packed=runner._put(np.packbits(m)),
                           nbytes=layout.nrows_padded // 8)
            runner.cache.put(ckey, cm)
    return cm.packed, True


class _Ready:
    """A pending-result shim for values already materialized (constant
    trees, host-gated parts): harvest() is a no-op handoff, so callers
    drive one protocol whether or not a dispatch is in flight."""

    __slots__ = ("_value",)

    def __init__(self, value):
        self._value = value

    def harvest(self, sync=None):
        return self._value


class _StatsPending:
    """An in-flight fused filter|stats dispatch.

    Holds the asynchronous jax result arrays; nothing blocks until
    harvest(), so a caller can keep several parts' dispatches
    outstanding (tpu/pipeline.py) and materialize them in submission
    order.  sync: host-materialization hook (np.asarray semantics) —
    the pipeline passes a timed wrapper so host-sync wait is counted."""

    __slots__ = ("runner", "f", "part", "bss", "spec", "asm", "handled",
                 "flat", "mp")

    def __init__(self, runner, f, part, bss, spec, asm, handled, flat,
                 mp):
        self.runner = runner
        self.f = f
        self.part = part
        self.bss = bss
        self.spec = spec
        self.asm = asm
        self.handled = handled
        self.flat = flat
        self.mp = mp

    def harvest(self, sync=None):
        sync = sync or np.asarray
        asm, spec = self.asm, self.spec
        flat = np.asarray(sync(self.flat))
        any_maybe = bool(flat[-1])
        if spec.value_fields:
            stats = flat[:-1].reshape(len(spec.value_fields), 7, asm.nb)
            counts = stats[0][0]
            stats_np = {fld: stats[k] for k, fld in
                        enumerate(spec.value_fields)}
        else:
            counts = flat[:-1]
            stats_np = {}
        partials = self.runner._partials_from_counts(asm, counts,
                                                     stats_np)
        if any_maybe:
            maybe_np = np.unpackbits(np.asarray(sync(self.mp))) \
                [:asm.layout.nrows_padded].astype(bool)
            partials.extend(_residue_partials(self.f, self.bss, spec,
                                              asm.layout, maybe_np,
                                              part=self.part))
        return {}, self.handled, partials


def fused_stats_submit(runner, f, part, bss, spec, asm):
    """Plan + DISPATCH the single fused filter|stats program without
    materializing anything; returns a pending handle (harvest() ->
    (bms, handled, partials)) or None when the shape declines.

    asm: the runner's assembled stats axes (AxesAssembly).  Requires
    every candidate block to be stats-eligible (the fused path never
    routes blocks through the row pipeline)."""
    layout = asm.layout
    if any(any(bi not in el for el in asm.eligibility) for bi in bss):
        return None
    # `args`: the host-side argument build (plan, staging lookups, the
    # candidate mask, the operand block: numpy and bytes only, no jax
    # op); `launch` (_launch): the jitted call until it returns its
    # async handles
    with tracing.current_span().span("args"):
        planner = _Planner(runner, part, bss, layout)
        try:
            tree = planner.plan(f)
        except _NoFuse:
            return None

        handled = set(bss)
        if tree == ("false",):
            return _Ready(({}, handled, []))

        cand_packed, has_cand = _stage_cand_mask(runner, part, bss,
                                                 layout)
        # prog slots 5/6: segment count of a packed super-dispatch and
        # the VL_PALLAS gate for the seg-major count kernel — static, so
        # the jitted program specializes per (pack size, gate) like
        # every other static knob (stats_seg.py)
        seg_pallas = bool(asm.nseg) and runner.pallas_enabled()
        prog = (tree, layout.nrows_padded, planner.has_maybe, has_cand,
                tuple(planner.arg_rows), asm.nseg, seg_pallas)
        seg_map = runner._stage_seg_slots(part, layout).ids if asm.nseg \
            else runner._stub((1, 1), np.int32)
        values_tuple = tuple(asm.numerics[fld].values
                             for fld in spec.value_fields)
        name = program_name("fused", tree,
                            stats_reduction(spec, len(values_tuple)))
        blk = planner.block()
    runner._bump("device_calls")
    _bump_plane_scans(runner, planner, tree)
    runner._bump("stats_dispatches")
    runner._bump("fused_dispatches")
    runner._bump_max("stats_onehot_width",
                     asm.nb // max(asm.nseg, 1))
    runner._kind("fused_stats")
    if asm.nseg:
        runner._kind("fused_stats_seg")
    if spec.uniq_fields:
        runner._kind("fused_uniq")
    if spec.quantile_fields:
        runner._kind("fused_quantile")
    flat, mp = _launch(
        runner, runner._dispatch_fused, name, prog, asm.strides, asm.nb,
        len(values_tuple), blk, cand_packed, seg_map, asm.ids_tuple,
        values_tuple, tuple(planner.args))
    return _StatsPending(runner, f, part, bss, spec, asm, handled, flat,
                         mp)


# ---------------- fused filter | sort-topk prefilter ----------------

def _topk_dispatch(prog, k, desc, nseg, blk, cand_packed, seg_ids,
                   seg_map, values, args):
    """One device call: filter tree -> top-k threshold -> packed row sets.

    values: uint32[RLp] offsets from the part's column minimum (the same
    staging the stats path uses); the threshold is the k-th best key
    among DEFINITE filter matches, and the return is
    (packed definite rows >= threshold, packed maybe rows >= threshold)
    — see sort_device.py for the soundness argument.  Scores ride int32
    (eligibility caps vmax-vmin below 2**31-2); -1 marks non-candidates,
    so a part with fewer than k matches degenerates to the full match
    set.  Runs unchanged over mesh-sharded inputs (GSPMD inserts the
    top_k gather; only the packed bits come back).

    nseg > 0: a packed super-dispatch — members gather into their own
    padded rows of the seg slot grid (seg_map int32[S, Lp], Lp >= k;
    stats_seg.build_seg_slot_map) and ONE batched lax.top_k over the
    slot axis yields every member's k-th-best threshold at once, which
    scatters back per row through seg_ids.  Each member gets exactly
    the threshold its own single-part dispatch would have computed
    (padding slots score -1, the same sentinel as non-matches), so the
    harvested per-member candidate sets are bit-identical to the
    serial per-part walk — and the k-selection work is the members'
    own padded slots, LESS than a per-part dispatch's chunk-padded
    scan.  nseg == 0: seg_ids/seg_map are ignored zeros stubs.
    """
    import jax.numpy as jnp
    tree, _rlp, has_maybe, has_cand = prog[:4]
    rl = values.shape[0]
    d, m = _eval_node(tree, args, blk, rl)
    if has_cand:
        cand = _unpack_bits(cand_packed, rl)
    else:
        cand = jnp.arange(rl, dtype=jnp.int32) < blk[BLOCK_NROWS]
    d = d & cand
    mv = (m & cand) if (has_maybe and m is not None) else None
    with jax.named_scope("topk"):
        out_d, out_m = _topk_select(k, desc, nseg, seg_ids, seg_map,
                                    values, d, mv)
    return (jnp.packbits(out_d.astype(jnp.uint8)),
            jnp.packbits(out_m.astype(jnp.uint8)))


def _topk_select(k, desc, nseg, seg_ids, seg_map, values, d, mv):
    """_topk_dispatch's reduction: (definite, maybe) rows at or above
    the k-th best key among the definite matches `d`."""
    import jax.numpy as jnp
    rl = values.shape[0]
    v = values.astype(jnp.int32)
    if not desc:
        v = jnp.int32((1 << 31) - 2) - v   # ascending: reverse the order
    if nseg == 0:
        s = jnp.where(d, v, jnp.int32(-1))
        kv = jax.lax.top_k(s, k)[0][k - 1]
        out_d = d & (s >= kv)
        if mv is not None:
            out_m = mv & (jnp.where(mv, v, jnp.int32(-1)) >= kv)
        else:
            out_m = jnp.zeros(rl, dtype=bool)
    else:
        s = jnp.where(d, v, jnp.int32(-1))
        safe = jnp.maximum(seg_map, 0)
        s2 = jnp.where(seg_map >= 0, s[safe], jnp.int32(-1))
        kv = jax.lax.top_k(s2, k)[0][:, k - 1]       # (S,) thresholds
        thr = kv[seg_ids.astype(jnp.int32)]          # scatter per row
        out_d = d & (s >= thr)
        if mv is not None:
            out_m = mv & (v >= thr)
        else:
            out_m = jnp.zeros(rl, dtype=bool)
    return out_d, out_m


def topk_program(name: str):
    return _program(name, _topk_dispatch, lambda fn: jax.jit(
        fn, static_argnums=(0, 1, 2, 3)))


def fused_topk_submit(runner, f, part, bss, spec):
    """Plan + DISPATCH the filter|sort-topk program without
    materializing anything; returns a pending handle (harvest() ->
    block_idx -> bitmap, the _FilterPending protocol — maybe rows above
    threshold settle through the filter's own host predicate), a _Ready
    result for constant-false trees, or None when the shape declines
    (the caller hands the part to BatchRunner.run_part).

    part may be a PackedPart (tpu/pipeline.py): its per-row segment ids
    stage like the stats seg axis and the dispatch k-selects per
    member, so flush-sized parts under `sort | head` stop paying one
    dispatch each."""
    from .stats_device import MAX_ABS_TIMES_ROWS, MAX_STAT_ROWS
    with tracing.current_span().span("args"):
        layout = runner._stats_layout(part)
        if layout.nrows > MAX_STAT_ROWS:
            return None
        sn = runner._stage_numeric(part, spec.field, layout,
                                   MAX_ABS_TIMES_ROWS)
        if sn is None or any(bi not in sn.eligible for bi in bss):
            return None
        if sn.vmax - sn.vmin > (1 << 31) - 2:
            return None                # int32 score space
        k = min(spec.k, layout.nrows_padded)
        nseg = 0
        seg_ids = runner._stub((1,), np.int32)
        seg_map = runner._stub((1, 1), np.int32)
        if getattr(part, "num_segments", 0) > 1:
            sg = runner._stage_segments(part, layout)
            if sg is None:
                return None
            nseg = len(sg.values)
            seg_ids = sg.ids
            # the slot grid needs >= k slots per member for the batched
            # k-selection (padding slots carry the -1 sentinel)
            seg_map = runner._stage_seg_slots(part, layout, min_len=k).ids
        planner = _Planner(runner, part, bss, layout)
        try:
            tree = planner.plan(f)
        except _NoFuse:
            return None
        if tree == ("false",):
            return _Ready({bi: np.zeros(bss[bi].nrows, dtype=bool)
                           for bi in bss})

        cand_packed, has_cand = _stage_cand_mask(runner, part, bss,
                                                 layout)
        prog = (tree, layout.nrows_padded, planner.has_maybe, has_cand,
                tuple(planner.arg_rows))
        name = program_name("topk_seg" if nseg else "topk", tree)
        blk = planner.block()
    runner._bump("device_calls")
    _bump_plane_scans(runner, planner, tree)
    runner._bump("topk_dispatches")
    runner._kind("topk_seg" if nseg else "topk")
    dm, mm = _launch(
        runner, runner._dispatch_topk, name, prog, k, spec.desc, nseg,
        blk, cand_packed, seg_ids, seg_map, sn.values,
        tuple(planner.args))
    # the maybe vector is only meaningful when the program proved maybe
    # rows can exist; _FilterPending's harvest applies the same residue
    # discipline as the fused stats/filter paths
    return _FilterPending(runner, f, part, bss, layout, dm, mm,
                          planner.has_maybe)


# ---------------- fused filter-only dispatch (row queries) ----------------

def _filter_local(prog, axis, blk, cand_packed, args, rl):
    """Whole-filter-tree evaluation body: bit-packed (definite, maybe)
    row vectors.  axis/rl as in _fused_local (rl is this shard's rows)."""
    import jax.numpy as jnp
    tree, _rlp, has_maybe, has_cand = prog[:4]
    d, m = _eval_node(tree, args, blk, rl)
    if has_cand:
        cand = _unpack_bits(cand_packed, rl)
    else:
        idx = jnp.arange(rl, dtype=jnp.int32)
        if axis is not None:
            idx = idx + jax.lax.axis_index(axis) * rl
        cand = idx < blk[BLOCK_NROWS]
    d = d & cand
    if has_maybe and m is not None:
        mp = jnp.packbits((m & cand).astype(jnp.uint8))
    else:
        mp = jnp.zeros(1, dtype=jnp.uint8)
        if axis is not None:
            mp = K._vary(mp, (axis,))
    return jnp.packbits(d.astype(jnp.uint8)), mp


def _filter_dispatch(prog, blk, cand_packed, args):
    """One device call: the WHOLE filter tree -> bit-packed (definite,
    maybe) row vectors — the row-query analogue of _fused_dispatch.

    The same three-valued program the stats/topk paths run, as a
    single dispatch per part whose only downloads are two R/8-byte
    packed vectors, which is what makes the dispatch window's
    submit/harvest split (tpu/pipeline.py) worthwhile: one async
    handle per part."""
    return _filter_local(prog, None, blk, cand_packed, args, prog[1])


def filter_program(name: str):
    return _program(name, _filter_dispatch, lambda fn: jax.jit(
        fn, static_argnums=(0,)))


def _filter_dispatch_mesh(mesh, axis, prog, blk, cand_packed, args):
    """The filter-only program under shard_map: each device evaluates
    its row stripe, the packed (definite, maybe) vectors concatenate
    along the row axis (rl per shard is a multiple of 8, so the bit
    packing aligns across shard boundaries)."""
    from jax.sharding import PartitionSpec as P
    has_cand = prog[3]
    arg_rows = prog[4]
    rl = prog[1] // int(mesh.devices.size)
    in_specs = (P(), P(axis) if has_cand else P(),
                tuple(P(None, axis) if r == 2 else
                      (P(axis) if r else P()) for r in arg_rows))

    def fn(b, cp, leaf_args):
        return _filter_local(prog, axis, b, cp, leaf_args, rl)

    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=(P(axis), P(axis)))(
        blk, cand_packed, args)


def filter_mesh_program(name: str):
    return _program(name + "_mesh", _filter_dispatch_mesh, lambda fn: jax.jit(
        fn, static_argnums=(0, 1, 2)))


class _FilterPending:
    """An in-flight fused filter dispatch for a row query; harvest()
    returns block_idx -> bool bitmap, bit-identical to the CPU path
    (maybe rows are settled by the filter tree's own apply_to_block,
    the same residue discipline as the fused stats path)."""

    __slots__ = ("runner", "f", "part", "bss", "layout", "dm", "mm",
                 "has_maybe")

    def __init__(self, runner, f, part, bss, layout, dm, mm, has_maybe):
        self.runner = runner
        self.f = f
        self.part = part
        self.bss = bss
        self.layout = layout
        self.dm = dm
        self.mm = mm
        self.has_maybe = has_maybe

    def harvest(self, sync=None):
        sync = sync or np.asarray
        rlp = self.layout.nrows_padded
        dm = np.unpackbits(np.asarray(sync(self.dm)))[:rlp].astype(bool)
        mm = None
        if self.has_maybe:
            mm = np.unpackbits(np.asarray(sync(self.mm)))[:rlp] \
                .astype(bool)
        bms = {}
        for bi, bs in self.bss.items():
            start = self.layout.starts[bi]
            n = bs.nrows
            bm = dm[start:start + n].copy()
            if mm is not None:
                sel = mm[start:start + n]
                if sel.any():
                    vbm = sel.copy()
                    self.f.apply_to_block(bs, vbm)
                    bm |= vbm
            bms[bi] = bm
        return bms


def fused_filter_submit(runner, f, part, bss):
    """Single-dispatch evaluation of a row query's whole filter tree.

    Returns a pending handle (harvest() -> block_idx -> bitmap), a
    _Ready result for constant trees, or None when the shape declines
    (the runner hands the part to the host executor)."""
    from .stats_device import MAX_STAT_ROWS
    with tracing.current_span().span("args"):
        layout = runner._stats_layout(part)
        if layout.nrows > MAX_STAT_ROWS:
            return None
        planner = _Planner(runner, part, bss, layout)
        try:
            tree = planner.plan(f)
        except _NoFuse:
            return None
        if tree == ("false",):
            return _Ready({bi: np.zeros(bss[bi].nrows, dtype=bool)
                           for bi in bss})
        if tree == ("true",):
            return _Ready({bi: np.ones(bss[bi].nrows, dtype=bool)
                           for bi in bss})
        cand_packed, has_cand = _stage_cand_mask(runner, part, bss,
                                                 layout)
        prog = (tree, layout.nrows_padded, planner.has_maybe, has_cand,
                tuple(planner.arg_rows))
        name = program_name("filter", tree)
        blk = planner.block()
    runner._bump("device_calls")
    _bump_plane_scans(runner, planner, tree)
    runner._bump("filter_dispatches")
    runner._kind("fused_filter")
    dm, mm = _launch(runner, runner._dispatch_filter, name, prog, blk,
                     cand_packed, tuple(planner.args))
    return _FilterPending(runner, f, part, bss, layout, dm, mm,
                          planner.has_maybe)
