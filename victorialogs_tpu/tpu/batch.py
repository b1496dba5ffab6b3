"""Part-at-a-time device execution: leaf planning, staging, the host
gate and the runner the dispatch window drives.

A query's filter tree is planned per leaf (`device_plan`: the scan ops,
bloom tokens and field of one leaf) and compiled whole by the fused
planner (tpu/fused.py) into ONE device program a part or pack: filter,
filter|stats or filter|sort-topk.  A part's columns are staged into HBM
once in the stats layout's row coordinates and cached across queries
(parts are immutable).  There is one device path:

    host gate -> host executor
    fused planner -> one fused / topk / filter program
    planner declines -> host executor

The host executor is `_host_eval_blocks`, the CPU path's own per-block
filter evaluation, so a gated or declined part's answer is the
reference's by construction; the parity tests in tests/test_batch_runner.py
and tests/test_decline_to_host.py diff the device programs against it
bit-exactly.  Bloom pruning stays on the kill-path before staging
(filter_phrase.go:302 analogue) as one batched plane probe a (part,
column) through the filter-index subsystem (storage/filterbank.py +
tpu/bloom_device.py); rows longer than the staging width are truncated
on device and settled on the host with the filter's full predicate.

This mirrors the reference's batched scanning (64-block batches per worker:
lib/logstorage/block_search.go:16, storage_search.go:1035-1121) reshaped for
a dispatch-latency-bound accelerator: fewer, bigger programs win.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field as dc_field

import os
import threading
import weakref

import numpy as np

from .. import config
from ..logsql import filters as F
from ..storage.filterbank import bloom_keep_mask
from ..storage.values_encoder import VT_DICT, VT_STRING
from ..utils.hashing import cached_token_hashes
from . import compile_stats
from . import kernels as K
from .layout import StagingCache


# ---------------- leaf planning ----------------

@dataclass
class ScanOp:
    pattern: bytes
    mode: int
    starts_tok: bool = False
    ends_tok: bool = False
    # specials that need no device scan:
    match_nonempty: bool = False   # prefix "": any non-empty value
    match_empty: bool = False      # contains "": only the empty value
    # ASCII-case-insensitive compare (pattern pre-lowered); rows with any
    # byte >= 0x80 are re-checked on the host — Unicode lower() can map
    # non-ASCII onto ASCII (U+212A -> 'k'), which the byte fold can't see
    fold: bool = False


@dataclass
class LeafPlan:
    filter: object                 # the original Filter (host fallback + pred)
    field: str
    ops: list
    combine: str                   # 'and' | 'or'
    bloom_tokens: list
    verify: bool = False           # re-check survivors with filter._pred
    pair: tuple | None = None      # (A, B) for the device `A.*B` fast path


def device_plan(f) -> LeafPlan | None:
    """Compile one filter leaf into device scan ops; None => host-only leaf."""
    from ..logsql.filters import canonical_field
    from ..logsql.matchers import is_word_char

    def ok(s: str) -> bool:
        return s.isascii() and 0 < len(s) <= K.MAX_PATTERN_LEN

    if isinstance(f, F.FilterPhrase):
        if not ok(f.phrase):
            return None
        return LeafPlan(f, canonical_field(f.field),
                        [ScanOp(f.phrase.encode(), K.MODE_PHRASE,
                                is_word_char(f.phrase[0]),
                                is_word_char(f.phrase[-1]))],
                        "and", f._tokens())

    if isinstance(f, F.FilterPrefix):
        fld = canonical_field(f.field)
        if not f.prefix:
            return LeafPlan(f, fld, [ScanOp(b"", 0, match_nonempty=True)],
                            "and", [])
        if not ok(f.prefix):
            return None
        return LeafPlan(f, fld,
                        [ScanOp(f.prefix.encode(), K.MODE_PREFIX,
                                is_word_char(f.prefix[0]), False)],
                        "and", f._tokens())

    if isinstance(f, F.FilterAnyCasePhrase):
        if not ok(f._lower):
            return None
        return LeafPlan(f, canonical_field(f.field),
                        [ScanOp(f._lower.encode(), K.MODE_PHRASE,
                                is_word_char(f._lower[0]),
                                is_word_char(f._lower[-1]), fold=True)],
                        "and", [])

    if isinstance(f, F.FilterAnyCasePrefix):
        fld = canonical_field(f.field)
        if not f._lower:
            # match_any_case_prefix("") == any non-empty value
            return LeafPlan(f, fld, [ScanOp(b"", 0, match_nonempty=True)],
                            "and", [])
        if not ok(f._lower):
            return None
        return LeafPlan(f, fld,
                        [ScanOp(f._lower.encode(), K.MODE_PREFIX,
                                is_word_char(f._lower[0]), False,
                                fold=True)],
                        "and", [])

    if isinstance(f, F.FilterExact):
        if not ok(f.value):
            return None
        return LeafPlan(f, canonical_field(f.field),
                        [ScanOp(f.value.encode(), K.MODE_EXACT)], "and", [])

    if isinstance(f, F.FilterExactPrefix):
        if not ok(f.prefix):
            return None
        return LeafPlan(f, canonical_field(f.field),
                        [ScanOp(f.prefix.encode(), K.MODE_EXACT_PREFIX)],
                        "and", [])

    if isinstance(f, F.FilterSequence):
        if not f.phrases or any(not ok(p) for p in f.phrases):
            return None
        # phrases carry word boundaries (match_sequence via phrase_pos):
        # MODE_PHRASE is exact per phrase; ORDER still needs host verify
        # when there is more than one
        ops = [ScanOp(p.encode(), K.MODE_PHRASE, is_word_char(p[0]),
                      is_word_char(p[-1])) for p in f.phrases]
        return LeafPlan(f, canonical_field(f.field), ops, "and",
                        f._tokens(), verify=len(f.phrases) > 1)

    if isinstance(f, F.FilterContainsAll):
        if f.subquery is not None and not f.values:
            return None
        return _contains_plan(f, require_all=True)

    if isinstance(f, F.FilterContainsAny):
        if f.subquery is not None and not f.values:
            return None
        return _contains_plan(f, require_all=False)

    if isinstance(f, F.FilterRegexp):
        from ..logsql.filters import canonical_field as cf
        import re
        # `A.*B` with literal A and B: decided fully on device (positions +
        # newline guard — kernels.match_ordered_pair); only rows containing
        # a newline fall back to host re.search
        pair = getattr(f, "_pair", None)  # computed once in __post_init__
        if pair is not None and all(len(p) <= K.MAX_PATTERN_LEN
                                    for p in pair):
            return LeafPlan(f, cf(f.field), [], "and", f._tokens(),
                            pair=pair)
        # full literal RUNS (partial words included) are sound for plain
        # substring prefilters; word tokens stay for the bloom kill-path
        literals = [t for t in getattr(f, "_substr_literals", []) if ok(t)]
        ops = [ScanOp(t.encode(), K.MODE_SUBSTRING) for t in literals]
        pure = (re.escape(f.pattern) == f.pattern and len(literals) == 1
                and literals[0] == f.pattern)
        return LeafPlan(f, cf(f.field), ops, "and", f._tokens(),
                        verify=not pure)

    return None


def device_plans(f) -> list:
    """All device-scannable leaf plans of a filter tree (prefetch uses the
    same bloom tokens / fields the evaluator will)."""
    out: list = []

    def walk(g):
        if isinstance(g, (F.FilterAnd, F.FilterOr)):
            for sub in g.filters:
                walk(sub)
        elif isinstance(g, F.FilterNot):
            walk(g.inner)
        else:
            plan = device_plan(g)
            if plan is not None and (plan.ops or plan.pair):
                out.append(plan)
    walk(f)
    return out


def _tree_has_time(f) -> bool:
    """Does the tree hold a FilterTime leaf (fused prefetch must stage
    the timestamp planes the planner's _time_leaf will ask for)?"""
    if isinstance(f, F.FilterTime):
        return True
    if isinstance(f, (F.FilterAnd, F.FilterOr)):
        return any(_tree_has_time(s) for s in f.filters)
    if isinstance(f, F.FilterNot):
        return _tree_has_time(f.inner)
    return False


def _contains_plan(f, require_all: bool) -> LeafPlan | None:
    from ..logsql.filters import canonical_field
    from ..logsql.matchers import is_word_char
    if not f.values:
        return None
    ops = []
    for p in f.values:
        if not p:
            ops.append(ScanOp(b"", 0, match_empty=True))
            continue
        if not p.isascii() or len(p) > K.MAX_PATTERN_LEN:
            return None
        ops.append(ScanOp(p.encode(), K.MODE_PHRASE, is_word_char(p[0]),
                          is_word_char(p[-1])))
    tokens = f._tokens() if require_all else []
    return LeafPlan(f, canonical_field(f.field), ops,
                    "and" if require_all else "or", tokens)


_UNSTAGEABLE = object()  # cache marker: part+field can't be staged


# ---------------- stats staging (device partials) ----------------

_INT_VTYPES = None


def _int_vtypes():
    global _INT_VTYPES
    if _INT_VTYPES is None:
        from ..storage.values_encoder import (VT_INT64, VT_UINT8, VT_UINT16,
                                              VT_UINT32, VT_UINT64)
        _INT_VTYPES = (VT_UINT8, VT_UINT16, VT_UINT32, VT_UINT64, VT_INT64)
    return _INT_VTYPES


@dataclass
class StatsLayout:
    """Canonical whole-part row layout of every staged column: every
    block in index order."""
    starts: dict                   # block_idx -> row start
    nrows: int                     # real rows
    nrows_padded: int              # STATS_CHUNK multiple

    def device_bytes(self) -> int:
        return 64 * len(self.starts)


@dataclass
class StagedNumeric:
    """One value column staged for exact device stats.

    values: uint32 offsets from vmin over eligible (int-typed) blocks;
    other blocks hold 0 and must be masked off by the caller.  The same
    array doubles as the quantile-axis ids when vmax-vmin fits the
    histogram cap (combine_ids casts on device)."""
    values: object                 # jax uint32[Rp]
    vmin: int
    vmax: int
    eligible: frozenset            # block idxs with int-typed columns
    nbytes: int

    def device_bytes(self) -> int:
        return self.nbytes


@dataclass
class StagedBuckets:
    ids: object                    # jax int32[Rp]
    base: int                      # bucketed-ns value of bucket 0
    num_buckets: int
    nbytes: int

    def device_bytes(self) -> int:
        return self.nbytes


@dataclass
class StagedDict:
    """A group-by column staged as per-row GLOBAL dict codes.

    Eligible blocks are dict-encoded, const, or missing (missing/const
    map every row to one code; '' is a value like any other, matching the
    host's group-by semantics for absent fields)."""
    ids: object                    # jax int32[Rp]
    values: list                   # code -> value string (this part)
    eligible: frozenset            # block idxs covered
    nbytes: int

    def device_bytes(self) -> int:
        return self.nbytes


def stage_num_buckets(part, field: str, layout: StatsLayout,
                      fstep: float, foff: float,
                      put=None) -> StagedDict | None:
    """Stage a numeric group-by bucket axis: per-row codes into a table
    of bucket-KEY strings, using the HOST's exact formula
    (floor((v - off) / step) * step + off, keys via format_number) so
    group keys are bit-identical (pipes.PipeStats._bucket_value)."""
    import jax.numpy as jnp
    from ..logsql.stats_funcs import format_number
    from ..storage.values_encoder import VT_FLOAT64
    if put is None:
        put = jnp.asarray

    ids = np.zeros(layout.nrows_padded, dtype=np.int32)
    values: list[str] = []
    code_of: dict[str, int] = {}
    eligible = []
    numeric_vts = _int_vtypes() + (VT_FLOAT64,)
    for bi in range(part.num_blocks):
        meta = part.block_column_meta(bi, field)
        if meta is None or meta["t"] not in numeric_vts:
            continue  # const/dict/string/ipv4/ts blocks: host path
        col = part.block_column(bi, field)
        f = col.nums.astype(np.float64)
        vb = np.floor((f - foff) / fstep) * fstep + foff
        uniq, inv = np.unique(vb, return_inverse=True)
        remap = np.empty(uniq.shape[0], dtype=np.int32)
        for k, v in enumerate(uniq.tolist()):
            key = format_number(v)
            c = code_of.get(key)
            if c is None:
                c = code_of[key] = len(values)
                values.append(key)
            remap[k] = c
        start = layout.starts[bi]
        ids[start:start + f.shape[0]] = remap[inv]
        eligible.append(bi)
    if not eligible:
        return None
    return StagedDict(ids=put(ids), values=values,
                      eligible=frozenset(eligible),
                      nbytes=layout.nrows_padded * 4)


def stage_dict_codes(part, field: str, layout: StatsLayout,
                     put=None) -> StagedDict | None:
    """Stage one group-by column as int32 global codes per row."""
    import jax.numpy as jnp
    from ..storage.values_encoder import VT_DICT
    if put is None:
        put = jnp.asarray

    ids = np.zeros(layout.nrows_padded, dtype=np.int32)
    values: list[str] = []
    code_of: dict[str, int] = {}

    def code(v: str) -> int:
        c = code_of.get(v)
        if c is None:
            c = code_of[v] = len(values)
            values.append(v)
        return c

    eligible = []
    for bi in range(part.num_blocks):
        start = layout.starts[bi]
        n = part.block_rows(bi)
        if field in ("_stream", "_stream_id"):
            # virtual per-block constants
            v = part.block_tags(bi) if field == "_stream" else \
                part.block_stream_id(bi).as_string()
            ids[start:start + n] = code(v)
            eligible.append(bi)
            continue
        meta = part.block_column_meta(bi, field)
        if meta is None:
            consts = dict(part.block_consts(bi))
            ids[start:start + n] = code(consts.get(field, ""))
            eligible.append(bi)
            continue
        if meta["t"] != VT_DICT:
            continue  # string/numeric-encoded: host path for this block
        col = part.block_column(bi, field)
        remap = np.fromiter((code(v) for v in col.dict_values),
                            dtype=np.int32, count=len(col.dict_values))
        ids[start:start + n] = remap[col.ids]
        eligible.append(bi)
    if not eligible:
        return None
    return StagedDict(ids=put(ids), values=values,
                      eligible=frozenset(eligible),
                      nbytes=layout.nrows_padded * 4)


@dataclass
class AxesAssembly:
    """Everything _assemble_axes staged for one part's stats dispatch."""
    layout: StatsLayout
    numerics: dict                 # field -> StagedNumeric
    axes: list                     # (kind, ids_jax, size, decode_payload)
    eligibility: list              # frozensets of eligible block idxs
    ids_tuple: tuple
    strides: tuple
    nb: int
    uniq_shared: list              # (field, axis_idx)
    # packed super-dispatch segment count (0 = no seg axis).  When set,
    # ids_tuple[0] is the per-row segment ids and the fused kernel runs
    # the SEGMENT-MAJOR reduction (tpu/stats_seg.py): the one-hot
    # bucket width is nb // nseg — it no longer scales with the pack
    # size, and MAX_BUCKETS gates only that base product.
    nseg: int = 0


def part_stats_layout(part, shards: int = 1) -> StatsLayout:
    """shards: pad rows to a (STATS_CHUNK * shards) multiple so a mesh
    runner can split the row axis evenly with whole chunks per device."""
    from .kernels import stats_pad_rows, STATS_CHUNK
    starts = {}
    pos = 0
    for bi in range(part.num_blocks):
        starts[bi] = pos
        pos += part.block_rows(bi)
    padded = stats_pad_rows(pos)
    mult = STATS_CHUNK * max(shards, 1)
    padded = ((padded + mult - 1) // mult) * mult
    return StatsLayout(starts=starts, nrows=pos, nrows_padded=padded)


def stage_numeric(part, field: str, layout: StatsLayout,
                  max_abs_times_rows: int, put=None) -> StagedNumeric | None:
    """Stage one uint/int column as exact uint32 offsets from its minimum.

    Returns None when no block is int-typed, the value range exceeds
    uint32, or magnitudes could break float64 exactness on the host side
    (stats_device.py exactness contract)."""
    import jax.numpy as jnp
    if put is None:
        put = jnp.asarray

    cols = {}
    vmin = None
    vmax = None
    for bi in range(part.num_blocks):
        col = part.block_column(bi, field)
        if col is None or col.vtype not in _int_vtypes():
            continue
        cols[bi] = col
        lo, hi = int(col.nums.min()), int(col.nums.max())
        vmin = lo if vmin is None else min(vmin, lo)
        vmax = hi if vmax is None else max(vmax, hi)
    if not cols:
        return None
    if vmax - vmin >= 1 << 32:
        return None
    if max(abs(vmin), abs(vmax)) * max(layout.nrows, 1) >= \
            max_abs_times_rows:
        return None
    vals = np.zeros(layout.nrows_padded, dtype=np.uint32)
    for bi, col in cols.items():
        start = layout.starts[bi]
        vals[start:start + col.nums.shape[0]] = \
            (col.nums.astype(np.int64) - vmin).astype(np.uint32)
    return StagedNumeric(values=put(vals), vmin=vmin, vmax=vmax,
                         eligible=frozenset(cols),
                         nbytes=layout.nrows_padded * 4)


def stage_len_column(part, field: str, layout: StatsLayout,
                     max_abs_times_rows: int, put=None
                     ) -> StagedNumeric | None:
    """Per-row CODE-POINT length of `field` as a synthetic uint32 value
    column — the device carrier for `sum_len(field)` partials (the sum
    plane of the standard stats kernel IS the total length; host
    semantics: Python len(value)).  Eligible block kinds: string (bytes
    minus UTF-8 continuation bytes via prefix sums), dict, const,
    missing, and int-typed (canonical decimal digit count); float/ipv4/
    ts-typed blocks decline to the host path."""
    import jax.numpy as jnp
    if put is None:
        put = jnp.asarray
    virtual = field in ("_stream", "_stream_id")
    vals = np.zeros(layout.nrows_padded, dtype=np.uint32)
    eligible = []
    vmax = 0
    i64min = np.iinfo(np.int64).min
    for bi in range(part.num_blocks):
        start = layout.starts[bi]
        n = part.block_rows(bi)
        if virtual:
            v = part.block_tags(bi) if field == "_stream" else \
                part.block_stream_id(bi).as_string()
            vals[start:start + n] = len(v)
            vmax = max(vmax, len(v))
            eligible.append(bi)
            continue
        meta = part.block_column_meta(bi, field)
        if meta is None:
            consts = dict(part.block_consts(bi))
            ln = len(consts.get(field, ""))
            vals[start:start + n] = ln
            vmax = max(vmax, ln)
        elif meta["t"] == VT_STRING:
            col = part.block_column(bi, field)
            if col.arena.size:
                cs = np.zeros(col.arena.size + 1, dtype=np.int64)
                np.cumsum((col.arena & 0xC0) != 0x80, out=cs[1:])
                offs = col.offsets.astype(np.int64)
                lens = col.lengths.astype(np.int64)
                cp = cs[offs + lens] - cs[offs]
            else:
                cp = np.zeros(n, dtype=np.int64)
            vals[start:start + n] = cp.astype(np.uint32)
            vmax = max(vmax, int(cp.max(initial=0)))
        elif meta["t"] == VT_DICT:
            col = part.block_column(bi, field)
            remap = np.array([len(v) for v in col.dict_values],
                             dtype=np.uint32)
            if remap.size:
                rowl = remap[col.ids]
                vals[start:start + n] = rowl
                vmax = max(vmax, int(remap.max()))
        elif meta["t"] in _int_vtypes():
            col = part.block_column(bi, field)
            v = col.nums.astype(np.int64)
            a = np.abs(v)
            d = np.ones(n, dtype=np.int64)
            t = 10
            while t <= 10 ** 18:
                d += a >= t
                t *= 10
            d += v < 0
            d = np.where(v == i64min, 20, d)  # abs(int64 min) wraps
            vals[start:start + n] = d.astype(np.uint32)
            vmax = max(vmax, int(d.max(initial=0)))
        else:
            continue       # float/ipv4/ts: host decodes these
        eligible.append(bi)
    if not eligible:
        return None
    if vmax * max(layout.nrows, 1) >= max_abs_times_rows:
        return None
    return StagedNumeric(values=put(vals), vmin=0, vmax=vmax,
                         eligible=frozenset(eligible),
                         nbytes=layout.nrows_padded * 4)


def stage_empty_column(part, field: str, layout: StatsLayout,
                       put=None) -> StagedNumeric | None:
    """Synthetic 0/1 column: 1 where `field` is the empty string — the
    device carrier for `count_empty(field)` (its sum plane is the empty
    count).  Every block kind is eligible: numeric/ipv4/ts-typed blocks
    have a value in every row (never empty)."""
    import jax.numpy as jnp
    if put is None:
        put = jnp.asarray
    vals = np.zeros(layout.nrows_padded, dtype=np.uint32)
    eligible = []
    for bi in range(part.num_blocks):
        start = layout.starts[bi]
        n = part.block_rows(bi)
        if field in ("_stream", "_stream_id"):
            eligible.append(bi)   # virtual renderings are never empty
            continue
        meta = part.block_column_meta(bi, field)
        if meta is None:
            consts = dict(part.block_consts(bi))
            if consts.get(field, "") == "":
                vals[start:start + n] = 1
        elif meta["t"] == VT_STRING:
            col = part.block_column(bi, field)
            em = col.lengths == 0
            if em.any():
                vals[start:start + n] = em.astype(np.uint32)
        elif meta["t"] == VT_DICT:
            col = part.block_column(bi, field)
            remap = np.array([1 if v == "" else 0
                              for v in col.dict_values], dtype=np.uint32)
            if remap.size and remap.any():
                vals[start:start + n] = remap[col.ids]
        # numeric/ipv4/ts blocks: never empty
        eligible.append(bi)
    return StagedNumeric(values=put(vals), vmin=0, vmax=1,
                         eligible=frozenset(eligible),
                         nbytes=layout.nrows_padded * 4)


def stage_time_buckets(part, layout: StatsLayout, step: int, offset: int,
                       max_buckets: int, put=None) -> StagedBuckets | None:
    """Bucket ids per row from block timestamps, matching the host's
    `((ts - off) // step) * step + off` bucketing bit-for-bit."""
    import jax.numpy as jnp
    if put is None:
        put = jnp.asarray

    ids = np.zeros(layout.nrows_padded, dtype=np.int64)
    base = None
    hi = None
    for bi in range(part.num_blocks):
        ts = part.block_timestamps(bi)
        vb = ((ts.astype(np.int64) - offset) // step) * step + offset
        start = layout.starts[bi]
        ids[start:start + vb.shape[0]] = vb
        lo_b, hi_b = int(vb.min()), int(vb.max())
        base = lo_b if base is None else min(base, lo_b)
        hi = hi_b if hi is None else max(hi, hi_b)
    if base is None:
        return None
    nb = (hi - base) // step + 1
    if nb > max_buckets:
        return None
    ids[:layout.nrows] = (ids[:layout.nrows] - base) // step
    ids[layout.nrows:] = 0
    return StagedBuckets(ids=put(ids.astype(np.int32)), base=base,
                         num_buckets=int(nb),
                         nbytes=layout.nrows_padded * 4)


# ---------------- cost model: device vs host, per part ----------------

class CostModel:
    """Per-part device-vs-host dispatch decision.

    The device path must never lose to the CPU executor: every dispatch
    pays a fixed round trip, so small parts and cheap filters can run
    slower on device than the native host scans.  This model estimates
    both sides and routes the part accordingly:

      est_host   = cand_rows / host_rows_per_s   (+ stats term)
      est_device = n_dispatch * rtt + scanned_bytes / dev_bytes_per_s
                   + amortized cold-staging upload

    The RTT is MEASURED on first use (a tiny dispatch round trip), and
    the scan / host rates are EWMA-updated from real part runs, so the decision
    tracks the actual machine instead of hard-coded constants.  Env
    overrides: VL_COST_FORCE=device|host pins the decision (tests pin
    `device` so kernel parity stays exercised); VL_COST_RTT_MS,
    VL_COST_DEV_GBPS, VL_COST_HOST_MROWS preseed the calibration.

    This is the TPU analogue of the reference scheduling work budget:
    the reference never pays a fixed per-query offload floor, so its
    worker model needs no such gate (storage_search.go:1035-1067); here
    the gate is what makes "device by default" safe on every shape.
    """

    _EWMA = 0.3                    # weight of a new observation
    _COLD_AMORT = 0.25             # staging reused across queries (LRU)

    def __init__(self):
        self._mu = threading.Lock()
        v = config.env("VL_COST_RTT_MS")
        self.rtt = float(v) / 1e3 if v else None
        v = config.env("VL_COST_DEV_GBPS")
        self.dev_bytes_per_s = float(v) * 1e9 if v else None
        v = config.env("VL_COST_HOST_MROWS")
        # round-3 PERF.md: native host scans sustain 10-14M rows/s
        self.host_rows_per_s = float(v) * 1e6 if v else 12e6
        self.host_stats_rows_per_s = 30e6
        self.upload_bytes_per_s = 1e9
        # per-unit host EMIT time EWMA (block materialization +
        # downstream write, EXCLUDING the device_sync blocked wait) —
        # the VL_INFLIGHT=auto depth signal (tpu/pipeline.py).  Folding
        # the wait in would make the signal self-referential: at depth d
        # each harvest blocks ~rtt/d, the EWMA converges toward rtt/d,
        # and ceil(rtt/ewma) contracts to the clamp floor exactly on the
        # high-RTT backends that need a deep window.
        self.emit_ewma: float | None = None
        # observed submit-to-harvest round trip of REAL dispatch units
        # (tpu/pipeline.harvest_one) — the EXPLAIN pricing pass's
        # per-unit term.  The probe rtt above is a minimal round trip
        # for the host-vs-device decision; a real fused unit also pays
        # program-arg marshalling and result download, which must not
        # inflate the routing gate but should price the plan.
        self.unit_rtt_ewma: float | None = None
        self._unit_rtt_seen = False    # first unit pays jit compile
        self.force = config.env("VL_COST_FORCE") or ""

    # vlint: allow-jax-host-sync(the blocking round trip IS the probe)
    def measured_rtt(self) -> float:
        if self.rtt is None:
            import time

            import jax
            import jax.numpy as jnp
            f = jax.jit(lambda x: x + 1)
            x = jnp.zeros(8, jnp.int32)
            np.asarray(f(x))           # compile + warm the path
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                np.asarray(f(x))
                best = min(best, time.perf_counter() - t0)
            with self._mu:
                if self.rtt is None:
                    self.rtt = best
        return self.rtt

    def _dev_rate(self) -> float:
        if self.dev_bytes_per_s is not None:
            return self.dev_bytes_per_s
        import jax
        # defaults until the first measured dispatch lands
        return 20e9 if jax.default_backend() == "tpu" else 1.5e9

    # -- EWMA feeders --
    def observe_device_scan(self, nbytes: int, elapsed: float) -> None:
        if self.force:
            # forced runners (mesh default, parity suites) never consult
            # the estimate — don't pay the lazy RTT probe to feed it
            return
        # measure the RTT lazily so the dispatch overhead is subtracted
        # even when prefer_host hasn't run yet (ADVICE r4: otherwise the
        # full round trip is attributed to device compute, biasing
        # dev_bytes_per_s low)
        compute = elapsed - self.measured_rtt()
        if compute <= 0 or nbytes <= 0:
            return
        rate = nbytes / compute
        with self._mu:
            cur = self.dev_bytes_per_s
            self.dev_bytes_per_s = rate if cur is None else \
                (1 - self._EWMA) * cur + self._EWMA * rate

    def observe_emit(self, elapsed: float) -> None:
        """One harvested unit's emit-phase time (wait-free host work).
        Unlike the routing rates this records even under VL_COST_FORCE:
        it calibrates the window depth, not a device-vs-host decision."""
        if elapsed <= 0:
            return
        with self._mu:
            cur = self.emit_ewma
            self.emit_ewma = elapsed if cur is None else \
                (1 - self._EWMA) * cur + self._EWMA * elapsed

    def observe_unit_rtt(self, elapsed: float) -> None:
        """One real dispatch unit's submit-to-harvest round trip
        (records under VL_COST_FORCE too: it prices plans, it never
        routes device-vs-host).

        Robust to jit compilation: the very first unit pays a one-time
        program compile that can be 100x the steady round trip —
        seeding the EWMA with it would poison every prediction for tens
        of queries — so the first observation is discarded, and later
        spikes (fresh pad buckets compiling mid-stream) clamp at 10x
        the current estimate instead of jerking it."""
        if elapsed <= 0:
            return
        with self._mu:
            if not self._unit_rtt_seen:
                self._unit_rtt_seen = True
                return
            cur = self.unit_rtt_ewma
            if cur is None:
                self.unit_rtt_ewma = elapsed
                return
            self.unit_rtt_ewma = (1 - self._EWMA) * cur \
                + self._EWMA * min(elapsed, 10 * cur)

    def observe_host_scan(self, rows: int, elapsed: float) -> None:
        if elapsed <= 0 or rows < 10000:
            return                 # tiny samples are all overhead
        rate = rows / elapsed
        with self._mu:
            self.host_rows_per_s = (1 - self._EWMA) * self.host_rows_per_s \
                + self._EWMA * rate

    # -- the decision --
    def prefer_host(self, cand_rows: int, scan_bytes: int,
                    n_dispatch: int, cold_bytes: int,
                    stats_rows: int = 0) -> bool:
        if self.force == "device":
            return False
        if self.force == "host":
            return True
        if n_dispatch <= 0:
            return True
        est_host = cand_rows / self.host_rows_per_s \
            + stats_rows / self.host_stats_rows_per_s
        est_dev = n_dispatch * self.measured_rtt() \
            + n_dispatch * scan_bytes / self._dev_rate() \
            + self._COLD_AMORT * cold_bytes / self.upload_bytes_per_s
        return est_host < est_dev

    # -- probe-free reads (EXPLAIN pricing; /metrics-safe) --

    # cold-calibration RTT stand-in: a local-backend-scale figure, so an
    # uncalibrated model underprices a remote backend instead of
    # overpricing local ones (the first real query measures the truth)
    _RTT_COLD_DEFAULT = 1e-3

    def peek(self) -> dict:
        """Calibration snapshot WITHOUT the lazy RTT probe: the raw
        EWMAs/fields plus cold-start defaults, for the EXPLAIN pricing
        pass (obs/explain.py) — `explain=1` must never dispatch, so it
        can't ride measured_rtt().  ``calibrated`` is False until a real
        query has measured the round trip."""
        with self._mu:
            rtt, dev, emit = self.rtt, self.dev_bytes_per_s, \
                self.emit_ewma
            unit_rtt = self.unit_rtt_ewma
            host, host_stats = self.host_rows_per_s, \
                self.host_stats_rows_per_s
        rtt_s = rtt if rtt is not None else self._RTT_COLD_DEFAULT
        return {
            "rtt_s": rtt_s,
            # the pricing term: observed whole-unit round trips when a
            # query has fed the EWMA, the probe rtt until then
            "unit_rtt_s": unit_rtt if unit_rtt is not None else rtt_s,
            "dev_bytes_per_s": dev if dev is not None
            else self._dev_rate(),
            "emit_unit_s": emit or 0.0,
            "host_rows_per_s": host,
            "host_stats_rows_per_s": host_stats,
            "upload_bytes_per_s": self.upload_bytes_per_s,
            "calibrated": rtt is not None or unit_rtt is not None,
            "force": self.force,
        }



# ---------------- the batch runner ----------------

# live runners, for the vlsan end-of-test sweep: a non-daemon
# vl-prefetch worker is fine while a reachable runner owns it (close()
# releases it; the long-lived server runner never closes), and a
# DROPPED runner's worker exits once the executor is collected — only
# an ownerless surviving worker is a leak
_live_runners: "weakref.WeakSet" = weakref.WeakSet()


def live_prefetch_pools() -> int:
    """How many live runners currently own a prefetch pool."""
    return sum(1 for r in list(_live_runners)
               if r._prefetch_pool is not None)


class BatchRunner:
    """Part-at-a-time evaluation: one fused device program a part (or
    pack), or the host executor where the cost gate or the fused planner
    says so.

    The dispatch window (tpu/pipeline.py) drives the run_part*_submit
    handles; run_part*() are the synchronous front of the same path
    (the members of a pack that declined)."""

    def __init__(self, max_cache_bytes: int | None = None,
                 max_part_bytes: int | None = None, devices=None):
        # staging budget off the device it stages onto: half of its
        # memory for the cache and a quarter for any one part's matrix,
        # times the devices the row axis stripes over (a mesh runner
        # passes them).  Backends that report no limit (jax-CPU) keep the
        # figures the cache was sized with before: 8 GiB / 4 GiB.
        # Replicated placements (bloom planes, seg slot maps: block-axis
        # sized) are charged once, not per copy.
        if max_cache_bytes is None or max_part_bytes is None:
            import jax
            devs = list(devices) if devices is not None \
                else jax.devices()[:1]
            limit = (devs[0].memory_stats() or {}).get("bytes_limit")
            per_dev = limit if limit else 16 << 30
            if max_cache_bytes is None:
                max_cache_bytes = per_dev // 2 * len(devs)
            if max_part_bytes is None:
                max_part_bytes = per_dev // 4 * len(devs)
        self.cache = StagingCache(max_cache_bytes)
        self.max_part_bytes = max_part_bytes
        self.cost = CostModel()
        self.device_calls = 0          # every dispatch issued to the device
        self.plane_scan_leaves = 0     # scan and `A.*B` leaves those
        #                                dispatches ran through the plane
        #                                kernel (tpu/kernels32.py)
        self.scan_plane_steps_swept = 0    # their sweep steps a tile, and
        self.scan_plane_steps_skipped = 0  # those cut off by the tiles'
        #                                longest rows (fused.FusedField)
        self.operand_blocks = 0        # fused/topk/filter dispatches that
        #                                shipped their host operands as
        #                                one block (tpu/fused.py:_launch)
        self.cpu_fallbacks = 0         # parts the fused planner declined:
        #                                evaluated by the host executor
        self.gated_host_parts = 0
        self.stats_dispatches = 0
        self.fused_dispatches = 0
        self.filter_dispatches = 0     # fused filter-only row dispatches
        self.topk_dispatches = 0
        self.bloom_plane_probes = 0
        self.agg_pruned_parts = 0
        self.maplet_probes = 0         # v2 maplet served a keep-mask
        self.maplet_pruned_blocks = 0  # blocks exact-killed pre-dispatch
        # async pipeline observability (tpu/pipeline.py)
        self.pipeline_units = 0        # units driven through the window
        self.scanned_parts = 0         # member parts those units carried
        self.shared_plan_walks = 0     # queries whose window consumed
        #                                the header walk their pricing
        #                                ran (engine/searcher)
        self.replicated_row_puts = 0   # mesh: row arrays that could not
        #                                stripe and were replicated
        self.packed_dispatches = 0     # super-dispatches over packed parts
        self.packed_parts = 0         # parts folded into super-dispatches
        self.packed_topk_dispatches = 0  # sort-topk super-dispatches
        self.cross_partition_packs = 0  # packs spanning a day boundary
        self.result_cache_units = 0    # units satisfied from the
        #                                per-part result cache (no
        #                                dispatch, no slot lease)
        self.prefetch_resident_units = 0  # units whose prefetch queued
        #                                nothing: every key it could
        #                                stage was in the staging cache
        # widest bucket one-hot any stats dispatch paid (the seg-major
        # kernel keeps this at the BASE bucket product — it must not
        # scale with VL_PACK_PARTS; bench-asserted)
        self.stats_onehot_width = 0
        self.inflight_hwm = 0          # in-flight window high-water mark
        self.host_sync_wait_s = 0.0    # time blocked materializing results
        self.sched_slot_wait_s = 0.0   # time leasing dispatch slots from
        #                                the shared scheduler (sched/)
        self.inflight_auto_depth = 0   # VL_INFLIGHT=auto chosen depth
        self.h2d_bytes_total = 0       # bytes handed to the device at the
        #                                placement seams (_put /
        #                                _put_replicated), once an array
        self.stats_shards = 1          # mesh runners stripe rows over >1
        # distinct dispatch shapes this runner has sent to the device —
        # the multichip dryrun asserts breadth here (verdict r4 weak #6)
        self.dispatch_kinds: set = set()
        self._counter_mu = threading.Lock()
        # striped staging locks: the prefetcher, concurrent partition
        # workers and the scan thread may race to stage the same
        # (part, field); the loser waits and takes the cache hit instead
        # of duplicating a multi-100MB upload.  A fixed stripe pool keeps
        # lock memory bounded across part churn (merges mint fresh uids).
        self._stage_locks = [threading.Lock() for _ in range(64)]
        # PackedPart instances (tpu/pipeline.py): a SMALL dedicated LRU,
        # not the byte-budgeted StagingCache — a pack strongly references
        # its member parts (incl. in-RAM InmemoryPart blocks), so its
        # true cost is member lifetime, not device bytes; the hard entry
        # cap bounds how long retired members can stay pinned.
        self._pack_mu = threading.Lock()
        self._packs: OrderedDict = OrderedDict()
        self._prefetch_pool = None  # lazy; see _prefetcher()
        self._stubs: dict = {}         # (shape, dtype) -> placed zeros
        _live_runners.add(self)

    def pallas_enabled(self) -> bool:
        """VL_PALLAS=1 selects the Pallas variants (bloom-plane probe,
        seg-major count) where a dispatch has one."""
        return config.env("VL_PALLAS") == "1"

    def sweeps_bounded(self) -> bool:
        """Whether the plane kernel's sweep stops at each tile's longest
        row here: the Pallas launcher, on the TPU backend."""
        from .kernels32 import on_tpu
        return on_tpu()

    def _bump(self, attr: str, n=1) -> None:
        with self._counter_mu:
            setattr(self, attr, getattr(self, attr) + n)

    def _bump_max(self, attr: str, v) -> None:
        with self._counter_mu:
            if v > getattr(self, attr):
                setattr(self, attr, v)

    def _set(self, attr: str, v) -> None:
        with self._counter_mu:
            setattr(self, attr, v)

    def _kind(self, label: str) -> None:
        with self._counter_mu:
            self.dispatch_kinds.add(label)

    def stats(self) -> dict:
        """Counter snapshot (served under /metrics as vl_tpu_*)."""
        with self._counter_mu:
            out = {
                "device_calls": self.device_calls,
                "plane_scan_leaves": self.plane_scan_leaves,
                "scan_plane_steps_swept": self.scan_plane_steps_swept,
                "scan_plane_steps_skipped": self.scan_plane_steps_skipped,
                "operand_blocks": self.operand_blocks,
                "cpu_fallbacks": self.cpu_fallbacks,
                "gated_host_parts": self.gated_host_parts,
                "stats_dispatches": self.stats_dispatches,
                "fused_dispatches": self.fused_dispatches,
                "filter_dispatches": self.filter_dispatches,
                "topk_dispatches": self.topk_dispatches,
                "bloom_plane_probes": self.bloom_plane_probes,
                "agg_pruned_parts": self.agg_pruned_parts,
                "maplet_probes": self.maplet_probes,
                "maplet_pruned_blocks": self.maplet_pruned_blocks,
                "pipeline_units": self.pipeline_units,
                "scanned_parts": self.scanned_parts,
                "shared_plan_walks": self.shared_plan_walks,
                "replicated_row_puts": self.replicated_row_puts,
                "packed_dispatches": self.packed_dispatches,
                "packed_parts": self.packed_parts,
                "packed_topk_dispatches": self.packed_topk_dispatches,
                "cross_partition_packs": self.cross_partition_packs,
                "result_cache_units": self.result_cache_units,
                "prefetch_resident_units": self.prefetch_resident_units,
                "stats_onehot_width": self.stats_onehot_width,
                "inflight_hwm": self.inflight_hwm,
                "host_sync_wait_s": self.host_sync_wait_s,
                "sched_slot_wait_s": self.sched_slot_wait_s,
                "inflight_auto_depth": self.inflight_auto_depth,
                "h2d_bytes_total": self.h2d_bytes_total,
            }
        out.update({f"staging_cache_{k}": v
                    for k, v in self.cache.stats().items()})
        # where the staged bytes physically sit: a mesh runner must show
        # a share on EVERY device (chip_smoke.py reads this); replicated
        # placements count on each device that holds a copy
        for dev_id, nbytes in sorted(
                self.cache.device_resident_bytes().items()):
            out[f'staged_device_bytes{{device="{dev_id}"}}'] = nbytes
        out.update(compile_stats())
        with self._pack_mu:
            out["pack_cache_entries"] = len(self._packs)
        # cost-model calibration gauges (ROADMAP "RTT-aware auto depth"
        # baseline signal): read raw fields, NEVER measured_rtt() — a
        # /metrics scrape must not trigger the lazy RTT probe dispatch
        out["cost_rtt_seconds"] = self.cost.rtt or 0.0
        out["cost_unit_rtt_seconds"] = self.cost.unit_rtt_ewma or 0.0
        out["cost_dev_bytes_per_s"] = self.cost.dev_bytes_per_s or 0.0
        out["cost_emit_ewma_seconds"] = self.cost.emit_ewma or 0.0
        if self.cost.rtt is not None:
            from .pipeline import pack_rows_cap
            cap = pack_rows_cap(self)
        else:
            # RTT not yet measured: report only an explicit VALID
            # override (a malformed value would make pack_rows_cap fall
            # through to measured_rtt and dispatch to the device from a
            # /metrics scrape)
            v = config.env_int("VL_PACK_MAX_ROWS")
            cap = max(1, v) if v is not None else 0
        out["pack_rows_cap"] = cap
        return out

    def _prefetcher(self):
        """Lazily create the single prefetch worker.  Fully under the
        counter lock: partition workers race here against each other AND
        against close(), and an unlocked fast-path read could return the
        pool close() is concurrently shutting down (or None)."""
        from concurrent.futures import ThreadPoolExecutor
        with self._counter_mu:
            if self._prefetch_pool is None:
                self._prefetch_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="vl-prefetch")
            return self._prefetch_pool

    def close(self) -> None:
        """Release the prefetch worker (callers owning a per-query runner
        should close it; the long-lived server runner never needs to)."""
        # under _counter_mu: a partition worker racing through
        # _prefetcher() must either see the live pool or rebuild one,
        # never shut down a pool it is about to submit to
        with self._counter_mu:
            pool, self._prefetch_pool = self._prefetch_pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    def _key_lock(self, key) -> threading.Lock:
        return self._stage_locks[hash(key) % len(self._stage_locks)]

    # ---- prefetch (stage part N+k while parts N..N+k-1 scan) ----
    def submit_prefetch(self, part, f, stats_spec=None,
                        cand_bis=None, sort_field=None) -> None:
        """Queue background staging of what the query will need from
        `part`, so the host decode/upload of UPCOMING parts overlaps the
        device scans of the current ones (SURVEY §7 hard-part 3).  The
        async pipeline (tpu/pipeline.py) submits this for every part
        within its in-flight window, so staging depth follows
        VL_INFLIGHT instead of the old depth-1 double buffer.

        Applies the SAME gates as the evaluator so prefetch never stages
        a column it would skip: the bloom kill-path over the candidate
        blocks, and the narrow-candidate heuristic (a small candidate
        fraction takes the host path instead of staging).
        cand_bis: candidate block idxs (after tenant/stream/time
        pruning); None means every block is a candidate.
        Stages what the fused programs read: layout-coordinate columns
        and timestamp planes, for packed super-parts too.
        sort_field: the sort-topk by-column — its uint32 value staging
        (the fused topk dispatch's score operand) uploads ahead like
        the stats value columns.

        A unit whose every key is already in the staging cache queues
        nothing (`prefetch_resident_units`): the worker would only look
        the keys up again, in Python, while this thread launches."""
        if all(self.cache.contains(key) for key, _plan in
               self._prefetch_keys(part, f, stats_spec, sort_field)):
            self._bump("prefetch_resident_units")
            return
        from ..obs import activity, tracing
        # staging runs on the vl-prefetch worker: re-enter the caller's
        # span AND activity record there so staged_entries/staged_bytes
        # attribution isn't silently dropped on the dominant
        # (prefetched) path; attrs/counters are lock-guarded, so adds
        # racing the final to_dict/snapshot are safe
        caller_span = tracing.current_span()
        caller_act = activity.current_activity()

        def work():
            try:
                with tracing.use_span(caller_span), \
                        activity.use_activity(caller_act):
                    self._prefetch_work(part, f, stats_spec, cand_bis,
                                        sort_field)
            # vlint: allow-broad-except(prefetch is best-effort)
            except Exception:
                pass  # prefetch is best-effort; the scan path re-stages
        try:
            self._prefetcher().submit(work)
        except RuntimeError:
            pass  # pool closed between return and submit; best-effort

    def _prefetch_keys(self, part, f, stats_spec, sort_field) -> list:
        """The staging keys a unit's prefetch may stage, in the order it
        stages them, as (key, plan) pairs: `_prefetch_work` stages the
        missing ones, `submit_prefetch` skips a unit that has them all.
        A `#fl` key carries its leaf plan (the bloom probe and the
        narrowness gate decide whether it stages), every other None.
        The layout comes first: the rest is staged against it."""
        uid = part.uid
        keys = [((uid, "#layout"), None)]
        if _tree_has_time(f):
            keys.append(((uid, "#ts2"), None))
        if sort_field is not None:
            keys.append(((uid, "#num", sort_field), None))
        keys += [((uid, "#fl", plan.field), plan)
                 for plan in device_plans(f)]
        if stats_spec is not None:
            keys += [((uid, "#num", fld), None)
                     for fld in stats_spec.value_fields]
            keys += [((uid, "#tb", bk.step, bk.offset) if bk.kind == "time"
                      else (uid, "#dict", bk.name), None)
                     for bk in stats_spec.by]
        return keys

    def _prefetch_work(self, part, f, stats_spec, cand_bis,
                       sort_field) -> None:
        bis = list(cand_bis) if cand_bis is not None else \
            list(range(part.num_blocks))
        cand_rows = sum(part.block_rows(bi) for bi in bis)
        if self._gate_host_est(
                f, part, cand_rows,
                stats_rows=cand_rows if stats_spec or sort_field
                else 0):
            return     # the evaluator will take the host path
        from .stats_device import MAX_ABS_TIMES_ROWS, MAX_BUCKETS, \
            MAX_STAT_ROWS
        layout = self._stats_layout(part)
        if layout.nrows > MAX_STAT_ROWS:
            return     # every fused family declines: the host evaluates
        for key, plan in self._prefetch_keys(part, f, stats_spec,
                                             sort_field)[1:]:
            kind = key[1]
            if kind == "#ts2":
                self._stage_ts_planes(part, layout)
            elif kind == "#num":
                # a stats value column or the topk score operand
                # (fused_topk_submit's staging key); where the column is
                # not numeric here the topk program declines and the
                # filter program reads the same columns
                self._stage_numeric(part, key[2], layout,
                                    MAX_ABS_TIMES_ROWS)
            elif kind == "#fl":
                surv = bis
                if plan.bloom_tokens:
                    hashes = cached_token_hashes(plan.filter,
                                                 plan.bloom_tokens)
                    # observe=False: the evaluator/planner re-probes
                    # this exact (part, field, bis) at dispatch —
                    # counting the prefetch warm-up too would double
                    # every histogram sample and trace counter
                    keep = bloom_keep_mask(part, plan.field, hashes,
                                           bis, observe=False)
                    surv = [bi for bi, k in zip(bis, keep) if k]
                if not surv:
                    continue
                rows = sum(part.block_rows(bi) for bi in surv)
                # mirrors _scan_leaf's narrowness gate
                if self.cache.contains(key) or rows * 8 >= part.num_rows:
                    self._stage_fused_field(part, plan.field, layout)
            elif kind == "#tb":
                self._stage_buckets(part, layout, key[2], key[3],
                                    MAX_BUCKETS)
            else:
                self._stage_dict(part, key[2], layout)

    # ---- device placement hook (MeshBatchRunner shards the row axis) ----
    def _put(self, arr, row_axis: int = 0):
        import jax.numpy as jnp
        self._bump("h2d_bytes_total", arr.nbytes)
        return jnp.asarray(arr)

    def _put_replicated(self, arr):
        """Placement for block-axis arrays (bloom planes) and small
        whole-array operands (patterns): every device needs the whole
        array — a mesh runner replicates instead of striping (the block
        axis is not the sharded row axis)."""
        import jax.numpy as jnp
        self._bump("h2d_bytes_total", arr.nbytes)
        return jnp.asarray(arr)

    def _stub(self, shape: tuple, dtype):
        """Zeros placeholder for a program input this dispatch does not
        use (no candidate mask, no segment axis), placed like every other
        non-row operand and uploaded once per runner."""
        key = (shape, np.dtype(dtype).str)
        got = self._stubs.get(key)
        if got is None:
            # racing first uses upload twice and keep one: harmless
            got = self._stubs.setdefault(key, self._put_replicated(
                np.zeros(shape, dtype=dtype)))
        return got

    # ---- dispatch hooks (MeshBatchRunner shard_maps + psum-reduces)
    # `name`: the program's name (fused.program_name), under which the
    # one jitted callable of that kind is looked up
    def _dispatch_fused(self, name, prog, strides, nb, n_values, blk,
                        cand_packed, seg_map, ids_tuple, values_tuple,
                        args):
        from .fused import fused_program
        return fused_program(name)(prog, strides, nb, n_values, blk,
                                   cand_packed, seg_map, ids_tuple,
                                   values_tuple, args)

    def _dispatch_topk(self, name, prog, k, desc, nseg, blk,
                       cand_packed, seg_ids, seg_map, values, args):
        from .fused import topk_program
        return topk_program(name)(prog, k, desc, nseg, blk, cand_packed,
                                  seg_ids, seg_map, values, args)

    def _dispatch_filter(self, name, prog, blk, cand_packed, args):
        from .fused import filter_program
        return filter_program(name)(prog, blk, cand_packed, args)

    # ---- cost gate (device must never lose to the CPU executor) ----
    def _gate_host(self, f, part, bss: dict, stats_rows: int = 0) -> bool:
        """True => run this part through the host executor instead."""
        return self._gate_host_est(f, part,
                                   sum(bs.nrows for bs in bss.values()),
                                   stats_rows=stats_rows)

    def _gate_host_est(self, f, part, cand_rows: int,
                       stats_rows: int = 0) -> bool:
        """The estimate behind _gate_host, keyed on cand_rows only so the
        prefetcher can apply the SAME decision before BlockSearch objects
        exist (ADVICE r4: a diverging prefetch gate declined to stage
        parts run_part then routed to device, paying the cold upload
        synchronously)."""
        plans = device_plans(f)
        if not plans:
            if not stats_rows:
                return True        # nothing device-scannable
            # stats-only shape (`* | stats ...`): ids+mask traffic only
            return self.cost.prefer_host(0, cand_rows * 8, 1, 0,
                                         stats_rows=stats_rows)
        scan_bytes = cand_rows * 128        # W estimate; fidelity is low
        # cold upload: once per FIELD not yet staged (a column three
        # leaves scan is one upload, not three — enough to send a
        # 72k-row pack to the host at the chip's 1 ms round trip)
        cold = 0
        for fld in {plan.field for plan in plans}:
            if not self.cache.contains((part.uid, "#fl", fld)):
                cold += scan_bytes
        n_dispatch = 1 if stats_rows else \
            sum(max(len(p.ops), 1) for p in plans)
        return self.cost.prefer_host(cand_rows, scan_bytes, n_dispatch,
                                     cold, stats_rows=stats_rows)

    @staticmethod
    def _host_eval_blocks(f, bss: dict) -> dict:
        """The CPU executor's own per-block path (native scans inside the
        filters)."""
        out = {}
        for bi, bs in bss.items():
            bm = np.ones(bs.nrows, dtype=bool)
            f.apply_to_block(bs, bm)
            out[bi] = bm
        return out

    def _host_eval_part(self, f, bss: dict) -> dict:
        """A part the gate sent to the host executor; timed to keep the
        cost model's host rate honest."""
        import time
        t0 = time.perf_counter()
        out = self._host_eval_blocks(f, bss)
        self.cost.observe_host_scan(sum(bs.nrows for bs in bss.values()),
                                    time.perf_counter() - t0)
        return out

    # ---- part-level evaluation ----
    def run_part(self, f, part, bss: dict) -> dict:
        """Evaluate the filter tree over candidate blocks of one part.

        bss: block_idx -> BlockSearch (with .ctx set for stream filters).
        Returns block_idx -> bool bitmap, bit-identical to the CPU path."""
        return self.run_part_submit(f, part, bss).harvest()

    def _decline_to_host(self, f, bss: dict) -> dict:
        """A part the fused planner declined (fused._NoFuse, axes
        _assemble_axes refuses, a layout over MAX_STAT_ROWS): the host
        executor evaluates it, counted once a part.  Not timed into the
        host rate: the gate routes on the parts it sent to the host
        itself, whatever an earlier query's shape was (a narrow decline,
        most of whose blocks die in the bloom, reads two to three times
        the rate and would pull parts the device serves to the host)."""
        self._bump("cpu_fallbacks")
        return self._host_eval_blocks(f, bss)

    # ---- device stats partials (filter bitmap -> per-bucket aggregates) ----

    def _stats_layout(self, part) -> StatsLayout:
        key = (part.uid, "#layout")
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is None:
                got = part_stats_layout(part, shards=self.stats_shards)
                self.cache.put_small(key, got)
            return got

    def _stage_numeric(self, part, field: str, layout: StatsLayout,
                       max_abs_times_rows: int):
        """Stage a value column for device stats.  `field` may be a
        synthetic token (stats_device.SYNTH_LEN/SYNTH_EMPTY prefixes)
        carrying sum_len/count_empty as derived uint32 columns."""
        from .stats_device import SYNTH_EMPTY, SYNTH_LEN
        key = (part.uid, "#num", field)
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is _UNSTAGEABLE:
                return None
            if got is None:
                if field.startswith(SYNTH_LEN):
                    got = stage_len_column(part, field[len(SYNTH_LEN):],
                                           layout, max_abs_times_rows,
                                           put=self._put)
                elif field.startswith(SYNTH_EMPTY):
                    got = stage_empty_column(
                        part, field[len(SYNTH_EMPTY):], layout,
                        put=self._put)
                else:
                    got = stage_numeric(part, field, layout,
                                        max_abs_times_rows,
                                        put=self._put)
                if got is None:
                    self.cache.put_small(key, _UNSTAGEABLE)
                else:
                    self.cache.put(key, got)
            return got

    def _stage_dict(self, part, field: str, layout: StatsLayout):
        key = (part.uid, "#dict", field)
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is _UNSTAGEABLE:
                return None
            if got is None:
                got = stage_dict_codes(part, field, layout,
                                       put=self._put)
                if got is None:
                    self.cache.put_small(key, _UNSTAGEABLE)
                else:
                    self.cache.put(key, got)
            return got

    def _stage_segments(self, part, layout: StatsLayout):
        """Per-row segment ids for a packed part (block -> member
        ordinal); None when the part has no segment map (plain parts
        never see a 'seg' by-key)."""
        seg_of = getattr(part, "segment_of_block", None)
        nseg = getattr(part, "num_segments", 0)
        if seg_of is None or not nseg:
            return None
        key = (part.uid, "#seg")
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is None:
                ids = np.zeros(layout.nrows_padded, dtype=np.int32)
                for bi in range(part.num_blocks):
                    start = layout.starts[bi]
                    ids[start:start + part.block_rows(bi)] = seg_of(bi)
                got = StagedDict(
                    ids=self._put(ids),
                    values=[str(s) for s in range(nseg)],
                    eligible=frozenset(range(part.num_blocks)),
                    nbytes=layout.nrows_padded * 4)
                self.cache.put(key, got)
            return got

    def _stage_seg_slots(self, part, layout: StatsLayout,
                         min_len: int = 0):
        """Segment-aligned slot map of a packed part (int32[S, Lp] row
        indices, -1 padding — tpu/stats_seg.build_seg_slot_map): the
        single-device seg-major kernels and the packed topk k-selection
        gather members into their own padded slot rows through it.
        min_len: floor on Lp (a topk dispatch needs >= k slots)."""
        from .stats_seg import build_seg_slot_map, pad_slots
        lp = pad_slots(max(p.num_rows for p in part.members), min_len)
        key = (part.uid, "#segslots", lp)
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is None:
                idx = build_seg_slot_map(part, layout, min_len)
                # small and consumed whole by every device (the topk
                # k-selection runs under GSPMD on mesh runners):
                # replicated placement, like the bloom planes
                got = StagedBuckets(ids=self._put_replicated(idx),
                                    base=0,
                                    num_buckets=idx.shape[1],
                                    nbytes=int(idx.nbytes))
                self.cache.put(key, got)
            return got

    def _stage_buckets(self, part, layout: StatsLayout, step: int,
                       offset: int, max_buckets: int):
        key = (part.uid, "#tb", step, offset)
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is _UNSTAGEABLE:
                return None
            if got is None:
                got = stage_time_buckets(part, layout, step, offset,
                                         max_buckets, put=self._put)
                if got is None:
                    self.cache.put_small(key, _UNSTAGEABLE)
                else:
                    self.cache.put(key, got)
            return got

    def _assemble_axes(self, part, spec) -> "AxesAssembly | None":
        """Stage everything the stats dispatch needs (value columns,
        bucket/dict/uniq axes); None => this part can't run device stats."""
        from .stats_device import (MAX_ABS_TIMES_ROWS, MAX_BUCKETS,
                                   MAX_QUANTILE_RANGE, MAX_STAT_ROWS)
        layout = self._stats_layout(part)
        if layout.nrows > MAX_STAT_ROWS:
            return None
        numerics = {}
        for fld in spec.value_fields:
            sn = self._stage_numeric(part, fld, layout, MAX_ABS_TIMES_ROWS)
            if sn is None:
                return None
            numerics[fld] = sn

        # one id axis per by key (time buckets / dict-code tables), plus
        # one axis per count_uniq field (its codes enumerate the set)
        axes = []          # (kind, ids_jax, size, decode_payload)
        eligibility = [numerics[fld].eligible
                       for fld in spec.value_fields]
        for bk in spec.by:
            if bk.kind == "seg":
                # per-part segment axis of a packed super-dispatch: the
                # PackedPart's block->member map as per-row int32 ids
                # (tpu/pipeline.py; stats_device.with_segment_axis)
                sg = self._stage_segments(part, layout)
                if sg is None:
                    return None
                axes.append(("s", sg.ids, len(sg.values), None))
                # every block belongs to exactly one segment
                eligibility.append(sg.eligible)
                continue
            if bk.kind == "time":
                sb = self._stage_buckets(part, layout, bk.step, bk.offset,
                                         MAX_BUCKETS)
                if sb is None:
                    return None
                axes.append(("t", sb.ids, sb.num_buckets,
                             (sb.base, bk.step)))
            elif bk.kind == "numbucket":
                key = (part.uid, "#nb", bk.name, bk.fstep, bk.foff)
                with self._key_lock(key):
                    sd = self.cache.get(key)
                    if sd is _UNSTAGEABLE:
                        return None
                    if sd is None:
                        sd = stage_num_buckets(part, bk.name, layout,
                                               bk.fstep, bk.foff,
                                               put=self._put)
                        if sd is None:
                            self.cache.put_small(key, _UNSTAGEABLE)
                            return None
                        self.cache.put(key, sd)
                # payload name None: a uniq axis must never share a
                # BUCKETED axis (it needs raw value codes)
                axes.append(("v", sd.ids, len(sd.values),
                             (None, sd.values)))
                eligibility.append(sd.eligible)
            else:
                sd = self._stage_dict(part, bk.name, layout)
                if sd is None:
                    return None
                axes.append(("v", sd.ids, len(sd.values),
                             (bk.name, sd.values)))
                eligibility.append(sd.eligible)
        uniq_shared = []   # (field, axis_idx): by-field doubles as uniq
        for fld in spec.uniq_fields:
            shared = next((i for i, (k, _i, _s, p) in enumerate(axes)
                           if k == "v" and p[0] == fld), None)
            if shared is not None:
                # same field grouped AND counted: its group axis already
                # enumerates the codes (the S x S product would only fill
                # the diagonal and trip MAX_BUCKETS needlessly)
                uniq_shared.append((fld, shared))
                continue
            sd = self._stage_dict(part, fld, layout)
            if sd is None:
                return None
            axes.append(("u", sd.ids, len(sd.values), (fld, sd.values)))
            eligibility.append(sd.eligible)
        for fld in spec.quantile_fields:
            # the value staging doubles as the histogram axis: same
            # uint32 offsets, cast to int32 inside the jit (combine_ids)
            sn = self._stage_numeric(part, fld, layout,
                                     MAX_ABS_TIMES_ROWS)
            if sn is None or sn.vmax - sn.vmin + 1 > MAX_QUANTILE_RANGE:
                return None
            axes.append(("q", sn.values, sn.vmax - sn.vmin + 1,
                         (fld, sn.vmin)))
            eligibility.append(sn.eligible)
        nb = 1
        nseg = 0
        for k, _i, size, _p in axes:
            nb *= size
            if k == "s":
                nseg = size
        # the segment axis of a packed super-dispatch does NOT count
        # toward the bucket cap: the segment-major kernels
        # (tpu/stats_seg.py) reduce it outside the bucket one-hot, so
        # only the per-member base product pays VMEM/compare width.
        # The [S, buckets] accumulator still scales with the pack —
        # bounded by VL_PACK_PARTS * MAX_BUCKETS output cells.
        if nb // max(nseg, 1) > MAX_BUCKETS:
            return None
        if axes:
            ids_tuple = tuple(a[1] for a in axes)
            # row-major strides in by order
            strides = []
            s = 1
            for _k, _i, size, _p in reversed(axes):
                strides.append(s)
                s *= size
            strides = tuple(reversed(strides))
        else:
            key = (part.uid, "#tb0")
            sb0 = self.cache.get(key)
            if sb0 is None:
                sb0 = StagedBuckets(
                    ids=self._put(np.zeros(layout.nrows_padded,
                                           np.int32)),
                    base=0, num_buckets=1,
                    nbytes=layout.nrows_padded * 4)
                self.cache.put(key, sb0)
            ids_tuple, strides = (sb0.ids,), (1,)
        return AxesAssembly(layout=layout, numerics=numerics, axes=axes,
                            eligibility=eligibility, ids_tuple=ids_tuple,
                            strides=strides, nb=nb,
                            uniq_shared=uniq_shared, nseg=nseg)

    def _key_parts(self, asm: "AxesAssembly", idx: int) -> tuple:
        """(group-key components, uniq-axis values) for one cell."""
        ks = [(idx // stride) % size
              for (_k, _i, size, _p), stride in zip(asm.axes, asm.strides)]
        out = []
        uniq = {}
        qv = {}
        for (kind, _ids, size, payload), k in zip(asm.axes, ks):
            if kind == "s":
                # packed-part segment: stripped (and used to route the
                # partial to its member part) by the pipeline harvest
                out.append(("s", k))
            elif kind == "t":
                base, step = payload
                out.append(("t", base + k * step))
            elif kind == "v":
                out.append(("v", payload[1][k]))
            elif kind == "q":     # quantile histogram: numeric cell value
                fld, vmin0 = payload
                qv[fld] = vmin0 + k
            else:  # uniq axis: not part of the group key
                fld, values = payload
                uniq[fld] = values[k]
        for fld, ai in asm.uniq_shared:
            uniq[fld] = asm.axes[ai][3][1][ks[ai]]
        return tuple(out), uniq, qv

    def _partials_from_counts(self, asm: "AxesAssembly", counts,
                              stats_np: dict) -> list:
        from .stats_device import combine_plane_sums
        partials = []
        for idx in np.nonzero(counts)[0]:
            cnt = int(counts[idx])
            fs = {}
            for fld, packed in stats_np.items():
                vmin0 = asm.numerics[fld].vmin
                s = combine_plane_sums(packed[1:5, idx]) + cnt * vmin0
                fs[fld] = (s, int(packed[5, idx]) + vmin0,
                           int(packed[6, idx]) + vmin0)
            kp, uniq, qv = self._key_parts(asm, int(idx))
            partials.append((kp, cnt, fs, uniq, qv))
        return partials

    # -- fused-path staging hooks (layout-coordinate columns, ts planes) --

    def _stage_fused_field(self, part, field: str, layout):
        from .fused import stage_layout_column
        key = (part.uid, "#fl", field)
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is _UNSTAGEABLE:
                return None
            if got is None:
                got = stage_layout_column(part, field, layout,
                                          self.max_part_bytes,
                                          put=self._put)
                if got is None:
                    self.cache.put_small(key, _UNSTAGEABLE)
                else:
                    self.cache.put(key, got)
            return got

    def _stage_multibyte(self, part, field: str, layout):
        from .fused import stage_multibyte_mask
        key = (part.uid, "#mb", field)
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is None:
                got = stage_multibyte_mask(part, field, layout,
                                           put=self._put)
                self.cache.put(key, got)
            return got

    def _stage_ts_planes(self, part, layout):
        from .fused import stage_ts_planes
        key = (part.uid, "#ts2")
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is None:
                got = stage_ts_planes(part, layout, put=self._put)
                self.cache.put(key, got)
            return got

    def _stage_bloom_plane(self, part, field: str):
        """HBM-resident packed bloom plane for the fused in-dispatch
        bloom kill (tpu/bloom_device.py); cached like all staging."""
        from .bloom_device import stage_bloom_plane
        key = (part.uid, "#bloom", field)
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is _UNSTAGEABLE:
                return None
            if got is None:
                got = stage_bloom_plane(part, field,
                                        put=self._put_replicated)
                if got is None:
                    self.cache.put_small(key, _UNSTAGEABLE)
                else:
                    self.cache.put(key, got)
            return got

    def _stage_sb_plane(self, part, field: str):
        """HBM-resident split-block plane (sealed-part filter index v2)
        for the fused in-dispatch bloom kill: ONE contiguous 8-lane
        gather per (block, token) instead of 6 scattered lane selects.
        None when the part has no valid v2 sidecar for the column."""
        from .bloom_device import stage_sb_plane
        key = (part.uid, "#sbbloom", field)
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is _UNSTAGEABLE:
                return None
            if got is None:
                got = stage_sb_plane(part, field,
                                     put=self._put_replicated)
                if got is None:
                    self.cache.put_small(key, _UNSTAGEABLE)
                else:
                    self.cache.put(key, got)
            return got

    def _stage_block_ids(self, part, layout):
        from .bloom_device import stage_block_ids
        key = (part.uid, "#bid")
        with self._key_lock(key):
            got = self.cache.get(key)
            if got is None:
                got = stage_block_ids(part, layout, put=self._put)
                self.cache.put(key, got)
            return got

    def run_part_topk(self, f, part, bss: dict, spec):
        """Filter + sort-topk threshold prefilter for one part in ONE
        dispatch (tpu/fused.py fused_topk_submit; spec from
        sort_device.device_sort_spec).  Returns block_idx -> bitmap
        holding exactly the filter-matching rows at-or-above the part's
        k-th best sort key (a superset of the part's contribution to the
        global top-k — the host sort processor resolves order and ties
        exactly like the CPU path), or None when the host gate or the
        fused planner declines (run_part then serves the part)."""
        pending = self.run_part_topk_submit(f, part, bss, spec)
        return None if pending is None else pending.harvest()

    def run_part_topk_submit(self, f, part, bss: dict, spec):
        """Async variant of run_part_topk: the dispatch (packed or
        single-part) is ISSUED now and materialized at harvest(), so
        the windowed pipeline keeps sort-topk units outstanding like
        every other query shape.  None when the host gate or the fused
        planner declines: the caller hands the part to run_part, whose
        filter program reads the same staged columns."""
        cand_rows = sum(bs.nrows for bs in bss.values())
        if self._gate_host(f, part, bss, stats_rows=max(cand_rows, 1)):
            return None               # run_part re-gates and runs host
        from .fused import fused_topk_submit
        return fused_topk_submit(self, f, part, bss, spec)

    def run_part_stats(self, f, part, bss: dict, spec):
        """Filter + stats partials for one part.

        When the whole filter tree is device-expressible and every
        candidate block is stats-eligible (tpu/fused.py), filter AND
        stats run as ONE device dispatch: the row bitmap never leaves
        HBM and only (buckets,)-sized partials come back, the fused
        analogue of the reference's per-worker stats shards merged at
        flush (pipe_stats.go:354-377).  Otherwise the host executor
        evaluates the filter and the host pipe aggregates.

        Returns (bms, handled, partials):
        - bms: block_idx -> bitmap (covers at least the non-handled
          blocks; empty when everything was handled on device);
        - handled: block idxs fully accounted for by the partials (the
          caller must NOT feed them through the row path);
        - partials: list of
          (key_parts, count, field_stats, uniq_vals, quant_vals) where
          key_parts follows the spec's by order with elements
          ("t", bucket_ns) for the time axis and ("v", value_str) for
          group-by fields, field_stats maps
          field -> (sum:int, vmin:int, vmax:int), uniq_vals maps
          count_uniq fields to the cell's value string, and quant_vals
          maps quantile/median fields to the cell's numeric value.
        """
        return self.run_part_stats_submit(f, part, bss, spec).harvest()

    def run_part_stats_submit(self, f, part, bss: dict, spec):
        """Async variant of run_part_stats: the fused dispatch (when the
        shape allows one) is ISSUED now and materialized at harvest(), so
        the windowed pipeline can keep several parts outstanding.  Host-
        gated and declined parts compute synchronously and come back as
        ready handles — one protocol either way."""
        from .fused import _Ready, fused_stats_submit
        cand_rows = sum(bs.nrows for bs in bss.values())
        if self._gate_host(f, part, bss, stats_rows=max(cand_rows, 1)):
            self._bump("gated_host_parts")
            return _Ready((self._host_eval_part(f, bss), set(), []))
        asm = self._assemble_axes(part, spec)
        if asm is not None:
            pending = fused_stats_submit(self, f, part, bss, spec, asm)
            if pending is not None:
                return pending
        return _Ready((self._decline_to_host(f, bss), set(), []))

    def run_part_submit(self, f, part, bss: dict):
        """Async variant of run_part for ROW queries: the whole filter
        tree compiles into ONE fused dispatch (fused.fused_filter_submit)
        whose packed result is materialized at harvest(); a part the
        planner declines comes back ready from the host executor."""
        from .fused import _Ready, fused_filter_submit
        if self._gate_host(f, part, bss):
            self._bump("gated_host_parts")
            return _Ready(self._host_eval_part(f, bss))
        pending = fused_filter_submit(self, f, part, bss)
        if pending is not None:
            return pending
        return _Ready(self._decline_to_host(f, bss))
