"""Async multi-part device pipeline: in-flight dispatch window +
small-part packing.

Each completed dispatch pays a fixed round trip on top of its kernel
time, which is why round 3 collapsed each part to ONE fused dispatch —
but the part walk itself stayed serial: every part's dispatch blocked on
the previous part's host materialization, so a query over P parts paid P
serial round trips even though the dispatches are independent.  This
module is the per-part execution driver that removes that serialization
(engine/searcher feeds it every selected partition's parts):

1. **In-flight dispatch window** — fused dispatches return asynchronous
   jax arrays; nothing forces them to the host at submit time.  Up to
   ``VL_INFLIGHT`` (default 4) units keep their dispatches outstanding;
   completed results are harvested strictly in submission order, so the
   downstream block order (and the stats absorb order) is bit-identical
   to the serial walk.  Prefetch staging (BatchRunner.submit_prefetch)
   follows the same depth, so the host decode/upload of part N+k
   overlaps the device scans of parts N..N+k-1 instead of the old
   depth-1 double buffer.

2. **Small-part packing** — LSM partitions are full of small fresh
   parts, and each one still costs a full dispatch RTT.  Consecutive
   parts whose row counts share a padded-size bucket (kernels.pad_bucket
   — the same bucketing the staging layer uses to keep jit caches small)
   are presented to the fused planner as ONE part-like value
   (PackedPart: members' blocks concatenated, in member order) and
   evaluated in ONE fused super-dispatch.  Row bitmaps split back per
   member on the host; stats partials carry a per-part segment axis
   (stats_device.with_segment_axis) and are segment-reduced back to
   per-member partials, so the stats processor sees exactly the per-part
   absorb granularity of the serial path.  P small parts cost
   ceil(P / VL_PACK_PARTS) dispatches instead of P.

Cancellation (`QueryCancelled`) and deadline expiry
(`QueryTimeoutError`) drain the window without writing partial blocks
downstream: in-flight handles are simply dropped (jax buffers are
released when the device finishes; staging entries are complete,
keyed, budget-accounted values, so the StagingCache stays balanced).

Sizes: VL_INFLIGHT=1 reduces to the serial submit-then-harvest walk;
VL_PACK_PARTS=1 disables packing.
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass

import numpy as np
from .. import config

from ..obs import activity, events, hist, tracing
from .. import sched
from .kernels import pad_bucket

# adaptive pack-size clamps: parts below the floor always pack (the
# bench measures 1.4-4x wins for flush-sized parts even on a ~0.1ms
# local backend); parts above the ceiling never do
_PACK_ROWS_FLOOR = 16384
_PACK_ROWS_CEIL = 1 << 20


_AUTO_DEPTH_MIN = 2
_AUTO_DEPTH_MAX = 16
_AUTO_DEPTH_DEFAULT = 4


def inflight_auto() -> bool:
    return (config.env("VL_INFLIGHT") or "").strip().lower() == "auto"


def inflight_depth(runner=None, probe: bool = True) -> int:
    """VL_INFLIGHT: max units with outstanding dispatches (>=1).

    ``VL_INFLIGHT=auto`` derives the depth from the cost model's
    calibration EWMAs (vl_tpu_cost_rtt_seconds and the per-unit emit
    EWMA, both /metrics gauges): the window hides one dispatch RTT
    behind wait-free host emit work, so the device never idles once
    ``depth * emit_per_unit >= rtt`` — depth = ceil(rtt / emit_ewma),
    clamped to [2, 16].  An explicit integer always wins; cold
    calibration falls back to the default.

    probe=False never issues the lazy RTT calibration dispatch — the
    EXPLAIN pricing pass (obs/explain.py) prices with the SAME depth
    derivation but must stay zero-dispatch (like pack_rows_cap)."""
    v = config.env("VL_INFLIGHT")
    if v.strip().lower() == "auto":
        return _auto_depth(runner, probe)
    try:
        return max(1, int(v))
    except ValueError:
        return _AUTO_DEPTH_DEFAULT


def _auto_depth(runner, probe: bool = True) -> int:
    if runner is None:
        return _AUTO_DEPTH_DEFAULT
    host = runner.cost.emit_ewma
    if not host:
        # calibration cold: no harvested unit observed yet (first query
        # of this runner) — the default window, like VL_INFLIGHT unset
        return _AUTO_DEPTH_DEFAULT
    # we're on the query path already, so the lazy RTT probe is fair
    # game here (unlike /metrics scrapes — see BatchRunner.stats);
    # probe=False callers price with the unprobed calibration instead
    rtt = runner.cost.measured_rtt() if probe else runner.cost.rtt
    if not rtt:
        return _AUTO_DEPTH_DEFAULT
    import math
    return min(_AUTO_DEPTH_MAX,
               max(_AUTO_DEPTH_MIN, math.ceil(rtt / host)))


def pack_limit() -> int:
    """VL_PACK_PARTS: max parts per super-dispatch (<=1 disables)."""
    return max(1, config.env_int("VL_PACK_PARTS"))


def pack_topk_k() -> int:
    """VL_PACK_TOPK_K: largest `sort ... limit` k eligible for packed
    sort-topk super-dispatches (0 disables topk packing).  The packed
    dispatch k-selects per member over the segment slot grid, whose
    slot axis must hold at least k entries per member — a huge k
    inflates every member's padded slots, so past this cap the
    per-part dispatches win."""
    return max(0, config.env_int("VL_PACK_TOPK_K"))


def pack_policy(runner, sort_spec, probe: bool = True):
    """(packable, pack_max, rows_cap) — THE pack-eligibility rule, in
    one place for the execution planner (_unit_stream) and the EXPLAIN
    walk (obs/explain.py), so the displayed pack membership can never
    diverge from the dispatched one.  Sort-topk shapes pack when their
    k fits the VL_PACK_TOPK_K cap (the packed dispatch k-selects per
    member); stats/row shapes pack as before."""
    pack_max = pack_limit()
    packable = pack_max > 1 and (
        sort_spec is None or 0 < sort_spec.k <= pack_topk_k())
    rows_cap = pack_rows_cap(runner, probe) if packable else 0
    return packable, pack_max, rows_cap


def pack_rows_cap(runner, probe: bool = True) -> int:
    """Parts above this many rows never pack.

    Packing trades per-dispatch overhead for a bigger fused program, so
    it pays while a part's whole-part scan time is below the dispatch
    round trip — which the cost model MEASURES.  The cap scales with
    rtt * device_rate (at ~128 scanned bytes/row), so big parts keep
    their own dispatches on fast-RTT backends (0.5-0.7x regressions when
    packing 128k-row parts, counted on jax-CPU) while a slow-RTT backend
    packs far larger parts.  VL_PACK_MAX_ROWS overrides the adaptive cap
    outright.

    probe=False never issues the lazy RTT calibration dispatch: the
    EXPLAIN pricing pass (obs/explain.py) plans with the floor until a
    real query has measured the round trip — `explain=1` must stay
    zero-dispatch."""
    v = config.env("VL_PACK_MAX_ROWS")
    if v:
        try:
            return max(1, int(v))
        except ValueError:
            pass
    rtt = runner.cost.measured_rtt() if probe else runner.cost.rtt
    if rtt is None:
        return _PACK_ROWS_FLOOR
    cap = rtt * runner.cost._dev_rate() / 128
    return int(min(max(cap, _PACK_ROWS_FLOOR), _PACK_ROWS_CEIL))


# ---------------- packed parts ----------------

class PackedPart:
    """Several small immutable parts presented as ONE part-like value.

    Blocks are the members' blocks concatenated in member order with
    re-based indices, so every staging/planning routine that walks
    ``range(part.num_blocks)`` (stage_layout_column, part_stats_layout,
    stage_numeric/dict/buckets, the bloom filterbank, the fused planner)
    works unchanged over the pack;  ``segment_of_block`` maps a pack
    block back to its member ordinal — the segment id of the
    super-dispatch.  The uid is the member-uid tuple, so StagingCache
    entries for a pack are stable across queries exactly like per-part
    staging (parts are immutable; a merge mints fresh member uids and
    therefore a fresh pack identity)."""

    def __init__(self, members: list):
        self.members = list(members)
        self.uid = ("pack",) + tuple(p.uid for p in self.members)
        self._offsets = []
        self._map = []
        for mi, p in enumerate(self.members):
            self._offsets.append(len(self._map))
            for bi in range(p.num_blocks):
                self._map.append((mi, p, bi))
        self.num_rows = sum(p.num_rows for p in self.members)
        self.min_ts = min(p.min_ts for p in self.members)
        self.max_ts = max(p.max_ts for p in self.members)

    @property
    def num_blocks(self) -> int:
        return len(self._map)

    @property
    def num_segments(self) -> int:
        return len(self.members)

    def block_offset(self, mi: int) -> int:
        """Pack block index of member mi's block 0."""
        return self._offsets[mi]

    def segment_of_block(self, bi: int) -> int:
        return self._map[bi][0]

    # -- block-level delegation (Part / InmemoryPart uniform API) --
    def block_rows(self, bi: int) -> int:
        _mi, p, b = self._map[bi]
        return p.block_rows(b)

    def block_min_ts(self, bi: int) -> int:
        _mi, p, b = self._map[bi]
        return p.block_min_ts(b)

    def block_stream_id(self, bi: int):
        _mi, p, b = self._map[bi]
        return p.block_stream_id(b)

    def block_tags(self, bi: int) -> str:
        _mi, p, b = self._map[bi]
        return p.block_tags(b)

    def block_consts(self, bi: int):
        _mi, p, b = self._map[bi]
        return p.block_consts(b)

    def block_column_meta(self, bi: int, name: str):
        _mi, p, b = self._map[bi]
        return p.block_column_meta(b, name)

    def block_column(self, bi: int, name: str):
        _mi, p, b = self._map[bi]
        return p.block_column(b, name)

    def block_column_bloom(self, bi: int, name: str):
        _mi, p, b = self._map[bi]
        return p.block_column_bloom(b, name)

    def block_timestamps(self, bi: int):
        _mi, p, b = self._map[bi]
        return p.block_timestamps(b)


# pack instances strongly reference their members (incl. in-RAM
# InmemoryPart blocks), so the cache is a SMALL hard-capped LRU — it
# only needs to keep the hot packs' filter banks warm across queries;
# the staged tensors live in the byte-budgeted StagingCache keyed by
# the (deterministic) pack uid and survive regardless of this cache
_PACK_CACHE_MAX = 32


def _get_pack(runner, members: list) -> PackedPart:
    key = tuple(p.uid for p in members)
    with runner._pack_mu:
        got = runner._packs.get(key)
        if got is None:
            got = runner._packs[key] = PackedPart(members)
        runner._packs.move_to_end(key)
        while len(runner._packs) > _PACK_CACHE_MAX:
            runner._packs.popitem(last=False)
        return got


# ---------------- units and harvested results ----------------

@dataclass
class _Member:
    """One member part's share of a harvested unit."""
    part: object
    blocks: list                   # [(orig block idx, BlockSearch)]
    bms: dict                      # orig block idx -> bool bitmap
    handled: set                   # orig idxs fully covered by partials
    partials: list


@dataclass
class _Unit:
    part: object                   # Part or PackedPart (dispatch target)
    bss: dict                      # dispatch-coord block idx -> BlockSearch
    members: list                  # [(member part, [(orig_bi, bs), ...])]
    pack: bool = False


class _UnitReady:
    """Already-materialized unit result (host paths, constant trees)."""

    def __init__(self, members: list):
        self._members = members

    def harvest(self, sync) -> list:
        return self._members


class _CacheHit:
    """Planning marker for a part whose result came from the per-part
    result cache (engine/standing/resultcache.py).  num_rows sits above
    every pack cap so iter_pack_groups keeps the hit in its own
    singleton group — a cached part must never join a pack dispatch."""

    __slots__ = ("part", "entry")
    num_rows = 1 << 62

    def __init__(self, part, entry):
        self.part = part
        self.entry = entry


class _CachedUnit:
    """A unit satisfied entirely from the result cache: no prefetch, no
    dispatch, no scheduler slot — it rides the window as an
    already-materialized member so harvest stays in submission order
    (downstream block order and stats absorb order bit-identical to the
    uncached walk)."""

    pack = False
    cached = True

    def __init__(self, part, member: "_Member"):
        self.part = part
        self.bss: dict = {}
        self.members = [(part, member.blocks)]
        self.ready = [member]


class _SingleRows:
    def __init__(self, unit: _Unit, pending):
        self.unit = unit
        self.pending = pending

    def harvest(self, sync) -> list:
        bms = self.pending.harvest(sync)
        part, blocks = self.unit.members[0]
        return [_Member(part, blocks, bms, set(), [])]


class _SingleStats:
    def __init__(self, unit: _Unit, pending):
        self.unit = unit
        self.pending = pending

    def harvest(self, sync) -> list:
        bms, handled, partials = self.pending.harvest(sync)
        part, blocks = self.unit.members[0]
        return [_Member(part, blocks, bms, handled, partials)]


class _PackRows:
    def __init__(self, unit: _Unit, pending):
        self.unit = unit
        self.pending = pending

    def harvest(self, sync) -> list:
        packbms = self.pending.harvest(sync)   # keyed by pack block idx
        out = []
        for mi, (p, blocks) in enumerate(self.unit.members):
            off = self.unit.part.block_offset(mi)
            bms = {bi: packbms[off + bi] for bi, _bs in blocks}
            out.append(_Member(p, blocks, bms, set(), []))
        return out


class _PackStats:
    """Harvest of a packed stats super-dispatch: partials come back with
    a leading ("s", member_idx) key component (the segment axis) and are
    segment-reduced to per-member partial lists, absorbed in member
    order — exactly the serial per-part granularity."""

    def __init__(self, unit: _Unit, pending):
        self.unit = unit
        self.pending = pending

    def harvest(self, sync) -> list:
        _bms, _handled, partials = self.pending.harvest(sync)
        per_seg: dict[int, list] = {}
        for kp, cnt, fs, uniq, qv in partials:
            seg = int(kp[0][1])     # leading component IS the segment
            per_seg.setdefault(seg, []).append((kp[1:], cnt, fs, uniq,
                                                qv))
        out = []
        for mi, (p, blocks) in enumerate(self.unit.members):
            out.append(_Member(p, blocks, {}, {bi for bi, _bs in blocks},
                               per_seg.get(mi, [])))
        return out


# ---------------- planning ----------------

def pack_bucket(part) -> int:
    """The padded-row bucket packing groups on (shared with the EXPLAIN
    planner so the displayed pack membership is the dispatched one)."""
    return pad_bucket(max(part.num_rows, 1), minimum=1024)


# widest time range one pack may cover: the fused ts staging carries
# ns offsets from the pack minimum as (hi >> 16) int32 planes, exact
# only below 2**47 ns (~39h).  Same-day packs never come close; packs
# spanning a partition boundary (cross-partition window) must split
# when the data really spans further.
PACK_TS_SPAN_MAX = 1 << 47


def iter_pack_groups(items, packable: bool, pack_max: int,
                     rows_cap: int):
    """Fold an iterable of pruned part items into dispatch-unit groups
    — THE pack-membership rules, in one place: consecutive small parts
    (<= rows_cap rows) sharing a padded-row bucket group up to
    pack_max, provided the group's combined time range stays inside
    the staging-exact PACK_TS_SPAN_MAX window; everything else is its
    own unit.  Items are tuples whose first element is the part (the
    execution stream carries (part, bis, ctx); EXPLAIN carries
    (part, bis)) — passed through untouched.  Lazy: pulls from `items`
    only as groups are consumed, so the execution stream's early exits
    (limit, deadline) stop the header walk exactly where the serial
    loop would, and the EXPLAIN pricing pass (obs/explain.py) walks
    the identical grouping without dispatching."""
    group: list = []        # packable run sharing one row bucket
    gmin = gmax = 0         # group's combined time range (ns)
    for it in items:
        part = it[0]
        small = packable and part.num_rows <= rows_cap
        if not small:
            if group:
                yield group
                group = []
            yield [it]
            continue
        if group and (
                pack_bucket(group[0][0]) != pack_bucket(part)
                or max(gmax, part.max_ts) - min(gmin, part.min_ts)
                >= PACK_TS_SPAN_MAX):
            yield group
            group = []
        if group:
            gmin = min(gmin, part.min_ts)
            gmax = max(gmax, part.max_ts)
        else:
            gmin, gmax = part.min_ts, part.max_ts
        group.append(it)
        if len(group) >= pack_max:
            yield group
            group = []
    if group:
        yield group


def _unit_stream(runner, items, head, stats_spec, sort_spec,
                 token_leaves, check_deadline, qcache=None):
    """Lazily fold the pruned part stream into dispatch units, in part
    order.  `items` yields (PartStep, ctx), the header walk's surviving
    parts with their candidate blocks (engine/planwalk.py) — the
    cross-partition window feeds parts from EVERY selected partition
    through one stream (each carrying its partition's SearchContext),
    so packs may span a day boundary when the members share a pad
    bucket.

    Consecutive parts pack when packing is on, the query shape supports
    a pack dispatch (pack_policy — sort-topk packs under the
    VL_PACK_TOPK_K cap via the per-member k-selection), every member is
    small (pack_rows_cap) and the members share a padded-row bucket
    (the shared width/nrows bucketing that keeps the jit cache small
    keeps pack shapes small too).  Lazy on purpose: a `limit`-style
    early exit (head.is_done) or a deadline must stop the header walk
    exactly like the serial loop did — the consumer only pulls the
    window's lookahead ahead of execution."""
    from ..engine import planwalk
    from ..engine.block_search import BlockSearch
    from ..engine.searcher import QueryCancelled
    packable, pack_max, rows_cap = pack_policy(runner, sort_spec)

    def make_unit(group):
        if len(group) == 1 and isinstance(group[0][0], _CacheHit):
            hit, bis, ctx = group[0]
            e = hit.entry
            if e.kind == "stats":
                member = _Member(hit.part, [], {}, set(),
                                 qcache.entry_partials(e))
            else:
                blocks = []
                for bi in bis:
                    bs = BlockSearch(hit.part, bi)
                    bs.ctx = ctx
                    blocks.append((bi, bs))
                member = _Member(hit.part, blocks, qcache.entry_bms(e),
                                 set(), [])
            return _CachedUnit(hit.part, member)
        if len(group) == 1:
            p, bis, ctx = group[0]
            bss = {}
            blocks = []
            for bi in bis:
                bs = BlockSearch(p, bi)
                bs.ctx = ctx
                bss[bi] = bs
                blocks.append((bi, bs))
            return _Unit(p, bss, [(p, blocks)])
        pack = _get_pack(runner, [g[0] for g in group])
        if len({id(g[2].partition) for g in group}) > 1:
            runner._bump("cross_partition_packs")
        bss = {}
        members = []
        for mi, (p, bis, ctx) in enumerate(group):
            off = pack.block_offset(mi)
            blocks = []
            for bi in bis:
                bs = BlockSearch(p, bi)
                bs.ctx = ctx
                bss[off + bi] = bs
                blocks.append((bi, bs))
            members.append((p, blocks))
        return _Unit(pack, bss, members, pack=True)

    act = activity.current_activity()

    def pruned():
        for step, ctx in items:
            check_deadline()
            if head.is_done():
                raise QueryCancelled()
            if step.cold and planwalk.kill_cold(step, token_leaves):
                # the priced walk probed cached aggregates only: the
                # cold fold, and its kill, is paid as the window
                # reaches the part
                planwalk.observe(step, runner)
                continue
            part, bis = step.part, step.bis
            # registry progress at part granularity (the planning pull
            # IS the prune stage, so these land as the walk advances)
            activity.note_part_scanned(act, part, bis, step.rows)
            if qcache is not None:
                e = qcache.probe(part, bis)
                if e is not None:
                    # result cached from an earlier identical query:
                    # the part never enters the dispatch stream
                    yield _CacheHit(part, e), bis, ctx
                    continue
            yield part, bis, ctx

    for group in iter_pack_groups(pruned(), packable, pack_max,
                                  rows_cap):
        yield make_unit(group)


# ---------------- submission ----------------

def _submit(runner, f, unit: _Unit, stats_spec, sort_spec, spec_seg):
    if stats_spec is not None:
        if unit.pack:
            return _submit_pack_stats(runner, f, unit, stats_spec,
                                      spec_seg)
        return _SingleStats(unit, runner.run_part_stats_submit(
            f, unit.part, unit.bss, stats_spec))
    if sort_spec is not None:
        if unit.pack:
            return _submit_pack_topk(runner, f, unit, sort_spec)
        pending = runner.run_part_topk_submit(f, unit.part, unit.bss,
                                              sort_spec)
        if pending is None:
            # the gate or the topk program declined: a row unit, the
            # host pipe sorts
            pending = runner.run_part_submit(f, unit.part, unit.bss)
        # async: the dispatch stays outstanding in the window like
        # every other shape (harvest -> block_idx -> bitmap)
        return _SingleRows(unit, pending)
    if unit.pack:
        return _submit_pack_rows(runner, f, unit)
    return _SingleRows(unit, runner.run_part_submit(f, unit.part,
                                                    unit.bss))


def _count_pack(runner, unit: _Unit, pending) -> None:
    """Count a packed SUPER-DISPATCH — constant-tree packs come back as
    _Ready without touching the device, and must not inflate the
    dispatch-reduction numbers the bench/PERF cost model reports."""
    from .fused import _Ready
    if isinstance(pending, _Ready):
        return
    runner._bump("packed_dispatches")
    runner._bump("packed_parts", len(unit.members))


def _host_members(runner, f, unit: _Unit) -> list:
    out = []
    for p, blocks in unit.members:
        mbss = dict(blocks)
        out.append(_Member(p, blocks, runner._host_eval_part(f, mbss),
                           set(), []))
    return out


def _submit_pack_rows(runner, f, unit: _Unit):
    if runner._gate_host(f, unit.part, unit.bss):
        runner._bump("gated_host_parts", len(unit.members))
        return _UnitReady(_host_members(runner, f, unit))
    from .fused import fused_filter_submit
    pending = fused_filter_submit(runner, f, unit.part, unit.bss)
    if pending is not None:
        _count_pack(runner, unit, pending)
        return _PackRows(unit, pending)
    # the planner declined the pack, maybe for a reason a member does
    # not have: each member goes through the single-part submit
    out = []
    for p, blocks in unit.members:
        bms = runner.run_part_submit(f, p, dict(blocks)).harvest()
        out.append(_Member(p, blocks, bms, set(), []))
    return _UnitReady(out)


def _submit_pack_topk(runner, f, unit: _Unit, sort_spec):
    """Packed sort-topk super-dispatch: ONE fused dispatch k-selects
    per member over the concatenated pack (fused._topk_dispatch's
    segment unroll), so every member's harvested candidate set — and
    therefore the host sort processor's input, order and ties included
    — is bit-identical to its own single-part dispatch."""
    cand_rows = sum(bs.nrows for bs in unit.bss.values())
    if runner._gate_host(f, unit.part, unit.bss,
                         stats_rows=max(cand_rows, 1)):
        runner._bump("gated_host_parts", len(unit.members))
        return _UnitReady(_host_members(runner, f, unit))
    from .fused import _Ready, fused_topk_submit
    pending = fused_topk_submit(runner, f, unit.part, unit.bss, sort_spec)
    if pending is not None:
        _count_pack(runner, unit, pending)
        if not isinstance(pending, _Ready):
            runner._bump("packed_topk_dispatches")
        return _PackRows(unit, pending)
    # decline (non-numeric sort column, unfusable leaf): each member
    # goes through the single-part submit
    out = []
    for p, blocks in unit.members:
        mbss = dict(blocks)
        bms = runner.run_part_topk(f, p, mbss, sort_spec)
        if bms is None:
            bms = runner.run_part(f, p, mbss)
        out.append(_Member(p, blocks, bms, set(), []))
    return _UnitReady(out)


def _submit_pack_stats(runner, f, unit: _Unit, stats_spec, spec_seg):
    cand_rows = sum(bs.nrows for bs in unit.bss.values())
    if runner._gate_host(f, unit.part, unit.bss,
                         stats_rows=max(cand_rows, 1)):
        runner._bump("gated_host_parts", len(unit.members))
        return _UnitReady(_host_members(runner, f, unit))
    from .fused import fused_stats_submit
    pending = None
    asm = runner._assemble_axes(unit.part, spec_seg)
    if asm is not None:
        pending = fused_stats_submit(runner, f, unit.part, unit.bss,
                                     spec_seg, asm)
    if pending is not None:
        _count_pack(runner, unit, pending)
        return _PackStats(unit, pending)
    # decline (ineligible column, bucket blowup, unfusable leaf): each
    # member goes through the single-part submit with the ORIGINAL spec
    out = []
    for p, blocks in unit.members:
        bms, handled, partials = runner.run_part_stats(f, p, dict(blocks),
                                                       stats_spec)
        out.append(_Member(p, blocks, bms, handled, partials))
    return _UnitReady(out)


# ---------------- the window driver ----------------

def _make_sync(runner):
    """The window's SINGLE deliberate host-sync point: everything the
    device path downloads during a windowed scan funnels through here,
    so the blocked time is measurable (host_sync_wait_s) and the hot
    path stays statically clean (tools/vlint hotpath checker)."""

    def sync(arr):
        t0 = time.perf_counter()
        # the window's single harvest point — materializing a
        # completed dispatch in submission order IS the pipeline's
        # output step; everything upstream stays async
        # vlint: allow-jax-host-sync(the single deliberate harvest sync; upstream stays async)
        out = np.asarray(arr)
        dt = time.perf_counter() - t0
        runner._bump("host_sync_wait_s", dt)
        hist.HOST_SYNC_WAIT.observe(dt)
        tracing.current_span().add("host_sync_wait_s", dt)
        return out

    return sync


def scan_device_stream(items, q, head, runner, needed, deadline,
                       stats_spec, sort_spec, token_leaves,
                       qcache=None) -> None:
    """Drive a cross-partition part stream through the async dispatch
    window.

    Candidate pruning and part-aggregate kills are the one header
    walk's (engine/planwalk.py); submission
    keeps up to VL_INFLIGHT units' dispatches outstanding; harvest is in
    submission order, so downstream block order and stats absorb
    granularity are identical to the serial path.  `items` yields
    (PartStep, ctx): the list a priced query's walk already produced,
    or the walk itself, lazy — partitions then resolve their stream
    filters and snapshot their parts only as the planning pull reaches
    them.  Either way parts from partition N+1 submit while partition N
    harvests, prefetch depth survives the day boundary, and packs may
    span it (iter_pack_groups' pad-bucket + time-span rules)."""
    from ..engine.block_result import BlockResult
    from ..engine.searcher import (QueryCancelled, QueryTimeoutError,
                                   _absorb_stats_partials)

    def check_deadline():
        if deadline is not None and time.monotonic() > deadline:
            raise QueryTimeoutError(
                "query exceeded -search.maxQueryDuration")

    def _slot_check():
        # runs on every fair-queue wait tick: a cancelled or
        # over-deadline query must leave the queue, not hold its place
        check_deadline()
        if head.is_done():
            raise QueryCancelled()

    f = q.filter
    depth = inflight_depth(runner)
    if inflight_auto():
        runner._set("inflight_auto_depth", depth)
    sync = _make_sync(runner)
    act = activity.current_activity()
    window: deque = deque()
    spec_seg = None
    if stats_spec is not None and pack_limit() > 1 and sort_spec is None:
        from .stats_device import with_segment_axis
        spec_seg = with_segment_axis(stats_spec)

    def emit(members: list) -> None:
        sp = tracing.current_span()
        for m in members:
            if qcache is not None:
                # harvest-side population: a fully-materialized member
                # is the per-part answer a repeated query can replay
                # (store skips parts this query already hit on)
                qcache.store_member(m)
            if stats_spec is not None and m.partials:
                sp.add("stats_partials", len(m.partials))
                _absorb_stats_partials(head, q, stats_spec, m.partials)
            for bi, bs in m.blocks:
                if bi in m.handled:
                    continue
                if head.is_done():
                    raise QueryCancelled()
                bm = m.bms[bi]
                if not bm.any():
                    continue
                br = BlockResult.from_block_search(bs, bm, needed)
                sp.add("blocks_out")
                sp.add("rows_downloaded", br.nrows)
                head.write_block(br)

    stream = _unit_stream(runner, items, head, stats_spec, sort_spec,
                          token_leaves, check_deadline, qcache=qcache)
    lookahead: deque = deque()
    exhausted = False
    prefetched: set = set()
    sort_field = sort_spec.field if sort_spec is not None else None
    psp = tracing.current_span()
    seq = 0

    def refill() -> None:
        # plan only the window's lookahead ahead of execution: an early
        # exit (limit hit, deadline) stops the header walk right where
        # the serial loop would have
        nonlocal exhausted
        if exhausted or len(lookahead) >= depth + 1:
            return
        act.set_phase("prune")
        # the planning pull IS the prune stage: a lazy header walk
        # advances inside _unit_stream, so its prune counters land on
        # this span (a priced query's walk ran under a `prune` span of
        # its own, engine/searcher)
        with psp.span("prune") as prsp:
            planned = 0
            while not exhausted and len(lookahead) < depth + 1:
                try:
                    lookahead.append(next(stream))
                    planned += 1
                except StopIteration:
                    exhausted = True
            prsp.set("units_planned", planned)

    def harvest_one() -> None:
        hseq, hunit, t_submit, pending, leased = window.popleft()
        act.set_phase("harvest")
        act.set("dispatches_in_flight", len(window))
        with psp.span("harvest", unit=hseq) as hsp:
            # device_sync: blocked materializing the dispatch result;
            # emit: host-side block materialization + downstream write
            # (for streaming sinks that includes NDJSON serialization).
            # Split children make the emit cost attributable per query
            # (?trace=1), not just in the bench.
            with hsp.span("device_sync"):
                members = pending.harvest(sync)
            # the dispatch is off the device: return the leased slot
            # BEFORE the host-side emit so contending queries overlap
            # their device work with our emit phase.  Known tradeoff:
            # the OTHER window entries' leases stay held while emit
            # runs, and a stalled streaming client (streamwork's
            # bounded queue) can block emit — pinning up to depth-1
            # slots per stalled query until its deadline/disconnect
            # drain fires.  Bounded and self-healing, but a
            # completion-driven release (harvest on dispatch-done
            # callbacks) would free them earlier — ROADMAP follow-on.
            # Cached units never leased a slot (nothing dispatched),
            # so only leased entries return one.
            if leased:
                slots.release()
            # _UnitReady units never dispatched (host gate / per-member
            # fallback): their submit-to-harvest time is pure window
            # queue wait and must not pollute the device-RTT histogram
            dispatched = not isinstance(pending, _UnitReady)
            rtt = time.perf_counter() - t_submit
            if dispatched:
                hist.DISPATCH_RTT.observe(rtt)
                # the EXPLAIN pricing pass's per-unit round-trip term
                # (CostModel.predict) feeds on REAL unit RTTs, not the
                # minimal probe the routing gate uses
                runner.cost.observe_unit_rtt(rtt)
            if hsp.enabled:
                if dispatched:
                    hsp.set("dispatch_rtt_s", round(rtt, 6))
                else:
                    hsp.set("host_unit", True)
                if hunit.pack:
                    hsp.set("pack_members",
                            [str(p.uid) for p, _b in hunit.members])
            t_e0 = time.perf_counter()
            act.set_phase("emit")
            with hsp.span("emit"):
                emit(members)
            emit_dt = time.perf_counter() - t_e0
            hist.EMIT_SECONDS.observe(emit_dt)
            # ONLY the emit phase feeds the VL_INFLIGHT=auto
            # calibration: including the device_sync wait would make
            # the signal track rtt/depth and contract the window on
            # exactly the high-RTT backends that need it deep.
            # Known tradeoff: emit_dt still includes downstream SINK
            # time — for a streaming response that can be a slow
            # client's backpressure (streamwork's bounded queue), which
            # shallows the derived depth.  That query is output-bound
            # (a deeper device window buys it nothing), and the EWMA
            # (alpha 0.3) recovers within a few units once a fast
            # consumer runs on the shared runner.
            runner.cost.observe_emit(emit_dt)

    with sched.device_slots(act) as slots:
        try:
            with psp.span("pipeline", inflight_depth=depth) as plsp:
                psp = plsp
                while True:
                    refill()
                    if not lookahead:
                        break
                    unit = lookahead.popleft()
                    check_deadline()
                    if head.is_done():
                        raise QueryCancelled()
                    # deepened prefetch: stage every unit inside the
                    # window's lookahead, so part N+k's host decode/
                    # upload overlaps the scans of N..N+k-1 (packs
                    # prefetch as the pack, hitting the same #fl/#num
                    # staging keys the super-dispatch will use)
                    todo = [uj for uj in lookahead
                            if not getattr(uj, "cached", False)
                            and uj.part.uid not in prefetched]
                    if todo:
                        with psp.span("stage", units=len(todo)):
                            for uj in todo:
                                prefetched.add(uj.part.uid)
                                runner.submit_prefetch(
                                    uj.part, f, stats_spec,
                                    cand_bis=list(uj.bss),
                                    sort_field=sort_field)
                    # our own window's depth backpressure is NOT
                    # scheduler wait: drain it untimed first, so the
                    # slot-wait metric means what it says
                    while len(window) >= depth:
                        check_deadline()
                        harvest_one()
                    if getattr(unit, "cached", False):
                        # a result-cache hit: rides the window for
                        # submission-order harvest but skips the slot
                        # lease, the dispatch counters and prefetch —
                        # the part's price collapsed to ~0
                        runner._bump("result_cache_units")
                        window.append((seq, unit, time.perf_counter(),
                                       _UnitReady(unit.ready), False))
                        seq += 1
                        runner._bump_max("inflight_hwm", len(window))
                        if act.enabled:
                            act.add("result_cache_hits")
                            act.set("dispatches_in_flight",
                                    len(window))
                        continue
                    # lease the submit slot from the shared scheduler:
                    # fast-path non-blocking grant (uncontended budget
                    # behaves exactly like the per-query window); under
                    # contention harvest our own oldest unit — freeing
                    # a slot the fair queue hands to whoever is
                    # furthest below their share — and block in the
                    # queue only once nothing of ours is in flight
                    t_w0 = time.perf_counter()
                    while not slots.try_acquire():
                        if window:
                            check_deadline()
                            harvest_one()
                        else:
                            with psp.span("sched_wait"):
                                slots.acquire(check=_slot_check)
                            break
                    slot_wait_s = time.perf_counter() - t_w0
                    hist.SLOT_WAIT.observe(slot_wait_s)
                    runner._bump("sched_slot_wait_s", slot_wait_s)
                    runner._bump("pipeline_units")
                    runner._bump("scanned_parts", len(unit.members))
                    hist.PACK_SIZE.observe(len(unit.members))
                    with psp.span("submit", unit=seq,
                                  blocks=len(unit.bss)) as ssp:
                        if ssp.enabled:
                            ssp.set("rows",
                                    sum(bs.nrows
                                        for bs in unit.bss.values()))
                            ssp.set("slot_wait_s",
                                    round(slot_wait_s, 6))
                            if unit.pack:
                                ssp.set("pack_size", len(unit.members))
                                ssp.set("pack_members",
                                        [str(p.uid)
                                         for p, _b in unit.members])
                            else:
                                ssp.set("part", str(unit.part.uid))
                        act.set_phase("scan")
                        # test-only drain-path hook (inject_fault /
                        # VL_FAULT_SUBMIT): raises AFTER the lease was
                        # taken, pinning release-on-error
                        sched.maybe_fail_submit()
                        window.append((seq, unit, time.perf_counter(),
                                       _submit(runner, f, unit,
                                               stats_spec, sort_spec,
                                               spec_seg), True))
                    seq += 1
                    runner._bump_max("inflight_hwm", len(window))
                    if act.enabled:
                        act.add("dispatches_submitted")
                        act.set("dispatches_in_flight", len(window))
                while window:
                    check_deadline()
                    harvest_one()
                plsp.set("units", seq)
        finally:
            # cancellation/deadline/fault drain: drop in-flight handles
            # without writing anything downstream.  jax releases the
            # device buffers when the dispatches complete, and every
            # StagingCache entry is a complete, budget-accounted value
            # (staged under its key lock), so the cache stays balanced
            # for the next query; the device_slots scope releases every
            # slot the dropped window still held, so the scheduler's
            # global budget stays balanced too.
            if window:
                # abnormal drain (a clean completion harvested the
                # window empty): journal it so cancelled/faulted scans
                # correlate with their query_done record by qid
                events.emit(
                    "pipeline_drain",
                    tenant=act.tenant if act.enabled else None,
                    qid=act.qid if act.enabled else "",
                    units_dropped=len(window))
            window.clear()
            act.set("dispatches_in_flight", 0)
            stream.close()
