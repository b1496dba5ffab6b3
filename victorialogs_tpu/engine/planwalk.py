"""The header walk of a query: partition -> parts -> candidate blocks.

ONE implementation for every consumer: the device path's dispatch
window (engine/searcher._device_walk feeding tpu/pipeline), the host
executor (engine/searcher._scan_parts), the continuous pricing pass and
`?explain=1` (obs/explain).  A `PartitionWalk` resolves the query's
stream filters and snapshots the partition's parts ONCE; `steps()` then
yields one `PartStep` a part, lazily.  Nothing else calls
`snapshot_parts`, `candidate_blocks`, `aggregate_kill_leaf` or
`maplet_leaf_keep` on behalf of a query.

The walk is PURE (part headers, stream indexes and filter sidecars; no
span, counter or registry record), so explain and pricing may run it;
`observe` lands the execution's accounting for a step.
"""

from __future__ import annotations

from dataclasses import dataclass

from ..obs import activity, tracing
from ..storage.filterbank import aggregate_kill_leaf, maplet_leaf_keep
from ..storage.filterindex import part_index


@dataclass
class SearchContext:
    partition: object
    tenants: tuple


class PartStep:
    """One part's outcome.  Retained: `bis` (candidate block idxs) and
    `rows` (their rows), `reason` None.  Killed: `bis` empty, `reason`
    one of time_range, tenant, stream_filter, xor_aggregate,
    aggregate_bloom, maplet; the last three cite `killed_by` = (field,
    tokens, filter, artifact).  `n_cand`: candidates before the token
    leaves pruned (with `maplet` set, the maplet probed that many).
    `cold`: the coverage gate held but only cached aggregates were
    probed, so a fold may still kill the part (`kill_cold`)."""

    __slots__ = ("part", "bis", "rows", "n_cand", "reason", "killed_by",
                 "maplet", "cold")

    def __init__(self, part, bis=(), rows=0, reason=None):
        self.part = part
        self.bis = bis
        self.rows = rows
        self.n_cand = len(bis)
        self.reason = reason
        self.killed_by = None
        self.maplet = False
        self.cold = False

    def _kill(self, reason: str, killed_by) -> None:
        self.bis = ()
        self.rows = 0
        self.reason = reason
        self.killed_by = killed_by


def _aggregate_reason(artifact: str) -> str:
    return "xor_aggregate" if artifact == "xor_aggregate" \
        else "aggregate_bloom"


class PartitionWalk:
    """One partition's share of the walk.  Construction is the partition
    SETUP: the stream filters resolve and the parts snapshot, once a
    query, so every consumer of one walk sees one part list."""

    __slots__ = ("ctx", "pruned_by_stream_filter", "parts", "in_range",
                 "_tenant_set", "_allowed_sids", "_min_ts", "_max_ts")

    def __init__(self, pt, tenants, min_ts, max_ts, sfs):
        self.ctx = SearchContext(partition=pt, tenants=tenants)
        self._min_ts, self._max_ts = min_ts, max_ts
        self._tenant_set = set(tenants)
        self._allowed_sids = None
        if sfs:
            self._allowed_sids = set.intersection(
                *(f.resolve(pt, tenants) for f in sfs))
        self.pruned_by_stream_filter = bool(sfs) and \
            not self._allowed_sids
        self.parts = [] if self.pruned_by_stream_filter else \
            [p for p in pt.ddb.snapshot_parts() if p.num_rows]
        # parts the time range leaves: the executors' parts_total
        self.in_range = sum(1 for p in self.parts
                            if p.min_ts <= max_ts and p.max_ts >= min_ts)

    def steps(self, token_leaves, build: bool = True):
        """Lazily one PartStep a part of the snapshot, in part order.
        build=False probes aggregates a prior query already folded and
        flags the step `cold` where a fold could still kill (the pricing
        pass must not pay a cold fold for a part a `limit` never
        reaches); sealed v2 parts answer the same either way."""
        min_ts, max_ts = self._min_ts, self._max_ts
        for part in self.parts:
            if part.min_ts > max_ts or part.max_ts < min_ts:
                yield PartStep(part, reason="time_range")
                continue
            bis, rows, n_time, n_tenant = _candidates(
                part, self._tenant_set, self._allowed_sids, min_ts,
                max_ts)
            if not bis:
                yield PartStep(part, reason="time_range" if n_time == 0
                               else "tenant" if n_tenant == 0
                               else "stream_filter")
                continue
            step = PartStep(part, bis, rows)
            if token_leaves:
                _token_prune(step, token_leaves, build)
            yield step


def _part_header_table(part) -> dict:
    """Per-part header summary cached on the (immutable) part object:
    the walk runs on EVERY query, so the per-block header object churn
    is paid once per part lifetime (filterbank.filter_bank's idiom)."""
    t = getattr(part, "_explain_htab", None)
    if t is None:
        nb = part.num_blocks
        sids = [part.block_stream_id(bi) for bi in range(nb)]
        rows = [part.block_rows(bi) for bi in range(nb)]
        tset = {s.tenant for s in sids}
        t = {
            "sids": sids, "rows": rows, "rows_total": sum(rows),
            "uniform_tenant": next(iter(tset)) if len(tset) == 1
            else None,
        }
        part._explain_htab = t
    return t


def _candidates(part, tenant_set, allowed_sids, min_ts, max_ts):
    """(bis, rows, n_time, n_tenant): the blocks of the part the time
    range, the tenants and the stream filters leave, header-only."""
    if part.min_ts >= min_ts and part.max_ts <= max_ts:
        # part fully inside the range: every block is a time candidate,
        # and the cached header table answers the tenant/stream
        # filtering without touching header groups
        htab = _part_header_table(part)
        sids, rows = htab["sids"], htab["rows"]
        uniform = htab["uniform_tenant"]
        if uniform is not None and uniform not in tenant_set:
            return [], 0, len(sids), 0
        if uniform is not None and allowed_sids is None:
            return (list(range(len(sids))), htab["rows_total"],
                    len(sids), len(sids))
        cand, sid_of, rows_of = range(len(sids)), sids.__getitem__, \
            rows.__getitem__
    else:
        # candidate_blocks skips whole header groups outside the range
        # without decoding them (v2 metaindex)
        cand, sid_of, rows_of = part.candidate_blocks(min_ts, max_ts), \
            part.block_stream_id, part.block_rows
    bis: list = []
    rows_cand = n_time = n_tenant = 0
    for bi in cand:
        n_time += 1
        sid = sid_of(bi)
        if sid.tenant not in tenant_set:
            continue
        n_tenant += 1
        if allowed_sids is not None and sid not in allowed_sids:
            continue
        bis.append(bi)
        rows_cand += rows_of(bi)
    return bis, rows_cand, n_time, n_tenant


def _token_prune(step: PartStep, leaves, build: bool) -> None:
    """The AND-path token leaves against one part: the part-level
    aggregate kill (a required token absent from EVERY block skips the
    part; the per-block kill-path would have zeroed each block anyway),
    then on sealed v2 parts the maplet's exact candidate list.  A COLD
    aggregate fold reads all the part's blooms, so it only pays when
    the candidates cover a sizable share of the part (the gate)."""
    part, bis = step.part, step.bis
    gate = len(bis) * 4 >= part.num_blocks
    killed = aggregate_kill_leaf(part, leaves, build=build and gate)
    if killed is not None:
        step._kill(_aggregate_reason(killed[3]), killed)
        return
    fi = part_index(part)
    if gate and not build:
        # the xor aggregate is exact: where it answers for every leaf
        # a classic fold cannot kill either
        step.cold = fi is None or \
            not all(fi.covers(field) for field, _t, _f in leaves)
    if fi is None:
        return
    keep, kill_leaf = maplet_leaf_keep(fi, leaves, bis)
    if keep is None:
        return
    step.maplet = True
    if kill_leaf is not None:
        step._kill("maplet", kill_leaf + ("maplet",))
    elif not keep.all():
        step.bis = [bi for bi, k in zip(bis, keep) if k]
        step.rows = sum(part.block_rows(bi) for bi in step.bis)


def kill_cold(step: PartStep, leaves) -> bool:
    """The fold a build=False walk left out, paid when the execution
    reaches the part: True when the built aggregate kills it."""
    step.cold = False
    killed = aggregate_kill_leaf(step.part, leaves, build=True)
    if killed is None:
        return False
    step._kill(_aggregate_reason(killed[3]), killed)
    return True


def observe(step: PartStep, runner=None) -> None:
    """The execution's prune accounting for one step: the ambient span,
    the active-query record and, on the device path, the runner's
    counters."""
    if step.reason in ("xor_aggregate", "aggregate_bloom"):
        sp = tracing.current_span()
        if sp.enabled:
            sp.add("parts_pruned_aggregate")
            sp.set("last_aggregate_prune_field", step.killed_by[0])
            sp.set("last_aggregate_prune_artifact", step.killed_by[3])
        activity.current_activity().add("parts_pruned")
        if runner is not None:
            runner._bump("agg_pruned_parts")
    elif step.maplet:
        killed = step.n_cand - len(step.bis)
        sp = tracing.current_span()
        if sp.enabled:
            sp.add("blocks_probed_maplet", step.n_cand)
            sp.add("blocks_killed_maplet", killed)
        if killed:
            activity.current_activity().add("blocks_killed_maplet",
                                            killed)
            if runner is not None:
                runner._bump("maplet_pruned_blocks", killed)
