"""Query EXPLAIN: priced physical plans and predicted-vs-actual cost
accountability.

Two consumers share one header-only plan walk:

- **`?explain=1`** (server/vlselect.handle_explain): the physical plan
  tree WITHOUT executing — partitions → parts (retained vs killed, with
  the reason: time range, tenant, stream filter, or the aggregate-bloom
  kill citing the filter leaf whose tokens are provably absent) →
  planned dispatch units (pack membership, pad bucket, fused program
  kind), each node annotated with cost-model predictions from the live
  calibration EWMAs (tpu/batch.CostModel.peek — never the lazy RTT
  probe, so a plain explain performs ZERO device dispatches and reads
  nothing past part headers, stream indexes and bloom sidecars).
  `?explain=analyze` executes the query and grafts actuals onto the
  same tree — per-unit dispatch_rtt_s/emit_s from the PR 4 span tree,
  query-level counters from the PR 6 activity record — sourced, never
  recomputed.  Cluster frontends merge per-node trees under
  `storage_node` nodes exactly like `?trace=1`
  (server/cluster.NetSelectStorage.net_explain).

- **continuous pricing** (engine/searcher hooks `predict_query` at plan
  time for every device-path query): the same walk at part granularity
  writes `predicted_duration_s` / `predicted_bytes` /
  `predicted_dispatches` onto the activity record, so `query_done`
  journal events carry predicted-vs-actual pairs, /metrics grows
  `vl_cost_model_rel_error_*` histograms (obs/activity computes the
  errors at deregister), and `top_queries?by=cost_error` surfaces the
  queries the model prices worst.  `predicted_duration_s` is shaped for
  sched/admission.py's deadline-feasibility gate to consume in a
  follow-up (a per-QUERY run estimate instead of the per-endpoint
  EWMA).  `VL_QUERY_PRICING=0` kills the continuous pass.

The plan walk deliberately REUSES the execution planner's own pieces —
`candidate_blocks` header selection, `filterbank.aggregate_kill_leaf`,
`pipeline.iter_pack_groups` pack membership, `CostModel` rates — so the
displayed plan cannot diverge from what a real run would dispatch.
"""

from __future__ import annotations

from .. import config

from . import activity, tracing

# cold-model host rates (CostModel defaults) for runner-less plans
_HOST_ONLY_PEEK = {
    "rtt_s": 0.0, "unit_rtt_s": 0.0, "dev_bytes_per_s": 1.0,
    "emit_unit_s": 0.0, "host_rows_per_s": 12e6,
    "host_stats_rows_per_s": 30e6, "upload_bytes_per_s": 1e9,
    "calibrated": False, "force": "host",
}

# the cost model's whole-query byte-per-row figure for device scan
# traffic (tpu/batch._gate_host_est's W estimate)
_SCAN_BYTES_PER_ROW = 128


def pricing_enabled() -> bool:
    """VL_QUERY_PRICING=0 kills the continuous plan-time pricing pass
    (the explain endpoints stay available either way)."""
    return config.env_flag("VL_QUERY_PRICING")


# ---------------- the plan walk ----------------

def build_plan(storage, tenants, q, runner=None) -> dict:
    """The priced physical plan tree without executing (?explain=1)."""
    return _walk(storage, tenants, q, runner, detail=True)


def predict_query(storage, tenants, q, runner=None) -> dict:
    """The cheap continuous pricing pass: predicted summary only (no
    per-part nodes, no cold aggregate builds — only aggregates a prior
    query already folded are probed, the execution walk that follows
    pays for new ones itself)."""
    return _walk(storage, tenants, q, runner, detail=False)["predicted"]


def _walk(storage, tenants, q, runner, detail: bool) -> dict:
    from ..logsql.filters import (filter_plan_tree,
                                  iter_and_path_token_leaves)
    from ..logsql.parser import MAX_TS, MIN_TS
    from ..storage.log_rows import TenantID
    from ..engine.searcher import _collect_stream_filters

    if isinstance(tenants, TenantID):
        tenants = [tenants]
    tenants = tuple(tenants)
    tenant_set = set(tenants)
    min_ts, max_ts = q.get_time_range()

    batch = runner is not None
    peek = runner.cost.peek() if batch else dict(_HOST_ONLY_PEEK)
    stats_spec = sort_spec = None
    plans = []
    if batch:
        from ..tpu.batch import device_plans
        from ..tpu.stats_device import device_stats_spec
        plans = device_plans(q.filter)
        stats_spec = device_stats_spec(q)
        if stats_spec is None:
            from ..tpu.sort_device import device_sort_spec
            sort_spec = device_sort_spec(q)
    shape = "stats" if stats_spec is not None else \
        "topk" if sort_spec is not None else "rows"

    sfs: list = []
    _collect_stream_filters(q.filter, sfs)
    token_leaves = list(iter_and_path_token_leaves(q.filter))
    if batch:
        # the SAME depth derivation the window dispatches with, minus
        # the lazy RTT probe (explain must stay zero-dispatch)
        from ..tpu.pipeline import inflight_depth
        depth = inflight_depth(runner, probe=False)
    else:
        depth = 1

    tree: dict = {
        "name": "explain",
        "mode": "plan",
        "query": q.to_string(),
        "shape": shape,
        "executor": "device" if batch else "host",
        "fused_filter": batch,
        "inflight_depth": depth,
        "time_range": {
            "min_ts": None if min_ts == MIN_TS else min_ts,
            "max_ts": None if max_ts == MAX_TS else max_ts,
        },
        "partitions": [],
    }
    if detail:
        tree["filter"] = filter_plan_tree(q.filter)

    tot = {"parts_total": 0, "parts_retained": 0, "parts_killed": 0,
           "parts_cached": 0, "blocks_candidate": 0, "rows_scanned": 0,
           "bytes_scanned": 0, "dispatches": 0, "bytes_staged": 0}
    cost = {"rtt_s": 0.0, "device_scan_s": 0.0, "upload_s": 0.0,
            "emit_s": 0.0, "host_s": 0.0}

    # result-cache peek (engine/standing/resultcache.py): parts whose
    # answer would replay from the cache are priced ~0 — the admission
    # layer then charges a repeated query only its post-cache residual
    # scan (price-after-cache).  peek touches no counters and no LRU
    # state, so explain=1 stays a pure read.
    from ..engine.standing.resultcache import QueryCache
    qcache = QueryCache.for_query(q, tenants, stats_spec, sort_spec,
                                  min_ts, max_ts)

    active_pts = 0
    retained_all: list = []   # (pnode, part, bis, rows_cand, bytes_est)
    for pt in storage.select_partitions(min_ts, max_ts):
        pnode, retained = _walk_partition(
            pt, tenants, tenant_set, min_ts, max_ts, sfs,
            token_leaves, detail, tot, qcache)
        if retained:
            active_pts += 1
        retained_all.extend((pnode, p, b, rc, be)
                            for p, b, rc, be in retained)
        if detail:
            tree["partitions"].append(pnode)

    # planned dispatch units: THE pack-membership rules the window
    # dispatches with (pipeline.pack_policy + iter_pack_groups), run
    # over the CROSS-PARTITION retained stream exactly like the
    # execution planner — packs may span a day boundary, and the unit
    # seq is global (it matches the window's submit/harvest span
    # numbering, which _graft keys on).  A unit node hangs off the
    # partition of its FIRST member.
    _price_units(retained_all, runner, batch, peek, plans, shape,
                 sort_spec, depth, detail, tot, cost)

    if not detail:
        tree.pop("partitions")

    # host-path per-day partitions scan concurrently under the worker
    # cap (engine/searcher._scan_partitions_parallel), so wall time
    # divides by the effective partition parallelism.  The device
    # path's cross-partition window overlaps round trips ACROSS
    # partitions already (depth folded above): no extra parallelism.
    npw = 1 if batch else max(1, min(active_pts, q.get_concurrency()))
    duration = sum(cost.values()) / npw
    tree["predicted"] = dict(tot)
    tree["predicted"].update({k: round(v, 6) for k, v in cost.items()})
    tree["predicted"]["duration_s"] = round(duration, 6)
    tree["predicted"]["calibrated"] = peek["calibrated"]
    return tree


def _maplet_exact(part, token_leaves, bis):
    """(exact_bis, killing_leaf, have_maplet): the sealed part's exact
    AND-path candidate blocks from its token→block maplets.  Pure
    probe — no trace/registry side effects, so both the explain
    endpoint and the continuous pricing pass may call it; the AND
    semantics live in ONE place (filterbank.maplet_leaf_keep, shared
    with the execution pruning).  Classic parts return
    (bis, None, False): their candidates stay the probabilistic
    per-block estimate."""
    from ..storage.filterbank import maplet_leaf_keep
    from ..storage.filterindex import part_index
    fi = part_index(part)
    if fi is None:
        return bis, None, False
    keep, kill_leaf = maplet_leaf_keep(fi, token_leaves, bis)
    if kill_leaf is not None:
        return [], kill_leaf, True
    if keep is None:
        return bis, None, True
    return [bi for bi, k in zip(bis, keep) if k], None, True


def _part_header_table(part) -> dict:
    """Per-part header summary cached on the (immutable) part object —
    the pricing walk runs on EVERY query, so the per-block header
    object churn (stream ids, row counts) is paid once per part
    lifetime instead of once per query.  Same attach idiom as
    storage/filterbank.filter_bank."""
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


def _walk_partition(pt, tenants, tenant_set, min_ts, max_ts, sfs,
                    token_leaves, detail, tot, qcache=None):
    from ..storage.filterbank import aggregate_kill_leaf

    pnode: dict = {"name": "partition",
                   "day": getattr(pt, "day", None),
                   "parts": [], "units": []}
    allowed_sids = None
    if sfs:
        allowed_sids = set.intersection(
            *(f.resolve(pt, tenants) for f in sfs))
        if not allowed_sids:
            pnode["pruned_by_stream_filter"] = True
            return pnode, []

    retained: list = []      # (part, bis, rows_cand, bytes_est)
    for part in pt.ddb.snapshot_parts():
        if not part.num_rows:
            continue
        tot["parts_total"] += 1
        # per-part detail nodes only exist on the explain endpoint; the
        # continuous pricing pass (detail=False, every query) must not
        # allocate throwaway dicts per part
        node: dict = {"part": str(part.uid), "rows": part.num_rows,
                      "blocks": part.num_blocks} if detail else {}
        if part.min_ts > max_ts or part.max_ts < min_ts:
            tot["parts_killed"] += 1
            if detail:
                node.update(status="killed", reason="time_range")
                pnode["parts"].append(node)
            continue
        bis: list = []
        rows_cand = 0
        n_time = n_tenant = 0
        if part.min_ts >= min_ts and part.max_ts <= max_ts:
            # part fully inside the range: every block is a time
            # candidate — the cached header table answers the tenant/
            # stream filtering without touching header groups
            htab = _part_header_table(part)
            sids, rows = htab["sids"], htab["rows"]
            n_time = len(sids)
            if htab["uniform_tenant"] is not None and \
                    htab["uniform_tenant"] not in tenant_set:
                pass                       # n_tenant stays 0: killed
            elif htab["uniform_tenant"] is not None and \
                    allowed_sids is None:
                n_tenant = n_time
                bis = list(range(n_time))
                rows_cand = htab["rows_total"]
            else:
                for bi, sid in enumerate(sids):
                    if sid.tenant not in tenant_set:
                        continue
                    n_tenant += 1
                    if allowed_sids is not None and \
                            sid not in allowed_sids:
                        continue
                    bis.append(bi)
                    rows_cand += rows[bi]
        else:
            block_sid = part.block_stream_id
            block_rows = part.block_rows
            for bi in part.candidate_blocks(min_ts, max_ts):
                n_time += 1
                sid = block_sid(bi)
                if sid.tenant not in tenant_set:
                    continue
                n_tenant += 1
                if allowed_sids is not None and sid not in allowed_sids:
                    continue
                bis.append(bi)
                rows_cand += block_rows(bi)
        if not bis:
            tot["parts_killed"] += 1
            if detail:
                node.update(status="killed",
                            reason="time_range" if n_time == 0 else
                            "tenant" if n_tenant == 0 else
                            "stream_filter")
                pnode["parts"].append(node)
            continue
        if token_leaves:
            # detailed plans apply the execution walk's own build gate;
            # the cheap continuous pass probes CACHED aggregates only
            # (build=False) — with the result memo those repeats are
            # dict lookups, and a cold part the execution would build+
            # kill shows up as prediction error instead of a second
            # cold fold per query.  Sealed v2 parts (filter-index
            # sidecar) answer either way from the loaded xor aggregate.
            killed = aggregate_kill_leaf(
                part, token_leaves,
                build=detail and len(bis) * 4 >= part.num_blocks)
            if killed is not None:
                field, tokens, f, artifact = killed
                tot["parts_killed"] += 1
                if detail:
                    node.update(status="killed",
                                reason="xor_aggregate"
                                if artifact == "xor_aggregate"
                                else "aggregate_bloom",
                                killed_by={"field": field,
                                           "tokens": list(tokens),
                                           "filter": f.to_string(),
                                           "artifact": artifact})
                    pnode["parts"].append(node)
                continue
            # sealed v2 parts: the token→block maplet yields the EXACT
            # candidate block list for the AND-path leaves — priced
            # units reflect what the execution walk will dispatch, and
            # an emptied list kills the part with the maplet cited
            exact_bis, kill_leaf, have_maplet = _maplet_exact(
                part, token_leaves, bis)
            if kill_leaf is not None:
                field, tokens, f = kill_leaf
                tot["parts_killed"] += 1
                if detail:
                    node.update(status="killed", reason="maplet",
                                killed_by={"field": field,
                                           "tokens": list(tokens),
                                           "filter": f.to_string(),
                                           "artifact": "maplet"})
                    pnode["parts"].append(node)
                continue
            if have_maplet and len(exact_bis) != len(bis):
                bis = exact_bis
                rows_cand = sum(part.block_rows(bi) for bi in bis)
                if detail:
                    node["maplet_exact"] = True
        if qcache is not None and qcache.peek(part, bis):
            # the part's answer replays from the result cache: it is
            # retained but priced ~0 (no dispatch, no bytes scanned) —
            # the dashboard-refresh query pays only its unsealed head
            tot["parts_retained"] += 1
            tot["parts_cached"] += 1
            if detail:
                node.update(status="retained", cached=True,
                            blocks_candidate=len(bis))
                pnode["parts"].append(node)
            continue
        bytes_est = int(rows_cand * activity.part_bytes_per_row(part))
        tot["parts_retained"] += 1
        tot["blocks_candidate"] += len(bis)
        tot["rows_scanned"] += rows_cand
        tot["bytes_scanned"] += bytes_est
        if detail:
            node.update(status="retained", blocks_candidate=len(bis),
                        rows_candidate=rows_cand, bytes_est=bytes_est)
            pnode["parts"].append(node)
        retained.append((part, bis, rows_cand, bytes_est))

    return pnode, retained


def _price_units(retained_all, runner, batch, peek, plans, shape,
                 sort_spec, depth, detail, tot, cost) -> None:
    """Group the retained-part stream into planned dispatch units and
    price each one.  retained_all: (pnode, part, bis, rows, bytes)
    tuples in partition-walk order — grouping runs over the WHOLE
    stream (cross-partition window) and the unit seq is global,
    matching the execution window's submit/harvest span numbering."""
    from ..tpu import pipeline
    if not retained_all:
        return
    by_part = {p.uid: (rc, be) for _pn, p, _b, rc, be in retained_all}
    pnode_of = {p.uid: pn for pn, p, _b, _rc, _be in retained_all}
    if batch:
        packable, pack_max, rows_cap = pipeline.pack_policy(
            runner, sort_spec, probe=False)

        def groups_of(items):
            return pipeline.iter_pack_groups(items, packable, pack_max,
                                             rows_cap)
    else:
        def groups_of(items):
            return ([it] for it in items)

    stream = ((p, b) for _pn, p, b, _rc, _be in retained_all)
    for seq, group in enumerate(groups_of(stream)):
        unode = _price_unit(seq, group, by_part, runner, batch, peek,
                            plans, shape, depth, cost, tot, detail)
        if detail and unode is not None:
            pnode_of[group[0][0].uid]["units"].append(unode)


def _price_unit(seq, group, by_part, runner, batch, peek, plans,
                shape, depth, cost, tot, detail: bool) -> dict | None:
    from ..tpu import pipeline

    rows = sum(by_part[p.uid][0] for p, _b in group)
    nbytes = sum(by_part[p.uid][1] for p, _b in group)
    blocks = sum(len(b) for _p, b in group)
    scan_bytes = rows * _SCAN_BYTES_PER_ROW
    # topk units gate exactly like stats units do at execution time
    # (run_part_topk_submit passes stats_rows=cand_rows): one fused
    # dispatch whose host alternative pays the aggregate-scan rate
    stats_rows = rows if shape in ("stats", "topk") else 0

    cold = 0
    # one fused program a unit, as the gate sees it: a tree with a scan
    # leaf, or a stats / topk shape
    n_dispatch = 1 if batch and (plans or stats_rows) else 0
    if batch and plans:
        # staging keys are per DISPATCH TARGET: a packed unit stages
        # under the pack's uid (tpu/pipeline PackedPart), not its
        # members' — the cold-bytes estimate must probe the same keys
        uid = ("pack",) + tuple(p.uid for p, _b in group) \
            if len(group) > 1 else group[0][0].uid
        # same rule as BatchRunner._gate_host_est: once per field
        for fld in {plan.field for plan in plans}:
            if not runner.cache.contains((uid, "#fl", fld)):
                cold += scan_bytes

    host = _prefers_host(peek, rows, scan_bytes, n_dispatch, cold,
                         stats_rows)
    kind = "host" if host else (
        "stats" if shape == "stats" else
        "topk" if shape == "topk" else "fused_filter")

    # the unit detail node exists only for the explain endpoint; the
    # continuous pricing pass keeps the accounting without the dicts
    unode: dict | None = None
    if detail:
        unode = {
            "name": "unit", "seq": seq, "kind": kind,
            "pack": len(group) > 1,
            "members": [str(p.uid) for p, _b in group],
            "pad_bucket": pipeline.pack_bucket(group[0][0]),
            "blocks": blocks, "rows": rows, "bytes_est": nbytes,
        }
    # every planned unit is one pipeline submission (host-gated units
    # included — dispatches_submitted counts them the same way)
    tot["dispatches"] += 1
    if host:
        host_s = rows / peek["host_rows_per_s"] \
            + stats_rows / peek["host_stats_rows_per_s"]
        cost["host_s"] += host_s
        if unode is not None:
            unode["predicted"] = {"host_s": round(host_s, 6)}
        return unode

    tot["bytes_staged"] += cold
    # window-overlapped REAL unit round trip (CostModel.unit_rtt_ewma):
    # at steady state the window amortizes each submit-to-harvest
    # across depth outstanding units
    rtt_s = peek["unit_rtt_s"] / depth
    scan_s = scan_bytes / peek["dev_bytes_per_s"]
    upload_s = 0.25 * cold / peek["upload_bytes_per_s"]
    emit_s = peek["emit_unit_s"]
    cost["rtt_s"] += rtt_s
    cost["device_scan_s"] += scan_s
    cost["upload_s"] += upload_s
    cost["emit_s"] += emit_s
    if unode is not None:
        unode["predicted"] = {
            "bytes_staged_cold": cold,
            "scan_bytes_device": scan_bytes,
            "rtt_s": round(rtt_s, 6),
            "device_scan_s": round(scan_s, 6),
            "emit_s": round(emit_s, 6),
            "duration_s": round(rtt_s + scan_s + upload_s + emit_s,
                                6),
        }
    return unode


def _prefers_host(peek, cand_rows, scan_bytes, n_dispatch, cold_bytes,
                  stats_rows) -> bool:
    """CostModel.prefer_host on peeked rates (no RTT probe)."""
    if peek["force"] == "device":
        return False
    if peek["force"] == "host":
        return True
    if n_dispatch <= 0:
        return True
    est_host = cand_rows / peek["host_rows_per_s"] \
        + stats_rows / peek["host_stats_rows_per_s"]
    est_dev = n_dispatch * peek["rtt_s"] \
        + n_dispatch * scan_bytes / peek["dev_bytes_per_s"] \
        + 0.25 * cold_bytes / peek["upload_bytes_per_s"]
    return est_host < est_dev


# ---------------- continuous pricing (engine hook) ----------------

def price_into_activity(storage, tenants, q, runner, act) -> None:
    """Plan-time pricing for ONE query: predicted summary onto the
    activity record (counters named predicted_* so they ride the
    query_done journal event next to the actuals; obs/activity folds
    the pair into vl_cost_model_rel_error_* at deregister).  Advisory:
    never fails the query."""
    try:
        pred = predict_query(storage, tenants, q, runner)
    # vlint: allow-broad-except(pricing is advisory, the query must run)
    except Exception:
        return
    act.set("predicted_duration_s", pred["duration_s"])
    act.set("predicted_bytes", pred["bytes_scanned"])
    act.set("predicted_dispatches", pred["dispatches"])
    act.set("predicted_rows", pred["rows_scanned"])


# ---------------- explain=analyze grafting ----------------

def analyze(storage, tenants, q, tree, runner=None, deadline=None,
            endpoint="explain", include_trace=False) -> None:
    """Execute the query and graft actuals onto the plan tree.

    Actuals are SOURCED, not recomputed: query-level counters from the
    activity record (PR 6), per-unit dispatch_rtt_s / device_sync /
    emit from the span tree (PR 4) — the same numbers ?trace=1 and
    /metrics report for this run."""
    from ..engine.searcher import run_query

    root = tracing.make_root("query", query=q.to_string())
    rows_emitted = [0]

    def sink(br) -> None:
        rows_emitted[0] += br.nrows

    with activity.reuse_or_track(endpoint, q.to_string(),
                                 tenants[0] if tenants else None) as act:
        root.set("qid", act.qid)
        with tracing.activate(root):
            run_query(storage, tenants, q, write_block=sink,
                      runner=runner, deadline=deadline)
        act.mark_exec_done()
        snap = act.snapshot()
    tdict = root.to_dict()
    _graft(tree, tdict, snap.get("progress", {}), rows_emitted[0])
    if include_trace:
        tree["trace"] = tdict


def _graft(tree, tdict, progress, rows_emitted) -> None:
    tree["mode"] = "analyze"
    actual = {k: v for k, v in sorted(progress.items())
              if isinstance(v, (int, float))}
    actual["rows_emitted"] = rows_emitted
    tree["actual"] = actual
    flat = tracing.flatten_tree(tdict)
    tree["actual_spans"] = {
        name: flat[name]
        for name in ("pipeline", "prune", "stage", "submit", "harvest",
                     "device_sync", "emit", "sched_wait")
        if name in flat}
    _graft_units(tree, tdict)


def _graft_units(tree, tdict) -> None:
    """Per-unit actuals: submit/harvest spans keyed by the pipeline's
    GLOBAL unit sequence — the cross-partition window numbers units
    across the whole query, and the plan walk generated its unit list
    with the same grouping and numbering (pipeline.iter_pack_groups
    both times), so matching is tree-wide."""
    submits: dict = {}
    harvests: dict = {}
    for sp in tracing.iter_tree(tdict, "submit"):
        attrs = sp.get("attrs") or {}
        if "unit" in attrs:
            submits[attrs["unit"]] = (sp, attrs)
    for sp in tracing.iter_tree(tdict, "harvest"):
        attrs = sp.get("attrs") or {}
        if "unit" in attrs:
            harvests[attrs["unit"]] = (sp, attrs)
    units = [u for pnode in tree.get("partitions", ())
             for u in pnode.get("units", ())]
    for unode in units:
        _attach_actual(unode, submits, harvests, unode.get("seq"))


def _attach_actual(unode, submits, harvests, seq) -> None:
    actual: dict = {}
    got = submits.get(seq)
    if got is not None:
        _sp, attrs = got
        for k in ("rows", "blocks", "slot_wait_s"):
            if k in attrs:
                actual[k] = attrs[k]
    got = harvests.get(seq)
    if got is not None:
        sp, attrs = got
        if "dispatch_rtt_s" in attrs:
            actual["dispatch_rtt_s"] = attrs["dispatch_rtt_s"]
        if attrs.get("host_unit"):
            actual["host_unit"] = True
        for child in sp.get("children", ()):
            if child.get("name") == "device_sync":
                actual["device_sync_s"] = round(
                    child.get("duration_ms", 0.0) / 1e3, 6)
            elif child.get("name") == "emit":
                actual["emit_s"] = round(
                    child.get("duration_ms", 0.0) / 1e3, 6)
    if actual:
        unode["actual"] = actual
