"""Query EXPLAIN: priced physical plans and predicted-vs-actual cost
accountability.

Three consumers share one header-only plan walk (engine/planwalk.py:
partitions → parts → candidate blocks → aggregate / maplet prunes), the
two below and the execution itself:

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

- **continuous pricing** (engine/searcher hooks `price_into_activity`
  at plan time for every device-path query): the engine runs the walk
  ONCE, this module prices the retained parts and the dispatch window
  then consumes the same list, so the priced plan is the executed plan.
  `predicted_duration_s` / `predicted_bytes` / `predicted_dispatches`
  land on the activity record, so `query_done`
  journal events carry predicted-vs-actual pairs, /metrics grows
  `vl_cost_model_rel_error_*` histograms (obs/activity computes the
  errors at deregister), and `top_queries?by=cost_error` surfaces the
  queries the model prices worst.  `predicted_duration_s` is shaped for
  sched/admission.py's deadline-feasibility gate to consume in a
  follow-up (a per-QUERY run estimate instead of the per-endpoint
  EWMA).  `VL_QUERY_PRICING=0` kills the continuous pass.

The plan is built from the execution planner's own pieces — the one
walk (`planwalk.PartitionWalk`: `candidate_blocks` header selection,
`filterbank.aggregate_kill_leaf`, the maplets),
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
    """The cheap pricing pass over a walk of its own: predicted summary
    only (no per-part nodes, no cold aggregate builds — only aggregates
    a prior query already folded are probed).  What a priced query
    writes on its record (price_into_activity), on the same snapshot."""
    return _walk(storage, tenants, q, runner, detail=False)["predicted"]


def _walk(storage, tenants, q, runner, detail: bool) -> dict:
    from ..logsql.filters import (filter_plan_tree,
                                  iter_and_path_token_leaves)
    from ..logsql.parser import MAX_TS, MIN_TS
    from ..storage.log_rows import TenantID
    from ..engine.planwalk import PartitionWalk
    from ..engine.searcher import _collect_stream_filters

    if isinstance(tenants, TenantID):
        tenants = [tenants]
    tenants = tuple(tenants)
    min_ts, max_ts = q.get_time_range()

    stats_spec = sort_spec = None
    if runner is not None:
        from ..tpu.stats_device import device_stats_spec
        stats_spec = device_stats_spec(q)
        if stats_spec is None:
            from ..tpu.sort_device import device_sort_spec
            sort_spec = device_sort_spec(q)
    pricing = _Pricing(q, runner, stats_spec, sort_spec, detail)

    sfs: list = []
    _collect_stream_filters(q.filter, sfs)
    token_leaves = list(iter_and_path_token_leaves(q.filter))

    tree: dict = {
        "name": "explain",
        "mode": "plan",
        "query": q.to_string(),
        "shape": pricing.shape,
        "executor": "device" if pricing.batch else "host",
        "fused_filter": pricing.batch,
        "inflight_depth": pricing.depth,
        "time_range": {
            "min_ts": None if min_ts == MIN_TS else min_ts,
            "max_ts": None if max_ts == MAX_TS else max_ts,
        },
        "partitions": [],
    }
    if detail:
        tree["filter"] = filter_plan_tree(q.filter)

    # result-cache peek (engine/standing/resultcache.py): parts whose
    # answer would replay from the cache are priced ~0 — the admission
    # layer then charges a repeated query only its post-cache residual
    # scan (price-after-cache).  peek touches no counters and no LRU
    # state, so explain=1 stays a pure read.
    from ..engine.standing.resultcache import QueryCache
    qcache = QueryCache.for_query(q, tenants, stats_spec, sort_spec,
                                  min_ts, max_ts)

    active_pts = 0
    for pt in storage.select_partitions(min_ts, max_ts):
        pw = PartitionWalk(pt, tenants, min_ts, max_ts, sfs)
        pnode: dict = {"name": "partition",
                       "day": getattr(pt, "day", None),
                       "parts": [], "units": []}
        if pw.pruned_by_stream_filter:
            pnode["pruned_by_stream_filter"] = True
        n0 = len(pricing.retained)
        # detailed plans apply the execution walk's own aggregate build
        # gate; the cheap pass probes CACHED aggregates only — a cold
        # part the execution would build+kill shows up as prediction
        # error instead of a second cold fold per query
        for step in pw.steps(token_leaves, build=detail):
            pricing.add(step, qcache, pnode if detail else None)
        if len(pricing.retained) > n0:
            active_pts += 1
        if detail:
            tree["partitions"].append(pnode)

    if not detail:
        tree.pop("partitions")
    tree["predicted"] = pricing.predicted(active_pts)
    return tree


def _kill_citation(killed_by) -> dict:
    field, tokens, f, artifact = killed_by
    return {"field": field, "tokens": list(tokens),
            "filter": f.to_string(), "artifact": artifact}


class _Pricing:
    """One query's tally of walk steps and their price: what `_walk`
    shows as a plan and what the engine writes on the activity record,
    from the same steps."""

    def __init__(self, q, runner, stats_spec, sort_spec, detail=False):
        self.q, self.runner, self.detail = q, runner, detail
        self.batch = runner is not None
        self.sort_spec = sort_spec
        self.peek = runner.cost.peek() if self.batch \
            else dict(_HOST_ONLY_PEEK)
        self.plans = []
        self.depth = 1
        if self.batch:
            from ..tpu.batch import device_plans
            from ..tpu.pipeline import inflight_depth
            self.plans = device_plans(q.filter)
            # the SAME depth derivation the window dispatches with,
            # minus the lazy RTT probe (explain must stay zero-dispatch)
            self.depth = inflight_depth(runner, probe=False)
        self.shape = "stats" if stats_spec is not None else \
            "topk" if sort_spec is not None else "rows"
        self.tot = {"parts_total": 0, "parts_retained": 0,
                    "parts_killed": 0, "parts_cached": 0,
                    "blocks_candidate": 0, "rows_scanned": 0,
                    "bytes_scanned": 0, "dispatches": 0,
                    "bytes_staged": 0}
        self.retained: list = []  # (pnode, part, bis, rows, bytes_est)

    def add(self, step, qcache, pnode=None) -> None:
        """Tally one walk step; `pnode` (the explain endpoint only)
        receives the per-part detail node — the continuous pass must
        not allocate throwaway dicts per part."""
        tot = self.tot
        part, bis = step.part, step.bis
        tot["parts_total"] += 1
        node = None
        if pnode is not None:
            node = {"part": str(part.uid), "rows": part.num_rows,
                    "blocks": part.num_blocks}
            pnode["parts"].append(node)
        if step.reason is not None:
            tot["parts_killed"] += 1
            if pnode is not None:
                node.update(status="killed", reason=step.reason)
                if step.killed_by is not None:
                    node["killed_by"] = _kill_citation(step.killed_by)
            return
        if pnode is not None and len(bis) != step.n_cand:
            # sealed v2 parts: the token→block maplet yields the EXACT
            # candidate block list for the AND-path leaves
            node["maplet_exact"] = True
        tot["parts_retained"] += 1
        if qcache is not None and qcache.peek(part, bis):
            # the part's answer replays from the result cache: it is
            # retained but priced ~0 (no dispatch, no bytes scanned) —
            # the dashboard-refresh query pays only its unsealed head
            tot["parts_cached"] += 1
            if pnode is not None:
                node.update(status="retained", cached=True,
                            blocks_candidate=len(bis))
            return
        bytes_est = int(step.rows * activity.part_bytes_per_row(part))
        tot["blocks_candidate"] += len(bis)
        tot["rows_scanned"] += step.rows
        tot["bytes_scanned"] += bytes_est
        if pnode is not None:
            node.update(status="retained", blocks_candidate=len(bis),
                        rows_candidate=step.rows, bytes_est=bytes_est)
        self.retained.append((pnode, part, bis, step.rows, bytes_est))

    def predicted(self, active_pts: int) -> dict:
        """Price the retained parts as planned dispatch units: THE
        pack-membership rules the window dispatches with
        (pipeline.pack_policy + iter_pack_groups), run over the
        CROSS-PARTITION retained stream exactly like the execution
        planner — packs may span a day boundary, and the unit seq is
        global (it matches the window's submit/harvest span numbering,
        which _graft keys on).  A unit node hangs off the partition of
        its FIRST member."""
        cost = {"rtt_s": 0.0, "device_scan_s": 0.0, "upload_s": 0.0,
                "emit_s": 0.0, "host_s": 0.0}
        _price_units(self.retained, self.runner, self.batch, self.peek,
                     self.plans, self.shape, self.sort_spec, self.depth,
                     self.detail, self.tot, cost)
        # host-path per-day partitions scan concurrently under the
        # worker cap (engine/searcher._scan_partitions_parallel), so
        # wall time divides by the effective partition parallelism.
        # The device path's cross-partition window overlaps round trips
        # ACROSS partitions already (depth folded above): no extra
        # parallelism.
        npw = 1 if self.batch else \
            max(1, min(active_pts, self.q.get_concurrency()))
        pred = dict(self.tot)
        pred.update({k: round(v, 6) for k, v in cost.items()})
        pred["duration_s"] = round(sum(cost.values()) / npw, 6)
        pred["calibrated"] = self.peek["calibrated"]
        return pred


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

def price_into_activity(items, q, runner, act, stats_spec, sort_spec,
                        qcache) -> None:
    """Plan-time pricing for ONE query from the header walk its
    execution is about to consume (`items`: the engine's retained
    (PartStep, ctx) list): predicted summary onto the activity record
    (counters named predicted_* so they ride the query_done journal
    event next to the actuals; obs/activity folds the pair into
    vl_cost_model_rel_error_* at deregister).  Advisory: never fails
    the query."""
    try:
        pricing = _Pricing(q, runner, stats_spec, sort_spec)
        for step, _ctx in items:
            pricing.add(step, qcache)
        pred = pricing.predicted(0)
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
