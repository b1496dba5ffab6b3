"""Query execution: partition/part/block scheduling + pipe chain driving.

The CPU analogue of the reference's storage_search.go: RunQuery materializes
subqueries, extracts the global time range from the filter tree, resolves
`{stream}` filters against each partition's index, schedules surviving blocks
through the filter tree, and feeds resulting batches through the pipe
processor chain with per-pipe cancellation (storage_search.go:102-185,
1035-1121).

With a runner the selected parts go through the device dispatch window
(tpu/pipeline.py); this module stays the correctness oracle and the host
executor every gated or declined part falls back to.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from ..logsql.filters import (Filter, FilterAnd, FilterIn, FilterContainsAll,
                              FilterContainsAny, FilterNone, FilterNoop,
                              FilterNot, FilterOr, FilterStream, FilterTime)
from ..obs import activity, events, tracing
from ..logsql.parser import MAX_TS, MIN_TS, Query, parse_query
from ..logsql.pipes import Processor, SinkProcessor
from ..storage.log_rows import TenantID
from .block_result import BlockResult
from .block_search import BlockSearch, new_bitmap
from .planwalk import PartitionWalk, observe


class QueryCancelled(Exception):
    pass


class QueryTimeoutError(Exception):
    """Raised when a query exceeds its deadline (reference
    -search.maxQueryDuration — app/vlselect/main.go:133-150)."""


class _CancelAwareHead:
    """Processor-chain head facade that folds the active-query
    registry's cancel flag (cancel_query / client-disconnect abandon —
    obs/activity.py) into is_done(): the scan loops already treat a
    done head as QueryCancelled, so an external cancel drains the
    device pipeline's in-flight window without downstream writes
    (tpu/pipeline.py PR 3 semantics) and stops the serial walk at its
    next block."""

    __slots__ = ("_head", "_act")

    def __init__(self, head, act):
        self._head = head
        self._act = act

    def write_block(self, br) -> None:
        self._head.write_block(br)

    def absorb_partials(self, key, states) -> None:
        self._head.absorb_partials(key, states)

    def flush(self) -> None:
        self._head.flush()

    def is_done(self) -> bool:
        return self._act.is_cancelled() or self._head.is_done()


def build_processor_chain(pipes: list, write_fn) -> Processor:
    pp: Processor = SinkProcessor(write_fn)
    for pipe in reversed(pipes):
        pp = pipe.make_processor(pp)
    return pp


def _iter_subquery_filters(f: Filter):
    if isinstance(f, (FilterIn, FilterContainsAll, FilterContainsAny)):
        if f.subquery is not None:
            yield f
    elif isinstance(f, (FilterAnd, FilterOr)):
        for sub in f.filters:
            yield from _iter_subquery_filters(sub)
    elif isinstance(f, FilterNot):
        yield from _iter_subquery_filters(f.inner)


def _run_single_column_subquery(storage, tenants, sub, runner=None
                                ) -> list[str]:
    """Run a subquery that must yield exactly one result column (the
    reference errors on multi-column in() subqueries too)."""
    values: list[str] = []
    col_name: list = [None]

    def sink(br: BlockResult):
        if br._bs is not None and br._restrict is None:
            # raw storage blocks (no fields projection): require an
            # explicit `| fields x` pipe
            raise ValueError(
                "in(<subquery>) must narrow its output to one column, "
                "e.g. `... | fields x`")
        names = br.column_names()
        if len(names) != 1:
            raise ValueError(
                f"in(<subquery>) must yield exactly one column, got "
                f"{names!r}")
        if col_name[0] is None:
            col_name[0] = names[0]
        elif col_name[0] != names[0]:
            raise ValueError(
                f"in(<subquery>) yielded inconsistent columns "
                f"{col_name[0]!r} vs {names[0]!r}")
        values.extend(br.column(names[0]))
    run_query(storage, tenants, sub, write_block=sink, runner=runner)
    return values


def init_subqueries(storage, tenants, q: Query, runner=None,
                    detach: bool = False) -> None:
    """Materialize in(<subquery>)-style filters (reference
    storage_search.go:530-553).

    detach=True drops the subquery after materialization so to_string()
    renders the literal value list — the cluster front uses this to
    resolve subqueries over the WHOLE cluster once and ship plain in(...)
    filters to the storage nodes (reference initFilterInValues)."""
    from ..logsql.pipes import PipeWhere
    subfilters = list(_iter_subquery_filters(q.filter))
    for p in q.pipes:
        if isinstance(p, PipeWhere):
            subfilters.extend(_iter_subquery_filters(p.filter))
    for f in subfilters:
        f.set_values(_run_single_column_subquery(storage, tenants,
                                                 f.subquery, runner=runner))
        if detach:
            f.subquery = None


def _collect_stream_filters(f: Filter, out: list) -> None:
    """Stream filters on the top-level AND path (usable for block pruning)."""
    if isinstance(f, FilterStream):
        out.append(f)
    elif isinstance(f, FilterAnd):
        for sub in f.filters:
            _collect_stream_filters(sub, out)


def run_query(storage, tenants, q: Query | str, write_block=None,
              timestamp: int | None = None, runner=None,
              deadline: float | None = None) -> None:
    """Execute a LogsQL query; write_block(BlockResult) receives results.

    write_block is the COLUMNAR sink protocol: blocks arrive with their
    storage backing attached whenever the pipe chain allows (the fields/
    delete pipes project without materializing), so sinks that serialize
    (server/vlselect.py NDJSON emit) go straight from the harvested
    bitmaps to response bytes via BlockResult.emit_columns() /
    engine.emit.ndjson_block() — rows never become per-row dicts on that
    path.  Dict-rows consumers keep using br.rows().

    runner: optional TPU runner (tpu/batch.py BatchRunner) — when given,
    block filtering dispatches to the device, one dispatch per leaf per
    part.
    deadline: monotonic-clock limit; past it the query fails with
    QueryTimeoutError (reference -search.maxQueryDuration).
    """
    if isinstance(q, str):
        q = parse_query(q, timestamp)
    if isinstance(tenants, TenantID):
        tenants = [tenants]
    tenants = tuple(tenants)

    # self-telemetry recursion guard: a query AGAINST the reserved
    # system tenant must not feed the journal it is reading.  Queries
    # registered in the activity registry are suppressed ambiently
    # (events.emit checks the record's tenant on EVERY worker thread —
    # the record propagates into partition/pool workers via
    # use_activity).  A bare engine-level entry with no record gets
    # both halves here: a thread-local guard for this thread's extent
    # AND a registered system-tenant record, so fan-out workers —
    # which re-enter the record but not the thread-local — are
    # suppressed too.
    if not events.in_guard() and \
            not activity.current_activity().enabled and \
            any(activity.tenant_str(t) == events.SYSTEM_TENANT
                for t in tenants):
        with events.guarded(), \
                activity.track("run_query", q.to_string(), tenants):
            _run_query_guarded(storage, tenants, q, write_block,
                               timestamp, runner, deadline)
        return

    _run_query_guarded(storage, tenants, q, write_block, timestamp,
                       runner, deadline)


def _run_query_guarded(storage, tenants, q, write_block, timestamp,
                       runner, deadline) -> None:
    if hasattr(storage, "net_run_query"):
        # cluster mode: storage is a NetSelectStorage — scatter-gather the
        # query over the storage nodes (server/cluster.py)
        storage.net_run_query(list(tenants), q, write_block=write_block,
                              timestamp=timestamp, deadline=deadline)
        return

    # continuous plan-time pricing (obs/explain.py): claim the record's
    # priced slot BEFORE subqueries materialize — an in(<subquery>)
    # executes through this same record and must not publish ITS
    # prediction as the outer query's
    from ..obs import explain
    act0 = activity.current_activity()
    price = runner is not None and act0.enabled and \
        explain.pricing_enabled() and not act0.counter("priced")
    if price:
        act0.set("priced", 1)

    init_subqueries(storage, tenants, q, runner=runner)
    # storage-backed pipes (join/union/stream_context) get their query hook
    for p in q.pipes:
        if hasattr(p, "init_with_storage"):
            p.init_with_storage(storage, tenants, runner)

    min_ts, max_ts = q.get_time_range()

    # rate()/rate_sum() divide by the time-filter range (reference
    # Query.initStatsRateFuncsFromTimeFilter — parser.go:1218-1224)
    if min_ts != MIN_TS and max_ts != MAX_TS:
        from ..logsql.pipes import PipeStats
        step_seconds = (max_ts - min_ts + 1) / 1e9
        for p in q.pipes:
            if isinstance(p, PipeStats):
                for fn in p.funcs:
                    if hasattr(fn, "step_seconds"):
                        fn.step_seconds = step_seconds

    act = activity.current_activity()
    if act.enabled and write_block is not None:
        # rows-emitted accounting at the FINAL sink (per block, never
        # per row): what the client actually received, after every pipe
        inner_sink = write_block

        def write_block(br):
            act.add("rows_emitted", br.nrows)
            inner_sink(br)

    head = build_processor_chain(q.pipes, write_block or (lambda br: None))
    if act.enabled:
        head = _CancelAwareHead(head, act)
    from ..logsql.pipes import compute_needed_fields
    needed = compute_needed_fields(q.pipes)

    # device stats partials: `<filter> | stats [by (_time:step)] ...` runs
    # as one fused dispatch per part after the filter bitmap, merging
    # per-bucket partials straight into the stats processor
    # (tpu/stats_device.py; reference pipe_stats.go:354-377)
    stats_spec = None
    if runner is not None:
        from ..tpu.stats_device import device_stats_spec
        stats_spec = device_stats_spec(q)

    # device sort-topk prefilter: `<filter> | sort by (f) limit N` keeps
    # only rows at-or-above each part's k-th best key (tpu/sort_device.py)
    sort_spec = None
    if stats_spec is None and runner is not None:
        from ..tpu.sort_device import device_sort_spec
        sort_spec = device_sort_spec(q)

    # per-part result cache (engine/standing/resultcache.py): a
    # repeated query's sealed parts replay their cached stats partials
    # / filter bitmaps instead of re-dispatching — only the unsealed
    # head recomputes.  for_query returns None when caching can't
    # apply (VL_RESULT_CACHE=0, in(<subquery>) filters).
    from .standing.resultcache import QueryCache
    qcache = QueryCache.for_query(q, tenants, stats_spec, sort_spec,
                                  min_ts, max_ts)

    sfs: list[FilterStream] = []
    _collect_stream_filters(q.filter, sfs)

    # part-level aggregate pruning (filter-index subsystem): AND-path
    # leaves with required word tokens can kill a WHOLE part in O(1)
    # against its Bloofi-style aggregate filter before any per-block
    # work — the per-block bloom kill-path would have zeroed each block
    # anyway, so results are identical (storage/filterbank.py)
    from ..logsql.filters import iter_and_path_token_leaves
    token_leaves = list(iter_and_path_token_leaves(q.filter))

    # CPU-path block workers (reference spawns GetConcurrency() workers
    # over a 64-block channel — storage_search.go:1035-1067; numpy/zstd
    # release the GIL, so threads overlap real work).  One pool is SHARED
    # across partitions so total workers stay bounded.
    nworkers = 1 if runner is not None else q.get_concurrency()
    pool = None
    if nworkers > 1:
        from concurrent.futures import ThreadPoolExecutor
        pool = ThreadPoolExecutor(max_workers=nworkers)

    def scan_partition(pt, sink_head):
        with tracing.current_span().span(
                "partition", day=getattr(pt, "day", None)) as psp:
            pw = PartitionWalk(pt, tenants, min_ts, max_ts, sfs)
            if pw.pruned_by_stream_filter:
                psp.set("pruned_by_stream_filter", True)
                return
            _scan_parts(pw, q, sink_head, needed, deadline, pool,
                        token_leaves, qcache)

    try:
        pts = storage.select_partitions(min_ts, max_ts)
        if runner is not None:
            # device path: ONE dispatch window across every selected
            # partition (tpu/pipeline.scan_device_stream) — parts from
            # partition N+1 submit while partition N harvests, packs
            # may span the day boundary, and prefetch depth survives
            # it.  The window IS the parallelism here (dispatches from
            # several partitions overlap on the one device), so the
            # thread-per-partition fan-out below is the host executor's.
            from ..tpu.pipeline import scan_device_stream
            qsp = tracing.current_span()
            walk_args = (pts, tenants, min_ts, max_ts, sfs, token_leaves,
                         runner)
            if price:
                # ONE header walk a query: eager, priced against the
                # live cost-model EWMAs (predicted_* ride the query_done
                # event beside the actuals), then the window's part
                # stream, so the priced plan IS the executed plan; a
                # cold aggregate fold waits until the window reaches the
                # part.  Unpriced, the window pulls the walk lazily and
                # a `limit` stops it early.
                act.set_phase("prune")
                with qsp.span("prune") as prsp:
                    items = list(_device_walk(prsp, *walk_args,
                                              build=False))
                    prsp.set("parts_retained", len(items))
                explain.price_into_activity(items, q, runner, act0,
                                            stats_spec, sort_spec, qcache)
                runner._bump("shared_plan_walks")
            else:
                items = _device_walk(qsp, *walk_args, build=True)
            scan_device_stream(items, q, head, runner, needed, deadline,
                               stats_spec, sort_spec, token_leaves,
                               qcache=qcache)
        else:
            # per-day partitions search CONCURRENTLY under a worker cap
            # (reference storage_search.go:1095-1126): a 30-day query
            # is no longer 30x the single-day latency.  The processor
            # chain is not thread-safe, so partition workers funnel
            # through a locked head; within one partition, block order
            # stays deterministic.
            npw = min(len(pts), q.get_concurrency())
            if npw <= 1:
                for pt in pts:
                    scan_partition(pt, head)
            else:
                _scan_partitions_parallel(pts, scan_partition, head,
                                          npw)
    except QueryCancelled:
        pass
    finally:
        if pool is not None:
            pool.shutdown(wait=True)
    head.flush()


class _SyncHead:
    """Thread-safe facade over the processor chain head for concurrent
    partition workers; also turns the cross-worker stop flag into
    is_done() so sibling scans exit at their next check."""

    def __init__(self, head, lock, stop):
        self._head = head
        self._lock = lock
        self._stop = stop

    def write_block(self, br) -> None:
        with self._lock:
            self._head.write_block(br)

    def absorb_partials(self, key, states) -> None:
        with self._lock:
            self._head.absorb_partials(key, states)

    def is_done(self) -> bool:
        if self._stop.is_set():
            return True
        with self._lock:
            return self._head.is_done()


def _scan_partitions_parallel(pts, scan_partition, head, npw) -> None:
    import threading as _threading
    from concurrent.futures import ThreadPoolExecutor

    lock = _threading.Lock()
    stop = _threading.Event()
    sync_head = _SyncHead(head, lock, stop)
    errors: list = []
    # contextvars don't cross thread spawns: re-enter the caller's span
    # AND activity record in each partition worker so their "partition"
    # spans nest under it and progress counters land on the registry
    parent_span = tracing.current_span()
    parent_act = activity.current_activity()

    def run_one(pt):
        if stop.is_set():
            return
        try:
            with tracing.use_span(parent_span), \
                    activity.use_activity(parent_act):
                scan_partition(pt, sync_head)
        except QueryCancelled:
            stop.set()
        # vlint: allow-broad-except(fan-out error channel, re-raised)
        except Exception as e:
            errors.append(e)
            stop.set()

    with ThreadPoolExecutor(max_workers=npw) as ex:
        list(ex.map(run_one, pts))
    if errors:
        raise errors[0]


def _device_walk(qsp, pts, tenants, min_ts, max_ts, sfs, token_leaves,
                 runner, build: bool):
    """The device path's header walk: a lazy stream of (PartStep, ctx),
    one a surviving part of every selected partition, the execution's
    accounting landed as it advances.  Partition setup stays lazy AND
    attributed: a partition resolves and snapshots only when the pull
    reaches it, under a short-lived span off `qsp` (day, part count,
    stream-filter prunes), so a lazy consumer's early exit (limit,
    deadline, cancel) stops the walk where the serial loop would."""
    act = activity.current_activity()
    for pt in pts:
        # the span covers partition SETUP only (it must not stay open
        # across planning pulls — spans are ambient via a contextvar,
        # and a generator holding one open would leak it into the
        # window driver's own spans between pulls)
        with qsp.span("partition", day=getattr(pt, "day", None)) as psp:
            pw = PartitionWalk(pt, tenants, min_ts, max_ts, sfs)
            if pw.pruned_by_stream_filter:
                psp.set("pruned_by_stream_filter", True)
            else:
                psp.set("parts", pw.in_range)
                act.add("parts_total", pw.in_range)
        for step in pw.steps(token_leaves, build):
            observe(step, runner)
            if step.bis:
                yield step, pw.ctx


def _eval_block_cpu(q, bs):
    bm = new_bitmap(bs.nrows)
    q.filter.apply_to_block(bs, bm)
    return bm


def _absorb_stats_partials(head, q, spec, partials) -> None:
    """Fold device per-bucket partials into the stats processor.

    key_parts elements: ("t", bucket_ns) -> RFC3339 (identical to the
    host bucketing), ("v", value) -> the group value string."""
    from ..tpu.stats_device import build_partial_states
    from .block_result import format_rfc3339
    ps = q.pipes[0]
    for key_parts, cnt, field_stats, uniq_vals, quant_vals in partials:
        key = tuple(format_rfc3339(v) if kind == "t" else v
                    for kind, v in key_parts)
        states = build_partial_states(spec, ps.funcs, key, cnt,
                                      field_stats, uniq_vals, quant_vals)
        head.absorb_partials(key, states)


def _scan_parts(pw, q, head, needed, deadline, pool, token_leaves,
                qcache) -> None:
    """The host executor's walk over one partition's parts."""
    ctx = pw.ctx
    sp = tracing.current_span()
    sp.set("parts", pw.in_range)
    act = activity.current_activity()
    act.add("parts_total", pw.in_range)
    act.set_phase("scan")
    for step in pw.steps(token_leaves):
        if deadline is not None and time.monotonic() > deadline:
            raise QueryTimeoutError(
                "query exceeded -search.maxQueryDuration")
        part, part_bis = step.part, step.bis
        sp.add("blocks_candidate", step.n_cand)
        observe(step)
        if not part_bis:
            continue
        activity.note_part_scanned(act, part, part_bis, step.rows)
        if qcache is not None and qcache.kind == "bms":
            # sealed-part replay: the cached bitmaps feed the chain in
            # the exact block order the walk below would produce
            e = qcache.probe(part, part_bis)
            if e is not None:
                cached_bms = qcache.entry_bms(e)
                for bi in part_bis:
                    if head.is_done():
                        raise QueryCancelled()
                    bm = cached_bms[bi]
                    if not bm.any():
                        continue
                    bs = BlockSearch(part, bi)
                    bs.ctx = ctx
                    br = BlockResult.from_block_search(bs, bm, needed)
                    sp.add("blocks_out")
                    sp.add("rows_out", br.nrows)
                    head.write_block(br)
                continue
        collected: dict[int, np.ndarray] = {}
        cand: dict[int, BlockSearch] = {}
        for bi in part_bis:
            if head.is_done():
                raise QueryCancelled()
            bs = BlockSearch(part, bi)
            bs.ctx = ctx
            if pool is not None:
                cand[bi] = bs
                continue
            bm = new_bitmap(bs.nrows)
            q.filter.apply_to_block(bs, bm)
            collected[bi] = bm
            if not bm.any():
                continue
            br = BlockResult.from_block_search(bs, bm, needed)
            sp.add("blocks_out")
            sp.add("rows_out", br.nrows)
            head.write_block(br)
        if not cand:
            if qcache is not None:
                qcache.store_bms(part, part_bis, collected)
            continue
        if head.is_done():
            raise QueryCancelled()
        # CPU worker pool: filters evaluate in parallel, results
        # are written downstream in deterministic block order
        order = list(cand)
        results = pool.map(lambda bi: _eval_block_cpu(q, cand[bi]),
                           order)
        bms = dict(zip(order, results))
        for bi, bs in cand.items():
            if head.is_done():
                raise QueryCancelled()
            bm = bms[bi]
            if not bm.any():
                continue
            br = BlockResult.from_block_search(bs, bm, needed)
            sp.add("blocks_out")
            sp.add("rows_out", br.nrows)
            head.write_block(br)
        if qcache is not None:
            collected.update(bms)
            qcache.store_bms(part, part_bis, collected)


def run_query_collect(storage, tenants, q: Query | str,
                      timestamp: int | None = None, runner=None,
                      deadline: float | None = None) -> list[dict]:
    """Execute and collect result rows as dicts (test/API convenience).

    Registers its own activity record when none is ambient (the
    engine-level entry point CLI tools and benches drive directly) so
    every query execution shows up in /select/logsql/active_queries;
    the HTTP handlers register endpoint-specific records first, which
    this inherits instead of double-registering."""
    rows: list[dict] = []

    def sink(br: BlockResult):
        rows.extend(br.rows())

    with _collect_ctx(q, tenants):
        run_query(storage, tenants, q, write_block=sink,
                  timestamp=timestamp, runner=runner, deadline=deadline)
    return rows


def _collect_ctx(q, tenants):
    """Activity registration shared by the collect entry points:
    inherit an ambient record (HTTP handlers register endpoint-specific
    ones first) or self-register."""
    if activity.current_activity().enabled:
        return contextlib.nullcontext()
    # vlint: allow-accounting-discipline(entered by the caller's with)
    return activity.track("run_query_collect",
                          q if isinstance(q, str) else q.to_string(),
                          tenants)


def run_query_collect_columns(storage, tenants, q: Query | str,
                              timestamp: int | None = None, runner=None,
                              deadline: float | None = None
                              ) -> tuple[dict, int]:
    """Columnar twin of run_query_collect: (cols, nrows) where cols is
    an insertion-ordered {name: [str, ...]} with every list nrows long
    (values absent in a block read "").

    Consumers that aggregate result rows (hits/facets/stats endpoints,
    the storage-backed aux pipes) ride this instead of rows() so the
    local and cluster paths share one columnar contract — per-column
    bulk lists, no per-row dict materialization."""
    blocks: list = []            # (names, {name: list}, nrows)
    order: dict[str, None] = {}

    def sink(br: BlockResult):
        names = br.column_names()
        blocks.append((names, {n: br.column(n) for n in names},
                       br.nrows))
        for n in names:
            order.setdefault(n, None)

    with _collect_ctx(q, tenants):
        run_query(storage, tenants, q, write_block=sink,
                  timestamp=timestamp, runner=runner, deadline=deadline)
    total = sum(b[2] for b in blocks)
    cols: dict[str, list] = {n: [] for n in order}
    for _names, bc, n in blocks:
        for name, out in cols.items():
            vals = bc.get(name)
            out.extend(vals if vals is not None else [""] * n)
    return cols, total


# ---- field/value introspection (vlselect support) ----

def get_field_names(storage, tenants, q: Query | str,
                    timestamp: int | None = None) -> list[dict]:
    """Distinct field names with hit counts (reference GetFieldNames)."""
    if isinstance(q, str):
        q = parse_query(q, timestamp)
    hits: dict[str, int] = {}

    def sink(br: BlockResult):
        for n in br.column_names():
            cnt = sum(1 for v in br.column(n) if v != "")
            if n in ("_time", "_stream", "_stream_id"):
                cnt = br.nrows
            if cnt:
                hits[n] = hits.get(n, 0) + cnt
    run_query(storage, tenants, q, write_block=sink, timestamp=timestamp)
    # vlint: allow-per-row-emit(introspection OUTPUT: one dict per distinct name)
    return [{"value": k, "hits": str(hits[k])} for k in sorted(hits)]


def get_field_values(storage, tenants, q: Query | str, field: str,
                     limit: int = 0, timestamp: int | None = None
                     ) -> list[dict]:
    """Distinct values of a field with hit counts (reference GetFieldValues)."""
    if isinstance(q, str):
        q = parse_query(q, timestamp)
    hits: dict[str, int] = {}

    def sink(br: BlockResult):
        for v in br.column(field):
            if v != "":
                hits[v] = hits.get(v, 0) + 1
    run_query(storage, tenants, q, write_block=sink, timestamp=timestamp)
    # vlint: allow-per-row-emit(introspection OUTPUT: one dict per distinct value)
    out = [{"value": k, "hits": str(hits[k])} for k in sorted(hits)]
    if limit and len(out) > limit:
        out = out[:limit]
    return out


def get_streams(storage, tenants, q: Query | str, limit: int = 0,
                timestamp: int | None = None) -> list[dict]:
    return get_field_values(storage, tenants, q, "_stream", limit, timestamp)


def get_stream_ids(storage, tenants, q: Query | str, limit: int = 0,
                   timestamp: int | None = None) -> list[dict]:
    return get_field_values(storage, tenants, q, "_stream_id", limit,
                            timestamp)
