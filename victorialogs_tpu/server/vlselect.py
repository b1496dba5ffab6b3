"""Query HTTP API handlers: /select/logsql/*.

Reference: app/vlselect (endpoints main.go:212-274, handlers in
app/vlselect/logsql): streamed NDJSON query results, hits histograms via an
injected `stats by (_time:step) count()` pipe (logsql.go:113-170), facets,
field/stream introspection, Prometheus-style stats_query[_range], live tail.
"""

from __future__ import annotations

import json
import math
import time

from ..engine.block_result import format_rfc3339, parse_rfc3339
from ..engine.searcher import (get_field_names, get_field_values, run_query,
                               run_query_collect,
                               run_query_collect_columns)
from ..obs import activity, slowlog, tracing
from ..logsql.duration import parse_duration, ts_bounds
from ..logsql.parser import (MAX_TS, MIN_TS, ParseError, Query, parse_query,
                             parse_filter_string)
from ..logsql.filters import FilterAnd, FilterIn
from ..logsql.pipes import PipeStats, ByField, PipeLimit, PipeOffset
from ..logsql import stats_funcs as sf
from .insertutil import get_tenant_id


class HTTPError(Exception):
    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status
        self.message = message


def _parse_time_arg(v: str, default: int, end: bool = False) -> int:
    if not v:
        return default
    if v == "now":
        return time.time_ns()
    d = parse_duration(v)
    if d is not None:
        return time.time_ns() - abs(d)
    tb = ts_bounds(v)
    if tb is not None:
        return tb[1] if end else tb[0]
    try:  # unix seconds / millis / nanos
        iv = float(v)
        from .insertutil import parse_timestamp
        ts = parse_timestamp(int(iv) if iv.is_integer() else iv)
        if ts is not None:
            return ts
    except ValueError:
        pass
    raise HTTPError(400, f"cannot parse time arg {v!r}")


def parse_common_args(storage, args, headers) -> tuple[Query, list]:
    with tracing.current_span().span("parse"):
        return _parse_common_args(args, headers)


def _parse_common_args(args, headers) -> tuple[Query, list]:
    qs = args.get("query", "")
    if not qs:
        raise HTTPError(400, "missing query arg")
    now = time.time_ns()
    ts = _parse_time_arg(args.get("time", ""), now, end=True)
    try:
        q = parse_query(qs, timestamp=ts)
    except (ParseError, ValueError) as e:
        raise HTTPError(400, f"cannot parse query: {e}")
    start = _parse_time_arg(args.get("start", ""), MIN_TS)
    end = _parse_time_arg(args.get("end", ""), MAX_TS, end=True)
    if start != MIN_TS or end != MAX_TS:
        q.add_time_filter(start, end)
    for extra_arg in ("extra_filters", "extra_stream_filters"):
        ef = args.get(extra_arg, "")
        if ef:
            _apply_extra_filters(q, ef)
    tenant = get_tenant_id(headers, args)
    return q, [tenant]


def _apply_extra_filters(q: Query, ef: str) -> None:
    try:
        obj = json.loads(ef)
    except json.JSONDecodeError:
        obj = None
    if isinstance(obj, dict):
        fs = []
        for k, vals in obj.items():
            if isinstance(vals, str):
                vals = [vals]
            fs.append(FilterIn(k, [str(v) for v in vals]))
        extra = FilterAnd(fs) if len(fs) > 1 else fs[0]
    else:
        try:
            extra = parse_filter_string(ef)
        except (ParseError, ValueError) as e:
            raise HTTPError(400, f"cannot parse extra_filters: {e}")
    f = q.filter
    if isinstance(f, FilterAnd):
        f.filters.insert(0, extra)
    else:
        q.filter = FilterAnd([extra, f])


DEFAULT_MAX_QUERY_DURATION_S = 30.0


def query_timeout_s(args) -> float:
    """Seconds of time budget for one request: per-request `timeout`
    arg capped by the -search.maxQueryDuration default.  Shared by the
    execution deadline (query_deadline) and the admission controller's
    deadline-aware shedding (server/app.py)."""
    t = args.get("timeout", "")
    secs = DEFAULT_MAX_QUERY_DURATION_S
    if t:
        d = parse_duration(t)
        if d is not None and d > 0:
            secs = min(d / 1e9, DEFAULT_MAX_QUERY_DURATION_S * 10)
    return secs


def query_deadline(args) -> float:
    """Monotonic deadline for one query: per-request `timeout` arg capped
    by the -search.maxQueryDuration default (reference
    app/vlselect/main.go:133-150, 277-287)."""
    return time.monotonic() + query_timeout_s(args)


def _int_arg(args, name, default=0) -> int:
    v = args.get(name, "")
    if not v:
        return default
    try:
        return int(v)
    except ValueError:
        raise HTTPError(400, f"invalid {name} arg {v!r}")


# ---------------- tracing plumbing (?trace=1 / slow-query log) ----------------

def want_trace(args) -> bool:
    return args.get("trace", "") in ("1", "true", "yes")


def _tracing_on(args) -> bool:
    """The request asked for a trace OR the slow-query log is armed (a
    slow query without a trace is exactly what the log exists to
    avoid); False keeps the zero-cost no-op path."""
    return want_trace(args) or slowlog.enabled()


def request_trace_root(path: str, args):
    """The `request` root server/app.py opens where a select request
    arrives, before the admission gate; None when tracing is off."""
    if _tracing_on(args):
        return tracing.request_root("request", path=path)
    return None


def _trace_roots(args, q: Query) -> tuple:
    """(root, top): the handler's `query` span and the span whose tree
    a ?trace=1 answer carries; (None, None) with tracing off.  Under a
    served request `query` hangs beneath the ambient `request` root,
    which is the top: it is still open while its own answer is written,
    so its exported extent ends where the tree is serialized.  Called
    without one (embedded use, tests) `query` is its own root."""
    outer = tracing.current_span()
    if outer.enabled:
        return tracing.make_child(outer, "query",
                                  query=q.to_string()), outer
    if _tracing_on(args):
        root = tracing.make_root("query", query=q.to_string())
        return root, root
    return None, None


def _partial_block(act) -> dict | None:
    """The ``"partial"`` payload block when the cluster scatter-gather
    degraded to surviving nodes (cluster.py stamps the record); None on
    a complete answer."""
    failed = act.counter("partial_failed_nodes")
    if failed:
        return {"failed_nodes": list(failed)}
    return None


def _run_collect_traced(storage, tenants, q, args, runner, endpoint,
                        collect=run_query_collect):
    """A collect entry point (run_query_collect or its columnar twin
    run_query_collect_columns) under an optional trace and an
    active-query registry record; returns (result, tree, partial)
    where tree is the span-tree dict only when the request asked for
    it and partial is the ``"partial"`` payload block (or None).
    Emits the slow-query line either way, with the qid correlating it
    to active_queries/traces."""
    root, top = _trace_roots(args, q)
    t0 = time.monotonic()
    # reuse the record the admission layer registered (server/app.py);
    # self-register when called without it (tests, embedded use)
    with activity.reuse_or_track(endpoint, q.to_string(),
                                 tenants[0]) as act:
        if root is not None:
            root.set("qid", act.qid)
        try:
            with tracing.activate(root):
                result = collect(storage, tenants, q, runner=runner,
                                 deadline=query_deadline(args))
            # exec/drain split: the engine walk is done; what remains
            # (JSON shaping, response write) is drain
            act.mark_exec_done()
        finally:
            # in finally: the slowest queries are exactly the ones that
            # die on the deadline — they must still produce their
            # slow-log line
            slowlog.maybe_log(endpoint, q.to_string(),
                              time.monotonic() - t0, root, qid=act.qid)
        partial = _partial_block(act)
    tree = top.to_dict() if top is not None and want_trace(args) \
        else None
    return result, tree, partial


# ---------------- ?explain=1 / ?explain=analyze ----------------

def want_explain(args) -> str:
    """'' (no explain), 'plan' (?explain=1) or 'analyze'
    (?explain=analyze); anything else is a client error."""
    v = args.get("explain", "")
    if not v:
        return ""
    if v in ("1", "true", "yes", "plan"):
        return "plan"
    if v == "analyze":
        return "analyze"
    raise HTTPError(400, f"invalid explain arg {v!r} "
                         f"(use explain=1 or explain=analyze)")


def handle_explain(storage, path, args, headers, runner=None) -> dict:
    """?explain on the query-execution endpoints: the priced physical
    plan tree (obs/explain.py) for EXACTLY the query the endpoint would
    run — including its injected pipes (hits' stats pipe, facets'
    pipe, stats_query_range's _time bucketing).

    explain=1 never executes: zero device dispatches, nothing read past
    part headers / stream indexes / bloom sidecars.  explain=analyze
    executes once and grafts the run's actuals (span-tree per-unit
    timings, activity counters) onto the same tree.  On a cluster
    frontend the per-node trees merge under storage_node nodes exactly
    like ?trace=1."""
    mode = want_explain(args)
    q, tenants = parse_common_args(storage, args, headers)
    if path.endswith("/query"):
        _query_pipes(q, args)
    elif path.endswith("/hits"):
        _hits_pipes(q, args)
    elif path.endswith("/facets"):
        _facets_pipes(q, args)
    elif path.endswith("/stats_query"):
        _require_stats_query(q)
    elif path.endswith("/stats_query_range"):
        _stats_range_pipes(q, args)
    from ..obs import explain as _explain
    if hasattr(storage, "net_explain"):
        # cluster frontend: scatter the explain, merge per-node trees
        # under storage_node nodes (server/cluster.py)
        tree = storage.net_explain(tenants, q, mode,
                                   deadline=query_deadline(args),
                                   include_trace=mode == "analyze"
                                   and want_trace(args))
    else:
        tree = _explain.build_plan(storage, tenants, q, runner=runner)
        if mode == "analyze":
            _explain.analyze(storage, tenants, q, tree, runner=runner,
                             deadline=query_deadline(args),
                             endpoint=path,
                             include_trace=want_trace(args))
    tree["endpoint"] = path
    return {"status": "ok", "explain": tree}


# ---------------- /select/logsql/query ----------------

def handle_query(storage, args, headers, runner=None):
    """Returns an iterator of NDJSON chunks.

    With ?trace=1 the row lines are bit-identical to the untraced
    response; ONE extra final line carries the span tree as
    {"_trace": {...}}."""
    q, tenants = parse_common_args(storage, args, headers)
    _query_pipes(q, args)

    # stream results as blocks arrive; the shared worker protocol
    # (bounded queue + abandon-stream cancellation) lives in streamwork
    from ..engine.emit import ndjson_block
    from .streamwork import stream_blocks

    def encode(br):
        # columnar emit: harvested bitmaps -> response bytes without
        # per-row dicts (engine/emit.py; VL_NATIVE_EMIT=0 kill-switch)
        data = ndjson_block(br)
        return data if data else None

    root, top = _trace_roots(args, q)
    deadline = query_deadline(args)

    def gen():
        # the registry record covers the whole response stream: the
        # admission layer's record is reused (or one registers when the
        # response starts iterating) and deregisters on every exit
        # path (done, deadline, disconnect)
        with activity.reuse_or_track("/select/logsql/query",
                                     q.to_string(), tenants[0]) as act:
            if root is not None:
                root.set("qid", act.qid)

            def run(sink):
                # the query executes on streamwork's worker thread:
                # activate the trace and re-enter the registry record
                # THERE (contextvars don't cross thread spawns); the
                # activation also closes the root on every exit path
                with tracing.activate(root), activity.use_activity(act):
                    run_query(storage, tenants, q, write_block=sink,
                              runner=runner, deadline=deadline)
                    # exec/drain split: the last unit is harvested and
                    # every block is in the response queue; what's left
                    # is the CLIENT draining the stream.  (The bounded
                    # queue means a stalled client can still back-
                    # pressure sink() writes — exec_s includes that,
                    # bounded at 64 chunks, drain_s gets the rest.)
                    activity.current_activity().mark_exec_done()

            t0 = time.monotonic()
            try:
                yield from stream_blocks(run, encode)
            except GeneratorExit:
                # the HTTP peer went away mid-stream: mark the record
                # abandoned and trip the cancel flag so the pipeline
                # drain path stops the device walk instead of finishing
                # a dead query
                act.abandon()
                raise
            finally:
                # in finally: deadline kills (QueryTimeoutError
                # re-raised from the worker) and client disconnects
                # (GeneratorExit at the yield) are exactly the slow
                # queries the log is for
                slowlog.maybe_log("/select/logsql/query", q.to_string(),
                                  time.monotonic() - t0, root,
                                  qid=act.qid)
            partial = _partial_block(act)
            if partial is not None:
                # row lines stay bit-identical to a complete answer;
                # ONE extra final line marks the degradation (the
                # X-VL-Partial header additionally covers every case
                # where the node loss preceded the first output chunk)
                yield json.dumps({"_partial": partial},
                                 ensure_ascii=False,
                                 separators=(",", ":")) + "\n"
            if top is not None and want_trace(args):
                yield json.dumps({"_trace": top.to_dict()},
                                 ensure_ascii=False,
                                 separators=(",", ":")) + "\n"

    return gen()


# ---------------- endpoint pipe preparation ----------------
#
# Each query-execution endpoint rewrites the parsed query's pipe chain
# before running it.  The rewrites live in these helpers so the EXPLAIN
# path (handle_explain) plans EXACTLY the query the endpoint would
# execute — injected stats pipes and all — instead of the raw input.

def _query_pipes(q: Query, args) -> None:
    """/select/logsql/query: offset + limit pushdown."""
    limit = _int_arg(args, "limit", 1000)
    offset = _int_arg(args, "offset", 0)
    if offset:
        q.pipes.append(PipeOffset(offset))
    if limit > 0:
        q.pipes.append(PipeLimit(limit))


def _hits_pipes(q: Query, args) -> list:
    """/select/logsql/hits: the injected `stats by (_time:step [, f..])
    count() hits` pipe; returns the extra group fields."""
    step = args.get("step", "1d")
    if parse_duration(step) is None:
        raise HTTPError(400, f"invalid step {step!r}")
    offset_s = args.get("offset", "0s")
    fields = [f.strip() for f in args.get("field", "").split(",")
              if f.strip()] + \
             [f.strip() for f in args.get("fields", "").split(",")
              if f.strip()]
    by = [ByField("_time", bucket=step, bucket_offset=offset_s)] + \
        [ByField(f) for f in fields]
    fn = sf.StatsCount([])
    fn.out_name = "hits"
    q.pipes.append(PipeStats(by, [fn]))
    return fields


def _facets_pipes(q: Query, args) -> None:
    from ..logsql.pipes_transform import PipeFacets
    q.pipes.append(PipeFacets(
        limit=_int_arg(args, "limit", 10),
        max_values_per_field=_int_arg(args, "max_values_per_field", 1000),
        max_value_len=_int_arg(args, "max_value_len", 1000),
        keep_const_fields=bool(args.get("keep_const_fields", ""))))


def _stats_range_pipes(q: Query, args) -> PipeStats:
    sp = _require_stats_query(q)
    step = args.get("step", "1d")
    if parse_duration(step) is None:
        raise HTTPError(400, f"invalid step {step!r}")
    if not any(b.name == "_time" for b in sp.by):
        sp.by.insert(0, ByField("_time", bucket=step))
    return sp


# ---------------- /select/logsql/hits ----------------

def handle_hits(storage, args, headers, runner=None) -> dict:
    q, tenants = parse_common_args(storage, args, headers)
    fields = _hits_pipes(q, args)
    # columnar collect: the stats output arrives as bulk columns (one
    # contract for local and cluster paths) — group rows are zipped
    # from the lists, never materialized as dicts
    (cols, n), trace_tree, partial = _run_collect_traced(
        storage, tenants, q, args, runner, "/select/logsql/hits",
        collect=run_query_collect_columns)
    tcol = cols.get("_time") or [""] * n
    hcol = cols.get("hits") or [""] * n
    fcols = [cols.get(f) or [""] * n for f in fields]
    groups: dict = {}
    for i in range(n):
        key = tuple((f, fc[i]) for f, fc in zip(fields, fcols))
        g = groups.setdefault(key, {"fields": dict(key), "timestamps": [],
                                    "values": [], "total": 0})
        g["timestamps"].append(tcol[i])
        hits = int(hcol[i] or "0")
        g["values"].append(hits)
        g["total"] += hits
    out = {"hits": sorted(groups.values(),
                          key=lambda g: -g["total"])}
    if partial is not None:
        out["partial"] = partial
    if trace_tree is not None:
        out["trace"] = trace_tree
    return out


# ---------------- /select/logsql/facets ----------------

def handle_facets(storage, args, headers, runner=None) -> dict:
    q, tenants = parse_common_args(storage, args, headers)
    _facets_pipes(q, args)
    (cols, n), trace_tree, partial = _run_collect_traced(
        storage, tenants, q, args, runner, "/select/logsql/facets",
        collect=run_query_collect_columns)
    out: dict[str, list] = {}
    for fname, fval, hits in zip(cols.get("field_name") or [],
                                 cols.get("field_value") or [],
                                 cols.get("hits") or []):
        # vlint: allow-per-row-emit(facet OUTPUT groups, bounded by limit*fields)
        out.setdefault(fname, []).append(
            {"field_value": fval, "hits": int(hits)})
    # vlint: allow-per-row-emit(facet OUTPUT: one dict per faceted field)
    res = {"facets": [{"field_name": f, "values": v}
                      for f, v in sorted(out.items())]}
    if partial is not None:
        res["partial"] = partial
    if trace_tree is not None:
        res["trace"] = trace_tree
    return res


# ---------------- field/stream introspection ----------------

def handle_field_names(storage, args, headers) -> dict:
    q, tenants = parse_common_args(storage, args, headers)
    return {"values": get_field_names(storage, tenants, q)}


def handle_field_values(storage, args, headers) -> dict:
    q, tenants = parse_common_args(storage, args, headers)
    field = args.get("field", "")
    if not field:
        raise HTTPError(400, "missing field arg")
    limit = _int_arg(args, "limit", 0)
    return {"values": get_field_values(storage, tenants, q, field, limit)}


def handle_streams(storage, args, headers) -> dict:
    q, tenants = parse_common_args(storage, args, headers)
    limit = _int_arg(args, "limit", 0)
    return {"values": get_field_values(storage, tenants, q, "_stream",
                                       limit)}


def handle_stream_ids(storage, args, headers) -> dict:
    q, tenants = parse_common_args(storage, args, headers)
    limit = _int_arg(args, "limit", 0)
    return {"values": get_field_values(storage, tenants, q, "_stream_id",
                                       limit)}


def handle_stream_field_names(storage, args, headers) -> dict:
    from ..storage.stream_filter import parse_stream_tags
    q, tenants = parse_common_args(storage, args, headers)
    hits: dict[str, int] = {}

    def sink(br):
        for v in br.column("_stream"):
            for name in parse_stream_tags(v):
                hits[name] = hits.get(name, 0) + 1
    run_query(storage, tenants, q, write_block=sink)
    # vlint: allow-per-row-emit(introspection OUTPUT: one dict per tag name)
    return {"values": [{"value": k, "hits": str(hits[k])}
                       for k in sorted(hits)]}


def handle_stream_field_values(storage, args, headers) -> dict:
    from ..storage.stream_filter import parse_stream_tags
    q, tenants = parse_common_args(storage, args, headers)
    field = args.get("field", "")
    if not field:
        raise HTTPError(400, "missing field arg")
    limit = _int_arg(args, "limit", 0)
    hits: dict[str, int] = {}

    def sink(br):
        for v in br.column("_stream"):
            tags = parse_stream_tags(v)
            if field in tags:
                hits[tags[field]] = hits.get(tags[field], 0) + 1
    run_query(storage, tenants, q, write_block=sink)
    # vlint: allow-per-row-emit(introspection OUTPUT: one dict per tag value)
    out = [{"value": k, "hits": str(v)}
           for k, v in sorted(hits.items(), key=lambda kv: (-kv[1], kv[0]))]
    if limit:
        out = out[:limit]
    return {"values": out}


# ---------------- stats_query / stats_query_range ----------------

def _require_stats_query(q: Query) -> PipeStats:
    for p in reversed(q.pipes):
        if isinstance(p, PipeStats):
            return p
    raise HTTPError(400, "query must end with a `stats` pipe")


def handle_stats_query(storage, args, headers, runner=None) -> dict:
    q, tenants = parse_common_args(storage, args, headers)
    sp = _require_stats_query(q)
    ts = _parse_time_arg(args.get("time", ""), time.time_ns(), end=True)
    (cols, nrows), trace_tree, partial = _run_collect_traced(
        storage, tenants, q, args, runner, "/select/logsql/stats_query",
        collect=run_query_collect_columns)
    result = []
    by_names = [b.name for b in sp.by]
    by_cols = [cols.get(n) or [""] * nrows for n in by_names]
    fn_cols = [cols.get(fn.out_name) or [""] * nrows
               for fn in sp.funcs]
    for i in range(nrows):
        for fn, vc in zip(sp.funcs, fn_cols):
            metric = {"__name__": fn.out_name}
            for n, bc in zip(by_names, by_cols):
                if bc[i] != "":
                    metric[n] = bc[i]
            # vlint: allow-per-row-emit(stats OUTPUT groups, bounded by group count)
            result.append({"metric": metric,
                           "value": [ts / 1e9, vc[i]]})
    out = {"status": "success",
           "data": {"resultType": "vector", "result": result}}
    if partial is not None:
        out["partial"] = partial
    if trace_tree is not None:
        out["trace"] = trace_tree
    return out


def handle_stats_query_range(storage, args, headers, runner=None) -> dict:
    q, tenants = parse_common_args(storage, args, headers)
    sp = _stats_range_pipes(q, args)
    (cols, nrows), trace_tree, partial = _run_collect_traced(
        storage, tenants, q, args, runner,
        "/select/logsql/stats_query_range",
        collect=run_query_collect_columns)
    series: dict = {}
    by_names = [b.name for b in sp.by if b.name != "_time"]
    tcol = cols.get("_time") or [""] * nrows
    by_cols = [cols.get(n) or [""] * nrows for n in by_names]
    fn_cols = [cols.get(fn.out_name) or [""] * nrows
               for fn in sp.funcs]
    for i in range(nrows):
        t = parse_rfc3339(tcol[i]) or 0
        for fn, vc in zip(sp.funcs, fn_cols):
            key = (fn.out_name,) + tuple((n, bc[i])
                                         for n, bc in zip(by_names,
                                                          by_cols))
            s = series.setdefault(key, {"metric": dict(
                [("__name__", fn.out_name)] +
                [(n, bc[i]) for n, bc in zip(by_names, by_cols)
                 if bc[i] != ""]),
                "values": []})
            s["values"].append([t / 1e9, vc[i]])
    for s in series.values():
        s["values"].sort()
    out = {"status": "success",
           "data": {"resultType": "matrix",
                    "result": list(series.values())}}
    if partial is not None:
        out["partial"] = partial
    if trace_tree is not None:
        out["trace"] = trace_tree
    return out


# ---------------- live tail ----------------

def handle_tail(storage, args, headers, stop_check=None, runner=None):
    """Generator yielding NDJSON chunks for new rows (poll loop, ~1s period
    with a lag offset — reference logsql.go:497-580)."""
    q, tenants = parse_common_args(storage, args, headers)
    if not q.can_live_tail():
        raise HTTPError(400, "query contains pipes that cannot live-tail")
    lag_ns = 2_500_000_000
    last_ts = time.time_ns() - lag_ns
    # one registry record for the whole tail connection: cancel_query
    # on its qid (or a client disconnect) ends the tail; the inner
    # polls inherit the record ambiently, so a cancel also drains a
    # poll that is mid-scan
    with activity.reuse_or_track("/select/logsql/tail", q.to_string(),
                                 tenants[0]) as act:
        try:
            yield from _tail_loop(storage, tenants, q, act, lag_ns,
                                  last_ts, stop_check, runner)
        except GeneratorExit:
            act.abandon()
            raise


def _tail_loop(storage, tenants, q, act, lag_ns, last_ts, stop_check,
               runner):
    from ..engine.emit import ndjson_block
    while True:
        if stop_check is not None and stop_check():
            return
        if act.is_cancelled():
            return
        now_end = time.time_ns() - lag_ns
        qq = q.clone()
        qq.add_time_filter(last_ts + 1, now_end)
        # columnar emit per block; the cross-block _time sort happens on
        # (int64-ns, line-bytes) pairs, never on row dicts.  Typed keys
        # also FIX the old lexical sort: trimmed RFC3339Nano misorders
        # sub-second rows ("..00.5Z" < "..00Z" byte-wise); blocks come
        # with their timestamps attached, so ns order is free.  Rows
        # whose _time is projected out keep arrival order (key 0),
        # like the old "" keys did.
        pairs: list = []

        def sink(br):
            if br.nrows == 0:
                return
            lines = ndjson_block(br).split(b"\n")[:br.nrows]
            names = br.column_names()
            native_keys = br.native_time_keys()
            if "_time" not in names:
                # projected out: arrival order, like the old "" keys
                keys = [0] * br.nrows
            elif native_keys is not None:
                # storage-backed or cluster wire view: the displayed
                # _time IS the native int64 array — sort on it directly
                keys = native_keys.tolist()
            else:
                # a pipe may have rewritten _time (copy/rename/extract):
                # the sort key must follow the DISPLAYED value, not the
                # original ingestion timestamps the block still carries
                keys = [parse_rfc3339(v) or 0
                        for v in br.column("_time")]
            pairs.extend(zip(keys, lines))
        run_query(storage, tenants, qq, write_block=sink, runner=runner)
        pairs.sort(key=lambda kv: kv[0])
        if pairs:
            yield b"\n".join(ln for _k, ln in pairs) + b"\n"
        else:
            yield ""  # keep-alive chunk
        last_ts = now_end
        # sleep on the cancel flag so cancel_query wakes the tail
        # immediately instead of after the poll period
        if act.wait_cancelled(1.0):
            return
