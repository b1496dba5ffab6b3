"""Observability layer: per-query hierarchical tracing (tracing.py),
fixed-bucket Prometheus histograms (hist.py), the slow-query log
(slowlog.py), the active-query registry with per-tenant resource
accounting (activity.py — /select/logsql/active_queries, cancel_query,
top_queries, vl_tenant_* /metrics series), query EXPLAIN with priced
physical plans and continuous cost-model error tracking (explain.py —
?explain=1/analyze, predicted_* on every query,
vl_cost_model_rel_error_* histograms), and the self-telemetry
journal: a process-wide structured event bus (events.py) whose
subscriber (journal.py) batches operational events — query
completions, admission sheds, merges/flushes, faults, slow queries —
into LogRows under the reserved system tenant (0, 0xFFFFFFFE), so the
database's own behavior is LogsQL-queryable with the engine it ships.

The tracing design constraint is that the DISABLED path must cost
nothing measurable on the hot query path: `tracing.current_span()`
returns a shared no-op singleton whenever no trace is active, and every
span operation on it (span()/set()/add()) is a constant-time no-op with
no allocation — asserted by tests/test_obs.py.  Real spans only exist
inside a `tracing.activate(root)` dynamic extent.  A served select
request enters it where it arrives (server/app.py, before the admission
gate) when it carries `?trace=1` (or the slow-query log is armed): the
root is `request`, with the wall clock beside its start
(`tracing.request_root`), and `admission_wait` (sched/admission.py),
`parse` and the handler's `query` span (`tracing.make_child`,
server/vlselect.py) hang beneath it, down to each dispatch's `submit` ->
`args` + `launch` (tpu/fused.py), so one tree on one clock runs from the
socket to the device and lays over a device profile.

What no request can ask for is always on: the stall watch
(stallwatch.py) is one heartbeat thread and a generation-2 `gc` hook,
started with the server, that write ONE line per stall through the slow
log's sink and the event bus (`vl_process_stalls_total`,
`vl_gc_pause_seconds_total`); it does no per-request work.
"""
