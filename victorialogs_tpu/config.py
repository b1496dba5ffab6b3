"""Central registry of VL_* environment knobs and vl_* metric names.

Every ``VL_*`` environment variable the process reads and every
``vl_*`` metric name it rolls is DECLARED here, once, with its default,
type and documentation.  Two consumers depend on that single source of
truth:

- the vlint ``env-registry`` / ``metric-registry`` checkers
  (tools/vlint/registry.py) flag raw ``os.environ`` reads and
  undeclared / double-rolled metric names anywhere else in the tree,
  so a new knob or counter cannot ship without its declaration;
- ``render_env_table()`` generates the README environment-variable
  table, and ``make lint`` fails when the committed README drifts from
  the registry — documentation rot became a lint failure, not a
  review catch.

This module must stay import-light (stdlib ``os`` only): the linter
loads it standalone via importlib, outside the package, and the
earliest package imports (native/, utils/) read it at import time.

Reading knobs
-------------
All readers re-read ``os.environ`` on every call (kill-switches are
flipped per-test via monkeypatch); nothing here caches values:

- ``env(name[, default])``      -> raw string (declared default when unset)
- ``env_int(name[, default])``  -> int; unset/empty/invalid -> default
- ``env_float(name[, default])``-> float; same fallback rule
- ``env_flag(name)``            -> bool, the `!= "0"` idiom (on unless "0")
- ``env_bool(name)``            -> bool, explicit truthy set (1/true/yes/on)

Reading an undeclared name raises ``UndeclaredEnvVar`` — the runtime
twin of the static checker.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

_U = object()          # "no per-call default supplied" sentinel


class UndeclaredEnvVar(KeyError):
    """An env read bypassed the declarations below — declare it first."""


class UndeclaredMetric(KeyError):
    """A metric name was used without a declaration below."""


# ---------------------------------------------------------------- env vars

@dataclass(frozen=True)
class EnvVar:
    name: str
    default: str | None     # parsing default; None = unset/off
    kind: str               # "flag" | "bool" | "int" | "float" | "str"
    doc: str                # one line, README table cell
    display: str            # README "default" cell

    def table_row(self) -> str:
        return f"| `{self.name}` | {self.display} | {self.doc} |"


_ENV: dict[str, EnvVar] = {}

_ENV_KINDS = ("flag", "bool", "int", "float", "str")


def declare_env(name: str, default: str | None, kind: str, doc: str,
                display: str | None = None) -> None:
    if name in _ENV:
        raise ValueError(f"duplicate env declaration: {name}")
    if kind not in _ENV_KINDS:
        raise ValueError(f"bad env kind {kind!r} for {name}")
    if not doc:
        raise ValueError(f"env declaration {name} needs a doc string")
    if display is None:
        display = "unset" if default is None else f"`{default}`"
    _ENV[name] = EnvVar(name, default, kind, doc, display)


def env_vars() -> dict[str, EnvVar]:
    return dict(_ENV)


def _decl(name: str) -> EnvVar:
    try:
        return _ENV[name]
    except KeyError:
        raise UndeclaredEnvVar(
            f"{name} is not declared in victorialogs_tpu/config.py — "
            f"declare_env() it (name, default, kind, doc) before reading"
        ) from None


def env(name: str, default=_U) -> str | None:
    """Raw string value (the declared default when unset)."""
    d = _decl(name)
    v = os.environ.get(name)
    if v is None:
        return d.default if default is _U else default
    return v


def env_int(name: str, default=_U) -> int | None:
    """int value; unset, empty or unparseable falls back to the default
    (the declared one unless a call-site default is given — dynamic
    defaults like VL_QUEUE_MAX's 2x max live at the call site)."""
    d = _decl(name)
    fb = d.default if default is _U else default
    v = os.environ.get(name)
    if v is not None and v != "":
        try:
            return int(v)
        except ValueError:
            pass
    if fb is None:
        return None
    return int(fb)


def env_float(name: str, default=_U) -> float | None:
    d = _decl(name)
    fb = d.default if default is _U else default
    v = os.environ.get(name)
    if v is not None and v != "":
        try:
            return float(v)
        except ValueError:
            pass
    if fb is None:
        return None
    return float(fb)


def env_flag(name: str) -> bool:
    """The kill-switch idiom: on unless the value is exactly "0"."""
    d = _decl(name)
    return os.environ.get(name, d.default or "") != "0"


_TRUTHY = ("1", "true", "yes", "on")


def env_bool(name: str) -> bool:
    """Explicit opt-in idiom: true only for 1/true/yes/on."""
    d = _decl(name)
    return (os.environ.get(name) or d.default or "").lower() in _TRUTHY


# Declarations, in README-table order (device pipeline -> emit -> wire
# -> filters -> observability -> scheduling -> fault tolerance -> misc).

declare_env(
    "VL_INFLIGHT", "4", "str",
    "async device pipeline window: how many parts/packs keep dispatches "
    "outstanding; `1` = serial submit-then-harvest walk; `auto` = derive "
    "depth from the measured dispatch RTT and the per-unit emit EWMA "
    "(ceil(rtt/emit), clamped to [2, 16]; chosen depth exported as "
    "`vl_tpu_inflight_auto_depth`) (`tpu/pipeline.py`)")
declare_env(
    "VL_NATIVE_EMIT", "1", "flag",
    "`0` = kill-switch for the columnar NDJSON serializer: query "
    "responses fall back to the per-row dict + `json.dumps` path "
    "(bit-identical bytes — `engine/emit.py`, `tests/test_emit.py`)")
declare_env(
    "VL_WIRE_TYPED", "1", "flag",
    "`0` = kill-switch for the typed columnar cluster wire format: this "
    "process neither requests nor serves typed frames, so every "
    "internal-select hop uses the legacy list-of-strings JSON frames "
    "(bit-identical results — `server/cluster.py`, `tests/test_wire.py`)")
declare_env(
    "VL_WIRE_TYPED_INSERT", "1", "flag",
    "`0` = kill-switch for the typed ingest wire format \"i1\": this "
    "process neither encodes nor accepts typed insert frames — "
    "frontends/vlagent ship legacy zstd'd JSON lines and storage nodes "
    "reject i1 bodies with a 400 so senders pin them to legacy "
    "(`server/wire_ingest.py`, `tests/test_wire_ingest.py`)")
declare_env(
    "VL_PACK_PARTS", "8", "int",
    "max small parts folded into one fused super-dispatch; `1` = "
    "packing off (kill-switch)")
declare_env(
    "VL_PACK_TOPK_K", "1024", "int",
    "largest `sort ... limit` k eligible for packed sort-topk "
    "super-dispatches (the packed dispatch k-selects once per member, "
    "so cost grows with pack_size * k); `0` = sort-topk packing off "
    "(`tpu/pipeline.py`)")
declare_env(
    "VL_PACK_MAX_ROWS", None, "int",
    "parts above this many rows never pack; default scales with the "
    "measured dispatch RTT (floor 16k rows, cap 1M — flush-sized parts "
    "always pack, big parts only when the RTT dwarfs their scan)",
    display="adaptive")
declare_env(
    "VL_DEVICE_BLOOM", "1", "flag",
    "`0` = bloom keep-masks stay host-side instead of probing "
    "in-dispatch")
declare_env(
    "VL_PALLAS", None, "str",
    "`1` = Pallas kernels (gated until profiled on hardware)",
    display="off")
declare_env(
    "VL_COST_FORCE", None, "str",
    "`device`/`host` pins the per-part cost-gate decision",
    display="unset")
declare_env(
    "VL_COST_RTT_MS", None, "float",
    "preseed the cost-model dispatch-RTT calibration (milliseconds)",
    display="measured")
declare_env(
    "VL_COST_DEV_GBPS", None, "float",
    "preseed the cost-model device-throughput calibration (GB/s)",
    display="measured")
declare_env(
    "VL_COST_HOST_MROWS", None, "float",
    "preseed the cost-model host-scan calibration (Mrows/s)",
    display="measured")
declare_env(
    "VL_BLOOM_PLANE_MAX_BYTES", str(256 << 20), "int",
    "per-plane host bloom-plane size cap (`storage/filterbank.py`); "
    "larger planes decline to the per-block path",
    display="256 MiB")
declare_env(
    "VL_BLOOM_BANK_MAX_BYTES", str(1 << 30), "int",
    "global budget for ALL host-resident bloom planes "
    "(`storage/filterbank.py`); loaded v2 filter-index sidecars charge "
    "the same bank, released by weakref finalize at part GC",
    display="1 GiB")
declare_env(
    "VL_FILTER_INDEX", None, "str",
    "`v1` = pin the classic blooms.bin path: sealed parts neither build "
    "nor read `filterindex.bin` sidecars (split-block planes / xor "
    "aggregates / maplets off — `storage/filterindex/`, bit-identical "
    "results)",
    display="`v2`")
declare_env(
    "VL_FILTER_INDEX_REBUILD", "0", "flag",
    "`1` = rebuild missing `filterindex.bin` sidecars for pre-v2 "
    "sealed parts IN PLACE at part-open time (from blooms.bin + "
    "columns, the same deterministic tokenizer as the seal-time "
    "build), so long-lived deployments get maplet/xor/split-block "
    "pruning without waiting for a merge; journaled as "
    "`filter_index_built` with `rebuilt=true` "
    "(`storage/filterindex/index.py`)")
declare_env(
    "VL_QUERY_PRICING", "1", "flag",
    "`0` = kill the continuous plan-time pricing pass: queries no "
    "longer compute `predicted_*` costs, `query_done` events lose the "
    "predicted-vs-actual pair and the `vl_cost_model_rel_error_*` "
    "histograms stop feeding (`obs/explain.py`; the `?explain=` "
    "endpoints stay available)")
declare_env(
    "VL_SLOW_QUERY_MS", None, "int",
    "slow-query log threshold: queries over it emit one structured "
    "JSON line (stderr) with the flattened per-stage trace summary "
    "(`victorialogs_tpu/obs/slowlog.py`)",
    display="off")
declare_env(
    "VL_JOURNAL", "1", "flag",
    "`0` = kill the self-telemetry journal: no event-bus subscriber, "
    "`events.emit()` structurally free (`obs/events.py`, "
    "`obs/journal.py`)")
declare_env(
    "VL_JOURNAL_FLUSH_MS", "500", "int",
    "journal flush cadence: how often queued events batch into "
    "`LogRows` and ingest under the system tenant")
declare_env(
    "VL_JOURNAL_MAX_QUEUE", "4096", "int",
    "journal queue bound; events past it drop (counted exact in "
    "`vl_journal_dropped_total`) — a wedged flush never blocks a query")
declare_env(
    "VL_JOURNAL_FLUSH_DEADLINE_MS", "5000", "int",
    "journal flush wall-time alarm: flushes over it count in "
    "`vl_journal_flushes_slow_total`")
declare_env(
    "VL_SCHED", "1", "flag",
    "`0` = disable the shared dispatch scheduler (every query burns its "
    "own window unmanaged — the pre-scheduler behavior, used as the "
    "bench baseline)")
declare_env(
    "VL_INFLIGHT_GLOBAL", "8", "int",
    "shared device-dispatch budget: max dispatch slots outstanding "
    "process-wide across ALL queries; per-query windows lease from it "
    "with weighted fair queuing (`victorialogs_tpu/sched/scheduler.py`)")
declare_env(
    "VL_MAX_CONCURRENT", "8", "int",
    "admission control: max queries executing per pool (select / "
    "cluster-internal) when the server ctor doesn't pin it "
    "(`sched/admission.py`)")
declare_env(
    "VL_TENANT_MAX_CONCURRENT", "0", "int",
    "per-tenant concurrency cap; over-limit arrivals shed 429 "
    "`reason=tenant_limit` (runtime per-tenant override via "
    "`POST /select/logsql/sched_config`)",
    display="= max")
declare_env(
    "VL_TENANT_MAX_BYTES", "0", "int",
    "per-tenant estimated bytes-in-flight budget (per-endpoint "
    "bytes-scanned EWMA); over-budget arrivals shed "
    "`reason=tenant_limit`",
    display="off")
declare_env(
    "VL_QUEUE_MAX", None, "int",
    "admission wait-queue bound; past it arrivals shed 429 "
    "`reason=queue_full` instead of queuing unboundedly",
    display="2×max")
declare_env(
    "VL_QUEUE_TIMEOUT_MS", "30000", "int",
    "max admission-queue wait (the old `-search.maxQueueDuration`); "
    "expiry sheds 429")
declare_env(
    "VL_TENANT_WEIGHTS", None, "str",
    "fair-share weights for the dispatch scheduler, e.g. "
    "`0:0=4,9:0=0.5` (runtime override via `sched_config`)",
    display="unset")
declare_env(
    "VL_FAULT_SUBMIT", None, "float",
    "fault injection: fail each dispatch submit with this probability "
    "(test/chaos hook; `sched.inject_fault()` is the deterministic "
    "one-shot form)",
    display="off")
declare_env(
    "VL_FAULT_NET", None, "str",
    "network fault injection: `refuse:0.2` / `5xx:1.0` fails each "
    "cluster HTTP attempt with that probability "
    "(`sched.inject_net_fault()` is the deterministic one-shot form; "
    "wire-level hang/reset/trickle modes ride the in-process "
    "`sched.FaultProxy`)",
    display="off")
declare_env(
    "VL_PARTIAL_RESULTS", "0", "bool",
    "`1` = default queries to partial-results mode: when a storage node "
    "is still down after retries, scatter-gather answers from the "
    "survivors, marked `X-VL-Partial: true` + a `partial.failed_nodes` "
    "block (per-request `?partial=1/0` overrides; default stays the "
    "reference's strict fail-the-whole-query)")
declare_env(
    "VL_NET_RETRIES", "2", "int",
    "extra attempts per idempotent select sub-query after the first "
    "(jittered exponential backoff, never past the request deadline, "
    "never after a frame was delivered; `0` disables)")
declare_env(
    "VL_NET_HEDGE_MS", None, "str",
    "straggler hedging delay: after this long without a first frame "
    "the sub-query is re-issued to the same node and the first answer "
    "wins (`auto` = p95-style EWMA of first-frame RTTs once 8 samples "
    "exist; `0` = off)",
    display="auto")
declare_env(
    "VL_BREAKER_FAILURES", "2", "int",
    "consecutive transport/5xx failures that open a node's circuit "
    "(shared select+insert breaker, `server/netrobust.py`)")
declare_env(
    "VL_BREAKER_OPEN_S", "10", "float",
    "seconds an open circuit refuses requests before half-opening a "
    "single probe (ingest 429s instead park only the node's INSERT "
    "path for their `Retry-After`, uncounted — selects keep flowing)")
declare_env(
    "VL_INSERT_SPOOL_MAX_BYTES", str(256 << 20), "int",
    "per-node durable ingest spool bound on cluster frontends: batches "
    "that exhaust every healthy node spool to disk and replay on "
    "recovery; past the bound they drop loudly (counted + journaled; "
    "`0` disables spooling)",
    display="256 MiB")
declare_env(
    "VL_CLUSTER_STATS_MS", "1000", "int",
    "cluster frontends poll every storage node's `GET /internal/usage` "
    "on this cadence, rolling per-tenant usage up into "
    "`vl_cluster_tenant_*_total` and node liveness into "
    "`vl_cluster_node_up{node=}` on the frontend /metrics plus "
    "`GET /select/logsql/tenants` (`obs/clusterstats.py`; `0` disables "
    "the poll loop)")
declare_env(
    "VL_INGEST_TRACE", "0", "bool",
    "`1` = per-batch ingest span trees: every accepted batch grows a "
    "real `obs/tracing.py` tree (one child span per hop: parse/encode/"
    "shard/ship/spool/replay/decode/store) surfaced on "
    "`GET /insert/status` and in `ingest_batch` journal events; off, "
    "only the always-on per-(tenant, hop) latency aggregates roll "
    "(`obs/ingestledger.py`; bench-asserted <=1.10x when off)")
declare_env(
    "VL_INGEST_BATCHES_MAX", "512", "int",
    "max in-flight ingest batch records the row-conservation ledger "
    "tracks; past it the oldest records evict to the completed ring "
    "(counters are unaffected — only per-batch detail is bounded)")
declare_env(
    "VL_MEMORY_ALLOWED_BYTES", None, "int",
    "query memory budget", display="auto")
declare_env(
    "VL_INGEST_THREADS", "1", "int",
    "ingest shard parallelism: bodies over 8 MB split at newline "
    "boundaries across this many workers, each scanning/assembling its "
    "own columnar batch and handing it to the sink on the worker "
    "(`server/vlinsert.py`)", display="auto")
declare_env(
    "VL_BLOCK_BUILD_THREADS", None, "int",
    "block-build shard parallelism on the storage flush path: each "
    "size-bounded block chunk's values-encode + token blooms builds "
    "on a per-DataDB thread pool, and part seals compress columns / "
    "build filter-index sidecar columns on the same pool "
    "(`storage/block_build.py`; flushed parts are byte-identical to "
    "the serial build; `0`/`1` = serial; default min(cores, 8))",
    display="auto")
declare_env(
    "VL_ARENA_BUILD", "1", "flag",
    "`1` = columnar values-encode: ASCII i1 wire columns feed block "
    "build as offset slices over the decoded arena, with vectorized "
    "const/dict/int/float detection — no per-row Python strings "
    "between `decode_frame` and the encoded block; `0` = always "
    "materialize per-row strings first (same bytes either way)")
declare_env(
    "VL_INSERT_PIPELINE", "0", "int",
    "storage-node `/internal/insert` hop overlap: depth of the "
    "bounded decode->store hand-off queue, letting frame N+1 decode "
    "while frame N builds blocks (rows count as ledger in-flight "
    "until stored; `0` = synchronous store on the request thread)")
declare_env(
    "VL_NO_NATIVE", None, "str",
    "`1` = skip the C++ host core, numpy fallbacks", display="off")
declare_env(
    "VL_RESULT_CACHE", "1", "bool",
    "per-part result cache (`engine/standing/resultcache.py`): "
    "repeated queries replay sealed parts' cached stats partials / "
    "filter bitmaps and re-dispatch only the unsealed head; `0` "
    "disables (every part recomputes)")
declare_env(
    "VL_RESULT_CACHE_MAX_BYTES", str(64 << 20), "int",
    "byte budget for the per-part result cache; past it LRU entries "
    "evict (counted + journaled as `result_cache_evict`), and a "
    "part's GC releases its entries' bytes like the bloom bank",
    display="64 MiB")
declare_env(
    "VL_STANDING", "1", "bool",
    "standing-query registration (`POST /select/logsql/"
    "standing_query`): one resident evaluation per distinct query "
    "fingerprint, re-run on storage flush/merge and fanned out to all "
    "subscribers; `0` refuses registrations (503)")
declare_env(
    "VL_STANDING_MAX", "64", "int",
    "max standing-query entries per node; past it registrations are "
    "refused with 429")
declare_env(
    "VL_STANDING_DEBOUNCE_MS", "100", "int",
    "coalescing window for standing re-evaluation: flush/merge bursts "
    "inside it trigger ONE re-run per registered query")


_TABLE_HEADER = ("| Variable | Default | Meaning |",
                 "|---|---|---|")


def render_env_table() -> str:
    """The README environment-variable table, generated from the
    declarations above (one row per variable, declaration order).
    ``make lint`` fails when the committed README section differs."""
    rows = list(_TABLE_HEADER)
    rows.extend(v.table_row() for v in _ENV.values())
    return "\n".join(rows) + "\n"


# ---------------------------------------------------------------- metrics

@dataclass(frozen=True)
class Metric:
    name: str
    kind: str               # "counter" | "gauge" | "histogram"
    help: str
    single_roll: bool       # True: exactly ONE static roll site allowed


_METRICS: dict[str, Metric] = {}

_METRIC_KINDS = ("counter", "gauge", "histogram")

# name spaces minted dynamically (runner stats keys render as
# vl_tpu_<key>); the static metric-registry checker cannot resolve
# them, so the vlsan runtime sweep guards them (non-negative) instead
DYNAMIC_METRIC_PREFIXES = ("vl_tpu_",)


def declare_metric(name: str, kind: str, help: str,
                   single_roll: bool = False) -> None:
    if name in _METRICS:
        raise ValueError(f"duplicate metric declaration: {name}")
    if kind not in _METRIC_KINDS:
        raise ValueError(f"bad metric kind {kind!r} for {name}")
    if not help:
        raise ValueError(f"metric declaration {name} needs help text")
    # server/app.py Metrics.render infers counter-vs-gauge from the
    # _total suffix; a declaration disagreeing with the renderer would
    # lie on /metrics
    if kind == "counter" and not name.endswith("_total"):
        raise ValueError(f"counter {name} must end in _total")
    if kind == "gauge" and name.endswith("_total"):
        raise ValueError(f"gauge {name} must not end in _total")
    _METRICS[name] = Metric(name, kind, help, single_roll)


def metric_decls() -> dict[str, Metric]:
    return dict(_METRICS)


def metric_declared(name: str) -> bool:
    if name in _METRICS:
        return True
    return any(name.startswith(p) for p in DYNAMIC_METRIC_PREFIXES)


# -- HTTP layer (server/app.py) --
declare_metric("vl_http_errors_total", "counter",
               "HTTP requests answered with a 5xx/unhandled error")
declare_metric("vl_http_requests_total", "counter",
               "HTTP requests by path", single_roll=True)
declare_metric("vl_http_request_duration_ms_total", "counter",
               "cumulative request wall time by path, milliseconds",
               single_roll=True)
declare_metric("vl_http_request_queue_timeouts_total", "counter",
               "requests shed after exceeding the admission queue wait",
               single_roll=True)
declare_metric("vl_queries_cancelled_total", "counter",
               "queries terminated via POST cancel_query",
               single_roll=True)
declare_metric("vl_rows_ingested_total", "counter",
               "rows accepted into storage by ingest protocol")
declare_metric("vl_ingest_bytes_total", "counter",
               "decompressed ingest payload bytes by protocol")
declare_metric("vl_ingest_parse_failures_total", "counter",
               "ingest payloads rejected as unparseable, by protocol")
declare_metric("vl_build_info", "gauge",
               "constant 1, labeled with version/app", single_roll=True)
declare_metric("vl_uptime_seconds", "gauge",
               "seconds since server start", single_roll=True)
declare_metric("vl_invalid_metric_name", "gauge",
               "defensive bucket for malformed stored sample names",
               single_roll=True)

# -- storage gauges (server/app.py render <- storage.update_stats) --
declare_metric("vl_partitions", "gauge", "live partitions")
declare_metric("vl_streams_created_total", "counter",
               "log streams ever registered")
declare_metric("vl_storage_rows", "gauge",
               "stored rows by part tier (inmemory/file/small/big)")
declare_metric("vl_storage_parts", "gauge",
               "live parts by tier")
declare_metric("vl_data_size_bytes", "gauge",
               "compressed on-disk size")
declare_metric("vl_uncompressed_data_size_bytes", "gauge",
               "uncompressed logical size")
declare_metric("vl_rows_dropped_total", "counter",
               "ingested rows dropped by retention (too_old/too_new)")
declare_metric("vl_storage_is_read_only", "gauge",
               "1 when the storage rejects writes (disk budget)")
declare_metric("vl_storage_pending_merges", "gauge",
               "queued tier compactions")
declare_metric("vl_storage_merges_total", "counter",
               "part merges completed")
declare_metric("vl_storage_flush_age_seconds", "gauge",
               "staleness of the oldest in-RAM rows")
declare_metric("vl_storage_merge_duration_seconds", "histogram",
               "wall time of one part merge")

# -- filter bank / device budget --
declare_metric("vl_tpu_bloom_bank_used_bytes", "gauge",
               "host bloom-plane budget occupancy", single_roll=True)
declare_metric("vl_tpu_bloom_bank_max_bytes", "gauge",
               "host bloom-plane budget bound", single_roll=True)
declare_metric("vl_filter_index_build_seconds", "histogram",
               "seal-time filterindex.bin sidecar build wall time")

# -- active-query registry / per-tenant accounting (obs/activity.py) --
declare_metric("vl_active_queries", "gauge",
               "live query executions (total + per endpoint)")
declare_metric("vl_tenant_select_queries_total", "counter",
               "completed select queries per tenant", single_roll=True)
declare_metric("vl_tenant_select_seconds_total", "counter",
               "select execution seconds per tenant", single_roll=True)
declare_metric("vl_tenant_bytes_scanned_total", "counter",
               "bytes scanned per tenant", single_roll=True)
declare_metric("vl_tenant_rows_ingested_total", "counter",
               "rows ingested per tenant", single_roll=True)
declare_metric("vl_tenant_ingest_bytes_total", "counter",
               "decompressed ingest bytes per tenant", single_roll=True)

# -- admission + dispatch scheduler (victorialogs_tpu/sched) --
declare_metric("vl_select_rejected_total", "counter",
               "admission sheds by pool/reason/tenant", single_roll=True)
declare_metric("vl_select_admitted_total", "counter",
               "admission grants by pool/tenant", single_roll=True)
declare_metric("vl_sched_queue_depth", "gauge",
               "admission queue depth per pool", single_roll=True)
declare_metric("vl_sched_admission_active", "gauge",
               "queries executing per admission pool", single_roll=True)
declare_metric("vl_sched_dispatch_budget", "gauge",
               "VL_INFLIGHT_GLOBAL shared dispatch budget",
               single_roll=True)
declare_metric("vl_sched_dispatch_in_flight", "gauge",
               "dispatch slots currently leased", single_roll=True)
declare_metric("vl_sched_dispatch_grants_total", "counter",
               "slot leases ever granted", single_roll=True)
declare_metric("vl_sched_dispatch_contended_total", "counter",
               "non-blocking lease attempts that found no free slot",
               single_roll=True)

# -- event bus + journal (obs/events.py, obs/journal.py) --
declare_metric("vl_journal_events_total", "counter",
               "events delivered to at least one subscriber",
               single_roll=True)
declare_metric("vl_journal_suppressed_total", "counter",
               "events suppressed by the recursion guard",
               single_roll=True)
declare_metric("vl_journal_subscriber_errors_total", "counter",
               "subscriber callbacks that raised", single_roll=True)
declare_metric("vl_trace_children_dropped_total", "counter",
               "span children dropped at MAX_CHILDREN")
declare_metric("vl_slowlog_emit_failures_total", "counter",
               "slow-query log lines whose sink write failed",
               single_roll=True)
declare_metric("vl_process_stalls_total", "counter",
               "stalls the always-on heartbeat saw: a beat over 250 ms "
               "late, or a query over 1 s old while none finished "
               "(obs/stallwatch.py)")
declare_metric("vl_process_stall_seconds_total", "counter",
               "seconds those stalls lasted")
declare_metric("vl_gc_collections_total", "counter",
               "generation-2 garbage collections")
declare_metric("vl_gc_pause_seconds_total", "counter",
               "seconds spent in generation-2 garbage collections")
declare_metric("vl_gc_young_collections_total", "counter",
               "generation-0 and generation-1 garbage collections")
declare_metric("vl_gc_young_pause_seconds_total", "counter",
               "seconds spent in generation-0 and generation-1 garbage "
               "collections")
declare_metric("vl_background_thread_cpu_seconds_total", "counter",
               "CPU seconds of the threads that serve no request, by "
               "thread name with its number dropped, read at scrape "
               "(obs/stallwatch.py)")
declare_metric("vl_top_queries_evicted_total", "counter",
               "completed-query ring evictions", single_roll=True)
declare_metric("vl_journal_dropped_total", "counter",
               "journal events dropped at the bounded queue",
               single_roll=True)
declare_metric("vl_journal_rows_written_total", "counter",
               "journal rows ingested into storage", single_roll=True)
declare_metric("vl_journal_queue_depth", "gauge",
               "journal events waiting to flush", single_roll=True)
declare_metric("vl_journal_flushes_total", "counter",
               "journal flush batches written", single_roll=True)
declare_metric("vl_journal_flushes_slow_total", "counter",
               "journal flushes over the cadence deadline",
               single_roll=True)
declare_metric("vl_journal_flush_errors_total", "counter",
               "journal flush attempts that raised", single_roll=True)

# -- cluster wire protocol (server/cluster.py) --
declare_metric("vl_wire_frames_total", "counter",
               "internal-select frames by dir (tx/rx) and format "
               "(typed/json)", single_roll=True)
declare_metric("vl_wire_bytes_total", "counter",
               "internal-select payload bytes by dir and format",
               single_roll=True)
declare_metric("vl_wire_fallbacks_total", "counter",
               "typed-requesting frontends answered with JSON frames",
               single_roll=True)

# -- typed ingest wire (server/wire_ingest.py) --
declare_metric("vl_ingest_wire_frames_total", "counter",
               "insert wire bodies by dir (tx/rx) and format "
               "(typed/json)", single_roll=True)
declare_metric("vl_ingest_wire_bytes_total", "counter",
               "insert wire body bytes (compressed) by dir and format",
               single_roll=True)
declare_metric("vl_ingest_wire_fallbacks_total", "counter",
               "insert hops pinned from i1 back to legacy JSON lines",
               single_roll=True)

# -- cluster fault policy (server/netrobust.py) --
declare_metric("vl_node_health", "gauge",
               "per-node breaker state: 1 closed, 0.5 half-open, 0 open",
               single_roll=True)
declare_metric("vl_node_breaker_opens_total", "counter",
               "circuit-breaker open transitions", single_roll=True)
declare_metric("vl_net_retries_total", "counter",
               "cluster sub-query retry attempts", single_roll=True)
declare_metric("vl_net_hedges_total", "counter",
               "hedged sub-queries by outcome (won=)", single_roll=True)
declare_metric("vl_partial_results_total", "counter",
               "queries answered partial (X-VL-Partial)",
               single_roll=True)
declare_metric("vl_insert_spooled_blocks_total", "counter",
               "ingest batches spooled to disk during node outages",
               single_roll=True)
declare_metric("vl_insert_replayed_blocks_total", "counter",
               "spooled ingest batches replayed on recovery",
               single_roll=True)
declare_metric("vl_insert_spool_overflow_total", "counter",
               "ingest batches dropped at the spool byte bound",
               single_roll=True)
declare_metric("vl_insert_spool_bytes", "gauge",
               "bytes currently spooled per node")
declare_metric("vl_insert_spool_entries", "gauge",
               "blocks currently spooled per node")
declare_metric("vl_insert_spool_oldest_age_seconds", "gauge",
               "age of the oldest unreplayed spool block per node")

# -- ingest observability plane (obs/ingestledger.py) --
declare_metric("vl_ingest_ledger_rows_total", "counter",
               "row-conservation ledger counters by tenant and state "
               "(accepted/received/forwarded/spooled/replayed/stored)",
               single_roll=True)
declare_metric("vl_ingest_ledger_dropped_total", "counter",
               "rows terminally dropped by tenant and reason "
               "(the ledger's only loss exit)", single_roll=True)
declare_metric("vl_ingest_ledger_in_flight", "gauge",
               "derived in-flight rows per tenant: accepted+received "
               "- stored - forwarded - dropped", single_roll=True)
declare_metric("vl_ingest_batches_in_flight", "gauge",
               "ingest batches currently tracked by the ledger",
               single_roll=True)
declare_metric("vl_ingest_watermark_seconds", "gauge",
               "per-tenant freshness lag: seconds since the max stored "
               "row timestamp", single_roll=True)

# -- /internal/insert decode/build overlap (server/cluster.py) --
declare_metric("vl_insert_pipeline_batches_total", "counter",
               "typed insert batches handed to the decode/build overlap "
               "pipeline (VL_INSERT_PIPELINE > 0)", single_roll=True)
declare_metric("vl_insert_pipeline_rows_stored_total", "counter",
               "rows stored by the insert pipeline drainer",
               single_roll=True)
declare_metric("vl_insert_pipeline_rows_dropped_total", "counter",
               "rows dropped by the insert pipeline drainer on store "
               "failure (also rolled into the ledger as "
               "pipeline_store_error)", single_roll=True)
declare_metric("vl_insert_pipeline_queue_depth", "gauge",
               "batches currently queued behind the insert pipeline "
               "drainer", single_roll=True)

# -- cluster observability plane (obs/clusterstats.py, federated
#    registry + cancel propagation in server/cluster.py + app.py) --
declare_metric("vl_cluster_tenant_select_seconds_total", "counter",
               "select execution seconds per tenant summed across all "
               "storage nodes (frontend rollup)", single_roll=True)
declare_metric("vl_cluster_tenant_bytes_scanned_total", "counter",
               "bytes scanned per tenant summed across all storage "
               "nodes (frontend rollup)", single_roll=True)
declare_metric("vl_cluster_tenant_rows_ingested_total", "counter",
               "rows ingested per tenant summed across all storage "
               "nodes (frontend rollup)", single_roll=True)
declare_metric("vl_cluster_node_up", "gauge",
               "1 when the node answered the last usage poll, else 0",
               single_roll=True)
declare_metric("vl_cluster_stats_age_seconds", "gauge",
               "staleness of a node's last successful usage poll",
               single_roll=True)
declare_metric("vl_cluster_ingest_in_flight", "gauge",
               "worst-case (max across nodes) in-flight ingest rows "
               "per tenant from the ledger rollup", single_roll=True)
declare_metric("vl_cluster_ingest_dropped", "gauge",
               "worst-case (max across nodes) dropped ingest rows per "
               "tenant from the ledger rollup", single_roll=True)
declare_metric("vl_queries_cancel_propagated_total", "counter",
               "sub-queries cancelled via propagated cluster cancel "
               "(POST /internal/select/cancel)", single_roll=True)

# -- standing queries / per-part result cache (engine/standing/) --
declare_metric("vl_result_cache_hits_total", "counter",
               "per-part result cache hits (parts replayed without a "
               "dispatch)")
declare_metric("vl_result_cache_misses_total", "counter",
               "per-part result cache misses (parts that recomputed)")
declare_metric("vl_result_cache_evictions_total", "counter",
               "entries evicted at the VL_RESULT_CACHE_MAX_BYTES "
               "budget (LRU)")
declare_metric("vl_result_cache_stores_total", "counter",
               "entries stored at harvest/absorb")
declare_metric("vl_result_cache_bytes", "gauge",
               "bytes resident in the per-part result cache")
declare_metric("vl_result_cache_max_bytes", "gauge",
               "VL_RESULT_CACHE_MAX_BYTES budget")
declare_metric("vl_result_cache_entries", "gauge",
               "live (fingerprint, part uid) entries")
declare_metric("vl_standing_queries", "gauge",
               "registered standing-query fingerprints on this node")
declare_metric("vl_standing_subscribers", "gauge",
               "subscriber streams attached across all standing "
               "queries")
declare_metric("vl_standing_reevals_total", "counter",
               "standing-query re-evaluations (flush/merge-triggered "
               "+ registration seeds)")
declare_metric("vl_standing_pushes_dropped_total", "counter",
               "payload pushes dropped at a stalled subscriber's "
               "queue bound")

# -- histograms (obs/hist.py) --
declare_metric("vl_query_duration_seconds", "histogram",
               "end-to-end /select query execution time")
declare_metric("vl_tpu_dispatch_rtt_seconds", "histogram",
               "device dispatch round-trip time")
declare_metric("vl_tpu_host_sync_wait_seconds", "histogram",
               "host-side wait for device results")
declare_metric("vl_tpu_emit_seconds", "histogram",
               "harvest emit phase wall time")
declare_metric("vl_tpu_pack_size_parts", "histogram",
               "parts folded per packed super-dispatch")
declare_metric("vl_tpu_bloom_prune_ratio", "histogram",
               "fraction of blocks killed by bloom pruning")
declare_metric("vl_sched_queue_wait_seconds", "histogram",
               "admission queue wait")
declare_metric("vl_sched_slot_wait_seconds", "histogram",
               "dispatch-slot lease wait")
declare_metric("vl_net_first_frame_seconds", "histogram",
               "cluster sub-query time to first frame")
declare_metric("vl_cost_model_rel_error_duration", "histogram",
               "cost-model relative error: predicted vs actual "
               "duration")
declare_metric("vl_cost_model_rel_error_bytes", "histogram",
               "cost-model relative error: predicted vs actual bytes")
declare_metric("vl_cost_model_rel_error_dispatches", "histogram",
               "cost-model relative error: predicted vs actual "
               "dispatch count")
declare_metric("vl_ingest_freshness_seconds", "histogram",
               "in-memory residency of rows at flush: flush time minus "
               "the flushed parts' oldest creation time")
declare_metric("vl_ingest_to_queryable_seconds", "histogram",
               "accept wall clock to rows queryable (storage "
               "must_add return), observed per batch")
