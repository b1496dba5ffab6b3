"""Active-query registry + per-tenant resource accounting.

Every query execution registers a QueryActivity record for its whole
lifetime (HTTP query/hits/facets/stats/tail, cluster internal-select,
engine-level run_query_collect), carrying the query id, tenant,
endpoint, LogsQL text, start time, current phase and live progress
counters (parts pruned/scanned vs total, blocks killed by bloom, bytes
staged/scanned, dispatches in flight, rows emitted).  The record is the
signal layer the reference serves via /select/logsql/active_queries
(app/vlselect/main.go:240-247) and the admission-control input a
concurrent-query scheduler needs (ROADMAP).

Locking discipline mirrors obs/tracing.py:

- ambient propagation via a contextvar; when no activity is registered
  `current_activity()` returns a shared no-op singleton whose every
  method is a constant-time no-op — instrumented hot paths cost nothing
  for untracked work (engine internals, tests without the registry);
- progress updates are amortized adds onto the record under a
  per-record lock (per dispatch unit / per part / per block — never per
  row), so the hot path gains no new sync points beyond what tracing
  already pays;
- read-side snapshots take the registry lock, then each record's lock —
  one fixed order, no lock cycles (`VLINT_LOCK_ORDER=1` clean).

The API is context-manager-only: `with activity.track(...) as act:` is
what guarantees every registered record deregisters on every exit path
(limit/deadline/cancel/abandon unwinds included) — enforced by the
vlint `accounting-discipline` checker exactly like span-discipline.

Cancellation: `cancel(qid)` (the /select/logsql/cancel_query endpoint)
flips the record's cancel flag; the query's processor-chain head reads
it via is_done(), so the async device pipeline drains its in-flight
window without downstream writes (tpu/pipeline.py PR 3 semantics) and
the serial walk stops at its next block.  Client-disconnect
abandonment rides the same flag via `QueryActivity.abandon()`.

Completed queries land in a 256-entry ring buffer powering
/select/logsql/top_queries (heavy hitters by duration or bytes
scanned).  Per-tenant totals (select seconds, bytes scanned, rows/bytes
ingested, parse failures) accumulate forever and are rendered into
/metrics by server/app.py Metrics.render via metrics_samples().
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
from collections import deque

from . import events, hist

_current: contextvars.ContextVar = contextvars.ContextVar(
    "vl_query_activity", default=None)

PHASES = ("queued", "plan", "prune", "scan", "harvest", "emit")

_COMPLETED_MAX = 256

# Process-unique origin token for CLUSTER-wide query identity: local
# qids are a plain per-process counter ("1", "2", ...), so two
# frontends mint colliding qids.  global_qid() prefixes the origin,
# and that spelling is what frontends propagate as `parent_qid` on
# every /internal/select hop — storage-node records tagged with it are
# attributable to exactly one frontend query, cluster-wide.
_ORIGIN = os.urandom(4).hex()


def global_qid(qid) -> str:
    """Cluster-unique spelling of one of THIS process's qids (the
    `parent_qid` value shipped with internal sub-requests and matched
    by the federated active_queries merge)."""
    return f"{_ORIGIN}:{qid}"


def tenant_str(tenant) -> str:
    """Canonical 'account:project' label value for any tenant spelling
    (TenantID, list of tenants, pre-formatted string, None)."""
    if tenant is None:
        return "0:0"
    if isinstance(tenant, str):
        return tenant
    if isinstance(tenant, (list, tuple)):
        return tenant_str(tenant[0]) if tenant else "0:0"
    acc = getattr(tenant, "account_id", None)
    if acc is not None:
        return f"{acc}:{getattr(tenant, 'project_id', 0)}"
    return str(tenant)


class QueryActivity:
    """One live query's registry record.  Construct only via
    activity.track() — see the module docstring (vlint:
    accounting-discipline)."""

    __slots__ = ("qid", "tenant", "endpoint", "query", "start_unix",
                 "start_mono", "exec_mono", "phase", "abandoned", "_mu",
                 "_c", "_cancel", "_phase_t0", "parent_qid")

    enabled = True

    def __init__(self, qid: str, endpoint: str, query: str, tenant: str,
                 parent_qid: str = ""):
        self.qid = qid
        self.endpoint = endpoint
        self.query = query
        self.tenant = tenant
        # the propagated cluster identity: the frontend query this
        # record is a sub-query of (global_qid spelling), or ""
        self.parent_qid = parent_qid
        # vlint: allow-wall-clock(start timestamp shown to operators is real wall time)
        self.start_unix = time.time()
        self.start_mono = time.monotonic()
        self.exec_mono: float | None = None
        self.phase = "plan"
        self.abandoned = False
        self._mu = threading.Lock()
        self._c: dict = {}
        self._cancel = threading.Event()
        self._phase_t0 = self.start_mono

    # -- progress counters (amortized: per unit/part/block, never per row) --
    def add(self, key: str, n=1) -> None:
        with self._mu:
            self._c[key] = self._c.get(key, 0) + n

    def set(self, key: str, value) -> None:
        with self._mu:
            self._c[key] = value

    def set_phase(self, phase: str) -> None:
        # phase timings accumulate into the progress counters
        # (phase_s_<name>) so the completion record — and its journal
        # event — shows where the query's wall time went
        now = time.monotonic()
        with self._mu:
            if phase != self.phase:
                self._fold_phase_locked(now)
                self.phase = phase

    def _fold_phase_locked(self, now: float) -> None:
        """Close the running phase's timer into the counters (caller
        holds _mu; deregistration path)."""
        key = "phase_s_" + self.phase
        self._c[key] = round(
            self._c.get(key, 0.0) + (now - self._phase_t0), 6)
        self._phase_t0 = now

    def relabel(self, endpoint: str = "", query: str = "") -> None:
        """Refine the record's labels once the handler has canonical
        values (the route-level admission layer registers with the raw
        request strings before parsing — see reuse_or_track)."""
        with self._mu:
            if endpoint:
                self.endpoint = endpoint
            if query:
                self.query = query

    def mark_exec_done(self) -> None:
        """Stamp EXECUTION completion — the last dispatch unit
        harvested and the final sink write made — separately from
        response-drain completion (the _Track exit).  The sink side of
        the ROADMAP's exec/drain split: admission's duration EWMA feeds
        on execution time only (sched/admission.py reads exec_mono), so
        a stalled streaming client no longer poisons deadline
        feasibility; query_done journals both exec_s and drain_s.
        First call wins (a tail's repeated polls keep the first)."""
        if self.exec_mono is not None:
            return
        now = time.monotonic()
        self.exec_mono = now
        with self._mu:
            self._c["exec_s"] = round(now - self.start_mono, 6)

    def counter(self, key: str):
        with self._mu:
            return self._c.get(key, 0)

    # -- cancellation --
    def cancel(self) -> None:
        self._cancel.set()

    def abandon(self) -> None:
        """The HTTP peer went away mid-stream: mark the record and trip
        the same cancel flag cancel_query uses, so the pipeline drain
        path stops the device walk instead of finishing a dead query."""
        with self._mu:
            self.abandoned = True
        self._cancel.set()

    def is_cancelled(self) -> bool:
        return self._cancel.is_set()

    def wait_cancelled(self, timeout: float) -> bool:
        """Block up to `timeout` for a cancel/abandon (poll loops like
        /tail sleep on this so cancellation wakes them immediately)."""
        return self._cancel.wait(timeout)

    # -- export --
    def snapshot(self) -> dict:
        with self._mu:
            progress = dict(self._c)
            phase = self.phase
            abandoned = self.abandoned
        out = {
            "qid": self.qid,
            "endpoint": self.endpoint,
            "tenant": self.tenant,
            "query": self.query,
            "phase": phase,
            "start_ts": self.start_unix,
            "duration_s": round(time.monotonic() - self.start_mono, 6),
            "progress": progress,
        }
        if self.parent_qid:
            out["parent_qid"] = self.parent_qid
        if self._cancel.is_set():
            out["cancel_requested"] = True
        if abandoned:
            out["abandoned"] = True
        return out


class _NoopActivity:
    """The ambient record when no query is tracked: every operation is
    a constant-time no-op (shared singleton, no allocation)."""

    __slots__ = ()

    enabled = False
    qid = ""
    tenant = "0:0"
    endpoint = ""
    query = ""
    phase = ""
    abandoned = False
    exec_mono = None
    parent_qid = ""

    def add(self, key, n=1) -> None:
        pass

    def set(self, key, value) -> None:
        pass

    def set_phase(self, phase) -> None:
        pass

    def relabel(self, endpoint="", query="") -> None:
        pass

    def mark_exec_done(self) -> None:
        pass

    def counter(self, key):
        return 0

    def cancel(self) -> None:
        pass

    def abandon(self) -> None:
        pass

    def is_cancelled(self) -> bool:
        return False

    def wait_cancelled(self, timeout: float) -> bool:
        return False

    def snapshot(self) -> dict:
        return {}


_NOOP = _NoopActivity()


def current_activity():
    """This thread's active query record, or the shared no-op singleton
    when no query is being tracked."""
    act = _current.get()
    return act if act is not None else _NOOP


# ---------------- the registry ----------------

# lock order: _reg_mu, then a record's _mu (snapshot/deregister);
# never the reverse
_reg_mu = threading.Lock()
_active: dict[str, QueryActivity] = {}
_completed: deque = deque(maxlen=_COMPLETED_MAX)
_qid_next = 0

# forever-accumulating per-tenant resource totals ("a:p" -> dict);
# the admission-control input for the scheduler PR.  Tenant ids come
# straight from client headers, so the map is hard-capped: once
# _TENANT_MAX distinct tenants exist, new ones aggregate into the
# "other" slot — a client cycling AccountID values can neither leak
# server memory nor explode /metrics label cardinality.
_TENANT_MAX = 1024
_TENANT_OVERFLOW = "other"
_tenant_totals: dict[str, dict] = {}
# per-protocol ingest parse failures ("proto" -> count)
_parse_failures: dict[str, int] = {}


def _next_qid() -> str:
    global _qid_next
    _qid_next += 1
    return str(_qid_next)


def _tenant_slot(tenant: str) -> dict:
    slot = _tenant_totals.get(tenant)
    if slot is None:
        if len(_tenant_totals) >= _TENANT_MAX and \
                tenant != _TENANT_OVERFLOW:
            return _tenant_slot(_TENANT_OVERFLOW)
        slot = _tenant_totals[tenant] = {
            "select_queries": 0, "select_seconds": 0.0,
            "bytes_scanned": 0, "rows_ingested": 0, "bytes_ingested": 0,
        }
    return slot


class _Track:
    """Dynamic extent of one tracked query: registers the record and
    sets the ambient activity on enter; deregisters, restores the
    ambient, and rolls the per-tenant accounting on EVERY exit path."""

    __slots__ = ("_endpoint", "_query", "_tenant", "_act", "_token",
                 "_parent_qid")

    def __init__(self, endpoint: str, query: str, tenant,
                 parent_qid: str = ""):
        self._endpoint = endpoint
        self._query = query
        self._tenant = tenant_str(tenant)
        self._act = None
        self._token = None
        self._parent_qid = parent_qid

    def __enter__(self) -> QueryActivity:
        with _reg_mu:
            qid = _next_qid()
            act = QueryActivity(qid, self._endpoint, self._query,
                                self._tenant,
                                parent_qid=self._parent_qid)
            _active[qid] = act
        self._act = act
        self._token = _current.set(act)
        return act

    def __exit__(self, exc_type, exc, tb) -> bool:
        act = self._act
        _current.reset(self._token)
        duration = time.monotonic() - act.start_mono
        if act.abandoned:
            status = "abandoned"
        elif act.is_cancelled():
            status = "cancelled"
        elif exc_type is not None:
            status = exc_type.__name__
        else:
            status = "ok"
        with act._mu:
            act._fold_phase_locked(time.monotonic())
            progress = dict(act._c)
        if act.exec_mono is not None:
            # exec/drain split: exec_s was stamped at the last harvest
            # (mark_exec_done); everything after is response drain —
            # the part a slow client owns, not the engine
            progress["drain_s"] = round(
                max(duration - progress.get("exec_s", 0.0), 0.0), 6)
        cost_error = _fold_cost_errors(progress, status, duration)
        rec = {
            "qid": act.qid, "endpoint": act.endpoint,
            "tenant": act.tenant, "query": act.query,
            "start_ts": act.start_unix,
            "duration_s": round(duration, 6),
            "status": status,
            "bytes_scanned": progress.get("bytes_scanned", 0),
            "rows_emitted": progress.get("rows_emitted", 0),
            "progress": progress,
        }
        if cost_error is not None:
            # what top_queries?by=cost_error sorts on: the dimension
            # the plan-time pricing got MOST wrong for this query
            rec["cost_error"] = cost_error
        if act.parent_qid:
            # the propagated cluster identity survives into the
            # completed ring (federated top_queries attribution) and
            # the query_done journal event below
            rec["parent_qid"] = act.parent_qid
        with _reg_mu:
            _active.pop(act.qid, None)
            if len(_completed) == _COMPLETED_MAX:
                # the ring is full: this append evicts the oldest
                # record — previously a silent truncation
                events.note("top_queries_evicted")
            _completed.append(rec)
            slot = _tenant_slot(act.tenant)
            slot["select_queries"] += 1
            slot["select_seconds"] += duration
            slot["bytes_scanned"] += progress.get("bytes_scanned", 0)
        # query-lifecycle completion onto the event bus (outside every
        # lock; system-tenant completions are suppressed there — the
        # journal must not journal queries against itself)
        extra = {"parent_qid": act.parent_qid} if act.parent_qid else {}
        events.emit("query_done", tenant=act.tenant, qid=act.qid,
                    endpoint=act.endpoint, status=status,
                    duration_ms=round(duration * 1e3, 3), **extra,
                    **{k: v for k, v in sorted(progress.items())
                       if isinstance(v, (int, float))})
        return False


def _fold_cost_errors(progress: dict, status: str,
                      duration: float) -> float | None:
    """Predicted-vs-actual accountability at deregister: fold the
    plan-time predicted_* counters (obs/explain.price_into_activity)
    against this run's actuals into per-dimension relative errors —
    cost_err_* fields on the completion record / query_done event, and
    the vl_cost_model_rel_error_* histograms so EWMA drift is
    alarmable.  Returns the worst dimension's error (the
    top_queries?by=cost_error sort key), or None for unpriced or
    abnormally-ended queries (a cancelled walk's actuals measure the
    cancel point, not the model)."""
    if status != "ok" or "predicted_duration_s" not in progress:
        return None
    # the prediction prices the planned EXECUTION (prune/scan/harvest/
    # emit): drain belongs to the client, and the queued/plan phases
    # (admission wait, parse, the pricing walk itself) precede the plan
    # being priced — both come off the actual before comparing
    actual_d = progress.get("exec_s") or duration
    actual_d = max(actual_d - progress.get("phase_s_queued", 0.0)
                   - progress.get("phase_s_plan", 0.0), 1e-6)
    errs = {}
    pd = progress["predicted_duration_s"]
    errs["duration"] = abs(actual_d - pd) / max(actual_d, 1e-6)
    hist.COST_ERR_DURATION.observe(errs["duration"])
    pb = progress.get("predicted_bytes")
    if pb is not None:
        ab = progress.get("bytes_scanned", 0)
        errs["bytes"] = abs(ab - pb) / max(ab, 1.0) if (ab or pb) \
            else 0.0
        hist.COST_ERR_BYTES.observe(errs["bytes"])
    pn = progress.get("predicted_dispatches")
    if pn is not None:
        an = progress.get("dispatches_submitted", 0)
        errs["dispatches"] = abs(an - pn) / max(an, 1.0) if (an or pn) \
            else 0.0
        hist.COST_ERR_DISPATCHES.observe(errs["dispatches"])
    for k, v in errs.items():
        progress[f"cost_err_{k}"] = round(v, 6)
    return round(max(errs.values()), 6)


def track(endpoint: str, query: str, tenant=None,
          parent_qid: str = "") -> _Track:
    """Register one query execution for its dynamic extent; the ONLY
    way to mint a QueryActivity (context-manager-only, enforced by the
    vlint accounting-discipline checker).  ``parent_qid`` tags a
    cluster sub-query with its frontend query's global_qid."""
    return _Track(endpoint, query, tenant, parent_qid=parent_qid)


class _ReuseOrTrack:
    """Reuse the ambient record (relabeling it with the handler's
    canonical endpoint/query) or fall back to registering a new one.

    The admission layer (server/app.py) registers the record at the
    HTTP route — BEFORE query parsing, so a QUEUED query is already
    visible in active_queries and cancellable by qid — and the handler
    then enters its own tracking scope on the same thread.  Reusing
    the ambient record keeps it ONE query = ONE record (per-tenant
    select counters stay exact); handlers called without the route
    layer (tests, embedded use) still self-register."""

    __slots__ = ("_endpoint", "_query", "_tenant", "_inner")

    def __init__(self, endpoint: str, query: str, tenant):
        self._endpoint = endpoint
        self._query = query
        self._tenant = tenant
        self._inner = None

    def __enter__(self) -> QueryActivity:
        act = _current.get()
        if act is not None and act.enabled:
            act.relabel(self._endpoint, self._query)
            return act
        self._inner = _Track(self._endpoint, self._query, self._tenant)
        return self._inner.__enter__()

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._inner is not None:
            return self._inner.__exit__(exc_type, exc, tb)
        return False


def reuse_or_track(endpoint: str, query: str,
                   tenant=None) -> _ReuseOrTrack:
    """Handler-level tracking scope: reuse the route-registered ambient
    record or register one (context-manager-only, enforced like
    track)."""
    return _ReuseOrTrack(endpoint, query, tenant)


class _UseActivity:
    """Re-enter an existing record in another thread — the propagation
    shim for worker fan-outs (partition workers, streamwork's query
    thread, the staging prefetch worker).  Does NOT deregister."""

    __slots__ = ("_act", "_token")

    def __init__(self, act):
        self._act = act
        self._token = None

    def __enter__(self):
        if self._act is not None and self._act.enabled:
            self._token = _current.set(self._act)
        return self._act

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._token is not None:
            _current.reset(self._token)
        return False


def use_activity(act) -> _UseActivity:
    return _UseActivity(act)


# ---------------- registry reads / control ----------------

def active_snapshot(tenant: str | None = None) -> list[dict]:
    """Live records, registration order (the /select/logsql/
    active_queries payload).  ``tenant`` ("a:p") scopes the view to one
    tenant's queries."""
    with _reg_mu:
        acts = list(_active.values())
    snaps = [a.snapshot() for a in acts]
    if tenant is not None:
        snaps = [s for s in snaps if s.get("tenant") == tenant]
    return snaps


def progress_counts() -> tuple[int, int]:
    """(queries ever registered, queries live now): their difference is
    the count that finished, which the stall watch (obs/stallwatch.py)
    compares from beat to beat."""
    with _reg_mu:
        return _qid_next, len(_active)


def cancel(qid: str) -> bool:
    """Flip a live query's cancel flag (POST /select/logsql/
    cancel_query).  False when no such query is active."""
    with _reg_mu:
        act = _active.get(str(qid))
    if act is None:
        return False
    act.cancel()
    return True


def cancel_by_parent(parent_qid: str) -> int:
    """Trip the cancel flag of every live record registered under
    ``parent_qid`` — the cluster cancel-propagation path (POST
    /internal/select/cancel): the flag folds into the processor head's
    is_done() exactly like a local cancel, so each sub-query's device
    window drains immediately instead of waiting for the frontend
    disconnect probe.  Returns how many records were cancelled."""
    if not parent_qid:
        return 0
    with _reg_mu:
        acts = [a for a in _active.values()
                if a.parent_qid == parent_qid]
    for a in acts:
        a.cancel()
    return len(acts)


# the top_queries sort dimensions (a request with anything else is a
# client error — server/app.py maps the ValueError to HTTP 400)
TOP_QUERIES_BY = ("duration", "bytes", "bytes_scanned", "cost_error")


def top_sort_key(by: str) -> tuple[str, float]:
    """(record key, missing-value default) for one top_queries sort
    dimension — shared by the local ring sort below and the federated
    cluster merge (server/cluster.py), so the two can never order
    differently.  Raises ValueError on an unknown ``by``."""
    if by not in TOP_QUERIES_BY:
        raise ValueError(
            f"invalid by={by!r}; allowed: {', '.join(TOP_QUERIES_BY)}")
    if by == "cost_error":
        return "cost_error", -1.0
    if by in ("bytes", "bytes_scanned"):
        return "bytes_scanned", 0
    return "duration_s", 0


def top_queries(n: int = 10, by: str = "duration",
                tenant: str | None = None) -> list[dict]:
    """Heavy hitters from the completed-query ring buffer, most
    expensive first.  by='duration' | 'bytes' — or 'cost_error' for
    the queries the plan-time cost model priced WORST (unpriced
    records sort last); anything else raises ValueError.  ``tenant``
    scopes the ring to one tenant's completions."""
    key, default = top_sort_key(by)
    with _reg_mu:
        recs = [r for r in _completed
                if tenant is None or r.get("tenant") == tenant]
    recs.sort(key=lambda r: r.get(key, default), reverse=True)
    return recs[:max(n, 0)]


def completed_snapshot() -> list[dict]:
    with _reg_mu:
        return list(_completed)


# ---------------- ingest-side accounting ----------------

def note_ingest(tenant, rows: int, nbytes: int = 0) -> None:
    """Per-tenant ingest accounting (called per accepted request/batch
    from the insert handlers — amortized, never per row)."""
    t = tenant_str(tenant)
    with _reg_mu:
        slot = _tenant_slot(t)
        slot["rows_ingested"] += rows
        slot["bytes_ingested"] += nbytes


def note_parse_failure(protocol: str) -> None:
    with _reg_mu:
        _parse_failures[protocol] = _parse_failures.get(protocol, 0) + 1


def usage_snapshot() -> dict:
    """This node's resource-usage snapshot for GET /internal/usage —
    the payload the cluster-stats poll loop (obs/clusterstats.py) pulls
    from every storage node: the forever-accumulating per-tenant
    totals plus the live registry depth.  Counters are monotonic, so
    the frontend rollup can sum last-seen values without re-reading
    history."""
    with _reg_mu:
        tenants = {t: dict(slot) for t, slot in _tenant_totals.items()}
        active = len(_active)
    return {"tenants": tenants, "active_queries": active}


# ---------------- /metrics integration ----------------

def metrics_samples() -> list[tuple[str, dict, float]]:
    """(base_name, labels, value) samples for Metrics.render: the
    vl_active_queries gauge by endpoint plus the per-tenant counters the
    scheduler's admission control will consume."""
    out: list[tuple[str, dict, float]] = []
    with _reg_mu:
        by_endpoint: dict[str, int] = {}
        for a in _active.values():
            by_endpoint[a.endpoint] = by_endpoint.get(a.endpoint, 0) + 1
        tenants = {t: dict(slot) for t, slot in _tenant_totals.items()}
        failures = dict(_parse_failures)
    # the unlabeled total is always present (a scrape of an idle server
    # still shows the gauge at 0); per-endpoint splits ride alongside
    out.append(("vl_active_queries", {}, sum(by_endpoint.values())))
    for ep, n in sorted(by_endpoint.items()):
        out.append(("vl_active_queries", {"endpoint": ep}, n))
    for t, slot in sorted(tenants.items()):
        lbl = {"tenant": t}
        out.append(("vl_tenant_select_queries_total", lbl,
                    slot["select_queries"]))
        out.append(("vl_tenant_select_seconds_total", lbl,
                    slot["select_seconds"]))
        out.append(("vl_tenant_bytes_scanned_total", lbl,
                    slot["bytes_scanned"]))
        out.append(("vl_tenant_rows_ingested_total", lbl,
                    slot["rows_ingested"]))
        out.append(("vl_tenant_ingest_bytes_total", lbl,
                    slot["bytes_ingested"]))
    for proto, n in sorted(failures.items()):
        out.append(("vl_ingest_parse_failures_total", {"type": proto}, n))
    return out


# ---------------- scan-cost estimation ----------------

def part_bytes_per_row(part) -> float:
    """Uncompressed bytes per row of a part — the bytes_scanned
    estimator's unit cost (file parts carry exact meta; in-memory parts
    get a nominal figure)."""
    meta = getattr(part, "meta", None)
    nrows = getattr(part, "num_rows", 0)
    if meta and nrows:
        return meta.get("uncompressed_size", 0) / nrows
    return 64.0


def note_part_scanned(act, part, bis, rows: int) -> None:
    """One part's candidate blocks (`rows` rows, as the header walk
    counted them) entered the scan: the parts/rows/bytes progress adds
    in ONE place, shared by the serial walk
    (engine/searcher._scan_parts) and the device planner
    (tpu/pipeline._unit_stream) so the estimator can't diverge."""
    if not act.enabled or not bis:
        return
    act.add("parts_scanned")
    act.add("rows_scanned", rows)
    act.add("bytes_scanned", int(rows * part_bytes_per_row(part)))
