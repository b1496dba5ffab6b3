"""Admission control at the query entry: per-tenant concurrency and
bytes-in-flight limits, a bounded wait queue, deadline-aware shedding.

Replaces the raw FIFO ``threading.Semaphore`` gates in server/app.py.
The reference survives production traffic by gating everything behind
httpserver concurrency limiters (PAPER.md L6/L1); this is that gate,
with the three behaviors a saturated server needs:

- **shed, don't queue forever** — over-limit arrivals get 429 +
  ``Retry-After`` with a machine-readable reason
  (``tenant_limit`` / ``queue_full`` / ``deadline``) instead of an
  unbounded queue: the bounded queue (``VL_QUEUE_MAX``) absorbs
  bursts, everything past it sheds immediately;
- **per-tenant limits** — concurrency (``VL_TENANT_MAX_CONCURRENT``,
  runtime-overridable per tenant via the POST ``sched_config``
  endpoint) and estimated bytes-in-flight (``VL_TENANT_MAX_BYTES``,
  from the per-endpoint bytes-scanned EWMA) so one tenant cannot
  occupy the whole server;
- **deadline awareness** — a query that must queue is shed up front
  when the duration EWMA says its deadline cannot be met (queue wait
  estimate + run estimate > remaining budget), and a queued entry
  whose deadline passes while waiting sheds instead of running a
  walk that is already dead.

Queued-but-not-admitted queries are CANCELLABLE: the wait loop polls
the activity record's cancel flag (``cancel_query`` by qid — the
record registers BEFORE admission, phase "queued") and an optional
peer-disconnect probe, removing the entry from the queue before any
device work starts.

``admit(...)`` is context-manager-only: the with-block is what
decrements the concurrency/bytes accounting on every exit path and
feeds the duration/bytes EWMAs on completion.

Lock order: the controller condition lock is a leaf; the wait loop's
cancel/disconnect probes only read an Event / poll a socket.  The
activity record's own lock is never taken under ours (abandon/phase
updates happen outside the controller lock).
"""

from __future__ import annotations

import threading
import time
import weakref

from .. import config
from ..obs import events, hist, tracing

REASONS = ("tenant_limit", "queue_full", "deadline", "cancelled")

_EWMA = 0.3

# endpoints whose admission extent is a CONNECTION lifetime, not a
# query execution: feeding their wall time into the duration EWMA
# would poison the deadline-feasibility gate (a 10-minute tail would
# make every queued tail look infeasible) — same exclusion
# server/app.py applies to vl_query_duration_seconds
_LIFETIME_ENDPOINTS = frozenset(("/select/logsql/tail",))

# tenant label values and endpoint paths come from the client: both
# accounting keyspaces are hard-capped, overflow aggregating into one
# slot, so header/path cycling can neither leak memory nor explode
# /metrics cardinality (mirrors obs/activity._TENANT_MAX)
_TENANT_MAX = 1024
_ENDPOINT_MAX = 64
_OVERFLOW = "other"


def _capped_key(table: dict, key: str, cap: int) -> str:
    if key in table or len(table) < cap:
        return key
    return _OVERFLOW


class AdmissionShed(Exception):
    """A query was refused admission.  ``reason`` is machine-readable
    (tenant_limit | queue_full | deadline, plus cancelled for a queued
    entry killed before it started); ``retry_after`` feeds the
    Retry-After response header.  ``limit``/``current`` (when known)
    feed the X-VL-Concurrency-Limit/-Current response headers so
    clients (vlagent) can back off adaptively instead of sleeping a
    fixed Retry-After — the reference's X-Concurrency hint style."""

    def __init__(self, reason: str, message: str,
                 retry_after: float | None = 1.0, status: int = 429,
                 limit: int | None = None, current: int | None = None):
        super().__init__(message)
        self.reason = reason
        self.message = message
        self.retry_after = retry_after
        self.status = status
        self.limit = limit
        self.current = current


# ---------------- process-global admitted/shed accounting ----------------

_acct_mu = threading.Lock()
# (pool, reason, tenant) -> n — the pool label keeps a combined
# frontend+storage node's internal-pool sub-query sheds/admits from
# double-counting into the client-facing select series
_rejected: dict[tuple[str, str, str], int] = {}
_admitted: dict[tuple[str, str], int] = {}   # (pool, tenant) -> n
# persistent capped tenant keyspaces (O(1) on the shedding hot path)
_rejected_tenants: set = set()
_admitted_tenants: set = set()
_controllers: "weakref.WeakSet[AdmissionController]" = weakref.WeakSet()


def _capped_tenant(tenants: set, tenant: str) -> str:
    if tenant not in tenants:
        if len(tenants) >= _TENANT_MAX:
            tenant = _OVERFLOW
        tenants.add(tenant)
    return tenant


def note_rejected(tenant: str, reason: str,
                  pool: str = "select") -> None:
    with _acct_mu:
        key = (pool, reason, _capped_tenant(_rejected_tenants, tenant))
        _rejected[key] = _rejected.get(key, 0) + 1


def rejected_total(pool: str = "select") -> int:
    """Every shed of a pool so far.  The stall watch (obs/stallwatch.py)
    takes the select pool's out of its count of finished queries (a shed
    request registers and ends like any other): a burst of sheds is what
    a stall looks like, not progress."""
    with _acct_mu:
        return sum(n for (p, _r, _t), n in _rejected.items() if p == pool)


def _note_admitted(tenant: str, pool: str = "select") -> None:
    with _acct_mu:
        key = (pool, _capped_tenant(_admitted_tenants, tenant))
        _admitted[key] = _admitted.get(key, 0) + 1


def metrics_samples() -> list[tuple[str, dict, float]]:
    """Admission samples for Metrics.render: per-tenant admitted/shed
    counters plus live queue-depth/active gauges per pool."""
    out: list[tuple[str, dict, float]] = []
    with _acct_mu:
        rejected = dict(_rejected)
        admitted = dict(_admitted)
        ctls = list(_controllers)
    for (pool, reason, tenant), n in sorted(rejected.items()):
        out.append(("vl_select_rejected_total",
                    {"pool": pool, "reason": reason, "tenant": tenant},
                    n))
    for (pool, tenant), n in sorted(admitted.items()):
        out.append(("vl_select_admitted_total",
                    {"pool": pool, "tenant": tenant}, n))
    for c in ctls:
        snap = c.snapshot()
        lbl = {"pool": snap["pool"]}
        out.append(("vl_sched_queue_depth", lbl, snap["queued"]))
        out.append(("vl_sched_admission_active", lbl, snap["active"]))
    return out


def admission_snapshots() -> list[dict]:
    with _acct_mu:
        ctls = list(_controllers)
    return [c.snapshot() for c in ctls]


# ---------------- the controller ----------------

class _Waiter:
    __slots__ = ("tenant", "endpoint", "granted", "shed_reason", "dead",
                 "deadline", "est_bytes")

    def __init__(self, tenant: str, endpoint: str,
                 deadline: float | None):
        self.tenant = tenant
        self.endpoint = endpoint
        self.granted = False
        self.shed_reason: str | None = None
        self.dead = False
        self.deadline = deadline      # monotonic, None = no deadline
        self.est_bytes = 0            # reserved at grant time


class AdmissionController:
    """One admission pool (the single binary runs two: ``select`` for
    client queries, ``internal`` for cluster sub-queries, so a node
    acting as both frontend and storage node can't starve the
    sub-queries it fans out itself)."""

    def __init__(self, max_concurrent: int | None = None,
                 queue_timeout_s: float | None = None,
                 pool: str = "select"):
        self._mu = threading.Lock()
        self._cond = threading.Condition(self._mu)
        self.pool = pool
        self._max = max_concurrent if max_concurrent else \
            config.env_int("VL_MAX_CONCURRENT")
        if queue_timeout_s is None:
            queue_timeout_s = \
                config.env_int("VL_QUEUE_TIMEOUT_MS") / 1e3
        self.queue_timeout_s = queue_timeout_s
        self._queue_max = config.env_int("VL_QUEUE_MAX",
                                         2 * self._max)
        self._tenant_max_default = \
            config.env_int("VL_TENANT_MAX_CONCURRENT") or self._max
        self._tenant_max_bytes = config.env_int("VL_TENANT_MAX_BYTES")
        self._tenant_limits: dict[str, int] = {}
        self._active = 0
        self._tenant_active: dict[str, int] = {}
        self._tenant_bytes: dict[str, int] = {}   # estimated, in flight
        self._queue: list[_Waiter] = []
        # per-endpoint completion EWMAs: the deadline-feasibility and
        # bytes-in-flight estimators (fed on every admitted exit)
        self._dur_ewma: dict[str, float] = {}
        self._bytes_ewma: dict[str, float] = {}
        with _acct_mu:
            _controllers.add(self)

    # -- runtime config (POST sched_config) --

    def set_tenant_limit(self, tenant: str, max_concurrent: int) -> None:
        with self._cond:
            if max_concurrent <= 0:
                self._tenant_limits.pop(tenant, None)
            else:
                self._tenant_limits[tenant] = max_concurrent
        # config changes are audit events: who got capped to what,
        # queryable from the journal long after the fact
        events.emit("sched_config", pool=self.pool,
                    config_tenant=str(tenant),
                    max_concurrent=max_concurrent)

    def _tenant_cap(self, tenant: str) -> int:
        return self._tenant_limits.get(tenant, self._tenant_max_default)

    # -- estimators (callers hold self._mu) --

    def _run_estimate(self, endpoint: str) -> float:
        return self._dur_ewma.get(endpoint, 0.0)

    def _bytes_estimate(self, endpoint: str) -> int:
        return int(self._bytes_ewma.get(endpoint, 0.0))

    def _note_done(self, endpoint: str, duration: float,
                   nbytes: int) -> None:
        if endpoint in _LIFETIME_ENDPOINTS:
            # a connection's lifetime is not a query's run time: one
            # long tail must not convince the deadline gate that every
            # queued tail is infeasible
            return
        # streaming endpoints measure response DRAIN time too (a slow
        # client inflates the wall); clamping each observation at the
        # queue timeout bounds how far any stalled consumer can push
        # the feasibility estimate
        duration = min(duration, self.queue_timeout_s)
        endpoint = _capped_key(self._dur_ewma, endpoint, _ENDPOINT_MAX)
        old = self._dur_ewma.get(endpoint)
        self._dur_ewma[endpoint] = duration if old is None else \
            old + _EWMA * (duration - old)
        oldb = self._bytes_ewma.get(endpoint)
        self._bytes_ewma[endpoint] = nbytes if oldb is None else \
            oldb + _EWMA * (nbytes - oldb)

    def _grant_waiters(self) -> None:
        """Hand freed capacity to the queue head(s), FIFO; entries whose
        tenant filled up — concurrency OR bytes budget — while they
        waited shed with tenant_limit (callers hold self._mu and notify
        after).  The bytes estimate is RESERVED here, at grant, so two
        waiters granted in one pass cannot jointly overshoot the
        budget."""
        while self._queue and self._active < self._max:
            w = self._queue[0]
            if w.dead:
                self._queue.pop(0)
                continue
            if self._tenant_active.get(w.tenant, 0) >= \
                    self._tenant_cap(w.tenant):
                w.shed_reason = "tenant_limit"
                self._queue.pop(0)
                continue
            est = self._bytes_estimate(w.endpoint)
            if self._tenant_max_bytes > 0 and est and \
                    self._tenant_bytes.get(w.tenant, 0) + est > \
                    self._tenant_max_bytes:
                w.shed_reason = "tenant_limit"
                self._queue.pop(0)
                continue
            w.granted = True
            w.est_bytes = est
            if est:
                self._tenant_bytes[w.tenant] = \
                    self._tenant_bytes.get(w.tenant, 0) + est
            self._active += 1
            self._tenant_active[w.tenant] = \
                self._tenant_active.get(w.tenant, 0) + 1
            self._queue.pop(0)

    # -- the admission API (context-manager-only) --

    def admit(self, tenant: str = "0:0", endpoint: str = "",
              deadline_s: float | None = None, act=None,
              disconnected=None) -> "_Admission":
        """Admit one query for its dynamic extent or raise
        AdmissionShed.  ``deadline_s`` is the request's remaining time
        budget; ``act`` (activity record) makes the queued entry
        cancellable by qid; ``disconnected()`` polls the HTTP peer."""
        return _Admission(self, str(tenant), endpoint, deadline_s, act,
                          disconnected)

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "pool": self.pool,
                "max_concurrent": self._max,
                "active": self._active,
                "queued": sum(1 for w in self._queue if not w.dead),
                "queue_max": self._queue_max,
                "queue_timeout_s": self.queue_timeout_s,
                "tenant_active": {t: n for t, n in
                                  sorted(self._tenant_active.items())
                                  if n},
                "tenant_limits": dict(self._tenant_limits),
            }


class _Admission:
    """Dynamic extent of one admitted query: concurrency/bytes
    accounting on enter, release + EWMA feed on EVERY exit path."""

    __slots__ = ("_c", "_tenant", "_endpoint", "_deadline_s", "_act",
                 "_disconnected", "_t_admit", "_est_bytes")

    def __init__(self, c: AdmissionController, tenant: str,
                 endpoint: str, deadline_s, act, disconnected):
        self._c = c
        self._tenant = tenant
        self._endpoint = endpoint
        self._deadline_s = deadline_s
        self._act = act
        self._disconnected = disconnected
        self._t_admit = 0.0
        self._est_bytes = 0

    def _shed(self, reason: str, message: str, retry_after: float,
              limit: int | None = None,
              current: int | None = None) -> AdmissionShed:
        c = self._c
        if limit is None:
            limit = c._max
        if current is None:
            current = c._active
        note_rejected(self._tenant, reason, pool=c.pool)
        # sheds are exactly what the self-telemetry journal exists to
        # record: `tail` them live, stats-pipe them by tenant/reason
        # over hours.  Journal ingest bypasses admission entirely, so
        # this event survives the very overload it reports.
        events.emit("admission_shed", tenant=self._tenant,
                    reason=reason, endpoint=self._endpoint, pool=c.pool,
                    limit=limit, current=current,
                    retry_after_s=round(retry_after or 0.0, 3))
        return AdmissionShed(reason, message, retry_after=retry_after,
                             limit=limit, current=current)

    def _cancel_probe(self) -> str | None:
        """'cancelled' / 'abandoned' when the queued entry should leave
        the queue before any work starts (called WITHOUT the controller
        lock held)."""
        act = self._act
        if act is not None and getattr(act, "enabled", False) and \
                act.is_cancelled():
            return "cancelled"
        if self._disconnected is not None and self._disconnected():
            return "abandoned"
        return None

    def __enter__(self) -> "_Admission":
        # arrival to admitted, on the request's trace (a no-op with
        # tracing off and on the internal pool, which has no root)
        with tracing.current_span().span("admission_wait") as sp:
            if sp.enabled:
                c = self._c
                with c._cond:
                    sp.set("queued_behind",
                           sum(1 for w in c._queue if not w.dead))
            return self._enter()

    def _enter(self) -> "_Admission":
        c = self._c
        t0 = time.monotonic()
        deadline = None if self._deadline_s is None else \
            t0 + self._deadline_s
        with c._cond:
            cap = c._tenant_cap(self._tenant)
            if c._tenant_active.get(self._tenant, 0) >= cap:
                raise self._shed(
                    "tenant_limit",
                    f"tenant {self._tenant} at its concurrency limit "
                    f"({cap}); adjust VL_TENANT_MAX_CONCURRENT or the "
                    f"sched_config override",
                    retry_after=max(1.0, c._run_estimate(self._endpoint)),
                    limit=cap,
                    current=c._tenant_active.get(self._tenant, 0))
            if c._tenant_max_bytes > 0:
                est = c._bytes_estimate(self._endpoint)
                if c._tenant_bytes.get(self._tenant, 0) + est > \
                        c._tenant_max_bytes:
                    raise self._shed(
                        "tenant_limit",
                        f"tenant {self._tenant} over its bytes-in-"
                        f"flight budget (VL_TENANT_MAX_BYTES="
                        f"{c._tenant_max_bytes})",
                        retry_after=max(
                            1.0, c._run_estimate(self._endpoint)))
            if c._active < c._max and not c._queue:
                self._grant_locked()
                # reserve the bytes estimate under the SAME lock as the
                # grant so concurrent admits cannot jointly overshoot
                # the tenant budget
                self._est_bytes = c._bytes_estimate(self._endpoint)
                if self._est_bytes:
                    c._tenant_bytes[self._tenant] = \
                        c._tenant_bytes.get(self._tenant, 0) + \
                        self._est_bytes
                w = None
            else:
                w = self._enqueue_locked(deadline)
        if w is None:
            return self._admitted(0.0)
        try:
            waited = self._wait(w, t0)
        except BaseException:
            with c._cond:
                if w.granted:
                    # raced a concurrent grant (e.g. KeyboardInterrupt
                    # landing between the grant and the waiter's next
                    # poll): fold the slot AND its bytes reservation
                    # back or the pool shrinks permanently
                    self._est_bytes = w.est_bytes
                    self._release_locked()
                    w.granted = False
                w.dead = True
                c._grant_waiters()
                c._cond.notify_all()
            raise
        self._est_bytes = w.est_bytes
        return self._admitted(waited)

    def _enqueue_locked(self, deadline) -> _Waiter:
        """Queue-entry gate (caller holds c._mu): shed up front what
        provably cannot finish, bound the queue, else join it."""
        c = self._c
        est_run = c._run_estimate(self._endpoint)
        depth = sum(1 for w in c._queue if not w.dead)
        if self._deadline_s is not None:
            # shed only on the PROVABLE part: the queue wait ahead of
            # us.  Folding est_run into the comparison would let a
            # drain-inflated EWMA (slow clients) reject queries the
            # server could execute in milliseconds; a genuinely slow
            # execution still dies on its own deadline downstream.
            est_wait = est_run * (depth + 1) / max(c._max, 1)
            if self._deadline_s <= 0 or (
                    est_run > 0 and est_wait > self._deadline_s):
                raise self._shed(
                    "deadline",
                    f"deadline {self._deadline_s:.3f}s cannot be "
                    f"met (estimated queue wait {est_wait:.3f}s, "
                    f"per-query estimate {est_run:.3f}s)",
                    retry_after=max(1.0, est_wait))
        if depth >= c._queue_max:
            raise self._shed(
                "queue_full",
                f"admission queue full ({c._queue_max} waiting); "
                f"too many concurrent queries",
                retry_after=max(1.0, est_run * depth /
                                max(c._max, 1)))
        w = _Waiter(self._tenant, self._endpoint, deadline)
        c._queue.append(w)
        return w

    def _wait(self, w: _Waiter, t0: float) -> float:
        """Poll loop for one queued entry; returns the wait duration or
        raises AdmissionShed (granted/shed state transitions happen
        under the controller lock; cancel/disconnect probes outside)."""
        c = self._c
        while True:
            with c._cond:
                c._grant_waiters()
                if w.granted:
                    return time.monotonic() - t0
                if w.shed_reason:
                    raise self._shed(
                        w.shed_reason,
                        f"shed while queued ({w.shed_reason})",
                        retry_after=max(
                            1.0, c._run_estimate(self._endpoint)))
                now = time.monotonic()
                if w.deadline is not None and now >= w.deadline:
                    w.dead = True
                    raise self._shed(
                        "deadline",
                        "deadline expired while queued",
                        retry_after=None)
                if now - t0 >= c.queue_timeout_s:
                    w.dead = True
                    raise self._shed(
                        "queue_full",
                        f"query queued longer than "
                        f"-search.maxQueueDuration="
                        f"{c.queue_timeout_s}s; too many concurrent "
                        f"queries",
                        retry_after=max(
                            1.0, c._run_estimate(self._endpoint)))
                c._cond.wait(0.05)
            why = self._cancel_probe()
            if why is not None:
                with c._cond:
                    if w.granted:
                        # raced a grant: fold it (incl. the bytes
                        # reservation) back before leaving — and clear
                        # the flag so the caller's unwind handler
                        # can't fold it back twice
                        self._est_bytes = w.est_bytes
                        self._release_locked()
                        w.granted = False
                    w.dead = True
                    c._grant_waiters()
                    c._cond.notify_all()
                if why == "abandoned":
                    act = self._act
                    if act is not None:
                        act.abandon()
                note_rejected(self._tenant, "cancelled",
                              pool=c.pool)
                events.emit("admission_shed", tenant=self._tenant,
                            reason="cancelled",
                            endpoint=self._endpoint, pool=c.pool)
                raise AdmissionShed(
                    "cancelled",
                    "query cancelled while queued for admission",
                    retry_after=None, status=499)

    # -- bookkeeping (callers hold c._mu unless noted) --

    def _grant_locked(self) -> None:
        c = self._c
        c._active += 1
        c._tenant_active[self._tenant] = \
            c._tenant_active.get(self._tenant, 0) + 1

    def _release_locked(self) -> None:
        c = self._c
        c._active -= 1
        n = c._tenant_active.get(self._tenant, 1) - 1
        if n:
            c._tenant_active[self._tenant] = n
        else:
            c._tenant_active.pop(self._tenant, None)
        if self._est_bytes:
            b = c._tenant_bytes.get(self._tenant, 0) - self._est_bytes
            if b > 0:
                c._tenant_bytes[self._tenant] = b
            else:
                c._tenant_bytes.pop(self._tenant, None)

    def _admitted(self, waited: float) -> "_Admission":
        # the bytes reservation happened AT GRANT (immediate path: in
        # __enter__ under the grant lock; queued path: _grant_waiters)
        # so concurrent grants cannot jointly overshoot the budget
        c = self._c
        hist.SCHED_QUEUE_WAIT.observe(waited)
        _note_admitted(self._tenant, pool=c.pool)
        self._t_admit = time.monotonic()
        act = self._act
        if act is not None and getattr(act, "enabled", False) and waited:
            act.set("admission_wait_s", round(waited, 6))
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        c = self._c
        duration = time.monotonic() - self._t_admit
        nbytes = 0
        act = self._act
        if act is not None and getattr(act, "enabled", False):
            nbytes = act.counter("bytes_scanned")
            exec_mono = getattr(act, "exec_mono", None)
            if exec_mono is not None:
                # sink-side exec/drain split (obs/activity
                # mark_exec_done): the EWMA feeds on EXECUTION time
                # only, so a stalled streaming client's drain cannot
                # poison deadline feasibility for everyone queued
                # behind it.  (_note_done's queue-timeout clamp stays
                # as defense for records without the stamp.)  The
                # record also carries predicted_duration_s — the
                # per-QUERY priced estimate (obs/explain) this
                # per-endpoint EWMA could be upgraded to consume.
                duration = min(duration,
                               max(exec_mono - self._t_admit, 0.0))
        with c._cond:
            self._release_locked()
            c._note_done(self._endpoint, duration, nbytes)
            c._grant_waiters()
            c._cond.notify_all()
        return False
