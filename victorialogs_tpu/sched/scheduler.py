"""Shared device-dispatch scheduler: ONE global in-flight budget with
weighted fair queuing across active queries.

PR 3 gave every query its own ``VL_INFLIGHT`` dispatch window; PR 6
measured what that costs under concurrency (8 clients: p50 ~6.5x the
solo wall — every runner burns its own window and fights for the device
unmanaged).  This module makes the in-flight budget a SHARED resource:

- the process owns one :class:`DispatchScheduler` (``scheduler()``)
  with a global budget of ``VL_INFLIGHT_GLOBAL`` outstanding dispatch
  slots;
- each query's pipeline walk opens a :func:`device_slots` scope and
  LEASES a slot per submitted dispatch unit, releasing it when the
  unit's result is materialized (tpu/pipeline.py submit/harvest);
- when the budget is contended, a freed slot goes to the waiting query
  with the smallest weight-normalized in-flight count (round-robin on
  ties) — weighted max-min fair sharing, so one huge scan can no
  longer starve small queries, and tenants can be weighted
  (``VL_TENANT_WEIGHTS`` / the ``sched_config`` endpoint).

Lease discipline mirrors spans (obs/tracing.py) and activity records
(obs/activity.py): ``device_slots(...)`` is context-manager-only —
the with-block is what guarantees every lease this scope still holds
is released on EVERY exit path (limit, deadline, cancel, abandon and
fault-injection unwinds included), enforced by the vlint
``lease-discipline`` checker.  ``check_balanced()`` mirrors
StagingCache.check_balanced: with no queries running, the global
in-flight count must be exactly zero.

Fault injection (test-only): ``inject_fault()`` arms a one-shot
failure of a chosen upcoming dispatch submit; ``VL_FAULT_SUBMIT=p``
fails each submit with probability p.  Both raise
:class:`InjectedFaultError` from the pipeline's submit path, pinning
that a failed unit drains the window without downstream writes and
releases its lease (tests/test_sched.py).

Kill-switch: ``VL_SCHED=0`` grants every lease immediately (no budget,
no fairness) — the unmanaged PR 6 behavior, used as the bench baseline.

Lock order: the scheduler condition lock is a leaf — nothing is called
under it except flow bookkeeping; the waiter's ``check`` callback runs
with the lock held but only reads Events / raises (the processor-head
lock is never taken while a caller holds ours on the release side).
"""

from __future__ import annotations

import threading
import time
from .. import config

from ..obs import events


class InjectedFaultError(RuntimeError):
    """A dispatch submit failed via the fault-injection hook."""


def sched_enabled() -> bool:
    """VL_SCHED=0 disables the shared budget (leases grant instantly)."""
    return config.env_flag("VL_SCHED")


def global_budget() -> int:
    """VL_INFLIGHT_GLOBAL: max dispatch slots outstanding process-wide
    across ALL queries (>=1; default 8 = 2x the default per-query
    window, so a solo query never feels the scheduler)."""
    return max(1, config.env_int("VL_INFLIGHT_GLOBAL"))


# ---------------- tenant weights ----------------

_weights_mu = threading.Lock()
_weight_overrides: dict[str, float] = {}
_weights_env_cache: tuple[str, dict] | None = None


def set_tenant_weight(tenant: str, weight: float) -> None:
    """Runtime per-tenant fair-share weight (the POST sched_config
    endpoint); overrides VL_TENANT_WEIGHTS."""
    w = max(0.01, float(weight))
    with _weights_mu:
        _weight_overrides[str(tenant)] = w
    events.emit("sched_config", config_tenant=str(tenant), weight=w)


def tenant_weight(tenant: str) -> float:
    """Fair-share weight for one 'account:project' tenant (default 1.0;
    VL_TENANT_WEIGHTS="0:0=4,9:0=0.5" preseeds, sched_config updates)."""
    global _weights_env_cache
    env = config.env("VL_TENANT_WEIGHTS") or ""
    with _weights_mu:
        got = _weight_overrides.get(str(tenant))
        if got is not None:
            return got
        if _weights_env_cache is None or _weights_env_cache[0] != env:
            table: dict[str, float] = {}
            for item in env.split(","):
                if "=" not in item:
                    continue
                k, _, v = item.rpartition("=")
                try:
                    table[k.strip()] = max(0.01, float(v))
                except ValueError:
                    continue
            _weights_env_cache = (env, table)
        return _weights_env_cache[1].get(str(tenant), 1.0)


# ---------------- the scheduler ----------------

class _Flow:
    """One active query's fair-queuing state (shared by every
    device_slots scope of that query — partition workers attach to the
    same flow via refcount)."""

    __slots__ = ("key", "tenant", "weight", "held", "waiters", "refs",
                 "last_grant")

    def __init__(self, key, tenant: str, weight: float):
        self.key = key
        self.tenant = tenant
        self.weight = weight
        self.held = 0          # dispatch slots currently leased
        self.waiters = 0       # scopes blocked in acquire()
        self.refs = 0          # open device_slots scopes
        self.last_grant = 0    # grant sequence (round-robin tiebreak)


class DispatchScheduler:
    """The global dispatch-slot pool.  All state under one condition
    lock; grants happen inside ``_try_grant`` so the eligibility rule
    lives in exactly one place."""

    def __init__(self):
        self._mu = threading.Lock()
        self._cond = threading.Condition(self._mu)
        self._flows: dict = {}
        self._in_flight = 0
        self._grant_seq = 0
        self._grants_total = 0
        self._contended_total = 0

    # -- internal (callers hold self._mu) --

    def _flow_for(self, key, tenant: str, weight: float) -> _Flow:
        f = self._flows.get(key)
        if f is None:
            f = self._flows[key] = _Flow(key, tenant, weight)
        f.refs += 1
        return f

    def _deref(self, flow: _Flow) -> None:
        flow.refs -= 1
        if flow.refs <= 0:
            self._flows.pop(flow.key, None)

    def _eligible(self, flow: _Flow) -> bool:
        """Weighted max-min fairness: a waiting flow may take the next
        slot only if no OTHER waiting flow has a strictly smaller
        weight-normalized in-flight count (ties: least-recently
        granted first)."""
        best = None
        best_key = None
        for f in self._flows.values():
            if f.waiters <= 0 and f is not flow:
                continue
            k = (f.held / f.weight, f.last_grant)
            if best_key is None or k < best_key:
                best_key, best = k, f
        return best is None or best is flow

    def _try_grant(self, flow: _Flow) -> bool:
        if not sched_enabled():
            pass  # unmanaged: grant unconditionally (still counted)
        elif self._in_flight >= global_budget() or \
                not self._eligible(flow):
            return False
        self._in_flight += 1
        flow.held += 1
        self._grant_seq += 1
        flow.last_grant = self._grant_seq
        self._grants_total += 1
        return True

    # -- the lease API (context-manager-only, vlint lease-discipline) --

    def device_slots(self, act=None, tenant: str | None = None):
        """Open one query scope over the shared budget; the ONLY way to
        lease dispatch slots.  ``act`` is the query's activity record
        (flows of the same qid share fairness state across partition
        workers); tenant defaults to the record's."""
        return _SlotScope(self, act, tenant)

    # -- introspection --

    def check_balanced(self) -> bool:
        """True when every lease ever granted has been released and no
        query scope is still attached (mirrors
        StagingCache.check_balanced)."""
        with self._mu:
            return self._in_flight == 0 and not self._flows

    def in_flight(self) -> int:
        """Leased slots, process-wide: dispatch units submitted and not
        yet harvested (the trace's `device_queue_depth` reads this)."""
        with self._mu:
            return self._in_flight

    def snapshot(self) -> dict:
        with self._mu:
            flows = [{"key": str(f.key), "tenant": f.tenant,
                      "weight": f.weight, "held": f.held,
                      "waiting": f.waiters} for f in
                     self._flows.values()]
            return {"enabled": sched_enabled(),
                    "budget": global_budget(),
                    "in_flight": self._in_flight,
                    "grants_total": self._grants_total,
                    "contended_total": self._contended_total,
                    "flows": flows}


class _SlotScope:
    """Dynamic extent of one query scan's slot leases.  Releases every
    lease it still holds on exit — the drain path for cancel/deadline/
    fault unwinds — and detaches from the flow."""

    __slots__ = ("_s", "_act", "_tenant", "_flow", "_held")

    def __init__(self, s: DispatchScheduler, act, tenant):
        self._s = s
        self._act = act
        self._tenant = tenant
        self._flow = None
        self._held = 0

    def __enter__(self) -> "_SlotScope":
        act = self._act
        if self._tenant is None:
            self._tenant = getattr(act, "tenant", "0:0") or "0:0"
        key = act.qid if act is not None and \
            getattr(act, "enabled", False) else id(self)
        with self._s._cond:
            self._flow = self._s._flow_for(key, self._tenant,
                                           tenant_weight(self._tenant))
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        s = self._s
        with s._cond:
            if self._held:
                # drain: the window was dropped mid-flight
                self._flow.held -= self._held
                s._in_flight -= self._held
                self._held = 0
            s._deref(self._flow)
            self._flow = None
            s._cond.notify_all()
        return False

    def try_acquire(self) -> bool:
        """Non-blocking lease; the pipeline's fast path (uncontended
        budget: identical behavior to the PR 6 per-query window)."""
        s = self._s
        with s._cond:
            if s._try_grant(self._flow):
                self._held += 1
                return True
            s._contended_total += 1
            return False

    def acquire(self, check=None, poll_s: float = 0.02) -> float:
        """Blocking lease: wait for this flow's fair turn.  ``check``
        runs every poll tick and may raise (deadline / cancellation) —
        the scope's __exit__ then releases everything.  Returns the
        wait in seconds."""
        t0 = time.perf_counter()
        s = self._s
        with s._cond:
            self._flow.waiters += 1
            try:
                while not s._try_grant(self._flow):
                    s._cond.wait(poll_s)
                    if check is not None:
                        check()
            finally:
                self._flow.waiters -= 1
            self._held += 1
        return time.perf_counter() - t0

    def release(self) -> None:
        """Return one leased slot (unit harvested)."""
        s = self._s
        with s._cond:
            if self._held <= 0:
                raise AssertionError(
                    "scheduler lease release without a held slot")
            self._held -= 1
            self._flow.held -= 1
            s._in_flight -= 1
            s._cond.notify_all()

    @property
    def held(self) -> int:
        with self._s._cond:
            return self._held


_scheduler = DispatchScheduler()


def scheduler() -> DispatchScheduler:
    """The process-global dispatch scheduler."""
    return _scheduler


def device_slots(act=None, tenant: str | None = None) -> _SlotScope:
    """Module-level convenience over ``scheduler().device_slots`` (the
    form the pipeline uses; context-manager-only)."""
    return _scheduler.device_slots(act, tenant)


def check_balanced() -> bool:
    return _scheduler.check_balanced()


# ---------------- fault injection (test-only drain-path hook) ----------------

_fault_mu = threading.Lock()
_fault_targets: list[int] = []
_submit_count = 0


def inject_fault(nth: int = 0) -> None:
    """Arm a one-shot submit failure: the (nth+1)-th dispatch submit
    from now raises InjectedFaultError.  Deterministic counterpart of
    VL_FAULT_SUBMIT for tests pinning the drain paths."""
    with _fault_mu:
        _fault_targets.append(_submit_count + 1 + max(0, int(nth)))


def clear_faults() -> None:
    with _fault_mu:
        _fault_targets.clear()


def maybe_fail_submit() -> None:
    """Called by the pipeline immediately before each dispatch submit.
    Raises InjectedFaultError for an armed inject_fault() target or
    with probability VL_FAULT_SUBMIT — AFTER the slot lease was taken,
    so the tests prove the lease is released on the error path."""
    global _submit_count
    with _fault_mu:
        _submit_count += 1
        n = _submit_count
        hit = n in _fault_targets
        if hit:
            _fault_targets.remove(n)
    if hit:
        # fault injections are journal events too: a chaos run's
        # injected failures correlate with the query_done error
        # records they caused, by qid/time
        events.emit("fault_injected", kind="submit", submit_no=n,
                    source="inject_fault")
        raise InjectedFaultError(
            f"injected dispatch submit fault (submit #{n})")
    p = config.env("VL_FAULT_SUBMIT") or ""
    if p:
        try:
            prob = float(p)
        except ValueError:
            prob = 0.0
        if prob > 0:
            import random
            if prob >= 1.0 or random.random() < prob:
                events.emit("fault_injected", kind="submit",
                            submit_no=n, source="VL_FAULT_SUBMIT")
                raise InjectedFaultError(
                    f"injected dispatch submit fault "
                    f"(VL_FAULT_SUBMIT={prob})")


def metrics_samples() -> list[tuple[str, dict, float]]:
    """Dispatch-scheduler samples for Metrics.render."""
    snap = _scheduler.snapshot()
    return [
        ("vl_sched_dispatch_budget", {}, snap["budget"]),
        ("vl_sched_dispatch_in_flight", {}, snap["in_flight"]),
        ("vl_sched_dispatch_grants_total", {}, snap["grants_total"]),
        ("vl_sched_dispatch_contended_total", {},
         snap["contended_total"]),
    ]
