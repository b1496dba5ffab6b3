"""Overload-safe query scheduling.

Three cooperating pieces turn "fast for one query" into "stays up
under production traffic":

- :mod:`.admission` — per-tenant admission control at the HTTP/cluster
  entry (bounded queue, 429 + Retry-After shedding with machine-
  readable reasons, deadline-aware early rejection);
- :mod:`.scheduler` — the shared device-dispatch scheduler: one global
  in-flight budget, submit slots leased per dispatch unit with
  weighted fair queuing across active queries (tpu/pipeline.py);
- fault injection (``inject_fault`` / ``VL_FAULT_SUBMIT``) pinning the
  drain paths: a failed submit must release its lease and error the
  query cleanly.

Everything is observable: ``metrics_samples()`` feeds /metrics,
``snapshot()`` rides the /select/logsql/active_queries payload, and
slot/queue waits land in the obs.hist histograms and ?trace=1 trees.
"""

from __future__ import annotations

from .admission import (AdmissionController, AdmissionShed, REASONS,
                        admission_snapshots, note_rejected,
                        rejected_total)
from .admission import metrics_samples as _admission_metrics
from .netfaults import (FaultProxy, clear_net_faults, inject_net_fault,
                        maybe_fail_net)
from .scheduler import (DispatchScheduler, InjectedFaultError,
                        check_balanced, clear_faults, device_slots,
                        global_budget, inject_fault, maybe_fail_submit,
                        sched_enabled, scheduler, set_tenant_weight,
                        tenant_weight)
from .scheduler import metrics_samples as _scheduler_metrics

__all__ = [
    "AdmissionController", "AdmissionShed", "FaultProxy", "REASONS",
    "DispatchScheduler", "InjectedFaultError", "admission_snapshots",
    "check_balanced", "clear_faults", "clear_net_faults",
    "device_slots", "global_budget", "inject_fault", "inject_net_fault",
    "maybe_fail_net", "maybe_fail_submit", "metrics_samples",
    "note_rejected", "rejected_total", "sched_enabled", "scheduler",
    "set_tenant_weight", "snapshot", "tenant_weight",
]


def metrics_samples() -> list[tuple[str, dict, float]]:
    """(base, labels, value) samples for server/app.py Metrics.render:
    dispatch-scheduler gauges + per-tenant admitted/shed counters +
    per-pool queue gauges."""
    return _scheduler_metrics() + _admission_metrics()


def snapshot() -> dict:
    """Live scheduler state for /select/logsql/active_queries."""
    return {"dispatch": scheduler().snapshot(),
            "admission": admission_snapshots()}
