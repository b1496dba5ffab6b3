"""Fixed-bucket Prometheus histograms for the query path.

Unlike tracing (opt-in per request), histograms are ALWAYS on: each
observe() is a bisect over a small fixed bucket list under a lock, paid
at per-dispatch / per-part granularity (never per row), so the cost is
noise next to the work it measures.  server/app.py Metrics.render pulls
`render_all()` into /metrics with `# HELP` / `# TYPE` annotations.

The standard instruments are module attributes (QUERY_DURATION etc.) so
call sites hold direct references — no registry lookup on the hot path.
"""

from __future__ import annotations

import bisect
import threading


class Histogram:
    """One fixed-bucket histogram: cumulative `le` buckets + sum/count,
    rendered in Prometheus text exposition format."""

    def __init__(self, name: str, help_text: str, buckets):
        self.name = name
        self.help = help_text
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self._mu = threading.Lock()
        # per-bucket increments (cumulated at render time) + +Inf slot
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0

    def observe(self, v: float) -> None:
        i = bisect.bisect_left(self.buckets, v)
        with self._mu:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    def snapshot(self) -> tuple[list[int], float, int]:
        """(cumulative bucket counts incl. +Inf, sum, count)."""
        with self._mu:
            counts = list(self._counts)
            s, c = self._sum, self._count
        cum = []
        acc = 0
        for n in counts:
            acc += n
            cum.append(acc)
        return cum, s, c

    def render(self) -> list[str]:
        cum, s, c = self.snapshot()
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} histogram"]
        for le, n in zip(self.buckets, cum):
            le_s = format(le, "g")
            out.append(f'{self.name}_bucket{{le="{le_s}"}} {n}')
        out.append(f'{self.name}_bucket{{le="+Inf"}} {cum[-1]}')
        out.append(f"{self.name}_sum {format(s, 'g')}")
        out.append(f"{self.name}_count {c}")
        return out

    def reset(self) -> None:
        with self._mu:
            self._counts = [0] * (len(self.buckets) + 1)
            self._sum = 0.0
            self._count = 0


_registry: dict[str, Histogram] = {}
_registry_mu = threading.Lock()


def histogram(name: str, help_text: str, buckets) -> Histogram:
    with _registry_mu:
        h = _registry.get(name)
        if h is None:
            h = _registry[name] = Histogram(name, help_text, buckets)
        return h


def render_all() -> list[str]:
    with _registry_mu:
        hs = sorted(_registry.values(), key=lambda h: h.name)
    out = []
    for h in hs:
        out.extend(h.render())
    return out


def names() -> set:
    with _registry_mu:
        return set(_registry)


def reset_all() -> None:
    with _registry_mu:
        hs = list(_registry.values())
    for h in hs:
        h.reset()


# ---- the standard query-path instruments ----

QUERY_DURATION = histogram(
    "vl_query_duration_seconds",
    "end-to-end /select query execution time",
    (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
     1.0, 2.5, 5.0, 10.0, 30.0))

DISPATCH_RTT = histogram(
    "vl_tpu_dispatch_rtt_seconds",
    "device dispatch round trip: submit to harvested result "
    "(async window units and per-leaf scans)",
    (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
     0.05, 0.1, 0.25, 0.5, 1.0))

HOST_SYNC_WAIT = histogram(
    "vl_tpu_host_sync_wait_seconds",
    "time blocked materializing one dispatch result on the host "
    "(the window's single harvest sync point)",
    (0.00001, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
     0.025, 0.05, 0.1, 0.5))

EMIT_SECONDS = histogram(
    "vl_tpu_emit_seconds",
    "host-side emit phase of one harvested dispatch unit: block "
    "materialization + downstream write (NDJSON bytes on streaming "
    "sinks) — the columnar-emit counterpart of host_sync_wait",
    (0.00001, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
     0.025, 0.05, 0.1, 0.5))

PACK_SIZE = histogram(
    "vl_tpu_pack_size_parts",
    "parts per pipeline dispatch unit (1 = unpacked part)",
    (1, 2, 3, 4, 6, 8, 12, 16, 32))

PRUNE_RATIO = histogram(
    "vl_tpu_bloom_prune_ratio",
    "fraction of probed candidate blocks killed per bloom keep-mask "
    "probe (the filter-index kill path)",
    (0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0))

SCHED_QUEUE_WAIT = histogram(
    "vl_sched_queue_wait_seconds",
    "admission-queue wait before a query starts executing (0 = "
    "admitted immediately; sched/admission.py)",
    (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
     5.0, 10.0, 30.0))

SLOT_WAIT = histogram(
    "vl_sched_slot_wait_seconds",
    "wait for a device dispatch submit slot from the shared "
    "scheduler, incl. harvesting own units under contention "
    "(sched/scheduler.py, leased per pipeline dispatch unit)",
    (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
     0.05, 0.1, 0.25, 0.5, 1.0))

# cost-model accountability (obs/explain.py): per-query relative error
# |predicted - actual| / actual of the plan-time pricing pass, one
# histogram per priced dimension.  EWMA drift (backend change, host-link
# degradation, workload shift) shows up here as a rightward creep —
# alarmable long before the VL_INFLIGHT=auto window or a future
# priced-admission gate start making bad calls on stale rates.
_COST_ERR_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.0,
                     4.0, 8.0, 16.0)

COST_ERR_DURATION = histogram(
    "vl_cost_model_rel_error_duration",
    "relative error of the plan-time predicted execution duration vs "
    "the measured exec time (|pred-actual|/actual, per priced query)",
    _COST_ERR_BUCKETS)

COST_ERR_BYTES = histogram(
    "vl_cost_model_rel_error_bytes",
    "relative error of the plan-time predicted bytes scanned vs the "
    "query's actual bytes_scanned counter",
    _COST_ERR_BUCKETS)

COST_ERR_DISPATCHES = histogram(
    "vl_cost_model_rel_error_dispatches",
    "relative error of the planned dispatch-unit count vs the units "
    "actually submitted through the pipeline window",
    _COST_ERR_BUCKETS)

NET_FIRST_FRAME = histogram(
    "vl_net_first_frame_seconds",
    "cluster sub-query round trip to the node's first response frame "
    "(the hedging EWMA feeds on the same measurement — "
    "server/netrobust.py)",
    (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
     1.0, 2.5, 5.0, 10.0))

FILTER_INDEX_BUILD = histogram(
    "vl_filter_index_build_seconds",
    "wall time building one sealed part's v2 filter-index sidecar "
    "(split-block planes + xor aggregates + maplets, "
    "storage/filterindex — paid once per part at merge/flush seal)",
    (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
     2.5, 5.0))

INGEST_FRESHNESS = histogram(
    "vl_ingest_freshness_seconds",
    "how long flushed rows sat in memory: flush time minus the oldest "
    "flushed in-memory part's creation time (storage/datadb.py "
    "flush_inmemory_parts — the part-visible half of the freshness "
    "watermark pair)",
    (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0))

INGEST_TO_QUERYABLE = histogram(
    "vl_ingest_to_queryable_seconds",
    "accept wall clock to rows queryable: observed per batch at the "
    "storage chokepoint (snapshot_parts serves in-memory parts the "
    "moment must_add returns — obs/ingestledger.py)",
    (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
     2.5, 5.0, 10.0, 30.0))

MERGE_SECONDS = histogram(
    "vl_storage_merge_duration_seconds",
    "wall time of one background part merge (small/big tier "
    "compactions and force merges, storage/datadb.py)",
    (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
     30.0, 60.0))

INGEST_BLOCK_BUILD = histogram(
    "vl_ingest_block_build_seconds",
    "wall time of one format-independent block build: values encode + "
    "token blooms for one ingested batch, serial or sharded across the "
    "VL_BLOCK_BUILD_THREADS pool (storage/block_build.py, observed at "
    "the DataDB must_add chokepoint)",
    (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
     2.5, 5.0))
