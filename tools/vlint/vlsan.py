"""vlsan: end-of-test runtime invariant sanitizer.

The balance checker (tools/vlint/balance.py) proves acquire/release
discipline statically; vlsan proves the SAME invariants dynamically,
after every test, over whatever the test actually executed — the
runtime twin, exactly like the VLINT_LOCK_ORDER sanitizer (now folded
under this module) cross-validates the static lock-order graph.

Wired into tests/conftest.py as an autouse fixture; ``VLSAN=0`` is the
kill switch.  After each test the sweep checks, for every subsystem
the test touched (only modules already imported are inspected — a
parser test never pays for the cluster stack):

- ``sched.check_balanced()`` — every dispatch-slot lease released, no
  query flow still attached;
- ``StagingCache.check_balanced()`` on every live cache — byte total
  equals the recomputed cost of live entries;
- bloom bank: ``_bank_bytes`` equals the sum of live charges and is
  never negative (the PR 12 double-release class), retried once after
  ``gc.collect()`` so a pending part-GC finalizer can land;
- ``events.subscriber_count()`` restored to its pre-test baseline —
  the PR 8 ``is``-matched-unsubscribe leak class;
- every live ``JournalWriter``: accepted == written + dropped +
  queued + in-flight;
- ingest row-conservation ledger (obs/ingestledger.py):
  ``check_balanced()`` — no counter negative, no tenant resolved more
  rows than entered (accepted+received >= stored+forwarded+dropped),
  replays bounded by spools;
- per-part result cache (engine/standing/resultcache.py):
  ``cache_check_balanced()`` — cache bytes equal the sum of live
  part charges and the sum of entry sizes, never negative; retried
  after ``gc.collect()`` like the bank (part-GC finalizers release);
- standing-query registry drained back to its per-test baseline — a
  leaked registration keeps a resident evaluation (and its bus
  subscription) alive forever;
- admission pools drained: zero active, zero queued in every live
  controller;
- no new non-daemon thread left running (daemon pools are process
  infrastructure; a non-daemon leak blocks interpreter exit);
- no negative counter in any metrics_samples provider that feeds
  ``Metrics.render()`` (a negative *_total means a double release /
  double count shipped).

Checks that can race an in-flight background drain (journal flush,
weakref finalizers, thread teardown) retry briefly before reporting —
a sweep must never flake a healthy test.
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time


def enabled() -> bool:
    return os.environ.get("VLSAN", "1") != "0"


def _mod(name: str):
    """The module if the test run already imported it, else None —
    sweeps only pay for subsystems actually touched."""
    return sys.modules.get(name)


class Sanitizer:
    """Per-test sweep state.  begin_test() captures baselines,
    sweep() returns a list of human-readable problems (empty = clean).
    """

    def __init__(self):
        self._subs_baseline = 0
        self._threads_baseline: set[int] = set()
        self._standing_baseline = 0

    # -- baselines --

    def begin_test(self) -> None:
        ev = _mod("victorialogs_tpu.obs.events")
        self._subs_baseline = ev.subscriber_count() if ev else 0
        self._threads_baseline = {
            t.ident for t in threading.enumerate() if not t.daemon}
        sm = _mod("victorialogs_tpu.engine.standing.manager")
        self._standing_baseline = \
            len(sm.standing_snapshot()) if sm else 0

    # -- the sweep --

    def sweep(self) -> list[str]:
        problems: list[str] = []
        problems += self._check_sched()
        problems += self._check_staging()
        problems += self._check_bank()
        problems += self._check_result_cache()
        problems += self._check_standing()
        problems += self._check_subscribers()
        problems += self._check_journal()
        problems += self._check_ingest_ledger()
        problems += self._check_admission()
        problems += self._check_threads()
        problems += self._check_counters()
        return problems

    @staticmethod
    def _retry(fn, tries: int = 4, delay: float = 0.05):
        """(ok, detail) checks that may race a background drain."""
        ok, detail = fn()
        for _ in range(tries - 1):
            if ok:
                break
            time.sleep(delay)
            ok, detail = fn()
        return ok, detail

    def _check_sched(self) -> list[str]:
        sched = _mod("victorialogs_tpu.sched.scheduler")
        if sched is None:
            return []
        ok, _ = self._retry(
            lambda: (sched.check_balanced(), ""))
        if not ok:
            snap = sched.scheduler().snapshot()
            return [f"sched.check_balanced() failed: "
                    f"in_flight={snap['in_flight']} "
                    f"flows={snap['flows']} — a dispatch-slot lease "
                    f"leaked past the query's device_slots scope"]
        return []

    def _check_staging(self) -> list[str]:
        layout = _mod("victorialogs_tpu.tpu.layout")
        if layout is None:
            return []
        out = []
        for c in layout.staging_caches():
            if not c.check_balanced():
                s = c.stats()
                out.append(f"StagingCache.check_balanced() failed: "
                           f"bytes={s['bytes']} entries={s['entries']}"
                           f" — a staged entry's charge diverged from "
                           f"its cost")
        return out

    def _check_bank(self) -> list[str]:
        fb = _mod("victorialogs_tpu.storage.filterbank")
        if fb is None:
            return []

        def probe():
            ok, detail = fb.bank_check_balanced()
            if not ok:
                # a dead part's finalizer may still be queued
                gc.collect()
                ok, detail = fb.bank_check_balanced()
            return ok, detail

        ok, detail = self._retry(probe, tries=2)
        if not ok:
            return [f"bloom bank imbalance: {detail} — a charge was "
                    f"released twice or never released "
                    f"(VL_BLOOM_BANK_MAX_BYTES budget corrupt)"]
        return []

    def _check_result_cache(self) -> list[str]:
        rc = _mod("victorialogs_tpu.engine.standing.resultcache")
        if rc is None:
            return []

        def probe():
            ok, detail = rc.cache_check_balanced()
            if not ok:
                # a dead part's finalizer may still be queued
                gc.collect()
                ok, detail = rc.cache_check_balanced()
            return ok, detail

        ok, detail = self._retry(probe, tries=2)
        if not ok:
            return [f"result cache imbalance: {detail} — a part charge "
                    f"was released twice or never released "
                    f"(VL_RESULT_CACHE_MAX_BYTES budget corrupt)"]
        return []

    def _check_standing(self) -> list[str]:
        sm = _mod("victorialogs_tpu.engine.standing.manager")
        if sm is None:
            return []
        base = self._standing_baseline
        ok, detail = self._retry(
            lambda: sm.standing_check_drained(baseline=base))
        if not ok:
            return [f"standing registry not drained: {detail} — a "
                    f"registration leaked past its last subscriber "
                    f"(the entry keeps a resident evaluation alive)"]
        return []

    def _check_subscribers(self) -> list[str]:
        ev = _mod("victorialogs_tpu.obs.events")
        if ev is None:
            return []
        base = self._subs_baseline
        ok, _ = self._retry(
            lambda: (ev.subscriber_count() <= base, ""))
        if not ok:
            return [f"events.subscriber_count()="
                    f"{ev.subscriber_count()} > baseline {base} — a "
                    f"subscriber (JournalWriter?) leaked its bus "
                    f"subscription (the PR 8 is-vs-== unsubscribe "
                    f"class)"]
        return []

    def _check_journal(self) -> list[str]:
        jr = _mod("victorialogs_tpu.obs.journal")
        if jr is None:
            return []
        out = []
        for w in jr.live_writers():
            ok, detail = self._retry(w.check_balanced)
            if not ok:
                out.append(f"journal writer (app={w.app}) accounting "
                           f"broken: {detail}")
        return out

    def _check_ingest_ledger(self) -> list[str]:
        il = _mod("victorialogs_tpu.obs.ingestledger")
        if il is None:
            return []
        # rows may legitimately still be in flight (a spool the test
        # never drained), but no counter may go NEGATIVE and no tenant
        # may resolve more rows than entered — retried because a
        # storage roll can race the sweep by one flush
        ok, detail = self._retry(
            lambda: ((not il.check_balanced()),
                     "; ".join(il.check_balanced())))
        if not ok:
            return [f"ingest ledger conservation violated: {detail} — "
                    f"a hop rolled stored/forwarded/dropped without a "
                    f"matching accepted/received entry (or double-"
                    f"counted a terminal state)"]
        return []

    def _check_admission(self) -> list[str]:
        adm = _mod("victorialogs_tpu.sched.admission")
        if adm is None:
            return []

        def probe():
            for snap in adm.admission_snapshots():
                if snap["active"] or snap["queued"]:
                    return False, (f"pool={snap['pool']} "
                                   f"active={snap['active']} "
                                   f"queued={snap['queued']}")
            return True, ""

        # connection-lifetime endpoints (/tail) release admission only
        # when the ~1s poll loop notices the disconnect — give a just-
        # closed connection that long before calling it a leak (the
        # wait is only paid when the first probe fails)
        ok, detail = self._retry(probe, tries=10, delay=0.25)
        if not ok:
            return [f"admission pool not drained after test: {detail}"
                    f" — an _Admission scope leaked"]
        return []

    def _check_threads(self) -> list[str]:
        def probe():
            leaked = [t for t in threading.enumerate()
                      if not t.daemon and t.is_alive()
                      and t.ident not in self._threads_baseline]
            # vl-prefetch workers are non-daemon by stdlib design
            # (ThreadPoolExecutor); one owned by a still-reachable
            # runner is infrastructure, not a leak — a module-scoped
            # runner fixture legitimately outlives the test that made
            # it spawn the pool, and close() exists for owners.  Only
            # ownerless survivors count.
            prefetch = [t for t in leaked
                        if t.name.startswith("vl-prefetch")]
            if prefetch:
                batch = _mod("victorialogs_tpu.tpu.batch")
                owned = batch.live_prefetch_pools() if batch else 0
                if len(prefetch) <= owned:
                    leaked = [t for t in leaked if t not in prefetch]
            # vl-block-build workers: same ThreadPoolExecutor pattern —
            # a pool owned by a still-open DataDB is infrastructure
            # (DataDB.close() shuts it down); only ownerless survivors
            # count
            builders = [t for t in leaked
                        if t.name.startswith("vl-block-build")]
            if builders:
                bb = _mod("victorialogs_tpu.storage.block_build")
                owned = bb.live_build_pools() if bb else 0
                if len(builders) <= owned:
                    leaked = [t for t in leaked if t not in builders]
            # vl-ingest-encode workers: the refcounted shared encoder
            # pool (server/wire_ingest.py) spawns workers lazily, so a
            # function-scoped frontend can mint one that a module-scoped
            # frontend's reference keeps alive past this test;
            # release_pool() joins them when the LAST owner closes —
            # only ownerless survivors count
            encoders = [t for t in leaked
                        if t.name.startswith("vl-ingest-encode")]
            if encoders:
                wi = _mod("victorialogs_tpu.server.wire_ingest")
                if wi is not None and wi.live_pool_refs() > 0:
                    leaked = [t for t in leaked if t not in encoders]
            if leaked:
                # an abandoned ThreadPoolExecutor's workers exit once
                # the executor is collected (its weakref callback
                # drops a sentinel into the work queue) — give a
                # dropped-on-the-floor runner that chance before
                # calling its pool a leak
                gc.collect()
                return False, ", ".join(t.name for t in leaked)
            return True, ""

        ok, detail = self._retry(probe, tries=6, delay=0.1)
        if not ok:
            return [f"non-daemon thread(s) leaked: {detail} — they "
                    f"block interpreter exit; join them in the test "
                    f"or mark the worker daemon"]
        return []

    def _check_counters(self) -> list[str]:
        out = []
        for modname, provider in (
                ("victorialogs_tpu.obs.events", "metrics_samples"),
                ("victorialogs_tpu.obs.journal", "metrics_samples"),
                ("victorialogs_tpu.obs.ingestledger",
                 "metrics_samples"),
                ("victorialogs_tpu.obs.activity", "metrics_samples"),
                ("victorialogs_tpu.sched.scheduler", "metrics_samples"),
                ("victorialogs_tpu.sched.admission", "metrics_samples"),
                ("victorialogs_tpu.server.cluster",
                 "wire_metrics_samples"),
                ("victorialogs_tpu.server.netrobust",
                 "metrics_samples"),
                ("victorialogs_tpu.engine.standing.resultcache",
                 "metrics_samples"),
                ("victorialogs_tpu.engine.standing.manager",
                 "metrics_samples")):
            mod = _mod(modname)
            fn = getattr(mod, provider, None) if mod else None
            if fn is None:
                continue
            for base, labels, v in fn():
                if base.endswith("_total") and v < 0:
                    out.append(f"negative counter {base}{labels or ''}"
                               f"={v} from {modname} — a double "
                               f"release/decrement shipped")
        return out


# ---------------- lock-order runtime (VLINT_LOCK_ORDER=1) ----------------
#
# The pre-existing opt-in lock-order sanitizer, folded under the vlsan
# umbrella: install at conftest import, check at session finish.

def install_lock_order():
    """Install the acquisition-order-recording lock shim when
    VLINT_LOCK_ORDER=1 (else None)."""
    if os.environ.get("VLINT_LOCK_ORDER") != "1":
        return None
    from .runtime import install
    return install()


def lock_order_problems(sanitizer, repo_root: str) -> list[str]:
    """Session-end check: the observed acquisition graph must be
    acyclic and stay acyclic when merged with the static graph —
    INCLUDING the v3 effect-graph's RPC edges (a lock held across a
    cluster RPC feeds the remote handler's acquisitions: on a combined
    frontend+storage node that closes cycles no single process's
    observed order ever shows)."""
    from .effects import static_rpc_lock_edges
    from .locks import build_static_graph
    paths = [os.path.join(repo_root, "victorialogs_tpu")]
    edges, site_map = build_static_graph(paths, root=repo_root)
    edges |= static_rpc_lock_edges(paths, root=repo_root)
    return sanitizer.check_static_consistency(edges, site_map)
