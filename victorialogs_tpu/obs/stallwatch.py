"""The stall line that is always on.

A server that stops answering for a second leaves nothing behind unless
something was already watching: spans exist only under ``?trace=1``.
This module is that something, started with the server and costing the
request path nothing (no per-request work, no new lock on it):

- a ``gc.callbacks`` hook that times every collection: generation 2 in
  ``vl_gc_pause_seconds_total`` / ``vl_gc_collections_total``,
  generations 0-1 in ``vl_gc_young_pause_seconds_total`` /
  ``vl_gc_young_collections_total``, and all of them into the tracing
  core's process-wide pause total, which a real span reads at open and
  at close (its ``gc_ms``, obs/tracing.py);
- ONE heartbeat thread at 10 Hz that measures how late it wakes (the
  interpreter lock or the whole process was held) and how old the
  oldest live query is (obs/activity.py).  A beat later than LATE_S, or
  an oldest query over OLDEST_QUERY_S while no query has finished for
  as long (since the last beat is not enough: a tenth of a second
  without a completion is ordinary; and a shed request, which also
  registers and ends, does not count as one), is a stall:
  ``vl_process_stalls_total`` /
  ``vl_process_stall_seconds_total`` move and ONE line per stall goes
  through the slow log's sink (stderr by default) and the event bus
  (``process_stall``): the lag, the gc seconds and compiles of the
  interval, the dispatches in flight, and every live query's phase and
  age.  A no-progress stall writes its line when it begins and counts
  its seconds while it lasts.

``vl_background_thread_cpu_seconds_total{thread=...}`` is the CPU that
the threads the program starts beside its requests have used (flush,
merge, journal, prefetch, this heartbeat, the accept loop), read from
each thread's CPU clock when /metrics is scraped: never on a request's
path.  Left out are the request handler threads and the main thread,
which a server parks in ``signal.pause()`` and an embedding program
(the benchmark's child) uses for its own control work.

Thresholds are fixed: there is no knob.  ``start()``/``close()`` are
reference-counted, so several servers of one process share one thread
and one hook.
"""

from __future__ import annotations

import gc
import json
import re
import sys
import threading
import time

from .. import sched
from . import activity, events, slowlog, tracing

BEAT_S = 0.1                 # 10 Hz
LATE_S = 0.25                # a beat this late is a stall
OLDEST_QUERY_S = 1.0         # ... as is a query this old with none finishing

# connection-lifetime endpoints: their age says nothing about a stall
_LIFETIME_ENDPOINTS = frozenset(("/select/logsql/tail",
                                 "/select/logsql/standing_query"))

_mu = threading.Lock()       # start/close and the counters; never taken
#                              on a request's path
_counts = {"stalls": 0, "stall_seconds": 0.0}
_gc = {"collections": 0, "pause_seconds": 0.0,
       "young_collections": 0, "young_pause_seconds": 0.0, "t0": None}
_refs = 0
_watch = None

# the ThreadingHTTPServer's handler threads ("Thread-7
# (process_request_thread)") serve requests: their CPU is the requests'
# (the main thread is left out too: the module docstring)
_REQUEST_THREAD = "(process_request_thread)"
# a thread's last CPU reading and label, by thread; the sums of those
# that ended, by label (so the counter never falls)
_cpu_seen: dict = {}
_cpu_ended: dict = {}


def _on_gc(phase: str, info: dict) -> None:
    # collections never nest and run with the interpreter lock held: the
    # start/stop pair needs no lock of its own
    if phase == "start":
        _gc["t0"] = time.perf_counter_ns()
        return
    if _gc["t0"] is None:
        return
    ns = time.perf_counter_ns() - _gc["t0"]
    _gc["t0"] = None
    tracing.add_gc_pause(ns)
    if info["generation"] == 2:
        _gc["pause_seconds"] += ns / 1e9
        _gc["collections"] += 1
    else:
        _gc["young_pause_seconds"] += ns / 1e9
        _gc["young_collections"] += 1


def _compiles() -> int:
    """Compile requests so far; 0 in a process that never loaded the
    device plane (reading them must not import jax)."""
    tpu = sys.modules.get("victorialogs_tpu.tpu")
    return tpu.compile_stats()["jit_compiles_total"] if tpu else 0


class StallWatch:
    """The heartbeat.  `beat(lag_s)` is one beat's work, so a test can
    hand it a late beat without waiting for one."""

    def __init__(self, beat_s: float = BEAT_S):
        self.beat_s = beat_s
        self._stop = threading.Event()
        self._thread = None
        self._in_stall = False
        self._last = self._marks()
        self._quiet_s = 0.0          # since a query last finished

    @staticmethod
    def _marks() -> dict:
        started, live = activity.progress_counts()
        # a shed request registers and ends too: a burst of sheds is
        # what a stall looks like, not queries finishing
        return {"gc_s": _gc["pause_seconds"], "gc_n": _gc["collections"],
                "compiles": _compiles(),
                "finished": started - live - sched.rejected_total()}

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="vl-stallwatch")
        self._thread.start()

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _run(self) -> None:
        due = time.monotonic() + self.beat_s
        while not self._stop.wait(max(0.0, due - time.monotonic())):
            now = time.monotonic()
            self.beat(now - due)
            due = now + self.beat_s

    def beat(self, lag_s: float) -> bool:
        """One beat that woke `lag_s` after it was due; True when it
        wrote a stall line."""
        marks = self._marks()
        last, self._last = self._last, marks
        queries = [q for q in activity.active_snapshot()
                   if q["endpoint"] not in _LIFETIME_ENDPOINTS]
        oldest = max((q["duration_s"] for q in queries), default=0.0)
        if marks["finished"] != last["finished"]:
            self._quiet_s = 0.0
        else:
            self._quiet_s += self.beat_s + max(lag_s, 0.0)
        stuck = oldest > OLDEST_QUERY_S and \
            self._quiet_s > OLDEST_QUERY_S - self.beat_s / 2
        late = lag_s >= LATE_S
        begins = stuck and not self._in_stall
        self._in_stall = stuck
        if not (late or stuck):
            return False
        with _mu:
            _counts["stall_seconds"] += lag_s if late else self.beat_s
            if late or begins:
                _counts["stalls"] += 1
        if not (late or begins):
            return False        # a no-progress stall that goes on
        rec = {
            "msg": "process stall",
            "kind": "late_beat" if late else "no_progress",
            "lag_ms": round(lag_s * 1e3, 3),
            "oldest_query_s": round(oldest, 3),
            "gc_s": round(marks["gc_s"] - last["gc_s"], 6),
            "gc_collections": marks["gc_n"] - last["gc_n"],
            "compiles": marks["compiles"] - last["compiles"],
            "dispatches_in_flight": sched.scheduler().in_flight(),
            "queries": [{"qid": q["qid"], "endpoint": q["endpoint"],
                         "phase": q["phase"],
                         "age_s": round(q["duration_s"], 3)}
                        for q in queries],
            # vlint: allow-wall-clock(log-line timestamp is real wall time)
            "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        slowlog.write_line(json.dumps(rec, ensure_ascii=False,
                                      separators=(",", ":")))
        events.emit("process_stall", kind=rec["kind"],
                    lag_ms=rec["lag_ms"],
                    oldest_query_s=rec["oldest_query_s"],
                    gc_s=rec["gc_s"], compiles=rec["compiles"],
                    dispatches_in_flight=rec["dispatches_in_flight"],
                    live_queries=len(queries))
        return True


def start() -> None:
    """Called by a server as it starts; the first caller installs the
    hook and starts the thread."""
    global _refs, _watch
    with _mu:
        _refs += 1
        if _refs == 1:
            gc.callbacks.append(_on_gc)
            tracing.set_gc_hooked(True)
            _watch = StallWatch()
            _watch.start()


def close() -> None:
    global _refs, _watch
    with _mu:
        _refs -= 1
        watch = None
        if _refs == 0:
            watch, _watch = _watch, None
            gc.callbacks.remove(_on_gc)
            tracing.set_gc_hooked(False)
    if watch is not None:
        watch.close()


def thread_label(name: str) -> str:
    """A thread's name with its number dropped: "vl-prefetch_0" ->
    "vl-prefetch", "Thread-3 (serve_forever)" -> "Thread (serve_forever)"."""
    return re.sub(r"(?:[-_]\d+)+(?=\W|$)", "", name)


def _background_cpu() -> dict:
    """{label: CPU seconds} of every thread that serves no request, the
    ended ones at their last reading.  Called under _mu."""
    live = {}
    main = threading.main_thread()
    for t in threading.enumerate():
        if t is main or t.ident is None or \
                t.name.endswith(_REQUEST_THREAD):
            continue
        try:
            secs = time.clock_gettime(time.pthread_getcpuclockid(t.ident))
        except OSError:         # it ended since enumerate()
            continue
        live[t] = (thread_label(t.name), secs)
    for t, (label, secs) in _cpu_seen.items():
        if t not in live:
            _cpu_ended[label] = _cpu_ended.get(label, 0.0) + secs
    _cpu_seen.clear()
    _cpu_seen.update(live)
    out = dict(_cpu_ended)
    for label, secs in live.values():
        out[label] = out.get(label, 0.0) + secs
    return out


def metrics_samples() -> list[tuple[str, dict, float]]:
    with _mu:
        stalls, stall_s = _counts["stalls"], _counts["stall_seconds"]
        cpu = _background_cpu()
    return [("vl_process_stalls_total", {}, stalls),
            ("vl_process_stall_seconds_total", {}, stall_s),
            ("vl_gc_collections_total", {}, _gc["collections"]),
            ("vl_gc_pause_seconds_total", {}, _gc["pause_seconds"]),
            ("vl_gc_young_collections_total", {},
             _gc["young_collections"]),
            ("vl_gc_young_pause_seconds_total", {},
             _gc["young_pause_seconds"])] + [
        ("vl_background_thread_cpu_seconds_total", {"thread": label}, secs)
        for label, secs in sorted(cpu.items())]
