"""Slow-query log: one structured JSON line per query over the
VL_SLOW_QUERY_MS threshold (default: off).

When the threshold is armed, the query handlers force tracing on for
every query (the no-op path costs nothing when the log is off, and a
slow query without a trace is exactly the situation the log exists to
avoid), so the emitted line carries the flattened per-stage summary:

    {"msg": "slow query", "endpoint": "/select/logsql/query",
     "duration_ms": 812.4, "threshold_ms": 500.0, "query": "...",
     "trace": {"query": {"count": 1, "total_ms": 812.4},
               "harvest": {"count": 9, "total_ms": 617.0}, ...},
     "attrs": {...root span counters...}, "ts": "..."}

Lines go to stderr by default (the single binary's log stream); tests
inject their own sink via set_sink().
"""

from __future__ import annotations

import json
import sys
import time
from .. import config

from . import events

_sink = None


def set_sink(fn) -> None:
    """Test hook: fn(line_str) replaces the stderr write (None resets)."""
    global _sink
    _sink = fn


def threshold_ms() -> float | None:
    """The armed threshold, or None when the log is off."""
    v = config.env("VL_SLOW_QUERY_MS")
    if not v:
        return None
    try:
        return float(v)
    except ValueError:
        return None


def enabled() -> bool:
    return threshold_ms() is not None


def maybe_log(endpoint: str, query: str, duration_s: float,
              root=None, qid: str | None = None) -> bool:
    """Emit the slow-query line when duration exceeds the threshold.
    Returns True when a line was emitted (test convenience).

    qid: the active-query registry id (obs/activity.py) — carried on
    the line so slowlog records, ?trace=1 trees, and active_queries
    snapshots correlate by id."""
    thr = threshold_ms()
    if thr is None or duration_s * 1e3 < thr:
        return False
    rec = {
        "msg": "slow query",
        "endpoint": endpoint,
        "duration_ms": round(duration_s * 1e3, 3),
        "threshold_ms": thr,
        "query": query,
        # vlint: allow-wall-clock(log-line timestamp is real wall time)
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if qid:
        rec["qid"] = qid
    if root is not None and getattr(root, "enabled", False):
        rec["trace"] = root.flatten()
        if root.attrs:
            rec["attrs"] = root.attrs
    line = json.dumps(rec, ensure_ascii=False, separators=(",", ":"))
    # the same record rides the event bus into the self-telemetry
    # journal (obs/journal.py), so slow queries are LogsQL-queryable
    # over hours instead of scrolling off stderr; the bus suppresses
    # system-tenant queries (recursion guard) via the ambient record
    events.emit("slow_query", endpoint=endpoint, qid=qid or "",
                duration_ms=rec["duration_ms"],
                threshold_ms=thr, query=query)
    write_line(line)
    return True


def write_line(line: str) -> None:
    """One line to the log's sink: stderr, or what set_sink() put in its
    place.  Shared with the stall watch (obs/stallwatch.py), whose line
    belongs in the same stream."""
    sink = _sink
    try:
        if sink is not None:
            sink(line)
        else:
            sys.stderr.write(line + "\n")
    # vlint: allow-broad-except(a dead sink must not fail the query; counted)
    except Exception:
        # previously silent: a failing sink write now shows up as
        # vl_slowlog_emit_failures_total on /metrics
        events.note("slowlog_emit_failures")
