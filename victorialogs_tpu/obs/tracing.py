"""Hierarchical per-query span trees (the vltrace core).

A trace is a tree of Spans with monotonic (perf_counter) timings and
typed attributes (counters via add(), values via set()).  The API is
context-manager-only:

    root = tracing.make_root("query", query=qs)
    with tracing.activate(root):            # sets the ambient span
        ...
        sp = tracing.current_span()
        with sp.span("harvest", unit=3) as h:   # child span
            h.add("rows_downloaded", n)
    tree = root.to_dict()

A served select request makes its root where the request arrives
(server/app.py, `request_root("request", ...)`, before the admission
gate) and the handler hangs its `query` span under it with
`make_child()`, so one tree runs from the socket to the device.  Only a
`request_root` carries `start_unix_ns`, the wall clock read beside its
perf_counter start: any span's absolute time is `start_unix_ns +
start_ms`, the clock a device profile's gaps are converted to.

Every real span also carries `gc_ms`: the collector's pauses, any
generation, run by any thread, inside the span.  It is the difference of
one process-wide total of pause nanoseconds (`gc_pause_ns`) read before
the span's start and after its end, so the span's own reads fall outside
its duration.  The stall watch's gc hook (obs/stallwatch.py) pushes each
pause into that total; while no hook is installed (no server started)
the total reads None and spans carry no `gc_ms`.

Direct ``Span(...)`` construction and un-with'd ``.span(...)`` calls are
forbidden outside this module by the vlint `span-discipline` checker:
the with-block is what guarantees every span closes on every exit path
(including QueryCancelled / QueryTimeoutError unwinds), which the
no-open-spans tests pin.

Propagation is ambient via a contextvars.ContextVar, so the deep layers
(filterbank prune decisions, the async pipeline window, staging, the
mesh runner) read `current_span()` without any signature threading.
contextvars do NOT cross thread spawns; the three places the query
path hands work to other threads (partition fan-out in engine/searcher,
storage-node fetches in server/cluster, the staging prefetch worker in
tpu/batch.py) re-enter the caller's span with `use_span()`.

When no trace is active, `current_span()` returns _NOOP — a shared
singleton whose span() returns a shared reusable context manager and
whose set()/add() do nothing.  No allocation, no branching beyond the
method call: the disabled path is flat (asserted by test_obs).
"""

from __future__ import annotations

import contextvars
import threading
import time

from . import events

_current: contextvars.ContextVar = contextvars.ContextVar(
    "vl_trace_span", default=None)

# real-span creation counter: tests assert a tracing-disabled workload
# creates exactly zero spans (structural proof of zero overhead)
_created = 0
_created_mu = threading.Lock()

# attrs guard: set()/add() vs to_dict() snapshot — the prefetch worker
# (re-entered via use_span) can write attrs on a span the query thread
# is serializing; only real spans pay this, the no-op path never locks
_attrs_mu = threading.Lock()

# children cap per span: a pathological query must not balloon the
# trace without bound; drops are counted on the parent
# (children_dropped).  The pipeline span accrues ~3 children per
# dispatch unit (prune top-off, submit, harvest), so this covers
# queries beyond ~1300 units — past that the trace head plus the drop
# counter is the documented tradeoff (the tree is already ~MBs there).
MAX_CHILDREN = 4096

# the collector's pause total (module docstring): written only by the
# stall watch's gc hook, which runs with the interpreter lock held
_gc = {"pause_ns": 0, "hooked": False}


def spans_created() -> int:
    return _created


def add_gc_pause(ns: int) -> None:
    """The gc hook's report of one collection's pause."""
    _gc["pause_ns"] += ns


def set_gc_hooked(hooked: bool) -> None:
    """Whether a gc hook feeds the total (the stall watch says so as it
    installs and removes its hook)."""
    _gc["hooked"] = hooked


def gc_pause_ns() -> int | None:
    """Nanoseconds every generation's collections have paused the
    process so far; None while no hook is installed."""
    return _gc["pause_ns"] if _gc["hooked"] else None


class Span:
    """One node of a trace tree.  Construct only via make_root() /
    parent.span() — see the module docstring (vlint: span-discipline)."""

    __slots__ = ("name", "t0", "t1", "attrs", "children", "gc0", "gc1")

    enabled = True

    def __init__(self, name: str, attrs: dict):
        global _created
        self.name = name
        self.gc0 = gc_pause_ns()
        self.gc1 = None
        self.t0 = time.perf_counter()
        self.t1 = None
        self.attrs = attrs
        self.children: list = []
        with _created_mu:
            _created += 1

    # -- attributes --
    def set(self, key: str, value) -> None:
        with _attrs_mu:
            self.attrs[key] = value

    def add(self, key: str, n=1) -> None:
        """Accumulate a numeric attribute (counter semantics)."""
        # one shared lock: the prefetch worker (re-entered via
        # use_span) may add to a span the query thread is concurrently
        # serializing — to_dict snapshots under the same lock
        with _attrs_mu:
            self.attrs[key] = self.attrs.get(key, 0) + n

    # -- children --
    def span(self, name: str, **attrs) -> "_SpanCtx":
        """Open a child span; must be used as a context manager."""
        return _SpanCtx(self, name, attrs)

    def attach(self, tree: dict) -> None:
        """Adopt a pre-built span dict (a storage node's remote trace)
        as a child — the scatter-gather merge point."""
        if len(self.children) < MAX_CHILDREN:
            self.children.append(tree)
        else:
            self.add("children_dropped")
            events.note("trace_children_dropped")

    # -- lifecycle --
    def close(self) -> None:
        if self.t1 is None:
            self.t1 = time.perf_counter()
            self.gc1 = gc_pause_ns()

    def open_spans(self) -> int:
        """Descendants (incl. self) not yet closed — 0 after any query
        exit path, including cancellation and deadline unwinds."""
        n = 0 if self.t1 is not None else 1
        for c in self.children:
            if isinstance(c, Span):
                n += c.open_spans()
        return n

    # -- export --
    def to_dict(self, base: float | None = None) -> dict:
        """JSON-ready tree; start_ms is relative to the root's t0 so a
        rendered trace reads as a waterfall."""
        if base is None:
            base = self.t0
        if self.t1 is None:
            # an open span (the `request` root writing its own answer)
            # reads its clock and the gc total now
            end = time.perf_counter()
            gc1 = gc_pause_ns()
        else:
            end, gc1 = self.t1, self.gc1
        out = {
            "name": self.name,
            "start_ms": round((self.t0 - base) * 1e3, 3),
            "duration_ms": round((end - self.t0) * 1e3, 3),
        }
        with _attrs_mu:
            attrs = dict(self.attrs)
        if self.gc0 is not None and gc1 is not None:
            attrs["gc_ms"] = round((gc1 - self.gc0) / 1e6, 3)
        if attrs:
            out["attrs"] = attrs
        if self.children:
            out["children"] = [
                c.to_dict(base) if isinstance(c, Span) else c
                for c in self.children]
        return out

    def flatten(self) -> dict:
        """Per-span-name aggregate {name: {count, total_ms}} — the
        slow-query log's compact summary."""
        agg: dict[str, dict] = {}

        def walk(node) -> None:
            if isinstance(node, Span):
                name = node.name
                end = node.t1 if node.t1 is not None \
                    else time.perf_counter()
                ms = (end - node.t0) * 1e3
                kids = node.children
            else:
                name = node.get("name", "?")
                ms = node.get("duration_ms", 0.0)
                kids = node.get("children", ())
            a = agg.setdefault(name, {"count": 0, "total_ms": 0.0})
            a["count"] += 1
            a["total_ms"] += ms
            for c in kids:
                walk(c)

        walk(self)
        for a in agg.values():
            a["total_ms"] = round(a["total_ms"], 3)
        return agg


def flatten_tree(tree: dict) -> dict:
    """Span.flatten over an EXPORTED to_dict() tree: per-span-name
    aggregates {name: {count, total_ms}}.  The explain=analyze graft
    (obs/explain.py) and cluster-merged traces work on dict trees —
    storage-node frames arrive serialized, never as live Spans."""
    agg: dict[str, dict] = {}

    def walk(node: dict) -> None:
        name = node.get("name", "?")
        a = agg.setdefault(name, {"count": 0, "total_ms": 0.0})
        a["count"] += 1
        a["total_ms"] += node.get("duration_ms", 0.0)
        for c in node.get("children", ()):
            walk(c)

    if tree:
        walk(tree)
    for a in agg.values():
        a["total_ms"] = round(a["total_ms"], 3)
    return agg


def iter_tree(tree: dict, name: str):
    """Yield every node of an exported span tree with the given name
    (depth-first) — the explain graft's span lookup."""
    if not tree:
        return
    stack = [tree]
    while stack:
        node = stack.pop()
        if node.get("name") == name:
            yield node
        stack.extend(node.get("children", ()))


class _SpanCtx:
    """Context manager that creates the child at __enter__ and closes
    it (and restores the ambient span) on every exit path."""

    __slots__ = ("_parent", "_name", "_attrs", "_span", "_token")

    def __init__(self, parent: Span, name: str, attrs: dict):
        self._parent = parent
        self._name = name
        self._attrs = attrs
        self._span = None
        self._token = None

    def __enter__(self) -> Span:
        sp = Span(self._name, self._attrs)
        parent = self._parent
        if len(parent.children) < MAX_CHILDREN:
            parent.children.append(sp)
        else:
            parent.add("children_dropped")
            events.note("trace_children_dropped")
        self._span = sp
        self._token = _current.set(sp)
        return sp

    def __exit__(self, exc_type, exc, tb) -> bool:
        sp = self._span
        if exc_type is not None:
            sp.attrs.setdefault("error", exc_type.__name__)
        sp.close()
        _current.reset(self._token)
        return False


class _NoopCtx:
    """Shared reusable no-op context manager (no allocation per use)."""

    __slots__ = ()

    def __enter__(self):
        return _NOOP

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


class _NoopSpan:
    """The ambient span when tracing is off: every operation is a
    constant-time no-op returning shared singletons."""

    __slots__ = ()

    enabled = False
    name = "noop"
    attrs: dict = {}
    children: list = []

    def set(self, key, value) -> None:
        pass

    def add(self, key, n=1) -> None:
        pass

    def span(self, name, **attrs):
        return _NOOP_CTX

    def attach(self, tree) -> None:
        pass

    def close(self) -> None:
        pass

    def open_spans(self) -> int:
        return 0

    def to_dict(self, base=None) -> dict:
        return {}

    def flatten(self) -> dict:
        return {}


_NOOP = _NoopSpan()
_NOOP_CTX = _NoopCtx()


def current_span():
    """The ambient span of this thread's active trace, or the shared
    no-op singleton when tracing is off."""
    sp = _current.get()
    return sp if sp is not None else _NOOP


def make_root(name: str, **attrs) -> Span:
    """A detached root span; close it by exiting activate(root)."""
    return Span(name, attrs)


def request_root(name: str, **attrs) -> Span:
    """make_root for the span a request arrives under: it also records
    `start_unix_ns`, the wall clock beside its perf_counter start, so
    every span of the tree has an absolute time (start_unix_ns +
    start_ms) that lays over a device profile."""
    root = Span(name, attrs)
    attrs["start_unix_ns"] = time.time_ns()
    return root


def make_child(parent, name: str, **attrs) -> Span:
    """A detached span hung under `parent`: like make_root (close it by
    exiting activate(child), possibly on another thread), but part of
    the parent's tree.  The query handlers use it for the `query` span
    beneath the request's root."""
    sp = Span(name, attrs)
    parent.attach(sp)
    return sp


class _Activation:
    """Dynamic extent of a trace: sets the ambient span, closes the
    root on exit.  activate(None) is a no-op extent (tracing off)."""

    __slots__ = ("_root", "_token")

    def __init__(self, root):
        self._root = root
        self._token = None

    def __enter__(self):
        if self._root is not None:
            self._token = _current.set(self._root)
        return self._root

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._root is not None:
            if exc_type is not None:
                self._root.attrs.setdefault("error", exc_type.__name__)
            self._root.close()
            _current.reset(self._token)
        return False


def activate(root) -> _Activation:
    return _Activation(root)


class _UseSpan:
    """Re-enter an existing (still-open) span in another thread — the
    propagation shim for worker fan-outs.  Does NOT close the span."""

    __slots__ = ("_span", "_token")

    def __init__(self, span):
        self._span = span
        self._token = None

    def __enter__(self):
        if self._span is not None and self._span.enabled:
            self._token = _current.set(self._span)
        return self._span

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._token is not None:
            _current.reset(self._token)
        return False


def use_span(span) -> _UseSpan:
    return _UseSpan(span)
