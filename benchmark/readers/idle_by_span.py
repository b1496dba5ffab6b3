"""Source kind `idle_by_span`: the device's idle time charged to what the
host was doing, by overlap.

The child's reduction of the profile lists the busiest device's idle gaps
on the wall clock (`trace.gaps_unix_ns`: [start unix ns, length ns], the
longest 4000).  A traced request's tree carries `start_unix_ns` on its
root, so every span has an absolute extent, start_unix_ns + start_ms.
Every idle nanosecond of the listed gaps is charged to ONE state, the
first that holds at that instant:

  submit      some request is inside a `submit` span
  sync        else some request is inside a `device_sync` span: the host
              waits on a device that has nothing to do
  host        else some request is open, in any other span or in none
  no_request  else

spec: "state": that state's share of the listed idle time, in %; the
four sum to 100.  Absent where there is no device trace (a rehearsal) or
no tree on the wall clock (a program that records no `start_unix_ns`).
"""

import bisect
import sys

from readers import vltrace_span
from xplane import union

STATES = ("submit", "sync", "host", "no_request")
SPAN_STATE = {"submit": "submit", "device_sync": "sync"}


class Cover:
    """A union of intervals that answers how much of [a, b) it covers."""

    def __init__(self, intervals: list):
        self.iv = union(intervals)
        self.starts = [s for s, _e in self.iv]
        self.before = [0]          # covered length before interval i
        for s, e in self.iv:
            self.before.append(self.before[-1] + e - s)

    def upto(self, x: int) -> int:
        i = bisect.bisect_right(self.starts, x)
        if i == 0:
            return 0
        s, e = self.iv[i - 1]
        return self.before[i - 1] + min(x, e) - s

    def overlap(self, a: int, b: int) -> int:
        return self.upto(b) - self.upto(a)


def extents(tree: dict) -> dict:
    """{state: [[start, end) unix ns]} of one request's tree; {} when the
    tree is not on the wall clock."""
    base = tree.get("attrs", {}).get("start_unix_ns")
    if base is None:
        return {}
    out = {"submit": [], "sync": [], "host": []}

    def at(node):
        s = base + int(node["start_ms"] * 1e6)
        return [s, s + int(node["duration_ms"] * 1e6)]

    def walk(node):
        state = SPAN_STATE.get(node.get("name"))
        if state is not None:
            out[state].append(at(node))
        for c in node.get("children", []):
            walk(c)

    out["host"].append(at(tree))       # the whole request: any span or none
    walk(tree)
    return out


def charge(gaps: list, trees: list):
    """{state: idle ns} over the gaps, or None without a tree on the wall
    clock."""
    spans = {"submit": [], "sync": [], "host": []}
    found = False
    for t in trees:
        for state, iv in extents(t).items():
            spans[state] += iv
            found = True
    if not found or not gaps:
        return None
    # each cover holds the states before it too: first match wins
    in_submit = Cover(spans["submit"])
    in_sync = Cover(spans["submit"] + spans["sync"])
    in_request = Cover(spans["submit"] + spans["sync"] + spans["host"])
    out = dict.fromkeys(STATES, 0)
    for start, length in gaps:
        a, b = int(start), int(start) + int(length)
        sub = in_submit.overlap(a, b)
        syn = in_sync.overlap(a, b)
        req = in_request.overlap(a, b)
        out["submit"] += sub
        out["sync"] += syn - sub
        out["host"] += req - syn
        out["no_request"] += (b - a) - req
    return out


def read(spec: dict, ctx: dict):
    if "_idle_by_span" not in ctx:
        gaps = (ctx.get("trace") or {}).get("gaps_unix_ns") or []
        trees = [t for t in map(vltrace_span.tree, ctx["records"])
                 if t is not None] if gaps else []
        ctx["_idle_by_span"] = got = charge(gaps, trees)
        if got is not None:
            print("idle_by_span: %d gaps, %.3f s: " % (
                len(gaps), sum(got.values()) / 1e9) + ", ".join(
                    "%s %.3f s" % (k, v / 1e9) for k, v in got.items()),
                  file=sys.stderr)
    got = ctx["_idle_by_span"]
    if got is None or not sum(got.values()):
        return None
    return 100.0 * got[spec["state"]] / sum(got.values())
