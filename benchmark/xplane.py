"""From the profiler's trace (.xplane.pb) to numbers: the reduction every
PR's per-layer device metrics go through.  Runs in the child, which holds
jax; reads only the file.

Device planes are named "/device:TPU:<n>"; their line "XLA Ops" holds one
event for every operation that ran on the device (its name is the HLO
text, "%fusion.53 = pred[...] fusion(...)"), start and duration in
nanoseconds on the trace's clock; the line "XLA Modules" holds one event
for every program run ("jit__fused_dispatch(<id>)").  Busy is the union of
the operations' intervals; the window is the traced window, the seconds
the child had the profiler on, and idle is what of it the busiest device
was not busy.
The host plane holds the child's `bench_marker` annotation, emitted at a
recorded wall-clock instant, which ties the trace's clock to the parent's
request records.
"""

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MAX_GAPS = 4000


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_name(event_name: str, module: str) -> str:
    """`<module>/<operation>` with the instance numbers dropped, so that
    the same operation sums over calls and programs."""
    m = re.match(r"%([\w\-.]+) = ", event_name)
    op = re.sub(r"[.\d]+$", "", m.group(1) if m else event_name)
    module = re.sub(r"\(\d+\)$", "", module)
    return f"{module}/{op}" if module else op


def reduce_planes(planes: list, marker_ns: int = 0,
                  traced_s: float = 0.0) -> dict:
    """planes: [(plane name, [(line name, [(event name, start_ns, dur_ns,
    stats dict)])])]; traced_s: the seconds the profiler was on (absent:
    from the first device operation to the last).  Returns {} when no
    device plane holds an operation."""
    dev = {}
    ops = {}
    marker_trace_ns = None
    for pname, lines in planes:
        if pname.startswith("/host:"):
            for _lname, events in lines:
                for name, start, _dur, _stats in events:
                    if name == "bench_marker":
                        marker_trace_ns = start
            continue
        if not pname.startswith("/device:TPU:"):
            continue
        by_line = dict(lines)
        mods = sorted((start, start + dur, name) for name, start, dur, _st
                      in by_line.get(MODULES_LINE, []))
        mod_starts = [m[0] for m in mods]
        iv = dev.setdefault(pname, [])
        for name, start, dur, _stats in by_line.get(OPS_LINE, []):
            iv.append((start, start + dur))
            i = bisect.bisect_right(mod_starts, start) - 1
            module = mods[i][2] if i >= 0 and start < mods[i][1] else ""
            key = op_name(name, module)
            ops[key] = ops.get(key, 0.0) + dur / 1e9
    dev = {p: union(iv) for p, iv in dev.items() if iv}
    if not dev:
        return {}
    t0 = min(iv[0][0] for iv in dev.values())
    t1 = max(iv[-1][1] for iv in dev.values())
    busy = {p: sum(e - s for s, e in iv) / 1e9 for p, iv in dev.items()}
    busiest = max(busy, key=busy.get)
    window_s = max(traced_s, (t1 - t0) / 1e9)
    offset = (marker_ns - marker_trace_ns) if marker_trace_ns is not None \
        and marker_ns else None
    gaps = [(b[0] - a[1], a[1]) for a, b in zip(dev[busiest],
                                                dev[busiest][1:])]
    gaps = sorted(gaps, reverse=True)[:MAX_GAPS]
    return {
        "devices": len(dev),
        "window_s": window_s,
        "busy_s": sum(busy.values()) / len(busy),
        "busy_sum_s": sum(busy.values()),
        "idle_share_pct": 100.0 * (1.0 - busy[busiest] / window_s),
        "device_ops": sorted(([k, v / len(dev)] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:10],
        # [start on the parent's wall clock (unix ns), length ns]; absent
        # when the marker was not found
        "gaps_unix_ns": [[start + offset, length] for length, start in gaps]
        if offset is not None else [],
    }


def load(path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        keep = plane.name.startswith("/device:TPU:")
        lines = []
        for line in plane.lines:
            if keep and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                if keep:
                    events.append((ev.name, ev.start_ns, ev.duration_ns, {}))
                elif ev.name == "bench_marker":
                    events.append((ev.name, ev.start_ns, ev.duration_ns, {}))
            if events:
                lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def reduce_dir(trace_dir: str, marker_ns: int, traced_s: float) -> dict:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return {}
    return reduce_planes(load(max(files, key=os.path.getmtime)), marker_ns,
                         traced_s)
