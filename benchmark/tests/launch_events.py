"""What jax's own host events inside each `vl_launch` annotation add up to.

    python3 benchmark/tests/launch_events.py <file.xplane.pb>
    python3 benchmark/tests/launch_events.py --keep <out.xplane.pb> -- \\
        --workload baseline-1chip.adhoc_scan --seed <n> --seconds 51 --trace 1

A traced `launch` span runs the jitted call under a `vl_launch` profiler
annotation (tpu/fused.py:_launch).  On each host line (one thread) every
event that lies inside a vl_launch event is charged to it by name: its
calls, its total time, its self time (total less the events directly
inside it) and its depth (1 = directly inside vl_launch).  Prints, on
standard output, vl_launch's count, mean and self time (in no jax event)
a launch, then each nested event by self time a launch.  What other
threads run while a launch waits (the runtime's `pjrt-tpu-tasks` and
`tfrt-non-blocking-queue` lines) is not charged: dump_trace.py shows
those lines.

With --keep, first one run of benchmark/run.py with the arguments after
`--`: run.py deletes its trace directory as it ends, so its newest
.xplane.pb is copied to <out.xplane.pb> on the way; then the walk of that
file.  No metric reads this: benchmark/xplane.py keeps only the device
planes and the marker of the host ones.
"""

import bisect
import glob
import os
import shutil
import sys

ANNOTATION = "vl_launch"


def walk(lines) -> dict:
    """lines: one list of (event name, start_ns, duration_ns) a host line.
    -> {"launches", "launch_ns" (their total), "self_ns" (in no nested
    event), "by": {name: [calls, total_ns, self_ns, depths]}}."""
    out = {"launches": 0, "launch_ns": 0, "self_ns": 0, "by": {}}
    by = out["by"]

    def charge(frame):
        by[frame["name"]][2] += frame["self"]

    for events in lines:
        evs = sorted((s, -d, n) for n, s, d in events)
        starts = [e[0] for e in evs]
        for ls, neg, name in evs:
            if name != ANNOTATION:
                continue
            le = ls - neg
            out["launches"] += 1
            out["launch_ns"] += le - ls
            root = {"end": le, "self": le - ls, "name": None}
            stack = [root]
            for s, nd, n in evs[bisect.bisect_left(starts, ls):
                                bisect.bisect_right(starts, le)]:
                if n == ANNOTATION or s - nd > le:
                    continue
                while len(stack) > 1 and s >= stack[-1]["end"]:
                    charge(stack.pop())
                stack[-1]["self"] += nd
                b = by.setdefault(n, [0, 0, 0, set()])
                b[0] += 1
                b[1] -= nd
                b[3].add(len(stack))
                stack.append({"end": s - nd, "self": -nd, "name": n})
            while len(stack) > 1:
                charge(stack.pop())
            out["self_ns"] += root["self"]
    return out


def load(path: str) -> list:
    """The host lines of a trace file, as walk() takes them."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [[(e.name, e.start_ns, e.duration_ns) for e in line.events]
            for plane in data.planes if plane.name.startswith("/host")
            for line in plane.lines]


def report(w: dict) -> str:
    k = max(w["launches"], 1)
    rows = [f"{ANNOTATION}: {w['launches']} events, mean "
            f"{w['launch_ns'] / k / 1e6:.4f} ms, self (in no jax event) "
            f"{w['self_ns'] / k / 1e6:.4f} ms a launch",
            "   total ms/launch  self ms/launch   calls/launch  depth  name"]
    for n, (c, ns, self_ns, depths) in sorted(
            w["by"].items(), key=lambda kv: -kv[1][2]):
        rows.append(f"{ns / k / 1e6:14.4f} {self_ns / k / 1e6:15.4f} "
                    f"{c / k:14.2f}  {sorted(depths)}  {n}")
    return "\n".join(rows)


def run_keeping(out_pb: str, run_argv: list) -> int:
    """One benchmark run whose trace is copied to out_pb before run.py
    deletes it."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import run
    rmtree = shutil.rmtree

    def keeping(path, *a, **kw):
        if str(path).endswith(".trace") and os.path.isdir(path):
            files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                              recursive=True)
            if files:
                shutil.copy(max(files, key=os.path.getmtime), out_pb)
        return rmtree(path, *a, **kw)

    shutil.rmtree = keeping
    try:
        return run.main(run_argv)
    finally:
        shutil.rmtree = rmtree


def main(argv: list) -> int:
    if argv[:1] == ["--keep"]:
        out_pb, run_argv = argv[1], argv[argv.index("--") + 1:]
        rc = run_keeping(out_pb, run_argv)
        if rc or not os.path.exists(out_pb):
            print(f"run.py exited {rc}; trace kept: "
                  f"{os.path.exists(out_pb)}", file=sys.stderr)
            return rc or 1
        path = out_pb
    else:
        path = argv[0]
    print(report(walk(load(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
