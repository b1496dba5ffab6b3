"""Looks into a profiler trace by hand and cuts a small fixture from it.

    python3 benchmark/tests/dump_trace.py <file.xplane.pb> [fixture.json]

Prints every plane and line with its event count and first events (name,
start, duration, stats), which is how xplane.py's assumptions (plane and
line names, the stat that names the module) were checked against a trace
from the chip; with a second argument, writes what `xplane.load` keeps of
the file, cut to the first events of each line, as the JSON fixture that
tests/test_yardstick.py reduces.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xplane  # noqa: E402

KEEP = 400


def main() -> int:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(sys.argv[1])
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:3]:
                print(f"    {ev.name!r} start_ns={ev.start_ns} "
                      f"dur_ns={ev.duration_ns} stats={dict(ev.stats)}")
    if len(sys.argv) > 2:
        planes = [(p, [(ln, [(n, int(s), int(d), {k: str(v) for k, v in st.items()})
                             for n, s, d, st in evs[:KEEP]])
                       for ln, evs in lines])
                  for p, lines in xplane.load(sys.argv[1])]
        with open(sys.argv[2], "w") as f:
            json.dump(planes, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
