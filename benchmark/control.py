#!/usr/bin/env python3
"""The control of "how `correct` is decided", at a cell's own size.

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 \
        --seconds 51 --unreadable fresh,part:3

The system states no precision, so the control breaks one guarantee the
configuration states, "every row the set-up wrote is readable": the rows
of the fresh parts (the answer of a store that flushes later or scans
less), or of one part of the table (the answer after a part is lost).  It
is the plain reference put in the program's place with that guarantee
broken: for each seed it answers, as the server would send them, the
requests a run of that seed would sample, and hands them to the harness's
own comparison (run.check_answers: `normal_form`, the reference, the
limits), whose verdict it prints.  The limit on `mismatched` is 0, so
`correct` has to read false.  The benchmark's own runs do not run it;
benchmark/tests keeps it at a size a test run can hold.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reference  # noqa: E402
import run  # noqa: E402
import traffic as traffic_gen  # noqa: E402


def verdict(traffic: dict, layout, seed: int, seconds: float,
            unreadable: str) -> dict:
    """The harness's verdict on the control's answers to the window of
    `seed`."""
    recs = [{"req": traffic_gen.make_request(traffic, layout, seed, k, cls),
             "due": 0.0, "done": 0.0, "status": 200, "body": b""}
            for k, (_due, cls) in enumerate(
                traffic_gen.schedule(traffic, seconds))]
    ctl = reference.Reference(layout, seed,
                              reference.unreadable_rows(layout, unreadable))
    # the answers the harness will draw: the same seed, the same sample
    for r in run.pick_sample(recs, seed, int(traffic["check_sample"])).values():
        req = r["req"]
        spec = traffic["classes"][req["cls"]]["reference"]
        r["body"] = reference.render(req["endpoint"], ctl.answer(req, spec))
    return run.check_answers(recs, 0, traffic, layout, seed)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--unreadable", default="fresh",
                    help='comma-separated: "fresh", "part:<i>"')
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    for what in args.unreadable.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            v = verdict(cell["traffic"], cell["layout"], seed, args.seconds,
                        what)
            by_class = {}
            for w in v["wrong"]:
                by_class[w["cls"]] = by_class.get(w["cls"], 0) + 1
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "unreadable": what, "correct": v["correct"],
                              "checked": v["checked"],
                              "mismatched_by_class": by_class}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
