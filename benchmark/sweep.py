#!/usr/bin/env python3
"""The knee of an open-loop cell: one set-up, one window at each rate.

    python3 benchmark/sweep.py --workload <name> --seed <n> --seconds 20 \
        --rates 6,8,10,12

A tool for the PR that adds or re-rates a cell, not part of a run: the
cell's fixed rate (`rate_per_s` in its traffic file) is four fifths of
the highest rate here with no shed request and no backlog at the close.
Prints a table for PERF.md on standard error and no result line.
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402
import traffic as traffic_gen  # noqa: E402
from readers import client as client_reader  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    args.trace = 0
    rates = [float(r) for r in args.rates.split(",")]
    c = run.load_cell(args.workload, args.rehearsal)
    traffic = c["traffic"]
    # the gaps scale with the rate, so the highest rate's window holds
    # every request of the others: its warm-up covers them all
    child, _device, port = run.set_up(
        args, c, os.path.join(HERE, "serve.py"),
        traffic_gen.schedule(traffic, args.seconds, max(rates)))
    try:
        print("rate_per_s offered done failed p50_ms p95_ms late_p95_ms "
              "backlog_at_close", file=sys.stderr)
        for i, rate in enumerate(rates):
            sched = traffic_gen.schedule(traffic, args.seconds, rate)
            client = run.Client(port, int(traffic["client_threads"]), False)
            run.open_loop(client, run.make_requests(c, args.seed, sched,
                                                    f"s{i}_"))
            backlog = client.todo.unfinished_tasks
            client.drain(run.ANSWER_WAIT_S)
            ctx = {"records": [r for r in client.records
                               if r["status"] == 200]}
            p50, p95, late = (
                client_reader.read({"of": of, "stat": stat}, ctx)
                for of, stat in (("latency_ms", "p50"), ("latency_ms", "p95"),
                                 ("late_ms", "p95")))
            print(f"{rate} {len(sched)} {len(ctx['records'])} "
                  f"{len(sched) - len(ctx['records'])} {p50:.2f} {p95:.2f} "
                  f"{late:.3f} {backlog}", file=sys.stderr, flush=True)
            client.close()
    finally:
        child.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
