"""EXPLAIN + cost-model accountability bench (obs/explain.py).

On the 32x2048 bench corpus:

- **pricing overhead**: the continuous plan-time pricing pass runs on
  every device-path query; its median cost must stay within the PR 4
  trace-overhead bound (10% + 2 ms) of VL_QUERY_PRICING=0;
- **explain=1 is O(headers)**: building the priced plan must be >= 20x
  faster than executing the query it prices, with ZERO device
  dispatches;
- **cost-model fidelity**: median relative error of the predictions
  (duration / bytes, from the completed-query records) must stay under
  the recorded bounds — the continuous accountability this PR exists
  to provide.

Writes BENCH_explain.json; `make bench-explain`.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("VL_COST_FORCE", "device")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
N_PARTS = 32
ROWS_PER_PART = 2048
QUERY = "err warn | fields _time"

# acceptance bounds (recorded into the json next to the measurements)
OVERHEAD_BOUND = 1.10     # pricing-on median <= off * 1.10 + 2ms
OVERHEAD_SLACK_MS = 2.0
PLAN_SPEEDUP_MIN = 20.0   # execution median / plan median
ERR_DURATION_BOUND = 0.75
ERR_BYTES_BOUND = 0.25


def build_storage(path):
    from victorialogs_tpu.storage import datadb
    from victorialogs_tpu.storage.log_rows import LogRows, TenantID
    from victorialogs_tpu.storage.storage import Storage
    datadb.DEFAULT_PARTS_TO_MERGE = 10 ** 9
    t0 = 1_753_660_800_000_000_000
    ten = TenantID(0, 0)
    s = Storage(path, retention_days=100000, flush_interval=3600)
    n = 0
    for _pp in range(N_PARTS):
        lr = LogRows(stream_fields=["app"])
        for _i in range(ROWS_PER_PART):
            g = n
            n += 1
            lvl = ["info", "warn", "err"][g % 3]
            lr.add(ten, t0 + g * 1_000_000, [
                ("app", f"app{g % 5}"),
                ("_msg", f"m {lvl} request x{g % 97} of {g}"),
                ("dur", str(g % 211)),
            ])
        s.must_add_rows(lr)
        s.debug_flush()
    return s, ten, t0


def measure_queries(storage, ten, t0, runner, runs):
    from victorialogs_tpu.engine.searcher import run_query_collect
    rows = run_query_collect(storage, [ten], QUERY, timestamp=t0,
                             runner=runner)     # warmup
    times = []
    for _r in range(runs):
        t = time.perf_counter()
        rows = run_query_collect(storage, [ten], QUERY, timestamp=t0,
                                 runner=runner)
        times.append(time.perf_counter() - t)
    return statistics.median(times) * 1e3, len(rows)


def measure_plan(storage, ten, t0, runner, runs):
    from victorialogs_tpu.logsql.parser import parse_query
    from victorialogs_tpu.obs import explain
    q = parse_query(QUERY, timestamp=t0)
    explain.build_plan(storage, [ten], q, runner=runner)   # warm banks
    times = []
    tree = None
    for _r in range(runs):
        t = time.perf_counter()
        tree = explain.build_plan(storage, [ten], q, runner=runner)
        times.append(time.perf_counter() - t)
    return statistics.median(times) * 1e3, tree


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=15)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    import tempfile
    from victorialogs_tpu.obs import activity
    from victorialogs_tpu.tpu.batch import BatchRunner

    os.environ["VL_INFLIGHT"] = "4"
    os.environ["VL_PACK_PARTS"] = "8"

    with tempfile.TemporaryDirectory() as tmp:
        storage, ten, t0 = build_storage(os.path.join(tmp, "data"))
        runner = BatchRunner()

        # -- pricing OFF baseline --
        os.environ["VL_QUERY_PRICING"] = "0"
        off_ms, nrows_off = measure_queries(storage, ten, t0, runner,
                                            args.runs)

        # -- pricing ON (the default) --
        os.environ.pop("VL_QUERY_PRICING", None)
        # qid set, not a length slice: the completed ring is a capped
        # deque, so indices stop meaning "new" once it wraps
        before = {r["qid"] for r in activity.completed_snapshot()}
        on_ms, nrows_on = measure_queries(storage, ten, t0, runner,
                                          args.runs)
        assert nrows_on == nrows_off, "pricing changed query results"
        priced = [r["progress"] for r in activity.completed_snapshot()
                  if r["qid"] not in before
                  and "cost_err_duration" in r["progress"]]
        assert priced, "no priced completion records"
        err_dur = statistics.median(p["cost_err_duration"]
                                    for p in priced)
        err_bytes = statistics.median(p["cost_err_bytes"]
                                      for p in priced)

        # -- explain=1: O(headers), zero dispatches --
        d0 = runner.stats()["device_calls"]
        plan_ms, tree = measure_plan(storage, ten, t0, runner,
                                     args.runs)
        d1 = runner.stats()["device_calls"]
        speedup = on_ms / plan_ms if plan_ms else float("inf")

        out = {
            "corpus": {"parts": N_PARTS, "rows_per_part": ROWS_PER_PART,
                       "query": QUERY},
            "query_ms_pricing_off": round(off_ms, 3),
            "query_ms_pricing_on": round(on_ms, 3),
            "pricing_overhead_x": round(on_ms / off_ms, 4)
            if off_ms else None,
            "explain_plan_ms": round(plan_ms, 3),
            "plan_speedup_x": round(speedup, 2),
            "plan_device_calls": d1 - d0,
            "plan_predicted": tree["predicted"],
            "cost_err_duration_median": round(err_dur, 4),
            "cost_err_bytes_median": round(err_bytes, 4),
            "bounds": {
                "overhead": f"<= off * {OVERHEAD_BOUND} "
                            f"+ {OVERHEAD_SLACK_MS}ms",
                "plan_speedup_min": PLAN_SPEEDUP_MIN,
                "err_duration": ERR_DURATION_BOUND,
                "err_bytes": ERR_BYTES_BOUND,
            },
        }
        print(json.dumps(out, indent=2))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(out, f, indent=2)
                f.write("\n")

        failures = []
        if on_ms > off_ms * OVERHEAD_BOUND + OVERHEAD_SLACK_MS:
            failures.append(
                f"pricing overhead {on_ms:.2f}ms vs bound "
                f"{off_ms * OVERHEAD_BOUND + OVERHEAD_SLACK_MS:.2f}ms")
        if speedup < PLAN_SPEEDUP_MIN:
            failures.append(f"explain=1 speedup {speedup:.1f}x < "
                            f"{PLAN_SPEEDUP_MIN}x")
        if d1 != d0:
            failures.append(f"explain=1 issued {d1 - d0} device calls")
        if err_dur > ERR_DURATION_BOUND:
            failures.append(f"duration rel-error median {err_dur:.3f} "
                            f"> {ERR_DURATION_BOUND}")
        if err_bytes > ERR_BYTES_BOUND:
            failures.append(f"bytes rel-error median {err_bytes:.3f} "
                            f"> {ERR_BYTES_BOUND}")
        if failures:
            print("BENCH FAILED:\n  " + "\n  ".join(failures))
            storage.close()
            sys.exit(1)
        print("bench-explain: PASS")
        storage.close()


if __name__ == "__main__":
    main()
