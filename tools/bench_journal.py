"""Self-telemetry journal overhead: the bench-pipeline workload with the
journal off (no bus subscriber) vs on (JournalWriter ingesting into the
same storage it queries).

Asserts (the PR acceptance bound — same shape as the PR 4 vltrace
overhead assertion in tools/bench_pipeline.py):

- journal-off is structurally zero: no subscriber, zero events counted
  for the whole off phase;
- journal-on p50 within 10% + 2 ms of journal-off on the rows query
  (every query emits exactly ONE query_done event — amortized, never
  per row/block);
- the journal actually recorded the on-phase queries (rows_written
  covers one query_done per measured run, retrievable via LogsQL over
  the system tenant).

Writes BENCH_journal.json; `make bench-journal`.
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
N_PARTS = 16
ROWS_PER_PART = 2048
QUERY = "err warn | fields _time"


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


def measure(storage, ten, t0, runner, runs):
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", type=int, default=15)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    import tempfile
    from victorialogs_tpu.obs import events, journal
    from victorialogs_tpu.tpu.batch import BatchRunner

    os.environ["VL_INFLIGHT"] = "4"
    os.environ["VL_PACK_PARTS"] = "8"

    with tempfile.TemporaryDirectory() as td:
        print(f"building {N_PARTS} x {ROWS_PER_PART} bench storage ...",
              flush=True)
        storage, ten, t0 = build_storage(td)
        runner = BatchRunner()

        # ---- journal OFF: no subscriber, structurally zero ----
        assert events.subscriber_count() == 0, \
            "bench requires a clean bus"
        c0 = events.counters()
        off_p50, off_rows = measure(storage, ten, t0, runner, args.runs)
        c1 = events.counters()
        assert c1 == c0, \
            f"journal-off phase counted events: {c0} -> {c1}"

        # ---- journal ON: writer ingesting into the SAME storage ----
        jw = journal.JournalWriter(storage, flush_ms=200)
        on_p50, on_rows = measure(storage, ten, t0, runner, args.runs)
        jw.flush()
        jstats = jw.stats()
        from victorialogs_tpu.engine.searcher import run_query_collect
        done = run_query_collect(
            storage, [journal.SYSTEM_TENANT_ID],
            '{app="victorialogs-tpu",event="query_done"} '
            '| stats count() n', timestamp=time.time_ns())
        jw.close()

        assert off_rows == on_rows
        ratio = on_p50 / max(off_p50, 1e-9)
        print(f"journal overhead (rows query, packed config): "
              f"off={off_p50:.1f} ms  on={on_p50:.1f} ms  "
              f"({ratio:.3f}x)  journal rows={jstats['rows_written']} "
              f"dropped={jstats['dropped']}")
        print(f"query_done records queryable via LogsQL: "
              f"{done[0]['n']}")

        # acceptance: within the PR 4 trace-overhead bound
        assert on_p50 <= off_p50 * 1.10 + 2.0, \
            f"journal-on overhead beyond the trace bound: " \
            f"{off_p50:.1f} ms -> {on_p50:.1f} ms"
        # one query_done per measured+warmup run, none dropped
        assert jstats["dropped"] == 0
        assert int(done[0]["n"]) >= args.runs, done

        result = {
            "shape": f"{N_PARTS}x{ROWS_PER_PART}",
            "query": QUERY,
            "runs": args.runs,
            "off_p50_ms": round(off_p50, 3),
            "on_p50_ms": round(on_p50, 3),
            "ratio": round(ratio, 4),
            "journal": jstats,
            "query_done_records": int(done[0]["n"]),
        }
        if args.json:
            with open(args.json, "w") as f:
                json.dump(result, f, indent=2, sort_keys=True)
            print(f"wrote {args.json}")
        storage.close()
    print("PASS: journal-off structurally zero, "
          "journal-on within the trace-overhead bound")
    return 0


if __name__ == "__main__":
    sys.exit(main())
