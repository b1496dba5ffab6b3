"""End-to-end LogsQL benchmark: the 5 BASELINE.md configs through the REAL
query path (engine.searcher.run_query + tpu.batch.BatchRunner), not a
hand-staged kernel (round-1 weakness #2).

Data is generated vlogsgenerator-style into a real Storage (columnar fast
path), force-merged to one part, then each config runs twice — CPU executor
(the correctness oracle / baseline) and the TPU batch runner — with FULL
bitmap equality checked over every row of every block (not a sample).

Prints ONE JSON line:
  {"metric": ..., "value": <config-3 regex-scan rows/s/chip on device>,
   "unit": "rows/s", "vs_baseline": <device/cpu speedup on config 3>, ...}

vs_baseline is against this repo's own CPU executor: the reference's Go
toolchain is not present in this image (`go` binary absent), so the Go
numbers for BASELINE configs 1-5 cannot be produced here; the stderr
comment records that explicitly.

Timing discipline: a warmup run (compile + staging) precedes every timer
and every timed query includes its result downloads — these are
end-to-end latencies of run_query, not kernel times.

One process holds the chip: the Pallas micro-bench runs in THIS process
after the five configs (a child could not open the device), and its
failure fails the run.  Off-TPU the script exits non-zero before timing
anything: a CPU number is never printed under a per-chip metric name.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

# every config runs on the CPU executor first (the oracle): a warm
# per-part result cache would then REPLAY those parts to the device run
# instead of dispatching them, and the timing would be the cache's
os.environ.setdefault("VL_RESULT_CACHE", "0")

NS = 1_000_000_000
T0 = 1_753_660_800_000_000_000  # 2025-07-28T00:00:00Z
N_ROWS = int(os.environ.get("BENCH_ROWS", "4000000"))
N_STREAMS = 8
REPS = 3

WORDS = ["ok", "cache miss", "retry", "connection reset by peer",
         "deadline exceeded", "flushed wal segment"]
VERBS = ["GET", "POST", "PUT", "DELETE"]


def build_storage(path: str):
    """Generate N_ROWS rows into one force-merged part (columnar fast path:
    build_block_from_columns avoids the per-row LogRows loop)."""
    from victorialogs_tpu.storage.block import build_block_from_columns
    from victorialogs_tpu.storage.log_rows import LogRows, TenantID
    from victorialogs_tpu.storage.storage import Storage

    ten = TenantID(0, 0)
    s = Storage(path, retention_days=100000, flush_interval=3600)

    # mint the stream ids exactly the way normal ingestion does
    lr = LogRows(stream_fields=["app"])
    for k in range(N_STREAMS):
        lr.add(ten, T0, [("app", f"app{k}"), ("_msg", "x")])
    sids = list(lr.stream_ids)
    tags = list(lr.stream_tags_str)

    msgs = []
    traces = []
    for i in range(N_ROWS):
        msgs.append(f"{VERBS[i & 3]} /api/items/{i % 99991} "
                    f"status={200 if i % 7 else 500} dur={i % 907}ms "
                    f"msg={WORDS[i % 6]}")
        traces.append(f"tok{i % 500000}")

    pt = s._get_partition(T0 // NS // 86400)
    pt.idb.must_register_streams(list(zip(sids, tags)))
    blocks = []
    per_stream = N_ROWS // N_STREAMS
    for k in range(N_STREAMS):
        lo, hi = k * per_stream, (k + 1) * per_stream
        ts = T0 + np.arange(lo, hi, dtype=np.int64) * 1_000_000  # 1ms apart
        for j in range(lo, hi, 131072):
            je = min(j + 131072, hi)
            cols = {"app": [f"app{k}"] * (je - j),
                    "_msg": msgs[j:je],
                    "trace": traces[j:je]}
            blocks.append(build_block_from_columns(
                sids[k], ts[j - lo:je - lo], cols, stream_tags_str=tags[k]))
    pt.ddb.must_add_blocks(blocks)
    pt.debug_flush()
    pt.force_merge()
    return s, ten


def collect_bitmaps(storage, ten, query):
    """Run a query and capture the exact per-block selected-row sets."""
    from victorialogs_tpu.engine.searcher import run_query
    got = {}

    def sink(br):
        if br._bs is not None:
            key = (br._bs.part.uid, br._bs.block_idx)
            got[key] = np.array(br._sel)
    run_query(storage, [ten], query, write_block=sink, timestamp=T0)
    return got


def run_config(storage, ten, query, runner, scan_rows, reps=REPS,
               warmup=True):
    """Time a query; returns (p50_s, rows_per_sec, result_rows)."""
    from victorialogs_tpu.engine.searcher import run_query_collect
    if warmup:  # compile + staging cache (device path)
        rows = run_query_collect(storage, [ten], query, timestamp=T0,
                                 runner=runner)
    times = []
    for _ in range(reps):
        t0 = time.time()
        rows = run_query_collect(storage, [ten], query, timestamp=T0,
                                 runner=runner)
        times.append(time.time() - t0)
    p50 = statistics.median(times)
    return p50, scan_rows / p50, rows


def bitmap_equal(storage, ten, query, runner):
    """Full bitmap equality over ALL rows: CPU vs device path."""
    from victorialogs_tpu.engine.searcher import run_query
    cpu = collect_bitmaps(storage, ten, query)
    dev = {}

    def sink(br):
        if br._bs is not None:
            key = (br._bs.part.uid, br._bs.block_idx)
            dev[key] = np.array(br._sel)
    run_query(storage, [ten], query, write_block=sink, timestamp=T0,
              runner=runner)
    if set(cpu) != set(dev):
        return False
    return all(np.array_equal(cpu[k], dev[k]) for k in cpu)


def main():
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"bench.py: jax backend is {backend!r}, not tpu — this "
                 f"benchmark reports per-chip numbers and runs only on "
                 f"the chip (tests and make check cover the CPU)")

    t0 = time.time()
    tmp = tempfile.mkdtemp(prefix="vlbench")
    storage, ten = build_storage(tmp)
    gen_s = time.time() - t0

    from victorialogs_tpu.tpu.batch import BatchRunner
    runner = BatchRunner()

    from victorialogs_tpu.engine.block_result import format_rfc3339

    def ts_at(row):  # rows are 1ms apart starting at T0
        return format_rfc3339(T0 + row * 1_000_000)

    t_1m_end = ts_at(min(N_ROWS, 1_000_000))
    mid_lo, mid_hi = int(N_ROWS * 0.3), int(N_ROWS * 0.6)
    mid_range = f"[{ts_at(mid_lo)}, {ts_at(mid_hi)})"
    configs = {
        # 1: filterPhrase over a ~1M-row slice (BASELINE config 1)
        "phrase_1m": (f'_time:[2025-07-28T00:00:00Z, {t_1m_end}) '
                      f'"deadline exceeded" | stats count() c',
                      min(N_ROWS, 1_000_000)),
        # 2: filterAnd(phrase, time range) multi-block (config 2)
        "phrase_and_time": (f'_time:{mid_range} "deadline exceeded" '
                            f'| stats count() c', mid_hi - mid_lo),
        # 3: regex substring scan over every row (config 3 — headline)
        "regex_full": ('_msg:~"dead.*exceeded" | stats count() c', N_ROWS),
        # 4: stats pipe over every row (config 4; psum path exercised by
        #    tests/test_distributed.py and dryrun_multichip — one chip here)
        "stats_count_uniq": ('* | stats count() c, count_uniq(_stream_id) u',
                             N_ROWS),
        # 5: stream filter + bloom token probe on high-cardinality field
        "stream_bloom": ('{app="app3"} trace:tok123457 | stats count() c',
                         N_ROWS // N_STREAMS),
    }

    results = {}
    identical_all = True
    for name, (query, scan_rows) in configs.items():
        cpu_p50, cpu_rps, cpu_rows = run_config(storage, ten, query, None,
                                                scan_rows, reps=1,
                                                warmup=False)
        dev_p50, dev_rps, dev_rows = run_config(storage, ten, query,
                                                runner, scan_rows)
        same = (cpu_rows == dev_rows) and \
            bitmap_equal(storage, ten, query.split("|")[0], runner)
        identical_all &= same
        results[name] = {
            "cpu_p50_ms": round(cpu_p50 * 1e3, 1),
            "tpu_p50_ms": round(dev_p50 * 1e3, 1),
            "tpu_rows_per_sec": round(dev_rps),
            "speedup": round(dev_rps / cpu_rps, 2),
            "identical": same,
        }

    # pallas scan micro-bench, in-process (this process holds the chip)
    import bench_pallas
    pallas_info = bench_pallas.run()
    identical_all &= pallas_info["identical"]

    headline = results["regex_full"]
    out = {
        "metric": "logsql_e2e_regex_scan_rows_per_sec_per_chip",
        "value": headline["tpu_rows_per_sec"],
        "unit": "rows/s",
        "vs_baseline": headline["speedup"],
        "baseline_kind": "own_cpu_executor (Go toolchain absent in image)",
        "identical_hit_sets": identical_all,
        "backend": backend,
        "n_rows": N_ROWS,
        "configs": results,
        "pallas": pallas_info,
    }
    print(json.dumps(out))
    print(f"# end-to-end via run_query+BatchRunner; gen={gen_s:.1f}s "
          f"backend={backend} configs=5 full_bitmap_equality="
          f"{identical_all}; Go reference unavailable (no go toolchain) — "
          f"vs_baseline is vs this repo's CPU executor", file=sys.stderr)
    storage.close()
    if not identical_all:
        sys.exit(1)


if __name__ == "__main__":
    main()
