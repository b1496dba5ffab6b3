"""Emit-phase benchmark: per-row dicts + json.dumps vs the columnar
native NDJSON path, on the 32x2048 bench shape (the same storage the
pipeline bench uses).

The emit phase is everything AFTER the harvested bitmap: materializing
the selected rows and turning them into response bytes.  PR 4's traces
showed it dominating harvest (81 ms span vs 2.6 ms device RTT on the
bench shape), so this bench isolates exactly that phase: collect the
result blocks once, then serialize them repeatedly both ways.

  before   BlockResult.rows() dict per row + json.dumps per row
  after    BlockResult.emit_columns() + native vl_emit_ndjson

Output bytes must be identical; the columnar path must sustain >=2x the
rows/s of the per-row path (the acceptance floor; measured ~6-12x).

Run: make bench-emit   (defaults: 32 parts x 2048 rows, 7 runs)
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("VL_COST_FORCE", "device")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# emit shapes: full column set (the default /query response), a narrow
# fields projection (typed _time fast path), and a wide-match sweep
QUERIES = [
    ("rows", "err"),
    ("projected", "err | fields _time, app, dur"),
    ("wide", "request"),
]


def collect_blocks(storage, ten, t0, qs):
    from victorialogs_tpu.engine.searcher import run_query
    blocks = []
    run_query(storage, [ten], qs, write_block=blocks.append, timestamp=t0)
    return blocks


def best_of(fn, blocks, runs):
    best = float("inf")
    for _ in range(runs):
        t0 = time.perf_counter()
        total = 0
        for br in blocks:
            total += len(fn(br))
        best = min(best, time.perf_counter() - t0)
    return best, total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parts", type=int, default=32)
    ap.add_argument("--rows", type=int, default=2048)
    ap.add_argument("--runs", type=int, default=7)
    ap.add_argument("--json", default="")
    ap.add_argument("--no-assert", action="store_true")
    args = ap.parse_args()

    from tools.bench_pipeline import build_storage
    from victorialogs_tpu import native
    from victorialogs_tpu.engine.emit import ndjson_block, ndjson_block_py

    if not native.available():
        print("native lib unavailable — nothing to compare", file=sys.stderr)
        sys.exit(0 if args.no_assert else 1)
    os.environ["VL_NATIVE_EMIT"] = "1"

    import tempfile
    results = {}
    with tempfile.TemporaryDirectory(prefix="vlbenchemit") as tmp:
        print(f"building {args.parts} parts x {args.rows} rows ...",
              flush=True)
        storage, ten, t0 = build_storage(tmp, args.parts, args.rows)
        for name, qs in QUERIES:
            blocks = collect_blocks(storage, ten, t0, qs)
            nrows = sum(b.nrows for b in blocks)
            # warm both paths (decode caches, key tokens) + parity check
            for br in blocks:
                assert ndjson_block(br) == ndjson_block_py(br), \
                    f"columnar emit diverged from per-row on {qs!r}"
            t_py, nbytes = best_of(ndjson_block_py, blocks, args.runs)
            t_nat, _ = best_of(ndjson_block, blocks, args.runs)
            results[name] = {
                "query": qs, "rows": nrows, "bytes": nbytes,
                "per_row_ms": t_py * 1e3, "columnar_ms": t_nat * 1e3,
                "per_row_rows_per_s": nrows / t_py,
                "columnar_rows_per_s": nrows / t_nat,
                "speedup": t_py / t_nat,
            }
            print(f"  {name}: {nrows} rows, {nbytes} bytes", flush=True)
        storage.close()

    print(f"\nemit bench — {args.parts} parts x {args.rows} rows, "
          f"best of {args.runs}")
    print(f"{'shape':>10} {'rows':>7} {'per-row ms':>11} "
          f"{'columnar ms':>12} {'per-row r/s':>12} {'columnar r/s':>13} "
          f"{'speedup':>8}")
    for name, r in results.items():
        print(f"{name:>10} {r['rows']:>7} {r['per_row_ms']:>11.2f} "
              f"{r['columnar_ms']:>12.2f} "
              f"{r['per_row_rows_per_s']:>12.0f} "
              f"{r['columnar_rows_per_s']:>13.0f} "
              f"{r['speedup']:>7.1f}x")
    print("output bytes: identical on every block (asserted)")

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"parts": args.parts, "rows": args.rows,
                       "results": results}, f, indent=1)
        print(f"wrote {args.json}")

    if not args.no_assert:
        for name, r in results.items():
            assert r["speedup"] >= 2.0, \
                f"columnar emit must be >=2x on {name}, " \
                f"got {r['speedup']:.2f}x"
        print("acceptance: >=2x emit throughput on every shape OK")


if __name__ == "__main__":
    main()
