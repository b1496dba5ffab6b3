"""Cluster wire-protocol benchmark: typed columnar frames vs legacy
JSON frames on a real 2-node scatter-gather (PR 9).

Topology: two in-process storage-node servers (real HTTP on localhost)
behind a NetSelectStorage frontend.  The frontend side drives
vlselect.handle_query directly — the measured wall covers the full
frontend hot path (fan-out, frame decode, pipe chain, NDJSON emit) but
no frontend HTTP socket, so the number is "frontend-side rows/s".

  legacy  VL_WIRE_TYPED=0: list-of-strings JSON frames; the node
          materializes per-row strings + json.dumps, the frontend
          json.loads + re-packs string lists per block
  typed   wire format t1: BlockResult.wire_columns() arenas on the
          wire; the frontend decodes numpy views and feeds
          vl_emit_ndjson directly

Asserted: bit-identical hit sets (sorted NDJSON lines equal), >=2x
frontend rows/s for the typed path on the rows workload, and ZERO
typed frames on the wire under VL_WIRE_TYPED=0 (counter delta).

Run: make bench-wire   (defaults: 2 nodes, 24 parts x 2048 rows, 5 runs)
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

NS = 1_000_000_000
T0 = 1_753_660_800_000_000_000  # 2025-07-28T00:00:00Z

QUERIES = [
    ("rows", "err", 0),
    ("projected", "err | fields _time, app, dur", 0),
    ("stats", "* | stats by (app, lvl) count() c, sum(dur) s", 0),
]


def _mk_node(path):
    from victorialogs_tpu.server.app import VLServer
    from victorialogs_tpu.storage.storage import Storage
    storage = Storage(str(path), retention_days=100000,
                      flush_interval=3600)
    return VLServer(storage, listen_addr="127.0.0.1", port=0)


def _seed(nodes, parts, rows_per_part):
    """Shard rows over the nodes by stream hash through the normal
    ingest front (NetInsertStorage), flush per part."""
    from victorialogs_tpu.server.cluster import NetInsertStorage
    from victorialogs_tpu.storage.log_rows import LogRows, TenantID
    ten = TenantID(0, 0)
    sink = NetInsertStorage(
        [f"http://127.0.0.1:{n.port}" for n in nodes])
    n = 0
    for _p in range(parts):
        lr = LogRows(stream_fields=["app"])
        for _i in range(rows_per_part):
            g = n
            n += 1
            lr.add(ten, T0 + g * 1_000_000, [
                ("app", f"app{g % 8}"),
                ("_msg", f"GET /api/v1/items/{g % 1000} "
                         f"{'err' if g % 3 == 0 else 'ok'} "
                         f"user=u{g % 257} trace={g:08x}"),
                ("lvl", ["info", "warn", "err"][g % 3]),
                ("dur", str(g % 251)),
                ("region", ["us-east", "eu-west", "ap-south"][g % 3]),
            ])
        sink.must_add_rows(lr)
        for node in nodes:
            node.storage.debug_flush()
    return n


def run_query_bytes(net, qs, limit):
    """One frontend query via the real handler; returns (nrows, bytes)."""
    from victorialogs_tpu.server.vlselect import handle_query
    total = 0
    nrows = 0
    chunks = []
    for chunk in handle_query(net, {"query": qs, "limit": str(limit),
                                    "time": str(T0 + 3600 * NS)}, {}):
        data = chunk if isinstance(chunk, bytes) else chunk.encode()
        total += len(data)
        nrows += data.count(b"\n")
        chunks.append(data)
    return nrows, total, b"".join(chunks)


def bench_mode(net, runs):
    out = {}
    for name, qs, limit in QUERIES:
        best = float("inf")
        nrows = 0
        lines = None
        for _ in range(runs):
            t0 = time.perf_counter()
            nrows, _nbytes, data = run_query_bytes(net, qs, limit)
            best = min(best, time.perf_counter() - t0)
            lines = sorted(data.splitlines())
        out[name] = {"rows": nrows, "wall_s": best,
                     "rows_per_s": nrows / best if best else 0.0,
                     "_lines": lines}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parts", type=int, default=24)
    ap.add_argument("--rows", type=int, default=2048)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--json", default="")
    ap.add_argument("--no-assert", action="store_true")
    args = ap.parse_args()

    import tempfile
    from victorialogs_tpu.server import cluster
    from victorialogs_tpu.server.cluster import NetSelectStorage

    tmp = tempfile.TemporaryDirectory(prefix="vl-bench-wire-")
    nodes = [_mk_node(os.path.join(tmp.name, f"n{i}")) for i in (0, 1)]
    try:
        total_rows = _seed(nodes, args.parts, args.rows)
        print(f"seeded {total_rows} rows over {len(nodes)} storage "
              f"nodes ({args.parts} parts x {args.rows} rows)")
        urls = [f"http://127.0.0.1:{n.port}" for n in nodes]

        # typed (the default path)
        os.environ.pop("VL_WIRE_TYPED", None)
        net = NetSelectStorage(urls)
        assert net.wire_typed
        c0 = cluster.wire_counters()
        typed = bench_mode(net, args.runs)
        c1 = cluster.wire_counters()
        def _tf(c):
            return c.get("tx_frames_typed", 0) + c.get(
                "rx_frames_typed", 0)
        typed_frames = _tf(c1) - _tf(c0)
        assert typed_frames > 0, "typed path sent no typed frames"

        # legacy (kill-switch: both request and serve sides off)
        os.environ["VL_WIRE_TYPED"] = "0"
        try:
            net_legacy = NetSelectStorage(urls)
            assert not net_legacy.wire_typed
            c2 = cluster.wire_counters()
            legacy = bench_mode(net_legacy, args.runs)
            c3 = cluster.wire_counters()
        finally:
            os.environ.pop("VL_WIRE_TYPED", None)
        legacy_typed_frames = _tf(c3) - _tf(c2)
        assert legacy_typed_frames == 0, \
            f"VL_WIRE_TYPED=0 still put {legacy_typed_frames} typed " \
            f"frames on the wire"

        results = {}
        print(f"\n{'workload':<12} {'rows':>7} {'legacy rows/s':>14} "
              f"{'typed rows/s':>13} {'speedup':>8}")
        for name, _qs, _limit in QUERIES:
            t, l = typed[name], legacy[name]
            assert t["_lines"] == l["_lines"], \
                f"{name}: typed vs legacy hit sets differ"
            assert t["rows"] == l["rows"]
            speedup = t["rows_per_s"] / l["rows_per_s"] \
                if l["rows_per_s"] else 0.0
            results[name] = {
                "rows": t["rows"], "typed_wall_s": t["wall_s"],
                "legacy_wall_s": l["wall_s"],
                "typed_rows_per_s": round(t["rows_per_s"], 1),
                "legacy_rows_per_s": round(l["rows_per_s"], 1),
                "speedup": round(speedup, 2)}
            print(f"{name:<12} {t['rows']:>7} "
                  f"{l['rows_per_s']:>14,.0f} "
                  f"{t['rows_per_s']:>13,.0f} {speedup:>7.2f}x")
        print("hit sets: bit-identical on every workload (asserted)")
        print(f"typed frames on wire: {typed_frames} (typed run), "
              f"{legacy_typed_frames} (VL_WIRE_TYPED=0 run, asserted 0)")

        if args.json:
            with open(args.json, "w") as f:
                json.dump({"parts": args.parts, "rows": args.rows,
                           "nodes": len(nodes),
                           "results": results}, f, indent=2)
            print(f"wrote {args.json}")

        if not args.no_assert:
            assert results["rows"]["speedup"] >= 2.0, \
                f"typed wire speedup {results['rows']['speedup']}x " \
                f"under the 2x acceptance floor on the rows workload"
    finally:
        for n in nodes:
            n.close()
            n.storage.close()
        tmp.cleanup()


if __name__ == "__main__":
    main()
