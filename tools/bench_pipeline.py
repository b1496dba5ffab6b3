"""Many-small-parts pipeline benchmark: serial vs windowed vs packed.

The async pipeline (tpu/pipeline.py) exists for exactly this shape: an
LSM partition full of small fresh parts, where the serial device walk
pays one dispatch round trip per part.  This bench builds N_PARTS
equal-sized parts, runs the same queries end-to-end through run_query
in three configs —

  serial    VL_INFLIGHT=1  VL_PACK_PARTS=1   (the round-3 walk)
  windowed  VL_INFLIGHT=4  VL_PACK_PARTS=1   (in-flight dispatch window)
  packed    VL_INFLIGHT=4  VL_PACK_PARTS=8   (window + super-dispatches)

— and reports wall clock (p50 of R runs, warm staging) plus device
dispatches per query.  Hit sets must be bit-identical across configs
and vs the CPU executor; with packing on, dispatches/query must drop
>=4x on the stats shape (the acceptance bar; dispatch-count model:
P parts -> ceil(P / VL_PACK_PARTS) fused dispatches).

Run: make bench-pipeline   (defaults: 32 parts x 2048 rows, 5 runs)
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("VL_COST_FORCE", "device")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

QUERIES = [
    ("stats", "err | stats by (app) count() c, sum(dur) s"),
    ("rows", "err warn | fields _time"),
]

CONFIGS = [
    ("serial", "1", "1"),
    ("windowed", "4", "1"),
    ("windowed+packed", "4", "8"),
]


def build_storage(path, n_parts, rows_per_part):
    from victorialogs_tpu.storage import datadb
    from victorialogs_tpu.storage.log_rows import LogRows, TenantID
    from victorialogs_tpu.storage.storage import Storage
    # the bench IS the many-small-parts shape: keep the background
    # merger from folding the parts together mid-measurement
    datadb.DEFAULT_PARTS_TO_MERGE = 10 ** 9
    t0 = 1_753_660_800_000_000_000
    ten = TenantID(0, 0)
    s = Storage(path, retention_days=100000, flush_interval=3600)
    n = 0
    for _pp in range(n_parts):
        lr = LogRows(stream_fields=["app"])
        for _i in range(rows_per_part):
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
    parts = [p for pt in s.partitions.values()
             for p in pt.ddb.snapshot_parts() if p.num_rows]
    assert len(parts) == n_parts, f"expected {n_parts} parts, got " \
                                  f"{len(parts)} (merge interfered?)"
    return s, ten, t0


def run_config(storage, ten, t0, inflight, pack, runs):
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.tpu.batch import BatchRunner
    os.environ["VL_INFLIGHT"] = inflight
    os.environ["VL_PACK_PARTS"] = pack
    runner = BatchRunner()
    out = {}
    for name, qs in QUERIES:
        # warmup: XLA compiles + cold staging (parts are immutable, so
        # staging is reused across queries — steady-state is warm)
        rows = run_query_collect(storage, [ten], qs, timestamp=t0,
                                 runner=runner)
        d0 = runner.device_calls
        times = []
        for _r in range(runs):
            t0s = time.perf_counter()
            rows = run_query_collect(storage, [ten], qs, timestamp=t0,
                                     runner=runner)
            times.append(time.perf_counter() - t0s)
        out[name] = {
            "p50_ms": statistics.median(times) * 1e3,
            "dispatches_per_query":
                (runner.device_calls - d0) / runs,
            "rows": sorted(map(str, rows)),
        }
    out["counters"] = {k: v for k, v in runner.stats().items()
                       if not k.startswith("staging_")}
    return out


def build_storage_multiday(path, days, parts_per_day, rows_per_part):
    """3-day partitioned dataset of flush-sized parts — the ROADMAP's
    named proof shape for the cross-partition window."""
    from victorialogs_tpu.storage import datadb
    from victorialogs_tpu.storage.log_rows import LogRows, TenantID
    from victorialogs_tpu.storage.storage import Storage
    datadb.DEFAULT_PARTS_TO_MERGE = 10 ** 9
    t0 = 1_753_660_800_000_000_000
    ns_day = 86_400 * 1_000_000_000
    ten = TenantID(0, 0)
    s = Storage(path, retention_days=100000, flush_interval=3600)
    n = 0
    for day in range(days):
        for _pp in range(parts_per_day):
            lr = LogRows(stream_fields=["app"])
            for _i in range(rows_per_part):
                g = n
                n += 1
                lvl = ["info", "warn", "err"][g % 3]
                lr.add(ten, t0 + day * ns_day + (g % 1200) * 1_000_000, [
                    ("app", f"app{g % 5}"),
                    ("_msg", f"m {lvl} request x{g % 97} of {g}"),
                    ("dur", str(g % 211)),
                ])
            s.must_add_rows(lr)
            s.debug_flush()
    assert len(s.partitions) == days
    return s, ten, t0


MULTIDAY_QUERIES = [
    ("topk", "err | sort by (dur desc) limit 10 | fields dur, app"),
    ("stats-wide", "* | stats by (dur:1) count() c, sum(dur) s"),
    ("rows", "err warn | fields _time"),
]

MULTIDAY_CONFIGS = [
    # the universal packed device path
    ("cross-partition", {"VL_PACK_TOPK_K": "1024"}),
]


def run_multipartition(days, parts_per_day, rows_per_part, runs):
    """The global window over a 3-day fixture: wall clock,
    dispatches/query, packed-topk engagement and the seg-major
    no-widening pin, hit sets bit-identical to the CPU executor."""
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.tpu.batch import BatchRunner
    os.environ["VL_INFLIGHT"] = "4"
    os.environ["VL_PACK_PARTS"] = "8"
    out = {"days": days, "parts_per_day": parts_per_day,
           "rows_per_part": rows_per_part}
    with tempfile.TemporaryDirectory(prefix="vlbenchmp") as tmp:
        storage, ten, t0 = build_storage_multiday(
            tmp, days, parts_per_day, rows_per_part)
        cpu = {name: sorted(map(str, run_query_collect(
            storage, [ten], qs, timestamp=t0)))
            for name, qs in MULTIDAY_QUERIES}
        for label, env in MULTIDAY_CONFIGS:
            for k, v in env.items():
                os.environ[k] = v
            runner = BatchRunner()
            res = {}
            for name, qs in MULTIDAY_QUERIES:
                rows = run_query_collect(storage, [ten], qs,
                                         timestamp=t0, runner=runner)
                assert sorted(map(str, rows)) == cpu[name], \
                    f"{label}/{name} diverged from the CPU executor"
                d0 = runner.device_calls
                times = []
                for _r in range(runs):
                    t0s = time.perf_counter()
                    run_query_collect(storage, [ten], qs, timestamp=t0,
                                      runner=runner)
                    times.append(time.perf_counter() - t0s)
                res[name] = {
                    "p50_ms": statistics.median(times) * 1e3,
                    "dispatches_per_query":
                        (runner.device_calls - d0) / runs,
                }
            res["counters"] = {
                k: v for k, v in runner.stats().items()
                if not k.startswith("staging_")}
            out[label] = res
        storage.close()
    os.environ.pop("VL_PACK_TOPK_K", None)
    return out


def _find_spans(tree, name):
    out = []

    def walk(n):
        if n.get("name") == name:
            out.append(n)
        for c in n.get("children", ()):
            walk(c)
    walk(tree)
    return out


def measure_emit_split(storage, ten, t0, runs):
    """The harvest span's device_sync/emit children under the columnar
    native serializer vs the per-row fallback (VL_NATIVE_EMIT=0): same
    traced NDJSON streaming run, emit time must drop materially, and
    `emit` must show up as a distinct harvest child (the ?trace=1
    attribution the tentpole promises)."""
    from victorialogs_tpu.engine.emit import ndjson_block
    from victorialogs_tpu.engine.searcher import run_query
    from victorialogs_tpu.obs import tracing
    from victorialogs_tpu.tpu.batch import BatchRunner
    os.environ["VL_INFLIGHT"] = "4"
    os.environ["VL_PACK_PARTS"] = "8"
    qs = "err | fields _time, app, dur"
    runner = BatchRunner()

    def run_once():
        nbytes = 0

        def sink(br):
            nonlocal nbytes
            nbytes += len(ndjson_block(br))
        root = tracing.make_root("bench", query=qs)
        with tracing.activate(root):
            run_query(storage, [ten], qs, write_block=sink,
                      timestamp=t0, runner=runner)
        tree = root.to_dict()
        harvs = _find_spans(tree, "harvest")
        emits = _find_spans(tree, "emit")
        syncs = _find_spans(tree, "device_sync")
        assert harvs and emits and syncs, \
            "harvest must carry device_sync + emit child spans"
        for h in harvs:
            kids = {c.get("name") for c in h.get("children", ())}
            assert "emit" in kids and "device_sync" in kids
        return (sum(s["duration_ms"] for s in emits),
                sum(s["duration_ms"] for s in syncs), nbytes)

    out = {}
    for label, native in (("per_row", "0"), ("columnar", "1")):
        os.environ["VL_NATIVE_EMIT"] = native
        run_once()                      # warm (compiles, decode caches)
        best = None
        for _r in range(runs):
            got = run_once()
            best = got if best is None or got[0] < best[0] else best
        out[label] = {"emit_ms": best[0], "device_sync_ms": best[1],
                      "bytes": best[2]}
    os.environ["VL_NATIVE_EMIT"] = "1"
    assert out["per_row"]["bytes"] == out["columnar"]["bytes"]
    return out


def measure_trace_overhead(storage, ten, t0, runs):
    """Tracing-off vs tracing-on p50 on the packed workload, plus the
    structural zero-span check for the disabled path (obs/tracing.py:
    the no-op singleton must absorb every instrumentation call)."""
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.obs import tracing
    from victorialogs_tpu.tpu.batch import BatchRunner
    os.environ["VL_INFLIGHT"] = "4"
    os.environ["VL_PACK_PARTS"] = "8"
    runner = BatchRunner()
    _name, qs = QUERIES[1]  # the rows shape: most spans per unit
    run_query_collect(storage, [ten], qs, timestamp=t0, runner=runner)

    def p50(traced: bool):
        times = []
        for _r in range(runs):
            root = tracing.make_root("bench", query=qs) if traced \
                else None
            t0s = time.perf_counter()
            with tracing.activate(root):
                run_query_collect(storage, [ten], qs, timestamp=t0,
                                  runner=runner)
            times.append(time.perf_counter() - t0s)
        return statistics.median(times) * 1e3

    before = tracing.spans_created()
    off_ms = p50(traced=False)
    spans_off = tracing.spans_created() - before
    on_ms = p50(traced=True)
    spans_on = tracing.spans_created() - before
    return {"off_p50_ms": off_ms, "on_p50_ms": on_ms,
            "spans_disabled": spans_off, "spans_traced": spans_on}


def run_concurrent(storage, ten, t0, clients, queries_per_client):
    """Concurrent-clients mode: N same-process threads hammer the same
    storage+runner through run_query_collect (each query registers in
    the active-query registry), reporting per-query p50/p99 wall and
    aggregate rows/s — the measurement the ROADMAP scheduler item asks
    for, with vl_active_queries sampled mid-run as proof the registry
    sees the concurrency."""
    import statistics as st
    import threading
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.obs import activity
    from victorialogs_tpu.tpu.batch import BatchRunner
    os.environ["VL_INFLIGHT"] = "4"
    os.environ["VL_PACK_PARTS"] = "8"
    runner = BatchRunner()
    for _name, qs in QUERIES:      # warm: XLA compiles + staging
        run_query_collect(storage, [ten], qs, timestamp=t0,
                          runner=runner)

    lock = threading.Lock()
    lat: list = []
    rows_total = [0]
    barrier = threading.Barrier(clients + 1)

    def client(ci):
        barrier.wait()
        for r in range(queries_per_client):
            _name, qs = QUERIES[(ci + r) % len(QUERIES)]
            tq0 = time.perf_counter()
            rows = run_query_collect(storage, [ten], qs, timestamp=t0,
                                     runner=runner)
            dt = time.perf_counter() - tq0
            with lock:
                lat.append(dt)
                rows_total[0] += len(rows)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t_all = time.perf_counter()
    # sample the registry while the fleet runs: vl_active_queries is
    # exactly what a scrape would see mid-load
    max_active = 0
    while any(t.is_alive() for t in threads):
        max_active = max(max_active, len(activity.active_snapshot()))
        time.sleep(0.005)
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_all
    lat.sort()

    def q(p):
        return lat[min(len(lat) - 1, int(p * len(lat)))] * 1e3
    return {
        "clients": clients,
        "queries": len(lat),
        "p50_ms": st.median(lat) * 1e3,
        "p99_ms": q(0.99),
        "wall_s": wall,
        "agg_queries_per_s": len(lat) / wall,
        "agg_rows_per_s": rows_total[0] / wall,
        "max_active_queries": max_active,
    }


def run_tenant_mix(storage, ten, t0, n_heavy=2, n_light=4,
                   light_rounds=10):
    """Per-tenant mix fairness round: n_heavy full-scan stats clients
    (deep VL_INFLIGHT windows, tenant 9:0) vs n_light early-exit row
    clients (tenant 7:0), run twice — unmanaged (VL_SCHED=0: every
    runner burns its own window, the PR 6 contention) and managed
    (shared budget + weighted fair queuing).  The scheduler's promise
    is the LIGHT clients' tail: their single dispatch no longer queues
    behind every heavy window's outstanding dispatches."""
    import threading
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.obs import activity
    from victorialogs_tpu.tpu.batch import BatchRunner
    heavy_q = QUERIES[0][1]                       # full-scan stats
    light_q = "err warn | fields _time | limit 20"  # 1-unit early exit
    os.environ["VL_INFLIGHT"] = "8"
    os.environ["VL_PACK_PARTS"] = "1"   # heavy = many dispatches/query
    runner = BatchRunner()
    for qs in (heavy_q, light_q):
        run_query_collect(storage, [ten], qs, timestamp=t0,
                          runner=runner)
    # the light client's solo wall — the fairness yardstick
    solo = []
    for _r in range(10):
        tq0 = time.perf_counter()
        run_query_collect(storage, [ten], light_q, timestamp=t0,
                          runner=runner)
        solo.append(time.perf_counter() - tq0)
    solo_p50 = statistics.median(solo) * 1e3

    def one_mode(managed: bool) -> dict:
        os.environ["VL_SCHED"] = "1" if managed else "0"
        light_lat: list = []
        heavy_done = [0]
        stop = threading.Event()
        lock = threading.Lock()
        barrier = threading.Barrier(n_heavy + n_light + 1)

        def heavy_client():
            barrier.wait()
            while not stop.is_set():
                with activity.track("bench/heavy", heavy_q, "9:0"):
                    run_query_collect(storage, [ten], heavy_q,
                                      timestamp=t0, runner=runner)
                with lock:
                    heavy_done[0] += 1

        def light_client():
            barrier.wait()
            for _r in range(light_rounds):
                tq0 = time.perf_counter()
                with activity.track("bench/light", light_q, "7:0"):
                    run_query_collect(storage, [ten], light_q,
                                      timestamp=t0, runner=runner)
                with lock:
                    light_lat.append(time.perf_counter() - tq0)

        threads = [threading.Thread(target=heavy_client, daemon=True)
                   for _ in range(n_heavy)] + \
                  [threading.Thread(target=light_client, daemon=True)
                   for _ in range(n_light)]
        for t in threads:
            t.start()
        barrier.wait()
        t_all = time.perf_counter()
        for t in threads[n_heavy:]:
            t.join()
        wall = time.perf_counter() - t_all
        # snapshot heavy completions AT the wall-clock close: queries
        # the stop flag lets finish afterwards must not inflate
        # agg_queries_per_s
        with lock:
            heavy_snapshot = heavy_done[0]
        stop.set()
        for t in threads[:n_heavy]:
            t.join()
        light_lat.sort()

        def q(p):
            return light_lat[min(len(light_lat) - 1,
                                 int(p * len(light_lat)))] * 1e3
        return {
            "light_p50_ms": statistics.median(light_lat) * 1e3,
            "light_p99_ms": q(0.99),
            "heavy_done": heavy_snapshot,
            "wall_s": wall,
            "agg_queries_per_s":
                (heavy_snapshot + len(light_lat)) / wall,
        }

    out = {"heavy_clients": n_heavy, "light_clients": n_light,
           "light_rounds": light_rounds, "solo_light_p50_ms": solo_p50}
    out["unmanaged"] = one_mode(managed=False)
    out["managed"] = one_mode(managed=True)
    os.environ["VL_SCHED"] = "1"
    os.environ["VL_PACK_PARTS"] = "8"
    os.environ["VL_INFLIGHT"] = "4"
    return out


def run_shed_probe(storage, ten, t0, runner):
    """Overload shedding end-to-end: a VLServer over the bench storage,
    tenant 9:0 capped at 1 concurrent query via POST sched_config, 6
    parallel tenant-9 HTTP queries — the over-limit ones must shed with
    429 + Retry-After + a machine-readable reason, counted per tenant
    on /metrics, while another tenant keeps flowing."""
    import json as _json
    import threading
    import urllib.error
    import urllib.parse
    import urllib.request
    from victorialogs_tpu.server.app import VLServer
    srv = VLServer(storage, port=0, runner=runner, max_concurrent=8)
    base = f"http://127.0.0.1:{srv.port}"
    try:
        req = urllib.request.Request(
            f"{base}/select/logsql/sched_config?tenant=9:0"
            f"&max_concurrent=1", data=b"", method="POST")
        assert urllib.request.urlopen(req).status == 200
        q = urllib.parse.quote(QUERIES[0][1])
        results = {"ok": 0, "shed": 0}
        reasons = []
        retry_after = []
        lock = threading.Lock()

        def client():
            req = urllib.request.Request(
                f"{base}/select/logsql/query?query={q}",
                headers={"AccountID": "9"})
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()
                with lock:
                    results["ok"] += 1
            except urllib.error.HTTPError as e:
                body = _json.loads(e.read() or b"{}")
                with lock:
                    results["shed"] += 1
                    reasons.append((e.code, body.get("reason")))
                    retry_after.append(e.headers.get("Retry-After"))

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        m = urllib.request.urlopen(f"{base}/metrics").read().decode()
        counter = 0
        for line in m.splitlines():
            if line.startswith("vl_select_rejected_total") and \
                    'tenant="9:0"' in line:
                counter += int(float(line.rsplit(" ", 1)[1]))
        return {"ok": results["ok"], "shed": results["shed"],
                "reasons": reasons, "retry_after": retry_after,
                "rejected_counter": counter}
    finally:
        srv.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parts", type=int, default=32)
    ap.add_argument("--rows", type=int, default=2048)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--clients", type=int, default=0,
                    help="also run the concurrent-clients mode with "
                         "this many threaded clients, plus the "
                         "tenant-mix fairness round and the HTTP shed "
                         "probe")
    ap.add_argument("--queries-per-client", type=int, default=6)
    ap.add_argument("--light-clients", type=int, default=4)
    ap.add_argument("--days", type=int, default=3)
    ap.add_argument("--parts-per-day", type=int, default=6)
    ap.add_argument("--json", default="")
    ap.add_argument("--no-assert", action="store_true")
    args = ap.parse_args()

    from victorialogs_tpu.engine.searcher import run_query_collect
    with tempfile.TemporaryDirectory(prefix="vlbenchpipe") as tmp:
        print(f"building {args.parts} parts x {args.rows} rows ...",
              flush=True)
        storage, ten, t0 = build_storage(tmp, args.parts, args.rows)
        cpu = {name: sorted(map(str, run_query_collect(
            storage, [ten], qs, timestamp=t0)))
            for name, qs in QUERIES}
        results = {}
        for label, inflight, pack in CONFIGS:
            print(f"config {label} (VL_INFLIGHT={inflight} "
                  f"VL_PACK_PARTS={pack}) ...", flush=True)
            results[label] = run_config(storage, ten, t0, inflight,
                                        pack, args.runs)
        print("measuring vltrace overhead (tracing off vs on) ...",
              flush=True)
        trace_oh = measure_trace_overhead(storage, ten, t0, args.runs)
        print("measuring harvest emit split (per-row vs columnar) ...",
              flush=True)
        emit_split = measure_emit_split(storage, ten, t0, args.runs)
        concurrent = None
        tenant_mix = None
        shed_probe = None
        if args.clients > 0:
            print(f"concurrent-clients mode: {args.clients} clients x "
                  f"{args.queries_per_client} queries ...", flush=True)
            concurrent = run_concurrent(storage, ten, t0, args.clients,
                                        args.queries_per_client)
            print(f"tenant-mix fairness round: 2 heavy + "
                  f"{args.light_clients} light clients, "
                  f"unmanaged (VL_SCHED=0) vs managed ...", flush=True)
            tenant_mix = run_tenant_mix(storage, ten, t0,
                                        n_light=args.light_clients)
            print("HTTP shed probe: tenant capped at 1, 6 parallel "
                  "queries ...", flush=True)
            from victorialogs_tpu.tpu.batch import BatchRunner
            shed_probe = run_shed_probe(storage, ten, t0,
                                        BatchRunner())
        storage.close()

    print(f"multi-partition round: {args.days} days x "
          f"{args.parts_per_day} parts, cross-partition window ...",
          flush=True)
    multiday = run_multipartition(args.days, args.parts_per_day,
                                  args.rows, args.runs)

    print(f"\npipeline bench — {args.parts} parts x {args.rows} rows, "
          f"p50 of {args.runs} (jax-CPU backend)")
    print(f"{'config':>16} {'query':>6} {'p50 ms':>9} {'disp/query':>11}")
    for label, _i, _p in CONFIGS:
        for name, _qs in QUERIES:
            r = results[label][name]
            print(f"{label:>16} {name:>6} {r['p50_ms']:>9.1f} "
                  f"{r['dispatches_per_query']:>11.1f}")

    # hit sets must be bit-identical everywhere
    for label, _i, _p in CONFIGS:
        for name, _qs in QUERIES:
            assert results[label][name]["rows"] == cpu[name], \
                f"{label}/{name} diverged from the CPU executor"
    print("hit sets: bit-identical across serial/windowed/packed "
          "and vs CPU")

    serial = results["serial"]
    packed = results["windowed+packed"]
    disp_ratio = serial["stats"]["dispatches_per_query"] / \
        max(packed["stats"]["dispatches_per_query"], 1e-9)
    wall_ratio = min(
        serial[n]["p50_ms"] / max(packed[n]["p50_ms"], 1e-9)
        for n, _q in QUERIES)
    print(f"dispatch reduction (stats, packed vs serial): "
          f"{disp_ratio:.1f}x")
    for name, _qs in QUERIES:
        print(f"wall clock {name}: serial/packed = "
              f"{results['serial'][name]['p50_ms'] / max(packed[name]['p50_ms'], 1e-9):.2f}x")

    print(f"vltrace overhead (rows query, packed config): "
          f"off={trace_oh['off_p50_ms']:.1f} ms  "
          f"on={trace_oh['on_p50_ms']:.1f} ms  "
          f"({trace_oh['on_p50_ms'] / max(trace_oh['off_p50_ms'], 1e-9):.3f}x)  "
          f"spans: disabled={trace_oh['spans_disabled']} "
          f"traced={trace_oh['spans_traced']}")

    emit_ratio = emit_split["per_row"]["emit_ms"] / \
        max(emit_split["columnar"]["emit_ms"], 1e-9)
    print(f"harvest emit split (NDJSON streaming, "
          f"{emit_split['columnar']['bytes']} bytes): "
          f"per-row emit={emit_split['per_row']['emit_ms']:.1f} ms  "
          f"columnar emit={emit_split['columnar']['emit_ms']:.1f} ms  "
          f"({emit_ratio:.1f}x)  "
          f"device_sync={emit_split['columnar']['device_sync_ms']:.1f} ms")

    if concurrent is not None:
        print(f"concurrent clients ({concurrent['clients']} threads, "
              f"{concurrent['queries']} queries): "
              f"p50={concurrent['p50_ms']:.1f} ms  "
              f"p99={concurrent['p99_ms']:.1f} ms  "
              f"{concurrent['agg_rows_per_s']:.0f} rows/s  "
              f"{concurrent['agg_queries_per_s']:.1f} q/s  "
              f"max vl_active_queries={concurrent['max_active_queries']}")

    if tenant_mix is not None:
        um, mg = tenant_mix["unmanaged"], tenant_mix["managed"]
        print(f"tenant mix ({tenant_mix['heavy_clients']} heavy + "
              f"{tenant_mix['light_clients']} light, solo light "
              f"p50={tenant_mix['solo_light_p50_ms']:.1f} ms):")
        for label, r in (("unmanaged", um), ("managed", mg)):
            print(f"  {label:>10}: light p50={r['light_p50_ms']:.1f} "
                  f"p99={r['light_p99_ms']:.1f} ms  "
                  f"heavy done={r['heavy_done']}  "
                  f"agg={r['agg_queries_per_s']:.1f} q/s")
        print(f"  light p99 managed/unmanaged = "
              f"{mg['light_p99_ms'] / max(um['light_p99_ms'], 1e-9):.2f}x"
              f"  (vs solo: {mg['light_p99_ms'] / max(tenant_mix['solo_light_p50_ms'], 1e-9):.1f}x)")

    cross = multiday["cross-partition"]
    print(f"multi-partition ({multiday['days']} days x "
          f"{multiday['parts_per_day']} parts x "
          f"{multiday['rows_per_part']} rows):")
    for name, _qs in MULTIDAY_QUERIES:
        print(f"  {name:>10}: {cross[name]['p50_ms']:.1f} ms "
              f"({cross[name]['dispatches_per_query']:.1f} disp)")
    cc = cross["counters"]
    print(f"  packed_topk_dispatches={cc['packed_topk_dispatches']}  "
          f"cross_partition_packs={cc['cross_partition_packs']}  "
          f"stats_onehot_width={cc['stats_onehot_width']}")

    if shed_probe is not None:
        print(f"shed probe (tenant capped at 1, 6 parallel): "
              f"ok={shed_probe['ok']} shed={shed_probe['shed']} "
              f"reasons={shed_probe['reasons']} "
              f"Retry-After={shed_probe['retry_after']} "
              f"vl_select_rejected_total={shed_probe['rejected_counter']}")

    if args.json:
        if concurrent is None:
            # a default (no --clients) run must not clobber committed
            # concurrent-clients results with null — carry them forward
            try:
                with open(args.json) as f:
                    prev = json.load(f)
                concurrent = prev.get("concurrent")
                tenant_mix = prev.get("tenant_mix")
                shed_probe = prev.get("shed_probe")
            except (OSError, ValueError):
                pass
        with open(args.json, "w") as f:
            json.dump({"parts": args.parts, "rows": args.rows,
                       "cpu": {k: len(v) for k, v in cpu.items()},
                       "trace_overhead": trace_oh,
                       "emit_split": emit_split,
                       "multiday": multiday,
                       "concurrent": concurrent,
                       "tenant_mix": tenant_mix,
                       "shed_probe": shed_probe,
                       "results": {k: {n: {kk: vv for kk, vv in r.items()
                                           if kk != "rows"}
                                       for n, r in v.items()}
                                   for k, v in results.items()}},
                      f, indent=1)
        print(f"wrote {args.json}")

    if not args.no_assert:
        assert disp_ratio >= 4.0, \
            f"packing must cut dispatches >=4x, got {disp_ratio:.1f}x"
        assert wall_ratio >= 1.5, \
            f"windowed+packed must beat serial >=1.5x, got " \
            f"{wall_ratio:.2f}x"
        # disabled-tracing overhead within noise: structurally zero
        # spans, and the disabled path may not run slower than the
        # traced one beyond measurement jitter
        assert trace_oh["spans_disabled"] == 0, \
            "tracing-disabled run created spans"
        assert trace_oh["spans_traced"] > 0
        assert trace_oh["off_p50_ms"] <= \
            trace_oh["on_p50_ms"] * 1.10 + 2.0, \
            f"disabled-tracing path slower than traced beyond noise: " \
            f"{trace_oh['off_p50_ms']:.1f} vs {trace_oh['on_p50_ms']:.1f} ms"
        # the ?trace=1 emit child must show the columnar win per query:
        # materially reduced vs the per-row fallback on the bench shape
        assert emit_ratio >= 1.3, \
            f"columnar emit must materially cut the harvest emit span, " \
            f"got {emit_ratio:.2f}x"
        if args.clients > 0:
            # the registry must actually see the concurrency it exists
            # to expose (each client registers per query) — asserted
            # only on THIS run's measurement, never on carried-forward
            # JSON from a previous run
            assert concurrent["max_active_queries"] >= 2, \
                f"active-query registry never saw concurrent clients " \
                f"({concurrent['max_active_queries']})"
            # fairness: the managed light-client tail must not be worse
            # than unmanaged (the scheduler's whole point), with
            # aggregate throughput within 10%
            um = tenant_mix["unmanaged"]
            mg = tenant_mix["managed"]
            ratio = mg["light_p99_ms"] / max(um["light_p99_ms"], 1e-9)
            # measured 0.88x/0.96x across committed runs; p99 of ~40
            # threaded samples is the noisiest statistic here, so the
            # assert keeps a small headroom like its siblings
            assert ratio <= 1.05, \
                f"managed light p99 worse than unmanaged: {ratio:.2f}x"
            # the satellite's absolute bound: a light client's tail under
            # heavy contention stays within a small multiple of its solo
            # wall (measured 7.7x on jax-CPU; unmanaged has no bound)
            solo_x = mg["light_p99_ms"] / \
                max(tenant_mix["solo_light_p50_ms"], 1e-9)
            assert solo_x <= 12.0, \
                f"managed light p99 {solo_x:.1f}x the solo wall"
            # fairness costs the heavy clients some in-flight depth:
            # measured 0.91x aggregate on jax-CPU (within the 10%
            # criterion); the assert keeps headroom for machine noise
            agg = mg["agg_queries_per_s"] / \
                max(um["agg_queries_per_s"], 1e-9)
            assert agg >= 0.85, \
                f"managed aggregate throughput dropped too far: " \
                f"{agg:.2f}x"
            # over-limit clients observably shed: 429 + Retry-After +
            # reason + per-tenant counter, while in-limit work succeeds
            assert shed_probe["shed"] >= 1 and shed_probe["ok"] >= 1, \
                shed_probe
            assert all(code == 429 and reason == "tenant_limit"
                       for code, reason in shed_probe["reasons"]), \
                shed_probe["reasons"]
            assert all(ra is not None
                       for ra in shed_probe["retry_after"]), shed_probe
            assert shed_probe["rejected_counter"] >= \
                shed_probe["shed"], shed_probe
        # the 3-day fixture: packed topk engagement and the seg-major
        # no-widening bound are counter-asserted
        assert cc["packed_topk_dispatches"] > 0
        assert cc["cross_partition_packs"] > 0
        w = cc["stats_onehot_width"]
        assert w == 211, \
            "packed stats one-hot width must stay at the base group " \
            f"count (211), got {w}"
        print("acceptance: >=4x fewer dispatches, >=1.5x wall clock, "
              "multi-partition counters, "
              "vltrace disabled-overhead within noise, "
              f"emit span cut {emit_ratio:.1f}x OK")


if __name__ == "__main__":
    main()
