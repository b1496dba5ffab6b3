"""Cluster-observability bench: rollup overhead, federated snapshot
completeness, and cancel-propagation kill latency on a real 3-node
multi-process cluster.

Rounds (recorded into BENCH_cluster_obs.json, asserting as it goes):

1. rollup overhead — concurrent-query p50 through a frontend with the
   clusterstats poll loop OFF (VL_CLUSTER_STATS_MS=0) vs ON at an
   aggressive 100ms cadence; the rollup must cost <= 1.10x p50
   (journal-bench discipline: the observability must not tax the
   workload it observes).  The differential (frontend
   vl_cluster_tenant_* == sum of per-node vl_tenant_*) is asserted in
   the same round;
2. federated snapshot completeness — N concurrent heavy queries in
   flight; one active_queries?cluster=1 snapshot must show ALL of them
   with their storage-node sub-queries nested under them by propagated
   parent_qid;
3. cancel latency — time from kill to every node registry draining:
   POST cancel_query (parent_qid propagation) vs the old client-
   disconnect path (for a stats-shaped query the frontend only notices
   the dead peer at its first — i.e. final — write, so the nodes run
   the sub-queries to completion).  Propagated cancel must be well
   under the disconnect path.

Usage: python tools/bench_cluster_obs.py [--json BENCH_cluster_obs.json]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import statistics
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

BASE_ENV = {
    "VL_BREAKER_OPEN_S": "0.5",
    "VL_BREAKER_FAILURES": "2",
    "VL_NET_RETRIES": "1",
}

N_ROWS = 90_000           # heavy-tenant rows (30k per node)
N_LIGHT = 3_000           # light workload rows for the p50 round
CLIENTS = 4
QUERIES_PER_CLIENT = 25
INFLIGHT_QUERIES = 3
SLOW_Q = '~"request" | stats by (_msg) count() c, count_uniq(id) u'
OVERHEAD_CEILING = 1.10


def _start_bound(args, extra_env=None, retries=3):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(BASE_ENV)
    env.update(extra_env or {})
    for _ in range(retries):
        proc = subprocess.Popen(
            [sys.executable, "-m", "victorialogs_tpu.server",
             "-httpListenAddr", "127.0.0.1:0"] + args,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
            cwd=REPO)
        got = {}

        def rd():
            for raw in proc.stdout:
                line = raw.decode("utf-8", "replace").strip()
                if "started victoria-logs server at" in line:
                    got["port"] = int(line.rstrip("/").rsplit(":", 1)[1])
                    return

        t = threading.Thread(target=rd, daemon=True)
        t.start()
        t.join(60)
        if got.get("port"):
            return proc, got["port"]
        proc.terminate()
        proc.wait(10)
    raise RuntimeError("server did not start")


def _insert(port, rows, account=0):
    body = b"\n".join(json.dumps(r).encode() for r in rows)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/insert/jsonline?_stream_fields=app",
        data=body, headers={"AccountID": str(account)})
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert resp.status == 200


def _query(port, query, account=0, http_timeout=60, **extra):
    args = {"query": query, "limit": "0"}
    args.update(extra)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/select/logsql/query?"
        + urllib.parse.urlencode(args),
        headers={"AccountID": str(account)})
    with urllib.request.urlopen(req, timeout=http_timeout) as resp:
        return resp.status, dict(resp.headers), resp.read().decode()


def _get_json(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
        return json.loads(resp.read())


def _metrics(port):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
        return resp.read().decode()


def _sample(text, sample):
    for line in text.splitlines():
        if line.startswith(sample + " "):
            return float(line.split()[-1])
    return None


def _p50_round(port):
    """CLIENTS threads x QUERIES_PER_CLIENT stats queries; per-query
    wall p50/p99 + aggregate q/s."""
    lat = []
    mu = threading.Lock()

    def client():
        mine = []
        for _ in range(QUERIES_PER_CLIENT):
            t0 = time.monotonic()
            st, _h, _t = _query(port, "* | stats by (app) count() c",
                                timeout="30s")
            assert st == 200
            mine.append(time.monotonic() - t0)
        with mu:
            lat.extend(mine)

    t0 = time.monotonic()
    threads = [threading.Thread(target=client) for _ in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    lat.sort()
    return {
        "p50_s": round(statistics.median(lat), 5),
        "p99_s": round(lat[int(len(lat) * 0.99) - 1], 5),
        "queries": len(lat),
        "agg_qps": round(len(lat) / wall, 2),
    }


def _drain_nodes(node_ports, timeout=15.0):
    """Seconds until every node's active registry is empty."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        live = []
        for p in node_ports:
            live += _get_json(p, "/select/logsql/active_queries")["data"]
        if not live:
            return time.monotonic() - t0
        time.sleep(0.01)
    raise AssertionError(f"nodes still busy after {timeout}s: {live}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_cluster_obs.json")
    args = ap.parse_args()

    out = {"config": dict(BASE_ENV, rows=N_ROWS, clients=CLIENTS,
                          queries_per_client=QUERIES_PER_CLIENT,
                          rollup_cadence_ms=100)}
    procs = []
    tmp = tempfile.mkdtemp(prefix="vlbenchcobs")
    try:
        node_ports = []
        for k in range(3):
            proc, port = _start_bound(
                ["-storageDataPath", f"{tmp}/node{k}",
                 "-retentionPeriod", "100y"])
            procs.append(proc)
            node_ports.append(port)
        node_urls = [f"http://127.0.0.1:{p}" for p in node_ports]
        node_flags = sum((["-storageNode", u] for u in node_urls), [])

        # two frontends over the SAME nodes: rollups off vs on-fast
        front_off_p, front_off = _start_bound(
            ["-storageDataPath", f"{tmp}/front-off",
             "-retentionPeriod", "100y"] + node_flags,
            extra_env={"VL_CLUSTER_STATS_MS": "0"})
        procs.append(front_off_p)
        front_on_p, front_on = _start_bound(
            ["-storageDataPath", f"{tmp}/front-on",
             "-retentionPeriod", "100y"] + node_flags,
            extra_env={"VL_CLUSTER_STATS_MS": "100"})
        procs.append(front_on_p)

        light = [{"_time": 1_753_660_800_000_000_000 + i * 10**6,
                  "_msg": f"{'error' if i % 3 == 0 else 'ok'} req {i}",
                  "app": f"app{i % 10}"} for i in range(N_LIGHT)]
        _insert(front_on, light)
        for batch in range(6):
            heavy = [{"_time": 1_753_660_800_000_000_000
                      + (10**9) * (batch * 15000 + i),
                      "_msg": f"request {'error' if i % 3 == 0 else 'ok'}"
                              f" path=/x/{batch * 15000 + i}"
                              f" id={batch * 15000 + i}",
                      "app": f"app{i % 10}"}
                     for i in range(15000)]
            _insert(front_on, heavy, account=9)
        for p in node_ports:
            urllib.request.urlopen(
                f"http://127.0.0.1:{p}/internal/force_flush",
                timeout=30)

        # -- round 1: rollup overhead + differential --
        _p50_round(front_off)      # warm both paths once
        off = _p50_round(front_off)
        on = _p50_round(front_on)
        ratio = on["p50_s"] / off["p50_s"]
        # the differential: frontend rollup == sum of per-node counters
        deadline = time.monotonic() + 15
        diff_ok = False
        while time.monotonic() < deadline and not diff_ok:
            node_sum = sum(
                _sample(_metrics(p),
                        'vl_tenant_rows_ingested_total{tenant="9:0"}')
                or 0 for p in node_ports)
            roll = _sample(
                _metrics(front_on),
                'vl_cluster_tenant_rows_ingested_total{tenant="9:0"}')
            diff_ok = roll is not None and roll == node_sum \
                and node_sum == N_ROWS
            if not diff_ok:
                time.sleep(0.3)
        assert diff_ok, (roll, node_sum)
        out["rollup_overhead"] = {
            "p50_off_s": off["p50_s"], "p50_on_s": on["p50_s"],
            "p99_off_s": off["p99_s"], "p99_on_s": on["p99_s"],
            "agg_qps_off": off["agg_qps"], "agg_qps_on": on["agg_qps"],
            "p50_ratio": round(ratio, 4),
            "ceiling": OVERHEAD_CEILING,
            "differential_rows_exact": True,
        }
        print(f"rollup overhead: p50 {off['p50_s']}s off -> "
              f"{on['p50_s']}s on = {ratio:.3f}x "
              f"(ceiling {OVERHEAD_CEILING}x); differential exact "
              f"({N_ROWS} rows)")
        assert ratio <= OVERHEAD_CEILING, ratio

        # -- round 2: federated snapshot sees ALL in-flight queries --
        results = []
        threads = []
        for _ in range(INFLIGHT_QUERIES):
            r = {}
            results.append(r)
            t = threading.Thread(
                target=lambda r=r: r.update(
                    resp=_query(front_on, SLOW_Q, account=9,
                                timeout="60s")),
                daemon=True)
            threads.append(t)
        for t in threads:
            t.start()
        best = 0
        snap_linked = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and \
                any(t.is_alive() for t in threads):
            obj = _get_json(front_on,
                            "/select/logsql/active_queries?cluster=1")
            linked = [r for r in obj["data"]
                      if r.get("storage_node_queries")]
            if len(linked) > best:
                best = len(linked)
                snap_linked = linked
            if best >= INFLIGHT_QUERIES:
                break
            time.sleep(0.005)
        for t in threads:
            t.join(60)
        assert best >= INFLIGHT_QUERIES, \
            f"snapshot saw only {best}/{INFLIGHT_QUERIES} in flight"
        assert all(
            s["parent_qid"] == rec["global_qid"]
            for rec in snap_linked
            for s in rec["storage_node_queries"])
        sub_counts = [len(r["storage_node_queries"])
                      for r in snap_linked]
        out["federated_snapshot"] = {
            "inflight_queries": INFLIGHT_QUERIES,
            "linked_seen": best,
            "subqueries_per_query": sub_counts,
            "parent_linkage_exact": True,
        }
        print(f"federated snapshot: saw {best}/{INFLIGHT_QUERIES} "
              f"in-flight queries with sub-query linkage {sub_counts}")

        # -- round 3: cancel-propagation vs disconnect-probe latency --
        # (a) propagated cancel
        r = {}
        t = threading.Thread(
            target=lambda: r.update(
                resp=_query(front_on, SLOW_Q, account=9,
                            timeout="60s")),
            daemon=True)
        t.start()
        qid = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and qid is None:
            obj = _get_json(front_on,
                            "/select/logsql/active_queries?cluster=1")
            linked = [x for x in obj["data"]
                      if x.get("storage_node_queries")]
            if linked:
                qid = linked[0]["qid"]
            else:
                time.sleep(0.003)
        assert qid is not None, "never caught the query in flight"
        t_cancel = time.monotonic()
        req = urllib.request.Request(
            f"http://127.0.0.1:{front_on}/select/logsql/cancel_query"
            f"?qid={qid}", data=b"")
        with urllib.request.urlopen(req, timeout=30) as resp:
            cobj = json.loads(resp.read())
        assert cobj["propagated"]["cancelled"] >= 1, cobj
        prop_kill_s = _drain_nodes(node_ports)
        t.join(60)

        # (b) disconnect-probe baseline: same query, raw socket client
        # that hangs up mid-fan-out without cancelling.  The stats
        # response has exactly one write (at completion), so nothing
        # notices the dead peer until the sub-queries finish.
        qs = urllib.parse.urlencode(
            {"query": SLOW_Q, "limit": "0", "timeout": "60s"})
        sock = socket.create_connection(("127.0.0.1", front_on),
                                        timeout=10)
        sock.sendall(f"GET /select/logsql/query?{qs} HTTP/1.1\r\n"
                     f"Host: 127.0.0.1\r\nAccountID: 9\r\n"
                     f"\r\n".encode())
        deadline = time.monotonic() + 30
        seen = False
        while time.monotonic() < deadline and not seen:
            live = []
            for p in node_ports:
                live += _get_json(
                    p, "/select/logsql/active_queries")["data"]
            seen = any(x["endpoint"] == "/internal/select/query"
                       for x in live)
            if not seen:
                time.sleep(0.003)
        assert seen, "disconnect baseline never fanned out"
        sock.close()       # the disconnect — no cancel_query
        disc_kill_s = _drain_nodes(node_ports, timeout=90)
        speedup = disc_kill_s / max(prop_kill_s, 1e-4)
        out["cancel_latency"] = {
            "propagated_kill_s": round(prop_kill_s, 4),
            "disconnect_kill_s": round(disc_kill_s, 4),
            "speedup": round(speedup, 2),
        }
        print(f"cancel latency: propagated {prop_kill_s:.3f}s vs "
              f"disconnect {disc_kill_s:.3f}s ({speedup:.1f}x faster)")
        assert prop_kill_s < disc_kill_s, out["cancel_latency"]
        assert prop_kill_s < 2.0, prop_kill_s

        out["ok"] = True
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
        print(f"wrote {args.json}")
        return 0
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()


if __name__ == "__main__":
    sys.exit(main())
