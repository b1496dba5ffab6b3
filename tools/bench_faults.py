"""Network-chaos bench: fault-tolerance contract measured on a real
multi-process cluster (2 healthy storage nodes + 1 behind a
sched.netfaults.FaultProxy).

Rounds (all recorded into BENCH_faults.json, asserting as it goes):

1. no-fault differential — query answers with the proxy passing
   through must be identical to the same query repeated (the policy
   layer is a no-op on a healthy cluster);
2. node killed (refuse) — strict queries fail within the deadline
   (never the 120s transport timeout), ?partial=1 answers from the
   survivors carrying the partial marker and the exact surviving
   count;
3. node hung (accept + stream nothing) — strict failure bounded by
   the request deadline;
4. recovery latency — time from revival to the first complete strict
   answer (breaker half-open probe pacing);
5. ingest outage — rows ingested while the only storage node is dead
   spool on the frontend and replay on revival: zero rows lost, exact
   LogsQL count, replay drain time recorded.  The outage must be
   VISIBLE while it lasts (GET /insert/status shows stalled batches +
   spool depth) and the conservation ledger must balance to the row
   afterwards (accepted == forwarded == node-stored, replayed ==
   spooled, zero in flight, zero dropped) on /insert/status?cluster=1.

Usage: python tools/bench_faults.py [--json BENCH_faults.json]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CHAOS_ENV = {
    "VL_BREAKER_OPEN_S": "0.5",
    "VL_BREAKER_FAILURES": "2",
    "VL_NET_RETRIES": "1",
}

N_ROWS = 3000
N_SPOOL_ROWS = 1000


def _start_bound(args, retries=3):
    import threading
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(CHAOS_ENV)
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


def _insert(port, rows):
    body = b"\n".join(json.dumps(r).encode() for r in rows)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/insert/jsonline?_stream_fields=app",
        data=body)
    with urllib.request.urlopen(req, timeout=60) as resp:
        assert resp.status == 200


def _rows(n, offset=0):
    return [{"_time": 1_753_660_800_000_000_000 + (offset + i) * 10**6,
             "_msg": f"{'error' if i % 3 == 0 else 'ok'} request {i}",
             "app": f"app{i % 10}"} for i in range(n)]


def _query(port, query, http_timeout=60, **extra):
    args = {"query": query, "limit": "0"}
    args.update(extra)
    u = (f"http://127.0.0.1:{port}/select/logsql/query?"
         + urllib.parse.urlencode(args))
    with urllib.request.urlopen(u, timeout=http_timeout) as resp:
        return resp.status, dict(resp.headers), resp.read().decode()


def _count(port, **extra):
    _s, _h, text = _query(port, "* | stats count() n", **extra)
    for line in text.splitlines():
        obj = json.loads(line)
        if "n" in obj:
            return int(obj["n"])
    raise AssertionError(f"no count in {text!r}")


def _get_json(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
        return json.loads(resp.read())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_faults.json")
    args = ap.parse_args()
    from victorialogs_tpu.sched.netfaults import FaultProxy

    out = {"config": dict(CHAOS_ENV, rows=N_ROWS,
                          spool_rows=N_SPOOL_ROWS)}
    procs = []
    proxies = []
    tmp = tempfile.mkdtemp(prefix="vlbenchfaults")
    try:
        ports = []
        for k in range(3):
            proc, port = _start_bound(
                ["-storageDataPath", f"{tmp}/node{k}",
                 "-retentionPeriod", "100y"])
            procs.append(proc)
            ports.append(port)
        proxy = FaultProxy("127.0.0.1", ports[2])
        proxies.append(proxy)
        front, front_port = _start_bound(
            ["-storageDataPath", f"{tmp}/front",
             "-retentionPeriod", "100y"]
            + sum((["-storageNode", u] for u in
                   [f"http://127.0.0.1:{ports[0]}",
                    f"http://127.0.0.1:{ports[1]}", proxy.url]), []))
        procs.append(front)
        _insert(front_port, _rows(N_ROWS))
        for p in ports:
            urllib.request.urlopen(
                f"http://127.0.0.1:{p}/internal/force_flush",
                timeout=30)
        dead_count = _count(ports[2])
        live = N_ROWS - dead_count

        # -- round 1: no-fault differential + healthy latency --
        q = "error | stats by (app) count() c | sort by (app)"
        base = _query(front_port, q)[2]
        assert _query(front_port, q)[2] == base, "unstable baseline"
        lat = []
        for _ in range(10):
            t0 = time.monotonic()
            assert _count(front_port) == N_ROWS
            lat.append(time.monotonic() - t0)
        out["healthy"] = {
            "identical_repeat": True,
            "count_exact": True,
            "p50_s": round(statistics.median(lat), 4),
        }
        print(f"healthy: p50 {out['healthy']['p50_s']}s, "
              f"differential identical")

        # -- round 2: node killed --
        proxy.set_mode("refuse")
        t0 = time.monotonic()
        strict_err = None
        try:
            _count(front_port, timeout="5s")
        except (urllib.error.HTTPError, OSError) as e:
            strict_err = type(e).__name__
        strict_fail_s = time.monotonic() - t0
        assert strict_err is not None, "strict query must fail"
        assert strict_fail_s < 5.0, strict_fail_s
        t0 = time.monotonic()
        st, headers, text = _query(front_port, "* | stats count() n",
                                   partial="1", timeout="10s")
        partial_s = time.monotonic() - t0
        lines = [json.loads(l) for l in text.splitlines() if l]
        n_part = int(next(l["n"] for l in lines if "n" in l))
        marks = [l for l in lines if "_partial" in l]
        assert st == 200 and headers.get("X-VL-Partial") == "true"
        assert n_part == live and len(marks) == 1
        out["killed"] = {
            "strict_fail_s": round(strict_fail_s, 4),
            "strict_error": strict_err,
            "partial_ok_s": round(partial_s, 4),
            "partial_count_exact": True,
            "failed_nodes": marks[0]["_partial"]["failed_nodes"],
        }
        print(f"killed: strict fails in {strict_fail_s:.3f}s, "
              f"partial answers {n_part}/{N_ROWS} in {partial_s:.3f}s")

        # -- round 3: recovery latency --
        proxy.set_mode("pass")
        t0 = time.monotonic()
        while True:
            try:
                if _count(front_port, timeout="5s") == N_ROWS:
                    break
            except (urllib.error.HTTPError, OSError):
                pass
            if time.monotonic() - t0 > 30:
                raise AssertionError("no recovery within 30s")
            time.sleep(0.05)
        recovery_s = time.monotonic() - t0
        out["recovery"] = {"strict_ok_after_s": round(recovery_s, 4)}
        print(f"recovery: strict complete answer after "
              f"{recovery_s:.3f}s")

        # -- round 4: hang bounded by deadline --
        proxy.set_mode("hang")
        t0 = time.monotonic()
        hang_err = None
        try:
            _count(front_port, timeout="2s", http_timeout=60)
        except (urllib.error.HTTPError, OSError) as e:
            hang_err = type(e).__name__
        hang_s = time.monotonic() - t0
        assert hang_err is not None and hang_s < 8.0, \
            (hang_err, hang_s)
        out["hang"] = {"strict_fail_s": round(hang_s, 4),
                       "deadline_s": 2.0}
        print(f"hang: strict fails in {hang_s:.3f}s "
              f"(deadline 2s, transport timeout would be 120s)")
        proxy.set_mode("pass")

        # -- round 5: ingest outage -> spool -> replay, zero loss --
        node_s, node_s_port = _start_bound(
            ["-storageDataPath", f"{tmp}/spoolnode",
             "-retentionPeriod", "100y"])
        procs.append(node_s)
        sproxy = FaultProxy("127.0.0.1", node_s_port)
        proxies.append(sproxy)
        front_s, front_s_port = _start_bound(
            ["-storageDataPath", f"{tmp}/spoolfront",
             "-retentionPeriod", "100y", "-storageNode", sproxy.url])
        procs.append(front_s)
        _insert(front_s_port, _rows(500))
        assert _count(front_s_port) == 500
        sproxy.set_mode("refuse")
        time.sleep(0.1)
        t0 = time.monotonic()
        for k in range(4):
            _insert(front_s_port,
                    _rows(N_SPOOL_ROWS // 4,
                          offset=500 + k * (N_SPOOL_ROWS // 4)))
        ingest_s = time.monotonic() - t0

        # the outage must be VISIBLE while it lasts: GET /insert/status
        # shows the spooled batches as stalled and a non-empty durable
        # spool (poll briefly — the ship->spool handoff is async
        # relative to the ingest 200s)
        t0 = time.monotonic()
        while True:
            st = _get_json(front_s_port, "/insert/status")
            if st["stalled_batches"] >= 1 and \
                    st["spool"]["pending_bytes"] > 0:
                break
            if time.monotonic() - t0 > 10:
                raise AssertionError(f"outage invisible on "
                                     f"/insert/status: {st}")
            time.sleep(0.1)
        stall_seen = {
            "stalled_batches": st["stalled_batches"],
            "spool_pending_bytes": st["spool"]["pending_bytes"],
            "spool_entries": st["spool"].get("entries"),
        }
        print(f"outage visible: {stall_seen['stalled_batches']} stalled "
              f"batches, {stall_seen['spool_pending_bytes']} spool bytes")

        sproxy.set_mode("pass")
        t0 = time.monotonic()
        while True:
            try:
                if _count(front_s_port, timeout="5s") == \
                        500 + N_SPOOL_ROWS:
                    break
            except (urllib.error.HTTPError, OSError):
                pass
            if time.monotonic() - t0 > 60:
                raise AssertionError(
                    f"spool replay incomplete: "
                    f"{_count(front_s_port, partial='1')}")
            time.sleep(0.1)
        replay_s = time.monotonic() - t0

        # exact conservation after the drain: the federated status must
        # balance to the row — accepted rows all forwarded, every
        # spooled row replayed, nothing in flight, nothing dropped,
        # and the storage node's ledger shows them all stored
        total = 500 + N_SPOOL_ROWS
        t0 = time.monotonic()
        while True:
            st = _get_json(front_s_port, "/insert/status?cluster=1")
            if st["spool"]["pending_bytes"] == 0 and \
                    not st["in_flight"]:
                break
            if time.monotonic() - t0 > 30:
                raise AssertionError(f"ledger did not settle: {st}")
            time.sleep(0.1)
        assert st["cluster"] is True, st
        led = st["ledger"]["0:0"]
        assert led["accepted"] == total, led
        assert led["forwarded"] == total, led
        assert led["in_flight"] == 0, led
        assert led["dropped_rows"] == 0, led
        assert led["replayed"] == led["spooled"], led
        node_stored = sum(
            (n.get("ledger") or {}).get("0:0", {}).get("stored", 0)
            for n in st["nodes"] if n["up"])
        assert node_stored == total, (node_stored, st["nodes"])
        assert st["stalled_batches_cluster"] == 0, st

        out["ingest_outage"] = {
            "rows_during_outage": N_SPOOL_ROWS,
            "ingest_accept_s": round(ingest_s, 4),
            "replay_drain_s": round(replay_s, 4),
            "rows_lost": 0,
            "count_exact": True,
            "outage_visible": stall_seen,
            "ledger_balanced_exact": True,
            "ledger": {k: led[k] for k in
                       ("accepted", "forwarded", "spooled", "replayed",
                        "in_flight", "dropped_rows")},
        }
        print(f"ingest outage: {N_SPOOL_ROWS} rows accepted in "
              f"{ingest_s:.3f}s while node dead, replay drained in "
              f"{replay_s:.3f}s, zero rows lost")

        out["ok"] = True
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
        print(f"wrote {args.json}")
        return 0
    finally:
        for p in proxies:
            p.close()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()


if __name__ == "__main__":
    sys.exit(main())
