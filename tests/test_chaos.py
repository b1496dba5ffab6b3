"""Network-chaos suite: real multi-process cluster (3 storage nodes +
frontend) with one node behind an in-process FaultProxy
(sched/netfaults.py).  Kills/degrades/revives that node and asserts
the fault-tolerance contract end to end:

- strict queries fail cleanly within the deadline (refuse AND hang —
  no 120s transport-timeout pin);
- ?partial=1 queries succeed from the surviving nodes, carrying
  X-VL-Partial + the partial.failed_nodes block;
- the breaker surfaces as vl_node_health on /metrics and recovers
  (half-open probe) after revival;
- with the node down during ingest, zero rows are lost: the frontend
  spools, the replay drains on revival, LogsQL counts come back exact.
"""

import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from victorialogs_tpu.sched.netfaults import FaultProxy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fast-recovery knobs for every server in this module: breaker opens
# after 2 failures, half-opens after 0.5s, one retry per sub-query
CHAOS_ENV = {
    "VL_BREAKER_OPEN_S": "0.5",
    "VL_BREAKER_FAILURES": "2",
    "VL_NET_RETRIES": "1",
}


def _start(args, extra_env=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(CHAOS_ENV)
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, "-m", "victorialogs_tpu.server"] + args,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        cwd=REPO)


def _read_banner(proc, timeout=60):
    import threading
    got = {}

    def rd():
        for raw in proc.stdout:
            line = raw.decode("utf-8", "replace").strip()
            if "started victoria-logs server at" in line:
                try:
                    got["port"] = int(line.rstrip("/").rsplit(":", 1)[1])
                except (IndexError, ValueError):
                    pass
                return

    t = threading.Thread(target=rd, daemon=True)
    t.start()
    t.join(timeout)
    return got.get("port")


def _start_bound(args, extra_env=None, retries=3):
    for _ in range(retries):
        proc = _start(["-httpListenAddr", "127.0.0.1:0"] + args,
                      extra_env=extra_env)
        port = _read_banner(proc)
        if port is not None:
            return proc, port
        proc.terminate()
        proc.wait(10)
    raise RuntimeError("server did not start (no startup banner)")


def _insert(port, rows, stream_fields="app"):
    body = b"\n".join(json.dumps(r).encode() for r in rows)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/insert/jsonline?"
        f"_stream_fields={stream_fields}", data=body)
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.status == 200


def _flush(port):
    urllib.request.urlopen(
        f"http://127.0.0.1:{port}/internal/force_flush", timeout=30)


def _query_raw(port, query, http_timeout=30, **extra):
    """extra kwargs become QUERY args (timeout="5s" is the server-side
    deadline; the client-side urlopen bound is http_timeout)."""
    args = {"query": query, "limit": "0"}
    args.update(extra)
    u = (f"http://127.0.0.1:{port}/select/logsql/query?"
         + urllib.parse.urlencode(args))
    with urllib.request.urlopen(u, timeout=http_timeout) as resp:
        return (resp.status, dict(resp.headers),
                resp.read().decode())


def _count(port, **extra):
    _st, _h, text = _query_raw(port, "* | stats count() n", **extra)
    for line in text.splitlines():
        obj = json.loads(line)
        if "n" in obj:
            return int(obj["n"])
    raise AssertionError(f"no count row in {text!r}")


def _rows(n, offset=0):
    out = []
    for i in range(offset, offset + n):
        out.append({
            "_time": f"2026-07-28T{10 + (i // 3600) % 4}:"
                     f"{(i // 60) % 60:02d}:{i % 60:02d}Z",
            "_msg": f"{'error' if i % 3 == 0 else 'ok'} request {i}",
            "app": f"app{i % 10}",
        })
    return out


N_ROWS = 600


@pytest.fixture(scope="module")
def chaos():
    """3 storage nodes; node2 is reached through a FaultProxy so tests
    can kill/degrade/revive it without touching the process."""
    procs = []
    proxy = None
    tmp = tempfile.mkdtemp(prefix="vlchaos")
    try:
        node_ports = []
        for k in range(3):
            proc, port = _start_bound(
                ["-storageDataPath", f"{tmp}/node{k}",
                 "-retentionPeriod", "100y"])
            procs.append(proc)
            node_ports.append(port)
        proxy = FaultProxy("127.0.0.1", node_ports[2])
        storage_urls = [f"http://127.0.0.1:{node_ports[0]}",
                        f"http://127.0.0.1:{node_ports[1]}", proxy.url]
        front, front_port = _start_bound(
            ["-storageDataPath", f"{tmp}/front",
             "-retentionPeriod", "100y"]
            + sum((["-storageNode", u] for u in storage_urls), []))
        procs.append(front)
        _insert(front_port, _rows(N_ROWS))
        for p in node_ports:
            _flush(p)
        per_node = [_count(p) for p in node_ports]
        assert sum(per_node) == N_ROWS
        assert all(c > 0 for c in per_node), per_node
        yield {"front": front_port, "nodes": node_ports,
               "proxy": proxy, "per_node": per_node,
               "storage_urls": storage_urls}
    finally:
        if proxy is not None:
            proxy.close()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()


def _wait_strict_ok(port, want, timeout=15):
    """Poll a strict query until the cluster answers completely again
    (breaker half-open probe + recovery)."""
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            if _count(port, timeout="5s") == want:
                return
        except (urllib.error.HTTPError, OSError) as e:
            last = e
        time.sleep(0.25)
    raise AssertionError(f"cluster did not recover: {last}")


def test_chaos_baseline_no_faults_exact(chaos):
    st, headers, text = _query_raw(chaos["front"],
                                   "* | stats count() n")
    assert st == 200
    assert headers.get("X-VL-Partial") is None
    lines = [json.loads(l) for l in text.splitlines() if l]
    assert lines == [{"n": str(N_ROWS)}]   # no _partial line either


def test_chaos_killed_node_strict_fails_fast_partial_succeeds(chaos):
    proxy = chaos["proxy"]
    live = N_ROWS - chaos["per_node"][2]
    proxy.set_mode("refuse")
    try:
        # strict: fails loudly, well before any transport timeout
        t0 = time.monotonic()
        with pytest.raises((urllib.error.HTTPError, OSError)):
            _query_raw(chaos["front"], "* | stats count() n",
                       timeout="5s")
        assert time.monotonic() - t0 < 10

        # partial=1: the survivors answer, loudly marked
        st, headers, text = _query_raw(chaos["front"],
                                       "* | stats count() n",
                                       partial="1", timeout="10s")
        assert st == 200
        assert headers.get("X-VL-Partial") == "true"
        lines = [json.loads(l) for l in text.splitlines() if l]
        counts = [l for l in lines if "n" in l]
        marks = [l for l in lines if "_partial" in l]
        assert counts == [{"n": str(live)}]
        assert len(marks) == 1
        assert marks[0]["_partial"]["failed_nodes"] == [proxy.url]

        # JSON endpoint: the partial block + header ride the payload
        u = (f"http://127.0.0.1:{chaos['front']}/select/logsql/hits?"
             + urllib.parse.urlencode({"query": "*", "step": "1d",
                                       "partial": "1",
                                       "timeout": "10s"}))
        with urllib.request.urlopen(u, timeout=30) as resp:
            assert resp.headers.get("X-VL-Partial") == "true"
            obj = json.loads(resp.read())
        assert obj["partial"]["failed_nodes"] == [proxy.url]
        assert sum(sum(g["values"]) for g in obj["hits"]) == live

        # the breaker surfaces on /metrics: the dead node at health 0,
        # the survivors at 1
        with urllib.request.urlopen(
                f"http://127.0.0.1:{chaos['front']}/metrics",
                timeout=30) as resp:
            metrics = resp.read().decode()
        assert f'vl_node_health{{node="{proxy.url}"}} 0' in metrics
        assert 'vl_net_retries_total' in metrics
    finally:
        proxy.set_mode("pass")
    _wait_strict_ok(chaos["front"], N_ROWS)


def test_chaos_hang_strict_bounded_by_deadline(chaos):
    """The hang-fault pin: a node that accepts and streams nothing must
    cost the query deadline (here 3s), not the 120s transport
    timeout."""
    proxy = chaos["proxy"]
    live = N_ROWS - chaos["per_node"][2]
    proxy.set_mode("hang")
    try:
        t0 = time.monotonic()
        with pytest.raises((urllib.error.HTTPError, OSError)):
            _query_raw(chaos["front"], "* | stats count() n",
                       timeout="3s")
        wall = time.monotonic() - t0
        assert wall < 10, f"hung node pinned the frontend for {wall}s"

        # partial mode: the hung node is declared failed AT the
        # deadline and the survivors' answer comes back marked
        st, headers, text = _query_raw(chaos["front"],
                                       "* | stats count() n",
                                       partial="1", timeout="3s")
        assert st == 200
        assert headers.get("X-VL-Partial") == "true"
        counts = [json.loads(l) for l in text.splitlines()
                  if l and "n" in json.loads(l)]
        assert counts == [{"n": str(live)}]
    finally:
        proxy.set_mode("pass")
    _wait_strict_ok(chaos["front"], N_ROWS)


def test_chaos_reset_mid_stream_strict_fails_cleanly(chaos):
    proxy = chaos["proxy"]
    # a stats sub-query's whole reply fits in ~250 bytes: cut inside
    # the response HEADERS so the reset lands mid-stream for sure
    proxy.reset_after_bytes = 40
    proxy.set_mode("reset")
    try:
        t0 = time.monotonic()
        with pytest.raises((urllib.error.HTTPError, OSError)):
            _query_raw(chaos["front"], "* | stats count() n",
                       timeout="5s")
        assert time.monotonic() - t0 < 10
    finally:
        proxy.reset_after_bytes = 256
        proxy.set_mode("pass")
    _wait_strict_ok(chaos["front"], N_ROWS)


def test_chaos_ingest_spool_zero_rows_lost():
    """Single-node cluster behind the proxy: node down during ingest ->
    the frontend spools (HTTP 200, rows delayed not dropped) -> node
    revives -> replay drains -> the LogsQL count is exact."""
    procs = []
    proxy = None
    tmp = tempfile.mkdtemp(prefix="vlchaos-spool")
    try:
        node, node_port = _start_bound(
            ["-storageDataPath", f"{tmp}/node",
             "-retentionPeriod", "100y"])
        procs.append(node)
        proxy = FaultProxy("127.0.0.1", node_port)
        front, front_port = _start_bound(
            ["-storageDataPath", f"{tmp}/front",
             "-retentionPeriod", "100y", "-storageNode", proxy.url])
        procs.append(front)

        _insert(front_port, _rows(100))
        assert _count(front_port) == 100

        proxy.set_mode("refuse")
        time.sleep(0.1)
        # ingest INTO the outage: every batch is accepted (200) and
        # spooled durably on the frontend
        for k in range(4):
            _insert(front_port, _rows(50, offset=100 + 50 * k))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{front_port}/metrics",
                timeout=30) as resp:
            metrics = resp.read().decode()
        assert "vl_insert_spooled_blocks_total" in metrics
        spooled = [l for l in metrics.splitlines()
                   if l.startswith("vl_insert_spooled_blocks_total")]
        assert spooled and float(spooled[0].split()[-1]) >= 1

        proxy.set_mode("pass")
        # replay is breaker-paced: half-open at 0.5s, then the queue
        # drains; every row must arrive (zero lost, exact count)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                if _count(front_port, timeout="5s") == 300:
                    break
            except (urllib.error.HTTPError, OSError):
                pass
            time.sleep(0.25)
        assert _count(front_port) == 300
        with urllib.request.urlopen(
                f"http://127.0.0.1:{front_port}/metrics",
                timeout=30) as resp:
            metrics = resp.read().decode()
        replayed = [l for l in metrics.splitlines()
                    if l.startswith("vl_insert_replayed_blocks_total")]
        assert replayed and float(replayed[0].split()[-1]) >= 1
        spool_gauge = [l for l in metrics.splitlines()
                       if l.startswith("vl_insert_spool_bytes")]
        assert spool_gauge and \
            all(float(l.split()[-1]) == 0 for l in spool_gauge)
    finally:
        if proxy is not None:
            proxy.close()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_chaos_ingest_status_stalled_and_ledger_balances_exactly():
    """The ingest-observability acceptance round: 3 nodes + frontend,
    every node behind a FaultProxy.  With the node set unreachable
    mid-ingest (a single down node fails over to its healthy
    siblings — spooling needs the whole set down),
    GET /insert/status?cluster=1 shows the stalled (spooled) batches
    and marks the nodes down; after revive + replay drain the
    row-conservation ledger balances EXACTLY cluster-wide —
    frontend accepted == sum(node stored) + dropped, zero in flight
    (received telescopes against forwarded across hops)."""
    procs = []
    proxies = []
    tmp = tempfile.mkdtemp(prefix="vlchaos-ledger")
    try:
        node_ports = []
        for k in range(3):
            proc, port = _start_bound(
                ["-storageDataPath", f"{tmp}/node{k}",
                 "-retentionPeriod", "100y"])
            procs.append(proc)
            node_ports.append(port)
            proxies.append(FaultProxy("127.0.0.1", port))
        storage_urls = [p.url for p in proxies]
        front, front_port = _start_bound(
            ["-storageDataPath", f"{tmp}/front",
             "-retentionPeriod", "100y"]
            + sum((["-storageNode", u] for u in storage_urls), []))
        procs.append(front)

        _insert(front_port, _rows(120))
        assert _count(front_port) == 120

        for p in proxies:
            p.set_mode("refuse")
        time.sleep(0.1)
        # ingest INTO the outage: every shard spools on the frontend
        for k in range(4):
            _insert(front_port, _rows(30, offset=120 + 30 * k))

        # stalled batches are visible cluster-wide while the nodes
        # are down, and the down nodes are marked
        deadline = time.monotonic() + 10
        st = None
        while time.monotonic() < deadline:
            st = _get_json(front_port, "/insert/status?cluster=1")
            if st.get("stalled_batches_cluster", 0) >= 1:
                break
            time.sleep(0.2)
        assert st["cluster"] is True
        assert st["stalled_batches_cluster"] >= 1, st
        ups = {n["node"]: n["up"] for n in st["nodes"]}
        assert not any(ups.values()), ups
        assert st["spool"]["pending_bytes"] > 0, st["spool"]
        # the spool gauges ride /metrics (depth, entries, age)
        metrics = _metrics_text(front_port)
        for g in ("vl_insert_spool_bytes", "vl_insert_spool_entries",
                  "vl_insert_spool_oldest_age_seconds"):
            assert g in metrics, g

        for p in proxies:
            p.set_mode("pass")
        # replay drains breaker-paced; wait for the exact count AND
        # the ledger to quiesce (no batch in flight, spool empty)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            try:
                if _count(front_port, timeout="5s") == 240:
                    st = _get_json(front_port,
                                   "/insert/status?cluster=1")
                    if st["spool"]["pending_bytes"] == 0 \
                            and not st["in_flight"]:
                        break
            except (urllib.error.HTTPError, OSError):
                pass
            time.sleep(0.25)
        assert _count(front_port) == 240

        # EXACT conservation for tenant 0:0 across processes
        st = _get_json(front_port, "/insert/status?cluster=1")
        local = st["ledger"]["0:0"]
        assert local["accepted"] == 240, local
        assert local["in_flight"] == 0, local
        assert local["dropped_rows"] == 0, local
        assert local["forwarded"] == local["accepted"], local
        assert local["replayed"] == local["spooled"], local
        stored = dropped = 0
        for n in st["nodes"]:
            assert n["up"] is True, n
            slot = n["ledger"].get("0:0", {})
            stored += slot.get("stored", 0)
            dropped += slot.get("dropped_rows", 0)
            assert slot.get("in_flight", 1) == 0, n
        assert stored + dropped == local["accepted"], st
        assert dropped == 0, st
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


# ---------------- cluster observability plane ----------------
#
# Real multi-process coverage for the federated registry + usage
# rollups: unlike the in-process suite (tests/test_cluster_obs.py,
# where every server shares one process-global registry), each node
# here accounts only its own share — so the rollup-vs-node-sum
# differential is a genuine cross-process aggregation check, and the
# qid linkage crosses real process boundaries.

def _insert_tenant(port, rows, account, stream_fields="app"):
    body = b"\n".join(json.dumps(r).encode() for r in rows)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/insert/jsonline?"
        f"_stream_fields={stream_fields}", data=body,
        headers={"AccountID": str(account)})
    with urllib.request.urlopen(req, timeout=60) as resp:
        assert resp.status == 200


def _metrics_text(port):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
        return resp.read().decode()


def _sample(text, sample):
    """Value of one exact /metrics sample name (labels included), or
    None when absent."""
    for line in text.splitlines():
        if line.startswith(sample + " "):
            return float(line.split()[-1])
    return None


def _get_json(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
        return json.loads(resp.read())


def test_cluster_obs_rollup_matches_per_node_sum(chaos):
    """The 3-node differential: frontend vl_cluster_tenant_* == the sum
    of every node's own vl_tenant_* for a tenant whose work is spread
    across all nodes."""
    front = chaos["front"]
    rows = [{"_time": f"2026-07-28T11:00:{i % 60:02d}Z",
             "_msg": f"tenant7 row {i}", "app": f"app{i % 10}"}
            for i in range(300)]
    _insert_tenant(front, rows, account=7)
    for p in chaos["nodes"]:
        _flush(p)
    # two tenant-7 queries so select_seconds accrues on every node
    for _ in range(2):
        req = urllib.request.Request(
            f"http://127.0.0.1:{front}/select/logsql/query?"
            + urllib.parse.urlencode({"query": "* | stats count() n",
                                      "timeout": "10s"}),
            headers={"AccountID": "7"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status == 200
            resp.read()

    series = (("vl_tenant_rows_ingested_total",
               "vl_cluster_tenant_rows_ingested_total"),
              ("vl_tenant_select_seconds_total",
               "vl_cluster_tenant_select_seconds_total"),
              ("vl_tenant_bytes_scanned_total",
               "vl_cluster_tenant_bytes_scanned_total"))
    lbl = '{tenant="7:0"}'
    deadline = time.monotonic() + 20
    last = None
    while time.monotonic() < deadline:
        node_sums = {}
        per_node_rows = []
        for p in chaos["nodes"]:
            text = _metrics_text(p)
            for node_name, _cl in series:
                v = _sample(text, node_name + lbl) or 0.0
                node_sums[node_name] = node_sums.get(node_name, 0) + v
            per_node_rows.append(
                _sample(text, "vl_tenant_rows_ingested_total" + lbl)
                or 0.0)
        ftext = _metrics_text(front)
        got = {cl: _sample(ftext, cl + lbl) for _n, cl in series}
        last = (node_sums, got, per_node_rows)
        ok = all(
            got[cl] is not None
            and abs(got[cl] - node_sums[nn])
            <= max(1e-6, 1e-6 * abs(node_sums[nn]))
            for nn, cl in series)
        # every node holds a share (the work really is spread), the
        # nodes' own counters sum to the ingested total, and the
        # frontend rollup equals that sum
        if ok and node_sums["vl_tenant_rows_ingested_total"] == 300 \
                and all(v > 0 for v in per_node_rows) \
                and node_sums["vl_tenant_select_seconds_total"] > 0:
            break
        time.sleep(0.3)
    else:
        raise AssertionError(
            f"rollup never converged to the per-node sum: {last}")
    # node liveness gauges ride the same rollup
    for url in chaos["storage_urls"]:
        assert _sample(ftext, f'vl_cluster_node_up{{node="{url}"}}') \
            == 1

    # federated top_queries across real processes: node-run sub-query
    # completions carry their node URL, the frontend's own completions
    # stay node="frontend", and nothing is listed twice
    tq = _get_json(front, "/select/logsql/top_queries?cluster=1&n=100")
    origins = {r["node"] for r in tq["top_queries"]}
    assert "frontend" in origins
    assert origins & set(chaos["storage_urls"]), origins
    assert any(r["endpoint"] == "/internal/select/query"
               and r.get("parent_qid")
               for r in tq["top_queries"]), \
        "node sub-query completions missing parent_qid attribution"
    seen = [json.dumps({k: v for k, v in r.items() if k != "node"},
                       sort_keys=True)
            for r in tq["top_queries"]]
    assert len(seen) == len(set(seen)), "federated merge double-counted"


def test_cluster_obs_federated_views_degrade_and_recover(chaos):
    """Chaos coverage: with one node dead, active_queries?cluster=1 and
    /select/logsql/tenants answer partially (node marked down, never a
    hang or 500); after revival the rollup recovers."""
    proxy = chaos["proxy"]
    front = chaos["front"]
    want = _count(front)          # before the fault: breaker closed
    proxy.set_mode("refuse")
    try:
        t0 = time.monotonic()
        obj = _get_json(front, "/select/logsql/active_queries?cluster=1")
        assert time.monotonic() - t0 < 10
        ups = {n["node"]: n["up"] for n in obj["nodes"]}
        assert ups[proxy.url] is False
        assert all(ups[u] for u in chaos["storage_urls"][:2])
        assert obj["failed_nodes"] == [proxy.url]

        # the rollup marks the node down within a couple of polls and
        # keeps serving the survivors' (and last-seen) totals
        deadline = time.monotonic() + 15
        down = None
        while time.monotonic() < deadline:
            tenants = _get_json(front, "/select/logsql/tenants")
            down = {n["node"]: n["up"] for n in tenants["nodes"]}
            if down[proxy.url] is False:
                break
            time.sleep(0.25)
        assert down and down[proxy.url] is False
        assert tenants["tenants"].get("0:0"), \
            "last-seen totals vanished with the node"
        assert _sample(_metrics_text(front),
                       f'vl_cluster_node_up{{node="{proxy.url}"}}') == 0
    finally:
        proxy.set_mode("pass")
    _wait_strict_ok(front, want)
    deadline = time.monotonic() + 15
    up = False
    while time.monotonic() < deadline and not up:
        tenants = _get_json(front, "/select/logsql/tenants")
        up = {n["node"]: n["up"] for n in tenants["nodes"]}[proxy.url]
        time.sleep(0.25)
    assert up, "rollup never recovered after revival"


def test_cluster_obs_linkage_and_cancel_propagation(chaos):
    """End-to-end qid traceability across real processes: the federated
    view nests each node's sub-query under the frontend query by
    propagated parent_qid, and cancel_query on the frontend qid kills
    the sub-queries on every node directly (no disconnect-probe lag).
    Runs LAST in this module: it ingests extra rows."""
    import threading
    front = chaos["front"]
    # enough data that the fan-out stays in flight long enough to
    # observe: ~45k rows across 3 nodes, under a dedicated tenant
    for batch in range(3):
        rows = [{"_time": f"2026-07-28T12:{(i // 60) % 60:02d}:"
                          f"{i % 60:02d}Z",
                 "_msg": f"request {'error' if i % 3 == 0 else 'ok'} "
                         f"path=/x/{i} id={i}",
                 "app": f"app{i % 10}"}
                for i in range(batch * 15000, (batch + 1) * 15000)]
        _insert_tenant(front, rows, account=9)
    for p in chaos["nodes"]:
        _flush(p)
    slow_q = ('~"request" | stats by (_msg) count() c, '
              'count_uniq(id) u')

    prop0 = sum(_sample(_metrics_text(p),
                        "vl_queries_cancel_propagated_total") or 0
                for p in chaos["nodes"])
    linked = cancelled = None
    for _attempt in range(6):
        result = {}

        def go():
            req = urllib.request.Request(
                f"http://127.0.0.1:{front}/select/logsql/query?"
                + urllib.parse.urlencode({"query": slow_q,
                                          "timeout": "30s"}),
                headers={"AccountID": "9"})
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()
                result["done"] = "ok"
            except (urllib.error.HTTPError, OSError) as e:
                result["done"] = str(e)
        t = threading.Thread(target=go, daemon=True)
        t.start()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and "done" not in result:
            obj = _get_json(front,
                            "/select/logsql/active_queries?cluster=1")
            got = [r for r in obj["data"]
                   if r.get("storage_node_queries")]
            if got:
                linked = got[0]
                break
            time.sleep(0.003)
        if linked is not None and "done" not in result:
            req = urllib.request.Request(
                f"http://127.0.0.1:{front}/select/logsql/cancel_query"
                f"?qid={linked['qid']}", data=b"")
            t_cancel = time.monotonic()
            with urllib.request.urlopen(req, timeout=30) as resp:
                cobj = json.loads(resp.read())
            if cobj["propagated"]["cancelled"] >= 1:
                cancelled = cobj
                t.join(20)
                break
        t.join(30)
        linked = None
    assert linked is not None, "never caught the fan-out in flight"
    assert cancelled is not None, \
        "cancel never reached an in-flight sub-query"

    # linkage shape: sub-records carry the propagated parent identity
    subs = linked["storage_node_queries"]
    assert subs and all(s["parent_qid"] == linked["global_qid"]
                        for s in subs)
    assert {s["node"] for s in subs} <= set(chaos["storage_urls"])

    # the kill is direct: every node's registry drains promptly (the
    # old path waited for the frontend disconnect probe / next frame
    # write), and the node-side propagation counter moved
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        live = []
        for p in chaos["nodes"]:
            live += _get_json(p, "/select/logsql/active_queries")["data"]
        if not live:
            break
        time.sleep(0.05)
    drain_s = time.monotonic() - t_cancel
    assert not live, f"sub-queries still live {drain_s:.1f}s after cancel"
    prop1 = sum(_sample(_metrics_text(p),
                        "vl_queries_cancel_propagated_total") or 0
                for p in chaos["nodes"])
    assert prop1 > prop0
