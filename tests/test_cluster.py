"""Cluster integration tests: real server processes on localhost (the
reference's own multi-node test pattern — apptest/README.md, SURVEY §4).

Topology: 2 storage nodes + 1 front node started with -storageNode urls.
Ingest goes through the front (sharded by stream hash), queries
scatter-gather with the remote/local stats split."""

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
import urllib.parse
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    """A port nothing is listening on RIGHT NOW — only safe for
    simulating a DEAD endpoint.  Servers must never be started on a
    pre-picked port (two processes can draw the same one — the
    historical flake in the tpu-storage-nodes test); use _start_bound,
    which binds to port 0 and reports the OS-assigned port."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_http(port, timeout=30):
    for _ in range(int(timeout / 0.2)):
        try:
            socket.create_connection(("127.0.0.1", port), 0.3).close()
            return True
        except OSError:
            time.sleep(0.2)
    return False


def _start(args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return subprocess.Popen(
        [sys.executable, "-m", "victorialogs_tpu.server"] + args,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env, cwd=REPO)


def _read_banner(proc, timeout=60):
    """Scan the child's merged stdout for the startup banner
    ("started victoria-logs server at http://127.0.0.1:PORT/") with a
    wall-clock bound, skipping pre-banner noise (jax/absl warnings land
    on the same merged pipe under -tpu).  Returns the port, or None on
    EOF / timeout / unparseable banner.  The reader thread is daemonized
    so a child hung before printing can never block the suite."""
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


def _start_bound(args, retries=3):
    """Start a server on an OS-assigned port (-httpListenAddr :0) and
    return (proc, port) parsed from the startup banner.  Retries when
    startup dies early (e.g. EADDRINUSE from an auxiliary listener) —
    binding to port 0 removes the pick-then-bind race entirely."""
    for _ in range(retries):
        proc = _start(["-httpListenAddr", "127.0.0.1:0"] + args)
        port = _read_banner(proc)
        if port is not None and _wait_http(port):
            return proc, port
        proc.terminate()
        proc.wait(10)
    raise RuntimeError("server did not start (no startup banner)")


@pytest.fixture(scope="module")
def cluster():
    procs = []
    tmp = tempfile.mkdtemp(prefix="vlcluster")
    try:
        storage_ports = []
        for k in range(2):
            # 100y retention: the fixture's absolute 2026-07-28
            # timestamps must never age past the default 7d window
            # (they did — a wall-clock rollover flake)
            proc, port = _start_bound(
                ["-storageDataPath", f"{tmp}/node{k}",
                 "-retentionPeriod", "100y"])
            procs.append(proc)
            storage_ports.append(port)
        front, front_port = _start_bound(
            ["-storageDataPath", f"{tmp}/front",
             "-retentionPeriod", "100y"]
            + sum((["-storageNode", f"http://127.0.0.1:{p}"]
                   for p in storage_ports), []))
        procs.append(front)
        yield {"front": front_port, "nodes": storage_ports}
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()


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


def _query(port, query, **extra):
    args = {"query": query, "limit": "0"}
    args.update(extra)
    u = (f"http://127.0.0.1:{port}/select/logsql/query?"
         + urllib.parse.urlencode(args))
    with urllib.request.urlopen(u, timeout=60) as resp:
        text = resp.read().decode()
    return [json.loads(line) for line in text.splitlines() if line]


N_ROWS = 600
N_STREAMS = 10


@pytest.fixture(scope="module")
def ingested(cluster):
    rows = []
    for i in range(N_ROWS):
        rows.append({
            "_time": f"2026-07-28T10:{(i // 60) % 60:02d}:{i % 60:02d}Z",
            "_msg": f"{'error' if i % 3 == 0 else 'ok'} request {i}",
            "app": f"app{i % N_STREAMS}",
            "code": str(200 + (i % 5)),
        })
    _insert(cluster["front"], rows)
    for p in cluster["nodes"]:
        _flush(p)
    return cluster


def test_rows_sharded_across_nodes(ingested):
    counts = []
    for p in ingested["nodes"]:
        rows = _query(p, "* | stats count() n")
        counts.append(int(rows[0]["n"]))
    assert sum(counts) == N_ROWS
    # 10 streams hash-shard across 2 nodes: both must hold data
    assert all(c > 0 for c in counts), counts


def test_cluster_count_matches(ingested):
    rows = _query(ingested["front"], "* | stats count() as n")
    assert rows == [{"n": str(N_ROWS)}]


def test_cluster_filter_and_stats_split(ingested):
    rows = _query(ingested["front"], "error | stats count() as n")
    assert rows == [{"n": str(N_ROWS // 3)}]
    rows = _query(ingested["front"],
                  "* | stats by (app) count() as n | sort by (app)")
    assert len(rows) == N_STREAMS
    assert all(int(r["n"]) == N_ROWS // N_STREAMS for r in rows)


def test_cluster_count_uniq_merges_states(ingested):
    rows = _query(ingested["front"],
                  "* | stats count_uniq(app) as u, max(code) as m")
    assert rows == [{"u": str(N_STREAMS), "m": "204"}]


def test_cluster_raw_rows_and_local_pipes(ingested):
    rows = _query(ingested["front"],
                  'error | sort by (_time) | fields _msg | limit 5')
    assert len(rows) == 5
    assert all("error" in r["_msg"] for r in rows)


def test_cluster_stream_filter(ingested):
    rows = _query(ingested["front"], '{app="app3"} | stats count() as n')
    assert rows == [{"n": str(N_ROWS // N_STREAMS)}]


def test_cluster_hits_endpoint(ingested):
    u = (f"http://127.0.0.1:{ingested['front']}/select/logsql/hits?"
         + urllib.parse.urlencode({"query": "*", "step": "1h"}))
    with urllib.request.urlopen(u, timeout=60) as resp:
        obj = json.loads(resp.read())
    total = sum(sum(g["values"]) for g in obj["hits"])
    assert total == N_ROWS


def test_cluster_field_values(ingested):
    u = (f"http://127.0.0.1:{ingested['front']}/select/logsql/field_values?"
         + urllib.parse.urlencode({"query": "*", "field": "app"}))
    with urllib.request.urlopen(u, timeout=60) as resp:
        obj = json.loads(resp.read())
    assert len(obj["values"]) == N_STREAMS


def test_cluster_node_down_fails_query(ingested):
    # queries must fail loudly when a node is unreachable (no partial
    # results) — simulate with a front pointing at one live + one dead node
    dead = _free_port()
    import tempfile as tf
    tmp2 = tf.mkdtemp(prefix="vlfront2")
    front2, port = _start_bound(
        ["-storageDataPath", tmp2,
         "-storageNode", f"http://127.0.0.1:{ingested['nodes'][0]}",
         "-storageNode", f"http://127.0.0.1:{dead}"])
    try:
        u = (f"http://127.0.0.1:{port}/select/logsql/query?"
             + urllib.parse.urlencode({"query": "* | stats count() n"}))
        try:
            with urllib.request.urlopen(u, timeout=60) as resp:
                body = resp.read().decode()
                ok = resp.status == 200 and body.strip()
        except (urllib.error.HTTPError, OSError, Exception):
            # aborted chunked stream / HTTP error: the loud failure we want
            ok = False
        # either an HTTP error or an empty/errored stream — never a
        # partial count
        if ok:
            n = json.loads(body.splitlines()[0]).get("n")
            assert n is None or False, f"partial result returned: {body!r}"
    finally:
        front2.terminate()
        front2.wait(10)


def test_cluster_subquery_resolves_globally(ingested):
    # in(<subquery>) must materialize across ALL shards at the front, not
    # per-shard (values for app live on both nodes)
    rows = _query(ingested["front"],
                  'app:in(error | uniq by (app) | fields app) '
                  '| stats count() n')
    # every app stream has error rows => all rows match
    assert rows == [{"n": str(N_ROWS)}]


def test_cluster_join_pipe(ingested):
    rows = _query(ingested["front"],
                  'error | join by (app) (* | stats by (app) count() as '
                  'app_total) | limit 3 | fields app, app_total')
    assert len(rows) == 3
    assert all(r["app_total"] == str(N_ROWS // N_STREAMS) for r in rows)


def test_cluster_matches_single_node(ingested, tmp_path_factory):
    """Differential: the sharded cluster must answer exactly like a single
    node holding the same rows (sort-normalized where order is unspecified)."""
    import subprocess

    tmp = tempfile.mkdtemp(prefix="vlsingle")
    single, port = _start_bound(["-storageDataPath", tmp,
                                 "-retentionPeriod", "100y"])
    try:
        rows = []
        for i in range(N_ROWS):
            rows.append({
                "_time": f"2026-07-28T10:{(i // 60) % 60:02d}:"
                         f"{i % 60:02d}Z",
                "_msg": f"{'error' if i % 3 == 0 else 'ok'} request {i}",
                "app": f"app{i % N_STREAMS}",
                "code": str(200 + (i % 5)),
            })
        _insert(port, rows)
        _flush(port)

        queries = [
            "* | stats count() n",
            "error | stats by (app) count() n | sort by (app)",
            "* | stats count_uniq(app) u, max(code) mx, min(code) mn, "
            "sum(code) s, avg(code) a",
            "* | stats by (code) count() c | sort by (code)",
            'code:204 | sort by (_time) | fields _msg | limit 7',
            "* | uniq by (code) | sort by (code)",
            "* | top 3 by (app)",
            'error | extract "request <id>" | stats count_uniq(id) u',
            "* | math code + 1 as c1 | stats sum(c1) s",
            '{app=~"app[0-3]"} | stats count() n',
            "* | stats by (_time:10m) count() c | sort by (_time)",
            "* | facets 3",
        ]
        for qs in queries:
            single_rows = _query(port, qs)
            cluster_rows = _query(ingested["front"], qs)
            norm = lambda rs: sorted(  # noqa: E731
                (tuple(sorted(r.items())) for r in rs))
            assert norm(single_rows) == norm(cluster_rows), qs
    finally:
        single.terminate()
        single.wait(10)


def test_cluster_with_tpu_storage_nodes(tmp_path):
    """Full multi-process cluster where the STORAGE NODES run the device
    runner (-tpu on the jax-CPU backend): sharded ingest, stats pushdown
    through the device partials, results identical to a plain node."""
    procs = []
    tmp = str(tmp_path)
    try:
        ports = []
        for k in range(2):
            proc, port = _start_bound(
                ["-storageDataPath", f"{tmp}/tnode{k}",
                 "-retentionPeriod", "100y", "-tpu"])
            procs.append(proc)
            ports.append(port)
        front, front_port = _start_bound(
            ["-storageDataPath", f"{tmp}/tfront",
             "-retentionPeriod", "100y"]
            + sum((["-storageNode", f"http://127.0.0.1:{p}"]
                   for p in ports), []))
        procs.append(front)

        rows = []
        for i in range(4000):
            rows.append({"_time": 1_753_660_800_000_000_000 + i * 1_000_000,
                         "app": f"app{i % 5}",
                         "_msg": f"m {'err' if i % 3 == 0 else 'ok'} {i}",
                         "dur": str(i % 211)})
        _insert(front_port, rows)
        for p in ports:
            _flush(p)

        def q(query):
            url = (f"http://127.0.0.1:{front_port}/select/logsql/query?"
                   + urllib.parse.urlencode({
                       "query": query,
                       "start": "2025-07-01T00:00:00Z",
                       "end": "2025-08-30T00:00:00Z"}))
            with urllib.request.urlopen(url, timeout=60) as resp:
                return sorted(
                    (json.loads(l)
                     for l in resp.read().decode().splitlines()
                     if l.strip()), key=lambda r: sorted(r.items()))

        got = q("err | stats by (app) count() c, sum(dur) s")
        # expected computed directly
        exp = {}
        for i in range(4000):
            if i % 3 == 0:
                k = f"app{i % 5}"
                c, s_ = exp.get(k, (0, 0))
                exp[k] = (c + 1, s_ + i % 211)
        want = sorted(({"app": k, "c": str(c), "s": str(s_)}
                       for k, (c, s_) in exp.items()),
                      key=lambda r: sorted(r.items()))
        assert got == want
        got2 = q("* | stats count_uniq(_stream_id) u, count() c")
        assert got2 == [{"u": "5", "c": "4000"}]
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()
