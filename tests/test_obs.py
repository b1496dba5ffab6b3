"""vltrace observability layer: span-tree shape over the packed device
pipeline, bit-identical results with tracing on/off, no open spans on
cancellation/deadline unwinds, ?trace=1 JSON round-trips over HTTP,
Prometheus exposition validity (parsed), occupancy/cost gauges, the
slow-query log, and the disabled path's zero-span/zero-ish overhead
bound."""

import json
import http.client
import re
import time
import urllib.parse

import pytest

from victorialogs_tpu.engine.searcher import (QueryTimeoutError,
                                              run_query_collect)
from victorialogs_tpu.obs import hist, slowlog, tracing
from victorialogs_tpu.storage.log_rows import LogRows, TenantID
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu.batch import BatchRunner

NS = 1_000_000_000
T0 = 1_753_660_800_000_000_000  # 2025-07-28T00:00:00Z
TEN = TenantID(0, 0)
N_PARTS = 12                    # < datadb.DEFAULT_PARTS_TO_MERGE (15)
ROWS_PER_PART = 600


@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    """Many SMALL parts in one partition — the packed-pipeline shape,
    so traces cover pack super-dispatches with member attribution."""
    path = str(tmp_path_factory.mktemp("obsstore"))
    s = Storage(path, retention_days=100000, flush_interval=3600)
    n = 0
    for _pp in range(N_PARTS):
        lr = LogRows(stream_fields=["app"])
        for _i in range(ROWS_PER_PART):
            g = n
            n += 1
            lr.add(TEN, T0 + g * 50_000_000, [
                ("app", f"app{g % 4}"),
                ("_msg", f"GET /api/x{g % 7} "
                         f"{'error' if g % 3 == 0 else 'ok'} d={g % 97}"),
                ("lvl", ["info", "warn", "error"][g % 3]),
                ("dur", str(g % 251)),
            ])
        s.must_add_rows(lr)
        s.debug_flush()
    yield s
    s.close()


@pytest.fixture(scope="module")
def runner():
    return BatchRunner()


def find_spans(tree: dict, name: str) -> list:
    out = []

    def walk(n):
        if n.get("name") == name:
            out.append(n)
        for c in n.get("children", ()):
            walk(c)

    walk(tree)
    return out


def traced_query(storage, q, runner, **kw):
    root = tracing.make_root("query", query=q)
    with tracing.activate(root):
        rows = run_query_collect(storage, [TEN], q, runner=runner, **kw)
    return rows, root


# ---------------- span-tree shape ----------------

def test_trace_tree_covers_prune_stage_submit_harvest(storage, runner):
    rows, root = traced_query(storage, 'error | fields _time', runner)
    assert rows
    assert root.open_spans() == 0
    tree = root.to_dict()
    assert tree["name"] == "query"
    assert tree["attrs"]["query"] == 'error | fields _time'
    parts = find_spans(tree, "partition")
    assert len(parts) == 1
    pipelines = find_spans(tree, "pipeline")
    assert len(pipelines) == 1
    for stage in ("prune", "stage", "submit", "harvest"):
        assert find_spans(tree, stage), f"missing {stage} span"
    # per-stage monotonic timings: every span inside its parent's window
    def check(n, lo, hi):
        t0, t1 = n["start_ms"], n["start_ms"] + n["duration_ms"]
        assert n["duration_ms"] >= 0
        assert t0 >= lo - 0.5 and t1 <= hi + 0.5, n["name"]
        for c in n.get("children", ()):
            check(c, t0, t1)
    check(tree, tree["start_ms"],
          tree["start_ms"] + tree["duration_ms"])
    # submission/harvest pair up by unit
    subs = find_spans(tree, "submit")
    harvs = find_spans(tree, "harvest")
    assert {s["attrs"]["unit"] for s in subs} == \
        {h["attrs"]["unit"] for h in harvs}


def test_trace_pack_units_carry_member_attribution(storage, runner):
    _rows, root = traced_query(storage, 'error | fields _time', runner)
    subs = find_spans(root.to_dict(), "submit")
    packed = [s for s in subs if "pack_size" in s["attrs"]]
    assert packed, "expected at least one packed super-dispatch"
    for s in packed:
        members = s["attrs"]["pack_members"]
        assert s["attrs"]["pack_size"] == len(members) > 1
        assert len(set(members)) == len(members)
    # every fixture part appears in exactly one unit's attribution
    all_members = [m for s in packed for m in s["attrs"]["pack_members"]]
    singles = [s["attrs"]["part"] for s in subs
               if "part" in s["attrs"]]
    assert len(all_members) + len(singles) >= N_PARTS


def test_trace_prune_and_bloom_counters(storage, runner):
    # a token absent from every row: aggregate part kills + bloom
    # zero-hits must show up as prune accounting
    rows, root = traced_query(storage, '"zebra-absent-token"', runner)
    assert rows == []
    tree = root.to_dict()
    flat = root.flatten()
    assert flat["query"]["count"] == 1

    def total(key):
        out = 0

        def walk(n):
            nonlocal out
            out += n.get("attrs", {}).get(key, 0)
            for c in n.get("children", ()):
                walk(c)
        walk(tree)
        return out
    # either the part-level aggregate killed parts, or the per-block
    # bloom killed every candidate block — both are prune evidence
    assert total("parts_pruned_aggregate") + total("blocks_killed_bloom") \
        > 0


def test_trace_results_bit_identical(storage, runner):
    q = 'lvl:error dur:>100 | fields _time, dur'
    plain = run_query_collect(storage, [TEN], q, runner=runner)
    traced, root = traced_query(storage, q, runner)
    assert traced == plain
    assert root.open_spans() == 0


def test_trace_stats_query(storage, runner):
    q = '* | stats by (lvl) count() hits'
    plain = run_query_collect(storage, [TEN], q, runner=runner)
    traced, root = traced_query(storage, q, runner)
    assert sorted(map(str, traced)) == sorted(map(str, plain))
    assert root.open_spans() == 0


# ---------------- cancellation / deadline ----------------

def test_trace_no_open_spans_after_early_limit(storage, runner):
    rows, root = traced_query(storage, 'ok | limit 3', runner)
    assert len(rows) == 3
    assert root.open_spans() == 0


def test_trace_no_open_spans_after_deadline(storage, runner):
    root = tracing.make_root("query", query="*")
    with pytest.raises(QueryTimeoutError):
        with tracing.activate(root):
            run_query_collect(storage, [TEN], '*', runner=runner,
                              deadline=time.monotonic() - 1.0)
    assert root.open_spans() == 0
    # the error is recorded on the span that died
    assert root.attrs.get("error") == "QueryTimeoutError"


# ---------------- disabled-path overhead ----------------

def test_disabled_trace_is_zero_span_and_cheap(storage, runner):
    q = 'error | fields _time'
    run_query_collect(storage, [TEN], q, runner=runner)  # warm
    before = tracing.spans_created()
    t0 = time.perf_counter()
    plain = run_query_collect(storage, [TEN], q, runner=runner)
    t_off = time.perf_counter() - t0
    # structural zero: a tracing-disabled query creates NO spans —
    # the no-op singleton absorbed every instrumentation call
    assert tracing.spans_created() == before
    t0 = time.perf_counter()
    traced, _root = traced_query(storage, q, runner)
    t_on = time.perf_counter() - t0
    assert traced == plain
    # the untraced run must sit within noise of the traced one (the
    # instrumentation cost lives on the traced side; generous bound —
    # this guards against the disabled path picking up real work)
    assert t_off <= t_on * 3 + 0.25, (t_off, t_on)


def test_noop_span_microbench():
    sp = tracing.current_span()          # no active trace -> noop
    assert sp is tracing.current_span()  # shared singleton
    assert not sp.enabled
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with sp.span("x") as s:
            s.add("k")
            s.set("v", 1)
    per_op = (time.perf_counter() - t0) / n
    assert per_op < 5e-6, per_op          # ≈0: sub-microsecond typical


# ---------------- HTTP round trip ----------------

def _req(srv, method, path, body=None, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
    conn.request(method, path, body=body, headers=headers or {})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _mk_server(tmp_path, runner, **kw):
    from victorialogs_tpu.server.app import VLServer
    storage = Storage(str(tmp_path / "data"), retention_days=100000,
                      flush_interval=3600)
    srv = VLServer(storage, listen_addr="127.0.0.1", port=0,
                   runner=runner, **kw)
    return srv, storage


def _ingest(srv, n=40):
    body = "\n".join(json.dumps({
        "_time": T0 + i * NS,
        "_msg": f"hello {'error' if i % 2 else 'ok'} {i}",
        "app": "web",
    }) for i in range(n))
    status, _ = _req(srv, "POST",
                     "/insert/jsonline?_stream_fields=app",
                     body=body.encode())
    assert status == 200
    _req(srv, "GET", "/internal/force_flush")


def test_http_trace_roundtrip(tmp_path, runner):
    srv, storage = _mk_server(tmp_path, runner)
    try:
        _ingest(srv)
        q = urllib.parse.quote("error")
        _s, plain = _req(srv, "GET",
                         f"/select/logsql/query?query={q}&limit=100")
        _s, traced = _req(
            srv, "GET",
            f"/select/logsql/query?query={q}&limit=100&trace=1")
        plain_lines = plain.decode().splitlines()
        traced_lines = traced.decode().splitlines()
        # the trace rides ONE extra final line; rows are bit-identical
        assert traced_lines[:-1] == plain_lines
        tree = json.loads(traced_lines[-1])["_trace"]
        # the root is the request; the handler's `query` span (name,
        # extent and subtree as before) hangs beneath it
        assert tree["name"] == "request"
        (qtree,) = [c for c in tree["children"] if c["name"] == "query"]
        assert "error" in qtree["attrs"]["query"]
        assert find_spans(qtree, "partition")
        assert find_spans(tree, "partition")
        assert find_spans(tree, "harvest")
        # round-trips through JSON
        assert json.loads(json.dumps(tree)) == tree

        # stats endpoint carries the tree under "trace"
        sq = urllib.parse.quote("* | stats count() hits")
        _s, data = _req(srv, "GET",
                        f"/select/logsql/stats_query?query={sq}&trace=1")
        obj = json.loads(data)
        assert obj["trace"]["name"] == "request"
        assert [c["name"] for c in obj["trace"]["children"]] == \
            ["admission_wait", "parse", "query"]
        _s, data = _req(srv, "GET",
                        f"/select/logsql/stats_query?query={sq}")
        assert "trace" not in json.loads(data)
    finally:
        srv.close()
        storage.close()


def test_cluster_scatter_gather_trace(tmp_path, runner):
    """?trace=1 through a 2-storage-node cluster: the frontend's tree
    has one storage_node child per node with the node's own remote
    span tree attached under it."""
    n1, s1 = _mk_server(tmp_path / "n1", None)
    n2, s2 = _mk_server(tmp_path / "n2", None)
    front, sf = _mk_server(
        tmp_path / "front", runner,
        storage_nodes=[f"http://127.0.0.1:{n1.port}",
                       f"http://127.0.0.1:{n2.port}"])
    try:
        _ingest(front)
        for node in (n1, n2):
            _req(node, "GET", "/internal/force_flush")
        q = urllib.parse.quote("error")
        _s, plain = _req(front, "GET",
                         f"/select/logsql/query?query={q}&limit=100")
        _s, traced = _req(
            front, "GET",
            f"/select/logsql/query?query={q}&limit=100&trace=1")
        plain_lines = sorted(plain.decode().splitlines())
        traced_lines = traced.decode().splitlines()
        assert plain_lines, "cluster query returned no rows"
        tree = json.loads(traced_lines[-1])["_trace"]
        assert sorted(traced_lines[:-1]) == plain_lines
        nodes = find_spans(tree, "storage_node")
        assert len(nodes) == 2
        urls = {n["attrs"]["url"] for n in nodes}
        assert len(urls) == 2
        # each node shipped its own trace, merged scatter-gather style
        with_parts = 0
        for n in nodes:
            remotes = [c for c in n.get("children", ())
                       if c.get("name") == "storage_node_query"]
            assert len(remotes) == 1
            if find_spans(remotes[0], "partition"):
                with_parts += 1
        # rows shard by stream hash: one stream -> one node holds all
        # the data, the other's remote trace is legitimately partition-
        # free; at least the data-bearing node must show its scan
        assert with_parts >= 1
    finally:
        front.close()
        n1.close()
        n2.close()
        for s in (s1, s2, sf):
            s.close()


# ---------------- slow-query log ----------------

def test_slow_query_log(tmp_path, runner, monkeypatch):
    monkeypatch.setenv("VL_SLOW_QUERY_MS", "0")   # everything is slow
    lines: list = []
    slowlog.set_sink(lines.append)
    try:
        srv, storage = _mk_server(tmp_path, runner)
        try:
            _ingest(srv)
            q = urllib.parse.quote("error")
            _req(srv, "GET",
                 f"/select/logsql/query?query={q}&limit=10")
        finally:
            srv.close()
            storage.close()
        assert lines
        rec = json.loads(lines[-1])
        assert rec["msg"] == "slow query"
        assert rec["endpoint"] == "/select/logsql/query"
        assert rec["duration_ms"] >= 0
        assert "error" in rec["query"]
        # the flattened trace summary rides along even without ?trace=1
        assert rec["trace"]["query"]["count"] == 1
        assert rec["trace"]["query"]["total_ms"] > 0
    finally:
        slowlog.set_sink(None)


def test_slow_query_log_off_by_default(monkeypatch):
    monkeypatch.delenv("VL_SLOW_QUERY_MS", raising=False)
    assert not slowlog.enabled()
    assert not slowlog.maybe_log("/x", "*", 999.0, None)


# ---------------- Prometheus exposition validity ----------------

_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})? '
    r'(-?[0-9.eE+-]+|[+-]Inf|NaN)$')


def parse_prometheus(text: str):
    """Small exposition-format validator: returns {sample_name: value};
    asserts TYPE-before-samples, no duplicate TYPE lines, no duplicate
    samples, parseable label escaping."""
    samples: dict[str, float] = {}
    typed: dict[str, str] = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, kind = rest.rsplit(" ", 1)
            assert name not in typed, f"duplicate TYPE for {name}"
            assert kind in ("counter", "gauge", "histogram", "summary")
            typed[name] = kind
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        full = m.group(1) + (m.group(2) or "")
        assert full not in samples, f"duplicate sample {full}"
        samples[full] = float(m.group(4))
        base = m.group(1)
        for suffix in ("_bucket", "_sum", "_count"):
            if base.endswith(suffix) and \
                    base[:-len(suffix)] in typed:
                base = base[:-len(suffix)]
                break
        assert base in typed, f"sample {base} missing # TYPE"
    return samples


def test_metrics_prometheus_valid_and_collision_free(tmp_path, runner):
    srv, storage = _mk_server(tmp_path, runner)
    try:
        _ingest(srv)
        # force a name collision: a registry counter that shadows a
        # runner stat must merge, not duplicate
        srv.metrics.inc("vl_tpu_device_calls", 7)
        # and a label value needing escaping must render parseable
        from victorialogs_tpu.server.app import metric_name
        srv.metrics.inc(metric_name("vl_test_escape_total",
                                    path='we"ird\\p\nath'))
        q = urllib.parse.quote("error")
        _req(srv, "GET", f"/select/logsql/query?query={q}&limit=10")
        _s, body = _req(srv, "GET", "/metrics")
        samples = parse_prometheus(body.decode())
        # the collision merged: runner count + 7
        dev = [k for k in samples if k == "vl_tpu_device_calls"]
        assert len(dev) == 1
        assert samples["vl_tpu_device_calls"] >= 7
        # escaped label round-trips
        assert any(k.startswith("vl_test_escape_total{") for k in samples)
    finally:
        srv.close()
        storage.close()


def test_metrics_histograms_and_gauges(tmp_path, runner):
    srv, storage = _mk_server(tmp_path, runner)
    try:
        _ingest(srv)
        q = urllib.parse.quote("error")
        _req(srv, "GET", f"/select/logsql/query?query={q}&limit=10")
        _s, body = _req(srv, "GET", "/metrics")
        text = body.decode()
        samples = parse_prometheus(text)
        # acceptance: # TYPE-annotated histograms for query duration
        # and dispatch RTT
        assert "# TYPE vl_query_duration_seconds histogram" in text
        assert "# TYPE vl_tpu_dispatch_rtt_seconds histogram" in text
        assert samples["vl_query_duration_seconds_count"] >= 1
        # histogram internal consistency: cumulative buckets, +Inf=count
        for h in ("vl_query_duration_seconds",
                  "vl_tpu_dispatch_rtt_seconds",
                  "vl_tpu_host_sync_wait_seconds",
                  "vl_tpu_pack_size_parts",
                  "vl_tpu_bloom_prune_ratio"):
            buckets = [(k, v) for k, v in samples.items()
                       if k.startswith(h + "_bucket{")]
            assert buckets, h
            vals = [v for _k, v in buckets]
            assert vals == sorted(vals)
            inf = [v for k, v in buckets if 'le="+Inf"' in k]
            assert inf and inf[0] == samples[h + "_count"]
        # occupancy + cost-model gauges (satellites 2-3)
        for g in ("vl_tpu_bloom_bank_used_bytes",
                  "vl_tpu_bloom_bank_max_bytes",
                  "vl_tpu_staging_cache_bytes",
                  "vl_tpu_pack_cache_entries",
                  "vl_tpu_cost_rtt_seconds",
                  "vl_tpu_cost_dev_bytes_per_s",
                  "vl_tpu_pack_rows_cap"):
            assert g in samples, g
        assert samples["vl_tpu_bloom_bank_max_bytes"] > 0
    finally:
        srv.close()
        storage.close()


def test_histogram_unit():
    h = hist.Histogram("t_unit_seconds", "help", (0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    cum, s, c = h.snapshot()
    assert cum == [1, 2, 3, 4]
    assert c == 4 and abs(s - 55.55) < 1e-9
    lines = h.render()
    assert lines[0].startswith("# HELP t_unit_seconds")
    assert lines[1] == "# TYPE t_unit_seconds histogram"
    assert 't_unit_seconds_bucket{le="+Inf"} 4' in lines


# ---------------- review-hardening regressions ----------------

def test_bloom_probe_observe_flag_suppresses_metrics(storage):
    """The prefetcher's warm-up probe must not double-count: with
    observe=False neither the prune-ratio histogram nor the ambient
    span move; the default (evaluator) probe moves both."""
    from victorialogs_tpu.storage.filterbank import bloom_keep_mask
    from victorialogs_tpu.utils.hashing import hash_tokens
    pt = next(iter(storage.partitions.values()))
    part = [p for p in pt.ddb.snapshot_parts() if p.num_rows][0]
    hashes = hash_tokens(["error"])
    before = hist.PRUNE_RATIO.snapshot()[2]
    root = tracing.make_root("t")
    with tracing.activate(root):
        bloom_keep_mask(part, "_msg", hashes, [0], observe=False)
    assert hist.PRUNE_RATIO.snapshot()[2] == before
    assert "blocks_probed_bloom" not in root.attrs
    with tracing.activate(tracing.make_root("t2")) as r2:
        bloom_keep_mask(part, "_msg", hashes, [0])
    assert hist.PRUNE_RATIO.snapshot()[2] == before + 1
    assert r2.attrs.get("blocks_probed_bloom") == 1


def test_prefetch_staging_attribution_reaches_trace(tmp_path):
    """Staging done on the vl-prefetch worker must attribute
    staged_entries/staged_bytes to the caller's span (a fresh runner +
    fresh parts => cold staging, mostly via prefetch)."""
    s = Storage(str(tmp_path / "d"), retention_days=100000,
                flush_interval=3600)
    try:
        for pp in range(6):
            lr = LogRows(stream_fields=["app"])
            for i in range(300):
                g = pp * 300 + i
                lr.add(TEN, T0 + g * NS, [
                    ("app", "web"),
                    ("_msg", f"m {'error' if g % 2 else 'ok'} {g}")])
            s.must_add_rows(lr)
            s.debug_flush()
        r = BatchRunner()
        rows, root = traced_query(s, 'error | fields _time', r)
        assert rows
        # let any straggler prefetch land its attrs (lock-guarded)
        r.close()

        def total(n, key):
            out = n.get("attrs", {}).get(key, 0)
            for c in n.get("children", ()):
                out += total(c, key)
            return out
        tree = root.to_dict()
        assert total(tree, "staged_entries") > 0
        assert total(tree, "staged_bytes") > 0
    finally:
        s.close()


def test_cluster_trace_truncation_marked(tmp_path, runner):
    """An early-done cluster query (limit satisfied mid-stream) may cut
    a node's trailing trace frame — the frontend must mark the cut
    instead of silently presenting a complete-looking tree."""
    n1, s1 = _mk_server(tmp_path / "n1", None)
    front, sf = _mk_server(
        tmp_path / "front", runner,
        storage_nodes=[f"http://127.0.0.1:{n1.port}"])
    try:
        _ingest(front, n=60)
        _req(n1, "GET", "/internal/force_flush")
        q = urllib.parse.quote("*")
        _s, traced = _req(
            front, "GET",
            f"/select/logsql/query?query={q}&limit=1&trace=1")
        lines = traced.decode().splitlines()
        tree = json.loads(lines[-1])["_trace"]
        nodes = find_spans(tree, "storage_node")
        assert len(nodes) == 1
        node = nodes[0]
        remotes = [c for c in node.get("children", ())
                   if c.get("name") == "storage_node_query"]
        # either the remote tree arrived whole, or the cut is marked
        assert remotes or node["attrs"].get("trace_truncated") is True
    finally:
        front.close()
        n1.close()
        s1.close()
        sf.close()


def test_slow_query_log_fires_on_deadline_death(storage, runner,
                                                monkeypatch):
    """The slowest queries die on the deadline — the slow-log line must
    still be emitted from the finally path."""
    monkeypatch.setenv("VL_SLOW_QUERY_MS", "0")
    lines: list = []
    slowlog.set_sink(lines.append)
    try:
        from victorialogs_tpu.server.vlselect import _run_collect_traced
        with pytest.raises(QueryTimeoutError):
            from victorialogs_tpu.logsql.parser import parse_query
            q = parse_query("*")
            monkeypatch.setattr(
                "victorialogs_tpu.server.vlselect.query_deadline",
                lambda args: time.monotonic() - 1.0)
            _run_collect_traced(storage, [TEN], q, {}, runner, "/x")
        assert lines, "no slow-log line on deadline death"
        assert json.loads(lines[-1])["endpoint"] == "/x"
    finally:
        slowlog.set_sink(None)


def test_host_gated_units_excluded_from_dispatch_rtt(storage,
                                                     monkeypatch):
    """Host-gated _UnitReady units never dispatch: their window queue
    wait must not land in the device-RTT histogram."""
    monkeypatch.setenv("VL_COST_FORCE", "host")
    r = BatchRunner()
    before = hist.DISPATCH_RTT.snapshot()[2]
    rows, root = traced_query(storage, 'error | fields _time', r)
    assert rows
    assert hist.DISPATCH_RTT.snapshot()[2] == before
    harvs = find_spans(root.to_dict(), "harvest")
    assert harvs and all(h["attrs"].get("host_unit") for h in harvs)
    assert not any("dispatch_rtt_s" in h["attrs"] for h in harvs)
