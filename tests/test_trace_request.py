"""One clock from the socket to the device: the request-wide span tree
(root `request` with the wall clock, `admission_wait`, `parse`, `query`,
`submit` -> `args` + `launch`), device programs named by what they scan,
leaf scopes in the lowered program, the h2d counter and the always-on
stall watch (obs/stallwatch.py)."""

import gc
import http.client
import json
import re
import time
import urllib.parse

import pytest

from victorialogs_tpu.engine.searcher import run_query_collect
from victorialogs_tpu.obs import activity, slowlog, stallwatch, tracing
from victorialogs_tpu.server.app import VLServer
from victorialogs_tpu.storage.log_rows import TenantID
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu import fused
from victorialogs_tpu.tpu import kernels as K
from victorialogs_tpu.tpu.batch import BatchRunner

NS = 1_000_000_000
TEN = TenantID(0, 0)


def find_spans(tree: dict, name: str) -> list:
    out = [tree] if tree.get("name") == name else []
    for c in tree.get("children", ()):
        out += find_spans(c, name)
    return out


def _req(srv, path, method="GET", body=None):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
    conn.request(method, path, body=body)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A server over two parts of DIFFERENT size (so one query shape
    runs two programs), on the device path."""
    path = tmp_path_factory.mktemp("tracereq")
    storage = Storage(str(path / "data"), retention_days=100000,
                      flush_interval=3600)
    runner = BatchRunner()
    srv = VLServer(storage, listen_addr="127.0.0.1", port=0,
                   runner=runner)
    t0 = time.time_ns() - 3600 * NS
    n = 0
    for rows in (3000, 9000):
        body = "\n".join(json.dumps({
            "_time": t0 + (n + i) * 1_000_000,
            "_msg": f"hello {'error' if i % 2 else 'ok'} dead beef {i}",
            "app": "web", "dur": str(i % 17)}) for i in range(rows))
        n += rows
        status, _ = _req(srv, "/insert/jsonline?_stream_fields=app",
                         "POST", body.encode())
        assert status == 200
        _req(srv, "/internal/force_flush")
    yield srv, storage, runner
    srv.close()
    storage.close()


def _traced(srv, endpoint: str, query: str, extra: str = "") -> dict:
    q = urllib.parse.quote(query)
    status, data = _req(
        srv, f"/select/logsql/{endpoint}?query={q}&trace=1{extra}")
    assert status == 200, data
    if endpoint == "stats_query":
        return json.loads(data)["trace"]
    return json.loads(data.decode().splitlines()[-1])["_trace"]


# ---------------- A: the tree covers the request ----------------

@pytest.mark.parametrize("endpoint,query", [
    ("query", "error"),
    ("stats_query", "error | stats count() hits"),
])
def test_request_root_covers_admission_parse_and_query(served, endpoint,
                                                       query):
    srv, _storage, _runner = served
    before_ns = time.time_ns()
    tree = _traced(srv, endpoint, query)
    assert tree["name"] == "request"
    assert tree["attrs"]["path"] == f"/select/logsql/{endpoint}"
    # the wall clock beside the root's perf_counter start: any span's
    # absolute time is start_unix_ns + start_ms
    assert before_ns <= tree["attrs"]["start_unix_ns"] <= time.time_ns()
    assert tree["start_ms"] == 0.0
    assert [c["name"] for c in tree["children"]] == \
        ["admission_wait", "parse", "query"]
    adm, parse, qsp = tree["children"]
    assert adm["attrs"]["queued_behind"] == 0
    assert adm["start_ms"] + adm["duration_ms"] <= parse["start_ms"] + 0.5
    assert parse["start_ms"] + parse["duration_ms"] <= \
        qsp["start_ms"] + 0.5
    assert "error" in qsp["attrs"]["query"] and "qid" in qsp["attrs"]
    assert find_spans(qsp, "pipeline")


def test_submit_splits_into_args_and_launch(served, monkeypatch):
    # one dispatch a part: a pack of both would hide the second submit
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    srv, _storage, _runner = served
    tree = _traced(srv, "stats_query", "error | stats count() hits")
    subs = find_spans(tree, "submit")
    assert len(subs) == 2
    for sub in subs:
        kids = [c["name"] for c in sub["children"]]
        assert kids == ["args", "launch"], kids
        launch = sub["children"][1]
        depth = launch["attrs"]["device_queue_depth"]
        assert isinstance(depth, int) and depth >= 0
    # the second launch finds the first dispatch still leased
    assert subs[1]["children"][1]["attrs"]["device_queue_depth"] >= 1


def _capture_roots(monkeypatch) -> list:
    roots = []
    real = tracing.request_root

    def request_root(name, **attrs):
        roots.append(real(name, **attrs))
        return roots[-1]

    monkeypatch.setattr(tracing, "request_root", request_root)
    return roots


def _wait_closed(root) -> None:
    # the client has its last byte a moment before the handler thread
    # leaves the request's extent
    end = time.monotonic() + 5
    while root.t1 is None and time.monotonic() < end:
        time.sleep(0.005)
    assert root.t1 is not None


def test_no_open_spans_after_cancel(served, monkeypatch):
    """An early limit cancels the scan (QueryCancelled unwinds through
    pipeline, harvest and emit): every span of the request closes."""
    srv, _storage, _runner = served
    roots = _capture_roots(monkeypatch)
    tree = _traced(srv, "query", "error", "&limit=1")
    assert find_spans(tree, "pipeline")[0]["attrs"].get("error") == \
        "QueryCancelled"
    (root,) = roots
    _wait_closed(root)
    assert root.open_spans() == 0


def test_no_open_spans_after_shed(served, monkeypatch):
    srv, _storage, _runner = served
    roots = _capture_roots(monkeypatch)
    srv.admission.set_tenant_limit("0:0", 1)
    try:
        # the tenant's one slot is taken: the request sheds at the gate
        with srv.admission.admit(tenant="0:0", endpoint="/held"):
            status, _ = _req(srv,
                             "/select/logsql/query?trace=1&query=error")
    finally:
        srv.admission.set_tenant_limit("0:0", 0)     # lifts the limit
    assert status == 429
    (root,) = roots
    _wait_closed(root)
    assert root.open_spans() == 0
    tree = root.to_dict()
    assert [c["name"] for c in tree["children"]] == ["admission_wait"]
    assert tree["children"][0]["attrs"]["error"] == "AdmissionShed"


def test_untraced_request_creates_no_span(served):
    srv, _storage, _runner = served
    q = urllib.parse.quote("error | stats count() hits")
    _req(srv, f"/select/logsql/stats_query?query={q}")      # warm
    before = tracing.spans_created()
    for _ in range(3):
        status, data = _req(srv, f"/select/logsql/stats_query?query={q}")
        assert status == 200 and b"trace" not in data
    status, _ = _req(srv, "/select/logsql/query?query=error&limit=2")
    assert status == 200
    assert tracing.spans_created() == before


def test_noop_path_of_the_new_span_sites_is_cheap():
    """What an untraced request pays at the new sites (admission_wait,
    parse, args, launch): a contextvar read and a shared no-op context."""
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracing.current_span().span("launch") as sp:
            if sp.enabled:
                sp.set("device_queue_depth", 0)
    per_op = (time.perf_counter() - t0) / n
    assert per_op < 5e-6, per_op


def test_embedded_query_keeps_its_own_root(served):
    """Without a request root (library use) `query` is the root, as
    before."""
    from victorialogs_tpu.logsql.parser import parse_query
    from victorialogs_tpu.server import vlselect
    q = parse_query("error")
    assert vlselect._trace_roots({}, q) == (None, None)
    root, top = vlselect._trace_roots({"trace": "1"}, q)
    assert root is top and root.name == "query"
    root.close()
    outer = tracing.request_root("request", path="/x")
    with tracing.activate(outer):
        root, top = vlselect._trace_roots({}, q)
        assert top is outer and root.name == "query"
        assert outer.children == [root]
        with tracing.activate(root):
            pass
    assert outer.open_spans() == 0


# ---------------- B: programs named by what they scan ----------------

_LEAVES = "|".join(fused._PRIMARY_LEAVES + fused._SECONDARY_LEAVES
                   + ("all", "mixed"))
# the closed vocabulary: family, then up to MAX_NAMED_LEAVES leaf words,
# then (stats family) a reduction word
NAME_RE = re.compile(
    r"^(%s)_((%s)(_(%s)){0,%d})(__(%s))?$" % (
        "|".join(sorted(fused.FAMILIES, key=len, reverse=True)), _LEAVES,
        _LEAVES, fused.MAX_NAMED_LEAVES - 1, "|".join(fused.REDUCTIONS)))


def _scan(mode=K.MODE_PHRASE, ri=0, plen=5):
    return ("scan", ri, 1, -1, -1, 2, plen, mode, True, True, False)


TIME = ("time", 3, 4, 5, 6, 7, 8)
BLOOM = ("bloom_sb", 9, 10, 11, 12, 13)
PAIR = ("pair", 0, 1, -1, 14, 4, 15, 4)


@pytest.mark.parametrize("family,tree,reduction,want", [
    ("fused", ("and", (TIME, BLOOM, _scan())), "count",
     "fused_phrase__count"),
    ("fused", ("and", (TIME, _scan())), "bucket", "fused_phrase__bucket"),
    ("fused", PAIR, "count", "fused_regex__count"),
    ("fused", ("true",), "uniq", "fused_all__uniq"),
    ("fused", TIME, "bucket", "fused_time__bucket"),
    ("fused", ("and", (TIME, BLOOM)), "count", "fused_time_bloom__count"),
    ("filter", ("or", (_scan(K.MODE_EXACT), _scan(K.MODE_EXACT, plen=9))),
     "", "filter_exact"),
    ("filter", ("and", (_scan(), ("not", _scan(K.MODE_PREFIX)))), "",
     "filter_phrase_prefix"),
    ("filter", ("and", (_scan(), PAIR, ("numrange", 1, 2, 3))), "",
     "filter_phrase_regex_numrange"),
    ("filter", ("or", (_scan(), _scan(), _scan(), _scan())), "",
     "filter_mixed"),
    ("topk", ("lenrange", 1, -1, -1, 2, 3, 4), "", "topk_lenrange"),
    ("topk_seg", _scan(K.MODE_SUBSTRING), "", "topk_seg_substr"),
])
def test_program_name_vocabulary(family, tree, reduction, want):
    name = fused.program_name(family, tree, reduction)
    assert name == want
    assert NAME_RE.match(name), name


def test_program_name_ignores_literals_sizes_and_ids():
    """Arg indices, pattern lengths and token flags are not the shape."""
    a = ("and", (TIME, _scan(ri=0, plen=3)))
    b = ("and", (("time", 13, 14, 15, 16, 17, 18), _scan(ri=7, plen=11)))
    assert fused.program_name("fused", a, "count") == \
        fused.program_name("fused", b, "count") == "fused_phrase__count"


def test_two_part_sizes_run_under_one_name(served, monkeypatch):
    """The name is a function of the query's shape: two parts of
    different padded size are two programs of ONE jitted callable."""
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    _srv, storage, runner = served
    seen = []
    real = runner._dispatch_fused

    def dispatch(name, prog, *rest):
        seen.append((name, prog[1]))
        return real(name, prog, *rest)

    monkeypatch.setattr(runner, "_dispatch_fused", dispatch)
    rows = run_query_collect(storage, [TEN], "error | stats count() c",
                             runner=runner)
    assert rows == [{"c": "6000"}]
    assert len({rlp for _n, rlp in seen}) == 2, seen
    assert {n for n, _rlp in seen} == {"fused_phrase__count"}
    fn = fused.fused_program("fused_phrase__count")
    assert fn is fused.fused_program("fused_phrase__count")
    assert fn.__name__ == "fused_phrase__count"
    for name in fused._programs:
        assert NAME_RE.match(name.removesuffix("_mesh")), name


@pytest.mark.parametrize("query,name", [
    ('_msg:~"dead.*beef" | stats count() c', "fused_regex__count"),
    ("error | stats by (_time:5m) count() c", "fused_phrase__bucket"),
    ("* | stats count_uniq(app) u", "fused_all__uniq"),
    ("error | stats by (app) count() c", "fused_phrase__group"),
    ("error | stats sum(dur) s", "fused_phrase__stats"),
])
def test_stats_queries_name_their_reduction(served, monkeypatch, query,
                                            name):
    _srv, storage, runner = served
    seen = set()
    real = runner._dispatch_fused

    def dispatch(pname, *rest):
        seen.add(pname)
        return real(pname, *rest)

    monkeypatch.setattr(runner, "_dispatch_fused", dispatch)
    run_query_collect(storage, [TEN], query, runner=runner)
    assert seen == {name}


def test_row_and_sort_queries_name_their_family(served, monkeypatch):
    _srv, storage, runner = served
    seen = set()
    for seam in ("_dispatch_filter", "_dispatch_topk"):
        real = getattr(runner, seam)

        def dispatch(pname, *rest, _real=real):
            seen.add(pname)
            return _real(pname, *rest)

        monkeypatch.setattr(runner, seam, dispatch)
    run_query_collect(storage, [TEN], "error | fields _time",
                      runner=runner)
    run_query_collect(storage, [TEN], "error | sort by (dur desc) limit 3",
                      runner=runner)
    assert "filter_phrase" in seen
    assert seen & {"topk_phrase", "topk_seg_phrase"}, seen


def test_lowered_program_holds_the_leaf_scopes(served, monkeypatch):
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    _srv, storage, runner = served
    calls = []
    real = runner._dispatch_fused

    def dispatch(name, *args):
        calls.append((name, args))
        return real(name, *args)

    monkeypatch.setattr(runner, "_dispatch_fused", dispatch)
    t_lo = time.time_ns() - 7200 * NS
    run_query_collect(
        storage, [TEN],
        f"_time:[{t_lo}, {time.time_ns()}) error | stats count() c",
        runner=runner)
    name, args = calls[0]
    assert name == "fused_phrase__count"
    text = fused.fused_program(name).lower(*args).as_text(debug_info=True)
    # every leaf evaluates under its kind's scope, the reduction under
    # `stats`, inside the module named for the program
    assert "jit_fused_phrase__count" in text
    for scope in ("/phrase/", "/stats/"):
        assert scope in text, scope
    assert "match_scan" in text
    if len(calls[0][1][0][0]) > 1 and calls[0][1][0][0][0] == "and":
        assert "/time/" in text


# ---------------- D: the h2d counter and the stall watch ----------------

def test_h2d_bytes_count_once_an_array(served):
    import numpy as np
    _srv, _storage, runner = served
    before = runner.stats()["h2d_bytes_total"]
    assert before > 0                  # the fixture's staging
    runner._put(np.zeros(1000, dtype=np.uint8))
    runner._put_replicated(np.zeros(10, dtype=np.int32))
    assert runner.stats()["h2d_bytes_total"] == before + 1000 + 40
    status, text = _req(served[0], "/metrics")
    assert status == 200
    assert re.search(rb"^vl_tpu_h2d_bytes_total \d+$", text, re.M)


@pytest.mark.parametrize("query,leaves", [
    ("error | stats count() c", 1),
    ('error OR "dead beef" | stats count() c', 2),
    ('_msg:~"dead.*beef" NOT ok | stats count() c', 2),
    ("* | stats count_uniq(app) u", 0),
])
def test_plane_scan_leaves_counted_where_the_dispatch_is_issued(
        served, query, leaves):
    """vl_tpu_plane_scan_leaves: the scan and `A.*B` leaves of every
    dispatch, bumped on the host beside device_calls."""
    srv, storage, runner = served
    before = runner.stats()
    run_query_collect(storage, [TEN], query, runner=runner)
    after = runner.stats()
    calls = after["device_calls"] - before["device_calls"]
    assert calls > 0
    assert after["plane_scan_leaves"] - before["plane_scan_leaves"] \
        == leaves * calls
    status, text = _req(srv, "/metrics")
    assert status == 200
    assert re.search(rb"^vl_tpu_plane_scan_leaves \d+$", text, re.M)


@pytest.mark.parametrize("query,sweeps", [
    ("error | stats count() c", True),
    ('_msg:~"dead.*beef" NOT ok | stats count() c', True),
    ("* | stats count_uniq(app) u", False),
])
def test_plane_sweep_steps_counted_beside_the_leaves(
        served, monkeypatch, query, sweeps):
    """vl_tpu_scan_plane_steps_swept / _skipped, in sweep-tile steps:
    the direct launcher (jax-CPU) sweeps every step and skips none;
    where the sweep stops at each tile's longest row, the same
    dispatches split the same steps into swept and skipped (the
    fixture's rows are 17-27 B of a 32 B staging, and most of a part's
    tiles are padding)."""
    srv, storage, runner = served

    def steps(bounded):
        monkeypatch.setattr(runner, "sweeps_bounded", lambda: bounded)
        before = runner.stats()
        run_query_collect(storage, [TEN], query, runner=runner)
        after = runner.stats()
        return [after[k] - before[k] for k in (
            "device_calls", "scan_plane_steps_swept",
            "scan_plane_steps_skipped")]
    calls, whole, none = steps(False)
    calls2, swept, skipped = steps(True)
    assert calls == calls2 > 0
    assert none == 0
    assert swept + skipped == whole
    assert (skipped > 0 and swept > 0) == sweeps
    if not sweeps:
        assert whole == 0
    status, text = _req(srv, "/metrics")
    assert status == 200
    for name in (rb"swept", rb"skipped"):
        assert re.search(rb"^vl_tpu_scan_plane_steps_" + name + rb" \d+$",
                         text, re.M)


@pytest.fixture
def stall_lines():
    lines = []
    slowlog.set_sink(lines.append)
    yield lines
    slowlog.set_sink(None)


def _counts() -> dict:
    return {base: v for base, _labels, v in stallwatch.metrics_samples()}


def test_late_beat_writes_exactly_one_line(stall_lines):
    watch = stallwatch.StallWatch()
    before = _counts()
    assert watch.beat(0.01) is False
    assert watch.beat(0.4) is True           # 400 ms late: over 250
    assert watch.beat(0.0) is False
    assert len(stall_lines) == 1
    rec = json.loads(stall_lines[0])
    assert rec["msg"] == "process stall" and rec["kind"] == "late_beat"
    assert rec["lag_ms"] == 400.0
    for key in ("gc_s", "gc_collections", "compiles",
                "dispatches_in_flight", "queries", "oldest_query_s"):
        assert key in rec
    after = _counts()
    assert after["vl_process_stalls_total"] == \
        before["vl_process_stalls_total"] + 1
    assert after["vl_process_stall_seconds_total"] == pytest.approx(
        before["vl_process_stall_seconds_total"] + 0.4)


def test_no_progress_stall_is_one_line_while_it_lasts(stall_lines):
    watch = stallwatch.StallWatch()
    before = _counts()
    with activity.track("/select/logsql/query", "stuck", TEN) as act:
        act.set_phase("scan")
        act.start_mono -= 2.0                # over a second old
        for _ in range(9):                   # ... but quiet for 0.9 s only
            assert watch.beat(0.0) is False
        assert watch.beat(0.0) is True       # a second with none finishing
        assert watch.beat(0.0) is False      # the same stall goes on
        assert watch.beat(0.0) is False
    assert watch.beat(0.0) is False          # it finished: progress
    assert len(stall_lines) == 1
    rec = json.loads(stall_lines[0])
    assert rec["kind"] == "no_progress" and rec["oldest_query_s"] >= 2.0
    (q,) = [q for q in rec["queries"] if q["qid"] == act.qid]
    assert q["phase"] == "scan" and q["age_s"] >= 2.0
    after = _counts()
    assert after["vl_process_stalls_total"] == \
        before["vl_process_stalls_total"] + 1
    assert after["vl_process_stall_seconds_total"] == pytest.approx(
        before["vl_process_stall_seconds_total"] + 3 * watch.beat_s)


def test_a_burst_of_sheds_is_not_progress(stall_lines):
    """While a stall lasts, arrivals are shed: each registers and ends,
    and must not pass for a query that finished."""
    from victorialogs_tpu import sched
    watch = stallwatch.StallWatch()
    with activity.track("/select/logsql/query", "stuck", TEN) as act:
        act.start_mono -= 2.0
        for _ in range(9):
            with activity.track("/select/logsql/query", "shed", TEN):
                sched.note_rejected("0:0", "tenant_limit")
            assert watch.beat(0.0) is False
        assert watch.beat(0.0) is True
    assert len(stall_lines) == 1


def test_long_lived_endpoints_are_not_a_stall(stall_lines):
    watch = stallwatch.StallWatch()
    with activity.track("/select/logsql/tail", "*", TEN) as act:
        act.start_mono -= 60.0
        assert watch.beat(0.0) is False
    assert stall_lines == []


def test_forced_collection_moves_the_gc_counters(served):
    # `served` holds a server, so the hook is installed
    assert stallwatch._on_gc in gc.callbacks
    before = _counts()
    gc.collect()
    after = _counts()
    assert after["vl_gc_collections_total"] == \
        before["vl_gc_collections_total"] + 1
    assert after["vl_gc_pause_seconds_total"] > \
        before["vl_gc_pause_seconds_total"]
    gc.collect(0)                            # a young one is not timed
    assert _counts()["vl_gc_collections_total"] == \
        after["vl_gc_collections_total"]
    status, text = _req(served[0], "/metrics")
    assert status == 200
    for series in (b"vl_gc_collections_total", b"vl_gc_pause_seconds_total",
                   b"vl_process_stalls_total",
                   b"vl_process_stall_seconds_total"):
        assert re.search(rb"^" + series + rb" ", text, re.M), series


def test_stall_watch_is_one_thread_shared_by_servers(tmp_path, served):
    import threading

    def beats():
        return [t for t in threading.enumerate()
                if t.name == "vl-stallwatch"]

    assert len(beats()) == 1
    assert stallwatch.BEAT_S >= 0.1          # 10 Hz or slower
    storage = Storage(str(tmp_path / "d2"), retention_days=100000,
                      flush_interval=3600)
    srv2 = VLServer(storage, listen_addr="127.0.0.1", port=0)
    try:
        assert len(beats()) == 1
    finally:
        srv2.close()
        storage.close()
    assert len(beats()) == 1                 # `served` still holds it
