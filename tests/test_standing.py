"""Standing-query subsystem tests (engine/standing/): per-part result
cache bit-identity + budget/merge discipline, standing registrations
with delta push, and the HTTP surface.

The cache invariant under test everywhere: a warm cache changes WHERE
partials/bitmaps come from, never WHAT the query returns — cached,
uncached, and cache-disabled runs must produce identical results on
the same execution path (device packed and host serial), and the
byte budget must balance against live part charges at all times
(cache_check_balanced, swept by vlsan after every test here too).
"""

import gc
import http.client
import json
import time
import urllib.parse

import pytest

from victorialogs_tpu.engine.searcher import run_query, run_query_collect
from victorialogs_tpu.engine.standing import (StandingRegistry,
                                              cache_check_balanced,
                                              cache_stats,
                                              reset_for_tests,
                                              standing_check_drained)
from victorialogs_tpu.engine.standing.manager import (StandingLimit,
                                                      standing_fingerprint)
from victorialogs_tpu.logsql.parser import parse_query
from victorialogs_tpu.obs import events
from victorialogs_tpu.storage.log_rows import LogRows, TenantID
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu.batch import BatchRunner

TEN = TenantID(0, 0)
T0 = 1_753_660_800_000_000_000
NS_DAY = 86_400_000_000_000
TS = T0 + 10 ** 12  # query-eval timestamp past every row


def _fill_part(s, day, base, n=200):
    lr = LogRows(stream_fields=["app"])
    for i in range(n):
        g = base + i
        lr.add(TEN, T0 + day * NS_DAY + g * 1_000_000, [
            ("app", f"app{g % 3}"),
            ("_msg", f"m {'err' if g % 3 == 0 else 'ok'} x{g % 37} of {g}"),
            ("lvl", ["info", "warn", "err"][g % 3]),
            ("dur", str(g % 211)),
        ])
    s.must_add_rows(lr)
    s.debug_flush()


@pytest.fixture(autouse=True)
def _cache_on(monkeypatch):
    # conftest pins VL_RESULT_CACHE=0 so the parity suites keep
    # executing what they compare; this module IS the cache suite
    monkeypatch.setenv("VL_RESULT_CACHE", "1")


@pytest.fixture()
def storage(tmp_path):
    s = Storage(str(tmp_path / "standing"), retention_days=100000,
                flush_interval=3600)
    n = 0
    for day in range(2):
        for _ in range(2):
            _fill_part(s, day, n)
            n += 200
    reset_for_tests()
    yield s
    s.close()
    reset_for_tests()


@pytest.fixture(scope="module")
def runner():
    return BatchRunner()


# ---------------- per-part result cache: bit identity ----------------

# stats / topk / rows shapes — ≥10 distinct fingerprint classes
SHAPES = [
    "* | stats by (app) count() c",
    "* | stats count() c, sum(dur) s",
    "err | stats by (lvl) count() n, max(dur) mx",
    "* | stats by (app, lvl) count() c",
    "* | stats min(dur) mn, sum(dur) s, count() c",
    "err | sort by (dur desc) limit 5 | fields dur, app",
    "* | sort by (dur) limit 7 | fields dur, lvl",
    "err | fields _time, app, dur",
    "lvl:err | fields _msg, dur",
    "app:app1 | stats count() c",
    "x7 | fields dur, app",
]


def _run(storage, qs, runner):
    if runner is None:
        # the SERIAL host walk: partitions in day order, blocks in part
        # order.  Without the option the host path scans day partitions
        # on concurrent threads and LogsQL defines no row order across
        # them — bit identity would then compare scheduler luck.
        qs = "options(concurrency=1) " + qs
    return run_query_collect(storage, [TEN], qs, timestamp=TS,
                             runner=runner)


@pytest.mark.parametrize("qs", SHAPES)
def test_cache_bit_identity_device(storage, runner, qs, monkeypatch):
    cold = _run(storage, qs, runner)
    h0 = cache_stats()["hits"]
    warm = _run(storage, qs, runner)
    assert warm == cold
    assert cache_stats()["hits"] > h0, "warm run never hit the cache"
    # third run with the cache disabled: the kill switch is inert
    monkeypatch.setenv("VL_RESULT_CACHE", "0")
    assert _run(storage, qs, runner) == cold
    assert cache_check_balanced()[0]


@pytest.mark.parametrize("qs", SHAPES)
def test_cache_bit_identity_serial(storage, qs, monkeypatch):
    cold = _run(storage, qs, None)
    warm = _run(storage, qs, None)
    assert warm == cold
    monkeypatch.setenv("VL_RESULT_CACHE", "0")
    assert _run(storage, qs, None) == cold
    assert cache_check_balanced()[0]


def test_cache_cross_path_parity(storage, runner):
    """Rows-shape bitmap entries are runner-independent: the device
    path's stored bitmaps replay on the serial path and vice versa —
    same rows either way."""
    qs = "err | fields _time, app, dur"
    dev = _run(storage, qs, runner)      # device cold (stores)
    ser = _run(storage, qs, None)        # serial warm (replays)
    key = lambda r: json.dumps(r, sort_keys=True)  # noqa: E731
    assert sorted(dev, key=key) == sorted(ser, key=key)
    assert cache_stats()["hits"] > 0


# ---------------- merge + budget discipline ----------------

def test_cache_survives_part_merge(storage):
    # serial path: parts are referenced only by the partition, so the
    # merge really frees them and the uid-keyed entries must follow
    # via the GC finalizers (the device path's pack staging can keep
    # member parts alive longer — same discipline, later release)
    qs = "err | fields _time, app, dur"
    cold = _run(storage, qs, None)
    entries_warm = cache_stats()["entries"]
    assert entries_warm > 0
    storage.must_force_merge("")
    gc.collect()  # old parts die -> finalizers release their entries
    ok, detail = cache_check_balanced()
    assert ok, detail
    assert cache_stats()["entries"] < entries_warm, \
        "merged-away part uids must leave the cache"
    m0 = cache_stats()["misses"]
    assert _run(storage, qs, None) == cold
    assert cache_stats()["misses"] > m0, \
        "the merged part is new — it must recompute, not hit"
    assert _run(storage, qs, None) == cold


def test_cache_eviction_budget_and_events(storage, runner, monkeypatch):
    got = []
    fn = lambda ts, ev, f: got.append((ev, dict(f)))  # noqa: E731
    events.subscribe(fn)
    try:
        # budget fits roughly one part's stats entry, so a 4-part scan
        # must evict along the way and stay within budget
        monkeypatch.setenv("VL_RESULT_CACHE_MAX_BYTES", "2000")
        cold = _run(storage, "* | stats by (app, lvl) count() c",
                    runner)
        st = cache_stats()
        assert st["used_bytes"] <= 2000
        ok, detail = cache_check_balanced()
        assert ok, detail
        assert _run(storage, "* | stats by (app, lvl) count() c",
                    runner) == cold
        if st["evictions"]:
            assert any(ev == "result_cache_evict" for ev, _ in got)
    finally:
        events.unsubscribe(fn)


def test_cache_oversized_entry_declined(storage, runner, monkeypatch):
    monkeypatch.setenv("VL_RESULT_CACHE_MAX_BYTES", "10")
    cold = _run(storage, "* | stats by (app) count() c", runner)
    assert cache_stats()["entries"] == 0
    assert cache_stats()["used_bytes"] == 0
    assert _run(storage, "* | stats by (app) count() c",
                runner) == cold


# ---------------- explain pricing ----------------

def test_explain_prices_cached_parts(storage, runner):
    from victorialogs_tpu.obs.explain import build_plan
    qs = "* | stats by (app) count() c"
    cold_plan = build_plan(storage, [TEN],
                           parse_query(qs, timestamp=TS), runner=runner)
    assert cold_plan["predicted"]["parts_cached"] == 0
    _run(storage, qs, runner)
    warm_plan = build_plan(storage, [TEN],
                           parse_query(qs, timestamp=TS), runner=runner)
    p = warm_plan["predicted"]
    assert p["parts_cached"] == p["parts_retained"] > 0
    # cached parts priced ~0: no dispatches, no scan volume
    assert p["dispatches"] < cold_plan["predicted"]["dispatches"]
    assert p["rows_scanned"] == 0 and p["bytes_scanned"] == 0
    cached_nodes = [n for pt in warm_plan["partitions"]
                    for n in pt["parts"] if n.get("cached")]
    assert len(cached_nodes) == p["parts_cached"]


def test_runner_counts_cached_units(storage, runner):
    qs = "err | sort by (dur desc) limit 5 | fields dur"
    _run(storage, qs, runner)
    c0 = runner.stats()["result_cache_units"]
    _run(storage, qs, runner)
    assert runner.stats()["result_cache_units"] > c0


# ---------------- standing queries ----------------

def _ndjson_eval(storage, q, runner):
    from victorialogs_tpu.engine.emit import ndjson_block
    chunks = []
    run_query(storage, [TEN], q.clone(),
              write_block=lambda br: chunks.append(ndjson_block(br)),
              runner=runner)
    return b"".join(chunks)


def test_standing_delta_equals_fresh_eval(storage, runner):
    reg = StandingRegistry(storage, runner=runner)
    try:
        q = parse_query("* | stats by (app) count() c", timestamp=TS)
        fp = reg.register(q, (TEN,))
        assert fp == standing_fingerprint(q, (TEN,))
        sub = reg.attach_subscriber(fp)
        # seeded with the registration-time evaluation
        assert sub.get(timeout=5) == _ndjson_eval(storage, q, runner)
        # every flush: the pushed delta equals a fresh full evaluation
        for round_i in range(2):
            _fill_part(storage, 0, 10_000 + round_i * 1000)
            payload = sub.get(timeout=10)
            assert payload == _ndjson_eval(storage, q, runner)
        reg.detach_subscriber(fp, sub)
        assert reg.entry_count() == 0, \
            "last subscriber detach must drop the entry"
    finally:
        reg.close()
    ok, detail = standing_check_drained()
    assert ok, detail


def test_standing_collapses_to_one_evaluation(storage, runner):
    reg = StandingRegistry(storage, runner=runner)
    try:
        q = parse_query("err | stats count() n", timestamp=TS)
        # N panels asking the same query join ONE entry
        fps = [reg.register(q, (TEN,)) for _ in range(5)]
        assert len(set(fps)) == 1 and reg.entry_count() == 1
        subs = [reg.attach_subscriber(fps[0]) for _ in range(5)]
        seeded = [s.get(timeout=5) for s in subs]
        assert len(set(seeded)) == 1
        snap = reg.snapshot()
        assert snap[0]["subscribers"] == 5
        reevals0 = snap[0]["reevals"]
        _fill_part(storage, 1, 20_000)
        got = [s.get(timeout=10) for s in subs]
        assert len(set(got)) == 1, "every subscriber sees the delta"
        snap = reg.snapshot()
        # one shared re-evaluation served all five (debounce may fold
        # the flush burst into one extra pass at most)
        assert 0 < snap[0]["reevals"] - reevals0 <= 2
        for s in subs:
            reg.detach_subscriber(fps[0], s)
    finally:
        reg.close()


def test_standing_unregister_sends_sentinel(storage, runner):
    reg = StandingRegistry(storage, runner=runner)
    try:
        q = parse_query("* | stats count() c", timestamp=TS)
        fp = reg.register(q, (TEN,))
        sub = reg.attach_subscriber(fp)
        sub.get(timeout=5)
        assert reg.unregister(fp)
        assert sub.get(timeout=5) is None
        assert not reg.unregister(fp)
        reg.detach_subscriber(fp, sub)  # no-op after unregister
    finally:
        reg.close()


def test_standing_limits(storage, runner, monkeypatch):
    reg = StandingRegistry(storage, runner=runner)
    try:
        monkeypatch.setenv("VL_STANDING", "0")
        with pytest.raises(StandingLimit):
            reg.register(parse_query("*", timestamp=TS), (TEN,))
        monkeypatch.setenv("VL_STANDING", "1")
        monkeypatch.setenv("VL_STANDING_MAX", "1")
        q1 = parse_query("* | stats count() a", timestamp=TS)
        fp = reg.register(q1, (TEN,))
        # joining the SAME fingerprint is not a new registration
        assert reg.register(q1, (TEN,)) == fp
        with pytest.raises(StandingLimit):
            reg.register(parse_query("* | stats count() b",
                                     timestamp=TS), (TEN,))
        reg.unregister(fp)
    finally:
        reg.close()


def test_standing_events_and_system_suppression(storage, runner):
    got = []
    fn = lambda ts, ev, f: got.append((ev, dict(f)))  # noqa: E731
    events.subscribe(fn)
    reg = StandingRegistry(storage, runner=runner)
    try:
        q = parse_query("* | stats count() c", timestamp=TS)
        fp = reg.register(q, (TEN,))
        reg.unregister(fp)
        names = [ev for ev, _ in got]
        assert "standing_query_registered" in names
        assert "standing_query_reeval" in names
        assert "standing_query_unregistered" in names
        reg_f = next(f for ev, f in got
                     if ev == "standing_query_registered")
        assert reg_f["fingerprint"] == fp and reg_f["tenant"] == "0:0"
        # the system tenant's own standing queries never journal
        got.clear()
        sys_ten = TenantID(events.SYSTEM_ACCOUNT_ID,
                           events.SYSTEM_PROJECT_ID)
        fp2 = reg.register(q, (sys_ten,))
        reg.unregister(fp2)
        assert not [ev for ev, _ in got
                    if ev.startswith("standing_query_")]
    finally:
        reg.close()
        events.unsubscribe(fn)


# ---------------- HTTP surface ----------------

@pytest.fixture()
def server(tmp_path):
    from victorialogs_tpu.server.app import VLServer
    s = Storage(str(tmp_path / "srv"), retention_days=100000,
                flush_interval=3600)
    _fill_part(s, 0, 0)
    reset_for_tests()
    srv = VLServer(s, listen_addr="127.0.0.1", port=0)
    yield srv
    srv.close()
    s.close()
    reset_for_tests()


def _post(srv, path):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
    conn.request("POST", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_http_standing_roundtrip(server):
    qs = urllib.parse.quote("* | stats by (app) count() c")
    conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                      timeout=30)
    conn.request("POST",
                 f"/select/logsql/standing_query?query={qs}&time={TS}")
    resp = conn.getresponse()
    assert resp.status == 200
    fp = json.loads(resp.readline())["standing_fingerprint"]
    first = resp.readline()
    assert first.strip(), "register must seed an initial result"
    # GET lists the registration with one subscriber
    g = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
    g.request("GET", "/select/logsql/standing_query")
    lst = json.loads(g.getresponse().read())
    g.close()
    assert [e["fingerprint"] for e in lst["standing_queries"]] == [fp]
    assert lst["standing_queries"][0]["subscribers"] == 1
    # POST unregister ends the stream (sentinel -> chunked EOF)
    status, data = _post(
        server,
        f"/select/logsql/standing_query?unregister=1&fingerprint={fp}")
    assert status == 200 and json.loads(data)["removed"] == 1
    deadline = time.monotonic() + 10
    while resp.read(65536):
        assert time.monotonic() < deadline
    conn.close()
    assert server.standing.entry_count() == 0


def test_http_standing_shed_and_errors(server, monkeypatch):
    qs = urllib.parse.quote("* | stats count() c")
    monkeypatch.setenv("VL_STANDING", "0")
    status, data = _post(
        server, f"/select/logsql/standing_query?query={qs}&time={TS}")
    assert status == 503 and b"VL_STANDING=0" in data
    monkeypatch.setenv("VL_STANDING", "1")
    status, _ = _post(server,
                      "/select/logsql/standing_query?unregister=1")
    assert status == 400
    status, data = _post(
        server, "/select/logsql/standing_query"
                "?unregister=1&fingerprint=deadbeef")
    assert status == 200 and json.loads(data)["removed"] == 0
