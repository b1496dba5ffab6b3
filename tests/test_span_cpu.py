"""Where a traced span's time goes, and the threads beside the requests:
`gc_ms` on every real span (obs/tracing.py), the gc hook that times
every generation and the CPU of the threads that serve no request
(obs/stallwatch.py), the `vl_launch` annotation around the jitted call
(tpu/fused.py:_launch) — and nothing of it when tracing is off."""

import gc
import http.client
import json
import subprocess
import sys
import threading
import time
import urllib.parse

import jax
import pytest

from victorialogs_tpu.obs import stallwatch, tracing
from victorialogs_tpu.server.app import VLServer
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu.batch import BatchRunner

NS = 1_000_000_000


def _spin_cpu(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def _walk(tree: dict):
    yield tree
    for c in tree.get("children", ()):
        yield from _walk(c)


def _collect_elsewhere(generation: int) -> None:
    th = threading.Thread(target=gc.collect, args=(generation,))
    th.start()
    th.join(timeout=30)
    assert not th.is_alive()


@pytest.fixture
def hooked():
    stallwatch.start()
    try:
        yield
    finally:
        stallwatch.close()


# ---- the span alone ----

def test_tracing_core_imports_no_other_layer():
    """The gc total lives in the tracing core, which the stall watch
    feeds: importing tracing pulls in neither the stall watch nor the
    scheduler."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, sys, victorialogs_tpu.obs.tracing; "
         "print(json.dumps(sorted(sys.modules)))"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    mods = json.loads(out.stdout)
    assert "victorialogs_tpu.obs.tracing" in mods
    assert "victorialogs_tpu.obs.stallwatch" not in mods
    assert "victorialogs_tpu.sched" not in mods


def test_no_gc_ms_without_the_hook(monkeypatch):
    monkeypatch.setitem(tracing._gc, "hooked", False)
    assert tracing.gc_pause_ns() is None
    root = tracing.make_root("library")
    with tracing.activate(root):
        gc.collect()
    assert "gc_ms" not in root.to_dict().get("attrs", {})


def test_the_hook_turns_the_total_on_and_off():
    stallwatch.start()
    try:
        assert tracing.gc_pause_ns() is not None
        assert stallwatch._on_gc in gc.callbacks
    finally:
        stallwatch.close()
    # off again unless another server of this process still holds it
    assert (tracing.gc_pause_ns() is None) == (stallwatch._refs == 0)


@pytest.mark.parametrize("generation", [2, 0])
def test_collection_on_another_thread_shows_in_an_open_span(
        hooked, generation):
    """gc_ms counts a collection any thread runs; generation 2 feeds the
    old series, 0-1 the young ones."""
    before, ns0 = dict(stallwatch._gc), tracing.gc_pause_ns()
    root = tracing.make_root("waits")
    with tracing.activate(root):
        _collect_elsewhere(generation)
    after, ns1 = dict(stallwatch._gc), tracing.gc_pause_ns()
    assert root.to_dict()["attrs"]["gc_ms"] > 0
    assert ns1 > ns0
    if generation == 2:
        assert after["collections"] > before["collections"]
        assert after["pause_seconds"] > before["pause_seconds"]
    else:
        assert after["young_collections"] > before["young_collections"]
        assert after["young_pause_seconds"] > before["young_pause_seconds"]


def test_open_span_reads_the_gc_total_now(hooked):
    """The `request` root is exported while still open: it reads the
    pauses up to that moment, and its closed value after."""
    root = tracing.make_root("open")
    assert root.to_dict()["attrs"]["gc_ms"] == 0
    _collect_elsewhere(2)
    open_ms = root.to_dict()["attrs"]["gc_ms"]
    assert open_ms > 0
    with tracing.activate(root):
        pass
    assert root.to_dict()["attrs"]["gc_ms"] >= open_ms


def test_span_closed_on_another_thread_keeps_its_gc_ms(hooked):
    """The total is process-wide, so a detached root (make_child /
    make_root) that closes on another thread still reads it."""
    root = tracing.make_root("crossing")

    def other():
        gc.collect(2)
        with tracing.activate(root):
            pass                            # closes it here

    th = threading.Thread(target=other)
    th.start()
    th.join(timeout=30)
    assert not th.is_alive()
    assert root.to_dict()["attrs"]["gc_ms"] > 0


def test_a_span_reads_no_thread_clock(hooked, monkeypatch):
    """Only the gc total is read beside the wall clock: the thread CPU
    clock is a system call on some hosts, and counts in scheduler ticks
    there."""
    calls = []
    monkeypatch.setattr(time, "thread_time_ns",
                        lambda: calls.append(1) or 0)
    root = tracing.make_root("quiet")
    with tracing.activate(root):
        with tracing.current_span().span("child"):
            pass
    for node in _walk(root.to_dict()):
        assert set(node.get("attrs", {})) == {"gc_ms"}, node
    assert calls == []


# ---- a served query ----

def _req(srv, path, method="GET", body=None):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=60)
    conn.request(method, path, body=body)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A server over two parts, on the device path."""
    path = tmp_path_factory.mktemp("spancpu")
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
            "app": "web"}) for i in range(rows))
        n += rows
        status, _ = _req(srv, "/insert/jsonline?_stream_fields=app",
                         "POST", body.encode())
        assert status == 200
        _req(srv, "/internal/force_flush")
    yield srv, runner
    srv.close()
    storage.close()


def _traced(srv, endpoint: str, query: str) -> dict:
    q = urllib.parse.quote(query)
    status, data = _req(srv, f"/select/logsql/{endpoint}?query={q}&trace=1")
    assert status == 200, data
    if endpoint == "stats_query":
        return json.loads(data)["trace"]
    return json.loads(data.decode().splitlines()[-1])["_trace"]


def test_every_served_span_carries_gc_ms(served):
    srv, runner = served
    waits0 = runner.stats()["host_sync_wait_s"]
    tree = _traced(srv, "query", "error")
    assert tree["name"] == "request"
    names = set()
    for node in _walk(tree):
        names.add(node["name"])
        attrs = node.get("attrs", {})
        # a server has the gc hook: every span carries gc_ms
        assert attrs["gc_ms"] >= 0, node
        # the download wait is the runner's stat and histogram, not a
        # span attribute
        assert "host_sync_wait_s" not in attrs
    assert {"launch", "device_sync"} <= names
    assert runner.stats()["host_sync_wait_s"] > waits0


def test_untraced_query_reads_no_new_clock(served, monkeypatch):
    srv, _runner = served
    q = urllib.parse.quote("error | stats count() hits")
    _req(srv, f"/select/logsql/stats_query?query={q}")      # warm
    calls = []
    real = tracing.gc_pause_ns

    def counting():
        calls.append(1)
        return real()

    monkeypatch.setattr(tracing, "gc_pause_ns", counting)
    monkeypatch.setattr(time, "thread_time_ns",
                        lambda: calls.append("thread") or 0)
    before = tracing.spans_created()
    status, _ = _req(srv, f"/select/logsql/stats_query?query={q}")
    assert status == 200
    status, _ = _req(srv, "/select/logsql/query?query=error&limit=2")
    assert status == 200
    assert calls == [] and tracing.spans_created() == before
    # the counter does see a traced one, which reads no thread clock
    _traced(srv, "stats_query", "error | stats count() hits")
    assert calls and "thread" not in calls


def test_launch_is_annotated_only_when_traced(served, monkeypatch):
    srv, _runner = served
    names = []
    real = jax.profiler.TraceAnnotation

    def recording(name, **kw):
        names.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recording)
    status, _ = _req(srv, "/select/logsql/query?query=error")
    assert status == 200
    assert "vl_launch" not in names
    tree = _traced(srv, "query", "error")
    launches = [n for n in _walk(tree) if n["name"] == "launch"]
    assert launches
    assert names.count("vl_launch") == len(launches)


def _scrape(srv) -> dict:
    status, data = _req(srv, "/metrics")
    assert status == 200
    out = {}
    for ln in data.decode().splitlines():
        if ln.startswith("vl_background_thread_cpu_seconds_total{"):
            name, _, val = ln.rpartition(" ")
            out[name[name.index('"') + 1:name.rindex('"')]] = float(val)
    return out


def test_background_cpu_counts_a_busy_thread_not_requests(served):
    srv, _runner = served
    busy = threading.Event()
    stop = threading.Event()

    def spinner():
        _spin_cpu(0.1)
        busy.set()
        stop.wait(30)

    th = threading.Thread(target=spinner, name="vl-test-busy_8")
    th.start()
    assert busy.wait(30)
    first = _scrape(srv)
    assert first["vl-test-busy"] >= 0.09
    assert not any("process_request_thread" in k for k in first)
    assert "MainThread" not in first
    assert "vl-stallwatch" in first
    stop.set()
    th.join(timeout=30)
    assert not th.is_alive()
    # the thread ended: its last reading stays, the counter never falls
    second = _scrape(srv)
    assert second["vl-test-busy"] >= first["vl-test-busy"]
    for label, secs in first.items():
        assert second.get(label, 0.0) >= secs, label
