"""The readers PR 26 added, on hand-built trees and gaps: the self-time
reader, the attribute reader, the idle-by-span reader; and a whole
rehearsal run that has to print every new metric but the four idle
shares (no device is traced in a rehearsal).  Run by hand with the
rest: `python3 -m pytest benchmark/tests -q`."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import gen  # noqa: E402
from readers import idle_by_span, vltrace_attr, vltrace_self  # noqa: E402

NEW_METRICS = ("admission_wait_ms", "parse_ms", "request_self_ms",
               "launch_ms", "device_queue_depth", "h2d_bytes_per_query",
               "stalls_in_window", "gc_pause_s_in_window")
IDLE_METRICS = ("idle_in_submit_share", "idle_in_sync_share",
                "idle_in_host_share", "idle_no_request_share")
MS = 1_000_000                  # ns


def span(name, start_ms, duration_ms, children=(), **attrs):
    node = {"name": name, "start_ms": start_ms, "duration_ms": duration_ms}
    if attrs:
        node["attrs"] = attrs
    if children:
        node["children"] = list(children)
    return node


def record(tree: dict) -> dict:
    """A client record as run.py keeps it, for a /stats_query answer."""
    return {"req": {"endpoint": "stats_query"},
            "body": json.dumps({"status": "success", "trace": tree})}


def request(base_ns: int, *children, duration_ms=100.0):
    return span("request", 0.0, duration_ms, children,
                start_unix_ns=base_ns)


# ---- self time ----

def test_self_time_is_duration_minus_what_children_cover():
    tree = span("request", 0, 100, [
        span("admission_wait", 1, 2),
        span("parse", 3, 5),
        span("query", 10, 80, [
            span("pipeline", 20, 50, [
                span("prune", 20, 10),
                # two children that overlap (the prefetch worker's span
                # beside the scan thread's): their union counts once
                span("submit", 35, 10, [span("args", 35, 4),
                                        span("launch", 39, 5)]),
                span("stage", 40, 10)]),
            # a child that runs past its parent is clipped to it
            span("partition", 85, 10)])])
    assert vltrace_self.self_ms(tree) == pytest.approx(100 - 2 - 5 - 80)
    query = tree["children"][2]
    assert vltrace_self.self_ms(query) == pytest.approx(80 - 50 - 5)
    pipeline = query["children"][0]
    assert vltrace_self.self_ms(pipeline) == pytest.approx(50 - 10 - 15)
    names = {"request", "query", "pipeline"}
    assert vltrace_self.tree_self_ms(tree, names) == pytest.approx(
        13 + 25 + 25)
    # a span that is not asked for adds nothing, wherever it sits
    assert vltrace_self.tree_self_ms(tree, {"submit"}) == pytest.approx(1)
    spec = {"spans": ["request", "query", "pipeline"]}
    ctx = {"records": [record(tree), record(span("query", 0, 10))]}
    assert vltrace_self.read(spec, ctx) == pytest.approx((63 + 10) / 2)
    assert vltrace_self.read(spec, {"records": []}) is None


def test_attr_reader_means_over_spans_not_queries():
    one = span("request", 0, 10, [
        span("submit", 1, 2, [span("launch", 2, 1, device_queue_depth=0)]),
        span("submit", 4, 2, [span("launch", 5, 1, device_queue_depth=3)])])
    two = span("request", 0, 10, [
        span("submit", 1, 2, [span("launch", 2, 1, device_queue_depth=6)]),
        span("submit", 4, 2, [span("launch", 5, 1)])])       # no attr
    spec = {"span": "launch", "attr": "device_queue_depth"}
    ctx = {"records": [record(one), record(two)]}
    assert vltrace_attr.read(spec, ctx) == pytest.approx(3.0)
    # a program that records no such attribute: nothing, not 0
    assert vltrace_attr.read(spec, {"records": [record(
        span("query", 0, 5, [span("submit", 1, 2)]))]}) is None


# ---- idle by span ----

T0 = 1_790_000_000_000_000_000


def shares(gaps, trees) -> dict:
    ctx = {"trace": {"gaps_unix_ns": gaps},
           "records": [record(t) for t in trees]}
    return {st: idle_by_span.read({"state": st}, ctx)
            for st in idle_by_span.STATES}


def test_gap_that_begins_in_one_state_and_ends_in_another():
    # submit 10-20 ms, device_sync 30-60 ms, the request 0-100 ms
    tree = request(T0, span("query", 5, 90, [
        span("submit", 10, 10, [span("args", 10, 6), span("launch", 16, 4)]),
        span("harvest", 30, 40, [span("device_sync", 30, 30),
                                 span("emit", 60, 10)])]))
    # one gap 15-45 ms: 5 in submit, 10 in neither (host), 15 in sync;
    # charged by overlap, not to `submit` where it began
    got = idle_by_span.charge([[T0 + 15 * MS, 30 * MS]], [tree])
    assert got == {"submit": 5 * MS, "sync": 15 * MS, "host": 10 * MS,
                   "no_request": 0}
    # a gap that runs out of the request: 90-130 ms
    got = idle_by_span.charge([[T0 + 90 * MS, 40 * MS]], [tree])
    assert got == {"submit": 0, "sync": 0, "host": 10 * MS,
                   "no_request": 30 * MS}
    both = shares([[T0 + 15 * MS, 30 * MS], [T0 + 90 * MS, 40 * MS]],
                  [tree])
    assert both == {"submit": pytest.approx(100 * 5 / 70),
                    "sync": pytest.approx(100 * 15 / 70),
                    "host": pytest.approx(100 * 20 / 70),
                    "no_request": pytest.approx(100 * 30 / 70)}
    assert sum(both.values()) == pytest.approx(100.0)


def test_two_concurrent_requests_first_match_wins():
    # A waits in device_sync 0-50 ms while B submits 20-30 ms and then
    # does other host work 30-40 ms; after A ends B runs on to 80 ms
    a = request(T0, span("query", 0, 50, [span("device_sync", 0, 50)]),
                duration_ms=50.0)
    b = request(T0 + 20 * MS, span("query", 0, 60, [span("submit", 0, 10)]),
                duration_ms=60.0)
    got = idle_by_span.charge([[T0, 100 * MS]], [a, b])
    assert got == {"submit": 10 * MS,          # B's submit beats A's sync
                   "sync": 40 * MS,            # the rest of A's sync
                   "host": 30 * MS,            # 50-80 ms: only B, no span
                   "no_request": 20 * MS}      # 80-100 ms
    assert sum(got.values()) == 100 * MS


def test_no_trace_and_no_wall_clock_read_nothing():
    tree = request(T0, span("query", 0, 50, [span("submit", 0, 10)]))
    # a rehearsal: no device was traced
    for trace in (None, {}, {"gaps_unix_ns": []}):
        ctx = {"trace": trace, "records": [record(tree)]}
        assert idle_by_span.read({"state": "host"}, ctx) is None
    # the parent program: trees carry no start_unix_ns
    old = span("query", 0, 50, [span("submit", 0, 10)])
    ctx = {"trace": {"gaps_unix_ns": [[T0, 10 * MS]]},
           "records": [record(old)]}
    assert all(idle_by_span.read({"state": st}, ctx) is None
               for st in idle_by_span.STATES)


# ---- the files, and a whole rehearsal ----

def test_every_new_metric_has_its_file_its_reader_and_its_entry():
    bench = gen.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS + IDLE_METRICS:
        spec = gen.load_json(os.path.join(BENCH, "metrics", name + ".json"))
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["source_kind"] + ".py")), name
        assert entries[name]["unit"] == spec["unit"]
        assert entries[name]["layer"] == spec["layer"]
        assert entries[name]["moves"] == spec["moves"]
        assert "workloads" not in entries[name]


def test_rehearsal_prints_every_new_metric_but_the_idle_shares():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "baseline-1chip.needle", "--rehearsal", "--trace", "1", "--seed",
         "2147483999", "--seconds", "4"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["rehearsal"] is True
    for name in NEW_METRICS:
        assert name in result["metrics"], name
    for name in IDLE_METRICS:
        assert name not in result["metrics"], name
    m = result["metrics"]
    assert m["admission_wait_ms"]["value"] > 0
    assert m["parse_ms"]["value"] > 0
    assert m["launch_ms"]["value"] > 0
    assert m["stalls_in_window"]["value"] == 0
