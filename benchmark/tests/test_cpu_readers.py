"""The readers of the collector's pauses on every span (`gc_ms`) and of
the threads beside the requests: `vltrace_attr_sum` and
`vltrace_tail_attr` on hand-built trees, the four metrics' files and
entries, a whole rehearsal run that prints them, and launch_events.py's
walk of the `vl_launch` host events on hand-built lines.  Run by hand
with the rest: `python3 -m pytest benchmark/tests -q`."""

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
import launch_events  # noqa: E402
from readers import vltrace_attr_sum, vltrace_tail_attr  # noqa: E402

CPU_METRICS = ("request_gc_ms", "tail_gc_ms", "gc_young_pause_s_in_window",
               "background_cpu_s_per_query")


def node(name, duration_ms, children=(), **attrs):
    out = {"name": name, "start_ms": 0.0, "duration_ms": duration_ms}
    if attrs:
        out["attrs"] = attrs
    if children:
        out["children"] = list(children)
    return out


def rec(tree, latency_ms=10.0) -> dict:
    """A client record as run.py keeps it, for a /stats_query answer."""
    return {"req": {"endpoint": "stats_query"}, "due": 0.0,
            "sent": 0.0, "done": latency_ms / 1e3,
            "body": json.dumps({"status": "success", "trace": tree})}


def launches(*gc_ms):
    return node("request", 20, [node("query", 15, [
        node("submit", 3, [node("args", 1, gc_ms=0.25),
                           node("launch", 2, gc_ms=v)])
        for v in gc_ms])])


# ---- vltrace_attr_sum ----

def test_attr_sum_sums_a_query_then_means_over_queries():
    spec = {"spans": ["launch"], "attr": "gc_ms"}
    # 0.5 + 1.0 in the first query, 2.0 in the second, and a query that
    # reached no device reads 0
    ctx = {"records": [rec(launches(0.5, 1.0)), rec(launches(2.0)),
                       rec(node("request", 5, [node("parse", 1)]))]}
    assert vltrace_attr_sum.read(spec, ctx) == pytest.approx(3.5 / 3)
    # several names: each listed span once, the rest left out
    spec = {"spans": ["args", "launch"], "attr": "gc_ms"}
    assert vltrace_attr_sum.read(spec, ctx) == pytest.approx(
        (1.5 + 0.5 + 2.0 + 0.25) / 3)


def test_attr_sum_is_absent_where_no_span_carries_the_attribute():
    spec = {"spans": ["launch"], "attr": "gc_ms"}
    # the parent program: the spans are there, the attribute is not
    old = node("request", 20, [node("submit", 3, [node("launch", 2)])])
    assert vltrace_attr_sum.read(spec, {"records": [rec(old)]}) is None
    assert vltrace_attr_sum.read(spec, {"records": []}) is None
    # an untraced answer has no tree at all
    bare = {"req": {"endpoint": "query"}, "body": b'{"_msg":"x"}\n'}
    assert vltrace_attr_sum.read(spec, {"records": [bare]}) is None


# ---- vltrace_tail_attr ----

def test_tail_attr_means_over_the_requests_at_or_above_the_quantile():
    spec = {"span": "request", "attr": "gc_ms", "quantile": 0.95}
    # twenty requests 1..20 ms: the 95th percentile (inclusive) is
    # 19.05 ms, so the tail is the one at 20 ms
    recs = [rec(node("request", ms, gc_ms=ms / 10), ms)
            for ms in range(1, 21)]
    assert vltrace_tail_attr.read(spec, {"records": recs}) == \
        pytest.approx(2.0)
    spec = dict(spec, quantile=0.5)        # 10.5 ms: the ten from 11 up
    assert vltrace_tail_attr.read(spec, {"records": recs}) == \
        pytest.approx(sum(range(11, 21)) / 10 / 10)
    # a slow request whose root sat through no collection reads 0
    recs[-1] = rec(node("request", 20, gc_ms=0.0), 20)
    spec = dict(spec, quantile=0.95)
    assert vltrace_tail_attr.read(spec, {"records": recs}) == 0.0


def test_tail_attr_is_absent_where_no_span_carries_the_attribute():
    spec = {"span": "request", "attr": "gc_ms", "quantile": 0.95}
    recs = [rec(node("request", ms), ms) for ms in range(1, 21)]
    assert vltrace_tail_attr.read(spec, {"records": recs}) is None
    assert vltrace_tail_attr.read(spec, {"records": []}) is None


# ---- the files, and a whole rehearsal ----

def test_every_cpu_metric_has_its_file_its_reader_and_its_entry():
    bench = gen.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in CPU_METRICS:
        spec = gen.load_json(os.path.join(BENCH, "metrics", name + ".json"))
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["source_kind"] + ".py")), name
        assert entries[name]["unit"] == spec["unit"]
        assert entries[name]["layer"] == spec["layer"]
        assert entries[name]["moves"] == spec["moves"]
        assert entries[name]["better"] == "lower"
        assert "workloads" not in entries[name]


def test_rehearsal_reads_the_cpu_metrics():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "baseline-1chip.needle", "--rehearsal", "--trace", "1", "--seed",
         "2147483777", "--seconds", "4"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in CPU_METRICS:
        assert name in m, name
        assert m[name] >= 0, name
    assert m["background_cpu_s_per_query"] > 0


# ---- launch_events.py: the vl_launch host events ----

def test_launch_walk_charges_each_nested_event_its_self_time():
    line = [
        ("vl_launch", 1000, 1000),
        ("DevicePut", 1100, 200),
        ("PjitFunction(f)", 1400, 500),
        ("PjitFunction(f)", 1450, 400),      # nested in the first
        ("ParseArguments", 1460, 10),
        ("outside", 2100, 50),               # after the launch
        ("vl_launch", 3000, 100),            # a launch with nothing in it
    ]
    w = launch_events.walk([line, [("other thread", 1000, 900)]])
    assert (w["launches"], w["launch_ns"]) == (2, 1100)
    # 1000 - 200 - 500 in the first launch, all 100 of the second
    assert w["self_ns"] == 300 + 100
    by = w["by"]
    assert by["DevicePut"] == [1, 200, 200, {1}]
    assert by["PjitFunction(f)"] == [2, 900, 100 + 390, {1, 2}]
    assert by["ParseArguments"] == [1, 10, 10, {3}]
    assert "outside" not in by and "other thread" not in by
    text = launch_events.report(w)
    assert text.splitlines()[0].startswith("vl_launch: 2 events, mean "
                                           "0.0006 ms")
    assert "PjitFunction(f)" in text.splitlines()[2]


def test_launch_walk_reads_nothing_without_the_annotation():
    """The parent program has no vl_launch: the walk finds no launch."""
    w = launch_events.walk([[("PjitFunction(f)", 0, 500)]])
    assert w == {"launches": 0, "launch_ns": 0, "self_ns": 0, "by": {}}
