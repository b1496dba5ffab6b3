"""The benchmark's own tests: run by hand, `python3 -m pytest
benchmark/tests -q`.  They need numpy and nothing of the program."""

import glob
import io
import json
import os
import re
import sys
from contextlib import redirect_stdout

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import bytes as yardstick_bytes  # noqa: E402
import gen  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic as traffic_gen  # noqa: E402
import xplane  # noqa: E402
from readers import client as client_reader  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SCALE = gen.REHEARSAL_SCALE


def load(kind: str, name: str) -> dict:
    path = os.path.join(BENCH, kind, name + ".json")
    return gen.load_config(path) if kind == "configs" else gen.load_json(path)


# ---- the trace reduction ----

def test_union_and_busy_on_synthetic_planes():
    ops = [("%fusion.1 = pred[8]{0} fusion(u32[8]{0} %a), kind=kLoop", 1000,
            500, {}),
           ("%fusion.2 = pred[8]{0} fusion(u32[8]{0} %b)", 1200, 500, {}),
           ("%all-reduce.3 = u32[8]{0} all-reduce(u32[8]{0} %c)", 3000, 100,
            {})]
    mods = [("jit_f(123)", 990, 800, {}), ("jit_g(7)", 2990, 200, {})]
    planes = [("/host:CPU", [("python", [("bench_marker", 900, 10, {})])]),
              ("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", mods),
                                 ("Steps", ops)])]
    out = xplane.reduce_planes(planes, marker_ns=1_000_000_900)
    assert out["window_s"] == pytest.approx(2100e-9)
    assert out["busy_s"] == pytest.approx(800e-9)
    assert out["idle_share_pct"] == pytest.approx(100 * (1 - 800 / 2100))
    assert out["device_ops"] == [["jit_f/fusion", pytest.approx(1000e-9)],
                                 ["jit_g/all-reduce", pytest.approx(100e-9)]]
    # the one gap, on the parent's clock
    assert out["gaps_unix_ns"] == [[1_000_000_000 + 1700, 1300]]
    # idle is a share of the traced window, not of first-to-last operation
    out = xplane.reduce_planes(planes, marker_ns=1_000_000_900,
                               traced_s=3200e-9)
    assert out["window_s"] == pytest.approx(3200e-9)
    assert out["idle_share_pct"] == pytest.approx(100 * (1 - 800 / 3200))
    assert xplane.reduce_planes([("/host:CPU", [])]) == {}


def test_recorded_trace_from_the_chip():
    planes = gen.load_json(os.path.join(HERE, "trace_fixture.json"))
    out = xplane.reduce_planes([(p, [(ln, [tuple(e) for e in evs])
                                     for ln, evs in lines])
                                for p, lines in planes], marker_ns=1)
    assert out["devices"] >= 1
    assert 0 < out["busy_s"] <= out["window_s"]
    assert 0 <= out["idle_share_pct"] < 100
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    assert out["gaps_unix_ns"], "the marker ties the gaps to the wall clock"


# ---- percentiles and due-time arithmetic ----

def test_percentiles_and_due_time():
    recs = [{"due": 10.0, "sent": 10.0 + 0.001 * i, "done": 10.1 + 0.01 * i,
             "req": {"cls": "a" if i % 2 else "b"}} for i in range(101)]
    ctx = {"records": recs}
    lat = {"of": "latency_ms", "stat": "p50"}
    assert client_reader.read(lat, ctx) == pytest.approx(600.0)
    assert client_reader.read({**lat, "stat": "p95"}, ctx) \
        == pytest.approx(1050.0)
    assert client_reader.read({"of": "late_ms", "stat": "max"}, ctx) \
        == pytest.approx(100.0)
    # latency is from the due instant, not from when it was sent
    assert client_reader.read({"of": "service_ms", "stat": "p50"}, ctx) \
        == pytest.approx(550.0)
    assert client_reader.read({**lat, "classes": ["zzz"]}, ctx) is None


# ---- the schedule ----

@pytest.mark.parametrize("name", ["adhoc_scan", "needle"])
def test_schedule_is_the_files_own(name):
    traffic = load("traffic", name)
    a = traffic_gen.schedule(traffic, 30.0)
    assert a == traffic_gen.schedule(traffic, 30.0)
    assert all(0 < t < 30.0 for t, _ in a)
    rot = traffic["rotation"]
    counts = {c: sum(1 for _t, x in a if x == c) for c in set(rot)}
    for c, n in counts.items():
        share = rot.count(c) / len(rot)
        assert abs(n - share * len(a)) <= rot.count(c)
    # --seed changes every literal and no instant or class
    config = load("configs", "baseline-1chip")
    layout = gen.Layout(config, SCALE)
    q1 = [traffic_gen.make_request(traffic, layout, 1, k, c)["query"]
          for k, (_t, c) in enumerate(a[:40])]
    q2 = [traffic_gen.make_request(traffic, layout, 2**31 + 5, k, c)["query"]
          for k, (_t, c) in enumerate(a[:40])]
    assert q1 != q2 and len(set(q1)) == len(q1)
    # ... nor where a request's time window lies: the same parts, the same work
    for k, (_t, c) in enumerate(a[:40]):
        r1 = traffic_gen.make_request(traffic, layout, 1, k, c)
        r2 = traffic_gen.make_request(traffic, layout, 99, k, c)
        assert r1["t_range"] == r2["t_range"]
    # the heaviest class holds well over 5% of the requests
    assert min(rot.count(c) for c in set(rot)) / len(rot) > 0.1


# ---- bytes ----

def test_bytes_on_a_two_part_table():
    config = {"schema": "access_line", "_dir": BENCH, "streams": 8,
              "t0_unix_s": 0,
              "days": [{"day": 0, "span_s": 1000, "parts": [800, 200]}],
              "staged_width": {"_time": 8, "_msg": 128}}
    layout = gen.Layout(config)
    cls = {"columns_read": ["_time", "_msg"]}
    ns = gen.NS
    assert yardstick_bytes.required(config, layout, cls, None) \
        == {"rows": 1000, "bytes": 1000 * 136}
    # a window inside the first part excludes the second
    assert yardstick_bytes.required(config, layout, cls,
                                    (100 * ns, 200 * ns))["rows"] == 800
    assert yardstick_bytes.required(config, layout, cls,
                                    (900 * ns, 950 * ns))["rows"] == 200
    assert yardstick_bytes.required(config, layout, {"columns_read": ["_msg"]},
                                    (700 * ns, 900 * ns))["bytes"] \
        == 1000 * 128


# ---- every data file ----

def test_every_data_file_loads_and_is_named_well():
    bench = gen.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    listed_configs = {c["name"]: c for c in bench["configs"]}
    for path in glob.glob(os.path.join(BENCH, "configs", "*.json")):
        config = gen.load_config(path)
        assert NAME.match(config["name"])
        assert os.path.basename(path) == config["name"] + ".json"
        c = listed_configs.get(config["name"])
        if c is not None:
            assert os.path.join(ROOT, c["file"]) == path
            assert set(c["reduced"]) == set(config["reduced"])
        layout = gen.Layout(config)     # loads the schema it names
        assert os.path.exists(os.path.join(BENCH, "schemas",
                                           config["schema"] + ".py"))
        assert layout.rows == config["rows"]
        assert max(sum(1 for p in layout.parts if p["day"] == d["day"])
                   for d in config["days"]) < 15   # DEFAULT_PARTS_TO_MERGE
    for path in glob.glob(os.path.join(BENCH, "traffic", "*.json")):
        traffic = gen.load_json(path)
        assert NAME.match(traffic["name"])
        assert os.path.basename(path) == traffic["name"] + ".json"
        for cls in traffic["rotation"]:
            spec = traffic["classes"][cls]
            assert NAME.match(cls) and spec["answer"] in ("stats", "rows",
                                                          "sorted")
            assert spec["columns_read"] and spec["reference"]["stats"]
    listed = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for path in glob.glob(os.path.join(BENCH, "metrics", "*.json")):
        m = gen.load_json(path)
        assert os.path.basename(path) == m["name"] + ".json"
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           m["source_kind"] + ".py"))
        if m["name"] in listed:
            assert listed[m["name"]]["unit"] == m["unit"]
            assert listed[m["name"]].get("moves") == m.get("moves")
    for name in listed:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".json"))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert len(w["why"]) <= 200


# ---- the reference, its control, and a run with the timed path broken ----

def test_reference_on_rows_counted_by_hand():
    config = load("configs", "baseline-1chip")
    layout = gen.Layout(config, SCALE)
    import numpy as np
    idx = np.arange(layout.rows, dtype=np.int64)
    schema = layout.schema
    f = schema.row_fields(idx, 5, config)
    msgs = [m.decode() for m in
            schema.Text(config).text("_msg", idx, f).tolist()]
    ref = reference.Reference(layout, 5)
    req = {"t_range": None, "vals": {"phrase": "deadline exceeded",
                                     "alias": "c"}}
    got = ref.answer(req, {"where": [["phrase", "{phrase}"]],
                           "stats": [["count", "{alias}"]]})
    assert got == [(("c", sum("deadline exceeded" in m for m in msgs)),)]
    # a phrase stops at word boundaries, a regex does not
    req["vals"]["phrase"] = "deadline exceede"
    assert ref.answer(req, {"where": [["phrase", "{phrase}"]],
                            "stats": [["count", "{alias}"]]}) == [(("c", 0),)]
    got = ref.answer(req, {"where": [["regex", "dead.*exceede"]],
                           "stats": [["count", "{alias}"]]})
    assert got[0][0][1] == sum(bool(re.search("dead.*exceede", m))
                               for m in msgs)
    # half-open time range and 5-minute buckets
    t0, t1 = layout.span("bulk")
    req = {"t_range": (t0 + 600 * gen.NS, t0 + 1500 * gen.NS),
           "vals": {"alias": "c"}}
    got = ref.answer(req, {"where": [["time"]], "by_time_s": 300,
                           "stats": [["count", "{alias}"]]})
    times = layout.times(idx)
    inside = times[(times >= req["t_range"][0]) & (times < req["t_range"][1])]
    assert sum(dict(r)["c"] for r in got) == len(inside)
    assert [dict(r)["_time"] for r in got] == sorted(
        set((inside // (300 * gen.NS) * 300 * gen.NS).tolist()))


def drive(workload: str, fault: str, seed: int = 77, root: str = ROOT,
          scratch: str = HERE) -> dict:
    """A whole run of run.py over the stand-in child, for a workload of
    the BENCHMARK.json in `root`; the stand-in's wrapper is written to
    `scratch`."""
    traffic_path = run.load_cell(workload, True, root)["traffic_path"]
    stub = os.path.join(HERE, "stub_serve.py")
    wrapper = os.path.join(scratch, f".stub_{fault}.py")
    with open(wrapper, "w") as f:
        f.write("import sys, runpy\n"
                f"sys.argv += ['--traffic', {traffic_path!r}, "
                f"'--seconds', '3', '--fault', {fault!r}]\n"
                f"runpy.run_path({stub!r}, run_name='__main__')\n")
    out = io.StringIO()
    try:
        with redirect_stdout(out):
            rc = run.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", "3", "--trace", "0", "--rehearsal"],
                          serve_script=wrapper, root=root)
    finally:
        os.remove(wrapper)
    assert rc == 0
    return json.loads(out.getvalue().splitlines()[-1])


@pytest.mark.parametrize("workload", ["baseline-1chip.adhoc_scan",
                                      "baseline-1chip.needle"])
def test_a_sound_run_is_correct_and_the_control_is_not(workload):
    good = drive(workload, "none")
    assert good["correct"] is True and good["failed"] == 0
    assert good["checked"]["mismatched"] == {"value": 0, "limit": 0}
    assert list(good)[-1] == "checked"
    for lost in ("fresh", "part:3"):
        control = drive(workload, lost)
        assert control["correct"] is False
        assert control["checked"]["mismatched"]["value"] > 0


@pytest.mark.parametrize("workload", ["baseline-1chip.adhoc_scan",
                                      "baseline-1chip.needle"])
def test_the_control_goes_through_the_harness_comparison(workload):
    import control
    cell = run.load_cell(workload, rehearsal=True)
    seen = set()
    for lost in ("fresh", "part:3"):
        v = control.verdict(cell["traffic"], cell["layout"], 2**31 + 9, 30.0,
                            lost)
        assert v["correct"] is False
        assert v["checked"]["mismatched"]["value"] == len(v["wrong"]) > 0
        assert v["checked"]["mismatched"]["limit"] == 0
        seen |= {w["cls"] for w in v["wrong"]}
    # between them the two controls reach every class but the one that
    # can read no lost row (a token no row holds)
    assert seen == set(cell["traffic"]["rotation"]) - {"needle_miss"}


@pytest.mark.parametrize("workload", ["baseline-1chip.adhoc_scan",
                                      "baseline-1chip.needle"])
def test_a_broken_timed_path_is_not_correct(workload):
    """An answer altered where it is produced: the one fault of the
    contract's list that a one-chip query cell can have."""
    assert drive(workload, "altered")["correct"] is False
