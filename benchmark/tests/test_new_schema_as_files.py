"""A deployment on other rows comes as files alone (PR 28): this test writes
a schema module, a configuration, a traffic file and a BENCHMARK.json into
a temporary directory, edits and writes nothing under benchmark/, and
drives whole runs of run.py over the stand-in child.  The toy schema
differs from access_line where the configurations in the queue will: two
stream fields, 120 streams with uneven rows, a numeric and an ip field, a
second text field under a phrase filter, and a placeholder kind, a `where`
operator, a stats function and an answer kind of its own."""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, HERE)

import gen  # noqa: E402
import partbuild  # noqa: E402
from test_yardstick import drive  # noqa: E402

SCHEMA = '''
"""Toy rows: svc/host streams, _msg, path, bytes, ip, req."""
import numpy as np
from gen import row_hash

STREAM_FIELDS = ("svc", "host")
MESSAGE_FIELD = "_msg"
HOSTS = 10
WORDS = ["started", "stopped", "timeout waiting", "disk full"]
PATHS = ["/login", "/cart/add", "/cart/checkout", "/healthz"]
REQS = 100_000
U = np.uint64


def streams(config):
    return int(config["services"]) * HOSTS


def stream_of(idx, config):
    # the smaller of two draws: low streams hold many rows, high ones few
    h, n = row_hash(idx, 0x5EED), U(streams(config))
    return np.minimum(h % n, (h >> U(20)) % n).astype(np.int64)


def stream_tags(k, config):
    return [("svc", f"svc{k // HOSTS}"), ("host", f"host-{k % HOSTS}")]


def tenant(k, config):
    return (0, 0)


def row_fields(idx, seed, config):
    h = row_hash(idx, seed)
    return {"word": (h % U(4)).astype(np.int64),
            "path": ((h >> U(8)) % U(4)).astype(np.int64),
            "bytes": ((h >> U(16)) % U(5000)).astype(np.int64),
            "ip": ((h >> U(32)) % U(1 << 16)).astype(np.int64),
            "req": ((h >> U(44)) % U(REQS)).astype(np.int64)}


def _ips(ip):
    return np.strings.add(np.strings.add(
        np.strings.add(b"10.0.", (ip >> 8).astype("S3")), b"."),
        (ip & 255).astype("S3"))


class Text:
    def __init__(self, config):
        self.config = config
        tags = [stream_tags(k, config) for k in range(streams(config))]
        self.svc = np.array([t[0][1] for t in tags], "S")
        self.host = np.array([t[1][1] for t in tags], "S")
        self.words, self.paths = np.array(WORDS, "S"), np.array(PATHS, "S")

    def text(self, field, idx, f, end=""):
        if field == "path":
            out = self.paths[f["path"]]
        else:
            out = np.strings.add(np.strings.add(self.words[f["word"]],
                                                b" req="),
                                 f["req"].astype("S6"))
        return np.strings.add(out, end.encode()) if end else out

    def columns(self, idx, f):
        s = stream_of(idx, self.config)
        return {"svc": self.svc[s], "host": self.host[s],
                "_msg": self.text("_msg", idx, f),
                "path": self.text("path", idx, f),
                "bytes": f["bytes"].astype("S4"), "ip": _ips(f["ip"]),
                "req": f["req"].astype("S6")}


def row_token(field, row, seed, config):
    return str(int(row_fields(np.array([row]), seed, config)[field][0]))


def absent_token(field, draw, config):
    return str(REQS + draw(1000))


def selector(stream, config):
    return dict(stream_tags(stream, config))


def _bytes_range(name, p, draw, layout, seed):
    lo = draw(5000 - int(p["width"]))
    return {name + "_lo": str(lo), name + "_hi": str(lo + int(p["width"]))}


def _where_stream(blk, a, b, args, config):
    svc, host = int(args[0][3:]), int(args[1][5:])
    return stream_of(blk["idx"][a:b], config) == svc * HOSTS + host


def _where_token(blk, a, b, args, config):
    return blk[args[0]][a:b] == int(args[1])


def _where_between(blk, a, b, args, config):
    col = blk[args[0]][a:b]
    return (col >= int(args[1])) & (col <= int(args[2]))


class _Sum:
    def __init__(self, config):
        self.total = 0

    def add(self, blk, a, b, mask):
        self.total += int(blk["bytes"][a:b][mask].sum())

    def value(self):
        return self.total


def _answer_ips(ref, request, spec):
    rows = []
    for blk, a, z, mask in ref.matches(request, spec):
        rows += [{"ip": ip.decode()} for ip in
                 _ips(blk["ip"][a:z][mask]).tolist()]
    return sorted(tuple(sorted(r.items())) for r in rows)


PLACEHOLDERS = {"bytes_range": _bytes_range}
WHERE = {"stream": _where_stream, "token": _where_token,
         "between": _where_between}
STATS = {"sum_bytes": _Sum}
ANSWERS = {"ips": _answer_ips}
'''

CONFIG = {
    "name": "toy-1chip", "schema": "toy", "services": 12, "chips": 1,
    "runner": "BatchRunner", "rows": 102400, "t0_unix_s": 1753660800,
    "days": [{"day": 0, "span_s": 86400, "parts": [60000, 40000]},
             {"day": 1, "span_s": 60, "fresh": True, "parts": [1200, 1200]}],
    "staged_width": {"_time": 8, "_msg": 32, "path": 16, "bytes": 8,
                     "ip": 16, "req": 8},
    "reduced": [], "build_processes": 2}

ALIAS = {"alias": {"kind": "alias", "prefix": "c"}}
WINDOW = {"kind": "window", "span_frac": 0.5, "region": "bulk"}
TRAFFIC = {
    "name": "mixed", "loop": "open", "rate_per_s": 20.0,
    "schedule_seed": 7, "client_threads": 8, "warmup_callers": 2,
    "check_sample": 30,
    "rotation": ["path_phrase", "big_responses", "lookup", "who", "lookup"],
    "classes": {
        "path_phrase": {
            "endpoint": "query", "answer": "stats",
            "query": "_time:[{w_t0}, {w_t1}) path:\"{seg}\" "
                     "| stats count() {alias}",
            "params": {**ALIAS, "w": WINDOW,
                       "seg": {"kind": "choice",
                               "values": ["cart", "login"]}},
            "columns_read": ["_time", "path"],
            "reference": {"where": [["time"], ["phrase", "path", "{seg}"]],
                          "stats": [["count", "{alias}"]]}},
        "big_responses": {
            "endpoint": "stats_query", "answer": "stats",
            "query": "bytes:range[{r_lo}, {r_hi}] | stats count() {alias}, "
                     "sum(bytes) {salias}",
            "params": {**ALIAS, "salias": {"kind": "alias", "prefix": "s"},
                       "r": {"kind": "bytes_range", "width": 500}},
            "columns_read": ["bytes"],
            "reference": {"where": [["between", "bytes", "{r_lo}", "{r_hi}"]],
                          "stats": [["count", "{alias}"],
                                    ["sum_bytes", "{salias}"]]}},
        "lookup": {
            "endpoint": "query", "answer": "stats",
            "query": "{{svc=\"{tok_svc}\",host=\"{tok_host}\"}} req:{tok} "
                     "| stats count() {alias}",
            "params": {**ALIAS, "tok": {"kind": "row_token", "field": "req",
                                        "fresh_share": 0.5}},
            "columns_read": ["req"],
            "reference": {"where": [["stream", "{tok_svc}", "{tok_host}"],
                                    ["token", "req", "{tok}"]],
                          "stats": [["count", "{alias}"]]}},
        "who": {
            "endpoint": "query", "answer": "rows",
            "query": "_time:[{w_t0}, {w_t1}) \"disk full\" | fields ip",
            "params": {"w": {**WINDOW, "span_frac": 0.05}},
            "columns_read": ["_time", "_msg", "ip"],
            "reference": {"where": [["time"], ["phrase", "disk full"]],
                          "stats": [], "answer": "ips"}}}}

BENCHMARK = {
    "configs": [{"name": "toy-1chip", "file": "toy/configs/toy-1chip.json"}],
    "workloads": [{"name": "toy-1chip.mixed", "config": "toy-1chip",
                   "traffic": "mixed", "chips": 1}],
    "end_to_end": [{"name": "query_p50_ms"}, {"name": "setup_s"}],
    "per_layer": []}


def tracked_files() -> dict:
    """{path: mtime} of everything under benchmark/ but caches."""
    out = {}
    for d, _dirs, files in os.walk(BENCH):
        if "__pycache__" not in d:
            out.update({os.path.join(d, f): os.stat(os.path.join(d, f))
                        .st_mtime_ns for f in files})
    return out


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    root = tmp_path_factory.mktemp("toy_root")
    for d in ("configs", "schemas", "traffic"):
        (root / "toy" / d).mkdir(parents=True)
    (root / "toy" / "schemas" / "toy.py").write_text(SCHEMA)
    (root / "toy" / "configs" / "toy-1chip.json").write_text(
        json.dumps(CONFIG))
    (root / "toy" / "traffic" / "mixed.json").write_text(json.dumps(TRAFFIC))
    (root / "BENCHMARK.json").write_text(json.dumps(BENCHMARK))
    return str(root)


def test_the_toy_differs_where_the_queue_will(toy):
    config = gen.load_config(os.path.join(toy, "toy", "configs",
                                          "toy-1chip.json"))
    layout = gen.Layout(config)
    assert layout.streams == 120 and len(layout.schema.STREAM_FIELDS) == 2
    rows = np.bincount(layout.schema.stream_of(
        np.arange(layout.rows, dtype=np.int64), config), minlength=120)
    assert rows.max() > 5 * max(1, rows.min())     # uneven rows a stream


def test_a_whole_run_on_a_schema_the_harness_never_saw(toy):
    before = tracked_files()
    good = drive("toy-1chip.mixed", "none", root=toy, scratch=toy)
    assert good["correct"] is True and good["failed"] == 0
    assert good["checked"]["mismatched"] == {"value": 0, "limit": 0}
    assert good["checked"]["compared"]["value"] >= 20
    # the control: the fresh parts unreadable; and an altered answer
    for fault in ("fresh", "altered"):
        bad = drive("toy-1chip.mixed", fault, root=toy, scratch=toy)
        assert bad["correct"] is False
        assert bad["checked"]["mismatched"]["value"] > 0
    assert tracked_files() == before, "nothing under benchmark/ was touched"


def test_every_class_of_the_toy_reads_rows(toy):
    """No class of the toy is vacuous: each answers with rows or a count
    above nought somewhere in a window, through the schema's own filter,
    stats function, placeholder and answer kind."""
    import reference
    import run
    import traffic as traffic_gen
    c = run.load_cell("toy-1chip.mixed", True, toy)
    ref = reference.Reference(c["layout"], 5)
    seen = {}
    for k, (_due, cls) in enumerate(traffic_gen.schedule(c["traffic"], 3.0)):
        req = traffic_gen.make_request(c["traffic"], c["layout"], 5, k, cls)
        spec = c["traffic"]["classes"][cls]["reference"]
        rows = ref.answer(req, spec)
        seen[cls] = seen.get(cls, 0) + sum(
            v for row in rows for _k, v in row if isinstance(v, int)) \
            + sum(1 for row in rows for _k, v in row if isinstance(v, str))
    assert set(seen) == set(TRAFFIC["rotation"]) and min(seen.values()) > 0


def test_the_build_splits_a_toy_part_by_sets_of_streams(toy):
    """120 streams over 8 jobs: every row of the part lands in exactly
    one block, under its own stream's id and tags."""
    config = gen.load_config(os.path.join(toy, "toy", "configs",
                                          "toy-1chip.json"))
    layout = gen.Layout(config, 0.1)
    assert partbuild.jobs(layout) == 8
    sids, tags = partbuild.stream_ids(layout)
    part = layout.parts[0]
    job = json.dumps(config), 0.1, 9, 0
    blocks = partbuild.in_build_order(
        [b for j in range(8) for b in partbuild.part_blocks(job + (j,))])
    assert sum(b.num_rows for b in blocks) == part["hi"] - part["lo"]
    assert [b.stream_id for b in blocks] == sorted(b.stream_id
                                                   for b in blocks)
    tag_of = dict(zip(sids, tags))
    idx = np.arange(part["lo"], part["hi"], dtype=np.int64)
    times = layout.times(idx)
    stream = layout.schema.stream_of(idx, config)
    for b in blocks:
        assert b.stream_tags_str == tag_of[b.stream_id]
        k = sids.index(b.stream_id)
        assert np.isin(b.timestamps, times[stream == k]).all()
