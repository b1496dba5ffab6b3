"""Columnar emit differential suite: the native NDJSON serializer
(native/vlnative.cpp vl_emit_ndjson over BlockResult.emit_columns) must
be BYTE-IDENTICAL to the per-row path (dict per row + json.dumps with
ensure_ascii=False and (",", ":") separators) on every storage column
type and every escape class — VL_NATIVE_EMIT=0/1 over the HTTP query
path, plus randomized round-trips through
json.loads."""

import json
import random

import pytest

from victorialogs_tpu.engine.block_result import (BlockResult,
                                                  parse_rfc3339)
from victorialogs_tpu.engine.emit import (ndjson_block, ndjson_block_py,
                                          native_emit_enabled)
from victorialogs_tpu.engine.searcher import run_query, run_query_collect
from victorialogs_tpu.server import vlselect
from victorialogs_tpu.storage.log_rows import LogRows, TenantID
from victorialogs_tpu.storage.storage import Storage

NS = 1_000_000_000
T0 = 1_753_660_800_000_000_000
TEN = TenantID(0, 0)

# every escape class the serializer must reproduce: quotes, backslashes,
# named control escapes, \u00XX controls, DEL (NOT escaped), multibyte
# UTF-8 of 2/3/4 bytes, and an empty value (omitted field)
EDGE_VALUES = [
    'plain',
    'with "quotes" and \\backslashes\\',
    'tab\there\nnewline\rcr',
    'ctrl\x00\x01\x1f\x7fdel',
    'b\bf\f',
    'café 2-byte',
    '日本語 3-byte',
    'emoji \U0001f642 4-byte',
    '',
    ' leading and trailing ',
    '{"nested":"json"}',
    'sl/ash',
]


@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    s = Storage(str(tmp_path_factory.mktemp("emitstore")),
                retention_days=100000, flush_interval=3600)
    lr = LogRows(stream_fields=["app"])
    for i in range(256):
        fields = [
            ("app", f"app{i % 2}"),
            ("_msg", f"edge row {i}: {EDGE_VALUES[i % len(EDGE_VALUES)]}"),
            ("lvl", ["info", "warn", "err"][i % 3]),       # dict column
            ("code", str(200 + i % 5)),                    # uint column
            ("ratio", str(float(i) / 8.0)),                # float column
            ("ip", f"10.0.{i % 4}.{i % 250}"),             # ipv4 column
            ("iso", f"2025-07-28T00:00:{i % 60:02d}Z"),    # iso8601 column
            ("konst", "same-everywhere"),                  # const column
            ("weird", EDGE_VALUES[(i * 7) % len(EDGE_VALUES)]),
        ]
        if i % 3 == 0:
            fields.append(("sparse", f"only-sometimes-{i}"))
        # empty value == absent field: must be omitted either way
        fields.append(("empty", "" if i % 2 else f"e{i}"))
        lr.add(TEN, T0 + i * 137_000_003, fields)  # uneven ns fractions
    s.must_add_rows(lr)
    s.debug_flush()
    yield s
    s.close()


def _http_query(storage, q, runner=None, **extra):
    args = {"query": q, "limit": "0"}
    args.update(extra)
    chunks = list(vlselect.handle_query(storage, args, {}, runner=runner))
    return b"".join(c if isinstance(c, bytes) else c.encode("utf-8")
                    for c in chunks)


QUERIES = [
    "*",
    "edge",
    '* | fields _time, lvl, code',
    '* | fields weird, _msg',                 # projection ORDER: weird first
    '* | fields lvl, lvl, code',              # duplicate names dedupe
    '* | fields sparse, empty, konst',
    '* | delete _msg, weird',
    'code:>=202 | fields code, ratio, ip, iso',
    '* | sort by (code) limit 7',
    '* | stats by (lvl) count() hits',
    '* | limit 5',
]


def test_native_vs_python_http_matrix(storage, monkeypatch):
    """Acceptance matrix: byte-identical NDJSON under VL_NATIVE_EMIT=0/1,
    CPU executor and device runner."""
    from victorialogs_tpu.tpu.batch import BatchRunner
    runner = BatchRunner()
    for q in QUERIES:
        outs = {}
        for native in ("0", "1"):
            monkeypatch.setenv("VL_NATIVE_EMIT", native)
            outs[native] = _http_query(storage, q, runner=runner)
        assert outs["0"] == outs["1"], f"native/python diverged on {q!r}"
        assert outs["1"] == _http_query(storage, q, runner=None), \
            f"runner/CPU diverged on {q!r}"


def test_native_serializer_is_active(storage, monkeypatch):
    """The parity matrix is meaningless if the native path silently fell
    back — pin that it engages on this image."""
    from victorialogs_tpu import native
    monkeypatch.setenv("VL_NATIVE_EMIT", "1")
    assert native.available()
    assert native_emit_enabled()
    blocks = []
    run_query(storage, [TEN], "*", write_block=blocks.append,
              timestamp=T0)
    assert blocks
    names, cols = blocks[0].emit_columns()
    data = native.emit_ndjson_native(
        [(json.dumps(n, ensure_ascii=False) + ":").encode("utf-8")
         for n in names], cols, blocks[0].nrows)
    assert data is not None
    assert data == ndjson_block_py(blocks[0])


def test_projection_order_and_empty_omission(storage, monkeypatch):
    monkeypatch.setenv("VL_NATIVE_EMIT", "1")
    out = _http_query(storage, '* | fields weird, _msg, empty')
    lines = out.splitlines()
    assert len(lines) == 256
    for ln in lines:
        row = json.loads(ln)
        assert "empty" not in row or row["empty"] != ""
        keys = [k for k in row if k in ("weird", "_msg")]
        assert keys == sorted(keys, key=["weird", "_msg"].index)


def test_duplicate_fields_never_duplicate_json_keys(storage, monkeypatch):
    """`fields lvl, lvl` must collapse to one key like the materialized
    path always did — never two identical keys in the emitted JSON."""
    monkeypatch.setenv("VL_NATIVE_EMIT", "1")
    out = _http_query(storage, 'edge | fields lvl, lvl')
    for ln in out.splitlines():
        assert ln.count(b'"lvl"') == 1, ln


def test_block_result_emit_time_dict_const_columns(storage):
    """Typed emit paths (_time vectorized RFC3339, dict codes, consts,
    numerics) against the rows() oracle, on raw storage-backed blocks."""
    blocks = []
    run_query(storage, [TEN], "*", write_block=blocks.append,
              timestamp=T0)
    for br in blocks:
        assert br._bs is not None          # storage-backed, not a copy
        assert ndjson_block(br) == ndjson_block_py(br)


def test_fields_restriction_keeps_block_backing(storage):
    """The fields pipe must project WITHOUT materializing: the emit sink
    sees a block-backed result (the tentpole's whole point)."""
    blocks = []
    run_query(storage, [TEN], "* | fields _time, lvl",
              write_block=blocks.append, timestamp=T0)
    assert blocks
    for br in blocks:
        assert br._bs is not None
        assert br.column_names() == ["_time", "lvl"]
        assert ndjson_block(br) == ndjson_block_py(br)


def test_randomized_roundtrip_1000_rows(monkeypatch):
    """>=1000 random rows of hostile strings through from_columns:
    native bytes == python bytes, and every line json.loads back to the
    expected dict (non-empty values only)."""
    rng = random.Random(0xE417)
    alphabet = ('ab"\\\n\r\t\x00\x01\x1f\x7f'
                'é日\U0001f642 /{}[]:,')
    nrows = 1200

    def rand_val():
        return "".join(rng.choice(alphabet)
                       for _ in range(rng.randrange(0, 24)))

    cols = {f"f{k}": [rand_val() for _ in range(nrows)] for k in range(6)}
    cols["fixed"] = ["x"] * nrows
    br = BlockResult.from_columns(cols)
    monkeypatch.setenv("VL_NATIVE_EMIT", "1")
    nat = ndjson_block(br)
    assert nat == ndjson_block_py(br)
    lines = nat.splitlines()
    assert len(lines) == nrows
    names = list(cols)
    for i, ln in enumerate(lines):
        row = json.loads(ln)
        assert row == {n: cols[n][i] for n in names if cols[n][i] != ""}


def test_invalid_utf8_falls_back_to_python():
    """A value with invalid UTF-8 bytes must push the whole block to the
    per-row path (whose errors='replace' decode defines the output)."""
    import numpy as np
    from victorialogs_tpu import native
    if not native.available():
        pytest.skip("native lib unavailable")
    def one_value(buf):
        return native.emit_ndjson_native(
            [b'"k":'], [(0, buf, np.zeros(1, dtype=np.int64),
                         np.array([buf.size], dtype=np.int64))], 1)

    assert one_value(np.frombuffer(b"ok\xff\xfebad",
                                   dtype=np.uint8)) is None
    # incomplete multibyte tail is invalid too
    assert one_value(np.frombuffer("café".encode("utf-8")[:-1],
                                   dtype=np.uint8)) is None
    # lone surrogate halves (CESU-8) are rejected like Python's strict
    # decoder would replace them
    assert one_value(np.frombuffer(b"\xed\xa0\x80",
                                   dtype=np.uint8)) is None
    # sanity: the same helper emits a valid value fine
    ok = np.frombuffer("café".encode("utf-8"), dtype=np.uint8)
    assert one_value(ok) == '{"k":"café"}\n'.encode("utf-8")


def test_kill_switch_forces_python_path(storage, monkeypatch):
    monkeypatch.setenv("VL_NATIVE_EMIT", "0")
    assert not native_emit_enabled()
    calls = []
    import victorialogs_tpu.native as native_mod
    orig = native_mod.emit_ndjson_native

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)
    monkeypatch.setattr("victorialogs_tpu.engine.emit.emit_ndjson_native",
                        spy)
    _http_query(storage, "edge")
    assert calls == []


def test_tail_sink_sorts_by_true_timestamp(storage, monkeypatch):
    """handle_tail's columnar (int64-ns, line) sort: same line set as
    the dict path, ordered by TRUE timestamp — which fixes the old
    lexical sort's sub-second misordering ("..00.5Z" < "..00Z"
    byte-wise; the fixture's uneven fractions hit that case)."""
    from victorialogs_tpu.engine.block_result import parse_rfc3339
    monkeypatch.setenv("VL_NATIVE_EMIT", "1")
    blocks = []
    run_query(storage, [TEN], "edge", write_block=blocks.append,
              timestamp=T0)
    pairs = []
    for br in blocks:
        lines = ndjson_block(br).split(b"\n")[:br.nrows]
        ts = br.timestamps_np() if "_time" in br.column_names() else None
        keys = ts.tolist() if ts is not None else [0] * br.nrows
        pairs.extend(zip(keys, lines))
    pairs.sort(key=lambda kv: kv[0])
    got = [ln for _k, ln in pairs]
    rows = run_query_collect(storage, [TEN], "edge", timestamp=T0)
    rows.sort(key=lambda r: parse_rfc3339(r.get("_time", "")) or 0)
    want = [json.dumps(r, ensure_ascii=False,
                       separators=(",", ":")).encode("utf-8")
            for r in rows]
    assert got == want
    # the fixture really exercises the lexical-vs-numeric divergence
    lex = sorted((r.get("_time", "") for r in rows))
    num = [r.get("_time", "") for r in
           sorted(rows, key=lambda r: parse_rfc3339(r["_time"]) or 0)]
    assert lex != num, "fixture no longer covers the sub-second case"


def _tail_keys(br):
    """Mirror of handle_tail's sink sort-key selection."""
    names = br.column_names()
    if "_time" not in names:
        return [0] * br.nrows
    if br._bs is not None and br.timestamps_np() is not None:
        return br.timestamps_np().tolist()
    return [parse_rfc3339(v) or 0 for v in br.column("_time")]


def test_tail_sort_follows_displayed_time(storage):
    """When a live-tailable pipe REWRITES _time (copy), the tail sort
    key must follow the displayed value, not the original ingestion
    timestamps the materialized block still carries."""
    from victorialogs_tpu.engine.block_result import parse_rfc3339
    q = "edge | copy iso as _time"
    keyed = []

    def sink(br):
        vals = br.column("_time")
        keyed.extend(zip(_tail_keys(br), vals))
    run_query(storage, [TEN], q, write_block=sink, timestamp=T0)
    assert keyed
    for k, v in keyed:
        assert k == parse_rfc3339(v), \
            "sort key diverged from the displayed _time"
    # and the fixture makes displayed order differ from ingestion order
    disp = [k for k, _v in keyed]
    assert disp != sorted(disp)
