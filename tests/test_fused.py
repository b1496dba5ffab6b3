"""Single-dispatch fused filter|stats path (tpu/fused.py) vs the CPU
executor: bit-exact over adversarial tree shapes, with the residue
(maybe-row) machinery explicitly exercised.

The fused path's contract: same rows, same group keys, same aggregates
as the host executor for every query it accepts — and clean fallback
(still correct) for everything it declines."""

import numpy as np
import pytest

from victorialogs_tpu.engine.searcher import run_query_collect
from victorialogs_tpu.storage.log_rows import LogRows, TenantID
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu.batch import BatchRunner

NS = 1_000_000_000
T0 = 1_753_660_800_000_000_000  # 2025-07-28T00:00:00Z
TEN = TenantID(0, 0)


@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("fused"))
    s = Storage(path, retention_days=100000, flush_interval=3600)
    lr = LogRows(stream_fields=["app"])
    words = ["deadline exceeded", "connection reset", "ok", "retry later",
             "cache miss", "flushed"]
    for i in range(9000):
        msg = f"GET /api/x{i % 71} {words[i % 6]} dur={i % 351}ms"
        if i % 37 == 0:
            # multibyte runes: len_range must route these through the
            # residue (code points != bytes)
            msg = f"GÉT /äpi/x{i % 71} {words[i % 6]} ⏱={i % 351}"
        if i % 97 == 0:
            # newline between the A..B literals: the ordered-pair scan
            # must route these rows through the host residue pass
            msg = f"GET /api\nlate {words[i % 6]} tail"
        fields = [
            ("app", f"app{i % 4}"),
            ("_msg", msg),
            ("lvl", ["info", "warn", "error"][i % 3]),   # dict column
            ("dur", str(i % 351)),                        # uint column
        ]
        lr.add(TEN, T0 + i * 200_000_000, fields)
    s.must_add_rows(lr)
    s.debug_flush()
    yield s
    s.close()


FUSED_QUERIES = [
    # plain scans, and/or/not trees
    '"deadline exceeded" | stats count() c',
    '"deadline exceeded" OR "connection reset" | stats count() c',
    'NOT "ok" | stats count() c',
    '("retry later" OR "cache miss") "GET" | stats count() c',
    'NOT ("ok" OR "retry later") | stats by (_time:5m) count() c',
    # time filter composes on device (inclusive-bound semantics)
    '_time:[2025-07-28T00:05:00Z, 2025-07-28T00:20:00Z] "deadline '
    'exceeded" | stats count() c',
    '_time:[2025-07-28T00:00:00Z, 2025-07-28T00:10:00Z] | stats '
    'by (_time:1m) count() c',
    # prefix / exact / contains / substring-regex leaves
    '_msg:"GET"* | stats count() c',
    # numeric-typed column scanned as text: stage_layout_column declines,
    # the unfused path answers (still bit-identical)
    'dur:13* | stats count() c',
    'lvl:exact("error") | stats by (_time:10m) count() c',
    'lvl:contains_any("warn", "error") | stats count() c',
    '_msg:~"deadline" | stats count() c',
    # ordered-pair regex incl. newline rows -> host residue partials
    '_msg:~"GET.*exceeded" | stats count() c',
    '_msg:~"GET.*tail" | stats count() c',                # only \n rows
    '_msg:~"GET.*exceeded" | stats by (_time:5m, app) count() c',
    '_msg:~"GET.*exceeded" | stats by (app) sum(dur) s, min(dur) mn, '
    'max(dur) mx, count_uniq(lvl) u',
    # dict-column scans (materialized into the fused matrix)
    'lvl:error | stats by (app) count() c',
    'NOT lvl:error "deadline exceeded" | stats count() c',
    # stream filters fold to constants / mask leaves
    '{app="app1"} | stats count() c',
    '{app=~"app[12]"} "deadline exceeded" | stats by (_time:5m) count() c',
    # value-column stats + group-by + uniq through one dispatch
    '"GET" | stats by (app, _time:10m) count() c, sum(dur) s',
    '* | stats count_uniq(app) u, count() c',
    # numeric range on the int column (device compare over uint32 offsets)
    'dur:>300 | stats count() c',
    'dur:range[100, 200] | stats by (app) count() c',
    'dur:<=5 "deadline exceeded" | stats count() c',
    'dur:>10000 | stats count() c',                      # empty range
    'NOT dur:>=175 | stats by (_time:10m) count() c',
    # in() = OR of exact scans (dict + string columns)
    'lvl:in(error, warn) | stats count() c',
    'app:in(app1, app3) "deadline exceeded" | stats count() c',
    'lvl:in() | stats count() c',                         # empty set
    # len_range: byte lengths decide ASCII rows; multibyte rows in the
    # ambiguous byte window route through residue
    '_msg:len_range(10, 30) | stats count() c',
    'NOT _msg:len_range(0, 25) | stats by (app) count() c',
    # value_type: block-uniform constant from the column encoding
    'dur:value_type(uint16) | stats count() c',
    'NOT dur:value_type(uint16) | stats by (app) count() c',
    'lvl:value_type(dict) "deadline exceeded" | stats count() c',
    # empty-ish matches
    'nosuchliteral42 | stats count() c',
    '_msg:"" | stats count() c',
    # sum_len/count_empty: derived uint32 columns through the standard
    # sum partials (code points, not bytes — the GÉT/⏱ rows check that)
    '* | stats sum_len(_msg) s, count_empty(_msg) e',
    '"deadline exceeded" | stats by (app) sum_len(_msg) s, count() c',
    '* | stats by (_time:10m) count_empty(lvl) e, sum_len(lvl) s',
    'NOT "ok" | stats sum_len(dur) s',         # int column digit count
    '* | stats count_empty(nosuchfield) e, sum_len(nosuchfield) s',
    'dur:>100 | stats by (app) count_empty(app) e, sum_len(app) s',
    # case-insensitive phrase/prefix: ASCII byte fold on device, rows
    # with multibyte bytes settled by the host residue
    'i("DEADLINE Exceeded") | stats count() c',
    'i("CONNECTION reset") OR i("CACHE Miss") | stats by (app) count() c',
    '_msg:i("GeT"*) | stats count() c',
    'NOT i("OK") | stats count() c',
    'lvl:i("ERROR") | stats by (_time:10m) count() c',
]


def _norm(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


def test_fused_parity_and_engagement(storage):
    runner = BatchRunner()
    engaged = 0
    for qs in FUSED_QUERIES:
        cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
        before = runner.fused_dispatches
        dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                runner=runner)
        assert _norm(cpu) == _norm(dev), qs
        engaged += runner.fused_dispatches - before
    # most of the matrix must actually take the single-dispatch path
    assert engaged >= len(FUSED_QUERIES) // 2


def test_fused_residue_rows_are_settled(storage):
    """Newline rows flagged maybe by the pair kernel must contribute via
    the host residue: compare against CPU on a query whose ONLY hits are
    newline rows."""
    runner = BatchRunner()
    qs = '_msg:~"GET.*late" | stats count() c'
    cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
    before = runner.fused_dispatches
    dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                            runner=runner)
    assert runner.fused_dispatches > before
    assert cpu == dev
    assert int(cpu[0]["c"]) > 0  # the newline rows really match


def test_fused_declines_to_unfused_shapes(storage):
    """Non-fusable leaves (field-vs-field compare; non-ASCII any-case
    pattern) must fall back and still match the CPU executor."""
    runner = BatchRunner()
    for qs in ['lvl:eq_field(app) | stats count() c',
               'i("GÉT") | stats count() c']:
        cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
        before = runner.fused_dispatches
        dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                runner=runner)
        assert runner.fused_dispatches == before, qs
        assert _norm(cpu) == _norm(dev), qs


def test_fused_any_case_unicode_divergence(tmp_path):
    """U+212A (KELVIN SIGN) lowercases to ASCII 'k': the device byte fold
    cannot see that match, so the row must reach the host residue and
    still count.  Pure-ASCII mixed-case rows are decided on device."""
    s = Storage(str(tmp_path), retention_days=100000, flush_interval=3600)
    lr = LogRows(stream_fields=["app"])
    bodies = ["TEMP 30K outside", "temp 30K inside", "Temp 30k mid",
              "cool 20c none"] * 500
    for i, b in enumerate(bodies):
        lr.add(TEN, T0 + i * NS, [("app", "a"), ("_msg", b)])
    s.must_add_rows(lr)
    s.debug_flush()
    try:
        runner = BatchRunner()
        for qs in ['i("30K") | stats count() c',
                   'i("TEMP 30k") | stats count() c',
                   'i("temp"*) | stats count() c']:
            cpu = run_query_collect(s, [TEN], qs, timestamp=T0)
            dev = run_query_collect(s, [TEN], qs, timestamp=T0,
                                    runner=runner)
            assert _norm(cpu) == _norm(dev), qs
        assert int(cpu[0]["c"]) == 1500  # all three temp variants match
        assert runner.fused_dispatches > 0
    finally:
        s.close()


def test_fused_row_queries_unaffected(storage):
    """Queries with row output (no stats pipe) keep the ordinary path."""
    runner = BatchRunner()
    qs = '"deadline exceeded" | fields _msg, app | limit 5'
    cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
    dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                            runner=runner)
    assert runner.fused_dispatches == 0
    assert _norm(cpu) == _norm(dev)


def test_fused_topk_parity(storage):
    """Device sort-topk prefilter: `<filter> | sort by (f) limit N` must
    return the SAME rows in the SAME order as the CPU path — including
    ties at the k-th boundary (broken by arrival order on both engines)
    and maybe rows (pair-regex newlines) verified on host."""
    runner = BatchRunner()
    queries = [
        '"GET" | sort by (dur desc) limit 7 | fields dur, app',
        'lvl:error | sort by (dur) limit 5 | fields dur, lvl',
        '* | sort by (dur desc) offset 3 limit 4 | fields dur',
        'dur:>340 | sort by (dur) limit 1000 | fields dur',  # k > matches
        '_msg:~"GET.*exceeded" | sort by (dur desc) limit 5 | fields dur',
        '"deadline exceeded" | sort by (dur) limit 3 rank as r '
        '| fields dur, r',
        # heavy boundary ties: every dur value repeats across apps
        'app:in(app1, app2) | sort by (dur desc) limit 9 | fields dur, app',
    ]
    engaged = 0
    for qs in queries:
        cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
        before = runner.topk_dispatches
        dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                runner=runner)
        assert cpu == dev, qs          # exact rows, exact order
        engaged += runner.topk_dispatches - before
    assert engaged >= 5


def test_fused_topk_declines_cleanly(storage):
    """Shapes the topk prefilter must decline (string sort field,
    multi-field sort, partition_by) still match the CPU path through the
    ordinary device filter path."""
    runner = BatchRunner()
    for qs in ['* | sort by (lvl) limit 5 | fields lvl',
               '* | sort by (dur, app) limit 5 | fields dur, app',
               '* | sort by (dur) partition by (app) limit 2 '
               '| fields dur, app']:
        cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
        before = runner.topk_dispatches
        dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                runner=runner)
        assert runner.topk_dispatches == before, qs
        assert _norm(cpu) == _norm(dev), qs


@pytest.fixture(scope="module")
def multipart_storage(tmp_path_factory):
    """The FUSED_QUERIES corpus spread over several small parts, so the
    async pipeline's window and small-part packing engage."""
    import operand_cases as OC
    s = OC.make_storage(str(tmp_path_factory.mktemp("fusedmp")))
    yield s
    s.close()


@pytest.mark.parametrize("inflight,pack",
                         [("1", "1"), ("4", "1"), ("1", "8"), ("4", "8")])
def test_fused_parity_windowed_and_packed(multipart_storage, monkeypatch,
                                          inflight, pack):
    """The fused parity matrix re-run through the async pipeline over
    MANY small parts, at every window/packing config (tpu/pipeline.py):
    window depth and super-dispatch packing must be invisible in the
    results — residue rows, dict axes and value stats included."""
    monkeypatch.setenv("VL_INFLIGHT", inflight)
    monkeypatch.setenv("VL_PACK_PARTS", pack)
    runner = BatchRunner()
    for qs in FUSED_QUERIES[::3]:   # every 3rd query: runtime-bounded
        cpu = run_query_collect(multipart_storage, [TEN], qs,
                                timestamp=T0)
        dev = run_query_collect(multipart_storage, [TEN], qs,
                                timestamp=T0, runner=runner)
        assert _norm(cpu) == _norm(dev), (qs, inflight, pack)
    if pack != "1":
        assert runner.packed_dispatches > 0


def test_fused_truncation_overflow(tmp_path):
    """Values beyond MAX_ROW_WIDTH are truncated in staging; phrases
    hitting the truncated tail must be settled by the residue pass."""
    from victorialogs_tpu.tpu.layout import MAX_ROW_WIDTH
    s = Storage(str(tmp_path), retention_days=100000, flush_interval=3600)
    lr = LogRows(stream_fields=["app"])
    for i in range(4000):
        body = "x" * (MAX_ROW_WIDTH + 50) + " needle77" if i % 11 == 0 \
            else f"short {i}"
        lr.add(TEN, T0 + i * NS, [("app", "a"), ("_msg", body)])
    s.must_add_rows(lr)
    s.debug_flush()
    try:
        runner = BatchRunner()
        for qs in ['needle77 | stats count() c',
                   '"x" OR needle77 | stats by (_time:10m) count() c']:
            cpu = run_query_collect(s, [TEN], qs, timestamp=T0)
            dev = run_query_collect(s, [TEN], qs, timestamp=T0,
                                    runner=runner)
            assert _norm(cpu) == _norm(dev), qs
        assert int(cpu[0]["c"]) > 0
    finally:
        s.close()


# ---------------- the operand block (one host operand a dispatch) ------

def _operand_cases():
    import operand_cases as OC
    return pytest.mark.parametrize("case", OC.CASES, ids=OC.CASE_IDS)


@_operand_cases()
def test_operand_block_every_leaf_kind(multipart_storage, monkeypatch,
                                        case):
    """Every leaf kind that carries a host operand (time and range
    bounds, scan / pair / in patterns), a packed super-dispatch, a topk
    and a row-filter dispatch: answers bit-identical to the host path,
    the jitted call's operands hold exactly one leaf that is not a
    jax.Array (the int32 block), and `operand_blocks` grows by the
    `device_calls` delta."""
    import operand_cases as OC
    OC.check_case(multipart_storage, BatchRunner(), monkeypatch, case)


def test_operand_block_layout():
    """The block's own contract: word 0 is the live row count, offsets
    follow registration order, a byte string rides four bytes a
    little-endian word, a uint32 bound keeps its bits, and the length
    is a power of two (32 words at least) whatever the literals are."""
    from types import SimpleNamespace
    from victorialogs_tpu.tpu import fused
    pl = fused._Planner(None, None, {}, SimpleNamespace(nrows=1234))
    assert pl.host_words(7, -3) == 1
    assert pl.host_bytes(b"deadline exceeded") == 3       # 17 B: 5 words
    assert pl.host_words((1 << 32) - 1, 1 << 31) == 8
    blk = pl.block()
    assert blk.dtype == np.int32 and blk.shape == (32,)
    assert blk[fused.BLOCK_NROWS] == 1234
    assert list(blk[1:3]) == [7, -3]
    assert blk[3:8].tobytes()[:17] == b"deadline exceeded"
    assert blk[3:8].tobytes()[17:] == b"\0\0\0"
    assert list(blk[8:10].view(np.uint32)) == [(1 << 32) - 1, 1 << 31]
    assert not blk[10:].any()
    # what the program reads back (_block_bytes) is the pattern
    import jax
    got = jax.jit(lambda b: fused._block_bytes(b, 3, 17))(blk)
    assert bytes(np.asarray(got)) == b"deadline exceeded"
    # 33 words registered: the next bucket
    pl.host_bytes(b"x" * 92)
    assert pl.block().shape == (64,)


# the five classes of the benchmark's adhoc_scan cell
# (benchmark/traffic/adhoc_scan.json), with two literals of the same
# length and two time windows each
def _adhoc_scan_queries(phrase, tail, t0, t1):
    w = f"_time:[2025-07-28T00:{t0}:00Z, 2025-07-28T00:{t1}:00Z)"
    return [f'{w} "{phrase}" | stats count() c',
            f'{w} "{phrase}" | stats by (_time:5m) count() c',
            f'_msg:~"dead.*{tail}" | stats count() c',
            "* | stats count() c, count_uniq(_stream_id) u"]


# distinct (program name, static key, operand shapes) the five classes
# need over one part, counted on the parent tree (commit 9c190cc, where
# every scalar and pattern was an operand of its own): the block must
# not add one
PARENT_ADHOC_SCAN_PROGRAMS = 4


def test_operand_block_adds_no_program(storage, monkeypatch):
    """Other literals of the same lengths and other time windows run
    the programs the first round compiled (`vl_tpu_jit_compiles_total`
    does not move), and the benchmark's five adhoc_scan classes over
    one part need as many programs as on the parent."""
    import jax
    import operand_cases as OC
    from victorialogs_tpu.tpu import compile_stats
    runner = BatchRunner()
    seen = OC.record_launches(monkeypatch)

    def run(queries):
        for qs in queries:
            cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
            dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                    runner=runner)
            assert _norm(cpu) == _norm(dev), qs

    extra = ["dur:range[100, 200] | stats count() c",
             "_msg:len_range(10, 30) | stats count() c",
             "lvl:in(error, warn) | stats count() c",
             '"GET" | sort by (dur desc) limit 7 | fields dur',
             '"deadline exceeded" dur:>300 | fields _msg, app']
    run(_adhoc_scan_queries("deadline exceeded", "exceeded", "02", "17")
        + extra)
    first = len(seen)
    assert first >= 9

    def keys():
        return {tuple(a if not hasattr(a, "shape")
                      else (a.shape, str(a.dtype))
                      for a in jax.tree_util.tree_leaves(
                          args, is_leaf=lambda x: x is None))
                for args in seen}

    programs = keys()
    adhoc = {k for k in programs if k[0].startswith("fused_")
             and not k[0].startswith(("fused_numrange", "fused_lenrange",
                                      "fused_exact"))}
    assert len(adhoc) == PARENT_ADHOC_SCAN_PROGRAMS, sorted(
        k[0] for k in adhoc)
    compiles = compile_stats()["jit_compiles_total"]
    other = ["dur:range[150, 340] | stats count() c",
             "_msg:len_range(20, 60) | stats count() c",
             "lvl:in(trace, info) | stats count() c",
             '"POS" | sort by (dur desc) limit 7 | fields dur',
             '"connection reseted" dur:>200 | fields _msg, app']
    run(_adhoc_scan_queries("deadline extended", "extended", "05", "30")
        + other)
    assert len(seen) > first
    assert keys() == programs
    assert compile_stats()["jit_compiles_total"] == compiles
