"""Cross-partition pipeline window (PR 15): one dispatch window across
day partitions, packed sort-topk, segment-major packed stats.

Pins the three tentpole behaviors against the serial CPU walk:
- parity matrix (packed/serial x mesh runner)
  for sort-topk and wide (>=64 groups) group-by over a 3-day fixture,
  row order and hit sets bit-identical;
- the in-flight window survives partition boundaries (inflight_hwm
  reaches VL_INFLIGHT on a 3-partition run, more units than any one
  day holds);
- packed sort-topk dispatches engage (counter) and packed wide
  group-bys stop widening the bucket one-hot by pack size;
- cancellation mid-partition drains the window with zero downstream
  writes;
- VL_FILTER_INDEX_REBUILD rebuilds pre-v2 sidecars at part-open.
"""

import time

import pytest

from victorialogs_tpu.engine.searcher import run_query, run_query_collect
from victorialogs_tpu.storage.log_rows import LogRows, TenantID
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu.batch import BatchRunner

NS_DAY = 86_400 * 1_000_000_000
T0 = 1_753_660_800_000_000_000  # 2025-07-28T00:00:00Z
TEN = TenantID(0, 0)
N_DAYS = 3
PARTS_PER_DAY = 4               # 12 parts total, < DEFAULT_PARTS_TO_MERGE
ROWS_PER_PART = 420


@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    """Three day-partitions of flush-sized parts — the shape whose
    boundaries drained the PR 3 window on every day rollover."""
    path = str(tmp_path_factory.mktemp("crosspart"))
    s = Storage(path, retention_days=100000, flush_interval=3600)
    n = 0
    for day in range(N_DAYS):
        for _pp in range(PARTS_PER_DAY):
            lr = LogRows(stream_fields=["app"])
            for _i in range(ROWS_PER_PART):
                g = n
                n += 1
                lr.add(TEN, T0 + day * NS_DAY + (g % 600) * 50_000_000, [
                    ("app", f"app{g % 4}"),
                    ("_msg", f"m {'err' if g % 3 == 0 else 'ok'} "
                             f"x{g % 97} of {g}"),
                    ("lvl", ["info", "warn", "err"][g % 3]),
                    ("dur", str(g % 251)),
                ])
            s.must_add_rows(lr)
            s.debug_flush()
    assert len(s.partitions) == N_DAYS
    yield s
    s.close()


# sort-topk + wide group-by (251 numeric buckets >= 64 groups) are THE
# two shapes this PR brings into the packed path; the row/stats shapes
# ride along as regression cover
MATRIX_QUERIES = [
    'err | sort by (dur desc) limit 7 | fields dur, app',
    # limits sit on tie-group boundaries (7 err rows per dur value):
    # WHICH rows of a split tie group make the cut is arrival order,
    # and the host path scans day partitions on concurrent threads
    'err | sort by (dur) limit 14 | fields dur, app, _time',
    '* | stats by (dur:1) count() c, sum(dur) s, min(dur) mn, '
    'max(dur) mx',
    '"err" | stats by (dur:1) count() c',
    'err | fields _time, dur',
    '* | stats by (_time:1h) count() c',
]


def _norm(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


@pytest.mark.parametrize("pack", ["1", "8"])
def test_parity_matrix(storage, monkeypatch, pack):
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", pack)
    runner = BatchRunner()
    for qs in MATRIX_QUERIES:
        cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
        dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                runner=runner)
        assert _norm(cpu) == _norm(dev), (qs, pack)
    if pack != "1":
        assert runner.packed_dispatches > 0
        # packs really crossed a day boundary (consecutive parts of
        # adjacent partitions share the 1024-row pad bucket)
        assert runner.cross_partition_packs > 0


def test_parity_matrix_mesh(storage, monkeypatch):
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    from victorialogs_tpu.parallel.distributed import MeshBatchRunner
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    runner = MeshBatchRunner()
    for qs in MATRIX_QUERIES[:4]:
        cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
        dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                runner=runner)
        assert _norm(cpu) == _norm(dev), qs
    assert runner.packed_dispatches > 0


def test_row_order_matches_serial_across_partitions(storage,
                                                    monkeypatch):
    """Downstream block order across the 3-day walk is part of the
    contract: the global window must yield rows in the exact order of
    the serial walk (not just as a set): of the window at depth one
    without packing, and of the host executor on one thread."""
    qs = 'err | fields _time, dur'
    monkeypatch.setenv("VL_INFLIGHT", "1")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    serial = run_query_collect(storage, [TEN], qs, timestamp=T0,
                               runner=BatchRunner())
    # concurrency=1: the host executor otherwise scans day partitions
    # on concurrent threads, which is not a serial walk
    host = run_query_collect(storage, [TEN],
                             "options(concurrency=1) " + qs,
                             timestamp=T0)
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    windowed = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                 runner=BatchRunner())
    assert serial == windowed == host


def test_window_depth_survives_partition_boundary(storage, monkeypatch):
    """The window does not drain at a partition boundary: a day holds
    PARTS_PER_DAY/2 = 2 units here, so a window that fills to
    VL_INFLIGHT = 4 holds units of more than one day at once."""
    qs = 'err | stats count() c'
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "2")   # 2 units per partition
    globed = BatchRunner()
    run_query_collect(storage, [TEN], qs, timestamp=T0, runner=globed)
    # 6 units through a 4-window: the window FILLS to VL_INFLIGHT
    assert globed.inflight_hwm == 4 > PARTS_PER_DAY // 2
    assert _norm(run_query_collect(storage, [TEN], qs, timestamp=T0,
                                   runner=globed)) == \
        _norm(run_query_collect(storage, [TEN], qs, timestamp=T0))


def test_packed_topk_counter_and_cap(storage, monkeypatch):
    """Flush-sized parts under `sort | head` pack: counter-asserted;
    VL_PACK_TOPK_K=0 restores per-part topk dispatches."""
    qs = 'err | sort by (dur desc) limit 7 | fields dur'
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    runner = BatchRunner()
    want = run_query_collect(storage, [TEN], qs, timestamp=T0)
    got = run_query_collect(storage, [TEN], qs, timestamp=T0,
                            runner=runner)
    assert _norm(want) == _norm(got)
    assert runner.packed_topk_dispatches > 0
    assert runner.topk_dispatches == runner.packed_topk_dispatches
    # the cap: k above VL_PACK_TOPK_K declines packing, results equal
    monkeypatch.setenv("VL_PACK_TOPK_K", "0")
    r2 = BatchRunner()
    got2 = run_query_collect(storage, [TEN], qs, timestamp=T0,
                             runner=r2)
    assert _norm(got2) == _norm(want)
    assert r2.packed_topk_dispatches == 0
    assert r2.topk_dispatches > 0


def test_wide_groupby_onehot_width_not_widened(storage, monkeypatch):
    """The segment-major stats kernel keeps the bucket one-hot at the
    BASE group count: a 251-group packed group-by must report the same
    stats_onehot_width as the serial walk, with fewer dispatches and
    bit-identical results."""
    qs = '* | stats by (dur:1) count() c, sum(dur) s'
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    serial = BatchRunner()
    a = run_query_collect(storage, [TEN], qs, timestamp=T0,
                          runner=serial)
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    packed = BatchRunner()
    b = run_query_collect(storage, [TEN], qs, timestamp=T0,
                          runner=packed)
    assert _norm(a) == _norm(b)
    w_serial = serial.stats()["stats_onehot_width"]
    w_packed = packed.stats()["stats_onehot_width"]
    assert w_serial == 251
    assert w_packed == w_serial          # NOT 251 * pack size
    assert packed.fused_dispatches < serial.fused_dispatches


def test_cancellation_mid_partition_drains(storage, monkeypatch):
    """A `limit` hit inside partition 1 must stop the cross-partition
    header walk there (later partitions' parts never plan), drain the
    in-flight window with zero downstream writes after the cut, and
    leave the staging cache balanced."""
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = BatchRunner()
    qs = 'err | fields _time | limit 3'
    # `limit` without `sort` takes the first rows to ARRIVE: only the
    # serial host walk (concurrency=1) has the window's arrival order
    cpu = run_query_collect(storage, [TEN],
                            "options(concurrency=1) " + qs, timestamp=T0)
    dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                            runner=runner)
    assert _norm(cpu) == _norm(dev)
    assert runner.cache.check_balanced()
    # lazy planning stopped the walk before all 12 parts became units
    assert runner.pipeline_units < N_DAYS * PARTS_PER_DAY
    # the runner stays usable afterwards
    qs2 = 'err | stats count() c'
    assert _norm(run_query_collect(storage, [TEN], qs2, timestamp=T0,
                                   runner=runner)) == \
        _norm(run_query_collect(storage, [TEN], qs2, timestamp=T0))


def test_deadline_mid_stream_no_partial_writes(storage, monkeypatch):
    """Deadline expiry while cross-partition units are in flight: the
    error surfaces, nothing is written downstream, budgets balance."""
    from victorialogs_tpu.engine.searcher import QueryTimeoutError
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    # compile the query's program first (another runner): in a process
    # that has not compiled it, the first submit alone outlasts the
    # deadline and no second dispatch is ever in flight
    run_query_collect(storage, [TEN], "* | stats count() c",
                      timestamp=T0, runner=BatchRunner())
    runner = BatchRunner()
    orig = BatchRunner.run_part_stats_submit
    calls = {"n": 0}

    def slow(self, *a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:
            time.sleep(0.3)
        return orig(self, *a, **kw)

    monkeypatch.setattr(BatchRunner, "run_part_stats_submit", slow)
    sunk = []
    with pytest.raises(QueryTimeoutError):
        run_query(storage, [TEN], "* | stats count() c",
                  write_block=sunk.append, timestamp=T0, runner=runner,
                  deadline=time.monotonic() + 0.15)
    assert calls["n"] >= 2
    assert sunk == []
    assert runner.cache.check_balanced()


def test_explain_units_span_partitions(storage, monkeypatch):
    """EXPLAIN prices the cross-partition units the window dispatches:
    global seqs, packs whose members span partitions, analyze grafts
    per-unit actuals from the global span numbering."""
    from victorialogs_tpu.logsql.parser import parse_query
    from victorialogs_tpu.obs import explain
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    runner = BatchRunner()
    q = parse_query('err | fields _time', T0)
    tree = explain.build_plan(storage, [TEN], q, runner=runner)
    units = [u for pt in tree["partitions"] for u in pt["units"]]
    assert units
    seqs = [u["seq"] for u in units]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    all_parts = {p["part"] for pt in tree["partitions"]
                 for p in pt["parts"] if p["status"] == "retained"}
    # some planned pack holds members from more than one partition
    by_partition = [{p["part"] for p in pt["parts"]}
                    for pt in tree["partitions"]]
    crossing = [
        u for u in units
        if len({i for i, ps in enumerate(by_partition)
                for m in u["members"] if m in ps}) > 1]
    assert crossing, units
    assert {m for u in units for m in u["members"]} == all_parts
    # analyze: executed dispatches match the plan and actuals graft
    explain.analyze(storage, [TEN], q, tree, runner=runner)
    assert tree["mode"] == "analyze"
    assert tree["actual"]["dispatches_submitted"] == len(units)
    assert any("actual" in u for u in units)


def test_filter_index_rebuild(tmp_path, monkeypatch):
    """VL_FILTER_INDEX_REBUILD=1: a part sealed WITHOUT a sidecar
    (pre-v2 deployment, pinned via VL_FILTER_INDEX=v1 at build time)
    gets filterindex.bin rebuilt in place at part-open, journalled
    with rebuilt=true, and the maplet path serves the next probe —
    results identical either way."""
    import glob

    from victorialogs_tpu.obs import events
    monkeypatch.setenv("VL_FILTER_INDEX", "v1")
    s = Storage(str(tmp_path), retention_days=100000,
                flush_interval=3600)
    try:
        lr = LogRows(stream_fields=["app"])
        for g in range(800):
            lr.add(TEN, T0 + g * 1_000_000, [
                ("app", f"app{g % 3}"),
                ("_msg", f"m {'alpha' if g % 2 else 'beta'} x{g % 7}")])
        s.must_add_rows(lr)
        s.debug_flush()
        assert not glob.glob(str(tmp_path) + "/**/filterindex.bin",
                             recursive=True)
        cpu = run_query_collect(s, [TEN], "alpha | fields _time",
                                timestamp=T0)

        monkeypatch.setenv("VL_FILTER_INDEX", "v2")
        monkeypatch.setenv("VL_FILTER_INDEX_REBUILD", "1")
        got = []

        def on_event(ts_ns, ev, fields):
            if ev == "filter_index_built":
                got.append(dict(fields))

        events.subscribe(on_event)
        try:
            runner = BatchRunner()
            dev = run_query_collect(s, [TEN], "alpha | fields _time",
                                    timestamp=T0, runner=runner)
            assert _norm(cpu) == _norm(dev)
            side = glob.glob(str(tmp_path) + "/**/filterindex.bin",
                             recursive=True)
            assert side and not glob.glob(
                str(tmp_path) + "/**/filterindex.bin.tmp",
                recursive=True)
            assert any(f.get("rebuilt") for f in got), got
            assert runner.maplet_probes > 0
        finally:
            events.unsubscribe(on_event)
    finally:
        s.close()
