"""Async multi-part device pipeline (tpu/pipeline.py): bit-exact parity
under every window/packing config, the observability counters, and clean
draining on cancellation and deadline expiry while dispatches are in
flight."""

import time

import pytest

from victorialogs_tpu.engine.searcher import (QueryTimeoutError, run_query,
                                              run_query_collect)
from victorialogs_tpu.storage.log_rows import LogRows, TenantID
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu.batch import BatchRunner

NS = 1_000_000_000
T0 = 1_753_660_800_000_000_000  # 2025-07-28T00:00:00Z
TEN = TenantID(0, 0)
N_PARTS = 12                    # < datadb.DEFAULT_PARTS_TO_MERGE (15)
ROWS_PER_PART = 700


@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    """Many SMALL parts in one partition — the LSM shape the packing
    path exists for (each flush cycle becomes one file part)."""
    path = str(tmp_path_factory.mktemp("pipestore"))
    s = Storage(path, retention_days=100000, flush_interval=3600)
    n = 0
    for _pp in range(N_PARTS):
        lr = LogRows(stream_fields=["app"])
        for _i in range(ROWS_PER_PART):
            g = n
            n += 1
            msg = (f"GET /api/x{g % 7} "
                   f"{'error' if g % 3 == 0 else 'ok'} d={g % 97}")
            if g % 53 == 0:
                # newline between pair-regex literals: maybe rows that
                # must ride the residue channel through the window
                msg = f"GET /api\nlate tail {g}"
            lr.add(TEN, T0 + g * 50_000_000, [
                ("app", f"app{g % 4}"),
                ("_msg", msg),
                ("lvl", ["info", "warn", "error"][g % 3]),
                ("dur", str(g % 251)),
            ])
        s.must_add_rows(lr)
        s.debug_flush()
    parts = [p for pt in s.partitions.values()
             for p in pt.ddb.snapshot_parts() if p.num_rows]
    assert len(parts) >= N_PARTS
    yield s
    s.close()


ROW_QUERIES = [
    'error | fields _time',
    '"GET" ok | fields _time',
    '_msg:~"GET.*tail" | fields _time',          # maybe rows -> residue
    'lvl:error dur:>100 | fields _time, dur',
    '{app="app1"} error | fields _time',
    'NOT ok | fields _time',
    'nosuchtoken77 | fields _time',              # bloom/aggregate kills
]
STATS_QUERIES = [
    'error | stats count() c',
    '* | stats by (app) count() c, sum(dur) s, min(dur) mn, max(dur) mx',
    '* | stats by (_time:1m) count() c',
    '"GET" | stats count_uniq(lvl) u, avg(dur) a',
    'dur:>200 | stats by (lvl) count() c',
    '_msg:~"GET.*tail" | stats count() c',       # residue partials
]
SORT_QUERIES = [
    'error | sort by (dur desc) limit 7 | fields dur, app',
]


def _norm(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


@pytest.mark.parametrize("inflight,pack",
                         [("1", "1"), ("4", "1"), ("1", "8"), ("4", "8")])
def test_pipeline_parity_matrix(storage, monkeypatch, inflight, pack):
    """The acceptance matrix: serial window, deep window, packing off/on
    — every config must be bit-identical to the CPU executor."""
    monkeypatch.setenv("VL_INFLIGHT", inflight)
    monkeypatch.setenv("VL_PACK_PARTS", pack)
    runner = BatchRunner()
    for qs in ROW_QUERIES + STATS_QUERIES + SORT_QUERIES:
        cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
        dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                runner=runner)
        assert _norm(cpu) == _norm(dev), (qs, inflight, pack)
    if pack == "1":
        assert runner.packed_dispatches == 0
    else:
        assert runner.packed_dispatches > 0
        # parts packed per super-dispatch: >= 2 by construction
        assert runner.packed_parts >= 2 * runner.packed_dispatches


def test_row_order_matches_serial(storage, monkeypatch):
    """Downstream block order is part of the contract: harvested in
    submission order, the windowed/packed run must yield rows in the
    EXACT order of the serial walk (not just as a set)."""
    qs = 'error | fields _time, dur'
    monkeypatch.setenv("VL_INFLIGHT", "1")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    serial = run_query_collect(storage, [TEN], qs, timestamp=T0,
                               runner=BatchRunner())
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    windowed = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                 runner=BatchRunner())
    assert serial == windowed


def test_window_counters(storage, monkeypatch):
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = BatchRunner()
    run_query_collect(storage, [TEN], 'error | stats count() c',
                      timestamp=T0, runner=runner)
    st = runner.stats()
    assert st["pipeline_units"] >= N_PARTS
    assert st["inflight_hwm"] >= 4          # 12 units through a 4-window
    assert st["device_calls"] > 0           # dispatches issued
    assert st["host_sync_wait_s"] > 0
    assert st["staging_cache_entries"] > 0

    monkeypatch.setenv("VL_INFLIGHT", "1")
    r2 = BatchRunner()
    run_query_collect(storage, [TEN], 'error | stats count() c',
                      timestamp=T0, runner=r2)
    assert r2.inflight_hwm == 1             # serial window: one in flight


def test_inflight_auto_depth(storage, monkeypatch):
    """VL_INFLIGHT=auto: depth derives from the cost model's RTT/harvest
    EWMAs, clamps to [2, 16], results stay bit-identical, and the chosen
    depth is exposed as a counter."""
    from victorialogs_tpu.tpu import pipeline
    qs = 'error | fields _time, dur'
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = BatchRunner()
    want = run_query_collect(storage, [TEN], qs, timestamp=T0,
                             runner=runner)
    monkeypatch.setenv("VL_INFLIGHT", "auto")
    # cold runner: calibration empty -> default depth, still valid
    cold = BatchRunner()
    assert pipeline.inflight_depth(cold) == 4
    got = run_query_collect(storage, [TEN], qs, timestamp=T0,
                            runner=cold)
    assert got == want
    # warm: the first query fed the emit EWMA (wait-free host work ONLY
    # — folding in the device_sync wait would contract the depth toward
    # the clamp floor on high-RTT backends), so the derived depth is
    # the clamped rtt/emit ratio and the counter exposes it
    assert cold.cost.emit_ewma and cold.cost.emit_ewma > 0
    depth = pipeline.inflight_depth(cold)
    assert 2 <= depth <= 16
    got2 = run_query_collect(storage, [TEN], qs, timestamp=T0,
                             runner=cold)
    assert got2 == want
    assert 2 <= cold.stats()["inflight_auto_depth"] <= 16
    # explicit integer always wins over auto-derivation
    monkeypatch.setenv("VL_INFLIGHT", "3")
    assert pipeline.inflight_depth(cold) == 3


def test_packing_collapses_dispatches(storage, monkeypatch):
    """12 equal-sized small parts at VL_PACK_PARTS=8 -> 2 super-
    dispatches (8 + 4): >=4x fewer dispatches than the per-part walk,
    with identical stats output."""
    qs = '* | stats by (app) count() c, sum(dur) s'
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    serial = BatchRunner()
    cpu = run_query_collect(storage, [TEN], qs, timestamp=T0,
                            runner=serial)
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    packed = BatchRunner()
    dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                            runner=packed)
    assert _norm(cpu) == _norm(dev)
    assert serial.fused_dispatches >= N_PARTS
    assert packed.fused_dispatches <= (N_PARTS + 7) // 8 + 1
    assert serial.fused_dispatches >= 4 * packed.fused_dispatches
    assert packed.packed_parts == N_PARTS


def test_cancellation_drains_window(storage, monkeypatch):
    """`limit` fires head.is_done() while later units' dispatches are
    still in flight: the window must drain without writing their blocks
    and without unbalancing the StagingCache budget; the runner stays
    usable."""
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = BatchRunner()
    qs = 'error | fields _time | limit 3'
    cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
    dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                            runner=runner)
    assert _norm(cpu) == _norm(dev)
    assert runner.cache.check_balanced()
    # planning is lazy: the limit hit must stop the unit stream before
    # the whole partition's parts were planned/submitted
    assert runner.pipeline_units < N_PARTS
    qs2 = 'error | stats count() c'
    assert run_query_collect(storage, [TEN], qs2, timestamp=T0) == \
        run_query_collect(storage, [TEN], qs2, timestamp=T0,
                          runner=runner)


def test_deadline_expiry_drains_window(storage, monkeypatch):
    """Deadline passes while units are in flight (the second submit is
    artificially slowed past it): QueryTimeoutError must surface, NO
    partial block may reach the sink, the cache budget stays balanced
    and the runner survives."""
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
    assert calls["n"] >= 2              # dispatches really were in flight
    assert sunk == []                   # no partial blocks downstream
    assert runner.cache.check_balanced()
    monkeypatch.setattr(BatchRunner, "run_part_stats_submit", orig)
    qs = 'error | stats count() c'
    assert run_query_collect(storage, [TEN], qs, timestamp=T0) == \
        run_query_collect(storage, [TEN], qs, timestamp=T0,
                          runner=runner)


def test_pack_declines_fall_back_per_member(storage, monkeypatch):
    """A leaf the fused planner cannot express (eq_field) must decline
    the pack and ride the serial per-member path — identical results,
    no packed dispatch."""
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    runner = BatchRunner()
    qs = 'lvl:eq_field(app) | stats count() c'
    cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
    dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                            runner=runner)
    assert _norm(cpu) == _norm(dev)
    assert runner.packed_dispatches == 0


def test_pipeline_mesh_runner(storage, monkeypatch):
    """The windowed/packed pipeline over the 8-device CPU mesh: packed
    super-dispatches run SPMD (shard_map filter + psum stats) with the
    same bit-exact results."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    from victorialogs_tpu.parallel.distributed import MeshBatchRunner
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    runner = MeshBatchRunner()
    for qs in ['error | stats by (app) count() c, sum(dur) s',
               'error | fields _time',
               '_msg:~"GET.*tail" | stats count() c']:
        cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
        dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                runner=runner)
        assert _norm(cpu) == _norm(dev), qs
    assert runner.packed_dispatches > 0
    assert runner.inflight_hwm >= 1


# ---- prefetch only what is not resident (BatchRunner.submit_prefetch) ----

# the adhoc_scan shapes (phrase count over a time window, its chart, an
# `A.*B` regex, count_uniq over every row) and a sort-topk
PREFETCH_SHAPES = {
    "count": '_time:[2025-07-28T00:00:10Z, 2025-07-28T00:06:30Z) "GET" '
             '| stats count() c',
    "chart": '_time:[2025-07-28T00:00:10Z, 2025-07-28T00:06:30Z) "GET" '
             '| stats by (_time:5m) count() c',
    "pair": '_msg:~"GET.*tail" | stats count() c',
    "count_uniq": '* | stats count() c, count_uniq(_stream_id) u',
    "sort": 'error | sort by (dur desc) limit 7 | fields dur, app',
}
PREFETCH_QUERIES = list(PREFETCH_SHAPES.values()) + [
    '* | stats by (app) count() c, sum(dur) s',
    '"GET" ok | fields _time',
]


def _drain_prefetch(runner) -> None:
    """Wait for every queued prefetch: the pool has one worker, FIFO."""
    runner._prefetcher().submit(lambda: None).result()


def _spy_prefetch(runner, monkeypatch) -> tuple[list, list]:
    """(parts handed to submit_prefetch, parts _prefetch_work ran for)."""
    submitted, worked = [], []
    submit, work = runner.submit_prefetch, runner._prefetch_work

    def spy_submit(part, *a, **kw):
        submitted.append(part.uid)
        return submit(part, *a, **kw)

    def spy_work(part, *a, **kw):
        worked.append(part.uid)
        return work(part, *a, **kw)

    monkeypatch.setattr(runner, "submit_prefetch", spy_submit)
    monkeypatch.setattr(runner, "_prefetch_work", spy_work)
    return submitted, worked


def _round(storage, runner, queries) -> None:
    for qs in queries:
        cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
        dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                runner=runner)
        assert _norm(cpu) == _norm(dev), qs
    _drain_prefetch(runner)


def test_cold_runner_prefetches(storage, monkeypatch):
    """A fresh runner queues every lookahead unit's prefetch and stages
    through it: nothing is resident, so the counter stays 0."""
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = BatchRunner()
    submitted, worked = _spy_prefetch(runner, monkeypatch)
    _round(storage, runner, PREFETCH_QUERIES[:1])
    assert submitted and sorted(worked) == sorted(submitted)
    assert runner.prefetch_resident_units == 0
    assert runner.stats()["prefetch_resident_units"] == 0
    assert runner.cache.stats()["entries"] > 0


@pytest.mark.parametrize("pack", ["1", "8"])
def test_warm_runner_queues_no_prefetch(storage, monkeypatch, pack):
    """A second round of the same shapes on a warm runner finds every
    key a unit's prefetch could stage in the cache: _prefetch_work never
    runs, each skipped unit bumps prefetch_resident_units, and the
    answers stay the host executor's."""
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", pack)
    runner = BatchRunner()
    _round(storage, runner, PREFETCH_QUERIES)
    submitted, worked = _spy_prefetch(runner, monkeypatch)
    resident0 = runner.prefetch_resident_units
    _round(storage, runner, PREFETCH_QUERIES)
    assert submitted
    assert worked == []
    assert runner.prefetch_resident_units - resident0 == len(submitted)


def test_dropped_key_prefetches_again(storage, monkeypatch):
    """One `#fl` entry gone from a warm cache (evicted): that part's
    unit, and only it, queues its prefetch again, which stages the key
    back."""
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = BatchRunner()
    qs = PREFETCH_SHAPES["pair"]
    _round(storage, runner, [qs])
    submitted, worked = _spy_prefetch(runner, monkeypatch)
    _round(storage, runner, [qs])
    assert submitted and worked == []
    uid = submitted[-1]
    key = (uid, "#fl", "_msg")
    cache = runner.cache
    with cache._mu:                       # an eviction, as the LRU does it
        cache._bytes -= cache._cost(cache._lru.pop(key))
    resident0 = runner.prefetch_resident_units
    submitted.clear()
    _round(storage, runner, [qs])
    assert worked == [uid]
    assert runner.prefetch_resident_units - resident0 == \
        len(submitted) - 1
    assert cache.contains(key)


# the staging-key kinds each shape's unit needs: layout, timestamp
# planes, scan column, time buckets, sort score column
PREFETCH_KINDS = {
    "count": {"#layout", "#ts2", "#fl"},
    "chart": {"#layout", "#ts2", "#fl", "#tb"},
    "pair": {"#layout", "#fl"},
    "count_uniq": {"#layout"},
    "sort": {"#layout", "#num", "#fl"},
}


@pytest.mark.parametrize("shape", list(PREFETCH_SHAPES))
def test_prefetch_keys_are_what_prefetch_stages(storage, monkeypatch,
                                                shape):
    """The key list the residency check reads is exactly the set of keys
    _prefetch_work puts into the cache of a cold part, of the kinds the
    shape needs, and each one is a key the device dispatch reads."""
    from victorialogs_tpu.logsql.parser import parse_query
    from victorialogs_tpu.tpu.sort_device import device_sort_spec
    from victorialogs_tpu.tpu.stats_device import device_stats_spec

    q = parse_query(PREFETCH_SHAPES[shape], timestamp=T0)
    spec = device_stats_spec(q)
    sort = device_sort_spec(q)
    sort_field = sort.field if sort is not None else None
    assert (spec is None) == (shape == "sort")
    part = next(p for pt in storage.partitions.values()
                for p in pt.ddb.snapshot_parts() if p.num_rows)
    runner = BatchRunner()
    runner._prefetch_work(part, q.filter, spec, None, sort_field)
    keys = [k for k, _plan in
            runner._prefetch_keys(part, q.filter, spec, sort_field)]
    assert set(runner.cache._lru) == set(keys)
    assert {k[1] for k in keys} == PREFETCH_KINDS[shape]

    # a runner that never prefetches: what the dispatches stage alone
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    cold = BatchRunner()
    monkeypatch.setattr(cold, "submit_prefetch", lambda *a, **kw: None)
    run_query_collect(storage, [TEN], PREFETCH_SHAPES[shape],
                      timestamp=T0, runner=cold)
    assert set(keys) <= set(cold.cache._lru)
