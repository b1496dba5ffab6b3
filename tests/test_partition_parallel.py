"""Partition-parallel search: multi-day queries scan per-day partitions
concurrently (reference storage_search.go:1095-1126) with identical
results, and the batch runner's prefetcher overlaps staging with scans."""

import numpy as np
import pytest

from victorialogs_tpu.engine.searcher import run_query_collect
from victorialogs_tpu.storage.log_rows import LogRows, TenantID
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu.batch import BatchRunner

NS = 1_000_000_000
DAY = 86400 * NS
T0 = 1_753_660_800_000_000_000  # 2025-07-28T00:00:00Z
TEN = TenantID(0, 0)
N_DAYS = 5


@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("ppstore"))
    s = Storage(path, retention_days=100000, flush_interval=3600)
    for d in range(N_DAYS):
        lr = LogRows(stream_fields=["app"])
        for i in range(800):
            lr.add(TEN, T0 + d * DAY + i * NS, [
                ("app", f"app{i % 2}"),
                ("_msg", f"day{d} {'err' if i % 3 == 0 else 'ok'} n{i}"),
                ("dur", str((d * 800 + i) % 501)),
            ])
        s.must_add_rows(lr)
    s.debug_flush()
    yield s
    s.close()


QUERIES = [
    "err | stats count() c",
    "err | stats by (_time:1d) count() c, sum(dur) s",
    "* | stats min(dur) mn, max(dur) mx, avg(dur) a",
    "day2 | fields _time, _msg",
    'app:app1 _msg:~"err" | stats count() c',
]


def _norm(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


def test_multi_day_parallel_parity_cpu(storage):
    """Concurrent partition scans return the same results as the
    single-threaded scan (options(concurrency=1) forces sequential)."""
    for qs in QUERIES:
        par = run_query_collect(storage, [TEN], qs, timestamp=T0)
        seq = run_query_collect(storage, [TEN],
                                f"options(concurrency=1) {qs}",
                                timestamp=T0)
        assert _norm(par) == _norm(seq), qs


def test_multi_day_parallel_parity_device(storage):
    runner = BatchRunner()
    for qs in QUERIES:
        cpu = run_query_collect(storage, [TEN], qs, timestamp=T0)
        dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                                runner=runner)
        assert _norm(cpu) == _norm(dev), qs
    assert runner.device_calls > 0


def test_prefetch_stages_next_part(storage):
    """submit_prefetch stages the filter scan column and stats inputs so a
    later run_part* call is a pure cache hit."""
    import time

    from victorialogs_tpu.logsql.parser import parse_query
    from victorialogs_tpu.tpu.stats_device import device_stats_spec

    pts = storage.select_partitions(T0, T0 + N_DAYS * DAY)
    part = next(p for pt in pts for p in pt.ddb.snapshot_parts()
                if p.num_rows)
    q = parse_query("err | stats by (_time:1h) sum(dur) s", timestamp=T0)
    spec = device_stats_spec(q)
    assert spec is not None
    runner = BatchRunner()
    runner.submit_prefetch(part, q.filter, spec)
    runner._prefetch_pool.shutdown(wait=True)
    assert runner.cache.contains((part.uid, "#fl", "_msg"))
    assert runner.cache.contains((part.uid, "#num", "dur"))
    assert any(k[:2] == (part.uid, "#tb")
               for k in runner.cache._lru)


def test_partition_error_propagates(storage):
    """A deadline hit inside a partition worker surfaces as
    QueryTimeoutError (not swallowed by the thread pool)."""
    import time

    from victorialogs_tpu.engine.searcher import QueryTimeoutError

    with pytest.raises(QueryTimeoutError):
        run_query_collect(storage, [TEN], "* | stats count() c",
                          timestamp=T0,
                          deadline=time.monotonic() - 1)


def test_prefetch_respects_narrow_candidate_gate(tmp_path):
    """Prefetch must not stage a column the evaluator would scan on the
    host (narrow candidate fraction) — the staging cache stays empty."""
    from victorialogs_tpu.logsql.parser import parse_query

    s = Storage(str(tmp_path / "narrow"), retention_days=100000,
                flush_interval=3600)
    try:
        lr = LogRows(stream_fields=["app"])
        for i in range(3200):
            lr.add(TEN, T0 + i * NS, [("app", f"app{i % 16}"),
                                      ("_msg", f"err n{i}")])
        s.must_add_rows(lr)
        s.debug_flush()
        pt = s.select_partitions(T0, T0 + DAY)[0]
        part = next(p for p in pt.ddb.snapshot_parts()
                    if p.num_rows and p.num_blocks >= 16)
        q = parse_query("err", timestamp=T0)
        runner = BatchRunner()
        # one candidate block out of 16 => 1/16 of the rows: narrow
        runner.submit_prefetch(part, q.filter, None, cand_bis=[0])
        runner._prefetch_pool.shutdown(wait=True)
        assert not runner.cache.contains((part.uid, "_msg"))
    finally:
        s.close()
