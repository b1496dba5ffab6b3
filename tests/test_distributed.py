"""Multi-device mesh tests: the psum/shard_map stats path on the virtual
8-device CPU world the conftest provisions.

These exercise exactly what the driver's dryrun_multichip validates
(reference analogue: the remote/local stats split merged over the wire —
lib/logstorage/net_query_runner.go:67-96, pipe_stats.go:111-119 — mapped to
ICI psum in parallel/distributed.py)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from victorialogs_tpu.parallel.distributed import (  # noqa: E402
    distributed_scan_count, make_mesh, shard_batch, stage_block_batch)
from victorialogs_tpu.tpu import kernels as K  # noqa: E402


def _blocks(n_blocks, nrows=32, hit_every=4):
    out = []
    for b in range(n_blocks):
        vals = []
        for i in range(nrows):
            if i % hit_every == 0:
                vals.append(f"blk{b} error code={i}".encode())
            else:
                vals.append(f"blk{b} ok code={i}".encode())
        lengths = np.array([len(v) for v in vals], dtype=np.int64)
        offsets = np.zeros(nrows, dtype=np.int64)
        np.cumsum(lengths[:-1], out=offsets[1:])
        arena = np.frombuffer(b"".join(vals), dtype=np.uint8)
        out.append((arena, offsets, lengths))
    return out


def test_make_mesh_has_8_cpu_devices():
    mesh = make_mesh(8)
    assert mesh.devices.size == 8
    assert all(d.platform == "cpu" for d in mesh.devices.flat)


def test_make_mesh_raises_when_too_few():
    with pytest.raises(RuntimeError, match="need 64 devices"):
        make_mesh(64)


def test_distributed_scan_count_psum_exact():
    n_dev = 8
    mesh = make_mesh(n_dev)
    nrows, hit_every = 32, 4
    blocks = _blocks(2 * n_dev, nrows=nrows, hit_every=hit_every)
    rows, lengths, _rb = stage_block_batch(blocks, n_dev)
    bucket_ids = np.arange(rows.shape[0], dtype=np.int32) % 4
    arrs = shard_batch(mesh, rows, lengths, bucket_ids)
    pattern = jax.numpy.asarray(np.frombuffer(b"error", dtype=np.uint8))
    bms, total, hist = distributed_scan_count(
        mesh, *arrs, pattern, 5, K.MODE_PHRASE, True, True, 4)
    per_block = nrows // hit_every
    expect = per_block * 2 * n_dev
    assert int(total) == expect
    hist = np.asarray(hist)
    assert int(hist.sum()) == expect
    # per-bucket counts: blocks round-robin over 4 buckets
    assert hist.tolist() == [per_block * 4] * 4
    # the bitmaps must be bit-exact vs the scalar oracle
    from victorialogs_tpu.logsql.matchers import match_phrase
    bms = np.asarray(bms)
    for b, (arena, offsets, lens) in enumerate(blocks):
        for i in range(len(lens)):
            v = arena[offsets[i]:offsets[i] + lens[i]].tobytes().decode()
            assert bool(bms[b, i]) == match_phrase(v, "error"), (b, i, v)


def test_distributed_scan_uneven_blocks_padded():
    n_dev = 8
    mesh = make_mesh(n_dev)
    # 10 blocks pad to 16 so every device gets an equal shard
    blocks = _blocks(10, nrows=16, hit_every=2)
    rows, lengths, _rb = stage_block_batch(blocks, n_dev)
    assert rows.shape[0] % n_dev == 0
    bucket_ids = np.zeros(rows.shape[0], dtype=np.int32)
    arrs = shard_batch(mesh, rows, lengths, bucket_ids)
    pattern = jax.numpy.asarray(np.frombuffer(b"error", dtype=np.uint8))
    _bms, total, hist = distributed_scan_count(
        mesh, *arrs, pattern, 5, K.MODE_PHRASE, True, True, 1)
    assert int(total) == 8 * 10  # pad blocks are all-0xFF: no matches
    assert int(np.asarray(hist)[0]) == 8 * 10


# ---------------- MeshBatchRunner: the product multi-chip path ----------------

NS = 1_000_000_000
T0 = 1_753_660_800_000_000_000


def _mk_storage(tmp_path):
    from victorialogs_tpu.storage.log_rows import LogRows, TenantID
    from victorialogs_tpu.storage.storage import Storage
    ten = TenantID(0, 0)
    s = Storage(str(tmp_path / "mesh"), retention_days=100000,
                flush_interval=3600)
    lr = LogRows(stream_fields=["app"])
    for i in range(4000):
        lr.add(ten, T0 + i * 500_000_000, [
            ("app", f"app{i % 2}"),
            ("_msg", f"req {'deadline' if i % 5 == 0 else 'ok'} n{i % 20}"),
            ("dur", str(i % 311)),
        ])
    s.must_add_rows(lr)
    s.debug_flush()
    return s, ten


def test_mesh_batch_runner_query_parity(tmp_path):
    """run_query through MeshBatchRunner on the 8-device mesh must match
    the CPU executor bit-for-bit — filters AND device stats partials
    (psum/pmin/pmax over the mesh)."""
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.parallel.distributed import MeshBatchRunner

    s, ten = _mk_storage(tmp_path)
    try:
        runner = MeshBatchRunner(make_mesh(8))
        for qs in [
            "deadline | fields _time",
            "deadline | stats by (_time:5m) count() c, sum(dur) s, "
            "min(dur) mn, max(dur) mx",
            "* | stats count() c, avg(dur) a",
            '_msg:~"dead.*line" | stats by (_time:10m) count() c',
            "* | stats by (app) count() c, sum(dur) s",
            "deadline | stats by (app, _time:10m) count_uniq(app) u, "
            "min(dur) mn",
            "* | stats count_uniq(_stream_id) u",
        ]:
            cpu = run_query_collect(s, [ten], qs, timestamp=T0)
            dev = run_query_collect(s, [ten], qs, timestamp=T0,
                                    runner=runner)
            assert sorted(map(str, cpu)) == sorted(map(str, dev)), qs
        assert runner.stats_dispatches > 0
        assert runner.device_calls > 0
        # the SPMD fused single-dispatch path must have carried most of
        # these (shard_map + psum/pmin/pmax over the mesh)
        assert runner.fused_dispatches > 0
        # sort-topk prefilter compiles under GSPMD over the sharded
        # staging (exact order parity incl. boundary ties)
        for qs in ['deadline | sort by (dur desc) limit 6 | fields dur',
                   '* | sort by (dur) limit 9 | fields dur, app']:
            cpu = run_query_collect(s, [ten], qs, timestamp=T0)
            dev = run_query_collect(s, [ten], qs, timestamp=T0,
                                    runner=runner)
            assert cpu == dev, qs
        assert runner.topk_dispatches > 0
    finally:
        s.close()


def test_mesh_fused_residue_and_quantile(tmp_path):
    """Mesh fused path: the packed maybe-vector concatenates across
    shards (pair-regex newline rows settle via host residue) and the
    quantile histogram axis psums correctly."""
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.parallel.distributed import MeshBatchRunner
    from victorialogs_tpu.storage.log_rows import LogRows, TenantID
    from victorialogs_tpu.storage.storage import Storage

    ten = TenantID(0, 0)
    s = Storage(str(tmp_path / "mfr"), retention_days=100000,
                flush_interval=3600)
    lr = LogRows(stream_fields=["app"])
    for i in range(4000):
        msg = f"GET item deadline x{i}" if i % 9 else "GET\nitem deadline"
        lr.add(ten, T0 + i * 250_000_000,
               [("app", f"a{i % 2}"), ("_msg", msg), ("dur", str(i % 97))])
    s.must_add_rows(lr)
    s.debug_flush()
    try:
        runner = MeshBatchRunner(make_mesh(8))
        for qs in ['_msg:~"GET.*deadline" | stats count() c',
                   '_msg:~"GET.*item" | stats by (app) median(dur) m, '
                   'count() c']:
            cpu = run_query_collect(s, [ten], qs, timestamp=T0)
            dev = run_query_collect(s, [ten], qs, timestamp=T0,
                                    runner=runner)
            assert sorted(map(str, cpu)) == sorted(map(str, dev)), qs
        assert runner.fused_dispatches > 0
    finally:
        s.close()


def test_mesh_runner_staged_arrays_are_sharded(tmp_path):
    """The staged row matrices really spread over the mesh (not silently
    replicated): at least the stats-layout arrays shard on axis 0."""
    from victorialogs_tpu.engine.searcher import run_query_collect
    from victorialogs_tpu.parallel.distributed import MeshBatchRunner

    s, ten = _mk_storage(tmp_path)
    try:
        runner = MeshBatchRunner(make_mesh(8))
        run_query_collect(s, [ten],
                          "* | stats by (_time:5m) sum(dur) x",
                          timestamp=T0, runner=runner)
        staged = [v for k, v in runner.cache._lru.items()
                  if isinstance(k, tuple) and "#num" in k]
        assert staged
        sharding = staged[0].values.sharding
        assert len(sharding.device_set) == 8
        assert not sharding.is_fully_replicated  # really split, axis 0
    finally:
        s.close()


# ---------------- the operand block under the mesh ----------------

@pytest.fixture(scope="module")
def operand_storage(tmp_path_factory):
    import operand_cases as OC
    s = OC.make_storage(str(tmp_path_factory.mktemp("mesh_operands")))
    yield s
    s.close()


@pytest.fixture(scope="module")
def mesh_runner():
    from victorialogs_tpu.parallel.distributed import MeshBatchRunner
    return MeshBatchRunner(make_mesh(8))


def _operand_cases():
    import operand_cases as OC
    return pytest.mark.parametrize("case", OC.CASES, ids=OC.CASE_IDS)


@_operand_cases()
def test_mesh_operand_block_every_leaf_kind(operand_storage, mesh_runner,
                                            monkeypatch, case):
    """tests/test_fused.py's operand-block cases on the 8-device mesh:
    the block is the dispatch's one host operand, replicated (`P()`)
    to every shard; answers equal the host path's."""
    import operand_cases as OC
    OC.check_case(operand_storage, mesh_runner, monkeypatch, case)
