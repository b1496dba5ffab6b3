"""A part the fused planner declines is evaluated by the host executor.

There is one device path (tpu/batch.py's docstring): the host gate, one
fused / topk / filter program a part or pack, and, where the planner
declines, the host executor.  One case a decline reason: the answer is
the host executor's in order, no device call is made for the declined
part, `cpu_fallbacks` counts it once, and every cached column sits under
one of the fused staging keys: a string column of a part has one layout,
`(uid, "#fl", field)`.
"""

import math

import pytest

from victorialogs_tpu.engine.searcher import run_query_collect
from victorialogs_tpu.logsql.parser import parse_query
from victorialogs_tpu.storage.log_rows import LogRows, TenantID
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu import fused
from victorialogs_tpu.tpu.batch import BatchRunner

T0 = 1_753_660_800_000_000_000  # 2025-07-28T00:00:00Z
TEN = TenantID(0, 0)
N_PARTS = 3
ROWS_PER_PART = 400

# every staging key the runner writes (tpu/batch.py, the _stage_* hooks)
FUSED_KEYS = {"#layout", "#fl", "#mb", "#ts2", "#num", "#dict", "#seg",
              "#segslots", "#tb", "#tb0", "#nb", "#bloom", "#sbbloom",
              "#bid"}


@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    """One day, three flush-sized parts of sixteen streams: `dur`
    int-typed, `ratio` float-typed, `lvl` a three-value dict column,
    `_msg` strings."""
    s = Storage(str(tmp_path_factory.mktemp("decline")),
                retention_days=100000, flush_interval=3600)
    n = 0
    for _pp in range(N_PARTS):
        lr = LogRows(stream_fields=["app"])
        for _i in range(ROWS_PER_PART):
            g = n
            n += 1
            lr.add(TEN, T0 + g * 50_000_000, [
                ("app", f"app{g % 16}"),
                ("_msg", f"m {'err' if g % 3 == 0 else 'ok'} "
                         f"x{g % 97} of {g}"),
                ("lvl", ["info", "warn", "crit"][g % 3]),
                ("dur", str(g % 251)),
                ("ratio", f"{(g % 7) / 4}"),
            ])
        s.must_add_rows(lr)
        s.debug_flush()
    assert len(_parts(s)) == N_PARTS
    yield s
    s.close()


def _parts(s):
    return [p for pt in s.partitions.values()
            for p in pt.ddb.snapshot_parts()]


def _leaf(qs, **attrs):
    """The query with its LAST filter leaf's attributes replaced: the
    two reasons the parser keeps a LogsQL text from reaching."""
    def build():
        q = parse_query(qs, T0)
        for k, v in attrs.items():
            setattr(q.filter.filters[-1], k, v)
        return q
    return build


_MANY = ",".join(f"v{i}" for i in range(17))

# (reason the planner raises, the query, rows the answer must hold)
CASES = {
    "verify-regex": ("verify", '_msg:~"err x[0-9]+"', 400),
    "verify-sequence": ("verify", 'seq("err", "of")', 400),
    "verify-under-stats": ("verify",
                           '_msg:~"err x[0-9]+" | stats count() c', 1),
    "verify-under-topk": ("verify", '_msg:~"err x[0-9]+" '
                          '| sort by (dur desc) limit 3 | fields dur', 3),
    "text-over-typed-column": ("dur", "dur:17", 5),
    "time-as-string": ("_time-as-string",
                       _leaf("err lvl:2025", field="_time"), 400),
    "numrange-nan": ("numrange-nan",
                     _leaf("err dur:>5", min_value=math.nan), 0),
    "numrange-not-int": ("numrange", "err ratio:>1", 114),
    "in-cardinality": ("in-cardinality", f"err lvl:in({_MANY},info)", 400),
    "in-value": ("in-value", 'err lvl:in("é", info)', 400),
    "in-subquery": ("in-subquery", "err lvl:in(nosuch | fields lvl)", 0),
    "unsupported-leaf": ("FilterEqField", "err lvl:eq_field(lvl)", 400),
    # one stream of sixteen, its column not staged yet: under an eighth
    # of the part (the needle cell's trace-id lookups decline so)
    "narrow": ("narrow", '{app="app3"} err', 25),
}


def _run_both(storage, q, runner):
    """(device answer, host answer on one thread), each from a fresh
    Query: run_query materializes subqueries into the filter tree."""
    def fresh():
        return q() if callable(q) else parse_query(q, T0)
    dev = run_query_collect(storage, [TEN], fresh(), runner=runner)
    hq = fresh()
    hq.opts.concurrency = 1
    return dev, run_query_collect(storage, [TEN], hq)


@pytest.fixture
def reasons(monkeypatch):
    """The _NoFuse reasons the planner raised during the test."""
    seen = []
    plan = fused._Planner.plan

    def spy(self, f):
        try:
            return plan(self, f)
        except fused._NoFuse as e:
            seen.append(str(e))
            raise
    monkeypatch.setattr(fused._Planner, "plan", spy)
    return seen


def _assert_fused_keys_only(runner):
    keys = list(runner.cache._lru)
    assert keys, "the decline staged nothing: did it reach the planner?"
    odd = [k for k in keys if k[1] not in FUSED_KEYS]
    assert not odd, f"staging keys outside the fused layouts: {odd}"


def _assert_declined(runner, d0, nparts=N_PARTS):
    assert runner.device_calls == d0, \
        "a declined part made a device call"
    assert runner.cpu_fallbacks == nparts
    assert runner.gated_host_parts == 0
    _assert_fused_keys_only(runner)


@pytest.mark.parametrize("case", list(CASES))
def test_declined_shape_runs_on_host(storage, monkeypatch, reasons, case):
    reason, q, nrows = CASES[case]
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = BatchRunner()
    fed = []
    monkeypatch.setattr(runner.cost, "observe_host_scan",
                        lambda *a: fed.append(a))
    try:
        dev, host = _run_both(storage, q, runner)
        assert dev == host
        assert len(dev) == nrows
        assert reason in reasons, reasons
        _assert_declined(runner, 0)
        assert not fed, "a decline fed the gate's host rate"
    finally:
        runner.close()


def test_declined_shape_runs_on_host_ts_span(storage, monkeypatch,
                                             reasons):
    """A part spanning 2**47 ns or more: the (hi >> 16) int32 timestamp
    plane would not be exact, so the time leaf declines."""
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    for p in _parts(storage):
        monkeypatch.setitem(p.meta, "max_ts", p.min_ts + (1 << 47))
    runner = BatchRunner()
    try:
        dev, host = _run_both(storage, "err _time:2025-07-28", runner)
        assert dev == host and len(dev) == 400
        assert "ts-span" in reasons, reasons
        _assert_declined(runner, 0)
    finally:
        runner.close()


def test_declined_shape_runs_on_host_stats_axes(storage, monkeypatch,
                                                reasons):
    """A group-by the axes assembly refuses (more distinct values than
    a dict axis stages): no planner runs, the host pipe aggregates."""
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = BatchRunner()
    try:
        dev, host = _run_both(storage, "err | stats by (_msg) count() c",
                              runner)
        assert dev == host and len(dev) == 400
        assert not reasons
        _assert_declined(runner, 0)
    finally:
        runner.close()


@pytest.mark.parametrize("qs", ['_msg:~"err x[0-9]+"',
                                '_msg:~"err x[0-9]+" | stats count() c',
                                '_msg:~"err x[0-9]+" | sort by (dur desc) '
                                'limit 3 | fields dur'],
                         ids=["rows", "stats", "topk"])
def test_declining_pack_falls_back_member_by_member(storage, monkeypatch,
                                                    reasons, qs):
    """The pack declines, then each member through the single-part
    submit: every member is counted once, none twice."""
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    runner = BatchRunner()
    try:
        dev, host = _run_both(storage, qs, runner)
        assert dev == host and dev
        assert runner.pipeline_units == 1      # one pack of three
        assert runner.packed_dispatches == 0
        assert "verify" in reasons
        _assert_declined(runner, 0)
    finally:
        runner.close()


def test_declined_shape_runs_on_host_mesh(storage, monkeypatch, reasons):
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device mesh")
    from victorialogs_tpu.parallel.distributed import MeshBatchRunner
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = MeshBatchRunner()
    try:
        # a fused query first: the decline leaves its device calls alone
        assert len(run_query_collect(storage, [TEN], "err", timestamp=T0,
                                     runner=runner)) == 400
        d0 = runner.device_calls
        assert d0 == N_PARTS
        dev, host = _run_both(storage, CASES["verify-regex"][1], runner)
        assert dev == host and len(dev) == 400
        assert "verify" in reasons
        _assert_declined(runner, d0)
    finally:
        runner.close()


def test_one_string_layout(storage, monkeypatch):
    """After a mixed run of fused and declined queries a part's string
    columns are cached once, in the fused layout."""
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = BatchRunner()
    try:
        for qs in ["err", "lvl:warn app:app1", '_msg:~"err.*of"',
                   "_msg:i(ERR)", "err | stats by (lvl) count() c",
                   'err | sort by (dur) limit 5',
                   CASES["verify-regex"][1], CASES["verify-sequence"][1],
                   CASES["unsupported-leaf"][1], 'lvl:~"w[a-z]+n"']:
            dev, host = _run_both(storage, qs, runner)
            assert dev == host, qs
        assert runner.device_calls > 0 and runner.cpu_fallbacks > 0
        uids = {p.uid for p in _parts(storage)}
        keys = list(runner.cache._lru)
        for fld in ("_msg", "lvl"):
            for uid in uids:
                assert (uid, "#fl", fld) in keys
        _assert_fused_keys_only(runner)
    finally:
        runner.close()
