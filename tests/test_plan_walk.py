"""One header walk a query (PR 34): the pricing pass and the execution
share one walk over partitions, parts and candidate blocks
(engine/planwalk.py).

- the units the window dispatches (seq, members, blocks) are the units
  `?explain=1` shows, and the `predicted_*` the run writes on its
  activity record are `predict_query`'s on the same storage;
- with pricing on a query snapshots and resolves once a partition and
  probes a part's maplets once (counting monkeypatches);
- with `VL_QUERY_PRICING=0` a `limit` still stops the header walk early;
- `shared_plan_walks` moves once a priced device query and for nothing
  else.
"""

import pytest

from victorialogs_tpu.engine import planwalk
from victorialogs_tpu.engine.searcher import run_query, run_query_collect
from victorialogs_tpu.logsql.filters import FilterStream
from victorialogs_tpu.logsql.parser import parse_query
from victorialogs_tpu.obs import activity, explain, tracing
from victorialogs_tpu.storage.datadb import DataDB
from victorialogs_tpu.storage.log_rows import LogRows, TenantID
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu.batch import BatchRunner

NS_DAY = 86_400 * 1_000_000_000
T0 = 1_753_660_800_000_000_000  # 2025-07-28T00:00:00Z
TEN = TenantID(0, 0)
N_DAYS = 3
PARTS_PER_DAY = 4
ROWS_PER_PART = 420
N_PARTS = N_DAYS * PARTS_PER_DAY


@pytest.fixture(scope="module")
def storage(tmp_path_factory):
    """Three day partitions of four flush-sized sealed parts, four
    streams, a unique token a row."""
    path = str(tmp_path_factory.mktemp("planwalk"))
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
                    ("trace", f"tok{g}"),
                    ("dur", str(g % 251)),
                ])
            s.must_add_rows(lr)
            s.debug_flush()
    assert len(s.partitions) == N_DAYS
    yield s
    s.close()


@pytest.fixture
def runner(monkeypatch):
    monkeypatch.setenv("VL_INFLIGHT", "4")
    monkeypatch.setenv("VL_PACK_PARTS", "8")
    return BatchRunner()


def _find(tree, name):
    out, stack = [], [tree]
    while stack:
        n = stack.pop()
        if n.get("name") == name:
            out.append(n)
        stack.extend(n.get("children", ()))
    return out


def _traced_run(storage, qs, runner):
    """(progress counters of the run's record, its span tree)."""
    root = tracing.make_root("query", query=qs)
    with activity.track("test", qs, TEN) as act, tracing.activate(root):
        run_query(storage, [TEN], parse_query(qs, T0),
                  write_block=lambda br: None, runner=runner)
        progress = act.snapshot()["progress"]
    return progress, root.to_dict()


def _dispatched_units(tree):
    units = []
    for s in _find(tree, "submit"):
        a = s["attrs"]
        units.append((a["unit"], a.get("pack_members") or [a["part"]],
                      a["blocks"]))
    return sorted(units)


def _planned_units(plan):
    return sorted((u["seq"], u["members"], u["blocks"])
                  for pt in plan["partitions"] for u in pt["units"])


DAY1 = f"_time:[{T0 + NS_DAY}, {T0 + 2 * NS_DAY})"

SHAPES = {
    "windowed_phrase_stats": f'{DAY1} err | stats count() c',
    "all_rows_regex_stats": '_msg:~"x1.*of" | stats count() c',
    "stream_token_hit": '{app="app1"} trace:tok13 | stats count() c',
    "stream_token_miss": '{app="app1"} trace:tokabsent | stats count() c',
    "rows_limit": 'err | fields _time, dur | limit 5',
    "sort_limit": 'err | sort by (dur desc) limit 7 | fields dur, app',
    "pack_across_days": 'err | fields _time',
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_dispatched_units_are_the_priced_plan(storage, runner, shape):
    qs = SHAPES[shape]
    q = parse_query(qs, T0)
    # the run prices itself before it stages or dispatches anything, so
    # a pricing pass taken now sees the cost model and the staging cache
    # the run's own pricing will see
    want = explain.predict_query(storage, [TEN], q, runner)
    plan = explain.build_plan(storage, [TEN], q, runner=runner)
    progress, tree = _traced_run(storage, qs, runner)

    planned = _planned_units(plan)
    dispatched = _dispatched_units(tree)
    if shape == "rows_limit":
        # the limit may stop the window short of the plan's last units
        assert dispatched and dispatched == planned[:len(dispatched)]
    else:
        assert dispatched == planned
    if shape == "stream_token_miss":
        assert planned == []
    if shape == "pack_across_days":
        days = [{p["part"] for p in pt["parts"]}
                for pt in plan["partitions"]]
        assert any(sum(1 for d in days if d & set(members)) > 1
                   for _seq, members, _blocks in planned)

    assert progress["predicted_duration_s"] == want["duration_s"]
    assert progress["predicted_bytes"] == want["bytes_scanned"]
    assert progress["predicted_dispatches"] == want["dispatches"]
    assert progress["predicted_rows"] == want["rows_scanned"]
    # the one walk runs under a prune span of the query
    assert _find(tree, "prune")
    assert _find(tree, "partition")


def _count_walk_calls(monkeypatch):
    calls = {"snapshot": 0, "resolve": 0, "maplet": []}
    snap, resolve = DataDB.snapshot_parts, FilterStream.resolve
    keep = planwalk.maplet_leaf_keep

    def snapshot_parts(self):
        calls["snapshot"] += 1
        return snap(self)

    def resolve_counted(self, partition, tenants):
        calls["resolve"] += 1
        return resolve(self, partition, tenants)

    def maplet_leaf_keep(fi, leaves, bis):
        calls["maplet"].append(id(fi))
        return keep(fi, leaves, bis)

    monkeypatch.setattr(DataDB, "snapshot_parts", snapshot_parts)
    monkeypatch.setattr(FilterStream, "resolve", resolve_counted)
    monkeypatch.setattr(planwalk, "maplet_leaf_keep", maplet_leaf_keep)
    return calls


@pytest.mark.parametrize("qs", [
    '{app="app1"} trace:tok13 | stats count() c',
    '{app="app2"} err | stats count() c',
    'err | fields _time',
])
def test_one_snapshot_resolve_and_maplet_probe(storage, runner,
                                               monkeypatch, qs):
    """Pricing adds no header work: a priced query walks as often as an
    unpriced one, once a partition and once a part."""
    seen = {}
    for pricing in ("1", "0"):
        with monkeypatch.context() as m:
            m.setenv("VL_QUERY_PRICING", pricing)
            seen[pricing] = _count_walk_calls(m)
            shared0 = runner.shared_plan_walks
            run_query_collect(storage, [TEN], qs, timestamp=T0,
                              runner=runner)
            assert runner.shared_plan_walks - shared0 == int(pricing)
    on, off = seen["1"], seen["0"]
    assert on["snapshot"] == N_DAYS
    assert len(on["maplet"]) == len(set(on["maplet"])) <= N_PARTS
    assert on["snapshot"] == off["snapshot"]
    assert on["resolve"] == off["resolve"]
    assert sorted(on["maplet"]) == sorted(off["maplet"])
    if "{" in qs:
        # the planner resolves a stream leaf again a unit (memoized);
        # the walk itself resolves once a partition
        assert on["resolve"] >= N_DAYS


def test_unpriced_limit_stops_the_walk_early(storage, monkeypatch):
    monkeypatch.setenv("VL_INFLIGHT", "1")
    monkeypatch.setenv("VL_PACK_PARTS", "1")
    runner = BatchRunner()
    walked = []
    cand = planwalk._candidates

    def counted(part, *a):
        walked.append(part.uid)
        return cand(part, *a)

    monkeypatch.setattr(planwalk, "_candidates", counted)
    qs = '* | fields _time | limit 1'
    monkeypatch.setenv("VL_QUERY_PRICING", "0")
    rows = run_query_collect(storage, [TEN], qs, timestamp=T0,
                             runner=runner)
    assert len(rows) == 1
    assert 0 < len(walked) < N_PARTS
    # priced, the walk is the eager one the pricing pass always was
    del walked[:]
    monkeypatch.setenv("VL_QUERY_PRICING", "1")
    rows = run_query_collect(storage, [TEN], qs, timestamp=T0,
                             runner=runner)
    assert len(rows) == 1
    assert len(walked) == N_PARTS


def test_shared_plan_walks_counts_priced_device_queries(storage, runner,
                                                        monkeypatch):
    qs = 'err | stats count() c'
    run_query_collect(storage, [TEN], qs, timestamp=T0, runner=runner)
    assert runner.shared_plan_walks == 1
    assert runner.stats()["shared_plan_walks"] == 1
    # the host executor has no window to share a walk with
    run_query_collect(storage, [TEN], qs, timestamp=T0)
    assert runner.shared_plan_walks == 1
    # a nested in(<subquery>) runs through the outer query's record,
    # which is priced already: the outer query counts, once
    nested = 'trace:in(trace:tok13 | fields trace) | stats count() c'
    rows = run_query_collect(storage, [TEN], nested, timestamp=T0,
                             runner=runner)
    assert rows == [{"c": "1"}]
    assert runner.shared_plan_walks == 2
    # no activity record, no pricing: the window pulls the walk itself
    run_query(storage, [TEN], parse_query(qs, T0),
              write_block=lambda br: None, runner=runner)
    assert runner.shared_plan_walks == 2
    monkeypatch.setenv("VL_QUERY_PRICING", "0")
    run_query_collect(storage, [TEN], qs, timestamp=T0, runner=runner)
    assert runner.shared_plan_walks == 2


def test_cold_aggregate_fold_waits_for_the_window(storage, runner,
                                                  monkeypatch):
    """Classic parts (no v2 sidecar): the priced walk probes cached
    aggregates only, so a part whose fold nobody has built is priced as
    retained and killed when the window reaches it; the next query finds
    the fold and kills in the walk."""
    monkeypatch.setenv("VL_FILTER_INDEX", "v1")
    qs = 'zebraabsent | stats count() c'
    calls0 = runner.device_calls
    progress, _tree = _traced_run(storage, qs, runner)
    assert runner.agg_pruned_parts == N_PARTS
    assert runner.device_calls == calls0
    assert progress["predicted_dispatches"] > 0
    assert progress["parts_pruned"] == N_PARTS
    assert "parts_scanned" not in progress
    progress, _tree = _traced_run(storage, qs, runner)
    assert runner.agg_pruned_parts == 2 * N_PARTS
    assert progress["predicted_dispatches"] == 0
    assert run_query_collect(storage, [TEN], qs, timestamp=T0,
                             runner=runner) == [{"c": "0"}]
