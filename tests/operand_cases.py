"""The operand-block cases shared by tests/test_fused.py (BatchRunner)
and tests/test_distributed.py (MeshBatchRunner on virtual devices): one
query per leaf kind that carries a host-side operand, and the check
every case makes (tpu/fused.py: _Planner.host_words / host_bytes /
block, _launch)."""

import jax
import numpy as np

from victorialogs_tpu.engine.searcher import run_query_collect
from victorialogs_tpu.storage.log_rows import LogRows, TenantID
from victorialogs_tpu.storage.storage import Storage
from victorialogs_tpu.tpu import fused

NS = 1_000_000_000
T0 = 1_753_660_800_000_000_000  # 2025-07-28T00:00:00Z
TEN = TenantID(0, 0)
WORDS = ["deadline exceeded", "connection reset", "ok", "retry later",
         "cache miss", "flushed"]
# `tag` values are 31 bytes: the longest pattern a column staged at
# W=32 scans (a pattern of W bytes or more folds to the overflow leaf)
TAG31 = "t" * 26 + "00003"

# (id, query, the runner counter that must move, VL_PACK_PARTS)
CASES = [
    ("time", "_time:[2025-07-28T00:05:00Z, 2025-07-28T00:20:00Z] "
     "| stats count() c", "fused_dispatches", "1"),
    ("time_phrase", "_time:[2025-07-28T00:02:00Z, 2025-07-28T00:17:00Z) "
     '"deadline exceeded" | stats by (_time:5m) count() c',
     "fused_dispatches", "1"),
    ("phrase", '"deadline exceeded" | stats count() c',
     "fused_dispatches", "1"),
    ("prefix", '_msg:"GET"* | stats count() c', "fused_dispatches", "1"),
    ("substring", '_msg:~"eadline" | stats count() c',
     "fused_dispatches", "1"),
    ("exact", 'lvl:exact("error") | stats by (_time:10m) count() c',
     "fused_dispatches", "1"),
    ("startswith", 'lvl:exact("err"*) | stats count() c',
     "fused_dispatches", "1"),
    ("pair", '_msg:~"GET.*exceeded" | stats count() c',
     "fused_dispatches", "1"),
    ("in", "lvl:in(error, warn) | stats count() c",
     "fused_dispatches", "1"),
    ("numrange", "dur:range[100, 200] | stats by (app) count() c",
     "fused_dispatches", "1"),
    ("lenrange", "_msg:len_range(10, 30) | stats count() c",
     "fused_dispatches", "1"),
    ("casefold", 'i("DEADLINE Exceeded") | stats count() c',
     "fused_dispatches", "1"),
    ("width_minus_1", f'tag:exact("{TAG31}") | stats count() c',
     "fused_dispatches", "1"),
    ("packed", '"deadline exceeded" dur:>17 | stats by (app) count() c, '
     "sum(dur) s", "packed_dispatches", "8"),
    ("topk", '"GET" | sort by (dur desc) limit 7 | fields dur, app',
     "topk_dispatches", "1"),
    ("row_filter", '"deadline exceeded" dur:>300 | fields _msg, app',
     "filter_dispatches", "1"),
]
CASE_IDS = [c[0] for c in CASES]


def make_storage(path: str) -> Storage:
    """Six small parts: every case makes several dispatches, and the
    packed case has something to pack."""
    s = Storage(path, retention_days=100000, flush_interval=3600)
    n = 0
    for _part in range(6):
        lr = LogRows(stream_fields=["app"])
        for _i in range(1500):
            i = n
            n += 1
            msg = f"GET /api/x{i % 71} {WORDS[i % 6]} dur={i % 351}ms"
            if i % 37 == 0:
                msg = f"GÉT /äpi/x{i % 71} {WORDS[i % 6]} ⏱={i % 351}"
            if i % 97 == 0:
                msg = f"GET /api\nlate {WORDS[i % 6]} tail"
            lr.add(TEN, T0 + i * 200_000_000, [
                ("app", f"app{i % 4}"),
                ("_msg", msg),
                ("lvl", ["info", "warn", "error"][i % 3]),
                ("dur", str(i % 351)),
                ("tag", "t" * 26 + f"{i % 5:05d}"),
            ])
        s.must_add_rows(lr)
        s.debug_flush()
    return s


def _norm(rows):
    return sorted(tuple(sorted(r.items())) for r in rows)


def record_launches(monkeypatch) -> list:
    """Every fused/topk/filter dispatch from here on appends the
    operands _launch was handed (name, statics, block, device arrays)."""
    seen: list = []
    real = fused._launch

    def spy(runner, dispatch, *args):
        seen.append(args)
        return real(runner, dispatch, *args)

    monkeypatch.setattr(fused, "_launch", spy)
    return seen


def host_operands(args) -> list:
    """The array leaves of a dispatch's operands that are not
    jax.Arrays (statics are Python ints, strs, bools and tuples of
    them: not arrays)."""
    return [leaf for leaf in jax.tree_util.tree_leaves(args)
            if isinstance(leaf, (np.ndarray, np.generic))]


def check_case(storage, runner, monkeypatch, case) -> None:
    """Device answers equal the host path's; each dispatch ships exactly
    one operand that is not a jax.Array, the int32 block; and
    `operand_blocks` grows with `device_calls`."""
    _id, qs, counter, pack = case
    monkeypatch.setenv("VL_PACK_PARTS", pack)
    seen = record_launches(monkeypatch)
    host = run_query_collect(storage, [TEN], qs, timestamp=T0)
    before = dict(runner.stats())
    dev = run_query_collect(storage, [TEN], qs, timestamp=T0,
                            runner=runner)
    after = runner.stats()
    if "sort by" in qs:
        assert host == dev, qs          # exact rows, exact order
    else:
        assert _norm(host) == _norm(dev), qs
    assert host, qs
    if _id == "width_minus_1":
        assert int(host[0]["c"]) == 1800     # the 31-byte pattern matches
    assert after[counter] > before[counter], (qs, counter)
    calls = after["device_calls"] - before["device_calls"]
    assert calls == len(seen) > 0, qs
    assert after["operand_blocks"] - before["operand_blocks"] == calls, qs
    for args in seen:
        host_ops = host_operands(args)
        assert len(host_ops) == 1, (qs, host_ops)
        blk = host_ops[0]
        assert blk.dtype == np.int32 and blk.ndim == 1, qs
        assert blk.shape[0] >= 32 and \
            blk.shape[0] & (blk.shape[0] - 1) == 0, qs
        others = [leaf for leaf in jax.tree_util.tree_leaves(args)
                  if hasattr(leaf, "shape") and leaf is not blk]
        assert others and all(isinstance(o, jax.Array) for o in others), qs
