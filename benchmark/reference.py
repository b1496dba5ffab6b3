"""The plain reference: the same questions asked of the same rows, in numpy.

It imports nothing of the program and reads nothing the program wrote.  It
makes the rows again from --seed (gen.row_fields, gen.Text: the
benchmark's own generator, the data that takes the place of weights), and
answers a request from the class's "reference" entry in the traffic file:

  where  [["time"], ["phrase", text], ["regex", pattern],
          ["stream", "app3"], ["token", field, "tok123"]]   (all ANDed)
  by_time_s   bucket width of `stats by (_time:...)`, or absent
  stats  [["count", alias], ["count_uniq_stream", alias]]

LogsQL semantics kept: a phrase matches where the text occurs with no
letter, digit or underscore directly before or after it; a regex matches
anywhere in the value and `.` never crosses a row; `_time:[a, b)` is
half-open; `stats by (_time:5m)` floors to multiples of the step since
the epoch and emits only groups that have rows.

`unreadable=(lo, hi)` is the control of "How `correct` is decided": the
same reference with one stated guarantee broken (the rows lo..hi, a part
or the fresh parts, are not readable), which has to come out as not
correct.  `render` writes an answer as the server would send it, so that
the control and the tests' stand-in go through `normal_form` and the
harness's own comparison like any served answer.
"""

import calendar
import json
import re

import numpy as np

from gen import NS, Layout, Text, rfc3339, row_fields

BLOCK = 1 << 20
_WORD = np.zeros(256, dtype=bool)
for _c in b"0123456789_abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ":
    _WORD[_c] = True


class Reference:
    def __init__(self, layout: Layout, seed: int, unreadable=None):
        self.layout, self.seed = layout, seed
        self.rows = layout.rows
        self.unreadable = unreadable
        self._blocks = {}
        self._render = Text()

    # ---- rows ----
    def _block(self, b: int) -> dict:
        """Rows [b*BLOCK, (b+1)*BLOCK): integer columns and the `_msg`
        text, one fixed-width newline-ended row each."""
        blk = self._blocks.get(b)
        if blk is None:
            idx = np.arange(b * BLOCK, min((b + 1) * BLOCK, self.rows),
                            dtype=np.int64)
            blk = row_fields(idx, self.seed)
            blk["idx"] = idx
            self._blocks[b] = blk
        return blk

    def _text(self, blk: dict) -> np.ndarray:
        if "text" not in blk:
            blk["text"] = self._render.msg(blk["idx"], blk, end="\n")
        return blk["text"]

    # ---- filters: each returns a bool mask over rows [lo, hi) of a block
    def _match_text(self, blk, a, b, op, arg) -> np.ndarray:
        text = self._text(blk)[a:b]
        width = text.dtype.itemsize
        buf = text.tobytes()
        lit = arg.encode()
        pat = re.escape(lit) if op == "phrase" else lit
        starts = np.fromiter((m.start() for m in re.finditer(pat, buf)),
                             dtype=np.int64)
        if op == "phrase" and len(starts):
            raw = np.frombuffer(buf, dtype=np.uint8)
            ends = starts + len(lit)
            before = np.where(starts % width == 0, False,
                              _WORD[raw[np.maximum(starts - 1, 0)]])
            after = _WORD[raw[np.minimum(ends, len(raw) - 1)]]
            starts = starts[~before & ~after]
        mask = np.zeros(b - a, dtype=bool)
        mask[starts // width] = True
        return mask

    def _mask(self, blk, a, b, where, request) -> np.ndarray:
        idx = blk["idx"][a:b]
        if self.unreadable is None:
            mask = np.ones(b - a, dtype=bool)
        else:
            mask = (idx < self.unreadable[0]) | (idx >= self.unreadable[1])
        for cond in where:
            op = cond[0]
            if op == "time":
                t0, t1 = request["t_range"]
                t = self.layout.times(idx)
                mask &= (t >= t0) & (t < t1)
            elif op in ("phrase", "regex"):
                mask &= self._match_text(blk, a, b, op,
                                         cond[1].format(**request["vals"]))
            elif op == "stream":
                app = cond[1].format(**request["vals"])
                mask &= (idx % self.layout.streams) == int(app[3:])
            elif op == "token":
                # every `trace` value is one word "tok<n>", so a word
                # filter on the field is equality with it
                tok = cond[2].format(**request["vals"])
                n = int(tok[3:]) if tok[3:].isdigit() else -1
                mask &= blk[cond[1]][a:b] == n
            else:
                raise ValueError(f"unknown reference filter {op!r}")
        return mask

    # ---- answers ----
    def answer(self, request: dict, spec: dict) -> list:
        """The request's answer in normal form (see `normal_form`)."""
        where = spec.get("where", [])
        if request["t_range"] is not None and ["time"] in where:
            ranges = self.layout.row_range(*request["t_range"])
        else:
            ranges = [(0, self.rows)]
        step = int(spec["by_time_s"]) * NS if "by_time_s" in spec else None
        total, streams, buckets = 0, set(), {}
        for lo, hi in ranges:
            hi = min(hi, self.rows)
            for b in range(lo // BLOCK, (max(hi, lo + 1) - 1) // BLOCK + 1):
                a, z = max(lo, b * BLOCK), min(hi, (b + 1) * BLOCK)
                if z <= a:
                    continue
                blk = self._block(b)
                a, z = a - b * BLOCK, z - b * BLOCK
                mask = self._mask(blk, a, z, where, request)
                total += int(mask.sum())
                idx = blk["idx"][a:z][mask]
                streams.update(np.unique(idx % self.layout.streams).tolist())
                if step is not None:
                    t = self.layout.times(idx) // step * step
                    for k, c in zip(*np.unique(t, return_counts=True)):
                        buckets[int(k)] = buckets.get(int(k), 0) + int(c)
        names = {fn: alias.format(**request["vals"])
                 for fn, alias in spec["stats"]}
        if step is not None:
            rows = [{"_time": k, names["count"]: c}
                    for k, c in buckets.items()]
        else:
            rows = [{alias: total if fn == "count" else len(streams)
                     for fn, alias in names.items()}]
        return sorted(tuple(sorted(r.items())) for r in rows)


def unreadable_rows(layout: Layout, what: str) -> tuple:
    """The control's lost rows: "fresh" (the fresh parts) or "part:<i>"
    (part i of the config's table)."""
    if what == "fresh":
        return layout.region("fresh")
    part = layout.parts[int(what.removeprefix("part:"))]
    return part["lo"], part["hi"]


# ---- what the server said, in the same normal form ----

_TIME = re.compile(r"^(\d{4})-(\d\d)-(\d\d)T(\d\d):(\d\d):(\d\d)"
                   r"(?:\.(\d{1,9}))?Z$")


def _value(name: str, v):
    if name == "_time":
        m = _TIME.match(v)
        if m is None:
            raise ValueError(f"not a timestamp: {v!r}")
        secs = calendar.timegm(tuple(int(x) for x in m.groups()[:6]))
        return secs * NS + int((m.group(7) or "0").ljust(9, "0"))
    if isinstance(v, str) and v.lstrip("-").isdigit():
        return int(v)
    return v


def render(endpoint: str, rows: list) -> bytes:
    """Normal-form rows as the server sends them: the inverse of
    `normal_form`."""
    if endpoint == "stats_query":
        result = [{"metric": {"__name__": k}, "value": [0, str(v)]}
                  for k, v in rows[0]]
        return json.dumps({"status": "success",
                           "data": {"resultType": "vector",
                                    "result": result}}).encode()
    lines = [json.dumps({k: rfc3339(v) if k == "_time" else str(v)
                         for k, v in row}) for row in rows]
    return ("\n".join(lines) + "\n").encode()


def normal_form(endpoint: str, body: bytes) -> list:
    """A served answer as a sorted list of rows, each a sorted tuple of
    (name, value) with whole numbers and timestamps (ns) parsed.  LogsQL
    defines no order for stats groups, so rows compare as a multiset."""
    rows = []
    if endpoint == "stats_query":
        res = json.loads(body)
        if res.get("status") != "success" or res.get("partial"):
            raise ValueError(f"stats_query failed: {body[:300]!r}")
        row = {}
        for r in res["data"]["result"]:
            row[r["metric"]["__name__"]] = _value("", r["value"][1])
        rows.append(row)
    else:
        for ln in body.decode().splitlines():
            if ln and not ln.startswith('{"_trace"'):
                rows.append({k: _value(k, v)
                             for k, v in json.loads(ln).items()})
    return sorted(tuple(sorted(r.items())) for r in rows)
