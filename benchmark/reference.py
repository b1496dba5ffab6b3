"""The plain reference: the same questions asked of the same rows, in numpy.

It imports nothing of the program and reads nothing the program wrote.  It
makes the rows again from --seed (the configuration's row schema: the
benchmark's own generator, the data that takes the place of weights), and
answers a request from the class's "reference" entry in the traffic file:

  where  [["time"], ["phrase", text], ["phrase", field, text],
          ["regex", pattern], ["regex", field, pattern],
          [<an operator of the schema's WHERE>, args...]]   (all ANDed)
  by_time_s   bucket width of `stats by (_time:...)`, or absent
  stats  [["count", alias], [<a function of the schema's STATS>, alias]]
  answer      absent, or an answer kind of the schema's ANSWERS

What is LogsQL and not schema lives here: the time range, the phrase and
regex rules over a field's text (two elements: the schema's message
field), the time buckets, `count`.  What knows a field, a stream or a
token's form is the schema's, by name (`stream`, `token`,
`count_uniq_stream` in access_line): the contract of a schema module is
gen.py's docstring.

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

from gen import NS, Layout, rfc3339

BLOCK = 1 << 20
WHERE = ("time", "phrase", "regex")
_WORD = np.zeros(256, dtype=bool)
for _c in b"0123456789_abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ":
    _WORD[_c] = True


class Reference:
    def __init__(self, layout: Layout, seed: int, unreadable=None):
        self.layout, self.seed = layout, seed
        self.schema, self.config = layout.schema, layout.config
        self.rows = layout.rows
        self.unreadable = unreadable
        self._blocks = {}
        self._render = self.schema.Text(self.config)

    # ---- rows ----
    def _block(self, b: int) -> dict:
        """Rows [b*BLOCK, (b+1)*BLOCK): the schema's integer columns,
        "idx", and under "text:<field>" each text a filter has read, one
        fixed-width newline-ended row each."""
        blk = self._blocks.get(b)
        if blk is None:
            idx = np.arange(b * BLOCK, min((b + 1) * BLOCK, self.rows),
                            dtype=np.int64)
            blk = self.schema.row_fields(idx, self.seed, self.config)
            blk["idx"] = idx
            self._blocks[b] = blk
        return blk

    def _text(self, blk: dict, field: str) -> np.ndarray:
        key = "text:" + field
        if key not in blk:
            blk[key] = self._render.text(field, blk["idx"], blk, end="\n")
        return blk[key]

    # ---- filters: each returns a bool mask over rows [lo, hi) of a block
    def _match_text(self, blk, field, a, b, op, arg) -> np.ndarray:
        text = self._text(blk, field)[a:b]
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
                field = cond[1] if len(cond) == 3 else \
                    self.schema.MESSAGE_FIELD
                mask &= self._match_text(blk, field, a, b, op,
                                         cond[-1].format(**request["vals"]))
            elif op in self.schema.WHERE:
                args = [c.format(**request["vals"]) for c in cond[1:]]
                mask &= self.schema.WHERE[op](blk, a, b, args, self.config)
            else:
                raise ValueError(f"unknown reference filter {op!r}")
        return mask

    # ---- answers ----
    def matches(self, request: dict, spec: dict):
        """(blk, a, z, mask) for every stretch of rows that the request's
        time range leaves: rows [a, z) of block `blk`, `mask` those that
        pass every `where`."""
        where = spec.get("where", [])
        if request["t_range"] is not None and ["time"] in where:
            ranges = self.layout.row_range(*request["t_range"])
        else:
            ranges = [(0, self.rows)]
        for lo, hi in ranges:
            hi = min(hi, self.rows)
            for b in range(lo // BLOCK, (max(hi, lo + 1) - 1) // BLOCK + 1):
                a, z = max(lo, b * BLOCK), min(hi, (b + 1) * BLOCK)
                if z <= a:
                    continue
                blk = self._block(b)
                a, z = a - b * BLOCK, z - b * BLOCK
                yield blk, a, z, self._mask(blk, a, z, where, request)

    def answer(self, request: dict, spec: dict) -> list:
        """The request's answer in normal form (see `normal_form`)."""
        if "answer" in spec:
            return self.schema.ANSWERS[spec["answer"]](self, request, spec)
        step = int(spec["by_time_s"]) * NS if "by_time_s" in spec else None
        own = {fn: self.schema.STATS[fn](self.config)
               for fn, _alias in spec["stats"] if fn != "count"}
        total, buckets = 0, {}
        for blk, a, z, mask in self.matches(request, spec):
            total += int(mask.sum())
            for acc in own.values():
                acc.add(blk, a, z, mask)
            if step is not None:
                t = self.layout.times(blk["idx"][a:z][mask]) // step * step
                for k, c in zip(*np.unique(t, return_counts=True)):
                    buckets[int(k)] = buckets.get(int(k), 0) + int(c)
        names = {fn: alias.format(**request["vals"])
                 for fn, alias in spec["stats"]}
        if step is not None:
            rows = [{"_time": k, names["count"]: c}
                    for k, c in buckets.items()]
        else:
            rows = [{alias: total if fn == "count" else own[fn].value()
                     for fn, alias in names.items()}]
        return sorted(tuple(sorted(r.items())) for r in rows)


def check(spec: dict, schema) -> None:
    """Raises where a class's "reference" entry names an operator, a
    stats function or an answer kind that neither this file nor the
    schema has: before a child is started."""
    for cond in spec.get("where", []):
        if cond[0] not in WHERE and cond[0] not in schema.WHERE:
            raise ValueError(f"no reference filter {cond[0]!r} here or in "
                             f"schema {schema.__name__!r}")
    for fn, _alias in spec["stats"]:
        if fn != "count" and fn not in schema.STATS:
            raise ValueError(f"no stats function {fn!r} here or in schema "
                             f"{schema.__name__!r}")
    if "answer" in spec and spec["answer"] not in getattr(schema, "ANSWERS",
                                                          {}):
        raise ValueError(f"no answer kind {spec['answer']!r} in schema "
                         f"{schema.__name__!r}")


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
