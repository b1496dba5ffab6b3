"""Data from --seed: chip_smoke.py's HTTP-access rows, laid out by a config
file.  (Not vlogsgenerator's field mix: see the config's `assumed`.)

Copied in shape from chip_smoke.py (`row_hash`/`row_fields`) and bench.py
(columnar part build): every field of row i is a slice of one splitmix64
hash of (i, seed), so any range of rows can be made independently and the
child that builds the storage and the reference that checks the answers
agree on the data without sharing a byte.  numpy only: the parent imports
this, and the parent never touches jax.

Row i (global index, parts in the config's order):
  _time  day start + (i - first row of the day) * step of the day
  app    "app<i % streams>"            (the one stream field)
  _msg   "<VERB> /api/items/<item> status=<200|500> dur=<dur>ms msg=<WORD>"
  trace  "tok<0..499999>"              dur  "<0..906>"      seq  "<i>"
"""

import json
import time

import numpy as np

NS = 1_000_000_000
REHEARSAL_SCALE = 0.01      # a rehearsal's share of every part's rows
VERBS = ["GET", "POST", "PUT", "DELETE"]
WORDS = ["ok", "cache miss", "retry", "connection reset by peer",
         "deadline exceeded", "deadline extended", "flushed wal segment",
         "request completed"]
TRACE_CARD = 500_000
_M64 = (1 << 64) - 1


def row_hash(idx: np.ndarray, seed: int) -> np.ndarray:
    """splitmix64 of (row index, seed)."""
    with np.errstate(over="ignore"):
        z = idx.astype(np.uint64) + np.uint64(
            (seed * 0x9E3779B97F4A7C15 + 0x632BE59BD9B4E019) & _M64)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def row_fields(idx: np.ndarray, seed: int) -> dict:
    """Integer columns of the rows `idx`: item, status500, dur, word, trace."""
    h = row_hash(idx, seed)
    u = np.uint64
    return {"item": (h % u(99991)).astype(np.int64),
            "status500": ((h >> u(17)) % u(7)) == 0,
            "dur": ((h >> u(20)) % u(907)).astype(np.int64),
            "word": ((h >> u(30)) % u(len(WORDS))).astype(np.int64),
            "trace": ((h >> u(40)) % u(TRACE_CARD)).astype(np.int64)}


def rfc3339(ns: int) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(ns // NS)) \
        + f".{ns % NS:09d}Z"


class Layout:
    """The config's part table: which rows lie in which part and when."""

    def __init__(self, config: dict, rows_scale: float = 1.0):
        self.streams = int(config["streams"])
        self.t0_ns = int(config["t0_unix_s"]) * NS
        self.parts = []          # dicts: day, lo, hi, t_min, t_max
        self.days = []           # dicts: day, lo, hi, start_ns, step_ns
        lo = 0
        for d in config["days"]:
            sizes = [self._scaled(r, rows_scale) for r in d["parts"]]
            n = sum(sizes)
            start = self.t0_ns + int(d["day"]) * 86400 * NS
            step = int(d["span_s"]) * NS // n
            self.days.append({"day": int(d["day"]), "lo": lo, "hi": lo + n,
                              "start_ns": start, "step_ns": step,
                              "fresh": bool(d.get("fresh", False))})
            for r in sizes:
                self.parts.append({
                    "day": int(d["day"]), "lo": lo, "hi": lo + r,
                    "t_min": start + (lo - self.days[-1]["lo"]) * step,
                    "t_max": start + (lo + r - 1 - self.days[-1]["lo"])
                    * step})
                lo += r
        self.rows = lo

    def _scaled(self, rows: int, scale: float) -> int:
        """A rehearsal's smaller part, still a whole number of rows a
        stream."""
        if scale == 1.0:
            return int(rows)
        return max(self.streams,
                   int(rows * scale) // self.streams * self.streams)

    def times(self, idx: np.ndarray) -> np.ndarray:
        out = np.empty(len(idx), dtype=np.int64)
        for d in self.days:
            m = (idx >= d["lo"]) & (idx < d["hi"])
            out[m] = d["start_ns"] + (idx[m] - d["lo"]) * d["step_ns"]
        return out

    def row_range(self, t0_ns: int, t1_ns: int) -> list:
        """[(lo, hi)] of the rows with t0 <= _time < t1, a range a day."""
        out = []
        for d in self.days:
            n = d["hi"] - d["lo"]
            a = -((d["start_ns"] - t0_ns) // d["step_ns"])   # ceil
            b = -((d["start_ns"] - t1_ns) // d["step_ns"])
            a, b = max(0, min(n, a)), max(0, min(n, b))
            if b > a:
                out.append((d["lo"] + a, d["lo"] + b))
        return out

    def region(self, name: str) -> tuple:
        """(lo, hi) row range of `bulk` (days not fresh), `fresh` or `all`."""
        if name == "all":
            return 0, self.rows
        sel = [d for d in self.days if d["fresh"] == (name == "fresh")]
        return sel[0]["lo"], sel[-1]["hi"]

    def span(self, name: str) -> tuple:
        """(first, last) _time of a region, ns."""
        lo, hi = self.region(name)
        t = self.times(np.array([lo, hi - 1], dtype=np.int64))
        return int(t[0]), int(t[1])


class Text:
    """The string columns of rows, rendered with numpy tables: fixed-width
    byte strings (dtype S, NUL padded), no Python string per row."""

    def __init__(self):
        items = np.arange(99991).astype("S5")
        self.prefix = np.strings.add(
            np.array([v + " /api/items/" for v in VERBS], "S18")[:, None],
            items[None, :]).ravel()
        self.durs = np.arange(907).astype("S3")
        self.toks = np.strings.add(b"tok", np.arange(TRACE_CARD).astype("S6"))
        self._suffix = {}

    def msg(self, idx: np.ndarray, f: dict, end: str = "") -> np.ndarray:
        """`_msg` of the rows, each followed by `end`."""
        if end not in self._suffix:
            self._suffix[end] = np.array(
                [f" status={s} dur={d}ms msg={w}{end}"
                 for s in (200, 500) for d in range(907) for w in WORDS], "S")
        pi = (idx & 3) * 99991 + f["item"]
        si = (f["status500"] * 907 + f["dur"]) * len(WORDS) + f["word"]
        return np.strings.add(self.prefix[pi], self._suffix[end][si])

    def columns(self, idx: np.ndarray, f: dict, streams: int) -> dict:
        """Every stored field of the rows but `_time`, in schema order."""
        return {"app": np.strings.add(b"app", (idx % streams).astype("S2")),
                "_msg": self.msg(idx, f),
                "trace": self.toks[f["trace"]],
                "dur": self.durs[f["dur"]],
                "seq": idx.astype("S10")}


def arena(col: np.ndarray) -> tuple:
    """A fixed-width S column as one dense arena: (bytes, offsets,
    lengths), the padding squeezed out."""
    lens = np.strings.str_len(col).astype(np.int64)
    width = col.dtype.itemsize
    u8 = col.view(np.uint8).reshape(len(col), width)
    raw = u8[np.arange(width)[None, :] < lens[:, None]].tobytes()
    offs = np.zeros(len(col), dtype=np.int64)
    np.cumsum(lens[:-1], out=offs[1:])
    return raw, offs, lens


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
