"""Data from --seed, laid out by a configuration file; rows by a schema module.

What is generic lives here: the splitmix64 row hash, the part table
(`Layout`: days, parts, regions, which rows lie when), the arena form of a
column, and the loader of the row schema a configuration names.  numpy and
the standard library only: the parent imports this, and the parent never
touches jax.

**The contract of a schema module**, `schemas/<name>.py` beside the
`configs/` directory of the configuration whose `"schema"` key names it.
It owns every field name, string table and stream rule; the child, its
build workers, the reference, control.py, sweep.py and the tests' stand-in
all reach it through `load_schema(config)`, once a process, over a
configuration read with `load_config(path)`.  It imports
numpy, the standard library and this module's generic helpers, nothing of
the program.  Every field of row i derives from `row_hash(i, seed)`, so
any range of rows can be made independently and the child that builds the
storage and the reference that checks the answers agree on the data
without sharing a byte.  All of it vectorised: no call a row.

  STREAM_FIELDS            names of the stream fields, in column order
  MESSAGE_FIELD            the field a two-element ["phrase", text] means
  streams(config)          how many streams
  stream_of(idx, config)   the stream (0..streams-1) of each row
  stream_tags(k, config)   [(field, value)] of stream k
  tenant(k, config)        (account, project) of stream k
  row_fields(idx, seed, config)   {name: integer column} of the rows
  Text(config)             the renderer, its tables built once a process:
    .columns(idx, f)         every stored field but `_time` as fixed-width
                             S arrays, in schema order (f: row_fields)
    .text(field, idx, f, end)  a field a text filter may name, each row
                             followed by `end`
  row_token(field, row, seed, config)   the word a filter names to find
                             that field's value in row `row`
  absent_token(field, draw, config)     a word of that form no row holds;
                             draw(n, off=0) is a whole number in [0, n)
                             from the seed, the request and the placeholder;
                             `off` makes a further, independent draw
  selector(stream, config) {suffix: text}: a request's placeholder
                             {name}_{suffix} that selects the stream
  WHERE   {op: fn(blk, a, b, args, config) -> bool mask of rows [a, b)
          of the block}; blk is row_fields' dict plus "idx"
  STATS   {fn: class(config) with .add(blk, a, b, mask) and .value()}
  PLACEHOLDERS (optional)  {kind: fn(name, params, draw, layout, seed)
          -> {placeholder: text}}: traffic placeholder kinds of its own
  ANSWERS (optional)  {kind: fn(reference, request, spec) -> normal-form
          rows}: answer kinds of its own (see reference.Reference.matches)

Row i (global index, parts in the config's order) has
  _time  day start + (i - first row of the day) * step of the day
and the fields its schema gives it.
"""

import importlib.util
import json
import os
import time

import numpy as np

NS = 1_000_000_000
REHEARSAL_SCALE = 0.01      # a rehearsal's share of every part's rows
SCHEMA_NAMES = ("STREAM_FIELDS", "MESSAGE_FIELD", "streams", "stream_of",
                "stream_tags", "tenant", "row_fields", "Text", "row_token",
                "absent_token", "selector", "WHERE", "STATS")
_M64 = (1 << 64) - 1
_schemas = {}               # path -> module: one load a process


def row_hash(idx: np.ndarray, seed: int) -> np.ndarray:
    """splitmix64 of (row index, seed)."""
    with np.errstate(over="ignore"):
        z = idx.astype(np.uint64) + np.uint64(
            (seed * 0x9E3779B97F4A7C15 + 0x632BE59BD9B4E019) & _M64)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def rfc3339(ns: int) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(ns // NS)) \
        + f".{ns % NS:09d}Z"


class SchemaError(ValueError):
    """A configuration names no schema, or one that cannot be used."""


def load_config(path: str) -> dict:
    """A configuration file, the one way to read one: with the directory
    its `configs/` lies in under "_dir", beside which its schema and its
    traffic files are found.  A configuration made as a dict says "_dir"
    itself; `load_schema` refuses one that has none."""
    config = load_json(path)
    config["_dir"] = os.path.dirname(os.path.dirname(os.path.abspath(path)))
    return config


def load_schema(config: dict):
    """The schema module that `config` names (the contract: this file's
    docstring).  No default, of the name or of the place: a configuration
    says which rows it holds and (load_config) where its files lie."""
    name = config.get("schema")
    if not isinstance(name, str) or not name:
        raise SchemaError(
            f"configuration {config.get('name')!r} names no row schema: it "
            f"needs a \"schema\" key, the name of a module under schemas/")
    if "_dir" not in config:
        raise SchemaError(
            f"configuration {config.get('name')!r} does not say where its "
            f"schemas/ directory lies: read it with gen.load_config, or "
            f"give a dict \"_dir\"")
    path = os.path.join(config["_dir"], "schemas", name + ".py")
    mod = _schemas.get(path)
    if mod is None:
        if not os.path.isfile(path):
            raise SchemaError(f"schema {name!r} of configuration "
                              f"{config.get('name')!r}: no module at {path}")
        spec = importlib.util.spec_from_file_location("schema_" + name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        missing = [n for n in SCHEMA_NAMES if not hasattr(mod, n)]
        if missing:
            raise SchemaError(f"schema {name!r} at {path} lacks "
                              f"{', '.join(missing)}")
        _schemas[path] = mod
    return mod


class Layout:
    """The config's part table: which rows lie in which part and when;
    `schema` and `config` ride along for whoever makes or reads rows."""

    def __init__(self, config: dict, rows_scale: float = 1.0):
        self.config, self.schema = config, load_schema(config)
        self.streams = int(self.schema.streams(config))
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
