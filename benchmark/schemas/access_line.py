"""Row schema `access_line`: chip_smoke.py's HTTP-access rows.  (Not
vlogsgenerator's field mix: see the configuration's `assumed`.)

Moved here from gen.py, traffic.py, reference.py and partbuild.py byte for
byte (PR 28; benchmark/tests/test_schema_seam.py holds the proof): the
same constants, hash slices and string tables, so the same --seed gives
the same rows.  The contract of a schema module is gen.py's docstring.

Copied in shape from chip_smoke.py (`row_hash`/`row_fields`) and bench.py
(columnar part build): every field of row i is a slice of one splitmix64
hash of (i, seed), so any range of rows can be made independently.

Row i (global index, parts in the config's order):
  app    "app<i % streams>"            (the one stream field)
  _msg   "<VERB> /api/items/<item> status=<200|500> dur=<dur>ms msg=<WORD>"
  trace  "tok<0..499999>"              dur  "<0..906>"      seq  "<i>"
One tenant (0, 0); at most 100 streams (the tag is rendered in two digits).
"""

import numpy as np

from gen import row_hash

VERBS = ["GET", "POST", "PUT", "DELETE"]
WORDS = ["ok", "cache miss", "retry", "connection reset by peer",
         "deadline exceeded", "deadline extended", "flushed wal segment",
         "request completed"]
TRACE_CARD = 500_000

STREAM_FIELDS = ("app",)
MESSAGE_FIELD = "_msg"


# ---- streams and tenants ----

def streams(config: dict) -> int:
    n = int(config["streams"])
    if not 1 <= n <= 100:
        raise ValueError(f"schema access_line renders a stream as app<two "
                         f"digits>: {n} streams would share tags")
    return n


def stream_of(idx: np.ndarray, config: dict) -> np.ndarray:
    return idx % int(config["streams"])


def stream_tags(k: int, config: dict) -> list:
    return [("app", f"app{k}")]


def tenant(k: int, config: dict) -> tuple:
    return (0, 0)


# ---- rows ----

def row_fields(idx: np.ndarray, seed: int, config: dict) -> dict:
    """Integer columns of the rows `idx`: item, status500, dur, word, trace."""
    h = row_hash(idx, seed)
    u = np.uint64
    return {"item": (h % u(99991)).astype(np.int64),
            "status500": ((h >> u(17)) % u(7)) == 0,
            "dur": ((h >> u(20)) % u(907)).astype(np.int64),
            "word": ((h >> u(30)) % u(len(WORDS))).astype(np.int64),
            "trace": ((h >> u(40)) % u(TRACE_CARD)).astype(np.int64)}


class Text:
    """The string columns of rows, rendered with numpy tables: fixed-width
    byte strings (dtype S, NUL padded), no Python string per row."""

    def __init__(self, config: dict):
        self.streams = streams(config)
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

    def text(self, field: str, idx: np.ndarray, f: dict,
             end: str = "") -> np.ndarray:
        if field != MESSAGE_FIELD:
            raise ValueError(f"schema access_line: no text filter reads "
                             f"{field!r}, only {MESSAGE_FIELD!r}")
        return self.msg(idx, f, end)

    def columns(self, idx: np.ndarray, f: dict) -> dict:
        """Every stored field of the rows but `_time`, in schema order."""
        return {"app": np.strings.add(b"app",
                                      (idx % self.streams).astype("S2")),
                "_msg": self.msg(idx, f),
                "trace": self.toks[f["trace"]],
                "dur": self.durs[f["dur"]],
                "seq": idx.astype("S10")}


# ---- traffic placeholders ----

def row_token(field: str, row: int, seed: int, config: dict) -> str:
    f = row_fields(np.array([row], dtype=np.int64), seed, config)
    return f"tok{int(f[field][0])}"


def absent_token(field: str, draw, config: dict) -> str:
    return f"tok{TRACE_CARD + draw(400_000)}"


def selector(stream: int, config: dict) -> dict:
    return {"app": f"app{stream}"}


# ---- reference operators ----

def _where_stream(blk: dict, a: int, b: int, args: list,
                  config: dict) -> np.ndarray:
    return stream_of(blk["idx"][a:b], config) == int(args[0][3:])


def _where_token(blk: dict, a: int, b: int, args: list,
                 config: dict) -> np.ndarray:
    # every `trace` value is one word "tok<n>", so a word filter on the
    # field is equality with it
    field, tok = args
    n = int(tok[3:]) if tok[3:].isdigit() else -1
    return blk[field][a:b] == n


class _CountUniqStream:
    def __init__(self, config: dict):
        self.config, self.seen = config, set()

    def add(self, blk: dict, a: int, b: int, mask: np.ndarray) -> None:
        idx = blk["idx"][a:b][mask]
        self.seen.update(np.unique(stream_of(idx, self.config)).tolist())

    def value(self) -> int:
        return len(self.seen)


WHERE = {"stream": _where_stream, "token": _where_token}
STATS = {"count_uniq_stream": _CountUniqStream}
