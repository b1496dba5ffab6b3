"""LogsQL filter tree: AST nodes + CPU block evaluation.

The 26 filter kinds of the reference (lib/logstorage/filter_*.go; interface
filter.go:8-20).  Each node implements:

  apply_to_block(bs, bm)  — AND itself into a numpy bool bitmap over one
                            storage block (reference applyToBlockSearch)
  apply_to_values(vals_fn, n) -> mask — re-filtering over in-pipeline rows
                            (reference applyToBlockResult), used by `filter` pipe
  needed_fields()         — referenced field names for column pushdown
  to_string()             — canonical LogsQL rendering

Bloom-assisted pruning: phrase/prefix/exact/sequence/contains filters probe
the per-column token bloom before touching values (reference
matchBloomFilterAllTokens — filter_phrase.go:302) — on TPU this same probe is
the cheap block kill-path.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field as dc_field

import numpy as np

from ..storage.bloom import bloom_contains_all
from ..storage.values_encoder import (VT_FLOAT64, VT_INT64, VT_IPV4,
                                      VT_TIMESTAMP_ISO8601, VT_UINT8,
                                      VT_UINT16, VT_UINT32, VT_UINT64,
                                      VT_NAMES, VT_STRING, VT_DICT)
from ..utils.hashing import cached_token_hashes
from ..utils.tokenizer import tokenize_string
from ..engine.block_search import BlockSearch, visit_values
from .matchers import (is_word_char, match_any_case_phrase,
                       match_any_case_prefix, match_exact_prefix,
                       match_ipv4_range, match_len_range, match_phrase,
                       match_prefix, match_range, match_sequence,
                       match_string_range, parse_ipv4, parse_number)

_NUMERIC_VTS = (VT_UINT8, VT_UINT16, VT_UINT32, VT_UINT64, VT_INT64,
                VT_FLOAT64)


def quote_str(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _q(field: str) -> str:
    return f"{field}:" if field else ""


class Filter:
    def apply_to_block(self, bs: BlockSearch, bm: np.ndarray) -> None:
        raise NotImplementedError

    def apply_to_values(self, get_values, nrows: int) -> np.ndarray:
        """Evaluate over arbitrary row values: get_values(field)->list[str]."""
        raise NotImplementedError

    def needed_fields(self) -> set:
        return set()

    def to_string(self) -> str:
        raise NotImplementedError

    def __repr__(self):
        return f"<{type(self).__name__} {self.to_string()}>"


def _bloom_prunes(bs: BlockSearch, fld: str, f) -> bool:
    """True if the column bloom proves no row can match (all tokens of
    filter `f` are required); token hashes memoized on the filter so a
    query hashes them once, not once per block."""
    tokens = f._tokens()
    if not tokens:
        return False
    words = bs.bloom(fld)
    if words is None or words.shape[0] == 0:
        return False
    return not bloom_contains_all(words, cached_token_hashes(f, tokens))


def canonical_field(field: str) -> str:
    """Empty field name targets the message column (reference
    getCanonicalColumnName — a bare `foo` searches `_msg`)."""
    return field or "_msg"


def iter_and_path_token_leaves(f):
    """Yield (field, tokens, leaf) for bloom-prunable leaves on the
    top-level AND path.

    These leaves match nothing anywhere their required word tokens are
    absent, so a part whose aggregate filter (storage/filterbank.py)
    proves a token absent from EVERY block can be skipped outright —
    the per-block kill-path would have zeroed each block one by one.
    Only FilterAnd is recursed: under OR/NOT a leaf's emptiness doesn't
    imply the tree's.
    """
    if isinstance(f, FilterAnd):
        for sub in f.filters:
            yield from iter_and_path_token_leaves(sub)
    elif isinstance(f, _ValuePredFilter):
        toks = f._tokens()
        if toks:
            yield canonical_field(f.field), toks, f


def filter_plan_tree(f) -> dict:
    """Compact JSON-ready view of a filter tree for the EXPLAIN plan
    (obs/explain.py): operator kind, target field, and — on
    bloom-prunable leaves — the required word tokens the part-aggregate
    kill path (storage/filterbank.aggregate_kill_leaf) can cite when
    it kills a part.  Purely descriptive: no evaluation, no token
    hashing."""
    kind = type(f).__name__.removeprefix("Filter").lower() or "filter"
    if isinstance(f, (FilterAnd, FilterOr)):
        return {"op": kind,
                "children": [filter_plan_tree(s) for s in f.filters]}
    if isinstance(f, FilterNot):
        return {"op": "not", "children": [filter_plan_tree(f.inner)]}
    node: dict = {"op": kind, "filter": f.to_string()}
    if isinstance(f, FilterTime):
        node["min_ts"] = f.min_ts
        node["max_ts"] = f.max_ts
        return node
    fld = getattr(f, "field", None)
    if fld is not None:
        node["field"] = canonical_field(fld)
    if isinstance(f, _ValuePredFilter):
        toks = f._tokens()
        if toks:
            # the tokens whose provable absence kills blocks (bloom
            # plane) and whole parts (Bloofi-style aggregate)
            node["prune_tokens"] = list(toks)
    return node


def _native_scan_ops(col, ops, combine: str):
    """AND/OR native scans over one column; None if any scan unavailable
    (caller falls back to the per-row Python path)."""
    from .. import native
    acc = None
    for op in ops:
        nb = native.phrase_scan_native(col.arena, col.offsets,
                                       col.lengths, *op)
        if nb is None:
            return None
        if acc is None:
            acc = nb
        elif combine == "and":
            acc &= nb
        else:
            acc |= nb
        if combine == "and" and not acc.any():
            break
    return acc


def _any_case_scan(col, phrase_lower: str, mode: int, st: bool,
                   et: bool, pred, bm) -> bool:
    """Case-insensitive native scan: ASCII-lower a copy of the arena and
    scan it; rows containing non-ASCII bytes verify through pred (their
    unicode case folding can differ, e.g. 'İ').lower()).  Returns False
    to fall back entirely."""
    if not phrase_lower.isascii() or not phrase_lower:
        return False
    from .. import native
    arena = col.arena
    low = arena.copy()
    up = (low >= 65) & (low <= 90)
    low[up] += 32
    nb = native.phrase_scan_native(low, col.offsets, col.lengths,
                                   phrase_lower.encode(), mode, st, et)
    if nb is None:
        return False
    highs = np.zeros(arena.shape[0] + 1, dtype=np.int64)
    np.cumsum(arena >= 128, out=highs[1:])
    offs = col.offsets
    rowhigh = (highs[offs + col.lengths] - highs[offs]) > 0
    bm &= nb | rowhigh
    check = bm & rowhigh
    if check.any():
        _native_verify(col, check, pred)
        bm &= ~rowhigh | check
    return True


def _native_verify(col, bm, pred) -> None:
    """pred() survivors of a native prefilter, decoded row-by-row."""
    arena, offs, lens = col.arena, col.offsets, col.lengths
    for i in np.nonzero(bm)[0]:
        o = int(offs[i])
        v = arena[o:o + int(lens[i])].tobytes().decode("utf-8", "replace")
        if not pred(v):
            bm[i] = False


class _ValuePredFilter(Filter):
    """Base for single-field filters evaluated as a per-value predicate."""

    field: str

    def _pred(self, v: str) -> bool:
        raise NotImplementedError

    def _tokens(self) -> list[str]:
        return []

    def _scan_spec(self) -> tuple | None:
        """(pattern_bytes, mode, starts_tok, ends_tok) for the native
        arena scan, or None to stay on the per-row Python path.  Modes
        mirror tpu/kernels.py; the Python matchers remain the oracle
        (randomized parity in tests/test_native.py)."""
        return None

    def _multi_scan_spec(self) -> tuple | None:
        """(ops, combine, verify) for multi-pattern native scans:
        ops = [(pattern_bytes, mode, starts_tok, ends_tok)], combine in
        {'and','or'}, verify => re-check survivors with _pred (mirrors
        the device leaf plans in tpu/batch.py)."""
        return None

    @staticmethod
    def _scan_column(bs: BlockSearch, fld: str):
        """The VT_STRING column eligible for a native arena scan, or None
        (special fields, consts, dict/numeric encodings stay on the
        per-value Python path that visit_values optimizes already)."""
        if fld in ("_time", "_stream", "_stream_id") or \
                fld in bs.consts():
            return None
        col = bs.column(fld)
        if col is None or col.vtype != VT_STRING:
            return None
        return col

    def apply_to_block(self, bs: BlockSearch, bm: np.ndarray) -> None:
        fld = canonical_field(self.field)
        if _bloom_prunes(bs, fld, self):
            bm[:] = False
            return
        # native arena scan: one memmem pass over a packed string column
        # instead of nrows Python predicate calls (host analogue of the
        # device kernel; ~20-50x on phrase/prefix/exact filters)
        spec = self._scan_spec()
        multi = None if spec is not None else self._multi_scan_spec()
        if spec is not None or multi is not None:
            col = self._scan_column(bs, fld)
            if col is not None:
                from .. import native
                if spec is not None:
                    nb = native.phrase_scan_native(
                        col.arena, col.offsets, col.lengths, *spec)
                    if nb is not None:
                        bm &= nb
                        return
                else:
                    ops, combine, verify = multi
                    acc = _native_scan_ops(col, ops, combine)
                    if acc is not None:
                        bm &= acc
                        if verify:
                            _native_verify(col, bm, self._pred)
                        return
        visit_values(bs, fld, bm, self._pred)

    def apply_to_values(self, get_values, nrows: int) -> np.ndarray:
        vals = get_values(canonical_field(self.field))
        return np.fromiter((self._pred(v) for v in vals), dtype=bool,
                           count=nrows)

    def needed_fields(self) -> set:
        return {canonical_field(self.field)}


# ---------------- composite filters ----------------

@dataclass(repr=False)
class FilterAnd(Filter):
    filters: list

    def apply_to_block(self, bs, bm):
        for f in self.filters:
            if not bm.any():
                return
            f.apply_to_block(bs, bm)

    def apply_to_values(self, get_values, nrows):
        mask = np.ones(nrows, dtype=bool)
        for f in self.filters:
            mask &= f.apply_to_values(get_values, nrows)
        return mask

    def needed_fields(self):
        out = set()
        for f in self.filters:
            out |= f.needed_fields()
        return out

    def to_string(self):
        parts = []
        for f in self.filters:
            s = f.to_string()
            if isinstance(f, FilterOr):
                s = f"({s})"
            parts.append(s)
        return " ".join(parts)


@dataclass(repr=False)
class FilterOr(Filter):
    filters: list

    def apply_to_block(self, bs, bm):
        acc = np.zeros(bs.nrows, dtype=bool)
        for f in self.filters:
            sub = bm.copy()
            f.apply_to_block(bs, sub)
            acc |= sub
            if acc.all():
                break
        bm &= acc

    def apply_to_values(self, get_values, nrows):
        mask = np.zeros(nrows, dtype=bool)
        for f in self.filters:
            mask |= f.apply_to_values(get_values, nrows)
        return mask

    def needed_fields(self):
        out = set()
        for f in self.filters:
            out |= f.needed_fields()
        return out

    def to_string(self):
        return " or ".join(
            f"({f.to_string()})" if isinstance(f, FilterOr) else f.to_string()
            for f in self.filters)


@dataclass(repr=False)
class FilterNot(Filter):
    inner: Filter

    def apply_to_block(self, bs, bm):
        sub = new_full_bitmap(bs.nrows)
        self.inner.apply_to_block(bs, sub)
        bm &= ~sub

    def apply_to_values(self, get_values, nrows):
        return ~self.inner.apply_to_values(get_values, nrows)

    def needed_fields(self):
        return self.inner.needed_fields()

    def to_string(self):
        s = self.inner.to_string()
        if isinstance(self.inner, (FilterAnd, FilterOr)):
            s = f"({s})"
        return f"!{s}"


def new_full_bitmap(n: int) -> np.ndarray:
    return np.ones(n, dtype=bool)


@dataclass(repr=False)
class FilterNoop(Filter):
    """Matches everything: `*`."""

    def apply_to_block(self, bs, bm):
        pass

    def apply_to_values(self, get_values, nrows):
        return np.ones(nrows, dtype=bool)

    def to_string(self):
        return "*"


@dataclass(repr=False)
class FilterNone(Filter):
    """Matches nothing (used for pruned subtrees)."""

    def apply_to_block(self, bs, bm):
        bm[:] = False

    def apply_to_values(self, get_values, nrows):
        return np.zeros(nrows, dtype=bool)

    def to_string(self):
        return "_none_"


# ---------------- word / phrase family ----------------

@dataclass(repr=False)
class FilterPhrase(_ValuePredFilter):
    field: str
    phrase: str

    def _pred(self, v):
        return match_phrase(v, self.phrase)

    def _scan_spec(self):
        if not self.phrase:
            return None
        return (self.phrase.encode("utf-8"), 0,
                is_word_char(self.phrase[0]),
                is_word_char(self.phrase[-1]))

    def _tokens(self):
        return tokenize_string(self.phrase)

    def to_string(self):
        return f"{_q(self.field)}{quote_str(self.phrase)}"


@dataclass(repr=False)
class FilterPrefix(_ValuePredFilter):
    field: str
    prefix: str

    def _pred(self, v):
        return match_prefix(v, self.prefix)

    def _scan_spec(self):
        if not self.prefix:
            return None
        return (self.prefix.encode("utf-8"), 1,
                is_word_char(self.prefix[0]), False)

    def _tokens(self):
        # trailing partial token can't be bloom-probed
        # (reference getTokensSkipLast — filter_prefix.go:354)
        toks = tokenize_string(self.prefix)
        if toks and self.prefix and (self.prefix[-1].isalnum()
                                     or self.prefix[-1] == "_"
                                     or not self.prefix[-1].isascii()):
            toks = toks[:-1]
        return toks

    def to_string(self):
        return f"{_q(self.field)}{quote_str(self.prefix)}*"


@dataclass(repr=False)
class FilterExact(_ValuePredFilter):
    field: str
    value: str

    def _pred(self, v):
        return v == self.value

    def _scan_spec(self):
        if not self.value:
            return None
        return (self.value.encode("utf-8"), 3, False, False)

    def _tokens(self):
        return tokenize_string(self.value)

    def apply_to_block(self, bs, bm):
        # numeric-column prune: a typed numeric column only decodes to
        # numeric strings, so a non-numeric or out-of-range exact value
        # can't match any row
        meta = bs.column_meta(canonical_field(self.field))
        if meta is not None and meta["t"] in _NUMERIC_VTS:
            v = parse_number(self.value)
            if math.isnan(v) or not (meta["min"] <= v <= meta["max"]):
                bm[:] = False
                return
        super().apply_to_block(bs, bm)

    def to_string(self):
        return f"{_q(self.field)}={quote_str(self.value)}"


@dataclass(repr=False)
class FilterExactPrefix(_ValuePredFilter):
    field: str
    prefix: str

    def _pred(self, v):
        return match_exact_prefix(v, self.prefix)

    def _scan_spec(self):
        if not self.prefix:
            return None
        return (self.prefix.encode("utf-8"), 4, False, False)

    def _tokens(self):
        toks = tokenize_string(self.prefix)
        return toks[:-1] if toks else []

    def to_string(self):
        return f"{_q(self.field)}={quote_str(self.prefix)}*"


@dataclass(repr=False)
class FilterAnyCasePhrase(_ValuePredFilter):
    field: str
    phrase: str

    def __post_init__(self):
        self._lower = self.phrase.lower()

    def _pred(self, v):
        return match_any_case_phrase(v, self._lower)

    def apply_to_block(self, bs, bm):
        fld = canonical_field(self.field)
        col = self._scan_column(bs, fld)
        if col is not None and self._lower and \
                _any_case_scan(col, self._lower, 0,
                               is_word_char(self._lower[0]),
                               is_word_char(self._lower[-1]),
                               self._pred, bm):
            return
        visit_values(bs, fld, bm, self._pred)

    def to_string(self):
        return f"{_q(self.field)}i({quote_str(self.phrase)})"


@dataclass(repr=False)
class FilterAnyCasePrefix(_ValuePredFilter):
    field: str
    prefix: str

    def __post_init__(self):
        self._lower = self.prefix.lower()

    def _pred(self, v):
        return match_any_case_prefix(v, self._lower)

    def apply_to_block(self, bs, bm):
        fld = canonical_field(self.field)
        col = self._scan_column(bs, fld)
        if col is not None and self._lower and \
                _any_case_scan(col, self._lower, 1,
                               is_word_char(self._lower[0]), False,
                               self._pred, bm):
            return
        visit_values(bs, fld, bm, self._pred)

    def to_string(self):
        return f"{_q(self.field)}i({quote_str(self.prefix)}*)"


@dataclass(repr=False)
class FilterRegexp(_ValuePredFilter):
    field: str
    pattern: str

    def __post_init__(self):
        self._re = re.compile(self.pattern)
        self._substr_literals = regex_literal_runs(self.pattern)
        self._bloom_tokens = regex_literal_tokens(self.pattern)
        # `A.*B` with literal A and B: decided per row natively (same
        # predicate the device plan uses — tpu/batch.py device_plan)
        parts = self.pattern.split(".*")
        self._pair = None
        if len(parts) == 2 and all(p and re.escape(p) == p
                                   for p in parts):
            self._pair = (parts[0].encode("utf-8"),
                          parts[1].encode("utf-8"))

    def _pred(self, v):
        return self._re.search(v) is not None

    def _tokens(self):
        return self._bloom_tokens

    def apply_to_block(self, bs, bm):
        # native literal prefilter: every match must contain ALL the
        # regex's mandatory literal runs (filter_regexp.go:44-51), so one
        # memmem pass per run prunes candidates and re.search runs only
        # on survivors — decoded individually from the arena, never as a
        # whole-column string list
        fld = canonical_field(self.field)
        if _bloom_prunes(bs, fld, self):
            bm[:] = False
            return
        lits = [t for t in self._substr_literals if t]
        col = self._scan_column(bs, fld) if (lits or self._pair) else None
        if col is not None:
            from .. import native
            if self._pair is not None:
                got = native.ordered_pair_scan_native(
                    col.arena, col.offsets, col.lengths, *self._pair)
                if got is not None:
                    definite, verify = got
                    bm &= definite | verify
                    self._verify_rows(col, bm, verify)
                    return
            cand = _native_scan_ops(
                col, [(lit.encode("utf-8"), 2, False, False)
                      for lit in lits], "and")
            if cand is not None:
                bm &= cand
                self._verify_rows(col, bm, None)
                return
        visit_values(bs, fld, bm, self._pred)

    def _verify_rows(self, col, bm, only) -> None:
        """re.search survivors; only: optional mask restricting which set
        rows need verification (others are already definite matches)."""
        if only is None:
            _native_verify(col, bm, self._pred)
            return
        check = bm & only
        _native_verify(col, check, self._pred)  # clears failed rows
        bm &= ~only | check

    def to_string(self):
        return f"{_q(self.field)}~{quote_str(self.pattern)}"


def regex_literal_tokens(pattern: str) -> list[str]:
    """Extract word tokens that every matching string must contain.

    The reference derives mandatory literals from the regex parse tree
    (regexutil GetLiterals — filter_regexp.go:44-51) and skips the first/last
    token (they may be partial words).  We conservatively extract maximal
    literal runs outside any metacharacter scope, then drop first/last token
    of each run boundary the same way.  These are sound for BLOOM probes
    (which index whole words); for plain substring prefilters use
    regex_literal_runs, which keeps the full runs.
    """
    out = []
    for lit, drop_last, is_final in _regex_literal_parts(pattern):
        toks = tokenize_string(lit)
        if not toks:
            continue
        start = 1 if (lit and (lit[0].isalnum() or lit[0] == "_")) else 0
        end = len(toks)
        if drop_last or not is_final:
            end -= 1
        else:
            if lit and (lit[-1].isalnum() or lit[-1] == "_"):
                end -= 1
        out.extend(toks[start:end])
    return out


def regex_literal_runs(pattern: str) -> list[str]:
    """Maximal literal substrings every match must contain, UNtokenized.

    Unlike the bloom tokens above, partial words are fine here: a device
    substring scan for "dead" soundly prefilters `~"dead.*exceeded"`."""
    return [lit for lit, _d, _f in _regex_literal_parts(pattern) if lit]


def _regex_literal_parts(pattern: str) -> list[tuple[str, bool, bool]]:
    """Shared scanner: (literal_run, last_char_dropped, is_final) parts."""
    # Inline flags/groups like (?i) change matching semantics for the whole
    # pattern (case folding etc.), so any literal we extract could wrongly
    # prune via blooms — bail to "no mandatory tokens" (the reference parses
    # the regex tree and folds case; we stay conservative).
    if "(?" in pattern:
        return []
    literals = []
    cur = []
    i, n = 0, len(pattern)
    depth_unsafe = 0
    while i < n:
        c = pattern[i]
        if c == "\\":
            e = pattern[i + 1] if i + 1 < n else ""
            # control escapes denote real characters, not the escape letter
            ctrl = {"n": "\n", "t": "\t", "r": "\r", "f": "\f", "v": "\v",
                    "a": "\a", "0": "\0"}
            if e == "0" and i + 2 < n and pattern[i + 2] in "01234567":
                return []  # \0oo octal escape: stay conservative
            if e in ctrl:
                if depth_unsafe == 0:
                    cur.append(ctrl[e])
                i += 2
                continue
            # \xNN / \uNNNN / \UNNNNNNNN denote ONE character: decode it
            # (leaving the hex digits in the literal run silently pruned
            # real matches once this fed the native prefilter)
            if e in ("x", "u", "U"):
                width = {"x": 2, "u": 4, "U": 8}[e]
                hexs = pattern[i + 2:i + 2 + width]
                if len(hexs) != width or \
                        any(h not in "0123456789abcdefABCDEF"
                            for h in hexs):
                    return []  # malformed; re.compile rejects it anyway
                if depth_unsafe == 0:
                    cur.append(chr(int(hexs, 16)))
                i += 2 + width
                continue
            if e in "123456789":
                return []  # backreference: its text is unknown
            if e and e not in "wWdDsSbBAZ":
                if depth_unsafe == 0:
                    cur.append(e)
                i += 2
                continue
            # class escapes: unknown chars — break literal
            cur = _flush_literal(cur, literals, drop_last=True)
            i += 2
            continue
        if c in "|([{" :
            # alternation/group/class: everything inside is not mandatory
            if c == "|":
                if depth_unsafe == 0:
                    return []  # top-level alternation: nothing is mandatory
                i += 1
                continue
            if c == "{" and cur and depth_unsafe == 0:
                # quantifier may be {0,n}: the preceding char is optional
                cur.pop()
            cur = _flush_literal(cur, literals, drop_last=True)
            depth_unsafe += 1
            i += 1
            continue
        if c in ")]}":
            depth_unsafe = max(0, depth_unsafe - 1)
            cur = []
            i += 1
            continue
        if c in "*?+":
            # previous char is optional/repeated: drop it from the literal
            if cur and depth_unsafe == 0:
                cur.pop()
                cur = _flush_literal(cur, literals, drop_last=True)
            i += 1
            continue
        if c in ".^$":
            cur = _flush_literal(cur, literals, drop_last=True)
            i += 1
            continue
        if depth_unsafe == 0:
            cur.append(c)
        i += 1
    _flush_literal(cur, literals, drop_last=False, final=True)
    return literals


def _flush_literal(cur, literals, drop_last, final=False):
    if cur:
        literals.append(("".join(cur), drop_last, final))
    return []


# ---------------- multi-value filters ----------------

@dataclass(repr=False)
class FilterIn(_ValuePredFilter):
    field: str
    values: list
    subquery: object = None  # parsed Query, materialized by init_subqueries

    def __post_init__(self):
        self._set = set(self.values)

    def set_values(self, values):
        self.values = list(values)
        self._set = set(self.values)

    def _pred(self, v):
        return v in self._set

    def to_string(self):
        if self.subquery is not None:
            return f"{_q(self.field)}in({self.subquery.to_string()})"
        return f"{_q(self.field)}in({','.join(quote_str(v) for v in self.values)})"


@dataclass(repr=False)
class FilterContainsAll(_ValuePredFilter):
    field: str
    values: list
    subquery: object = None

    def set_values(self, values):
        self.values = list(values)

    def _pred(self, v):
        return all(match_phrase(v, p) for p in self.values)

    def _multi_scan_spec(self):
        if not self.values or any(not p for p in self.values):
            return None  # empty value: keep the Python semantics
        ops = [(p.encode("utf-8"), 0, is_word_char(p[0]),
                is_word_char(p[-1])) for p in self.values]
        return ops, "and", False

    def _tokens(self):
        out = []
        for p in self.values:
            out.extend(tokenize_string(p))
        return out

    def to_string(self):
        return (f"{_q(self.field)}contains_all("
                f"{','.join(quote_str(v) for v in self.values)})")


@dataclass(repr=False)
class FilterContainsAny(_ValuePredFilter):
    field: str
    values: list
    subquery: object = None

    def set_values(self, values):
        self.values = list(values)

    def _pred(self, v):
        return any(match_phrase(v, p) for p in self.values)

    def _multi_scan_spec(self):
        if not self.values or any(not p for p in self.values):
            return None
        ops = [(p.encode("utf-8"), 0, is_word_char(p[0]),
                is_word_char(p[-1])) for p in self.values]
        return ops, "or", False

    def to_string(self):
        return (f"{_q(self.field)}contains_any("
                f"{','.join(quote_str(v) for v in self.values)})")


@dataclass(repr=False)
class FilterSequence(_ValuePredFilter):
    field: str
    phrases: list

    def _pred(self, v):
        return match_sequence(v, self.phrases)

    def _multi_scan_spec(self):
        if not self.phrases or any(not p for p in self.phrases):
            return None
        # each phrase must appear at word boundaries (match_sequence uses
        # phrase_pos), so MODE_PHRASE prefilters are exact per phrase;
        # ORDER is checked by _pred on survivors when more than one
        ops = [(p.encode("utf-8"), 0, is_word_char(p[0]),
                is_word_char(p[-1])) for p in self.phrases]
        return ops, "and", len(self.phrases) > 1

    def _tokens(self):
        out = []
        for p in self.phrases:
            out.extend(tokenize_string(p))
        return out

    def to_string(self):
        return (f"{_q(self.field)}seq("
                f"{','.join(quote_str(v) for v in self.phrases)})")


# ---------------- range / numeric filters ----------------

@dataclass(repr=False)
class FilterRange(_ValuePredFilter):
    field: str
    min_value: float
    max_value: float
    repr_str: str = ""

    def _pred(self, v):
        return match_range(v, self.min_value, self.max_value)

    def apply_to_block(self, bs, bm):
        meta = bs.column_meta(canonical_field(self.field))
        if meta is not None and meta["t"] in _NUMERIC_VTS:
            # header-level prune + vectorized numeric compare
            if meta["max"] < self.min_value or meta["min"] > self.max_value:
                bm[:] = False
                return
            col = bs.column(canonical_field(self.field))
            nums = col.nums
            if nums.dtype == np.uint64:
                # integer-exact bounds: ceil the lower, floor the upper
                # (guarding inf: >x / <x filters carry infinite bounds)
                lo = 0 if self.min_value <= 0 else \
                    2**64 - 1 if math.isinf(self.min_value) else \
                    min(math.ceil(self.min_value), 2**64 - 1)
                hi = -1 if self.max_value < 0 else \
                    2**64 - 1 if math.isinf(self.max_value) else \
                    min(math.floor(self.max_value), 2**64 - 1)
                if lo > hi:
                    bm[:] = False
                    return
                mask = (nums >= np.uint64(lo)) & (nums <= np.uint64(hi))
            else:
                mask = (nums >= self.min_value) & (nums <= self.max_value)
            bm &= mask
            return
        super().apply_to_block(bs, bm)

    def to_string(self):
        if self.repr_str:
            return f"{_q(self.field)}{self.repr_str}"
        return f"{_q(self.field)}range[{self.min_value},{self.max_value}]"


@dataclass(repr=False)
class FilterStringRange(_ValuePredFilter):
    field: str
    min_value: str
    max_value: str
    repr_str: str = ""

    def _pred(self, v):
        return match_string_range(v, self.min_value, self.max_value)

    def to_string(self):
        if self.repr_str:
            return f"{_q(self.field)}{self.repr_str}"
        return (f"{_q(self.field)}string_range({quote_str(self.min_value)},"
                f"{quote_str(self.max_value)})")


@dataclass(repr=False)
class FilterLenRange(_ValuePredFilter):
    field: str
    min_len: int
    max_len: int

    def _pred(self, v):
        return match_len_range(v, self.min_len, self.max_len)

    def to_string(self):
        return f"{_q(self.field)}len_range({self.min_len},{self.max_len})"


@dataclass(repr=False)
class FilterIPv4Range(_ValuePredFilter):
    field: str
    min_value: int
    max_value: int

    def _pred(self, v):
        return match_ipv4_range(v, self.min_value, self.max_value)

    def apply_to_block(self, bs, bm):
        meta = bs.column_meta(canonical_field(self.field))
        if meta is not None and meta["t"] == VT_IPV4:
            col = bs.column(canonical_field(self.field))
            nums = col.nums
            bm &= (nums >= np.uint32(self.min_value)) & \
                  (nums <= np.uint32(self.max_value))
            return
        super().apply_to_block(bs, bm)

    def to_string(self):
        def ip(v):
            return f"{(v >> 24) & 255}.{(v >> 16) & 255}." \
                   f"{(v >> 8) & 255}.{v & 255}"
        return (f"{_q(self.field)}ipv4_range({ip(self.min_value)},"
                f"{ip(self.max_value)})")


@dataclass(repr=False)
class FilterValueType(Filter):
    field: str
    type_name: str

    def apply_to_block(self, bs, bm):
        if bs.value_type_name(canonical_field(self.field)) != self.type_name:
            bm[:] = False

    def apply_to_values(self, get_values, nrows):
        # in-pipeline values have lost their storage type; best effort: all
        # pass iff requesting 'string'
        keep = self.type_name == "string"
        return np.full(nrows, keep, dtype=bool)

    def needed_fields(self):
        return {canonical_field(self.field)}

    def to_string(self):
        return f"{_q(self.field)}value_type({self.type_name})"


# ---------------- cross-field filters ----------------

@dataclass(repr=False)
class FilterEqField(Filter):
    field: str
    other: str

    def apply_to_block(self, bs, bm):
        a = bs.values(canonical_field(self.field))
        b = bs.values(self.other)
        for i in np.nonzero(bm)[0]:
            if a[i] != b[i]:
                bm[i] = False

    def apply_to_values(self, get_values, nrows):
        a = get_values(self.field)
        b = get_values(self.other)
        return np.fromiter((x == y for x, y in zip(a, b)), dtype=bool,
                           count=nrows)

    def needed_fields(self):
        return {canonical_field(self.field), self.other}

    def to_string(self):
        return f"{_q(self.field)}eq_field({self.other})"


@dataclass(repr=False)
class FilterLeField(Filter):
    field: str
    other: str
    strict: bool = False  # True => lt_field

    def _cmp(self, x: str, y: str) -> bool:
        a, b = parse_number(x), parse_number(y)
        if not (math.isnan(a) or math.isnan(b)):
            return a < b if self.strict else a <= b
        return x < y if self.strict else x <= y

    def apply_to_block(self, bs, bm):
        a = bs.values(canonical_field(self.field))
        b = bs.values(self.other)
        for i in np.nonzero(bm)[0]:
            if not self._cmp(a[i], b[i]):
                bm[i] = False

    def apply_to_values(self, get_values, nrows):
        a = get_values(self.field)
        b = get_values(self.other)
        return np.fromiter((self._cmp(x, y) for x, y in zip(a, b)),
                           dtype=bool, count=nrows)

    def needed_fields(self):
        return {canonical_field(self.field), self.other}

    def to_string(self):
        fn = "lt_field" if self.strict else "le_field"
        return f"{_q(self.field)}{fn}({self.other})"


# ---------------- time / stream filters ----------------

@dataclass(repr=False)
class FilterTime(Filter):
    min_ts: int                      # inclusive, ns
    max_ts: int                      # inclusive, ns
    repr_str: str = ""

    def apply_to_block(self, bs, bm):
        if bs.part.block_min_ts(bs.block_idx) >= self.min_ts and \
           bs.part.block_max_ts(bs.block_idx) <= self.max_ts:
            return  # whole block inside the range
        ts = bs.timestamps()
        bm &= (ts >= self.min_ts) & (ts <= self.max_ts)

    def apply_to_values(self, get_values, nrows):
        from ..engine.block_result import parse_rfc3339
        vals = get_values("_time")
        out = np.zeros(nrows, dtype=bool)
        for i, v in enumerate(vals):
            t = parse_rfc3339(v)
            out[i] = t is not None and self.min_ts <= t <= self.max_ts
        return out

    def needed_fields(self):
        return {"_time"}

    def to_string(self):
        return f"_time:{self.repr_str}" if self.repr_str else \
            f"_time:[{self.min_ts},{self.max_ts}]"


@dataclass(repr=False)
class FilterDayRange(Filter):
    start_offset_ns: int   # offset into the day, inclusive
    end_offset_ns: int     # inclusive
    tz_offset_ns: int = 0
    repr_str: str = ""

    def apply_to_block(self, bs, bm):
        ts = bs.timestamps() + self.tz_offset_ns
        day_off = ts % (86400 * 1_000_000_000)
        bm &= (day_off >= self.start_offset_ns) & \
              (day_off <= self.end_offset_ns)

    def apply_to_values(self, get_values, nrows):
        from ..engine.block_result import parse_rfc3339
        vals = get_values("_time")
        out = np.zeros(nrows, dtype=bool)
        for i, v in enumerate(vals):
            t = parse_rfc3339(v)
            if t is None:
                continue
            off = (t + self.tz_offset_ns) % (86400 * 1_000_000_000)
            out[i] = self.start_offset_ns <= off <= self.end_offset_ns
        return out

    def needed_fields(self):
        return {"_time"}

    def to_string(self):
        return f"_time:day_range{self.repr_str}"


@dataclass(repr=False)
class FilterWeekRange(Filter):
    start_day: int   # 0=Sunday .. 6=Saturday, inclusive
    end_day: int
    tz_offset_ns: int = 0
    repr_str: str = ""

    def apply_to_block(self, bs, bm):
        ts = bs.timestamps() + self.tz_offset_ns
        # 1970-01-01 was a Thursday (weekday 4 with Sunday=0)
        days = ts // (86400 * 1_000_000_000)
        wd = (days + 4) % 7
        bm &= (wd >= self.start_day) & (wd <= self.end_day)

    def apply_to_values(self, get_values, nrows):
        from ..engine.block_result import parse_rfc3339
        vals = get_values("_time")
        out = np.zeros(nrows, dtype=bool)
        for i, v in enumerate(vals):
            t = parse_rfc3339(v)
            if t is None:
                continue
            wd = ((t + self.tz_offset_ns) // (86400 * 1_000_000_000) + 4) % 7
            out[i] = self.start_day <= wd <= self.end_day
        return out

    def needed_fields(self):
        return {"_time"}

    def to_string(self):
        return f"_time:week_range{self.repr_str}"


@dataclass(repr=False)
class FilterStream(Filter):
    """`{label="value", ...}` — resolved against the partition stream index."""

    stream_filter: object  # storage.stream_filter.StreamFilter

    def __post_init__(self):
        # per-partition resolution cache: id(partition) -> set[StreamID]
        self._resolved: dict = {}

    def resolve(self, partition, tenants) -> set:
        key = (id(partition), tuple(tenants))
        got = self._resolved.get(key)
        if got is None:
            got = set(partition.idb.search_stream_ids(list(tenants),
                                                      self.stream_filter))
            if len(self._resolved) > 64:
                self._resolved.clear()
            self._resolved[key] = got
        return got

    def apply_to_block(self, bs, bm):
        ctx = getattr(bs, "ctx", None)
        if ctx is None:
            return
        sids = self.resolve(ctx.partition, ctx.tenants)
        if bs.stream_id not in sids:
            bm[:] = False

    def apply_to_values(self, get_values, nrows):
        from ..storage.stream_filter import parse_stream_tags
        vals = get_values("_stream")
        out = np.zeros(nrows, dtype=bool)
        for i, v in enumerate(vals):
            out[i] = self.stream_filter.matches(parse_stream_tags(v))
        return out

    def needed_fields(self):
        return {"_stream"}

    def to_string(self):
        return self.stream_filter.to_string()


@dataclass(repr=False)
class FilterStreamID(Filter):
    stream_ids: list  # hex strings

    def __post_init__(self):
        self._set = set(self.stream_ids)

    def apply_to_block(self, bs, bm):
        if bs.stream_id.as_string() not in self._set:
            bm[:] = False

    def apply_to_values(self, get_values, nrows):
        vals = get_values("_stream_id")
        return np.fromiter((v in self._set for v in vals), dtype=bool,
                           count=nrows)

    def needed_fields(self):
        return {"_stream_id"}

    def to_string(self):
        if len(self.stream_ids) == 1:
            return f"_stream_id:{self.stream_ids[0]}"
        return "_stream_id:in(" + ",".join(self.stream_ids) + ")"
