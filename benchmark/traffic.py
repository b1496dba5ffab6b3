"""The one traffic generator: a traffic file's parameters -> requests.

A traffic file (benchmark/traffic/<name>.json) holds the loop kind, the
rate or client count, the class rotation, and for each class a LogsQL
template with named placeholders plus the rule that fills each.  The
arrival instants, the class at each instant and where each request's time
window lies come from the file alone (`schedule_seed`, `rate_per_s`,
`rotation`), so every run of a cell offers the same instants to the same
classes over the same parts: the same work, whatever the seed.  `--seed`
makes the data and every other literal (phrases, tokens, aliases), so the
answers differ by seed and the work and its overlap pattern do not.

Placeholder kinds (a class's "params"):
  alias        a name unique to the request, so no two requests share a
               result-cache key
  choice       one of "values", drawn from the seed
  window       {name}_t0/{name}_t1: a time window of `span_frac` of the
               region's span, or `span_s` seconds; its start is drawn from
               the file's `schedule_seed`, not from --seed
  row_token    {name}: the word that finds the `field` value of a row
               drawn from the seed, and {name}_<suffix>: what selects that
               row's stream; a share `fresh_share` of them (which ones:
               from `schedule_seed`) from the fresh parts
  absent_token {name}: a word of the field's form that no row holds,
               {name}_<suffix>: a stream drawn from the seed
What a field's word looks like and which suffixes select a stream
(`row_token`, `absent_token`, `selector`) is the row schema's, and a schema
may bring further kinds under `PLACEHOLDERS`: the contract of a schema
module is gen.py's docstring.
"""

import functools
import random

from gen import NS, Layout, rfc3339, row_hash

import numpy as np

KINDS = ("alias", "choice", "window", "row_token", "absent_token")


def schedule(traffic: dict, seconds: float, rate: float | None = None):
    """[(due_s, class)] of an open-loop cell: Poisson gaps from the file's
    own seed, classes by rotation, so each class's count is exact."""
    rate = float(rate if rate is not None else traffic["rate_per_s"])
    rng = random.Random(int(traffic["schedule_seed"]))
    rot = traffic["rotation"]
    out, t, k = [], 0.0, 0
    while True:
        t += rng.expovariate(rate)
        if t >= seconds:
            return out
        out.append((t, rot[k % len(rot)]))
        k += 1


def _draw(seed: int, k: int, salt: int, n: int, off: int = 0) -> int:
    """A whole number in [0, n) from (seed, request, placeholder); `off`
    for a further draw of the same placeholder."""
    h = row_hash(np.array([k * 1009 + salt + off], dtype=np.uint64),
                 seed ^ 0x5BD1E995)
    return int(h[0] % np.uint64(n))


def _selector(layout: Layout, name: str, stream: int) -> dict:
    """{name}_<suffix> placeholders that select `stream`."""
    return {f"{name}_{suffix}": text for suffix, text in
            layout.schema.selector(stream, layout.config).items()}


def make_request(traffic: dict, layout: Layout, seed: int, k: int,
                 cls: str, alias_prefix: str = "q") -> dict:
    """Request k of class `cls`: the query text, its endpoint, and what
    the yardstick needs (time range, parameter values)."""
    spec = traffic["classes"][cls]
    schema, config = layout.schema, layout.config
    fixed = int(traffic["schedule_seed"])
    vals, t_range = {}, None
    for salt, (name, p) in enumerate(sorted(spec.get("params", {}).items())):
        kind = p["kind"]
        draw = functools.partial(_draw, seed, k, salt)
        if kind == "alias":
            vals[name] = f"{alias_prefix}{p.get('prefix', 'c')}{k}"
        elif kind == "choice":
            vals[name] = p["values"][draw(len(p["values"]))]
        elif kind == "window":
            first, last = layout.span(p.get("region", "bulk"))
            span = int(p["span_s"] * NS) if "span_s" in p \
                else int((last - first) * p["span_frac"])
            room = max(1, last - first - span)
            # whole milliseconds, so the text and the yardstick agree
            start = first + _draw(fixed, k, salt, room) // 1_000_000 * 1_000_000
            t_range = (start, start + span)
            vals[name + "_t0"] = rfc3339(start)
            vals[name + "_t1"] = rfc3339(start + span)
        elif kind == "row_token":
            fresh = _draw(fixed, k, salt + 101, 1000) < \
                int(1000 * p.get("fresh_share", 0.0))
            lo, hi = layout.region("fresh" if fresh else "bulk")
            row = lo + draw(hi - lo)
            vals[name] = schema.row_token(p["field"], row, seed, config)
            stream = schema.stream_of(np.array([row], dtype=np.int64), config)
            vals.update(_selector(layout, name, int(stream[0])))
        elif kind == "absent_token":
            vals[name] = schema.absent_token(p["field"], draw, config)
            vals.update(_selector(layout, name, draw(layout.streams, 7)))
        elif kind in getattr(schema, "PLACEHOLDERS", {}):
            vals.update(schema.PLACEHOLDERS[kind](name, p, draw, layout,
                                                  seed))
        else:
            raise ValueError(f"unknown placeholder kind {kind!r} in {cls}")
    return {"k": k, "cls": cls, "endpoint": spec["endpoint"],
            "query": spec["query"].format(**vals), "vals": vals,
            "t_range": t_range, "answer": spec["answer"]}


def check(traffic: dict, schema) -> None:
    """Raises where a class of the rotation names a placeholder kind that
    neither this file nor the schema has: before a child is started."""
    own = getattr(schema, "PLACEHOLDERS", {})
    for cls in sorted(set(traffic["rotation"])):
        for name, p in traffic["classes"][cls].get("params", {}).items():
            if p["kind"] not in KINDS and p["kind"] not in own:
                raise ValueError(
                    f"traffic {traffic['name']!r}, class {cls}: placeholder "
                    f"{name!r} is of kind {p['kind']!r}, which neither "
                    f"traffic.py nor schema {schema.__name__!r} defines")
