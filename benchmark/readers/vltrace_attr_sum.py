"""Source kind `vltrace_attr_sum`: a numeric attribute summed over the
spans of some names in each `?trace=1` tree, mean over the queries.

spec: "spans" (names), "attr".  A query whose tree has none of those
spans, or none that carries the attribute, reads 0.  Absent when no span
of the window carries the attribute, as with a program that does not
record it.
"""

from readers import vltrace_span


def tree_sum(node: dict, names, attr: str, seen: list) -> float:
    """The attribute summed over the tree's spans named in `names`;
    appends to `seen` for each such span that carries it."""
    own = 0.0
    if node.get("name") in names and attr in node.get("attrs", {}):
        own = float(node["attrs"][attr])
        seen.append(own)
    return own + sum(tree_sum(c, names, attr, seen)
                     for c in node.get("children", []))


def read(spec: dict, ctx: dict):
    names = set(spec["spans"])
    seen = []
    sums = [tree_sum(t, names, spec["attr"], seen)
            for t in map(vltrace_span.tree, ctx["records"])
            if t is not None]
    if not seen:
        return None
    return sum(sums) / len(sums)
