"""Source kind `vltrace_self`: the self time of named spans of the
`?trace=1` tree, what no child covers.

spec: "spans": the milliseconds a query spends in spans of those names
and in none of their children (each node's duration minus the union of
its children's extents, clipped to the node), summed over the tree, mean
over the queries.  A query whose tree has no such span spent 0 ms there.
"""

from readers import vltrace_span


def self_ms(node: dict) -> float:
    t0 = node["start_ms"]
    t1 = t0 + node["duration_ms"]
    covered, upto = 0.0, t0
    for s, e in sorted((c["start_ms"], c["start_ms"] + c["duration_ms"])
                       for c in node.get("children", [])):
        s, e = max(s, upto), min(e, t1)
        if e > s:
            covered += e - s
            upto = e
    return node["duration_ms"] - covered


def tree_self_ms(node: dict, names) -> float:
    own = self_ms(node) if node.get("name") in names else 0.0
    return own + sum(tree_self_ms(c, names)
                     for c in node.get("children", []))


def read(spec: dict, ctx: dict):
    names = set(spec["spans"])
    trees = [t for t in map(vltrace_span.tree, ctx["records"])
             if t is not None]
    if not trees:
        return None
    return sum(tree_self_ms(t, names) for t in trees) / len(trees)
