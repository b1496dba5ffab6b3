"""Source kind `vltrace_attr`: a numeric attribute of the spans of one
name in the `?trace=1` trees.

spec: "span", "attr": the mean of that attribute over every such span of
the window that carries it (over spans, not over queries).  Absent when
no span carries it, as with a program that does not record it.
"""

from readers import vltrace_span


def values(node: dict, name: str, attr: str, out: list) -> None:
    if node.get("name") == name and attr in node.get("attrs", {}):
        out.append(float(node["attrs"][attr]))
    for c in node.get("children", []):
        values(c, name, attr, out)


def read(spec: dict, ctx: dict):
    vals = []
    for rec in ctx["records"]:
        t = vltrace_span.tree(rec)
        if t is not None:
            values(t, spec["span"], spec["attr"], vals)
    return sum(vals) / len(vals) if vals else None
