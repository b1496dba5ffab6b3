"""Source kind `vltrace_tail_attr`: a numeric attribute of the slowest
requests' `?trace=1` trees.

spec: "span", "attr", "quantile": the attribute summed over the spans of
that name in each tree, mean over the requests whose latency (done −
due, as readers/client.py computes it) is at or above the window's
quantile of it (statistics.quantiles(n=100, method='inclusive'), as the
client reader's percentiles).  A tail request whose tree has no such
span, or none that carries the attribute, reads 0.  Absent when no span
of the window carries the attribute.
"""

from readers import client, vltrace_attr_sum, vltrace_span


def read(spec: dict, ctx: dict):
    names = {spec["span"]}
    lat = client.values({"of": "latency_ms"}, ctx)
    cut = client.stat(lat, "p%d" % round(spec["quantile"] * 100))
    seen, tail = [], []
    for rec, ms in zip(ctx["records"], lat):
        t = vltrace_span.tree(rec)
        if t is None:
            continue
        v = vltrace_attr_sum.tree_sum(t, names, spec["attr"], seen)
        if ms >= cut:
            tail.append(v)
    if not seen or not tail:
        return None
    return sum(tail) / len(tail)
