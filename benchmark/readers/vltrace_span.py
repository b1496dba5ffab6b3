"""Source kind `vltrace_span`: the `?trace=1` span tree each request of
the traced run carries back (`{"_trace": ...}` as the last NDJSON line of
/query, `"trace"` in a /stats_query answer).

spec: "span": the milliseconds a query spends in spans of that name
(summed over the tree), mean over the queries; or "client_minus_root":
true, the client's service time minus the root span, mean per query.
A query whose tree has no such span spent 0 ms there.
"""

import json


def tree(rec: dict):
    body = rec["body"]
    if rec["req"]["endpoint"] == "stats_query":
        return json.loads(body).get("trace")
    last = body.rstrip().rsplit(b"\n", 1)[-1]
    if last.startswith(b'{"_trace"'):
        return json.loads(last)["_trace"]
    return None


def span_ms(node: dict, name: str) -> float:
    own = node["duration_ms"] if node.get("name") == name else 0.0
    return own + sum(span_ms(c, name) for c in node.get("children", []))


def read(spec: dict, ctx: dict):
    vals = []
    for rec in ctx["records"]:
        t = tree(rec)
        if t is None:
            continue
        if spec.get("client_minus_root"):
            vals.append((rec["done"] - rec["sent"]) * 1e3 - t["duration_ms"])
        else:
            vals.append(span_ms(t, spec["span"]))
    return sum(vals) / len(vals) if vals else None
