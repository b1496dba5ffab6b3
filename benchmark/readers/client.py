"""Source kind `client`: the parent's own request records.

spec: "of" (latency_ms: done - due; service_ms: done - sent; late_ms:
sent - due), "stat" (p50, p95, p99, mean, max), optional "classes".
Percentiles are over every completed request of the window, by
statistics.quantiles(n=100, method='inclusive').
"""

import statistics


def values(spec: dict, ctx: dict) -> list:
    a, b = {"latency_ms": ("done", "due"), "service_ms": ("done", "sent"),
            "late_ms": ("sent", "due")}[spec["of"]]
    classes = spec.get("classes")
    return [(r[a] - r[b]) * 1e3 for r in ctx["records"]
            if classes is None or r["req"]["cls"] in classes]


def stat(vals: list, name: str):
    if not vals:
        return None
    if name == "mean":
        return statistics.fmean(vals)
    if name == "max":
        return max(vals)
    if len(vals) == 1:
        return vals[0]
    return statistics.quantiles(vals, n=100, method="inclusive")[
        int(name[1:]) - 1]


def read(spec: dict, ctx: dict):
    return stat(values(spec, ctx), spec["stat"])
