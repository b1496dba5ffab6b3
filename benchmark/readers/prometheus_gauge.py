"""Source kind `prometheus_gauge`: a /metrics gauge at the window's end,
summed over its labels (one series a device)."""


def read(spec: dict, ctx: dict):
    vals = [v for k, v in ctx["prom1"].items()
            if k == spec["series"] or k.startswith(spec["series"] + "{")]
    return sum(vals) if vals else None
