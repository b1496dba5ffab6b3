"""Source kind `prometheus_delta`: a /metrics counter, after minus before
the window; "per": "query" divides by the completed requests.  Series
with labels are summed over the labels."""


def total(prom: dict, series: str) -> float:
    return sum(v for k, v in prom.items()
               if k == series or k.startswith(series + "{"))


def read(spec: dict, ctx: dict):
    delta = total(ctx["prom1"], spec["series"]) \
        - total(ctx["prom0"], spec["series"])
    if spec.get("per") == "query":
        if not ctx["records"]:
            return None
        delta /= len(ctx["records"])
    return delta
