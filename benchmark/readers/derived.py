"""Source kind `derived`: an expression over the run's named quantities
(setup_s, window_s, queries, rows_scanned, required_bytes, the device's
peaks from peaks.json, busy_s from the trace).  A quantity that was not
measured makes the metric absent, never 0."""


def read(spec: dict, ctx: dict):
    try:
        return eval(spec["expr"], {"__builtins__": {}}, dict(ctx["values"]))
    except (NameError, ZeroDivisionError):
        return None
