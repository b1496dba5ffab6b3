"""Source kind `xplane`: a field of the child's reduction of the
profiler's trace (benchmark/xplane.py).  Absent in a rehearsal, where no
device was traced."""


def read(spec: dict, ctx: dict):
    trace = ctx.get("trace") or {}
    return trace.get(spec["field"])
