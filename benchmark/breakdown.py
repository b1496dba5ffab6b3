"""The `breakdown` of a traced run: the device operations that took most
time, and the longest idle gaps of the busiest device named by what the
host was doing, which here is the classes of the requests in flight when
the gap began (the parent's records, on the wall clock the child's marker
ties to the trace)."""

import time


def build(trace: dict, records: list) -> dict:
    # the parent's monotonic clock -> unix ns
    shift = time.time_ns() - int(time.monotonic() * 1e9)
    spans = sorted((int(r["sent"] * 1e9) + shift, int(r["done"] * 1e9) + shift,
                    r["req"]["cls"]) for r in records)
    named = {}
    for gap_start, length in trace.get("gaps_unix_ns", []):
        live = sorted({cls for s, e, cls in spans if s <= gap_start < e})
        name = "request_in_flight:" + "_".join(live) if live \
            else "no_request_in_flight"
        named[name] = named.get(name, 0.0) + length / 1e9
    gaps = sorted(named.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": trace["device_ops"],
            "idle_gaps": [[k, v] for k, v in gaps]}
