"""The bytes a query had to read, from the config's part table alone.

For one completed request: the sum, over the parts that its time filter
cannot exclude, of the part's rows times the staged width of every column
its leaves read (the class's `columns_read`; widths from the config's
`staged_width`).  A stream filter excludes no part here, because every
part holds every stream.  Never from a counter in the program: the same
question costs the same bytes whatever implements the scan, so a share of
the roofline built on this number moves only when the time does.  The
bound that applies to a scan is bytes over HBM bandwidth; a scan's
arithmetic is far below the chip's FLOP/s peak.
"""


def required(config: dict, layout, cls_spec: dict, t_range) -> dict:
    """{"rows": ..., "bytes": ...} that one request of the class has to
    scan, given its time range (None: every row)."""
    width = sum(int(config["staged_width"][c])
                for c in cls_spec["columns_read"])
    rows = 0
    for p in layout.parts:
        if t_range is None or (p["t_max"] >= t_range[0]
                               and p["t_min"] < t_range[1]):
            rows += p["hi"] - p["lo"]
    return {"rows": rows, "bytes": rows * width}
