"""One stream's blocks of one part, built in a worker process of the child.

The storage's block build holds the interpreter lock for much of its time,
so the child builds the parts of the config's table in a few processes
(spawned, never forked; they import the storage's block build and nothing
of jax) and keeps for itself what needs the one Storage: buffering and
flushing.  A job is one stream of one part, because the block build cuts
each stream's rows into blocks on its own: the blocks are those of a build
of the whole part, and the first part is ready to flush after an eighth of
its build time.  Blocks come back pickled: numpy arrays, a few hundred MB
a run.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import numpy as np  # noqa: E402

import gen  # noqa: E402

_text = None


def stream_ids(layout: gen.Layout):
    """The stream ids and tags that normal ingestion mints for app0..N."""
    from victorialogs_tpu.storage.log_rows import LogRows, TenantID
    lr = LogRows(stream_fields=["app"])
    for k in range(layout.streams):
        lr.add(TenantID(0, 0), layout.t0_ns,
               [("app", f"app{k}"), ("_msg", "x")])
    return list(lr.stream_ids), list(lr.stream_tags_str)


def part_blocks(job: tuple) -> list:
    """(config, rows_scale, seed, part index, stream index) -> the blocks
    of that stream's rows in the part, through the storage's columnar
    ingest build (LogColumns over arenas, the shape the typed wire feeds
    must_add_columns)."""
    from victorialogs_tpu.storage.block_build import ArenaColumn
    from victorialogs_tpu.storage.log_rows import LogColumns, TenantID
    global _text
    config, rows_scale, seed, i, k = job
    layout = gen.Layout(config, rows_scale)
    part = layout.parts[i]
    if _text is None:
        _text = gen.Text()
    first = part["lo"] + (k - part["lo"]) % layout.streams
    idx = np.arange(first, part["hi"], layout.streams, dtype=np.int64)
    cols = _text.columns(idx, gen.row_fields(idx, seed), layout.streams)
    lc = LogColumns()
    g = lc.group(tuple(cols), (0,))
    refs = [lc.intern_stream(g, TenantID(0, 0), sid, tags)
            for sid, tags in zip(*stream_ids(layout))]
    g.ts = layout.times(idx)
    g.sref = np.asarray(refs, dtype=np.int64)[idx % layout.streams]
    g.cols = []
    for col in cols.values():
        raw, offs, lens = gen.arena(col)
        g.cols.append(ArenaColumn(raw, offs, lens, raw.decode("ascii")))
    lc.nrows = len(idx)
    return lc.build_blocks()
