"""A share of one part's blocks, built in a worker process of the child.

The storage's block build holds the interpreter lock for much of its time,
so the child builds the parts of the config's table in a few processes
(spawned, never forked; they import the storage's block build and nothing
of jax) and keeps for itself what needs the one Storage: buffering and
flushing.  A job is a set of streams of one part (stream k falls to job
k % jobs; which rows those are is the schema's `stream_of`, see gen.py's
docstring for the contract of a schema module), because the block build
cuts each stream's rows into blocks on its own: the blocks are those of a
build of the whole part, and the first part is ready to flush after a
fraction of its build time.  Blocks come back pickled: numpy arrays, a
few hundred MB a run.
"""

import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import numpy as np  # noqa: E402

import gen  # noqa: E402

JOBS_A_PART = 8


def jobs(layout: gen.Layout) -> int:
    return min(layout.streams, JOBS_A_PART)


def stream_ids(layout: gen.Layout):
    """The stream ids and tags that normal ingestion mints for the
    schema's streams 0..N-1."""
    from victorialogs_tpu.storage.log_rows import LogRows, TenantID
    schema, config = layout.schema, layout.config
    lr = LogRows(stream_fields=list(schema.STREAM_FIELDS))
    for k in range(layout.streams):
        lr.add(TenantID(*schema.tenant(k, config)), layout.t0_ns,
               schema.stream_tags(k, config) + [(schema.MESSAGE_FIELD, "x")])
    return list(lr.stream_ids), list(lr.stream_tags_str)


def in_build_order(blocks: list) -> list:
    """The jobs' blocks of one part in the order a build of the whole
    part gives: by stream id (a stream's blocks come from one job, in
    time order, and the sort is stable)."""
    return sorted(blocks, key=lambda b: b.stream_id)


@functools.lru_cache(maxsize=2)
def _worker_state(config_json: str, rows_scale: float) -> tuple:
    """What every job of a configuration shares, made once a process: the
    schema module's lookup, its string tables, the stream ids."""
    config = json.loads(config_json)
    layout = gen.Layout(config, rows_scale)
    sids, tags = stream_ids(layout)
    return config, layout, layout.schema.Text(config), sids, tags


def part_blocks(job: tuple) -> list:
    """(config as JSON, rows_scale, seed, part index, job index) -> the
    blocks of that job's streams' rows in the part, through the storage's
    columnar ingest build (LogColumns over arenas, the shape the typed
    wire feeds must_add_columns)."""
    from victorialogs_tpu.storage.block_build import ArenaColumn
    from victorialogs_tpu.storage.log_rows import LogColumns, TenantID
    config_json, rows_scale, seed, i, j = job
    config, layout, text, sids, tags = _worker_state(config_json, rows_scale)
    schema, part = layout.schema, layout.parts[i]
    idx = np.arange(part["lo"], part["hi"], dtype=np.int64)
    stream = schema.stream_of(idx, config)
    mine = stream % jobs(layout) == j
    idx, stream = idx[mine], stream[mine]
    if not len(idx):
        return []
    cols = text.columns(idx, schema.row_fields(idx, seed, config))
    lc = LogColumns()
    names = tuple(cols)
    g = lc.group(names, tuple(names.index(f) for f in schema.STREAM_FIELDS))
    refs = np.full(layout.streams, -1, dtype=np.int64)
    for k in np.unique(stream).tolist():
        refs[k] = lc.intern_stream(g, TenantID(*schema.tenant(k, config)),
                                   sids[k], tags[k])
    g.ts = layout.times(idx)
    g.sref = refs[stream]
    g.cols = []
    for col in cols.values():
        raw, offs, lens = gen.arena(col)
        g.cols.append(ArenaColumn(raw, offs, lens, raw.decode("ascii")))
    lc.nrows = len(idx)
    return lc.build_blocks()
