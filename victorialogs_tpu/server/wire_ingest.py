"""Typed ingest wire format "i1": ONE LogRows frame at every hop.

Sibling of the SELECT wire "t1" (server/cluster.py framing section):
since format "i1" every insert hop — frontend→storage
(`NetInsertStorage` → `/internal/insert`), the durable insert spool,
and vlagent's persistent delivery queues — can carry the SAME
self-describing typed frame instead of per-row JSON lines, so a batch
is encoded ONCE and every retry/replay ships the identical bytes, and
the receiving storage node decodes straight into an arena-backed
columnar batch (LogColumns) with ZERO per-row ``json.loads``.

Frame layout (inside the zstd outer framing, little-endian):

    magic  b"\\x00VLI1"          (JSON lines start with "{" — a reader
                                 sniffs the format per body, so mixed
                                 senders need no handshake)
    u32    total_rows
    u32    n_streams             global stream table for the batch
    u16    n_groups              schema groups (exact field-name tuples)
    u32    tags_arena_len  + tags arena (canonical stream-tags strings)
    per stream: u32 tag_off, u32 tag_len, u32 account_id, u32 project_id
    per group:
      u16  n_names;  per name: u16 len + utf-8 bytes
      u16  n_stream_pos; per: u16
      u32  n_rows
      i64[n_rows]  timestamps
      u32[n_rows]  stream refs (into the global stream table)
      per column (n_names): u32 arena_len + value arena,
                            u32[n_rows] offsets, u32[n_rows] lengths

StreamIDs are NOT shipped: the receiver recomputes the 128-bit hash
from the canonical tags bytes (one hash per unique stream, never per
row) so a forged frame can't claim rows into a stream its tags don't
hash to.  Decode bounds-checks every offset/length against its arena
BEFORE any slicing (the wire-taint discipline the vlint
interprocedural checker enforces); any structural corruption raises
``WireInsertError`` (a ValueError → whole-batch HTTP 400, never a
partial silent ingest).

``VL_WIRE_TYPED_INSERT=0`` kills the format on either side: senders
stop encoding i1, receivers reject i1 bodies with a 400 so senders
fall back to legacy JSON lines — pinning legacy behavior in BOTH
mixed-version directions (same discipline as VL_WIRE_TYPED for t1).
"""

from __future__ import annotations

import struct
import threading
import time

import numpy as np

from .. import config
from ..obs import tracing
from ..storage.block_build import ArenaColumn as _ArenaColumn
from ..storage.block_build import arena_build_enabled as _arena_cols
from ..storage.log_rows import (LogColumns, LogRows, StreamID, TenantID)
from ..utils import zstd as _zstd
from ..utils.hashing import stream_id_hash

WIRE_INSERT_FORMAT = "i1"
INSERT_MAGIC = b"\x00VLI1"

# decompressed-size bound for one insert body (matches the legacy
# /internal/insert bound)
MAX_FRAME_BYTES = 1 << 30


def wire_typed_insert_enabled() -> bool:
    """VL_WIRE_TYPED_INSERT=0 kill-switch: this process neither encodes
    nor accepts i1 frames (legacy JSON lines exactly)."""
    return config.env_flag("VL_WIRE_TYPED_INSERT")


class WireInsertError(ValueError):
    """Structural corruption in an i1 frame.  A ValueError so the HTTP
    layer maps it to 400 (whole-batch reject) like any malformed body."""


# ---- ingest-wire observability (vl_ingest_wire_* on /metrics) ----

_mu = threading.Lock()
_counts: dict[str, int] = {}


def note(key: str, delta: int = 1) -> None:
    with _mu:
        _counts[key] = _counts.get(key, 0) + delta


def counters() -> dict:
    with _mu:
        return dict(_counts)


def metrics_samples() -> list:
    """(base, labels, value) samples for Metrics.render — the insert
    spine's sibling of cluster.wire_metrics_samples(): frame/byte
    counts by direction and format, plus sticky-fallback events."""
    c = counters()
    out = []
    for fmt in ("typed", "json"):
        for d in ("tx", "rx"):
            # vlint: allow-per-row-emit(metric label dicts, bounded constant set)
            out.append(("vl_ingest_wire_frames_total",
                        {"dir": d, "fmt": fmt},
                        c.get(f"{d}_frames_{fmt}", 0)))
            # vlint: allow-per-row-emit(metric label dicts, bounded constant set)
            out.append(("vl_ingest_wire_bytes_total",
                        {"dir": d, "fmt": fmt},
                        c.get(f"{d}_bytes_{fmt}", 0)))
    out.append(("vl_ingest_wire_fallbacks_total", {},
                c.get("fallbacks", 0)))
    return out


# ---- encode ----

def _arena(vals) -> tuple[bytes, np.ndarray, np.ndarray]:
    """One dense utf-8 arena + u32 offsets/lengths for a value list.
    ASCII fast path: byte lengths == str lengths, so ONE encode of the
    joined string replaces per-value encodes."""
    wa = getattr(vals, "wire_arena", None)
    if wa is not None:
        # decoded ArenaColumn (storage/block_build): the wire arena IS
        # the value arena — a shard re-route or spool re-encode of a
        # decoded frame skips the join+encode entirely
        arena, offs, lens = wa()
        if len(arena) >= 1 << 32:
            raise ValueError("i1 frame arena overflow")
        return arena, offs, lens
    joined = "".join(vals)
    arena = joined.encode("utf-8")
    n = len(vals)
    if len(arena) == len(joined):
        lens = np.fromiter(map(len, vals), dtype=np.uint32, count=n)
    else:
        lens = np.fromiter((len(v.encode("utf-8")) for v in vals),
                           dtype=np.uint32, count=n)
    offs = np.zeros(n, dtype=np.uint32)
    if n > 1:
        np.cumsum(lens[:-1], out=offs[1:], dtype=np.uint32)
    if len(arena) >= 1 << 32:
        # u32 offsets can't address it — caller falls back to legacy
        raise ValueError("i1 frame arena overflow")
    return arena, offs, lens


def encode_columns(lc: LogColumns) -> bytes:
    """One LogColumns batch -> a compressed i1 body.  Raises ValueError
    (not WireInsertError) when the batch can't ride the format (arena
    or tenant-id overflow) so callers fall back to legacy encoding."""
    t0 = time.perf_counter()
    # global stream table
    sid_to_ref: dict = {}
    tags_list: list = []
    tenant_rows: list = []
    for g in lc.groups.values():
        for sid, tenant, tags in g.streams:
            if sid in sid_to_ref:
                continue
            a, p = tenant.account_id, tenant.project_id
            if not (0 <= a < 1 << 32 and 0 <= p < 1 << 32):
                raise ValueError("i1 frame tenant id overflow")
            sid_to_ref[sid] = len(tags_list)
            tags_list.append(tags)
            tenant_rows.append((a, p))
    groups = [g for g in lc.groups.values() if g.ts]
    if len(groups) >= 1 << 16:
        raise ValueError("i1 frame group count overflow")
    parts = [INSERT_MAGIC,
             struct.pack("<IIH", lc.nrows, len(tags_list), len(groups))]
    tags_arena, tags_offs, tags_lens = _arena(tags_list)
    parts.append(struct.pack("<I", len(tags_arena)))
    parts.append(tags_arena)
    stream_tbl = np.empty((len(tags_list), 4), dtype="<u4")
    if len(tags_list):
        stream_tbl[:, 0] = tags_offs
        stream_tbl[:, 1] = tags_lens
        stream_tbl[:, 2] = [a for a, _p in tenant_rows]
        stream_tbl[:, 3] = [p for _a, p in tenant_rows]
    parts.append(stream_tbl.tobytes())
    for g in groups:
        if len(g.names) >= 1 << 16:
            raise ValueError("i1 frame column count overflow")
        parts.append(struct.pack("<H", len(g.names)))
        for nm in g.names:
            nb = nm.encode("utf-8")
            if len(nb) >= 1 << 16:
                raise ValueError("i1 frame field name overflow")
            parts.append(struct.pack("<H", len(nb)))
            parts.append(nb)
        parts.append(struct.pack("<H", len(g.stream_pos)))
        if g.stream_pos:
            parts.append(np.asarray(g.stream_pos,
                                    dtype="<u2").tobytes())
        n = len(g.ts)
        parts.append(struct.pack("<I", n))
        parts.append(np.asarray(g.ts, dtype="<i8").tobytes())
        # remap group-local stream refs -> global table refs
        local = np.fromiter((sid_to_ref[sid] for sid, _t, _s
                             in g.streams),
                            dtype=np.uint32, count=len(g.streams))
        parts.append(local[np.asarray(g.sref, dtype=np.int64)]
                     .astype("<u4", copy=False).tobytes())
        for col in g.cols:
            arena, offs, lens = _arena(col)
            parts.append(struct.pack("<I", len(arena)))
            parts.append(arena)
            parts.append(offs.astype("<u4", copy=False).tobytes())
            parts.append(lens.astype("<u4", copy=False).tobytes())
    body = _zstd.compress(b"".join(parts))
    note("tx_frames_typed")
    note("tx_bytes_typed", len(body))
    note("encodes_typed")
    sp = tracing.current_span()
    if sp.enabled:
        sp.add("typed_frames")
        sp.add("encode_s", time.perf_counter() - t0)
    return body


def encode_rows(lr: LogRows) -> bytes:
    """LogRows (the per-row batch form) -> a compressed i1 body."""
    return encode_columns(rows_to_columns(lr))


def rows_to_columns(lr: LogRows) -> LogColumns:
    """Regroup a LogRows batch by exact field schema so the row-path
    hops (syslog/OTLP handlers, vlagent fan-in) ride the same frame."""
    lc = LogColumns()
    for i in range(len(lr)):
        fields = lr.rows[i]
        names = tuple(k for k, _v in fields)
        g = lc.group(names, ())
        lc.add(g, lr.tenants[i], lr.timestamps[i],
               [v for _k, v in fields], lr.stream_ids[i],
               lr.stream_tags_str[i])
    return lc


def encode_legacy_columns(lc: LogColumns) -> bytes:
    """The mandatory legacy fallback body (zstd'd JSON lines, the
    format every version's /internal/insert speaks) from a columnar
    batch — used when a receiver rejects i1 (old node, or
    VL_WIRE_TYPED_INSERT=0 on its side)."""
    import json
    lines = []
    for g in lc.groups.values():
        names = g.names
        for k in range(len(g.ts)):
            sid, tenant, tags = g.streams[g.sref[k]]
            # vlint: allow-per-row-emit(legacy ingest wire format is per-row framed JSON; fallback path only)
            lines.append(json.dumps(
                {"t": g.ts[k], "a": tenant.account_id,
                 "p": tenant.project_id, "s": tags,
                 "f": [[nm, c[k]] for nm, c in zip(names, g.cols)]},
                ensure_ascii=False, separators=(",", ":")))
    body = _zstd.compress("\n".join(lines).encode("utf-8"))
    note("tx_frames_json")
    note("tx_bytes_json", len(body))
    note("encodes_json")
    return body


def reencode_legacy(body: bytes) -> bytes | None:
    """Re-encode a stored compressed body as legacy JSON lines if (and
    only if) it is a typed i1 frame; None when it isn't one or can't be
    decoded.  Used by spool/queue replay when a receiver stopped
    speaking i1 between spool time and replay time."""
    try:
        data = _zstd.decompress(body, max_output_size=MAX_FRAME_BYTES)
    except ValueError:
        return None
    if not data.startswith(INSERT_MAGIC):
        return None
    try:
        lc = decode_frame(data)
    except WireInsertError:
        return None
    return encode_legacy_columns(lc)


# ---- decode ----

class _Reader:
    """Bounds-checked cursor over one decompressed i1 payload (the
    ingest sibling of cluster._FrameReader; raises WireInsertError so
    corruption maps to HTTP 400 instead of a transport error)."""

    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes, pos: int):
        self.buf = buf
        self.pos = pos

    def take(self, n: int) -> bytes:
        end = self.pos + n
        if n < 0 or end > len(self.buf):
            raise WireInsertError(
                "corrupted i1 frame: truncated payload")
        out = self.buf[self.pos:end]
        self.pos = end
        return out

    def array(self, dtype, count: int) -> np.ndarray:
        it = np.dtype(dtype).itemsize
        end = self.pos + it * count
        if count < 0 or end > len(self.buf):
            raise WireInsertError(
                "corrupted i1 frame: truncated array")
        a = np.frombuffer(self.buf, dtype=dtype, count=count,
                          offset=self.pos)
        self.pos = end
        return a


def _check_slices(offs: np.ndarray, lens: np.ndarray, alen: int,
                  what: str) -> None:
    """Every (offset, length) slice must lie inside its arena BEFORE
    anything reads through it — offsets are wire-derived."""
    if offs.size and int((offs.astype(np.int64)
                          + lens.astype(np.int64)).max()) > alen:
        raise WireInsertError(
            f"corrupted i1 frame: {what} slice out of arena bounds")


def _arena_text(raw: bytes, what: str) -> str:
    try:
        return raw.decode("utf-8")
    except UnicodeDecodeError as e:
        raise WireInsertError(
            f"corrupted i1 frame: {what} arena is not UTF-8: {e}") \
            from None


def _slice_all(text: str, raw: bytes, offs: np.ndarray,
               lens: np.ndarray) -> list:
    """Arena -> per-value strings.  ASCII arenas slice the decoded str
    directly (byte offsets == char offsets); otherwise slice bytes and
    decode per value (rare: non-ASCII log payloads)."""
    ends = (offs.astype(np.int64) + lens.astype(np.int64)).tolist()
    o = offs.tolist()
    if len(text) == len(raw):
        return [text[s:e] for s, e in zip(o, ends)]
    return [raw[s:e].decode("utf-8", "strict") for s, e in zip(o, ends)]


def decode_frame(data: bytes) -> LogColumns:
    """One decompressed i1 payload -> an arena-backed LogColumns batch
    ready for Storage.must_add_columns — no per-row json.loads anywhere.
    StreamIDs are recomputed from the canonical tags (one hash per
    unique stream).  Raises WireInsertError on ANY structural problem:
    the whole batch is rejected, never partially ingested."""
    if not data.startswith(INSERT_MAGIC):
        raise WireInsertError("corrupted i1 frame: bad magic")
    r = _Reader(data, len(INSERT_MAGIC))
    total_rows, n_streams, n_groups = struct.unpack("<IIH", r.take(10))
    tags_alen = struct.unpack("<I", r.take(4))[0]
    tags_raw = r.take(tags_alen)
    tbl = r.array("<u4", n_streams * 4).reshape(n_streams, 4)
    _check_slices(tbl[:, 0], tbl[:, 1], tags_alen, "stream tags")
    tags_text = _arena_text(tags_raw, "stream tags")
    streams: list = []
    t_off = tbl[:, 0].tolist()
    t_end = (tbl[:, 0].astype(np.int64)
             + tbl[:, 1].astype(np.int64)).tolist()
    t_acc = tbl[:, 2].tolist()
    t_proj = tbl[:, 3].tolist()
    ascii_tags = len(tags_text) == len(tags_raw)
    for i in range(n_streams):
        raw = tags_raw[t_off[i]:t_end[i]]
        tags = tags_text[t_off[i]:t_end[i]] if ascii_tags \
            else raw.decode("utf-8", "strict")
        hi, lo = stream_id_hash(raw)
        tenant = TenantID(t_acc[i], t_proj[i])
        streams.append((StreamID(tenant, hi, lo), tenant, tags))
    lc = LogColumns()
    rows_seen = 0
    for _gi in range(n_groups):
        n_names = struct.unpack("<H", r.take(2))[0]
        names = []
        for _ni in range(n_names):
            nlen = struct.unpack("<H", r.take(2))[0]
            names.append(_arena_text(r.take(nlen), "field name"))
        names_t = tuple(names)
        n_spos = struct.unpack("<H", r.take(2))[0]
        spos = tuple(int(p) for p in r.array("<u2", n_spos))
        if any(p >= n_names for p in spos):
            raise WireInsertError(
                "corrupted i1 frame: stream position out of range")
        n = struct.unpack("<I", r.take(4))[0]
        ts = r.array("<i8", n)
        srefs = r.array("<u4", n)
        if srefs.size and int(srefs.max()) >= n_streams:
            raise WireInsertError(
                "corrupted i1 frame: stream ref out of range")
        cols = []
        for _ci in range(n_names):
            alen = struct.unpack("<I", r.take(4))[0]
            raw = r.take(alen)
            offs = r.array("<u4", n)
            lens = r.array("<u4", n)
            _check_slices(offs, lens, alen, "value")
            text = _arena_text(raw, "value")
            if len(text) == len(raw) and n and _arena_cols():
                # ASCII arena: keep it dense all the way to the block
                # build (storage/block_build) — no per-row strings
                # exist between here and BlockData
                cols.append(_ArenaColumn(raw, offs, lens, text))
                continue
            try:
                cols.append(_slice_all(text, raw, offs, lens))
            except UnicodeDecodeError as e:
                raise WireInsertError(
                    "corrupted i1 frame: value slice is not "
                    f"UTF-8: {e}") from None
        if names_t in lc.groups:
            raise WireInsertError(
                "corrupted i1 frame: duplicate schema group")
        g = lc.group(names_t, spos)
        # group-local stream table: only the streams this group uses,
        # refs remapped (np.unique is sorted+vectorized)
        if n:
            uniq, inv = np.unique(srefs, return_inverse=True)
            for ref in uniq.tolist():
                sid, tenant, tags = streams[ref]
                g.stream_idx[sid] = len(g.streams)
                g.streams.append((sid, tenant, tags))
                if sid not in lc.stream_tags:
                    lc.stream_tags[sid] = tags
            g.ts = ts.tolist()
            g.sref = inv.tolist()
            g.cols = cols
            lc.nrows += n
        rows_seen += n
    if rows_seen != total_rows:
        raise WireInsertError(
            "corrupted i1 frame: row count mismatch "
            f"(header {total_rows}, groups {rows_seen})")
    if r.pos != len(data):
        raise WireInsertError("corrupted i1 frame: trailing garbage")
    return lc


def columns_tenant_rows(lc: LogColumns) -> dict:
    """Per-tenant row counts for a decoded batch (ingest accounting
    without touching rows): tenant -> rows, via one bincount per
    group's stream refs."""
    out: dict = {}
    for g in lc.groups.values():
        if not g.ts:
            continue
        counts = np.bincount(np.asarray(g.sref, dtype=np.int64),
                             minlength=len(g.streams))
        for (sid, tenant, _tags), c in zip(g.streams, counts.tolist()):
            if c:
                out[tenant] = out.get(tenant, 0) + c
    return out


# ---- node sharding (cluster frontends) ----

def split_columns_by_node(lc: LogColumns, n_nodes: int) -> dict:
    """Shard a columnar batch by stream hash: node -> sub-LogColumns
    with remapped stream refs (the columnar form of NetInsertStorage's
    per-row (hi^lo) % n routing).  The common one-node / one-stream
    batch returns the input uncopied."""
    if n_nodes == 1:
        return {0: lc}
    nodes_used: set = set()
    per_group: list = []
    for g in lc.groups.values():
        snodes = np.fromiter(((sid.hi ^ sid.lo) % n_nodes
                              for sid, _t, _s in g.streams),
                             dtype=np.int64, count=len(g.streams))
        row_nodes = snodes[np.asarray(g.sref, dtype=np.int64)] \
            if g.ts else np.empty(0, dtype=np.int64)
        per_group.append((g, row_nodes))
        nodes_used.update(np.unique(row_nodes).tolist())
    if len(nodes_used) <= 1:
        return {nodes_used.pop() if nodes_used else 0: lc}
    out: dict = {}
    for node in nodes_used:
        sub = LogColumns()
        for g, row_nodes in per_group:
            idxs = np.nonzero(row_nodes == node)[0]
            if not idxs.size:
                continue
            sg = sub.group(g.names, g.stream_pos)
            srefs = np.asarray(g.sref, dtype=np.int64)[idxs]
            uniq, inv = np.unique(srefs, return_inverse=True)
            for ref in uniq.tolist():
                sid, tenant, tags = g.streams[ref]
                sg.stream_idx[sid] = len(sg.streams)
                sg.streams.append((sid, tenant, tags))
                if sid not in sub.stream_tags:
                    sub.stream_tags[sid] = tags
            il = idxs.tolist()
            sg.ts = [g.ts[k] for k in il]
            sg.sref = inv.tolist()
            sg.cols = [[c[k] for k in il] for c in g.cols]
            sub.nrows += len(il)
        out[node] = sub
    return out


# ---- shared encoder pool ----
#
# Cluster frontends and vlagent encode per-node shard bodies in
# parallel (numpy packing + zstd drop the GIL); the pool is shared
# process-wide and refcounted so N NetInsertStorage/VLAgent instances
# (tests spin up several) don't each own idle threads.  The vlint
# "ingest-encoder-pool" balance pair enforces that every acquire_pool()
# caller file also release_pool()s.

_pool_mu = threading.Lock()
_pool = None
_pool_refs = 0
_POOL_WORKERS = 4


def acquire_pool():
    """Refcounted shared ThreadPoolExecutor for shard encoding."""
    global _pool, _pool_refs
    from concurrent.futures import ThreadPoolExecutor
    with _pool_mu:
        if _pool is None:
            _pool = ThreadPoolExecutor(
                max_workers=_POOL_WORKERS,
                thread_name_prefix="vl-ingest-encode")
        _pool_refs += 1
        return _pool


def live_pool_refs() -> int:
    """How many live acquirers own the shared encoder pool (vlsan: a
    worker spawned lazily while one remains is infrastructure, not a
    leak — release_pool() joins them when the last owner closes)."""
    with _pool_mu:
        return _pool_refs


def release_pool() -> None:
    global _pool, _pool_refs
    with _pool_mu:
        _pool_refs -= 1
        if _pool_refs > 0:
            return
        pool, _pool = _pool, None
        _pool_refs = 0
    if pool is not None:
        # wait: encode tasks are sub-ms, and an un-joined worker is a
        # non-daemon thread the vlsan leak sweep rightly flags
        pool.shutdown(wait=True)
