"""Cluster layer (L5): stream-hash sharded ingest + scatter-gather queries.

The TPU-native redesign of the reference's netinsert/netselect/
internalinsert/internalselect stack:

- ingest: rows shard to storage nodes by stream hash for locality
  (app/vlstorage/netinsert/netinsert.go:368-409), with a 10s circuit
  breaker per node and re-routing to healthy nodes
  (netinsert.go:283-289, 199-215);
- query: the pipe chain splits into a remote part (filters + streaming
  row-local pipes + per-node stats PARTIALS) and a local part (stats merge
  via the stats funcs' export/import contract + remaining pipes) —
  lib/logstorage/net_query_runner.go:67-96, pipe_stats.go:111-119; results
  stream back as length-prefixed zstd frames
  (app/vlselect/internalselect/internalselect.go:55-100);
- failure semantics: by default any node error fails the whole query (the
  reference's explicit no-partial-results design); ``?partial=1`` (or
  VL_PARTIAL_RESULTS=1) opts a request into merged results from the
  surviving nodes when a node is still down after the policy layer's
  retries, marked with X-VL-Partial + a ``partial.failed_nodes`` block.

Every HTTP hop here rides the fault-policy layer (server/netrobust.py:
per-node circuit breakers shared by select + insert, deadline-aware
retries, hedging, per-read deadlines, fault injection) — enforced by
the vlint ``net-discipline`` checker.  When re-routing exhausts healthy
nodes, ingest spools the serialized shard body to a per-node durable
queue and replays it when the node recovers, so an outage delays rows
instead of dropping them.

Wire formats are this repo's own: versioned via the `version` arg like
the reference's per-endpoint protocol versions (netselect.go:28-63).
Since wire format "t1", internal-select results ship as TYPED COLUMNAR
frames (string arenas + offsets, dict codes, native int64 _time —
BlockResult.wire_columns() on the wire) negotiated per request with the
legacy JSON frame as the mandatory fallback; see the framing section.
"""

from __future__ import annotations

import json
import os
import queue
import struct
import threading
import time

import numpy as np
from .. import config

from .. import sched
from ..engine.block_result import (WIRE_CONST, WIRE_DICT, WIRE_ISO,
                                   WIRE_STR, WIRE_TIME, BlockResult)
from ..logsql.parser import MAX_TS, MIN_TS, parse_query
from ..obs import activity, events, ingestledger, tracing
from ..logsql.pipes import PipeLimit, PipeStats, Processor
from ..storage.log_rows import LogRows, StreamID, TenantID
from ..utils.hashing import stream_id_hash
from . import netrobust, wire_ingest

PROTOCOL_VERSION = "v1"

# frames are written/read from many response and fetch threads; the
# utils.zstd helpers keep per-thread contexts (zstd objects are not
# thread-safe)
from ..utils import zstd as _zstd


# ---------------- stats split pipes ----------------

class PipeStatsExport(PipeStats):
    """Remote half of a stats split: emits per-group EXPORTED states
    instead of finalized values (reference `stats_remote` mode —
    pipe_stats.go:55-60)."""

    name = "stats_export"

    def __init__(self, ps: PipeStats):
        super().__init__(ps.by, ps.funcs)

    def to_string(self):
        return "stats_export:" + super().to_string()[len("stats "):]

    def make_processor(self, next_p):
        pipe = self
        inner = super().make_processor(None)

        class P(type(inner)):
            def flush(self):
                by_names = [b.name for b in pipe.by]
                cols: dict[str, list[str]] = {n: [] for n in by_names}
                for k in range(len(pipe.funcs)):
                    cols[f"__state_{k}"] = []
                for key, states in self.groups.items():
                    for n, kv in zip(by_names, key):
                        cols[n].append(kv)
                    for k, (fn, st) in enumerate(zip(pipe.funcs, states)):
                        # vlint: allow-per-row-emit(per-GROUP stats-state export, bounded by group count)
                        st_json = json.dumps(fn.export_state(st))
                        cols[f"__state_{k}"].append(st_json)
                self.next_p.write_block(
                    BlockResult.from_columns(cols)
                    if any(cols.values()) else BlockResult(0))
                self.next_p.flush()
        p = P(next_p)
        return p


class PipeStatsImport(PipeStats):
    """Local half: imports remote per-group states and merges them
    (reference `stats_local` — importState merging)."""

    name = "stats_import"

    def __init__(self, ps: PipeStats):
        super().__init__(ps.by, ps.funcs)

    def to_string(self):
        return "stats_import:" + super().to_string()[len("stats "):]

    def make_processor(self, next_p):
        pipe = self
        inner = super().make_processor(None)

        class P(type(inner)):
            def write_block(self, br):
                by_names = [b.name for b in pipe.by]
                key_cols = [br.column(n) for n in by_names]
                state_cols = [br.column(f"__state_{k}")
                              for k in range(len(pipe.funcs))]
                for i in range(br.nrows):
                    key = tuple(c[i] for c in key_cols)
                    states = self.groups.get(key)
                    incoming = [
                        fn.import_state(json.loads(state_cols[k][i]))
                        for k, fn in enumerate(pipe.funcs)]
                    if states is None:
                        self.groups[key] = incoming
                        self.budget.add(sum(len(k) for k in key) + 80)
                    else:
                        for k, fn in enumerate(pipe.funcs):
                            states[k] = fn.merge(states[k], incoming[k])
        return P(next_p)


def split_query(q):
    """(mode, split_at, local_pipes): remote part = pipes[:split_at]
    (+ stats export when mode == 'stats'); per-pipe pushdown follows the
    reference's splitToRemoteAndLocal contract (pipe.go:15-22) with
    can_live_tail() marking streaming row-local pipes."""
    for k, p in enumerate(q.pipes):
        if isinstance(p, PipeStats) and \
                all(pp.can_live_tail() for pp in q.pipes[:k]):
            return "stats", k, [PipeStatsImport(p)] + list(q.pipes[k + 1:])
    k = 0
    while k < len(q.pipes) and q.pipes[k].can_live_tail():
        k += 1
    local = list(q.pipes[k:])
    return "rows", k, local


# ---------------- framing ----------------
#
# Two frame payload formats share the outer framing (4-byte BE length +
# zstd payload):
#   - legacy JSON frames: {"cols": {name: [str,...]}, "ts": [...]} —
#     the mandatory fallback every version speaks;
#   - typed columnar frames (PROTOCOL since wire format "t1"): a binary
#     encoding of BlockResult.wire_columns() — string value arenas +
#     uint32 offsets/lengths, dict codes + tiny value arenas, native
#     int64 _time, consts — so the columnar representation survives the
#     network seam instead of being destroyed into row strings and
#     rebuilt on the frontend.
# Frames are self-describing: typed payloads start with a magic prefix
# no JSON document can (b"\x00VLT1"), so a reader handles a mixed
# stream (trace frames stay JSON) and a frontend that REQUESTED typed
# frames still decodes a legacy node's JSON replies — negotiation needs
# no handshake round-trip.  Storage nodes only ever send typed frames
# when the request carried `wire=t1`, so legacy frontends never see
# them.  VL_WIRE_TYPED=0 kills both sides (request and serve).

WIRE_FORMAT = "t1"
TYPED_MAGIC = b"\x00VLT1"

# wire-kind payload scalar dtypes (little-endian on the wire)
_W_NUM_DTYPES = {1: "<i8", 2: "<i8", 3: "<i8", 4: "<u8", 7: "<f8"}


def wire_typed_enabled() -> bool:
    """VL_WIRE_TYPED=0 kill-switch: restores legacy JSON frames exactly
    (this process neither requests nor serves typed frames)."""
    return config.env_flag("VL_WIRE_TYPED")


# ---- wire-protocol observability (vl_wire_* on /metrics) ----

_wire_mu = threading.Lock()
_wire_counts: dict[str, int] = {}


def _wire_note(key: str, delta: int = 1) -> None:
    with _wire_mu:
        _wire_counts[key] = _wire_counts.get(key, 0) + delta


def wire_counters() -> dict:
    with _wire_mu:
        return dict(_wire_counts)


def wire_metrics_samples() -> list:
    """(base, labels, value) samples for Metrics.render: frame counts
    and raw wire bytes (compressed, incl. frame headers), both labeled
    by direction and format — a combined frontend+storage node sends
    AND receives, so the two must not fold into one series.  Data and
    stats frames follow the negotiated format; trace frames always
    ride fmt="json"."""
    c = wire_counters()
    out = []
    for fmt in ("typed", "json"):
        for d in ("tx", "rx"):
            # vlint: allow-per-row-emit(metric label dicts, bounded constant set)
            out.append(("vl_wire_frames_total", {"dir": d, "fmt": fmt},
                        c.get(f"{d}_frames_{fmt}", 0)))
            # vlint: allow-per-row-emit(metric label dicts, bounded constant set)
            out.append(("vl_wire_bytes_total", {"dir": d, "fmt": fmt},
                        c.get(f"{d}_bytes_{fmt}", 0)))
    out.append(("vl_wire_fallbacks_total", {},
                c.get("fallbacks", 0)))
    return out


def write_frame(obj) -> bytes:
    payload = _zstd.compress(json.dumps(obj, ensure_ascii=False,
                                      separators=(",", ":")).encode("utf-8"))
    _wire_note("tx_frames_json")
    _wire_note("tx_bytes_json", len(payload) + 4)
    return struct.pack(">I", len(payload)) + payload


END_FRAME = struct.pack(">I", 0)


def write_typed_frame(br: BlockResult) -> bytes:
    """One result block as a typed columnar frame, serialized straight
    from BlockResult.wire_columns() — no per-row Python objects."""
    names, wcols = br.wire_columns()
    ts = br.timestamps_np()
    parts = [TYPED_MAGIC,
             struct.pack("<IHB", br.nrows, len(names),
                         1 if ts is not None else 0)]
    if ts is not None:
        parts.append(ts.astype("<i8", copy=False).tobytes())
    for name, wc in zip(names, wcols):
        nb = name.encode("utf-8")
        kind = wc[0]
        parts.append(struct.pack("<HB", len(nb), kind))
        parts.append(nb)
        if kind == WIRE_STR:
            arena, offs, lens = wc[1], wc[2], wc[3]
            if int(arena.shape[0]) >= 1 << 32:
                # uint32 offsets can't address it (never happens for
                # block-sized results) — caller falls back to JSON
                raise ValueError("typed frame arena overflow")
            parts.append(struct.pack("<I", int(arena.shape[0])))
            parts.append(arena.tobytes())
            parts.append(offs.astype("<u4").tobytes())
            parts.append(lens.astype("<u4").tobytes())
        elif kind == WIRE_TIME:
            pass            # value array IS the frame timestamps
        elif kind == WIRE_ISO:
            parts.append(struct.pack("<B", wc[2]))
            parts.append(wc[1].astype("<i8", copy=False).tobytes())
        elif kind == WIRE_DICT:
            codes, dvals = wc[1], wc[2]
            parts.append(struct.pack("<B", len(dvals)))
            for v in dvals:
                vb = v.encode("utf-8")
                parts.append(struct.pack("<H", len(vb)))
                parts.append(vb)
            parts.append(codes.astype(np.uint8, copy=False).tobytes())
        elif kind == WIRE_CONST:
            vb = wc[1].encode("utf-8")
            parts.append(struct.pack("<I", len(vb)))
            parts.append(vb)
        else:                # WIRE_INT / WIRE_UINT / WIRE_FLOAT
            parts.append(wc[1].astype(_W_NUM_DTYPES[kind],
                                      copy=False).tobytes())
    payload = _zstd.compress(b"".join(parts))
    _wire_note("tx_frames_typed")
    _wire_note("tx_bytes_typed", len(payload) + 4)
    return struct.pack(">I", len(payload)) + payload


class _FrameReader:
    """Bounds-checked cursor over one decompressed typed payload."""

    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes, pos: int):
        self.buf = buf
        self.pos = pos

    def take(self, n: int) -> bytes:
        end = self.pos + n
        if n < 0 or end > len(self.buf):
            raise IOError("corrupted typed frame: truncated payload")
        out = self.buf[self.pos:end]
        self.pos = end
        return out

    def array(self, dtype, count: int) -> np.ndarray:
        it = np.dtype(dtype).itemsize
        end = self.pos + it * count
        if end > len(self.buf):
            raise IOError("corrupted typed frame: truncated array")
        a = np.frombuffer(self.buf, dtype=dtype, count=count,
                          offset=self.pos)
        self.pos = end
        return a


def decode_typed_frame(payload: bytes) -> BlockResult:
    """Typed frame payload -> arena-backed BlockResult view.  Raises
    IOError on any structural corruption (the scatter-gather fan-out
    fails the whole query, like any other node transport error)."""
    r = _FrameReader(payload, len(TYPED_MAGIC))
    nrows, ncols, flags = struct.unpack("<IHB", r.take(7))
    ts = None
    if flags & 1:
        ts = r.array("<i8", nrows)
    names: list[str] = []
    wcols: dict = {}
    for _ in range(ncols):
        nlen, kind = struct.unpack("<HB", r.take(3))
        name = r.take(nlen).decode("utf-8", "replace")
        if kind == WIRE_STR:
            alen = struct.unpack("<I", r.take(4))[0]
            arena = np.frombuffer(r.take(alen), dtype=np.uint8)
            offs = r.array("<u4", nrows)
            lens = r.array("<u4", nrows)
            # bounds-check BEFORE these arrays can reach the native
            # emitter (which reads arena+offset unchecked): every
            # row's slice must lie inside the shipped arena
            if nrows and int((offs.astype(np.int64)
                              + lens.astype(np.int64)).max()) > alen:
                raise IOError("corrupted typed frame: string slice "
                              "out of arena bounds")
            wc = (WIRE_STR, arena, offs, lens)
        elif kind == WIRE_TIME:
            if ts is None:
                raise IOError("corrupted typed frame: _time column "
                              "without frame timestamps")
            wc = (WIRE_TIME, ts)
        elif kind == WIRE_ISO:
            frac_w = r.take(1)[0]
            if frac_w > 9:
                # encoders only produce 0-9 fractional digits; larger
                # values would overflow the native formatter's
                # fixed per-value output reservation
                raise IOError("corrupted typed frame: ISO8601 "
                              f"fractional width {frac_w}")
            wc = (WIRE_ISO, r.array("<i8", nrows), frac_w)
        elif kind == WIRE_DICT:
            nvals = r.take(1)[0]
            dvals = []
            for _j in range(nvals):
                vlen = struct.unpack("<H", r.take(2))[0]
                dvals.append(r.take(vlen).decode("utf-8", "replace"))
            codes = r.array(np.uint8, nrows)
            # nvals == 0 with rows present is out of range too (every
            # code must index a shipped value)
            if codes.size and (nvals == 0
                               or int(codes.max()) >= nvals):
                raise IOError("corrupted typed frame: dict code out "
                              "of range")
            wc = (WIRE_DICT, codes, dvals)
        elif kind == WIRE_CONST:
            vlen = struct.unpack("<I", r.take(4))[0]
            wc = (WIRE_CONST, r.take(vlen).decode("utf-8", "replace"))
        elif kind in _W_NUM_DTYPES:
            wc = (kind, r.array(_W_NUM_DTYPES[kind], nrows))
        else:
            raise IOError(f"corrupted typed frame: unknown column "
                          f"kind {kind}")
        names.append(name)
        wcols[name] = wc
    if r.pos != len(payload):
        raise IOError("corrupted typed frame: trailing garbage")
    return BlockResult.from_wire(names, wcols, nrows, ts_np=ts)


def read_frame_payloads(fp):
    """Yield (decompressed payload bytes, wire length) per frame until
    the end frame.  The payload's leading bytes identify its format
    (TYPED_MAGIC vs JSON) — see decode_typed_frame / json.loads."""
    while True:
        hdr = fp.read(4)
        if len(hdr) < 4:
            raise IOError("truncated frame header")
        n = struct.unpack(">I", hdr)[0]
        if n == 0:
            return
        payload = b""
        while len(payload) < n:
            chunk = fp.read(n - len(payload))
            if not chunk:
                raise IOError("truncated frame payload")
            payload += chunk
        yield (_zstd.decompress(payload, max_output_size=1 << 30),
               n + 4)


# ---------------- server side: /internal/select/query ----------------

def handle_internal_select(storage, args, runner=None):
    """Frames generator for one remote sub-query; validates EAGERLY.

    Validation and query parsing run before the generator is returned so
    bad requests surface as ValueError -> HTTP 400 instead of corrupting
    an already-started 200 chunked stream.  The worker thread never
    outlives the response: closing the generator (client disconnect, or
    the frontend's first-error/early-done cancel stopping mid-stream)
    aborts the query at the sink and unblocks any pending put (see
    streamwork).  The query runs under the same server-side deadline as
    single-node /select queries."""
    from ..engine.searcher import run_query
    from .vlselect import query_deadline
    if args.get("version", PROTOCOL_VERSION) != PROTOCOL_VERSION:
        raise ValueError(f"unsupported protocol version "
                         f"{args.get('version')!r}")
    qs = args["query"]
    ts = int(args.get("ts") or time.time_ns())
    mode = args.get("mode", "rows")
    split_at = int(args.get("split_at") or 0)
    limit = int(args.get("limit") or 0)
    tenants = [TenantID.parse(t)
               for t in (args.get("tenant", "0:0")).split(",") if t]
    q = parse_query(qs, timestamp=ts)
    all_pipes = q.pipes
    q.pipes = all_pipes[:split_at]
    if mode == "stats":
        ps = all_pipes[split_at]
        assert isinstance(ps, PipeStats), "split_at must point at stats"
        q.pipes = q.pipes + [PipeStatsExport(ps)]
    elif limit > 0:
        # pushed-down limit: each node returns at most N rows
        q.pipes.append(PipeLimit(limit))

    # EXPLAIN sub-request (frontend handle_explain fan-out): build —
    # and for analyze, execute — EAGERLY, then stream the one-frame
    # result; the tree covers this node's REMOTE half of the pipe
    # split, so the frontend's merged plan shows exactly what each
    # node would dispatch.  Frames stay legacy JSON (trees are small).
    explain_mode = args.get("explain", "")
    if explain_mode:
        if explain_mode not in ("plan", "analyze"):
            raise ValueError(f"invalid explain mode {explain_mode!r}")
        from ..obs import explain as _explain
        tree = _explain.build_plan(storage, tenants, q, runner=runner)
        if explain_mode == "analyze":
            _explain.analyze(storage, tenants, q, tree, runner=runner,
                             deadline=query_deadline(args),
                             endpoint="/internal/select/query",
                             include_trace=args.get("trace") == "1")

        def gen_explain():
            yield write_frame({"explain": tree})
            yield END_FRAME
        return gen_explain()

    # stream frames as blocks arrive; the shared worker protocol
    # (bounded queue + abandon-stream cancellation) lives in streamwork
    from .streamwork import stream_blocks

    # wire negotiation: typed frames only when the frontend asked for
    # them AND this node's kill-switch allows (old frontends never ask,
    # so they only ever see legacy JSON frames)
    typed_wire = args.get("wire") == WIRE_FORMAT and wire_typed_enabled()

    def encode(br):
        if typed_wire:
            try:
                return write_typed_frame(br)
            except ValueError:
                pass        # arena overflow: this block rides JSON
        # legacy frames materialize per-row strings — the fallback
        # every protocol version speaks
        cols = {n: br.column(n) for n in br.column_names()}
        return write_frame({"cols": cols, "ts": br.timestamps})

    deadline = query_deadline(args)
    # the frontend forwards ?trace=1: this node traces its own
    # execution and ships the tree back as the stream's last frame,
    # which the frontend attaches under its per-node span
    root = tracing.make_root("storage_node_query", query=qs) \
        if args.get("trace") == "1" else None
    # propagated query identity: the frontend ships its query's
    # global_qid as parent_qid, so this node's registry record, trace
    # tree and query_done journal event all correlate back to the ONE
    # frontend query that fanned out here
    parent_qid = args.get("parent_qid", "")

    def gen():
        # internal sub-queries register in the active-query registry
        # too: a storage node's active_queries shows the frontend fan-in
        # it is serving, and cancel_query on the node kills a runaway
        # sub-query with the same drain semantics
        with activity.track("/internal/select/query", qs,
                            tenants, parent_qid=parent_qid) as act:
            if root is not None:
                root.set("qid", act.qid)
                if parent_qid:
                    root.set("parent_qid", parent_qid)

            def run(sink):
                # the query executes on streamwork's worker thread:
                # activate the trace and re-enter the registry record
                # THERE (contextvars don't cross thread spawns)
                with tracing.activate(root), activity.use_activity(act):
                    run_query(storage, tenants, q, write_block=sink,
                              runner=runner, deadline=deadline)

            try:
                yield from stream_blocks(run, encode)
            except GeneratorExit:
                # frontend hung up (first-error/early-done cancel):
                # stop the device walk, don't finish a dead sub-query
                act.abandon()
                raise
            if root is not None:
                yield write_frame({"trace": root.to_dict()})
            yield END_FRAME
    return gen()


# ---------------- server side: /internal/insert ----------------

class _InsertPipeline:
    """Decode/store hop overlap for typed /internal/insert frames.

    With ``VL_INSERT_PIPELINE`` > 0 the request thread stops at the
    decode + ledger-entry rolls and hands the decoded batch to a
    bounded queue (maxsize = the configured depth, latched at first
    use); one daemon drainer re-enters the batch's ledger record via
    ``use_batch`` and runs the storage chokepoint, so frame N+1's
    decompress/decode overlaps frame N's block build.  The ledger
    stays exact: ``received`` rolls on the request thread, ``stored``
    (or ``dropped`` on a store error) rolls on the drainer under the
    SAME batch record, so derived in_flight counts queued rows until
    they land.  ``queue.Queue.put`` blocking on a full queue is the
    backpressure — at most ``depth`` batches ever wait.  Default 0
    keeps the store synchronous on the request thread (read-your-
    writes for every existing caller)."""

    def __init__(self):
        self._mu = threading.Lock()
        self._q = None
        self.enqueued_total = 0
        self.stored_total = 0
        self.dropped_total = 0

    def submit(self, storage, lc, per_tenant: dict, nbytes: int) -> bool:
        depth = config.env_int("VL_INSERT_PIPELINE") or 0
        if depth <= 0:
            return False
        with self._mu:
            if self._q is None:
                self._q = queue.Queue(maxsize=max(1, depth))
                threading.Thread(target=self._run, daemon=True,
                                 name="vl-insert-pipeline").start()
            self.enqueued_total += 1
            q = self._q
        q.put((storage, lc, dict(per_tenant), nbytes,
               ingestledger.current_batch()))
        return True

    def _run(self) -> None:
        while True:
            item = self._q.get()
            try:
                self._store(*item)
            # vlint: allow-broad-except(drainer thread must survive)
            except Exception:  # pragma: no cover - keep draining
                pass
            finally:
                self._q.task_done()

    def _store(self, storage, lc, per_tenant, nbytes, ctx) -> None:
        try:
            with ingestledger.use_batch(ctx):
                with ingestledger.hop("store"):
                    storage.must_add_columns(lc)
        # vlint: allow-broad-except(async store: any failure must roll dropped so the ledger balances)
        except Exception:
            for tenant, rows in per_tenant.items():
                ingestledger.note_dropped(
                    tenant, rows, "pipeline_store_error",
                    batch_id=ctx.batch_id if ctx is not None else None)
            with self._mu:
                self.dropped_total += lc.nrows
            return
        for tenant, rows in per_tenant.items():
            activity.note_ingest(tenant, rows,
                                 nbytes=nbytes * rows // lc.nrows)
        with self._mu:
            self.stored_total += lc.nrows

    def drain(self) -> None:
        """Block until every queued batch has stored (tests + shutdown)."""
        q = self._q
        if q is not None:
            q.join()

    def metrics_samples(self) -> list:
        with self._mu:
            depth = self._q.qsize() if self._q is not None else 0
            return [
                ("vl_insert_pipeline_batches_total", {},
                 self.enqueued_total),
                ("vl_insert_pipeline_rows_stored_total", {},
                 self.stored_total),
                ("vl_insert_pipeline_rows_dropped_total", {},
                 self.dropped_total),
                ("vl_insert_pipeline_queue_depth", {}, depth),
            ]


INSERT_PIPELINE = _InsertPipeline()


def handle_internal_insert(storage, args, body: bytes) -> int:
    if args.get("version", PROTOCOL_VERSION) != PROTOCOL_VERSION:
        raise ValueError(f"unsupported protocol version "
                         f"{args.get('version')!r}")
    # the batch identity the sender propagated (the ingest twin of
    # parent_qid): re-enter the frontend's in-flight record when it
    # lives in THIS process (in-process clusters), else register the
    # propagated id so the hop still traces/ledgers.  Legacy senders
    # without batch args get a fresh internal-origin record.
    try:
        accept = float(args.get("batch_ts") or 0.0)
    except ValueError:
        accept = 0.0
    with ingestledger.begin_batch(
            args.get("batch_tenant") or "0:0", origin="internal",
            batch_id=args.get("batch_id") or None,
            accept_unix=accept or None):
        return _internal_insert(storage, args, body)


def _internal_insert(storage, args, body: bytes) -> int:
    with ingestledger.hop("decode"):
        # an undecodable body raises ValueError: the sender's
        # corruption, not our 500 — whole-batch 400
        data = _zstd.decompress(body, max_output_size=1 << 30)
    if data.startswith(wire_ingest.INSERT_MAGIC):
        # typed i1 body (self-describing: JSON lines start with "{").
        # With the kill switch thrown this node speaks legacy ONLY —
        # the 400 tells the sender to re-encode and pin this node to
        # JSON lines (the mixed-version fallback discipline).
        if not wire_ingest.wire_typed_insert_enabled():
            raise ValueError(
                "typed insert frames disabled (VL_WIRE_TYPED_INSERT=0)")
        with ingestledger.hop("decode"):
            lc = wire_ingest.decode_frame(data)  # WireInsertError -> 400
        wire_ingest.note("rx_frames_typed")
        wire_ingest.note("rx_bytes_typed", len(body))
        wire_ingest.note("rx_rows_typed", lc.nrows)
        if lc.nrows:
            # entry roll BEFORE the storage chokepoint's `stored` roll
            per_tenant = wire_ingest.columns_tenant_rows(lc)
            for tenant, rows in per_tenant.items():
                ingestledger.note_received(tenant, rows)
            if not INSERT_PIPELINE.submit(storage, lc, per_tenant,
                                          len(data)):
                with ingestledger.hop("store"):
                    storage.must_add_columns(lc)
                for tenant, rows in per_tenant.items():
                    activity.note_ingest(
                        tenant, rows, nbytes=len(data) * rows // lc.nrows)
        return lc.nrows
    lr = LogRows()
    n = 0
    per_tenant: dict = {}
    with ingestledger.hop("decode"):
        for line in data.splitlines():
            if not line:
                continue
            row = json.loads(line)
            tenant = TenantID(int(row.get("a", 0)), int(row.get("p", 0)))
            tags_str = row.get("s", "")
            hi, lo = stream_id_hash(tags_str.encode("utf-8"))
            lr.timestamps.append(int(row["t"]))
            lr.rows.append([(k, v) for k, v in row["f"]])
            lr.stream_ids.append(StreamID(tenant, hi, lo))
            lr.stream_tags_str.append(tags_str)
            lr.tenants.append(tenant)
            per_tenant[tenant] = per_tenant.get(tenant, 0) + 1
            n += 1
    wire_ingest.note("rx_frames_json")
    wire_ingest.note("rx_bytes_json", len(body))
    wire_ingest.note("rx_rows_json", n)
    if n:
        for tenant, rows in per_tenant.items():
            ingestledger.note_received(tenant, rows)
        with ingestledger.hop("store"):
            storage.must_add_rows(lr)
        for tenant, rows in per_tenant.items():
            # apportion DECOMPRESSED bytes so vl_tenant_ingest_bytes_
            # total means the same thing on storage nodes as on
            # frontends (uncompressed request payload)
            activity.note_ingest(tenant, rows,
                                 nbytes=len(data) * rows // n)
    return n


# ---------------- client side: sharded ingest ----------------

# re-exported for callers that think in cluster terms; defined in the
# policy layer so the HTTP app can catch it without importing cluster
InsertRejectedError = netrobust.InsertRejectedError


class _ShardBodies:
    """Per-shard lazy wire-body cache: the typed i1 body and the legacy
    JSON-lines body are each built AT MOST ONCE per batch, whatever
    combination of preferred/fallback/re-routed sends ends up used —
    a retry never re-pays per-row encoding."""

    __slots__ = ("lc", "_typed", "_legacy")

    def __init__(self, lc):
        self.lc = lc
        self._typed = None
        self._legacy = None

    def typed(self) -> bytes | None:
        """The i1 body, or None when the batch can't ride the format
        (arena/tenant-id overflow — it falls back to legacy lines)."""
        if self._typed is None:
            try:
                self._typed = wire_ingest.encode_columns(self.lc)
            except ValueError:
                self._typed = b""
        return self._typed or None

    def legacy(self) -> bytes:
        if self._legacy is None:
            self._legacy = wire_ingest.encode_legacy_columns(self.lc)
        return self._legacy


class NetInsertStorage:
    """LogRowsStorage that ships rows to storage nodes by stream hash.

    Implements the reference's placement policy (stream-hash routing
    for locality, re-routing to the next healthy node —
    netinsert.go:368-409, 283-289) on top of the shared fault-policy
    layer: per-node circuit breakers (netrobust.breaker_for — the same
    breakers the select fan-out feeds), client-error classification
    (4xx surfaces, 5xx/transport breaks, ingest 429s honor Retry-After
    via breaker.throttle), and a durable per-node spool: when
    re-routing exhausts healthy nodes the already-serialized shard body
    lands in a PersistentQueue (bounded by VL_INSERT_SPOOL_MAX_BYTES)
    and a background thread replays it once the node's breaker lets a
    probe through — a storage-node outage delays rows instead of
    dropping them."""

    def __init__(self, node_urls: list, timeout: float = 30.0,
                 spool_dir: str | None = None):
        if not node_urls:
            raise ValueError("no storage nodes configured")
        self.urls = [u.rstrip("/") for u in node_urls]
        self.timeout = timeout
        # nodes that rejected an i1 frame stay pinned to legacy JSON
        # lines for this process's lifetime (mixed-version discipline);
        # plain set: single-item ops are atomic under the GIL
        self._legacy_nodes: set[int] = set()
        self._encode_pool = wire_ingest.acquire_pool()
        self._spool_dir = spool_dir
        self._spools: dict[int, object] = {}
        self._spool_mu = threading.Lock()
        self._replay_stop = threading.Event()
        self._replay_wake = threading.Event()
        self._replay_thread = None
        if self._spool_enabled():
            # leftover spools from a previous process must replay even
            # if this process never spools: open every existing queue
            for idx in range(len(self.urls)):
                if os.path.isdir(self._spool_path(idx)):
                    self._spool_queue(idx)
            self._start_replay()

    def _spool_enabled(self) -> bool:
        return self._spool_dir is not None and \
            netrobust.spool_max_bytes() > 0

    def _spool_path(self, idx: int) -> str:
        """One node's spool directory, keyed by URL hash so a node
        list reorder never mixes queues (the ONE place the layout is
        defined: startup discovery and queue creation both use it)."""
        import hashlib
        return os.path.join(
            self._spool_dir,
            hashlib.sha256(self.urls[idx].encode()).hexdigest()[:16])

    def must_add_rows(self, lr: LogRows) -> None:
        if not len(lr):
            return
        self.must_add_columns(wire_ingest.rows_to_columns(lr))

    def must_add_columns(self, lc) -> None:
        """Ship a columnar batch: shard by stream hash, encode each
        shard's wire body ONCE (i1 when the node speaks it, legacy
        JSON lines otherwise), deliver with re-route + durable-spool
        semantics.  Multi-shard encodes run on the shared encoder pool
        (numpy packing + zstd drop the GIL)."""
        if lc.nrows == 0:
            return
        batch = ingestledger.current_batch()
        with ingestledger.hop("shard"):
            shards = sorted(wire_ingest.split_columns_by_node(
                lc, len(self.urls)).items())
            items = [(node, _ShardBodies(slc)) for node, slc in shards]
        if len(items) > 1:
            with ingestledger.hop("encode"):
                for f in [self._encode_pool.submit(
                        self._preferred_body, node, bodies)
                        for node, bodies in items]:
                    f.result()
        errors = []
        for node, bodies in items:
            # per-tenant shard rows for the conservation rolls; only
            # batch-tracked flows ledger (journal self-ingest and
            # direct test writes carry no ambient batch)
            tenant_rows = wire_ingest.columns_tenant_rows(bodies.lc) \
                if batch is not None else None
            try:
                with ingestledger.hop("ship"):
                    delivered = self._send_shard(node, bodies) or any(
                        alt != node and self._send_shard(alt, bodies)
                        for alt in range(len(self.urls)))
            except InsertRejectedError:
                # the 400 path is terminal for these rows: the client
                # gets the rejection, nothing is retried or spooled
                if tenant_rows:
                    for t, rows in tenant_rows.items():
                        ingestledger.note_dropped(t, rows,
                                                  "rejected_by_node")
                raise
            if delivered:
                # re-route to any healthy node already folded in above
                # (data locality is a preference, not a correctness
                # requirement)
                if tenant_rows:
                    for t, rows in tenant_rows.items():
                        ingestledger.note_forwarded(t, rows)
                continue
            # every node is down/throttled: spool durably and replay
            # when the shard's node recovers — delay, don't drop.
            # The ALREADY-ENCODED body spools verbatim: replay ships
            # the same bytes, no re-encode per attempt.
            with ingestledger.hop("spool"):
                spooled = self._spool(
                    node, self._preferred_body(node, bodies),
                    nrows=bodies.lc.nrows, tenant_rows=tenant_rows,
                    batch=batch)
            if spooled:
                continue
            errors.append(f"all nodes down for shard {node}")
        if errors:
            raise IOError("; ".join(errors))

    def _node_speaks_typed(self, idx: int) -> bool:
        return wire_ingest.wire_typed_insert_enabled() and \
            idx not in self._legacy_nodes

    def _preferred_body(self, idx: int, bodies: _ShardBodies) -> bytes:
        """The wire body this node should receive (building it if
        needed) — the pool pre-encode and the spool both route here so
        format choice has exactly one home."""
        if self._node_speaks_typed(idx):
            body = bodies.typed()
            if body is not None:
                return body
        return bodies.legacy()

    def _send_shard(self, idx: int, bodies: _ShardBodies) -> bool:
        """One node delivery with the typed→legacy sticky fallback: a
        4xx on an i1 frame pins the node to legacy JSON lines and
        resends the SAME batch once (negotiation without a handshake,
        the t1 discipline on the insert hop)."""
        typed_body = bodies.typed() if self._node_speaks_typed(idx) \
            else None
        if typed_body is None:
            return self._send(idx, bodies.legacy())
        try:
            return self._send(idx, typed_body)
        except InsertRejectedError:
            self._legacy_nodes.add(idx)
            wire_ingest.note("fallbacks")
            events.emit("wire_fallback", url=self.urls[idx],
                        requested=wire_ingest.WIRE_INSERT_FORMAT,
                        hop="insert")
            try:
                return self._send(idx, bodies.legacy())
            except InsertRejectedError:
                # the legacy body was rejected too: the BATCH is the
                # problem, not the node's protocol — unpin it
                self._legacy_nodes.discard(idx)
                raise

    @staticmethod
    def _batch_args(batch_meta: dict | None) -> str:
        """The propagated batch identity on /internal/insert — the
        ingest twin of parent_qid.  From the spool record's header on
        replay (``batch_meta``), else from the ambient batch."""
        from urllib.parse import urlencode
        if batch_meta is not None:
            args = {"batch_id": batch_meta.get("batch_id", ""),
                    "batch_tenant": batch_meta.get("tenant", "")}
            if batch_meta.get("ts"):
                args["batch_ts"] = f"{batch_meta['ts']:.6f}"
        else:
            ctx = ingestledger.current_batch()
            if ctx is None:
                return ""
            args = {"batch_id": ctx.batch_id, "batch_tenant": ctx.tenant,
                    "batch_ts": f"{ctx.accept_unix:.6f}"}
        return "&" + urlencode(args)

    def _send(self, idx: int, body: bytes,
              batch_meta: dict | None = None) -> bool:
        """One policy-managed delivery attempt.  False means 'this node
        cannot take the batch right now' (down/throttled — breaker
        accounting already done inside netrobust.request); a 4xx
        rejection raises InsertRejectedError instead, because re-routing
        a malformed batch would just cascade the rejection."""
        url = self.urls[idx]
        try:
            status, _headers, rbody = netrobust.request(
                url, f"/internal/insert?version={PROTOCOL_VERSION}"
                     f"{self._batch_args(batch_meta)}",
                body,
                headers={"Content-Type": "application/octet-stream"},
                timeout=self.timeout)
        except (IOError, OSError):
            return False
        if 200 <= status < 300:
            return True
        if status != 429 and 400 <= status < 500:
            raise InsertRejectedError(
                f"storage node {url} rejected the batch: HTTP {status}: "
                f"{rbody[:200].decode('utf-8', 'replace')}")
        return False  # 429 (throttled via Retry-After) or 5xx

    # ---- the durable spool ----

    def _spool_queue(self, idx: int):
        from ..utils.persistentqueue import PersistentQueue
        with self._spool_mu:
            q = self._spools.get(idx)
            if q is None:
                q = PersistentQueue(
                    self._spool_path(idx),
                    max_pending_bytes=netrobust.spool_max_bytes())
                self._spools[idx] = q
            return q

    def _spool(self, idx: int, body: bytes, nrows: int,
               tenant_rows: dict | None = None, batch=None) -> bool:
        if not self._spool_enabled():
            if tenant_rows:
                # spool disabled is a hard drop for a batch-tracked
                # shard once every node refused it
                for t, rows in tenant_rows.items():
                    ingestledger.note_dropped(t, rows, "spool_disabled")
            return False
        from ..utils.persistentqueue import QueueOverflowError
        q = self._spool_queue(idx)
        was_empty = q.pending_bytes() == 0
        rec = body
        if batch is not None and tenant_rows:
            # self-describing spool record: replay (this process or the
            # next one after a restart) still attributes the rows to
            # their batch, tenant and accept time
            primary = max(tenant_rows, key=tenant_rows.get)
            rec = ingestledger.wrap_record(
                body, batch.batch_id, primary, nrows,
                accept_unix=batch.accept_unix)
        try:
            q.append(rec)
        except QueueOverflowError:
            netrobust.note("spool_overflow")
            events.emit("spool_overflow", node=self.urls[idx],
                        rows=nrows, pending_bytes=q.pending_bytes())
            if tenant_rows:
                for t, rows in tenant_rows.items():
                    ingestledger.note_dropped(t, rows, "spool_overflow")
            return False
        netrobust.note("spooled_blocks")
        netrobust.note("spooled_rows", nrows)
        if tenant_rows:
            for t, rows in tenant_rows.items():
                ingestledger.note_spooled(t, rows)
        if was_empty:
            # one event per outage burst, not per batch
            events.emit("ingest_spool_start", node=self.urls[idx])
        self._start_replay()
        self._replay_wake.set()
        return True

    def _start_replay(self) -> None:
        with self._spool_mu:
            if self._replay_thread is None:
                self._replay_thread = threading.Thread(
                    target=self._replay_loop, daemon=True,
                    name="vl-insert-spool-replay")
                self._replay_thread.start()

    def _replay_loop(self) -> None:
        """Drain per-node spools back to their nodes.  Paced by the
        breakers: while a node's circuit is open the send attempt is
        refused instantly, and the half-open probe IS the replay —
        recovery and replay are one mechanism."""
        while not self._replay_stop.is_set():
            self._replay_wake.wait(0.25)
            self._replay_wake.clear()
            if self._replay_stop.is_set():
                return
            with self._spool_mu:
                spools = list(self._spools.items())
            for idx, q in spools:
                drained = 0
                while not self._replay_stop.is_set() and \
                        q.pending_bytes() > 0:
                    data = q.read(timeout=None)
                    if data is None:
                        break
                    # batch-tracked records carry a self-describing
                    # header (wrap_record); pre-upgrade records pass
                    # through with meta=None and skip the ledger
                    meta, payload = ingestledger.unwrap_record(data)
                    # a node already pinned to legacy can't take a
                    # spooled i1 frame: re-encode the SAME rows as
                    # JSON lines (typed frames replay verbatim)
                    send_data = payload
                    if idx in self._legacy_nodes:
                        alt = wire_ingest.reencode_legacy(payload)
                        if alt is not None:
                            send_data = alt
                    try:
                        with ingestledger.hop(
                                "replay",
                                tenant=meta["tenant"] if meta else None):
                            sent = self._send(idx, send_data,
                                              batch_meta=meta)
                        if not sent:
                            break
                    except InsertRejectedError:
                        verdict = "poison"
                        if send_data is payload:
                            verdict = self._replay_reject_fallback(
                                idx, q, data, payload, meta)
                        if verdict == "ok":
                            drained += 1
                            continue
                        if verdict == "down":
                            break   # keep the block; retry later
                        # a poisoned block must not wedge the whole
                        # queue behind it: drop it, loudly
                        netrobust.note("spool_rejected_blocks")
                        events.emit("spool_block_rejected",
                                    node=self.urls[idx])
                        if meta:
                            ingestledger.note_dropped(
                                meta["tenant"], meta["nrows"],
                                "spool_block_rejected",
                                batch_id=meta.get("batch_id"),
                                from_spool=True)
                        q.ack(len(data))
                        continue
                    q.ack(len(data))
                    drained += 1
                    netrobust.note("replayed_blocks")
                    if meta:
                        ingestledger.note_replayed(
                            meta["tenant"], meta["nrows"],
                            batch_id=meta.get("batch_id"))
                if drained and q.pending_bytes() == 0:
                    events.emit("ingest_spool_replayed",
                                node=self.urls[idx], blocks=drained)

    def _replay_reject_fallback(self, idx: int, q, data: bytes,
                                payload: bytes,
                                meta: dict | None) -> str:
        """A spooled body was rejected: if it is an i1 frame, the node
        may have stopped speaking typed between spool time and replay
        (downgrade / kill switch) — pin the node to legacy and retry
        the SAME rows as JSON lines once.  Returns 'ok' (delivered +
        acked), 'down' (node unavailable: keep the block, retry
        later), or 'poison' (rejected either way: caller drops it).
        ``data`` is the raw spool record (what ack() measures),
        ``payload`` the wire body inside it."""
        legacy = wire_ingest.reencode_legacy(payload)
        if legacy is None:
            return "poison"       # not typed / undecodable
        self._legacy_nodes.add(idx)
        wire_ingest.note("fallbacks")
        events.emit("wire_fallback", url=self.urls[idx],
                    requested=wire_ingest.WIRE_INSERT_FORMAT,
                    hop="insert-replay")
        try:
            if self._send(idx, legacy, batch_meta=meta):
                q.ack(len(data))
                netrobust.note("replayed_blocks")
                if meta:
                    ingestledger.note_replayed(
                        meta["tenant"], meta["nrows"],
                        batch_id=meta.get("batch_id"))
                return "ok"
            return "down"
        except InsertRejectedError:
            # rejected as legacy too: genuinely poisoned — the batch
            # was the problem, not the node's protocol, so unpin
            self._legacy_nodes.discard(idx)
            return "poison"

    def spool_pending_bytes(self) -> int:
        with self._spool_mu:
            spools = list(self._spools.values())
        return sum(q.pending_bytes() for q in spools)

    def spool_metrics_samples(self) -> list:
        """(base, labels, value) gauges for Metrics.render."""
        with self._spool_mu:
            spools = list(self._spools.items())
        out = []
        for idx, q in spools:
            lbl = {"node": self.urls[idx]}
            # vlint: allow-per-row-emit(metric samples, bounded by node count)
            out.append(("vl_insert_spool_bytes", lbl,
                        q.pending_bytes()))
            out.append(("vl_insert_spool_entries", lbl,
                        q.pending_entries()))
            out.append(("vl_insert_spool_oldest_age_seconds", lbl,
                        round(q.oldest_age_seconds(), 3)))
        return out

    def spool_status(self) -> dict:
        """Per-node spool depth/age for GET /insert/status — the
        wedged-spool view that matters mid-outage."""
        with self._spool_mu:
            spools = list(self._spools.items())
        # vlint: allow-per-row-emit(introspection metadata, bounded by node count)
        nodes = [{"node": self.urls[idx],
                  "pending_bytes": q.pending_bytes(),
                  "entries": q.pending_entries(),
                  "oldest_age_seconds": round(q.oldest_age_seconds(), 3)}
                 for idx, q in spools]
        return {"pending_bytes": sum(n["pending_bytes"] for n in nodes),
                "nodes": nodes}

    def close(self) -> None:
        self._replay_stop.set()
        self._replay_wake.set()
        t = self._replay_thread
        if t is not None:
            t.join(timeout=5)
        with self._spool_mu:
            spools, self._spools = list(self._spools.values()), {}
        for q in spools:
            q.close()
        wire_ingest.release_pool()


# ---------------- client side: scatter-gather select ----------------

def _node_http_error(url: str,
                     e: netrobust.NodeHTTPError) -> Exception:
    """Map a storage node's HTTP error for the fan-out paths: a 429
    (the node's admission control shed us) becomes AdmissionShed so the
    frontend answers 429 + Retry-After with the node's reason and
    concurrency hints — overload propagates as overload, not as an
    internal error.  Other statuses keep the NodeHTTPError: a 4xx
    means this frontend's sub-request was rejected by a live node
    (version/endpoint skew) — never partial-eligible, never a breaker
    trip, surfaced as an internal cluster error (HTTP 500) like the
    legacy path's IOError; 5xx never reaches here (netrobust converts
    it to NodeDownError after retries)."""
    if e.status != 429:
        return e
    try:
        info = json.loads(e.body.decode("utf-8", "replace"))
    except ValueError:
        info = {}
    return sched.AdmissionShed(
        info.get("reason", "queue_full"),
        f"storage node {url} shed the sub-query: "
        f"{info.get('error', 'overloaded')}",
        retry_after=netrobust.retry_after_s(e.headers),
        # forward the node's concurrency hints so the frontend's 429
        # carries X-VL-Concurrency-* end to end
        limit=info.get("limit"),
        current=info.get("current"))


# ---------------- federated introspection (cluster observability) ----------------
#
# The cluster-wide views of the PR 6 registry endpoints: a frontend
# fans one introspection request out to every storage node through the
# netrobust policy layer (select-path breaker gating, injected faults)
# and merges the answers.  A down/hung node is DATA here — marked
# `up: false` in the per-node metadata — never a query failure: the
# federated view must work best exactly when part of the cluster does
# not.

# per-node bound on one introspection fan-out / cancel propagation;
# a hung node costs at most this, and its breaker opens for next time
FED_TIMEOUT_S = 5.0


def _fanout_json(urls, path: str, *, method: str = "GET",
                 timeout: float | None = None, retry: bool = True):
    """One introspection request to every node in parallel.  Returns
    (results, failures): url -> parsed JSON body / url -> error string.
    Never raises — node loss degrades the view, marked per node."""
    from concurrent.futures import ThreadPoolExecutor
    if not urls:
        return {}, {}
    if timeout is None:
        # late-bound so tests/operators can shrink the bound
        timeout = FED_TIMEOUT_S

    # one retry on a transport blip (idempotent introspection; the
    # breaker makes the repeat near-free when the node is truly down);
    # callers with side effects that COUNT (cancel propagation) pass
    # retry=False so a blip after the node acted can't double-count
    attempts = 1 + min(1, netrobust.net_retries()) if retry else 1

    def one(url: str):
        err = ""
        for _ in range(attempts):
            try:
                status, _h, body = netrobust.request(
                    url, path, method=method, timeout=timeout,
                    gate="select")
            except (IOError, OSError) as e:
                err = str(e)
                continue
            if status != 200:
                return url, None, f"HTTP {status}"
            try:
                return url, json.loads(body), None
            except ValueError as e:
                return url, None, f"bad JSON: {e}"
        return url, None, err

    with ThreadPoolExecutor(max_workers=len(urls)) as ex:
        rows = list(ex.map(one, list(urls)))
    results = {u: obj for u, obj, err in rows if err is None}
    failures = {u: err for u, _obj, err in rows if err is not None}
    return results, failures


def federated_active_queries(urls, tenant: str | None = None,
                             timeout: float | None = None) -> dict:
    """GET /select/logsql/active_queries?cluster=1: this frontend's
    live records with each node's sub-query records nested under their
    parent query (matched by the propagated parent_qid == the parent's
    global_qid).  Node records with no parent here (another frontend's
    fan-out, direct node queries) land in ``unlinked`` with node
    attribution; a node that cannot answer is marked down."""
    path = "/select/logsql/active_queries"
    if tenant:
        from urllib.parse import urlencode
        path += "?" + urlencode({"tenant": tenant})
    # local view: frontend-level records only — this process's OWN
    # internal sub-query records (combined frontend+storage deployments,
    # in-process clusters) are re-fetched via the node fan-out below
    # and must not show up twice
    local = [r for r in activity.active_snapshot(tenant=tenant)
             if r["endpoint"] != "/internal/select/query"]
    by_gqid: dict[str, dict] = {}
    for rec in local:
        rec["global_qid"] = activity.global_qid(rec["qid"])
        rec["storage_node_queries"] = []
        by_gqid[rec["global_qid"]] = rec
    results, failures = _fanout_json(urls, path, timeout=timeout)
    nodes, unlinked = [], []
    for url in urls:
        if url in failures:
            # vlint: allow-per-row-emit(introspection metadata, bounded by node count)
            nodes.append({"node": url, "up": False,
                          "error": failures[url]})
            continue
        data = results[url].get("data") or []
        sub = [r for r in data
               if r["endpoint"] == "/internal/select/query"]
        # vlint: allow-per-row-emit(introspection metadata, bounded by node count)
        nodes.append({"node": url, "up": True, "active": len(data)})
        for nrec in sub:
            nrec["node"] = url
            parent = by_gqid.get(nrec.get("parent_qid") or "")
            if parent is not None:
                parent["storage_node_queries"].append(nrec)
            else:
                unlinked.append(nrec)
    out = {"status": "ok", "cluster": True, "data": local,
           "nodes": nodes, "scheduler": sched.snapshot()}
    if unlinked:
        out["unlinked"] = unlinked
    if failures:
        out["failed_nodes"] = sorted(failures)
    return out


def _rec_fingerprint(rec: dict) -> str:
    """Content identity of one completed-query record, attribution
    excluded (the cross-process dedup key for the federated merge)."""
    return json.dumps({k: v for k, v in rec.items() if k != "node"},
                      sort_keys=True, default=str)


def federated_top_queries(urls, n: int = 10, by: str = "duration",
                          tenant: str | None = None,
                          timeout: float | None = None) -> dict:
    """GET /select/logsql/top_queries?cluster=1: this frontend's
    completed ring merged with every node's, re-sorted on the same
    dimension, each record attributed to where it ran (``node``:
    "frontend" or the node URL).  Raises ValueError on an unknown
    ``by`` (HTTP 400 upstream, same as the local form)."""
    from urllib.parse import urlencode
    key, default = activity.top_sort_key(by)
    merged = [dict(r, node="frontend")
              for r in activity.top_queries(n, by=by, tenant=tenant)]
    # dedup guard for combined frontend+storage deployments (and
    # in-process clusters), where the node fan-out re-fetches records
    # this process's own ring already contributed: a record's full
    # content minus the attribution IS its identity
    seen = {_rec_fingerprint(r) for r in merged}
    args = {"n": str(n), "by": by}
    if tenant:
        args["tenant"] = tenant
    path = "/select/logsql/top_queries?" + urlencode(args)
    results, failures = _fanout_json(urls, path, timeout=timeout)
    nodes = []
    for url in urls:
        if url in failures:
            # vlint: allow-per-row-emit(introspection metadata, bounded by node count)
            nodes.append({"node": url, "up": False,
                          "error": failures[url]})
            continue
        # vlint: allow-per-row-emit(introspection metadata, bounded by node count)
        nodes.append({"node": url, "up": True})
        for r in results[url].get("top_queries") or []:
            fp = _rec_fingerprint(r)
            if fp in seen:
                continue
            seen.add(fp)
            merged.append(dict(r, node=url))
    merged.sort(key=lambda r: r.get(key, default), reverse=True)
    out = {"status": "ok", "cluster": True,
           "top_queries": merged[:max(n, 0)], "nodes": nodes}
    if failures:
        out["failed_nodes"] = sorted(failures)
    return out


def federated_insert_status(urls, local: dict,
                            timeout: float | None = None) -> dict:
    """GET /insert/status?cluster=1: this frontend's own payload (the
    spool lives here) plus every storage node's, per node — never
    summed: combined frontend+storage deployments and in-process
    clusters share one process-global ledger, so summing would
    multi-count (the same reason federated_top_queries dedups).  A
    node that cannot answer is marked down — exactly the state in
    which its unshipped batches show as this frontend's stalled/
    spooled entries."""
    results, failures = _fanout_json(urls, "/insert/status",
                                     timeout=timeout)
    nodes = []
    stalled = local.get("stalled_batches", 0)
    for url in urls:
        if url in failures:
            # vlint: allow-per-row-emit(introspection metadata, bounded by node count)
            nodes.append({"node": url, "up": False,
                          "error": failures[url]})
            continue
        p = results[url]
        stalled = max(stalled, p.get("stalled_batches", 0))
        # vlint: allow-per-row-emit(introspection metadata, bounded by node count)
        nodes.append({"node": url, "up": True,
                      "stalled_batches": p.get("stalled_batches", 0),
                      "in_flight": len(p.get("in_flight") or []),
                      "spool": p.get("spool"),
                      "ledger": p.get("ledger")})
    out = dict(local)
    out.update({"cluster": True, "nodes": nodes,
                "stalled_batches_cluster": stalled})
    if failures:
        out["failed_nodes"] = sorted(failures)
    return out


def propagate_cancel(urls, qid: str, gqid: str,
                     timeout: float | None = None) -> dict:
    """Cascade one frontend cancel to every storage node (POST
    /internal/select/cancel?parent_qid=): each node trips the cancel
    flag of every record registered under the query's global_qid, so
    the sub-queries' device windows drain immediately — replacing the
    frontend-disconnect probe (which a node only notices at its next
    frame write) as the primary kill mechanism.  Best-effort by
    design: a dead node cannot be running the sub-query anyway, so its
    failure is recorded (journal ``query_cancel_propagated``), never
    raised."""
    from urllib.parse import urlencode
    path = ("/internal/select/cancel?"
            + urlencode({"parent_qid": gqid}))
    results, failures = _fanout_json(urls, path, method="POST",
                                     timeout=timeout, retry=False)
    cancelled = sum(int(r.get("cancelled") or 0)
                    for r in results.values())
    fail_fields = {"failed_nodes": ",".join(sorted(failures))} \
        if failures else {}
    events.emit("query_cancel_propagated", qid=qid, parent_qid=gqid,
                cancelled=cancelled, nodes_ok=len(results),
                nodes_failed=len(failures), **fail_fields)
    out = {"cancelled": cancelled, "nodes_ok": len(results),
           "nodes_failed": len(failures)}
    if failures:
        out["failed_nodes"] = sorted(failures)
    return out


def federated_standing_queries(urls,
                               timeout: float | None = None) -> dict:
    """GET /select/logsql/standing_query?cluster=1: this frontend's
    standing registrations plus every node's, each node's entries
    attributed to it.  A node that cannot answer is marked down —
    degraded view, never an error."""
    from ..engine.standing import manager as _standing
    path = "/select/logsql/standing_query"
    local = _standing.standing_snapshot()
    results, failures = _fanout_json(urls, path, timeout=timeout)
    nodes = []
    for url in urls:
        if url in failures:
            # vlint: allow-per-row-emit(introspection metadata, bounded by node count)
            nodes.append({"node": url, "up": False,
                          "error": failures[url]})
            continue
        entries = results[url].get("standing_queries") or []
        # vlint: allow-per-row-emit(introspection metadata, bounded by node count)
        nodes.append({"node": url, "up": True,
                      "standing_queries": entries})
    out = {"status": "ok", "cluster": True,
           "standing_queries": local, "nodes": nodes}
    if failures:
        out["failed_nodes"] = sorted(failures)
    return out


def federated_standing_unregister(urls, fp: str,
                                  timeout: float | None = None) -> dict:
    """Cascade one standing-query unregister to every storage node
    (POST /select/logsql/standing_query?unregister=1): a panel torn
    down at the frontend must not leave node-local registrations
    re-evaluating forever.  retry=False — an unregister that landed
    must not double-count on a transport blip; best-effort like cancel
    propagation (a dead node's registry died with it)."""
    from urllib.parse import urlencode
    path = ("/select/logsql/standing_query?"
            + urlencode({"unregister": "1", "fingerprint": fp}))
    results, failures = _fanout_json(urls, path, method="POST",
                                     timeout=timeout, retry=False)
    removed = sum(int(r.get("removed") or 0)
                  for r in results.values())
    out = {"removed": removed, "nodes_ok": len(results),
           "nodes_failed": len(failures)}
    if failures:
        out["failed_nodes"] = sorted(failures)
    return out


class NetSelectStorage:
    """Query layer over N storage nodes: remote/local pipe split, parallel
    fan-out, first-error cancellation (netselect.go:324-369)."""

    def __init__(self, node_urls: list, timeout: float = 120.0):
        if not node_urls:
            raise ValueError("no storage nodes configured")
        self.urls = [u.rstrip("/") for u in node_urls]
        self.timeout = timeout
        # request typed columnar frames from storage nodes (nodes that
        # predate the format, or run VL_WIRE_TYPED=0, ignore the arg
        # and answer with legacy JSON frames — handled per frame)
        self.wire_typed = wire_typed_enabled()

    def net_explain(self, tenants, q, mode: str,
                    timestamp: int | None = None,
                    deadline: float | None = None,
                    include_trace: bool = False) -> dict:
        """Cluster EXPLAIN: scatter the (pipe-split) query to every
        storage node with explain=<mode>, merge the per-node plan trees
        under storage_node nodes — the same merge shape ?trace=1 uses —
        and fold the node predictions into one cluster summary
        (counts/seconds sum; duration is the max, nodes run in
        parallel)."""
        from concurrent.futures import ThreadPoolExecutor
        from urllib.parse import urlencode
        if isinstance(q, str):
            q = parse_query(q, timestamp)
        ts = q.timestamp if getattr(q, "timestamp", None) else \
            (timestamp or time.time_ns())
        if mode == "analyze":
            # the run needs in(<subquery>) values; a plain explain=1
            # must not execute anything, so subqueries stay symbolic
            from ..engine.searcher import init_subqueries
            init_subqueries(self, tenants, q, detach=True)
        split_mode, split_at, local_pipes = split_query(q)
        # limit pushdown parity with net_run_query: the plan (and the
        # analyze execution) must describe the sub-query each node would
        # actually run, early-exit included
        push_limit = 0
        if split_mode == "rows" and local_pipes and \
                isinstance(local_pipes[0], PipeLimit):
            push_limit = local_pipes[0].n
        tenants = list(tenants) or [TenantID(0, 0)]
        tenant_arg = ",".join(f"{t.account_id}:{t.project_id}"
                              for t in tenants)
        remaining_s = None
        if deadline is not None:
            remaining_s = max(deadline - time.monotonic(), 0.001)
        act = activity.current_activity()

        def fetch(url: str) -> dict:
            form = {
                "version": PROTOCOL_VERSION,
                "query": q.to_string(),
                "ts": str(ts),
                "mode": split_mode,
                "split_at": str(split_at),
                "limit": str(push_limit),
                "tenant": tenant_arg,
                "explain": mode,
            }
            if act.enabled:
                # identity propagation parity with net_run_query: the
                # node's explain/analyze record correlates by qid too
                form["parent_qid"] = activity.global_qid(act.qid)
            if remaining_s is not None:
                form["timeout"] = f"{remaining_s:.3f}s"
            if include_trace:
                # trace parity with the single-node path: each node's
                # analyze tree then carries its own span tree
                form["trace"] = "1"
            http_timeout = self.timeout if remaining_s is None else \
                min(self.timeout, remaining_s + 5.0)
            tree = None
            try:
                # the policy layer owns retries/breaker/deadline; an
                # explain sub-request is idempotent by construction
                frames = netrobust.node_stream(
                    url, "/internal/select/query",
                    urlencode(form).encode("utf-8"),
                    {"Content-Type":
                     "application/x-www-form-urlencoded"},
                    io_timeout=http_timeout, deadline=deadline,
                    idempotent=True)
                try:
                    for payload, _n in frames:
                        frame = json.loads(payload)
                        if "explain" in frame:
                            tree = frame["explain"]
                finally:
                    frames.close()
            except netrobust.NodeHTTPError as e:
                # a node's admission control shedding the explain
                # sub-request must surface as 429 + Retry-After at the
                # frontend, exactly like net_run_query
                raise _node_http_error(url, e) from None
            if tree is None:
                raise IOError(f"{url}: no explain frame in reply")
            return {"name": "storage_node", "url": url,
                    "explain": tree}

        with ThreadPoolExecutor(max_workers=len(self.urls)) as ex:
            nodes = list(ex.map(fetch, self.urls))
        merged: dict = {
            "name": "explain", "mode": mode, "cluster": True,
            "query": q.to_string(), "storage_nodes": nodes,
        }
        pred: dict = {}
        calibrated = True
        for node in nodes:
            np_ = node["explain"].get("predicted") or {}
            calibrated = calibrated and bool(np_.get("calibrated"))
            for k, v in np_.items():
                if not isinstance(v, (int, float)) or \
                        isinstance(v, bool):
                    continue
                if k == "duration_s":
                    pred[k] = max(pred.get(k, 0.0), v)
                else:
                    pred[k] = round(pred.get(k, 0) + v, 6)
        pred["calibrated"] = calibrated
        merged["predicted"] = pred
        return merged

    def net_run_query(self, tenants, q, write_block=None,
                      timestamp: int | None = None,
                      deadline: float | None = None,
                      partial: bool | None = None) -> None:
        """Scatter-gather one query.  ``partial=None`` resolves the
        partial-results mode from the ambient activity record (the HTTP
        layer stamps ?partial=1 there) falling back to the
        VL_PARTIAL_RESULTS default; True/False pin it."""
        from ..engine.searcher import build_processor_chain, init_subqueries
        if isinstance(q, str):
            q = parse_query(q, timestamp)
        ts = q.timestamp if getattr(q, "timestamp", None) else \
            (timestamp or time.time_ns())
        # subqueries resolve against the WHOLE cluster here, then ship as
        # literal value lists (per-shard resolution would be wrong)
        init_subqueries(self, tenants, q, detach=True)
        # storage-backed pipes (join/union/stream_context) also query the
        # cluster through this front
        for p in q.pipes:
            if hasattr(p, "init_with_storage"):
                p.init_with_storage(self, tenants, None)
        mode, split_at, local_pipes = split_query(q)

        # rate()/rate_sum() step for locally-finalized stats
        min_ts, max_ts = q.get_time_range()
        if min_ts != MIN_TS and max_ts != MAX_TS:
            step_seconds = (max_ts - min_ts + 1) / 1e9
            for p in local_pipes:
                if isinstance(p, PipeStats):
                    for fn in p.funcs:
                        if hasattr(fn, "step_seconds"):
                            fn.step_seconds = step_seconds

        push_limit = 0
        if mode == "rows" and local_pipes and \
                isinstance(local_pipes[0], PipeLimit):
            push_limit = local_pipes[0].n

        head = build_processor_chain(local_pipes,
                                     write_block or (lambda br: None))
        # external cancellation (cancel_query / disconnect abandon):
        # the frontend's registry record ends the scatter-gather the
        # same way early-done does — fetch threads stop pulling frames
        act = activity.current_activity()
        if partial is not None:
            partial_ok = partial
        else:
            pf = act.counter("partial_ok")
            partial_ok = pf > 0 if pf else netrobust.partial_default()
        lock = threading.Lock()
        stop = threading.Event()
        errors: list = []          # (url, exception) per failed node
        tenants = list(tenants) or [TenantID(0, 0)]
        tenant_arg = ",".join(f"{t.account_id}:{t.project_id}"
                              for t in tenants)

        # forward the caller's remaining deadline so storage nodes enforce
        # the same budget the single-node path would (they re-derive it via
        # query_deadline(args) from this `timeout` arg)
        remaining_s = None
        if deadline is not None:
            remaining_s = max(deadline - time.monotonic(), 0.001)
        # scatter-gather tracing: each node fetch gets a child span under
        # the caller's trace, and nodes ship their own span tree back as
        # the stream's final frame, attached under that child — one
        # merged tree for the whole cluster query
        parent_span = tracing.current_span()

        def fetch(url: str):
            from urllib.parse import urlencode
            # POST the query as a form body: materialized in(...) value
            # lists can exceed sane URL lengths
            form = {
                "version": PROTOCOL_VERSION,
                "query": q.to_string(),
                "ts": str(ts),
                "mode": mode,
                "split_at": str(split_at),
                "limit": str(push_limit),
                "tenant": tenant_arg,
            }
            if act.enabled:
                # query identity propagation: every storage node tags
                # its sub-query record/trace/journal with the frontend
                # query's cluster-unique id — the primitive the
                # federated registry and cascading cancel ride
                form["parent_qid"] = activity.global_qid(act.qid)
            if remaining_s is not None:
                form["timeout"] = f"{remaining_s:.3f}s"
            if parent_span.enabled:
                form["trace"] = "1"
            if self.wire_typed:
                form["wire"] = WIRE_FORMAT
            body = urlencode(form).encode("utf-8")
            http_timeout = self.timeout if remaining_s is None else \
                min(self.timeout, remaining_s + 5.0)
            try:
                saw_json_data = False
                with tracing.use_span(parent_span), \
                        tracing.current_span().span("storage_node",
                                                    url=url) as nsp:
                    # ALL fault policy (breaker, retries, hedging,
                    # per-read deadlines, injected faults) lives in the
                    # policy layer; this loop only decodes frames
                    frames = netrobust.node_stream(
                        url, "/internal/select/query", body,
                        {"Content-Type":
                         "application/x-www-form-urlencoded"},
                        io_timeout=http_timeout, deadline=deadline,
                        idempotent=True, span=nsp)
                    try:
                        for payload, wire_n in frames:
                            if stop.is_set() or act.is_cancelled():
                                # abandoning the stream also abandons
                                # the node's trailing trace frame — the
                                # cancellation (which aborts the node's
                                # query) outranks trace completeness,
                                # so the cut is marked instead
                                nsp.set("trace_truncated", True)
                                return
                            t_dec = time.monotonic()
                            if payload.startswith(TYPED_MAGIC):
                                br = decode_typed_frame(payload)
                                _wire_note("rx_frames_typed")
                                _wire_note("rx_bytes_typed", wire_n)
                                nsp.add("typed_frames")
                            else:
                                frame = json.loads(payload)
                                _wire_note("rx_frames_json")
                                _wire_note("rx_bytes_json", wire_n)
                                if "trace" in frame:
                                    nsp.attach(frame["trace"])
                                    continue
                                if self.wire_typed and \
                                        not saw_json_data:
                                    # we asked for typed frames; the
                                    # node answered legacy — a
                                    # mixed-version cluster running on
                                    # the fallback is worth an
                                    # operator-visible journal event
                                    saw_json_data = True
                                    _wire_note("fallbacks")
                                    events.emit("wire_fallback",
                                                url=url,
                                                requested=WIRE_FORMAT)
                                br = BlockResult.from_columns(
                                    frame.get("cols") or {},
                                    timestamps=frame.get("ts"))
                            nsp.add("wire_decode_s",
                                    time.monotonic() - t_dec)
                            nsp.add("wire_rx_bytes", wire_n)
                            nsp.add("blocks_received")
                            with lock:
                                head.write_block(br)
                                if head.is_done():
                                    stop.set()
                                    nsp.set("trace_truncated", True)
                                    return
                    finally:
                        frames.close()
            except netrobust.NodeHTTPError as e:
                # 429 -> AdmissionShed, other 4xx stay client errors;
                # both always fail the whole query (partial covers node
                # LOSS, not a sub-query the node judged invalid)
                errors.append((url, _node_http_error(url, e)))
                stop.set()
            # collected errors re-raise on the caller thread after join
            # vlint: allow-broad-except(fan-out error channel)
            except Exception as e:
                errors.append((url, e))
                if not (partial_ok and isinstance(e, (IOError, OSError))):
                    # strict mode: first error cancels the other
                    # fetches.  In partial mode a transport failure
                    # must NOT stop the surviving nodes — their merged
                    # answer IS the degraded result.
                    stop.set()

        threads = [threading.Thread(target=fetch, args=(u,), daemon=True)
                   for u in self.urls]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            # Default: no partial results — any storage-node failure
            # fails the query.  Local typed errors (memory budget,
            # deadline) raised by head.write_block re-raise unwrapped so
            # the HTTP layer maps them to 422/503 exactly as in
            # single-node mode; only genuine transport failures become
            # IOError.  A shed outranks other failures
            # deterministically: the client must see 429 + Retry-After
            # whenever ANY node shed, not only when that node's fetch
            # thread happened to error first.
            shed = next((e for _u, e in errors
                         if isinstance(e, sched.AdmissionShed)), None)
            if shed is None and partial_ok and \
                    len(errors) < len(self.urls) and \
                    all(isinstance(e, (IOError, OSError))
                        for _u, e in errors):
                # opted-in degradation: at least one node survived and
                # every failure is an availability failure — answer
                # from the survivors, loudly marked
                failed = sorted({u for u, _e in errors})
                act.set("partial_failed_nodes", failed)
                parent_span.set("partial_failed_nodes", failed)
                netrobust.note("partial_results")
                events.emit("partial_result", query=q.to_string(),
                            failed_nodes=",".join(failed),
                            surviving=len(self.urls) - len(failed))
                head.flush()
                return
            err = shed if shed is not None else errors[0][1]
            if isinstance(err, (IOError, OSError)):
                raise IOError(f"cluster query failed: {err}")
            raise err
        head.flush()
