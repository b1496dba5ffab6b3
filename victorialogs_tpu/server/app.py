"""The victoria-logs single binary: HTTP server wiring insert + select +
storage.

Reference: app/victoria-logs/main.go (request routing insert->select->storage
— main.go:79-103), app/vlinsert/main.go:61-89 (ingest routes),
app/vlselect/main.go:212-274 (query routes), app/vlstorage/main.go:208-255
(/internal/force_merge, /internal/force_flush) and the /metrics surface
(main.go:354-410).
"""

from __future__ import annotations

import gzip
import io
import json
import os
import queue
import re
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer


from ..engine.searcher import QueryTimeoutError
from ..obs import (activity, events, hist, ingestledger, journal,
                   stallwatch, tracing)
from ..storage.storage import Storage
from ..utils.memory import QueryMemoryError
from .. import sched
from .insertutil import (CommonParams, LocalLogRowsStorage,
                         LogMessageProcessor, get_tenant_id)
from . import netrobust, vlinsert
from .vlselect import (HTTPError, handle_explain, handle_facets,
                       handle_field_names, handle_field_values,
                       handle_hits, handle_query, handle_stats_query,
                       handle_stats_query_range,
                       handle_stream_field_names, handle_stream_field_values,
                       handle_stream_ids, handle_streams, handle_tail,
                       parse_common_args, query_timeout_s,
                       request_trace_root, want_explain)


def escape_label_value(v: str) -> str:
    """Prometheus text-format label-value escaping."""
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def metric_name(base: str, **labels) -> str:
    """`base{k="escaped v",...}` — the ONE place sample names with
    labels are built, so arbitrary request strings (paths, types) can
    never corrupt the exposition format."""
    if not labels:
        return base
    inner = ",".join(f'{k}="{escape_label_value(str(v))}"'
                     for k, v in sorted(labels.items()))
    return f"{base}{{{inner}}}"


# full sample name -> (base, "{labels}" or "")
_SAMPLE_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})?$")

# the canonical tenant spelling for ?tenant= filters (activity
# records label tenants "accountID:projectID"); the literal "other"
# is the registry's hard-cap overflow bucket — the label an operator
# most needs to drill into when tenant cardinality overflows
_TENANT_ARG_RE = re.compile(r"^(\d+:\d+|other)$")


def _tenant_arg(args):
    """Validated optional ?tenant= filter for the registry views:
    None when absent, the canonical "a:p" string (or the "other"
    overflow bucket) when well-formed, HTTP 400 otherwise (a malformed
    filter silently matching nothing would read as 'no queries')."""
    t = args.get("tenant", "")
    if not t:
        return None
    if not _TENANT_ARG_RE.match(t):
        raise HTTPError(400, f"invalid tenant arg {t!r} "
                             f"(want 'accountID:projectID')")
    return t


def _want_cluster(args) -> bool:
    return args.get("cluster", "") in ("1", "true", "yes")

# endpoints whose wall time IS a query execution (vl_query_duration_
# seconds); excludes /tail (connection lifetime) and introspection
_QUERY_DURATION_PATHS = frozenset((
    "/select/logsql/query", "/select/logsql/hits",
    "/select/logsql/facets", "/select/logsql/stats_query",
    "/select/logsql/stats_query_range"))


class Metrics:
    """Prometheus-text metrics registry.

    render() emits VALID exposition text: every metric gets exactly one
    `# TYPE` line with all its samples grouped directly under it,
    label values ride escape_label_value, duplicate sample names merge
    by summation (a registry counter colliding with a runner counter
    must not emit the same series twice), and the obs.hist histograms
    render with `# HELP`/`# TYPE histogram` + cumulative `le` buckets.
    tests/test_obs.py validates the output with a small parser."""

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: dict[str, int] = {}

    def inc(self, name: str, delta: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + delta

    @staticmethod
    def _split(name: str) -> tuple[str, str]:
        m = _SAMPLE_RE.match(name)
        if m is None:
            # defensive: a malformed stored name becomes a label so the
            # exposition stays parseable
            return "vl_invalid_metric_name", \
                "{name=\"" + escape_label_value(name) + "\"}"
        return m.group(1), m.group(2) or ""

    def render(self, storage: Storage, runner=None, server=None) -> str:
        # base name -> {labels_str -> value}; insertion-ordered so each
        # metric's samples stay contiguous under its TYPE line
        metrics: dict[str, dict[str, float]] = {}

        def add(name: str, v) -> None:
            base, labels = self._split(name)
            series = metrics.setdefault(base, {})
            series[labels] = series.get(labels, 0) + v

        with self._lock:
            for name in sorted(self.counters):
                add(name, self.counters[name])
        if runner is not None and hasattr(runner, "stats"):
            # device-runner counters incl. the async pipeline's
            # (dispatches issued, packed parts, in-flight high-water
            # mark, host-sync wait — tpu/batch.py BatchRunner.stats)
            for name, v in sorted(runner.stats().items()):
                add(f"vl_tpu_{name}", v)
        # filter-index host-plane budget occupancy (storage/filterbank)
        from ..storage.filterbank import bank_stats
        bs = bank_stats()
        add("vl_tpu_bloom_bank_used_bytes", bs["used_bytes"])
        add("vl_tpu_bloom_bank_max_bytes", bs["max_bytes"])
        # active-query registry: vl_active_queries by endpoint plus the
        # per-tenant select/ingest accounting the scheduler's admission
        # control consumes (obs/activity.py)
        for base, labels, v in activity.metrics_samples():
            add(metric_name(base, **labels), v)
        # scheduler surface: dispatch budget/in-flight gauges plus the
        # per-tenant admitted/shed counters and admission-queue depth
        # (victorialogs_tpu/sched)
        for base, labels, v in sched.metrics_samples():
            add(metric_name(base, **labels), v)
        # self-telemetry: event-bus totals + the previously-silent
        # truncation counters (obs/events.py) and the journal writer's
        # queue/drop/write accounting (obs/journal.py)
        for base, labels, v in events.metrics_samples():
            add(metric_name(base, **labels), v)
        for base, labels, v in journal.metrics_samples():
            add(metric_name(base, **labels), v)
        # the always-on stall watch: stalls seen by its heartbeat, gc
        # pauses by generation and the CPU of the threads that serve no
        # request (obs/stallwatch.py)
        for base, labels, v in stallwatch.metrics_samples():
            add(metric_name(base, **labels), v)
        # ingest conservation ledger: per-tenant accepted/forwarded/
        # stored/dropped{reason} rolls, derived in-flight rows and the
        # freshness watermark age (obs/ingestledger.py)
        for base, labels, v in ingestledger.metrics_samples():
            add(metric_name(base, **labels), v)
        # cluster wire-protocol accounting: typed vs legacy frame
        # counts and raw tx/rx bytes (server/cluster.py; lazy import —
        # cluster pulls in the whole select stack)
        from . import cluster as _cluster
        for base, labels, v in _cluster.wire_metrics_samples():
            add(metric_name(base, **labels), v)
        # storage-node insert pipeline (VL_INSERT_PIPELINE hop overlap):
        # queued-batch depth + stored/dropped row totals
        for base, labels, v in _cluster.INSERT_PIPELINE.metrics_samples():
            add(metric_name(base, **labels), v)
        # typed ingest wire accounting: i1 vs legacy insert bodies by
        # direction + sticky fallbacks (server/wire_ingest.py)
        from . import wire_ingest as _wire_ingest
        for base, labels, v in _wire_ingest.metrics_samples():
            add(metric_name(base, **labels), v)
        # cluster fault-policy surface: per-node breaker health
        # (vl_node_health), retry/hedge/partial counters and the
        # ingest-spool accounting (server/netrobust.py)
        from . import netrobust as _netrobust
        for base, labels, v in _netrobust.metrics_samples():
            add(metric_name(base, **labels), v)
        # standing-query plane: per-part result-cache occupancy and
        # hit/miss/eviction accounting plus the resident standing
        # registrations and their re-evaluation totals
        # (engine/standing/)
        from ..engine.standing import resultcache as _resultcache
        from ..engine.standing import manager as _standing
        for base, labels, v in _resultcache.metrics_samples():
            add(metric_name(base, **labels), v)
        for base, labels, v in _standing.metrics_samples():
            add(metric_name(base, **labels), v)
        if server is not None and \
                hasattr(getattr(server, "sink", None),
                        "spool_metrics_samples"):
            for base, labels, v in server.sink.spool_metrics_samples():
                add(metric_name(base, **labels), v)
        if server is not None and \
                getattr(server, "clusterstats", None) is not None:
            # cluster frontends: per-tenant usage rolled up across
            # storage nodes + per-node rollup liveness/staleness
            # (obs/clusterstats.py poll loop)
            for base, labels, v in server.clusterstats.metrics_samples():
                add(metric_name(base, **labels), v)
        if server is not None:
            from .. import __version__
            add(metric_name("vl_build_info", version=__version__,
                            app="victorialogs-tpu"), 1)
            add("vl_uptime_seconds",
                round(time.monotonic() - server.start_time, 3))
        s = storage.update_stats()
        gauges = {
            "vl_partitions": s["partitions"],
            "vl_streams_created_total": s["streams"],
            metric_name("vl_storage_rows", type="inmemory"):
                s["inmemory_rows"],
            metric_name("vl_storage_rows", type="file"): s["file_rows"],
            metric_name("vl_storage_rows", type="small"):
                s["small_rows"],
            metric_name("vl_storage_rows", type="big"): s["big_rows"],
            metric_name("vl_storage_parts", type="inmemory"):
                s["inmemory_parts"],
            metric_name("vl_storage_parts", type="small"):
                s["small_parts"],
            metric_name("vl_storage_parts", type="big"): s["big_parts"],
            "vl_data_size_bytes": s["compressed_size"],
            "vl_uncompressed_data_size_bytes": s["uncompressed_size"],
            metric_name("vl_rows_dropped_total", reason="too_old"):
                s["rows_dropped_too_old"],
            metric_name("vl_rows_dropped_total", reason="too_new"):
                s["rows_dropped_too_new"],
            "vl_storage_is_read_only": int(s["is_read_only"]),
            # merge/flush health (storage/datadb.py stats): queued tier
            # compactions, total merges, staleness of in-RAM rows
            "vl_storage_pending_merges": s["pending_merges"],
            "vl_storage_merges_total": s["merges_done"],
            "vl_storage_flush_age_seconds":
                round(s["flush_age_seconds"], 3),
        }
        for name, v in gauges.items():
            add(name, v)

        out = []
        for base, series in metrics.items():
            kind = "counter" if base.endswith("_total") else "gauge"
            out.append(f"# TYPE {base} {kind}")
            for labels, v in series.items():
                # ints render exactly (byte budgets overflow %g), floats
                # compactly
                v_s = str(v) if isinstance(v, int) else format(v, ".9g")
                out.append(f"{base}{labels} {v_s}")
        out.extend(hist.render_all())
        return "\n".join(out) + "\n"


class BaseHTTPApp:
    """HTTP scaffolding shared by the single binary and vlagent: request
    decompression, routing dispatch, response helpers."""

    def _start_http(self, listen_addr: str, port: int) -> None:
        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args_):
                pass

            def do_GET(self):
                outer.dispatch(self, b"")

            def do_HEAD(self):
                outer.dispatch(self, b"")

            def do_POST(self):
                ln = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(ln) if ln else b""
                enc = (self.headers.get("Content-Encoding") or "").lower()
                try:
                    if enc == "gzip":
                        body = gzip.decompress(body)
                    elif enc == "zstd":
                        from ..utils import zstd as _zstd
                        body = _zstd.decompress(
                            body, max_output_size=1 << 30)
                    elif enc == "deflate":
                        import zlib
                        body = zlib.decompress(body)
                    elif enc == "snappy":
                        pass  # loki protobuf handles snappy itself
                # vlint: allow-broad-except(malformed body maps to 400)
                except Exception:
                    outer.respond(self, 400, "text/plain",
                                  b"cannot decompress request body")
                    return
                outer.dispatch(self, body)

            do_PUT = do_POST
            do_DELETE = do_GET

        self.httpd = ThreadingHTTPServer((listen_addr, port), Handler)
        self.httpd.daemon_threads = True
        self.port = self.httpd.server_address[1]
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    # ---- helpers ----
    def respond(self, h, status: int, ctype: str, body: bytes,
                extra_headers: dict | None = None) -> None:
        try:
            h.send_response(status)
            h.send_header("Content-Type", ctype)
            for k, v in (extra_headers or {}).items():
                h.send_header(k, v)
            h.send_header("Content-Length", str(len(body)))
            h.end_headers()
            if h.command != "HEAD":
                h.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass

    def respond_json(self, h, obj, status: int = 200,
                     extra_headers: dict | None = None) -> None:
        self.respond(h, status, "application/json",
                     json.dumps(obj, ensure_ascii=False).encode("utf-8"),
                     extra_headers=extra_headers)

    def respond_stream(self, h, gen, ctype="application/x-ndjson",
                       headers_fn=None) -> None:
        try:
            # headers_fn: extra response headers computed AFTER the
            # first chunk exists (a partial-results marker is only
            # known once the scatter-gather has made progress); pulling
            # the first chunk before the status line keeps headers
            # truthful whenever the failure precedes the first output
            # block — and ALWAYS for stats-shaped queries, whose single
            # output chunk follows the full gather
            it = iter(gen)
            first = next(it, None)
            extra = headers_fn() if headers_fn is not None else {}
            # error paths that fire after this point (e.g. a storage
            # node shedding mid-stream) must not write a second status
            # line into the chunked body — see respond_shed
            h._vl_streamed = True
            h.send_response(200)
            h.send_header("Content-Type", ctype)
            for k, v in extra.items():
                h.send_header(k, v)
            h.send_header("Transfer-Encoding", "chunked")
            h.end_headers()

            def chunks():
                if first is not None:
                    yield first
                yield from it

            for chunk in chunks():
                if not chunk:
                    continue
                data = chunk.encode("utf-8") if isinstance(chunk, str) \
                    else chunk
                h.wfile.write(f"{len(data):x}\r\n".encode())
                h.wfile.write(data)
                h.wfile.write(b"\r\n")
                h.wfile.flush()
            h.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError):
            pass

    # ---- routing ----
    def dispatch(self, h, body: bytes) -> None:
        # per-request state: the handler object is reused across
        # keep-alive requests on one connection
        h._vl_streamed = False
        parsed = urllib.parse.urlparse(h.path)
        path = parsed.path
        args = {k: v[0] for k, v in
                urllib.parse.parse_qs(parsed.query).items()}
        ctype = (h.headers.get("Content-Type") or "").split(";")[0].strip()
        if h.command == "POST" and ctype in (
                "application/x-www-form-urlencoded",):
            for k, v in urllib.parse.parse_qs(
                    body.decode("utf-8", "replace")).items():
                args.setdefault(k, v[0])
        try:
            self.route(h, path, args, body, ctype)
        except HTTPError as e:
            self.metrics.inc("vl_http_errors_total")
            events.emit("http_error", path=path, status=e.status,
                        error=e.message)
            self.respond(h, e.status, "text/plain",
                         e.message.encode("utf-8"))
        except sched.AdmissionShed as e:
            # a storage node shed our sub-query (cluster.py surfaces
            # its 429 as AdmissionShed): propagate overload AS
            # overload, with the node's reason and Retry-After
            self.respond_shed(h, e)
        except QueryTimeoutError as e:
            self.metrics.inc("vl_http_errors_total")
            events.emit("http_error", path=path, status=503,
                        error=str(e))
            self.respond(h, 503, "text/plain", str(e).encode("utf-8"))
        except QueryMemoryError as e:
            self.metrics.inc("vl_http_errors_total")
            events.emit("http_error", path=path, status=422,
                        error=str(e))
            self.respond(h, 422, "text/plain", str(e).encode("utf-8"))
        except (BrokenPipeError, ConnectionResetError):
            pass
        # vlint: allow-broad-except(last-resort 500 handler, logged)
        except Exception as e:  # pragma: no cover
            import traceback
            traceback.print_exc()
            self.metrics.inc("vl_http_errors_total")
            events.emit("http_error", path=path, status=500,
                        error=f"{type(e).__name__}: {e}")
            self.respond(h, 500, "text/plain", str(e).encode("utf-8"))

    @staticmethod
    def _insert_proto(path: str) -> str:
        """Protocol label for one insert path (ingest counters).

        Deliberately a separate path->label table rather than
        per-branch strings: the parse-failure counter in
        handle_insert's except path needs the protocol before/without
        a branch body running.  A new insert endpoint must add its row
        here too, or its traffic lands as type="unknown"."""
        if path == "/insert/jsonline":
            return "jsonline"
        if path.endswith("/_bulk"):
            return "elasticsearch"
        if path.startswith("/insert/loki/"):
            return "loki"
        if path.startswith("/insert/opentelemetry/"):
            return "opentelemetry"
        if path.startswith("/insert/datadog/"):
            return "datadog"
        if path.startswith("/insert/journald/"):
            return "journald"
        return "unknown"

    def handle_insert(self, h, path, args, body, ctype) -> None:
        m = self.metrics
        cp = CommonParams.from_request(h.headers, args)
        lmp = LogMessageProcessor(cp, self.sink)
        proto = self._insert_proto(path)

        def count(n: int) -> None:
            # per-protocol rows + request bytes, per-tenant rows/bytes
            # (the registry side feeds vl_tenant_* on /metrics)
            m.inc(metric_name("vl_rows_ingested_total", type=proto), n)
            m.inc(metric_name("vl_ingest_bytes_total", type=proto),
                  len(body))
            activity.note_ingest(cp.tenant, n, nbytes=len(body))

        # the accept point: mint the batch_id that rides every hop
        # (sink ship, /internal/insert, spool replay) — the ingest twin
        # of activity.track.  Everything below, final flush included,
        # runs inside the batch extent so the sink's ledger rolls
        # attribute here; the extent's exit settles the batch state
        # (done / shipping / spooled).
        with ingestledger.begin_batch(cp.tenant, origin=proto):
            try:
                if path == "/insert/jsonline":
                    with ingestledger.hop("parse"):
                        n = vlinsert.handle_jsonline(cp, body, lmp)
                    count(n)
                elif path.endswith("/_bulk"):
                    with ingestledger.hop("parse"):
                        n, resp = vlinsert.handle_elasticsearch_bulk(
                            cp, body, lmp)
                    count(n)
                    lmp.flush()
                    self.respond_json(h, resp)
                    return
                elif path == "/insert/loki/api/v1/push":
                    with ingestledger.hop("parse"):
                        if ctype == "application/x-protobuf" or \
                                (body[:1] != b"{" and
                                 ctype != "application/json"):
                            n = vlinsert.handle_loki_protobuf(
                                cp, body, lmp)
                        else:
                            n = vlinsert.handle_loki_json(cp, body, lmp)
                    count(n)
                    lmp.flush()
                    self.respond(h, 204, "text/plain", b"")
                    return
                elif path == "/insert/opentelemetry/v1/logs":
                    with ingestledger.hop("parse"):
                        if ctype == "application/json":
                            n = vlinsert.handle_otlp_json(cp, body, lmp)
                        else:
                            n = vlinsert.handle_otlp_protobuf(
                                cp, body, lmp)
                    count(n)
                    lmp.flush()
                    self.respond_json(h, {"partialSuccess": {}})
                    return
                elif path in ("/insert/datadog/api/v2/logs",
                              "/insert/datadog/api/v1/input"):
                    with ingestledger.hop("parse"):
                        n = vlinsert.handle_datadog(cp, body, lmp)
                    count(n)
                    lmp.flush()
                    self.respond_json(h, {})
                    return
                elif path == "/insert/journald/upload":
                    with ingestledger.hop("parse"):
                        n = vlinsert.handle_journald(cp, body, lmp)
                    count(n)
                elif path.startswith("/insert/elasticsearch"):
                    # ES-compat discovery endpoints
                    self.respond_json(h, {"version": {"number": "8.9.0"}})
                    return
                else:
                    raise HTTPError(404, f"unknown insert path {path}")
            except vlinsert.IngestError as e:
                # parse failures land in the registry's per-protocol
                # counter (vl_ingest_parse_failures_total on /metrics)
                activity.note_parse_failure(proto)
                raise HTTPError(400, str(e))
            except netrobust.InsertRejectedError as e:
                # a storage node judged the forwarded batch malformed
                # (cluster 4xx): a client error end to end, never a
                # 500 — and never a breaker trip / re-route cascade
                # (cluster.py)
                raise HTTPError(400, str(e))
            try:
                # small batches reach the sink HERE (no size-triggered
                # mid-parse flush happened): same rejection mapping
                lmp.flush()
            except netrobust.InsertRejectedError as e:
                raise HTTPError(400, str(e))
            self.respond_json(h, {"status": "ok", "ingested": n})

    def respond_shed(self, h, e) -> None:
        """429 (or 499 for cancelled-while-queued) with Retry-After and
        the machine-readable reason body — the shed response contract
        (sched/admission.py)."""
        self.metrics.inc("vl_http_errors_total")
        if e.reason == "queue_full":
            # continuity with the pre-scheduler queue-timeout counter
            self.metrics.inc("vl_http_request_queue_timeouts_total")
        if getattr(h, "_vl_streamed", False):
            # the 200 chunked headers are already on the wire (a
            # storage node shed mid-stream): writing a 429 status line
            # now would corrupt the chunked body — cut the connection
            # so the client sees a truncated response, not garbage
            h.close_connection = True
            return
        obj = {"error": e.message, "reason": e.reason}
        limit = getattr(e, "limit", None)
        current = getattr(e, "current", None)
        if limit is not None:
            obj["limit"] = limit
        if current is not None:
            obj["current"] = current
        body = json.dumps(obj, ensure_ascii=False).encode("utf-8")
        try:
            h.send_response(e.status)
            h.send_header("Content-Type", "application/json")
            if e.retry_after is not None:
                h.send_header("Retry-After",
                              str(max(1, int(e.retry_after))))
            # adaptive-backoff hints (reference X-Concurrency style):
            # clients like vlagent scale their retry delay by how far
            # over capacity the server is, instead of sleeping the
            # fixed Retry-After (server/vlagent.py honors these)
            if limit is not None:
                h.send_header("X-VL-Concurrency-Limit", str(limit))
            if current is not None:
                h.send_header("X-VL-Concurrency-Current", str(current))
            h.send_header("Content-Length", str(len(body)))
            h.end_headers()
            if h.command != "HEAD":
                h.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            pass

    @staticmethod
    def _peer_gone(h):
        """A zero-cost probe for 'the HTTP peer hung up': readable
        socket + EOF on a peek.  Lets the admission queue drop entries
        whose client is gone before any device work starts (pipelined
        request bytes read as alive, which is correct)."""
        import select as _select
        import socket as _socket
        sock = h.connection

        def gone() -> bool:
            try:
                r, _w, _x = _select.select([sock], [], [], 0)
                if not r:
                    return False
                return sock.recv(1, _socket.MSG_PEEK) == b""
            except (OSError, ValueError):
                return True
        return gone

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()


class VLServer(BaseHTTPApp):
    """Single-binary server instance (storage + HTTP)."""

    def __init__(self, storage: Storage, listen_addr: str = "127.0.0.1",
                 port: int = 0, runner=None, max_concurrent: int = 8,
                 max_queue_duration: float = 30.0,
                 storage_nodes: list | None = None):
        self.storage = storage
        self.metrics = Metrics()
        self.runner = runner
        self.start_time = time.monotonic()
        # admission control (sched/admission.py) replaces the old raw
        # FIFO semaphores: per-tenant concurrency/bytes limits, a
        # bounded wait queue, deadline-aware shedding.  Internal
        # (cluster) sub-queries get their own pool: a node acting as
        # both frontend and storage node must not have frontend queries
        # starve the sub-queries they themselves fan out.
        self.admission = sched.AdmissionController(
            max_concurrent=max_concurrent,
            queue_timeout_s=max_queue_duration, pool="select")
        self.internal_admission = sched.AdmissionController(
            max_concurrent=max_concurrent,
            queue_timeout_s=max_queue_duration, pool="internal")
        self.max_queue_duration = max_queue_duration
        if storage_nodes:
            # cluster mode: ingest shards to the nodes, queries
            # scatter-gather over them (reference -storageNode switch —
            # app/vlstorage/main.go:87-93).  The ingest spool lives
            # next to the frontend's own data so a frontend restart
            # replays whatever a node outage left behind.
            from .cluster import NetInsertStorage, NetSelectStorage
            self.sink = NetInsertStorage(
                storage_nodes,
                spool_dir=os.path.join(storage.path,
                                       "cluster-insert-spool"))
            self.query_storage = NetSelectStorage(storage_nodes)
            # cluster-wide tenant usage rollups: the frontend-owned
            # poll loop over every node's /internal/usage
            # (obs/clusterstats.py; VL_CLUSTER_STATS_MS=0 disables)
            from ..obs import clusterstats
            self.clusterstats = clusterstats.maybe_start(storage_nodes)
        else:
            self.sink = LocalLogRowsStorage(storage)
            self.query_storage = storage
            self.clusterstats = None
        # self-telemetry journal (obs/journal.py): the event bus's
        # subscriber, writing operational events through the NORMAL
        # ingest path (self.sink — local storage, or the cluster
        # sharder on a frontend) under the reserved system tenant.
        # VL_JOURNAL=0 returns None and leaves the bus subscriber-free
        # (emit() structurally zero-cost).  Never behind admission: the
        # journal must not be shed by the overload it records.
        self.journal = journal.maybe_start(self.sink)
        # the stall line that is always on (obs/stallwatch.py): one
        # heartbeat thread and a gc hook for the whole process
        stallwatch.start()
        # standing-query registry (engine/standing/manager.py):
        # resident merged state per distinct query fingerprint,
        # re-evaluated on flush/merge bus events, deltas fanned out to
        # tail-style subscriber streams.  Evaluates against the SAME
        # storage facade interactive queries use (local storage, or the
        # scatter-gather view on a cluster frontend) and is priced
        # through the select admission pool like any tenant workload.
        from ..engine.standing import StandingRegistry
        self.standing = StandingRegistry(
            self.query_storage, runner=runner,
            admission=self.admission)
        try:
            self._start_http(listen_addr, port)
        except BaseException:
            # a failed bind must not leak the journal's bus
            # subscription + flush thread (nor the usage poll loop or
            # the standing registry's worker/bus subscription)
            self.standing.close()
            stallwatch.close()
            if self.journal is not None:
                self.journal.close()
            if self.clusterstats is not None:
                self.clusterstats.close()
            raise

    def route(self, h, path, args, body, ctype) -> None:
        m = self.metrics
        headers = h.headers
        # ---- health / misc (deliberately OUTSIDE the admission gate:
        # a server shedding 429s must still answer its liveness and
        # readiness probes, or the orchestrator kills exactly the node
        # that is correctly protecting itself) ----
        if path in ("/health", "/-/healthy", "/ping"):
            self.respond(h, 200, "text/plain", b"OK")
            return
        if path in ("/ready", "/-/ready", "/insert/ready"):
            # readiness = the storage accepts writes; a read-only
            # storage (disk limit) should be rotated out of ingest LBs
            if self.storage.is_read_only:
                self.respond(h, 503, "text/plain",
                             b"storage is read-only")
            else:
                self.respond(h, 200, "text/plain", b"OK")
            return
        if path == "/metrics":
            self.respond(h, 200, "text/plain",
                         m.render(self.storage, runner=self.runner,
                                  server=self).encode())
            return
        if path == "/":
            self.respond_json(h, {
                "app": "victorialogs-tpu",
                "uptime_seconds": round(time.monotonic() - self.start_time, 1)})
            return

        # ---- embedded web UI (reference vmui — vlselect/main.go:71-74) ----
        if path in ("/select/vmui", "/select/vmui/", "/vmui", "/vmui/"):
            from .vmui import VMUI_HTML
            self.respond(h, 200, "text/html; charset=utf-8",
                         VMUI_HTML.encode("utf-8"))
            return

        # ---- ingest observability (before the /insert/ prefix match,
        # and deliberately outside any admission gate: the spool/ledger
        # view matters most exactly when a storage node is down) ----
        if path == "/insert/status":
            payload = self._insert_status_payload()
            urls = self._cluster_urls()
            if _want_cluster(args) and urls:
                from . import cluster
                payload = cluster.federated_insert_status(urls, payload)
            self.respond_json(h, payload)
            return

        # ---- ingestion ----
        if path.startswith("/insert/"):
            self.handle_insert(h, path, args, body, ctype)
            return

        # ---- active-query registry (reference-parity introspection:
        # /select/logsql/active_queries + cancel/top — obs/activity.py).
        # Deliberately NOT behind the query semaphore: a saturated
        # server is exactly when operators need to see and kill queries.
        if path == "/select/logsql/active_queries":
            # queued-but-not-admitted queries show up here too (phase
            # "queued") — that is what makes them cancellable by qid —
            # alongside the live scheduler state (budget, in-flight
            # leases, admission pools).  ?tenant= scopes the view;
            # ?cluster=1 on a frontend federates it: every node's
            # sub-query records nested under their parent query here
            tenant = _tenant_arg(args)
            urls = self._cluster_urls()
            if _want_cluster(args) and urls:
                from . import cluster
                self.respond_json(h, cluster.federated_active_queries(
                    urls, tenant=tenant))
                return
            self.respond_json(h, {
                "status": "ok",
                "data": activity.active_snapshot(tenant=tenant),
                "scheduler": sched.snapshot()})
            return
        if path == "/select/logsql/sched_config":
            # mutating (per-tenant QoS knobs): POST only, same
            # discipline as cancel_query
            if h.command != "POST":
                raise HTTPError(405, "sched_config requires POST")
            tenant = args.get("tenant", "")
            if not tenant:
                raise HTTPError(400, "missing tenant arg")
            try:
                if "weight" in args:
                    sched.set_tenant_weight(tenant,
                                            float(args["weight"]))
                if "max_concurrent" in args:
                    self.admission.set_tenant_limit(
                        tenant, int(args["max_concurrent"]))
            except ValueError as e:
                raise HTTPError(400, f"invalid sched_config arg: {e}")
            self.respond_json(h, {
                "status": "ok", "tenant": tenant,
                "weight": sched.tenant_weight(tenant),
                "admission": self.admission.snapshot()})
            return
        if path == "/select/logsql/cancel_query":
            # destructive: POST only (a GET from a crawler/prefetcher
            # must never kill a live query)
            if h.command != "POST":
                raise HTTPError(405, "cancel_query requires POST")
            qid = args.get("qid", "")
            if not qid:
                raise HTTPError(400, "missing qid arg")
            if not activity.cancel(qid):
                raise HTTPError(404, f"no active query with qid {qid!r}")
            m.inc("vl_queries_cancelled_total")
            resp = {"status": "ok", "qid": qid}
            urls = self._cluster_urls()
            if urls:
                # cascading cancel: every node trips the sub-queries
                # registered under this query's global_qid, draining
                # their device windows NOW instead of at the next
                # disconnect-probe/frame-write detection (best-effort:
                # a dead node isn't running the sub-query anyway)
                from . import cluster
                resp["propagated"] = cluster.propagate_cancel(
                    urls, qid, activity.global_qid(qid))
            self.respond_json(h, resp)
            return
        if path == "/select/logsql/tenants":
            # cluster-wide per-tenant usage (the clusterstats rollup
            # cache — never an inline fan-out, so a hung node can't
            # hang this view); single-node servers serve their local
            # registry totals under the same shape
            tenant = _tenant_arg(args)
            cs = self.clusterstats
            if cs is not None:
                self.respond_json(h, cs.tenants_payload(tenant=tenant))
                return
            tenants = activity.usage_snapshot()["tenants"]
            if tenant is not None:
                tenants = {t: s for t, s in tenants.items()
                           if t == tenant}
            self.respond_json(h, {
                "status": "ok", "cluster": False,
                "tenants": {t: tenants[t] for t in sorted(tenants)}})
            return
        if path == "/select/logsql/top_queries":
            try:
                n = int(args.get("n") or args.get("limit") or "10")
            except ValueError:
                raise HTTPError(400, "invalid n arg")
            # validated + clamped: an unknown by= is a client error
            # (400 with the allowed set), never a silent fallthrough,
            # and n is bounded by the completed-ring capacity region
            n = max(1, min(n, 1000))
            tenant = _tenant_arg(args)
            by = args.get("by", "duration")
            urls = self._cluster_urls()
            if _want_cluster(args) and urls:
                from . import cluster
                try:
                    out = cluster.federated_top_queries(
                        urls, n, by=by, tenant=tenant)
                except ValueError as e:
                    raise HTTPError(400, str(e))
                self.respond_json(h, out)
                return
            try:
                top = activity.top_queries(n, by=by, tenant=tenant)
            except ValueError as e:
                raise HTTPError(400, str(e))
            self.respond_json(h, {"status": "ok", "top_queries": top})
            return

        if path == "/select/logsql/standing_query":
            # standing queries (engine/standing): NOT behind the
            # select gate itself — registration/introspection must work
            # on a shedding server, and the re-evaluations the registry
            # runs are individually priced through the SAME admission
            # pool (manager._reeval), so the workload is still
            # accounted per tenant
            self.handle_standing_query(h, path, args, headers)
            return

        # ---- queries (admission-controlled: per-tenant limits, a
        # bounded queue with deadline-aware shedding — sched/admission;
        # replaces the raw FIFO semaphore + -search.maxQueueDuration
        # timeout of the reference main.go:34-46) ----
        if path.startswith("/select/"):
            # register the record BEFORE admission: a queued query is
            # already visible in active_queries (phase "queued") and
            # cancellable by qid; the handler reuses this record via
            # activity.reuse_or_track, so counters stay one-per-query
            tenant = get_tenant_id(headers, args)
            # the trace's root is made HERE, where the request arrives
            # (None keeps the no-op path): admission wait, parse and
            # the handler's `query` span hang under it, so one tree on
            # one clock runs from the socket to the device
            with tracing.activate(request_trace_root(path, args)), \
                    activity.track(path, args.get("query", ""),
                                   tenant) as act:
                # resolve the partial-results mode HERE (explicit
                # ?partial arg over the VL_PARTIAL_RESULTS default) and
                # stamp it on the record: the scatter-gather reads it
                # ambiently on whatever thread runs the fan-out.  Only
                # stamped when it deviates from default-strict, so
                # ordinary query_done records stay unchanged.
                want_partial = netrobust.partial_requested(args)
                if want_partial or "partial" in args:
                    act.set("partial_ok", 1 if want_partial else -1)
                # the tree's top node carries the qid that slow-log
                # lines and active_queries records correlate by
                tracing.current_span().set("qid", act.qid)
                act.set_phase("queued")
                try:
                    with self.admission.admit(
                            tenant=act.tenant, endpoint=path,
                            deadline_s=query_timeout_s(args), act=act,
                            disconnected=self._peer_gone(h)):
                        act.set_phase("plan")
                        self.handle_select(h, path, args, headers)
                except sched.AdmissionShed as e:
                    self.respond_shed(h, e)
            return

        # ---- cluster-internal endpoints ----
        if path == "/internal/usage":
            # the cluster-stats poll target (obs/clusterstats.py):
            # per-tenant totals + live/queued depth + storage gauges.
            # Outside the admission gate — the rollup must keep seeing
            # a node that is shedding queries.
            usage = activity.usage_snapshot()
            adm_sel = self.admission.snapshot()
            adm_int = self.internal_admission.snapshot()
            s = self.storage.update_stats()
            usage.update({
                "status": "ok",
                "queued": adm_sel["queued"] + adm_int["queued"],
                "admission": {"select": adm_sel, "internal": adm_int},
                # per-tenant conservation totals: what the frontend's
                # clusterstats poll rolls up into the cluster-wide
                # zero-lost-rows view (obs/ingestledger.py)
                "ingest_ledger": ingestledger.usage_section(),
                "storage": {
                    "rows_small": s["small_rows"],
                    "rows_big": s["big_rows"],
                    "rows_inmemory": s["inmemory_rows"],
                    "pending_merges": s["pending_merges"],
                    "flush_age_seconds":
                        round(s["flush_age_seconds"], 3),
                    "is_read_only": bool(s["is_read_only"]),
                },
            })
            self.respond_json(h, usage)
            return
        if path == "/internal/select/cancel":
            # the cancel-propagation target: trip every sub-query
            # registered under the frontend query's global_qid (and/or
            # one node-local qid).  POST-only like cancel_query.
            if h.command != "POST":
                raise HTTPError(405, "cancel requires POST")
            parent_qid = args.get("parent_qid", "")
            qid = args.get("qid", "")
            if not parent_qid and not qid:
                raise HTTPError(400, "missing parent_qid or qid arg")
            n = activity.cancel_by_parent(parent_qid) \
                if parent_qid else 0
            if qid and activity.cancel(qid):
                n += 1
            if n:
                m.inc("vl_queries_cancel_propagated_total", n)
            self.respond_json(h, {"status": "ok", "cancelled": n})
            return
        if path == "/internal/insert":
            from . import cluster
            try:
                n = cluster.handle_internal_insert(self.storage, args, body)
            except ValueError as e:
                raise HTTPError(400, str(e))
            m.inc("vl_rows_ingested_total{type=\"internal\"}", n)
            self.respond_json(h, {"status": "ok", "ingested": n})
            return
        if path == "/internal/select/query":
            # same admission gate + shedding as /select/ — a storage
            # node hammered by N frontends must shed, not pile up
            # threads; the shed 429 carries the reason body the
            # frontend re-raises as AdmissionShed (cluster.py)
            from . import cluster
            tenant_lbl = (args.get("tenant") or "0:0").split(",")[0]
            try:
                with self.internal_admission.admit(
                        tenant=tenant_lbl, endpoint=path,
                        deadline_s=query_timeout_s(args),
                        disconnected=self._peer_gone(h)):
                    try:
                        gen = cluster.handle_internal_select(
                            self.storage, args, runner=self.runner)
                    except ValueError as e:
                        raise HTTPError(400, str(e))
                    self.respond_stream(h, gen,
                                        ctype="application/octet-stream")
            except sched.AdmissionShed as e:
                self.respond_shed(h, e)
            return

        # ---- profiling (reference exposes net/http/pprof; we expose the
        # Python-native equivalents — SURVEY §5 tracing/profiling) ----
        if path == "/debug/pprof/threads":
            import sys
            import traceback
            names = {t.ident: t.name for t in threading.enumerate()}
            out = []
            for tid, frame in sys._current_frames().items():
                out.append(f"--- thread {tid} ({names.get(tid, '?')}) ---")
                out.extend(s.rstrip()
                           for s in traceback.format_stack(frame))
            self.respond(h, 200, "text/plain",
                         ("\n".join(out) + "\n").encode())
            return
        if path == "/debug/pprof/profile":
            # statistical sampler over every thread's stack (cProfile only
            # instruments its own thread, which here would just sleep)
            import sys
            import traceback
            seconds = min(float(args.get("seconds", "5")), 30.0)
            me = threading.get_ident()
            samples: dict[str, int] = {}
            n_samples = 0
            deadline = time.monotonic() + seconds
            while time.monotonic() < deadline:
                for tid, frame in sys._current_frames().items():
                    if tid == me:
                        continue
                    stack = traceback.extract_stack(frame)[-6:]
                    key = " <- ".join(
                        f"{f.name}({os.path.basename(f.filename)}:"
                        f"{f.lineno})" for f in reversed(stack))
                    samples[key] = samples.get(key, 0) + 1
                n_samples += 1
                time.sleep(0.01)
            out = [f"# {n_samples} samples over {seconds}s "
                   f"(count stack)"]
            for key, cnt in sorted(samples.items(),
                                   key=lambda kv: -kv[1])[:60]:
                out.append(f"{cnt}\t{key}")
            self.respond(h, 200, "text/plain",
                         ("\n".join(out) + "\n").encode())
            return

        # ---- storage maintenance ----
        if path == "/internal/force_merge":
            self.storage.must_force_merge(args.get("partition_prefix", ""))
            self.respond(h, 200, "text/plain", b"OK")
            return
        if path == "/internal/force_flush":
            self.storage.debug_flush()
            self.respond(h, 200, "text/plain", b"OK")
            return

        self.respond(h, 404, "text/plain",
                     f"unknown path {path}".encode())

    def close(self) -> None:
        # stop standing re-evaluations FIRST: they run queries against
        # the storage being torn down and emit journal events the
        # (still-alive) journal should record
        self.standing.close()
        # stop the usage poll loop (reads only; before the sink so a
        # mid-poll node error can't race the teardown)
        if self.clusterstats is not None:
            self.clusterstats.close()
        # the stall watch writes through the event bus: before the journal
        stallwatch.close()
        # drain the journal FIRST (its flush writes through self.sink)
        if self.journal is not None:
            self.journal.close()
        # then the sink (the cluster sharder owns the spool-replay
        # thread + per-node durable queues)
        sink_close = getattr(self.sink, "close", None)
        if sink_close is not None:
            sink_close()
        super().close()

    def _cluster_urls(self) -> list | None:
        """Storage-node URLs when this server is a cluster frontend
        (the federated registry/cancel/rollup fan-out set), else
        None."""
        return getattr(self.query_storage, "urls", None)

    def _insert_status_payload(self) -> dict:
        """This node's GET /insert/status body: the ledger's in-flight/
        recent batches, conservation counters, hop latencies and
        freshness watermarks, plus the durable-spool depth/age when the
        sink is the cluster sharder."""
        payload = ingestledger.status_payload()
        payload["status"] = "ok"
        spool_status = getattr(self.sink, "spool_status", None)
        if spool_status is not None:
            payload["spool"] = spool_status()
        return payload

    @staticmethod
    def _partial_headers() -> dict:
        """X-VL-Partial marker when the ambient query record shows the
        scatter-gather degraded to surviving nodes (cluster.py stamps
        partial_failed_nodes).  Evaluated AFTER the handler produced
        its payload (JSON endpoints) or its first chunk (streams)."""
        if activity.current_activity().counter("partial_failed_nodes"):
            return {"X-VL-Partial": "true"}
        return {}

    def handle_standing_query(self, h, path, args, headers) -> None:
        """/select/logsql/standing_query — GET lists registrations
        (?cluster=1 federates the view on a frontend); POST with
        ?unregister=1&fingerprint= tears one down (federated on a
        frontend); POST with ?query= registers (or joins) the standing
        evaluation and streams result deltas until the client goes
        away.  N dashboard panels asking the same query collapse to
        ONE resident evaluation per node."""
        from ..engine.standing.manager import StandingLimit
        reg = self.standing
        urls = self._cluster_urls()
        if h.command != "POST":
            # introspection: local registrations, or the cluster-wide
            # view (every node's registry + this frontend's own)
            if _want_cluster(args) and urls:
                from . import cluster
                self.respond_json(
                    h, cluster.federated_standing_queries(urls))
                return
            self.respond_json(h, {
                "status": "ok", "cluster": False,
                "standing_queries": reg.snapshot()})
            return
        if args.get("unregister", "") not in ("", "0"):
            fp = args.get("fingerprint", "")
            if not fp:
                raise HTTPError(400, "missing fingerprint arg")
            resp = {"status": "ok", "fingerprint": fp,
                    "removed": int(reg.unregister(fp))}
            if urls:
                # best-effort cascade, retry=False like cancel
                # propagation: an unregister that already landed must
                # not double-count on a transport blip
                from . import cluster
                resp["propagated"] = \
                    cluster.federated_standing_unregister(urls, fp)
            self.respond_json(h, resp)
            return
        # POST with a query: register (or join) + subscribe; the
        # response is a tail-style chunked NDJSON stream whose first
        # line carries the fingerprint (the unregister/introspection
        # handle), followed by one payload per changed re-evaluation
        q, tenants = parse_common_args(self.query_storage, args,
                                       headers)
        try:
            fp = reg.register(q, tenants,
                              parent_qid=args.get("parent_qid", ""))
        except StandingLimit as e:
            status = 503 if "VL_STANDING=0" in str(e) else 429
            self.respond(h, status, "text/plain",
                         (str(e) + "\n").encode())
            return
        sub = reg.attach_subscriber(fp)
        gone = self._peer_gone(h)
        with activity.reuse_or_track(path, q.to_string(),
                                     tenants[0]) as act:
            def gen():
                yield (json.dumps({"standing_fingerprint": fp})
                       + "\n").encode()
                while True:
                    if gone() or act.is_cancelled():
                        return
                    try:
                        payload = sub.get(timeout=1.0)
                    except queue.Empty:
                        # keep-alive tick: respond_stream drops empty
                        # chunks, so this only drives the gone() probe
                        yield b""
                        continue
                    if payload is None:
                        return  # unregistered underneath us
                    yield payload
            try:
                self.respond_stream(h, gen())
            finally:
                reg.detach_subscriber(fp, sub)

    def handle_select(self, h, path, args, headers) -> None:
        s = self.query_storage
        m = self.metrics
        m.inc(metric_name("vl_http_requests_total", path=path))
        t0 = time.monotonic()
        if path in _QUERY_DURATION_PATHS and want_explain(args):
            # ?explain=1 / ?explain=analyze: the priced physical plan
            # (JSON document, not a row stream) — vlselect.handle_explain
            self.respond_json(h, handle_explain(s, path, args, headers,
                                                runner=self.runner))
        elif path == "/select/logsql/query":
            gen = handle_query(s, args, headers, runner=self.runner)
            self.respond_stream(h, gen,
                                headers_fn=self._partial_headers)
        elif path == "/select/logsql/hits":
            self.respond_json(h, handle_hits(s, args, headers,
                                             runner=self.runner),
                              extra_headers=self._partial_headers())
        elif path == "/select/logsql/facets":
            self.respond_json(h, handle_facets(s, args, headers,
                                               runner=self.runner),
                              extra_headers=self._partial_headers())
        elif path == "/select/logsql/field_names":
            self.respond_json(h, handle_field_names(s, args, headers))
        elif path == "/select/logsql/field_values":
            self.respond_json(h, handle_field_values(s, args, headers))
        elif path == "/select/logsql/streams":
            self.respond_json(h, handle_streams(s, args, headers))
        elif path == "/select/logsql/stream_ids":
            self.respond_json(h, handle_stream_ids(s, args, headers))
        elif path == "/select/logsql/stream_field_names":
            self.respond_json(h, handle_stream_field_names(s, args,
                                                           headers))
        elif path == "/select/logsql/stream_field_values":
            self.respond_json(h, handle_stream_field_values(s, args,
                                                            headers))
        elif path == "/select/logsql/stats_query":
            self.respond_json(h, handle_stats_query(s, args, headers,
                                                    runner=self.runner),
                              extra_headers=self._partial_headers())
        elif path == "/select/logsql/stats_query_range":
            self.respond_json(h, handle_stats_query_range(
                s, args, headers, runner=self.runner),
                extra_headers=self._partial_headers())
        elif path == "/select/logsql/tail":
            stop = {"flag": False}
            # empty keep-alive chunks are never written (a zero-length
            # chunk would TERMINATE the chunked stream), so an idle
            # tail has no write to fail on when the client goes away —
            # probe the socket instead, or the tail (and its registry
            # record) lingers until the next matching row
            gone = self._peer_gone(h)

            def stop_check():
                return stop["flag"] or gone()
            gen = handle_tail(s, args, headers, stop_check=stop_check,
                              runner=self.runner)
            try:
                self.respond_stream(h, gen)
            finally:
                stop["flag"] = True
        else:
            raise HTTPError(404, f"unknown select path {path}")
        dt = time.monotonic() - t0
        m.inc(metric_name("vl_http_request_duration_ms_total", path=path),
              int(dt * 1000))
        if path in _QUERY_DURATION_PATHS:
            # only query EXECUTION endpoints: a /tail connection's
            # lifetime or a cheap introspection call would drown the
            # distribution the histogram exists to show
            hist.QUERY_DURATION.observe(dt)
