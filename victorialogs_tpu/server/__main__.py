"""victoria-logs single binary entry point.

Usage:
  python -m victorialogs_tpu.server \
      -storageDataPath /var/lib/victorialogs \
      -httpListenAddr :9428 -retentionPeriod 7d

Flag names mirror the reference binary (app/vlstorage/main.go:23-75,
app/victoria-logs/main.go); flags may also be set via environment variables
with the VL_ prefix (dots/dashes -> underscores), like the reference's
envflag support.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys

from ..logsql.duration import parse_duration
from ..storage.storage import Storage
from .app import VLServer
from .syslog import SyslogServer


# vlint: allow-env-registry(envflag mirror: names derive from the CLI flag spellings at runtime, not from fixed knobs the config registry could declare)
def _env_default(name: str, default):
    env = "VL_" + name.replace(".", "_").replace("-", "_")
    return os.environ.get(env, default)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="victoria-logs",
                                description=__doc__, prefix_chars="-")
    p.add_argument("-storageDataPath",
                   default=_env_default("storageDataPath",
                                        "victoria-logs-data"))
    p.add_argument("-httpListenAddr",
                   default=_env_default("httpListenAddr", ":9428"))
    p.add_argument("-retentionPeriod",
                   default=_env_default("retentionPeriod", "7d"))
    p.add_argument("-futureRetention",
                   default=_env_default("futureRetention", "2d"))
    p.add_argument("-inmemoryDataFlushInterval",
                   default=_env_default("inmemoryDataFlushInterval", "5s"))
    p.add_argument("-retention.maxDiskSpaceUsageBytes", type=int,
                   dest="max_disk_bytes",
                   default=int(_env_default(
                       "retention.maxDiskSpaceUsageBytes", 0)))
    p.add_argument("-syslog.listenAddr.tcp", dest="syslog_tcp", default="")
    p.add_argument("-syslog.listenAddr.udp", dest="syslog_udp", default="")
    p.add_argument("-syslog.tls.certFile", dest="syslog_tls_cert",
                   default="")
    p.add_argument("-syslog.tls.keyFile", dest="syslog_tls_key",
                   default="")
    p.add_argument("-search.maxConcurrentRequests", type=int,
                   dest="max_concurrent", default=8)
    p.add_argument("-search.maxQueueDuration", dest="max_queue_duration",
                   default="30s",
                   help="how long a query may wait for a free concurrency "
                        "slot before shedding with 429 (reference "
                        "app/vlselect/main.go:34-46)")
    p.add_argument("-tpu", action="store_true",
                   help="enable the TPU block runner for queries")
    p.add_argument("-storageNode", action="append", dest="storage_nodes",
                   default=None,
                   help="cluster mode: storage node base URL (repeatable); "
                        "this instance then shards ingest and "
                        "scatter-gathers queries over the nodes "
                        "(reference -storageNode)")
    args = p.parse_args(argv)

    retention_ns = parse_duration(args.retentionPeriod)
    if retention_ns is None:
        print(f"invalid -retentionPeriod {args.retentionPeriod!r}",
              file=sys.stderr)
        return 2
    # explicit 0 means shed immediately; only a missing/invalid value errors
    max_queue_ns = 0 if args.max_queue_duration.strip() == "0" \
        else parse_duration(args.max_queue_duration)
    if max_queue_ns is None:
        print(f"invalid -search.maxQueueDuration "
              f"{args.max_queue_duration!r}", file=sys.stderr)
        return 2
    flush_ns = parse_duration(args.inmemoryDataFlushInterval) or 5e9
    future_ns = parse_duration(args.futureRetention) or 2 * 86400e9

    runner = None
    if args.tpu:
        import jax

        from ..tpu import compile_cache_dir, cpu_pinned
        devs = jax.devices()
        platform = devs[0].platform
        # -tpu means TPU: a chip that failed to initialise (held by
        # another process, wrong libtpu) must not leave a server that
        # answers from the jax-CPU backend and says nothing.  Only an
        # explicit JAX_PLATFORMS naming cpu asks for the jax-CPU device
        # path (tests, make check).
        if platform != "tpu" and not cpu_pinned():
            print(f"-tpu: jax selected platform={platform!r}, not a TPU; "
                  f"refusing to serve from a silent fallback — set "
                  f"JAX_PLATFORMS=cpu to run the device path on jax-CPU "
                  f"on purpose", file=sys.stderr)
            return 3
        if len(devs) > 1:
            # multi-chip: shard staged rows over the mesh, psum stats
            from ..parallel.distributed import MeshBatchRunner
            runner = MeshBatchRunner()
        else:
            from ..tpu.batch import BatchRunner
            runner = BatchRunner()
        print(f"device: platform={platform} kind={devs[0].device_kind} "
              f"n={len(devs)} runner={type(runner).__name__} "
              f"compile_cache={compile_cache_dir()}", flush=True)

    storage = Storage(
        args.storageDataPath,
        retention_days=retention_ns / 86400e9,
        flush_interval=flush_ns / 1e9,
        future_retention_days=future_ns / 86400e9,
        max_disk_usage_bytes=args.max_disk_bytes,
    )

    host, _, port_s = args.httpListenAddr.rpartition(":")
    server = VLServer(storage, listen_addr=host or "0.0.0.0",
                      port=int(port_s or 9428), runner=runner,
                      max_concurrent=args.max_concurrent,
                      max_queue_duration=max_queue_ns / 1e9,
                      storage_nodes=args.storage_nodes)
    print(f"started victoria-logs server at "
          f"http://{host or '0.0.0.0'}:{server.port}/", flush=True)

    syslog_server = None
    if args.syslog_tcp or args.syslog_udp:
        def addr_port(a):
            if not a:
                return -1
            return int(a.rpartition(":")[2])
        syslog_server = SyslogServer(
            server.sink,
            tcp_port=addr_port(args.syslog_tcp),
            udp_port=addr_port(args.syslog_udp),
            tls_cert_file=args.syslog_tls_cert,
            tls_key_file=args.syslog_tls_key)
        print(f"syslog listeners: tcp={syslog_server.tcp_port} "
              f"udp={syslog_server.udp_port}", flush=True)

    stop = []

    def on_signal(_sig, _frm):
        stop.append(1)
    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    try:
        while not stop:
            signal.pause()
    except KeyboardInterrupt:
        pass
    # graceful shutdown: insert listeners first, then select, then storage
    # (reference app/victoria-logs/main.go:47-77 ordering)
    if syslog_server:
        syslog_server.close()
    server.close()
    storage.close()
    print("shut down gracefully", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
