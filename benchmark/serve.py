"""The benchmark's one child: it holds the chip(s) and is the system under test.

Builds the configuration's data from --seed (columnar, part by part, so the
part table is the config's list whatever the seed), then constructs
Storage + runner + VLServer the way victorialogs_tpu/server/__main__.py
does, and serves.  It speaks JSON lines with the parent: events on stdout
(`device`, `ready`, answers to commands), commands on stdin (`memory`,
`trace_start`, `trace_stop`, `quit`).  Only this process may trace the
device, so the profiler starts and stops here, on the parent's word.
"""

import argparse
import json
import multiprocessing
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import gen  # noqa: E402
import partbuild  # noqa: E402


def say(event: str, **kw) -> None:
    print(json.dumps({"event": event, **kw}), flush=True)


def die(msg: str) -> None:
    print(f"serve: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(3)


def build_runner(config: dict, rehearsal: bool):
    """The runner `-tpu` would give, and the device as jax reports it."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not rehearsal:
        die(f"jax selected platform={platform!r}, not a TPU")
    if len(devs) < int(config["chips"]):
        die(f"{len(devs)} device(s), the configuration needs "
            f"{config['chips']}")
    if len(devs) > 1:
        from victorialogs_tpu.parallel.distributed import MeshBatchRunner
        runner = MeshBatchRunner()
    else:
        from victorialogs_tpu.tpu.batch import BatchRunner
        runner = BatchRunner()
    if type(runner).__name__ != config["runner"]:
        die(f"runner is {type(runner).__name__}, the configuration states "
            f"{config['runner']}")
    from victorialogs_tpu.tpu import compile_cache_dir
    say("device", platform=platform, kind=devs[0].device_kind,
        count=len(devs), runner=type(runner).__name__,
        compile_cache=str(compile_cache_dir()))
    return runner


def build_data(storage, config: dict, layout: gen.Layout, seed: int,
               rows_scale: float) -> None:
    """Every part of the config's table: worker processes build the parts'
    blocks, a set of streams of a part each (partbuild.py), and each day
    partition buffers and flushes its parts in order, one file part for
    each entry."""
    sids, tags = partbuild.stream_ids(layout)
    config_json = json.dumps(config)

    def store_day(day: int, parts: list) -> None:
        pt = storage._get_partition(layout.t0_ns // gen.NS // 86400 + day)
        pt.idb.must_register_streams(list(zip(sids, tags)))
        for results in parts:
            pt.ddb.must_add_blocks(partbuild.in_build_order(
                [b for r in results for b in r.get()]))
            pt.debug_flush()

    days = sorted({p["day"] for p in layout.parts})
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(int(config["build_processes"])) as pool, \
            ThreadPoolExecutor(len(days)) as store:
        results = [(p["day"], [pool.apply_async(
            partbuild.part_blocks, ((config_json, rows_scale, seed, i, j),))
            for j in range(partbuild.jobs(layout))])
            for i, p in enumerate(layout.parts)]
        for f in [store.submit(store_day, day,
                               [r for d, r in results if d == day])
                  for day in days]:
            f.result()
        pool.close()
        pool.join()


def check_parts(storage, layout: gen.Layout) -> None:
    """The served part table has to be the config's list: a merge or a
    split flush would change the compiled programs."""
    want = sorted((p["day"], p["hi"] - p["lo"]) for p in layout.parts)
    got = []
    day0 = layout.t0_ns // gen.NS // 86400
    for day, pt in sorted(storage.partitions.items()):
        got += [(day - day0, p.num_rows) for p in pt.ddb.snapshot_parts()]
    if sorted(got) != want:
        die(f"served parts {sorted(got)} are not the configuration's {want}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--trace-dir", default="")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    config = gen.load_config(args.config)
    rows_scale = gen.REHEARSAL_SCALE if args.rehearsal else 1.0
    layout = gen.Layout(config, rows_scale)

    if not os.path.isdir(os.path.join(ROOT, "victorialogs_tpu")):
        die(f"no victorialogs_tpu package in {ROOT}")
    sys.path.insert(0, ROOT)
    runner = build_runner(config, args.rehearsal)

    from victorialogs_tpu import native
    from victorialogs_tpu.server.app import VLServer
    from victorialogs_tpu.storage.storage import Storage
    # built here once (a new checkout has no binary), before the build's
    # workers would each compile it; without it the program is another one
    if not native.available():
        die("the native core (victorialogs_tpu/native) did not build")
    srv = config["server"]
    t0 = time.monotonic()
    storage = Storage(args.data_dir,
                      retention_days=float(srv["retention_days"]),
                      flush_interval=float(srv["flush_interval_s"]),
                      future_retention_days=2.0)
    build_data(storage, config, layout, args.seed, rows_scale)
    check_parts(storage, layout)
    build_s = time.monotonic() - t0
    # the admission controller reads these two when it is made; a
    # configuration that leaves them out gets the program's defaults
    for key, env in (("tenant_max_concurrent", "VL_TENANT_MAX_CONCURRENT"),
                     ("queue_max", "VL_QUEUE_MAX")):
        if key in srv:
            os.environ[env] = str(int(srv[key]))
    server = VLServer(storage, listen_addr="127.0.0.1", port=0,
                      runner=runner,
                      max_concurrent=int(srv["max_concurrent"]),
                      max_queue_duration=float(srv["max_queue_duration_s"]))
    say("ready", port=server.port, rows=layout.rows, build_s=build_s)

    import jax
    marker_ns = 0
    traced_from = 0.0
    for line in sys.stdin:
        cmd = line.strip()
        if cmd == "memory":
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in jax.devices()]
            say("memory", memory_peak_bytes=max(peaks))
        elif cmd == "trace_start":
            # device and host TraceMe events only: the Python tracer
            # would slow every server thread and write tens of MB
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(args.trace_dir, profiler_options=opts)
            traced_from = time.monotonic()
            # one marker at a known wall-clock instant ties the trace's
            # clock to the parent's request records
            marker_ns = time.time_ns()
            with jax.profiler.TraceAnnotation("bench_marker"):
                time.sleep(0.002)
            say("trace_started", marker_unix_ns=marker_ns)
        elif cmd == "trace_stop":
            traced_s = time.monotonic() - traced_from
            jax.profiler.stop_trace()
            import xplane
            say("trace", **xplane.reduce_dir(args.trace_dir, marker_ns,
                                             traced_s))
        elif cmd == "quit":
            break
    server.close()
    storage.close()
    say("bye")
    return 0


if __name__ == "__main__":
    sys.exit(main())
