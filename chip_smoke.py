#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path runs on the chip.

Drives the system the way a user would, once, at a real data size:

  1. starts ``python -m victorialogs_tpu.server -tpu`` as a child (this
     parent never imports jax or anything under victorialogs_tpu/tpu or
     parallel/ — one process per chip), reads the device banner;
  2. ingests --rows log rows (generated from --seed in the bench.py /
     vlogsgenerator shape) through POST /insert/jsonline from several
     client threads, then /internal/force_flush — parts stay as the LSM
     leaves them;
  3. runs the five BASELINE query shapes plus two sort|limit queries and
     one two-axis group-by over HTTP, each twice (second time with a
     same-length different literal: warm programs and staging, cold
     per-part result cache), reading /metrics around every query;
  4. SIGTERMs the server (must exit 0, "shut down gracefully");
  5. on the now-free chip: compiles the three Pallas kernels through
     Mosaic and diffs them against their XLA twins
     (tests/pallas_check.py --chip), then restarts the server with
     VL_PALLAS=1 for the queries that reach a Pallas branch;
  6. restarts on the SAME data dir WITHOUT -tpu under JAX_PLATFORMS=cpu —
     the host executor is the oracle — repeats every query and requires
     equal answers: exact for stats, equal multisets of NDJSON lines
     where LogsQL defines no order, equal sequences where the sort key
     is unique.  That is also the durability check: every acknowledged
     row is read back after a restart.

Prints two JSON lines to stdout and exits 0 only if every phase passed
on a TPU: first the report (rows, per-query seconds / device calls / host
share / equality, cost model, staged bytes per device, Pallas parity,
compile cache; ends with "claim": null), then, as the LAST line, the
verdict the driver reads, with exactly these keys:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
(the device as jax reports it in the server's banner).  Any mismatch,
child crash, platform other than "tpu", or raised phase ends the run
non-zero with nothing on stdout.

``--cpu-rehearsal`` (tier-1 uses it) runs the same script on jax-CPU by
setting JAX_PLATFORMS=cpu for the -tpu server; it skips only the steps
that need Mosaic (5).  It is a flag, not the ambient variable, because
the sandbox this repo is developed in exports JAX_PLATFORMS=cpu and a
bare ``python chip_smoke.py`` there must fail.
"""

import argparse
import http.client
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.parse
from collections import Counter

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

ROWS_DEFAULT = 8_000_000
ROWS_FLOOR = 4_000_000          # a chip run below this proves too little
TIME_LIMIT_S = 1150             # the driver allows 1200 with compilation

NS = 1_000_000_000
T0_NS = 1_753_660_800 * NS      # 2025-07-28T00:00:00Z
SPAN_NS = 36 * 3600 * NS        # bulk rows spread over 1.5 day partitions
TAIL_T0_NS = T0_NS + 48 * 3600 * NS   # the fresh tail: a third day
TAIL_PARTS = 6                  # small flushed parts a live store always
TAIL_PART_ROWS = 12_000         # has; same pad bucket, so they pack
N_STREAMS = 8
BATCH_ROWS = 50_000
CLIENT_THREADS = 6

VERBS = ["GET", "POST", "PUT", "DELETE"]
# two same-length phrases so the second run of a query swaps literals
WORDS = ["ok", "cache miss", "retry", "connection reset by peer",
         "deadline exceeded", "deadline extended", "flushed wal segment",
         "request completed"]

_M64 = (1 << 64) - 1


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


# ---------------- data, from --seed ----------------

def row_hash(idx: np.ndarray, seed: int) -> np.ndarray:
    """splitmix64 of (row index, seed): every field of row i is a slice
    of this, so any batch can be generated independently."""
    with np.errstate(over="ignore"):
        z = idx.astype(np.uint64) + np.uint64(
            (seed * 0x9E3779B97F4A7C15 + 0x632BE59BD9B4E019) & _M64)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def row_fields(lo: int, hi: int, seed: int):
    h = row_hash(np.arange(lo, hi, dtype=np.uint64), seed)
    return ((h % np.uint64(99991)).tolist(),                    # item
            ((h >> np.uint64(17)) % np.uint64(7)).tolist(),     # 0 => 500
            ((h >> np.uint64(20)) % np.uint64(907)).tolist(),   # dur
            ((h >> np.uint64(30)) % np.uint64(len(WORDS))).tolist(),
            ((h >> np.uint64(40)) % np.uint64(500000)).tolist())  # trace


def bulk_rows(rows: int) -> int:
    """Rows [0, bulk) are the bulk load; [bulk, rows) the fresh tail."""
    return rows - TAIL_PARTS * min(TAIL_PART_ROWS, rows // (4 * TAIL_PARTS))


def row_time(i: int, rows: int) -> int:
    bulk = bulk_rows(rows)
    if i < bulk:
        return T0_NS + i * (SPAN_NS // bulk)
    return TAIL_T0_NS + (i - bulk) * 1_000_000


def gen_batch(lo: int, hi: int, rows: int, seed: int) -> bytes:
    """Rows [lo, hi) as JSON lines; a batch lies wholly in the bulk or
    wholly in the tail, so its timestamps are one arithmetic series."""
    item, st, dur, word, trace = row_fields(lo, hi, seed)
    t_lo = row_time(lo, rows)
    t_step = row_time(lo + 1, rows) - t_lo if hi - lo > 1 else 0
    lines = [
        f'{{"_time":"{t_lo + (i - lo) * t_step}","app":"app{i % N_STREAMS}",'
        f'"_msg":"{VERBS[i & 3]} /api/items/{it} '
        f'status={500 if s == 0 else 200} dur={d}ms msg={WORDS[w]}",'
        f'"trace":"tok{t}","dur":"{d}","seq":"{i}"}}'
        for i, it, s, d, w, t in zip(range(lo, hi), item, st, dur, word,
                                     trace)]
    return ("\n".join(lines) + "\n").encode()


def rfc3339(ns: int) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime(ns // NS)) \
        + f".{ns % NS:09d}Z"


def build_queries(rows: int, seed: int) -> list:
    """[(name, endpoint, [query run 1, query run 2], kind)].

    kind: 'stats' = exact; 'rows' = multiset of NDJSON lines (LogsQL
    defines no order); 'sorted' = exact sequence (unique sort key).
    Aliases differ per query so no two share a result-cache key."""
    bulk = bulk_rows(rows)

    def ts(frac):
        return rfc3339(row_time(int(bulk * frac), rows))
    slice_1 = f"_time:[{ts(0)}, {ts(0.125)})"
    slice_2 = f"_time:[{ts(0.3)}, {ts(0.6)})"
    slice_3 = f"_time:[{ts(0.7)}, {ts(0.72)})"
    # two 6-digit trace tokens that exist in stream app3
    toks = []
    _it, _st, _dur, _w, trace = row_fields(0, 4096, seed)
    for i in range(3, 4096, N_STREAMS):
        if 100000 <= trace[i] and f"tok{trace[i]}" not in toks:
            toks.append(f"tok{trace[i]}")
        if len(toks) == 2:
            break
    a, b = "deadline exceeded", "deadline extended"
    return [
        # BASELINE 1: phrase in a time slice
        ("phrase_slice", "query",
         [f'{slice_1} "{p}" | stats count() c1' for p in (a, b)], "stats"),
        # BASELINE 2: phrase AND time range over many blocks
        ("phrase_and_time", "query",
         [f'{slice_2} "{p}" | stats count() c2' for p in (a, b)], "stats"),
        # BASELINE 3: regex substring scan over every row
        ("regex_full", "query",
         [f'_msg:~"dead.*{p}" | stats count() c3'
          for p in ("exceeded", "extended")], "stats"),
        # BASELINE 4: stats over every row
        ("stats_count_uniq", "stats_query",
         [f"* | stats count() {c}, count_uniq(_stream_id) {u}"
          for c, u in (("c4", "u4"), ("d4", "v4"))], "stats"),
        # BASELINE 5: stream filter + bloom token on a high-card field
        ("stream_bloom", "query",
         [f'{{app="app3"}} trace:{t} | stats count() c5' for t in toks],
         "stats"),
        # rows out, device sort-topk on a unique numeric key (the filter
        # is selective because the ORACLE materializes every match)
        ("sort_topk", "query",
         [f'"{p}" "status=500" DELETE | sort by (seq desc) limit 20 '
          f'| fields _time, app, seq, dur, trace' for p in (a, b)],
         "sorted"),
        # rows out, two-key sort (unique: _time is) over a device filter;
        # time-sliced because a rows answer costs one whole-block value
        # decode per block with a match, on the device path and the
        # oracle alike
        ("sort_two_keys", "query",
         [f'{slice_3} "{p}" "status=500" '
          f'| sort by (dur desc, _time) limit 20 '
          f'| fields _time, _msg, dur' for p in (a, b)], "sorted"),
        # two-axis group-by: time buckets x numeric buckets
        ("stats_by_time_dur", "query",
         [f'"{p}" | stats by (_time:5m, dur:100) count() c8'
          for p in (a, b)], "rows"),
    ]


FULL_SCAN = {"regex_full", "stats_count_uniq", "sort_topk",
             "stats_by_time_dur"}
GATE_CHECKED = {"regex_full", "stats_count_uniq", "stats_by_time_dur"}
PALLAS_QUERIES = {"stream_bloom", "stats_by_time_dur", "stats_count_uniq"}


# ---------------- server child ----------------

class Server:
    """One ``python -m victorialogs_tpu.server`` child."""

    def __init__(self, data_dir: str, tpu: bool, env_extra: dict,
                 log_path: str):
        env = dict(os.environ)
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        env.update(env_extra)
        args = [sys.executable, "-m", "victorialogs_tpu.server",
                "-storageDataPath", data_dir,
                "-httpListenAddr", "127.0.0.1:0",
                "-retentionPeriod", "100y"]
        if tpu:
            args.append("-tpu")
        self.log_path = log_path
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(args, stdout=subprocess.PIPE,
                                     stderr=self._log, env=env, cwd=HERE)
        self.lines: list = []
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        self.port = 0
        self.device = None

    def _read(self):
        for raw in self.proc.stdout:
            self.lines.append(raw.decode("utf-8", "replace").rstrip("\n"))

    def log_tail(self) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return f.read()[-6000:].decode("utf-8", "replace")

    def wait_started(self, timeout: float = 300.0) -> None:
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            for ln in list(self.lines):
                m = re.match(r"device: platform=(\S+) kind=(.*) n=(\d+) "
                             r"runner=(\S+) compile_cache=(.*)$", ln)
                if m:
                    self.device = {"platform": m.group(1),
                                   "kind": m.group(2),
                                   "count": int(m.group(3)),
                                   "runner": m.group(4),
                                   "compile_cache": m.group(5)}
                m = re.match(r"started victoria-logs server at "
                             r"http://127\.0\.0\.1:(\d+)/", ln)
                if m:
                    self.port = int(m.group(1))
                    return
            if self.proc.poll() is not None:
                fail(f"server exited rc={self.proc.returncode} before "
                     f"serving:\n{self.log_tail()}")
            time.sleep(0.1)
        fail(f"server did not start within {timeout}s:\n{self.log_tail()}")

    def request(self, method: str, path: str, body: bytes | None = None,
                timeout: float = 400.0) -> bytes:
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            conn.request(method, path, body=body)
            resp = conn.getresponse()
            data = resp.read()
        finally:
            conn.close()
        if resp.status >= 300:
            fail(f"{method} {path[:200]} -> {resp.status}: "
                 f"{data[:500]!r}\n{self.log_tail()}")
        return data

    def metrics(self) -> dict:
        out = {}
        for ln in self.request("GET", "/metrics").decode().splitlines():
            if ln and not ln.startswith("#"):
                name, _, val = ln.rpartition(" ")
                out[name] = float(val)
        return out

    def stop_gracefully(self) -> None:
        self.proc.send_signal(signal.SIGTERM)
        rc = self.proc.wait(timeout=300)
        self._reader.join(timeout=10)
        self._log.close()
        if rc != 0:
            fail(f"server exited rc={rc} on SIGTERM:\n{self.log_tail()}")
        if "shut down gracefully" not in self.lines:
            fail("server exited 0 without 'shut down gracefully'")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)


# ---------------- phases ----------------

def ingest(srv: Server, rows: int, seed: int) -> float:
    """Bulk load from CLIENT_THREADS clients + force_flush, then the
    fresh tail: TAIL_PARTS small batches, each flushed to its own part
    (parts stay as the LSM leaves them — no force_merge anywhere)."""
    bulk = bulk_rows(rows)
    batches = [(lo, min(lo + BATCH_ROWS, bulk))
               for lo in range(0, bulk, BATCH_ROWS)]
    nxt = iter(batches)
    mu = threading.Lock()
    errors: list = []

    def client():
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=300)
        while not errors:
            with mu:
                span = next(nxt, None)
            if span is None:
                break
            body = gen_batch(span[0], span[1], rows, seed)
            conn.request("POST", "/insert/jsonline?_stream_fields=app",
                         body=body)
            resp = conn.getresponse()
            data = resp.read()
            if resp.status >= 300:
                errors.append(f"insert {span} -> {resp.status}: "
                              f"{data[:300]!r}")
        conn.close()

    def guarded():
        try:
            client()
        except Exception as e:          # surfaced below: fails the run
            errors.append(f"{type(e).__name__}: {e}")

    t0 = time.monotonic()
    threads = [threading.Thread(target=guarded)
               for _ in range(CLIENT_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        fail(f"ingest: {errors[0]}\n{srv.log_tail()}")
    srv.request("GET", "/internal/force_flush")
    per = (rows - bulk) // TAIL_PARTS
    for k in range(TAIL_PARTS):
        lo = bulk + k * per
        srv.request("POST", "/insert/jsonline?_stream_fields=app",
                    body=gen_batch(lo, lo + per, rows, seed))
        srv.request("GET", "/internal/force_flush")
    return time.monotonic() - t0


def run_query(srv: Server, endpoint: str, query: str):
    """Returns a comparable answer: list of NDJSON lines, or for
    stats_query the sorted (metric, value) pairs."""
    qs = {"query": query, "timeout": "300s"}
    if endpoint == "stats_query":
        qs["time"] = rfc3339(TAIL_T0_NS + 24 * 3600 * NS)
    data = srv.request(
        "GET", f"/select/logsql/{endpoint}?" + urllib.parse.urlencode(qs))
    if endpoint == "stats_query":
        res = json.loads(data)
        if res.get("status") != "success" or res.get("partial"):
            fail(f"stats_query {query!r}: {res}")
        return sorted(json.dumps(r, sort_keys=True)
                      for r in res["data"]["result"])
    return [ln for ln in data.decode().splitlines() if ln]


def run_queries(srv: Server, queries: list, only=None) -> dict:
    """name -> {"answers": [run1, run2], "seconds": [...], deltas...}."""
    watch = ("vl_tpu_device_calls", "vl_tpu_packed_dispatches",
             "vl_tpu_gated_host_parts",
             "vl_tpu_cpu_fallbacks", "vl_tpu_scanned_parts",
             "vl_tpu_jit_compiles_total", "vl_tpu_compile_cache_hits_total")
    out = {}
    for name, endpoint, variants, _kind in queries:
        if only is not None and name not in only:
            continue
        rec = {"answers": [], "seconds": [], "compile_s": []}
        for k in watch:
            rec[k] = []
        for q in variants:
            m0 = srv.metrics()
            t0 = time.monotonic()
            rec["answers"].append(run_query(srv, endpoint, q))
            rec["seconds"].append(round(time.monotonic() - t0, 3))
            m1 = srv.metrics()
            for k in watch:
                rec[k].append(int(m1.get(k, 0) - m0.get(k, 0)))
            rec["compile_s"].append(round(
                m1.get("vl_tpu_jit_compile_seconds_total", 0)
                - m0.get("vl_tpu_jit_compile_seconds_total", 0), 2))
        out[name] = rec
    return out


def answers_equal(kind: str, got: list, want: list) -> bool:
    if kind == "rows":
        return Counter(got) == Counter(want)
    return got == want


def compare(queries: list, got: dict, want: dict, what: str) -> dict:
    eq = {}
    for name, _endpoint, variants, kind in queries:
        if name not in got:
            continue
        for i, q in enumerate(variants):
            g, w = got[name]["answers"][i], want[name]["answers"][i]
            if not answers_equal(kind, g, w):
                fail(f"{what}: {name} run {i + 1} differs from the host "
                     f"executor\nquery: {q}\n"
                     f"got  ({len(g)} lines): {g[:5]}\n"
                     f"want ({len(w)} lines): {w[:5]}")
            if not w:
                fail(f"{name} run {i + 1}: empty answer proves nothing "
                     f"(query: {q})")
        eq[name] = True
    return eq


def count_rows(answers: dict) -> int:
    """Rows the store read back, off the `* | stats count()` answer."""
    for line in answers["stats_count_uniq"]["answers"][0]:
        r = json.loads(line)
        if r["metric"]["__name__"] == "c4":
            return int(r["value"][1])
    fail("no c4 in the stats_count_uniq answer")


def cache_entries(path: str) -> int:
    if not path or path == "None" or not os.path.isdir(path):
        return 0
    return sum(len(files) for _d, _s, files in os.walk(path))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=ROWS_DEFAULT)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the -tpu server on jax-CPU on purpose "
                         "(tier-1); never a chip result")
    ap.add_argument("--data-dir",
                    default=os.path.join(HERE, ".chip_smoke_data"))
    args = ap.parse_args()
    t_start = time.monotonic()

    def check_time(phase: str) -> None:
        if time.monotonic() - t_start > TIME_LIMIT_S:
            fail(f"over {TIME_LIMIT_S}s after {phase}")

    # built from what git would commit: the native host core compiles
    # here, on first use, or the run is not the system users get
    if not os.path.isdir(os.path.join(HERE, "victorialogs_tpu")):
        fail(f"no victorialogs_tpu package beside {__file__}: the smoke "
             f"drives the checkout it sits in, nothing else")
    sys.path.insert(0, HERE)
    from victorialogs_tpu import native
    if not native.available():
        fail("native host core unavailable (g++ build failed?)")

    rehearsal_env = {"JAX_PLATFORMS": "cpu"} if args.cpu_rehearsal else {}
    if not args.cpu_rehearsal and args.rows < ROWS_FLOOR:
        fail(f"--rows {args.rows} is below the {ROWS_FLOOR} floor of a "
             f"chip run")
    shutil.rmtree(args.data_dir, ignore_errors=True)
    os.makedirs(args.data_dir)
    store = os.path.join(args.data_dir, "store")
    queries = build_queries(args.rows, args.seed)
    servers: list = []

    def start(tpu: bool, env_extra: dict, tag: str) -> Server:
        srv = Server(store, tpu, env_extra,
                     os.path.join(args.data_dir, f"server_{tag}.log"))
        servers.append(srv)
        srv.wait_started()
        return srv

    try:
        # ---- the chip: ingest + queries through the served path ----
        srv = start(True, rehearsal_env, "tpu")
        dev = srv.device
        if dev is None:
            fail("server printed no device banner")
        if dev["platform"] != "tpu" and not args.cpu_rehearsal:
            fail(f"platform is {dev['platform']!r}, not tpu")
        want_runner = "BatchRunner" if dev["count"] == 1 \
            else "MeshBatchRunner"
        if dev["runner"] != want_runner:
            fail(f"{dev['count']} device(s) but runner {dev['runner']}")
        cache0 = cache_entries(dev["compile_cache"])
        ingest_s = ingest(srv, args.rows, args.seed)
        check_time("ingest")
        dev_ans = run_queries(srv, queries)
        check_time("device queries")
        rows_back = count_rows(dev_ans)
        if rows_back != args.rows:
            fail(f"ingested {args.rows} rows, read back {rows_back}")
        m = srv.metrics()
        staged = {k[len('vl_tpu_staged_device_bytes{device="'):-2]: int(v)
                  for k, v in m.items()
                  if k.startswith("vl_tpu_staged_device_bytes{")}
        if m.get("vl_tpu_device_calls", 0) <= 0:
            fail("vl_tpu_device_calls is 0: nothing ran on the device")
        if not args.cpu_rehearsal and \
                m.get("vl_tpu_packed_dispatches", 0) <= 0:
            fail("vl_tpu_packed_dispatches is 0: the fresh tail's small "
                 "parts never packed into a super-dispatch")
        if m.get("vl_tpu_replicated_row_puts", 0) > 0:
            fail(f"{int(m['vl_tpu_replicated_row_puts'])} row arrays "
                 f"were replicated instead of striped over the mesh")
        if len(staged) != dev["count"] or min(staged.values()) <= 0:
            fail(f"staged bytes not on every device: {staged} "
                 f"(devices: {dev['count']})")
        per_query = {}
        for name, _e, _v, _k in queries:
            r = dev_ans[name]
            scanned = sum(r["vl_tpu_scanned_parts"])
            hosted = sum(r["vl_tpu_gated_host_parts"]) \
                + sum(r["vl_tpu_cpu_fallbacks"])
            share = round(hosted / scanned, 4) if scanned else 0.0
            # claims about the chip run only: at rehearsal sizes the cost
            # gate rightly keeps small parts on the host
            chip = not args.cpu_rehearsal
            if chip and name in FULL_SCAN \
                    and min(r["vl_tpu_device_calls"]) <= 0:
                fail(f"{name}: device_calls did not grow on a full "
                     f"scan: {r['vl_tpu_device_calls']}")
            if chip and name in GATE_CHECKED and share >= 0.5:
                fail(f"{name}: {hosted} of {scanned} scanned parts went "
                     f"to the host executor (gate/fallback share "
                     f"{share})")
            per_query[name] = {
                "first_s": r["seconds"][0], "second_s": r["seconds"][1],
                "compiles": r["vl_tpu_jit_compiles_total"],
                "compile_s": r["compile_s"],
                "compile_cache_hits": r["vl_tpu_compile_cache_hits_total"],
                "device_calls": r["vl_tpu_device_calls"],
                "packed_dispatches": r["vl_tpu_packed_dispatches"],
                "scanned_parts": r["vl_tpu_scanned_parts"],
                "host_gated_parts": r["vl_tpu_gated_host_parts"],
                "cpu_fallbacks": r["vl_tpu_cpu_fallbacks"],
                "host_share": share}
        compiles = {
            "jit_compiles": int(m.get("vl_tpu_jit_compiles_total", 0)),
            "jit_compile_s": round(
                m.get("vl_tpu_jit_compile_seconds_total", 0.0), 1),
            "cache_hits": int(m.get("vl_tpu_compile_cache_hits_total", 0)),
            "cache_misses": int(
                m.get("vl_tpu_compile_cache_misses_total", 0))}
        cost = {"rtt_s": m.get("vl_tpu_cost_rtt_seconds", 0.0),
                "unit_rtt_s": m.get("vl_tpu_cost_unit_rtt_seconds", 0.0),
                "dev_bytes_per_s": m.get("vl_tpu_cost_dev_bytes_per_s",
                                         0.0)}
        parts = {t: int(m.get(f'vl_storage_parts{{type="{t}"}}', 0))
                 for t in ("inmemory", "small", "big")}
        srv.stop_gracefully()
        cache1 = cache_entries(dev["compile_cache"])

        # ---- Pallas: Mosaic parity, then the VL_PALLAS=1 branches ----
        pallas = "skipped (cpu rehearsal: interpret-mode parity is " \
                 "tests/test_pallas.py)"
        if not args.cpu_rehearsal:
            res = subprocess.run(
                [sys.executable, os.path.join(HERE, "tests",
                                              "pallas_check.py"),
                 "--chip"], capture_output=True, timeout=600, cwd=HERE)
            if res.returncode != 0:
                fail("pallas_check.py --chip failed:\n"
                     + res.stderr.decode("utf-8", "replace")[-6000:])
            pallas = json.loads(res.stdout.decode().splitlines()[-1])
            if pallas.get("platform") != "tpu":
                fail(f"pallas parity ran on {pallas.get('platform')}")
            check_time("pallas parity")
            psrv = start(True, {"VL_PALLAS": "1"}, "pallas")
            pallas_ans = run_queries(psrv, queries, only=PALLAS_QUERIES)
            psrv.stop_gracefully()
            check_time("VL_PALLAS=1 queries")

        # ---- the oracle: host executor on the same data dir ----
        hsrv = start(False, {"JAX_PLATFORMS": "cpu"}, "host")
        if hsrv.device is not None:
            fail("the oracle server built a device runner")
        host_ans = run_queries(hsrv, queries)
        hsrv.stop_gracefully()
        check_time("host queries")
        equal = compare(queries, dev_ans, host_ans, "device")
        if not args.cpu_rehearsal:
            compare(queries, pallas_ans, host_ans, "VL_PALLAS=1")
        for name in per_query:
            per_query[name]["equal"] = equal[name]
            per_query[name]["host_s"] = host_ans[name]["seconds"]
    finally:
        for s in servers:
            s.kill()
    shutil.rmtree(args.data_dir, ignore_errors=True)

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"]}
    report = {
        "ok": True,
        "device": device,
        "cpu_rehearsal": args.cpu_rehearsal,
        "runner": dev["runner"],
        "rows_ingested": args.rows,
        "rows_read_back": rows_back,
        "rows_cut_from_default": args.rows < ROWS_DEFAULT,
        "seed": args.seed,
        "parts": parts,
        "ingest_s": round(ingest_s, 2),
        "queries": per_query,
        "cost_model": cost,
        "staged_device_bytes": staged,
        "native_available": True,
        "pallas": pallas,
        "compile_cache": {"dir": dev["compile_cache"],
                          "entries_before": cache0,
                          "entries_after": cache1, **compiles},
        "total_s": round(time.monotonic() - t_start, 1),
        "claim": None,
    }
    print(json.dumps(report))
    # the driver's contract: the last line holds these keys and no other
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
