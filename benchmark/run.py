#!/usr/bin/env python3
"""One cell of the benchmark: `--workload <name> --seed <n> --seconds <s>
--trace <0|1>`, on the served path of a `-tpu` victoria-logs server.

This parent is the load generator, a real client: `http.client` threads in
a process of their own, off the server's interpreter lock.  It never
imports jax or anything under victorialogs_tpu; benchmark/serve.py, its
one child, holds the chip(s).  Everything that belongs to one
configuration, one traffic mix or one metric is a file found by the name
in BENCHMARK.json: configs/<config>.json and the row schema it names,
schemas/<schema>.py (the contract: gen.py's docstring),
traffic/<traffic>.json, metrics/<metric>.json, readers/<source kind>.py.

A run: start the child (set-up: data from --seed, server, warm-up of the
window's own request shapes), offer the window's load, read counters and
memory, stop the child, then check a seeded sample of the answers against
the plain reference (reference.py) and print one JSON line.
"""

import argparse
import http.client
import importlib
import json
import os
import queue
import random
import shutil
import subprocess
import sys
import threading
import time
import urllib.parse

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import bytes as yardstick_bytes  # noqa: E402  (benchmark/bytes.py)
import gen  # noqa: E402
import reference  # noqa: E402
import traffic as traffic_gen  # noqa: E402

T_START = time.monotonic()
ANSWER_WAIT_S = 60.0        # past the window's close, for late answers


def fail(msg: str) -> None:
    print(f"benchmark: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


# ---------------- the child ----------------

class Child:
    def __init__(self, args, cell: dict, config_path: str, config: dict,
                 serve_script: str):
        self.data_dir = os.path.join(ROOT, ".bench_data", cell["name"])
        self.trace_dir = self.data_dir + ".trace"
        for d in (self.data_dir, self.trace_dir):
            shutil.rmtree(d, ignore_errors=True)
        os.makedirs(self.data_dir)
        env = dict(os.environ)
        env.pop("BENCH_RUN", None)
        cmd = [sys.executable, serve_script,
               "--config", config_path, "--seed", str(args.seed),
               "--data-dir", self.data_dir, "--trace-dir", self.trace_dir]
        if args.rehearsal:
            cmd.append("--rehearsal")
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                f"{config['chips']}")
        self.proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, env=env,
                                     cwd=ROOT, text=True)
        self.events = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        for line in self.proc.stdout:
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            if isinstance(ev, dict) and "event" in ev:
                self.events.put(ev)
        self.events.put({"event": "eof"})

    def expect(self, event: str, timeout: float) -> dict:
        end = time.monotonic() + timeout
        while True:
            try:
                ev = self.events.get(timeout=max(0.1, end - time.monotonic()))
            except queue.Empty:
                fail(f"child said no {event!r} within {timeout}s")
            if ev["event"] == event:
                return ev
            if ev["event"] == "eof":
                fail(f"child ended (rc={self.proc.wait()}) before {event!r}")

    def command(self, cmd: str, event: str, timeout: float = 120.0) -> dict:
        self.proc.stdin.write(cmd + "\n")
        self.proc.stdin.flush()
        return self.expect(event, timeout)

    def stop(self) -> None:
        """Stops the child and waits until it has ended."""
        if self.proc.poll() is None:
            try:
                self.proc.stdin.write("quit\n")
                self.proc.stdin.flush()
                self.proc.wait(timeout=60)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait(timeout=30)
        for d in (self.data_dir, self.trace_dir):
            shutil.rmtree(d, ignore_errors=True)


# ---------------- the client ----------------

class Client:
    """Worker threads, each with one persistent connection, opened here
    one after another: a connection first opened inside the window, at a
    moment when the server is slow to accept, overflows its accept queue
    of 5 and is reset.  A request handed over at its due instant is sent
    by the first free worker."""

    def __init__(self, port: int, workers: int, trace: bool):
        self.port, self.trace = port, trace
        self.todo = queue.Queue()
        self.records = []
        self._mu = threading.Lock()
        self.threads = []
        for _ in range(workers):
            conn = self.connect()
            conn.connect()
            self.threads.append(threading.Thread(target=self._work,
                                                 args=(conn,), daemon=True))
            self.threads[-1].start()

    def connect(self):
        return http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=ANSWER_WAIT_S + 60)

    def _work(self, conn):
        while True:
            item = self.todo.get()
            if item is None:
                return
            req, due = item
            if conn is None:
                conn = self.connect()
            rec = self.send(conn, req, due)
            if "error" in rec:
                # only a broken connection is dropped: a 429 is a whole
                # answer, and reconnecting after each one in a burst of
                # sheds overflows the server's accept queue (resets)
                conn.close()
                conn = None
            with self._mu:
                self.records.append(rec)
            self.todo.task_done()

    def send(self, conn, req: dict, due: float) -> dict:
        form = {"query": req["query"], "timeout": "300s"}
        if req["endpoint"] == "stats_query":
            form["time"] = req["time"]
        if self.trace:
            form["trace"] = "1"
        body = urllib.parse.urlencode(form)
        rec = {"req": req, "due": due, "sent": time.monotonic(),
               "status": 0, "body": b""}
        try:
            conn.request("POST", f"/select/logsql/{req['endpoint']}", body,
                         {"Content-Type": "application/x-www-form-urlencoded"})
            resp = conn.getresponse()
            rec["body"] = resp.read()
            rec["status"] = resp.status
        except (OSError, http.client.HTTPException) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["done"] = time.monotonic()
        return rec

    def drain(self, timeout: float) -> int:
        """Waits for every handed-over request; returns how many never
        got an answer in time."""
        end = time.monotonic() + timeout
        while self.todo.unfinished_tasks and time.monotonic() < end:
            time.sleep(0.01)
        return self.todo.unfinished_tasks

    def close(self):
        for _ in self.threads:
            self.todo.put(None)


def open_loop(client: Client, requests: list) -> float:
    """Offers each (due_s, request) at its instant, whatever the answers
    do; returns the window's start on the monotonic clock."""
    start = time.monotonic() + 0.05
    for due_s, req in requests:
        due = start + due_s
        while True:
            left = due - time.monotonic()
            if left <= 0:
                break
            time.sleep(left if left > 0.002 else 0.0002)
        client.todo.put((req, due))
    return start


def warm_up(port: int, requests: list, callers: int) -> None:
    """Every request of the window once, under other aliases (the result
    cache, the program's one cache keyed on the query, keys on the alias
    and so answers none of the window's), back to back from a few
    callers: every program the window will run is compiled or loaded
    here, inside set-up.  The whole window and not each class once: a
    cell's 3 to 5 classes run 10 to 42 programs, and which of them a
    request needs depends on what the program's prune and bloom leave of
    the parts for its window and literal.  A replay covers them whatever
    the program's rules are; a shorter list would have to model them
    here and would go stale with them.  It costs the window's own work
    at the server's full rate, four fifths of the window's length."""
    client = Client(port, callers, False)
    for req in requests:
        client.todo.put((req, time.monotonic()))
    left = client.drain(1200)
    client.close()
    bad = [r for r in client.records if r["status"] != 200]
    if left or bad:
        why = bad[0].get("error", bad[0]["body"][:300]) if bad else ""
        fail(f"warm-up: {left} unanswered, {len(bad)} failed {why}")


# ---------------- metrics ----------------

def metrics_text(port: int) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode()
    conn.close()
    out = {}
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            name, _, val = ln.rpartition(" ")
            out[name] = float(val)
    return out


def read_metric(name: str, ctx: dict):
    """metrics/<name>.json -> readers/<source kind>.py -> a number, or
    None when there is nothing to read."""
    spec = gen.load_json(os.path.join(HERE, "metrics", name + ".json"))
    reader = importlib.import_module("readers." + spec["source_kind"])
    value = reader.read(spec, ctx)
    return None if value is None else {"value": float(value),
                                       "unit": spec["unit"]}


def cell_metrics(bench: dict, cell: dict, kind: str) -> list:
    return [m["name"] for m in bench[kind]
            if "workloads" not in m or cell["name"] in m["workloads"]]


# ---------------- correctness ----------------

def pick_sample(done: list, seed: int, sample: int) -> dict:
    """{k: record}: about `sample` of the completed requests, drawn from
    the seed class by class in the classes' own shares, and the slowest
    request of the window."""
    by_cls = {}
    for r in sorted(done, key=lambda r: r["req"]["k"]):
        by_cls.setdefault(r["req"]["cls"], []).append(r)
    rng = random.Random(seed)
    picked = {}
    for _cls, recs in sorted(by_cls.items()):
        n = max(1, round(sample * len(recs) / len(done)))
        for r in rng.sample(recs, min(n, len(recs))):
            picked[r["req"]["k"]] = r
    if done:
        slowest = max(done, key=lambda r: r["done"] - r["due"])
        picked[slowest["req"]["k"]] = slowest
    return picked


def check_answers(records: list, unanswered: int, traffic: dict, layout,
                  seed: int) -> dict:
    """A seeded sample of the window's answers against the plain
    reference: every number compared beside its limit."""
    picked = pick_sample([r for r in records if r["status"] == 200], seed,
                         int(traffic["check_sample"]))
    ref = reference.Reference(layout, seed)
    wrong = []
    for k, r in sorted(picked.items()):
        req = r["req"]
        want = ref.answer(req, traffic["classes"][req["cls"]]["reference"])
        try:
            got = reference.normal_form(req["endpoint"], r["body"])
        except ValueError as e:
            got = f"unreadable: {e}"
        if got != want:
            wrong.append({"k": k, "cls": req["cls"], "query": req["query"],
                          "got": str(got)[:300], "want": str(want)[:300]})
    # a shed request (429) is a failure of load, counted in `failed`; any
    # other answer that is not a 200 says the wrong thing
    errors = sum(1 for r in records if r["status"] not in (200, 429))
    checked = {"compared": {"value": len(picked), "at_least": 1},
               "mismatched": {"value": len(wrong), "limit": 0},
               "error_answers": {"value": errors, "limit": 0},
               "unanswered": {"value": unanswered, "limit": 0}}
    ok = len(picked) >= 1 and not wrong and not errors and unanswered == 0
    return {"correct": ok, "checked": checked, "wrong": wrong}


# ---------------- one run ----------------

def load_cell(workload: str, rehearsal: bool = False,
              root: str = ROOT) -> dict:
    """What BENCHMARK.json (in `root`) and the data files say of one
    workload.  Whatever a file names and no code has, the schema module
    first, fails here: before a child spends a set-up on it."""
    bench = gen.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        fail(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config_path = os.path.join(root, conf["file"])
    config = gen.load_config(config_path)
    traffic_path = os.path.join(config["_dir"], "traffic",
                                cell["traffic"] + ".json")
    traffic = gen.load_json(traffic_path)
    if traffic["loop"] != "open":
        fail(f"traffic {traffic['name']!r}: no loop kind {traffic['loop']!r}")
    try:
        layout = gen.Layout(config,
                            gen.REHEARSAL_SCALE if rehearsal else 1.0)
        traffic_gen.check(traffic, layout.schema)
        for cls in traffic["rotation"]:
            reference.check(traffic["classes"][cls]["reference"],
                            layout.schema)
    except ValueError as e:         # gen.SchemaError among them
        fail(str(e))
    return {"bench": bench, "cell": cell, "config_path": config_path,
            "config": config, "traffic": traffic,
            "traffic_path": traffic_path, "layout": layout,
            "peaks": gen.load_json(os.path.join(HERE, "peaks.json")),
            "stats_time": gen.rfc3339(layout.span("all")[1]
                                      + 86400 * gen.NS)}


def make_requests(c: dict, seed: int, sched: list, prefix: str = "q") -> list:
    """[(due_s, request)] of a schedule, literals from the seed."""
    out = []
    for k, (due, cls) in enumerate(sched):
        req = traffic_gen.make_request(c["traffic"], c["layout"], seed, k,
                                       cls, prefix)
        req["time"] = c["stats_time"]
        out.append((due, req))
    return out


def set_up(args, c: dict, serve_script: str, sched: list):
    """Starts the child, checks its device against the cell, waits for the
    data and warms up `sched`; returns (child, device, port).  The caller
    stops the child."""
    child = Child(args, c["cell"], c["config_path"], c["config"],
                  serve_script)
    try:
        device = child.expect("device", 300)
        if not args.rehearsal:
            if device["platform"] != "tpu":
                fail(f"platform is {device['platform']!r}, not tpu")
            if device["kind"] not in c["peaks"]:
                fail(f"no peaks for device kind {device['kind']!r} in "
                     f"benchmark/peaks.json")
        if device["count"] < c["cell"]["chips"]:
            fail(f"{device['count']} chip(s), the cell needs "
                 f"{c['cell']['chips']}")
        ready = child.expect("ready", 900)
        port = ready["port"]
        t_ready = time.monotonic()
        before = metrics_text(port)
        warm_up(port, [r for _due, r in make_requests(c, args.seed, sched,
                                                      "w")],
                int(c["traffic"]["warmup_callers"]))
        after = metrics_text(port)
        # a compile request the persistent cache answers counts as one
        loaded = after.get("vl_tpu_jit_compiles_total", 0) \
            - before.get("vl_tpu_jit_compiles_total", 0)
        print(f"setup: child ready after {t_ready - T_START:.1f} s (data "
              f"{ready['build_s']:.1f} s), warm-up of {len(sched)} requests "
              f"{time.monotonic() - t_ready:.1f} s, {loaded:.0f} programs "
              f"compiled or loaded in it", file=sys.stderr)
    except BaseException:
        child.stop()
        raise
    return child, device, port


def main(argv=None, serve_script=os.path.join(HERE, "serve.py"),
         root: str = ROOT) -> int:
    """One run.  `serve_script` and `root` are what tests replace, to
    drive a whole run over a stand-in for the system under test and over a
    BENCHMARK.json, configuration, schema and traffic of their own."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="jax-CPU, a hundredth of the rows: proves the "
                         "control flow, prints no device metric, never a "
                         "chip result")
    args = ap.parse_args(argv)
    c = load_cell(args.workload, args.rehearsal, root)
    bench, cell, traffic = c["bench"], c["cell"], c["traffic"]
    sched = traffic_gen.schedule(traffic, args.seconds)

    child, device, port = set_up(args, c, serve_script, sched)
    try:
        client = Client(port, int(traffic["client_threads"]),
                        bool(args.trace))
        prom0 = metrics_text(port)
        trace_ev = None
        if args.trace:
            child.command("trace_start", "trace_started")
        setup_s = time.monotonic() - T_START
        start = open_loop(client, make_requests(c, args.seed, sched))
        unanswered = client.drain(ANSWER_WAIT_S + args.seconds
                                  - (time.monotonic() - start))
        records = list(client.records)
        window_s = max([args.seconds] + [r["done"] - start for r in records])
        if args.trace:
            trace_ev = child.command("trace_stop", "trace", 300)
        prom1 = metrics_text(port)
        memory = child.command("memory", "memory")
        client.close()
    finally:
        child.stop()

    compiled = prom1.get("vl_tpu_jit_compiles_total", 0) \
        - prom0.get("vl_tpu_jit_compiles_total", 0)
    lat = sorted(r["done"] - r["due"] for r in records) or [0.0]
    print(f"window: {len(records)} requests, {window_s:.3f} s, "
          f"{compiled:.0f} compiled inside it, median latency "
          f"{lat[len(lat) // 2] * 1e3:.1f} ms (trace {args.trace})",
          file=sys.stderr)
    for r in [r for r in records if r["status"] != 200][:5]:
        print(f"failed: {r['req']['cls']} status {r['status']} "
              f"{r.get('error', r['body'][:200])}", file=sys.stderr)
    # the program's state is freed: now the reference
    t_ref = time.monotonic()
    verdict = check_answers(records, unanswered, traffic, c["layout"],
                            args.seed)
    print(f"reference: {verdict['checked']['compared']['value']} answers in "
          f"{time.monotonic() - t_ref:.1f} s", file=sys.stderr)
    done = [r for r in records if r["status"] == 200]
    work = [yardstick_bytes.required(c["config"], c["layout"],
                                     traffic["classes"][r["req"]["cls"]],
                                     r["req"]["t_range"]) for r in done]
    ctx = {"records": done, "prom0": prom0, "prom1": prom1,
           "trace": trace_ev,
           "values": {"setup_s": setup_s, "window_s": window_s,
                      "queries": len(done),
                      "rows_scanned": sum(w["rows"] for w in work),
                      "required_bytes": sum(w["bytes"] for w in work)}}
    if not args.rehearsal:
        ctx["values"].update(c["peaks"][device["kind"]]["values"])
    traced = bool(trace_ev and trace_ev.get("busy_s"))
    if traced:
        ctx["values"]["busy_sum_s"] = trace_ev["busy_sum_s"]
    names = cell_metrics(bench, cell, "per_layer" if args.trace
                         else "end_to_end")
    metrics = {}
    for name in names:
        m = read_metric(name, ctx)
        if m is not None:
            metrics[name] = m
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": memory["memory_peak_bytes"]}
    result = {"correct": verdict["correct"], "attempted": len(records)
              + unanswered,
              "failed": len(records) - len(done) + unanswered,
              "metrics": metrics, "device": dev}
    if args.rehearsal:
        result["rehearsal"] = True
    if traced:
        dev["busy_s"] = trace_ev["busy_s"]
        dev["window_s"] = trace_ev["window_s"]
        import breakdown
        result["breakdown"] = breakdown.build(trace_ev, records)
    if verdict["wrong"]:
        result["wrong"] = verdict["wrong"][:5]
    result["checked"] = verdict["checked"]
    for name, chk in verdict["checked"].items():
        print(f"checked {name}: {json.dumps(chk)}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
