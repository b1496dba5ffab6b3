"""A stand-in for benchmark/serve.py that holds no chip: it speaks the same
JSON lines and answers the same HTTP requests from the plain reference, so
a test can drive a whole run of run.py over it and break what it produces.

--fault none        every answer as the reference gives it
--fault altered     one answer in five altered where it is produced (+1)
--fault fresh | part:<i>   the control: those rows are not readable
"""

import argparse
import json
import os
import sys
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import gen  # noqa: E402
import reference  # noqa: E402
import traffic as traffic_gen  # noqa: E402


def say(event, **kw):
    print(json.dumps({"event": event, **kw}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="none")
    args, _rest = ap.parse_known_args()
    config = gen.load_config(args.config)
    traffic = gen.load_json(args.traffic)
    layout = gen.Layout(config,
                        gen.REHEARSAL_SCALE if args.rehearsal else 1.0)
    lost = None if args.fault in ("none", "altered") \
        else reference.unreadable_rows(layout, args.fault)
    ref = reference.Reference(layout, args.seed, lost)
    # every request the run can send, by its text
    plan = [(k, cls) for k, (_d, cls) in enumerate(
        traffic_gen.schedule(traffic, args.seconds))]
    known = {}
    for k, cls in plan:
        for prefix in ("w", "q"):
            req = traffic_gen.make_request(traffic, layout, args.seed, k,
                                           cls, prefix)
            known[req["query"]] = req
    mu = threading.Lock()
    served = [0]

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_GET(self):
            self._send(b"")

        def do_POST(self):
            form = urllib.parse.parse_qs(
                self.rfile.read(int(self.headers["Content-Length"])).decode())
            req = known[form["query"][0]]
            with mu:
                rows = ref.answer(req, traffic["classes"][req["cls"]]
                                  ["reference"])
                served[0] += 1
                alter = args.fault == "altered" and served[0] % 5 == 0
            if alter:
                rows = [tuple((k, v if k == "_time" else
                               v + 1 if isinstance(v, int) else v + "x")
                              for k, v in row) for row in rows]
            self._send(reference.render(req["endpoint"], rows))

        def _send(self, body: bytes):
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    say("device", platform="cpu", kind="stub", count=int(config["chips"]),
        runner=config["runner"], compile_cache="None")
    say("ready", port=httpd.server_address[1], rows=layout.rows, build_s=0.0)
    for line in sys.stdin:
        cmd = line.strip()
        if cmd == "memory":
            say("memory", memory_peak_bytes=0)
        elif cmd == "trace_start":
            say("trace_started", marker_unix_ns=0)
        elif cmd == "trace_stop":
            say("trace")
        elif cmd == "quit":
            break
    httpd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
