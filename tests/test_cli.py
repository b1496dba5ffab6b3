"""CLI smoke tests: vlogscli REPL and vlogsgenerator against a live
server (reference apptest pattern)."""

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
import urllib.parse
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _start_server(tmp):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    port = _free_port()
    proc = subprocess.Popen(
        [sys.executable, "-m", "victorialogs_tpu.server",
         "-storageDataPath", tmp, "-httpListenAddr",
         f"127.0.0.1:{port}"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        cwd=REPO)
    for _ in range(100):
        try:
            socket.create_connection(("127.0.0.1", port), 0.3).close()
            break
        except OSError:
            time.sleep(0.2)
    return proc, port, env


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_generator_and_cli(tmp_path):
    proc, port, env = _start_server(str(tmp_path))
    try:
        gen = subprocess.run(
            [sys.executable, "-m", "victorialogs_tpu.cli.vlogsgenerator",
             "-addr", f"http://127.0.0.1:{port}", "-streams", "4",
             "-logsPerStream", "25", "-u16FieldsPerLog", "1",
             "-i64FieldsPerLog", "1"],
            capture_output=True, timeout=60, env=env, cwd=REPO)
        assert gen.returncode == 0, gen.stderr.decode()
        assert b"emitted 100 rows" in gen.stderr

        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/internal/force_flush", timeout=30)
        u = (f"http://127.0.0.1:{port}/select/logsql/query?"
             + urllib.parse.urlencode({"query": "* | stats count() n"}))
        n = json.loads(urllib.request.urlopen(
            u, timeout=30).read().splitlines()[0])["n"]
        assert n == "100"

        cli = subprocess.run(
            [sys.executable, "-m", "victorialogs_tpu.cli.vlogscli",
             "-datasource.url", f"http://127.0.0.1:{port}"],
            input=b"* | stats count() as n\n\\q\n",
            capture_output=True, timeout=60, env=env, cwd=REPO)
        assert cli.returncode == 0, cli.stdout.decode()
        assert b'"n":"100"' in cli.stdout or b"'n': '100'" in cli.stdout \
            or b"100" in cli.stdout
    finally:
        proc.terminate()
        proc.wait(10)
