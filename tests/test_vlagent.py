"""vlagent + persistent queue tests: durable forwarding, replication to
every remote, delivery resume across outages and restarts."""

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

from victorialogs_tpu.utils.persistentqueue import PersistentQueue

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------- persistent queue unit tests ----------------

def test_queue_fifo_and_ack(tmp_path):
    q = PersistentQueue(str(tmp_path / "q"))
    q.append(b"one")
    q.append(b"two")
    assert q.read() == b"one"
    assert q.read() == b"one"          # read peeks until ack
    q.ack(3)
    assert q.read() == b"two"
    q.ack(3)
    assert q.read(timeout=0.05) is None
    q.close()


def test_queue_survives_reopen(tmp_path):
    q = PersistentQueue(str(tmp_path / "q"))
    q.append(b"aaa")
    q.append(b"bbbb")
    assert q.read() == b"aaa"
    q.ack(3)
    q.close()
    q2 = PersistentQueue(str(tmp_path / "q"))
    assert q2.read() == b"bbbb"        # unacked block re-delivered
    q2.ack(4)
    assert q2.read(timeout=0.05) is None
    q2.close()


def test_queue_segment_rollover(tmp_path):
    from victorialogs_tpu.utils import persistentqueue as pq
    orig = pq.SEGMENT_MAX_BYTES
    pq.SEGMENT_MAX_BYTES = 256
    try:
        q = PersistentQueue(str(tmp_path / "q"))
        blocks = [f"block-{i}".encode() * 8 for i in range(20)]
        for b in blocks:
            q.append(b)
        for b in blocks:
            got = q.read()
            assert got == b
            q.ack(len(got))
        assert q.read(timeout=0.05) is None
        q.close()
    finally:
        pq.SEGMENT_MAX_BYTES = orig


def test_queue_overflow(tmp_path):
    q = PersistentQueue(str(tmp_path / "q"), max_pending_bytes=100)
    with pytest.raises(IOError):
        for _ in range(10):
            q.append(b"x" * 40)
    q.close()


# ---------------- end-to-end agent -> storage ----------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _wait_http(port, timeout=30):
    for _ in range(int(timeout / 0.2)):
        try:
            socket.create_connection(("127.0.0.1", port), 0.3).close()
            return True
        except OSError:
            time.sleep(0.2)
    return False


def _start(module, args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    return subprocess.Popen([sys.executable, "-m", module] + args,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, env=env, cwd=REPO)


def _query_count(port, query="*"):
    u = (f"http://127.0.0.1:{port}/select/logsql/query?"
         + urllib.parse.urlencode({"query": f"{query} | stats count() n"}))
    with urllib.request.urlopen(u, timeout=30) as resp:
        return int(json.loads(resp.read().splitlines()[0])["n"])


def test_agent_forwards_and_resumes(tmp_path):
    procs = []
    try:
        s_port = _free_port()
        storage = _start("victorialogs_tpu.server",
                         ["-storageDataPath", str(tmp_path / "store"),
                          "-httpListenAddr", f"127.0.0.1:{s_port}"])
        procs.append(storage)
        a_port = _free_port()
        agent = _start("victorialogs_tpu.server.vlagent",
                       ["-remoteWrite.url", f"http://127.0.0.1:{s_port}",
                        "-remoteWrite.tmpDataPath", str(tmp_path / "q"),
                        "-httpListenAddr", f"127.0.0.1:{a_port}"])
        procs.append(agent)
        assert _wait_http(s_port) and _wait_http(a_port)

        rows = b"\n".join(json.dumps(
            {"_msg": f"agent row {i}", "app": f"a{i % 3}"}).encode()
            for i in range(100))
        req = urllib.request.Request(
            f"http://127.0.0.1:{a_port}/insert/jsonline?_stream_fields=app",
            data=rows)
        assert urllib.request.urlopen(req, timeout=30).status == 200

        deadline = time.time() + 30
        while time.time() < deadline:
            urllib.request.urlopen(
                f"http://127.0.0.1:{s_port}/internal/force_flush",
                timeout=10)
            try:
                if _query_count(s_port) == 100:
                    break
            except Exception:
                pass
            time.sleep(0.3)
        assert _query_count(s_port) == 100

        # outage: kill storage, keep ingesting into the agent
        storage.terminate()
        storage.wait(10)
        rows2 = b"\n".join(json.dumps(
            {"_msg": f"late row {i}", "app": "late"}).encode()
            for i in range(50))
        req = urllib.request.Request(
            f"http://127.0.0.1:{a_port}/insert/jsonline?_stream_fields=app",
            data=rows2)
        assert urllib.request.urlopen(req, timeout=30).status == 200
        time.sleep(1.0)

        # storage returns on the same port: queue must drain
        storage2 = _start("victorialogs_tpu.server",
                          ["-storageDataPath", str(tmp_path / "store"),
                           "-httpListenAddr", f"127.0.0.1:{s_port}"])
        procs.append(storage2)
        assert _wait_http(s_port)
        deadline = time.time() + 45
        while time.time() < deadline:
            urllib.request.urlopen(
                f"http://127.0.0.1:{s_port}/internal/force_flush",
                timeout=10)
            try:
                if _query_count(s_port) == 150:
                    break
            except Exception:
                pass
            time.sleep(0.5)
        assert _query_count(s_port) == 150
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_queue_truncates_torn_tail(tmp_path):
    import struct
    q = PersistentQueue(str(tmp_path / "torn"))
    q.append(b"good-one")
    q.close()
    # simulate a crash mid-append: length prefix says 5000, payload torn
    seg = [n for n in os.listdir(tmp_path / "torn")
           if n.startswith("seg_")][0]
    with open(tmp_path / "torn" / seg, "ab") as f:
        f.write(struct.pack(">I", 5000) + b"only 100 bytes" * 7)
    q2 = PersistentQueue(str(tmp_path / "torn"))
    q2.append(b"after-crash")
    assert q2.read() == b"good-one"
    q2.ack(8)
    # the torn record is gone; framing stays intact
    assert q2.read() == b"after-crash"
    q2.ack(11)
    assert q2.read(timeout=0.05) is None
    q2.close()
