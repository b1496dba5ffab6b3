"""chip_smoke.py cannot rot between chip runs: tier-1 rehearses it end
to end on jax-CPU, and pins the two ways it (and the server under it)
must refuse to pass for a chip run."""

import glob
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**kw):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    # the smoke runs the server at its defaults, not at the parity
    # suite's pins (tests/conftest.py)
    for k in ("VL_COST_FORCE", "VL_RESULT_CACHE", "XLA_FLAGS"):
        env.pop(k, None)
    env.update(kw)
    return env


def test_cpu_rehearsal_end_to_end(tmp_path):
    res = subprocess.run(
        [sys.executable, SMOKE, "--rows", "20000", "--cpu-rehearsal",
         "--data-dir", str(tmp_path / "data")],
        capture_output=True, timeout=600, env=_env(), cwd=REPO)
    assert res.returncode == 0, res.stderr.decode()[-4000:]
    report, verdict = res.stdout.decode().splitlines()[-2:]
    # the driver reads the last line and allows it exactly these keys
    assert json.loads(verdict) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    out = json.loads(report)
    assert out["ok"] is True and out["cpu_rehearsal"] is True
    assert out["device"] == json.loads(verdict)["device"]
    assert out["device"]["platform"] == "cpu"
    assert out["rows_read_back"] == out["rows_ingested"] == 20000
    assert out["runner"] == "BatchRunner"
    assert out["native_available"] is True
    assert len(out["queries"]) == 8
    assert all(q["equal"] for q in out["queries"].values())
    assert sum(sum(q["device_calls"])
               for q in out["queries"].values()) > 0
    assert list(out)[-1] == "claim" and out["claim"] is None
    assert not (tmp_path / "data").exists()     # cleaned up after itself


def test_smoke_fails_without_a_chip(tmp_path):
    """No --cpu-rehearsal, jax held to the CPU: non-zero, and nothing on
    stdout that could be read as a result."""
    res = subprocess.run(
        [sys.executable, SMOKE, "--data-dir", str(tmp_path / "data")],
        capture_output=True, timeout=300,
        env=_env(JAX_PLATFORMS="cpu"), cwd=REPO)
    assert res.returncode != 0
    assert res.stdout.decode().strip() == ""
    assert "not tpu" in res.stderr.decode()


@pytest.mark.skipif(bool(glob.glob("/dev/accel*")),
                    reason="this machine has a TPU: -tpu rightly serves")
def test_tpu_flag_refuses_silent_cpu_fallback(tmp_path):
    """-tpu on a machine with no TPU and no explicit JAX_PLATFORMS=cpu:
    jax falls back to its CPU backend, the server must not."""
    env = _env()
    env.pop("JAX_PLATFORMS", None)
    res = subprocess.run(
        [sys.executable, "-m", "victorialogs_tpu.server", "-tpu",
         "-storageDataPath", str(tmp_path / "d"),
         "-httpListenAddr", "127.0.0.1:0"],
        capture_output=True, timeout=300, env=env, cwd=REPO)
    assert res.returncode != 0
    assert "not a TPU" in res.stderr.decode()
    assert "started victoria-logs" not in res.stdout.decode()
    assert not (tmp_path / "d").exists()        # refused before storage
