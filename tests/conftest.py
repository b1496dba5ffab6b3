"""Test configuration: JAX on a virtual 8-device CPU world.

Set by environment before the first JAX import — JAX_PLATFORMS=cpu keeps
the device path on the jax-CPU backend (no accelerator is probed) and
XLA_FLAGS provisions the 8 virtual devices the mesh suites shard over.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# parity suites exist to diff the device kernels against the host path —
# pin the cost gate so it never silently routes everything to host on the
# (fast-RTT) CPU backend; the gate itself is covered by
# tests/test_cost_model.py, which overrides this per-test
os.environ.setdefault("VL_COST_FORCE", "device")
# the per-part result cache replays a warm part instead of executing
# it — correct (and covered by tests/test_standing.py, which opts back
# in), but it would silently hollow out every CPU-vs-device parity
# differential in this suite: the serial oracle run would seed the
# cache and the device run would replay it, exercising no kernel at
# all.  Parity suites must execute what they compare, so the cache is
# opt-in under test.
os.environ.setdefault("VL_RESULT_CACHE", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# ---- vlsan runtime sanitizers (tools/vlint/vlsan.py) ----
# Two layers under one umbrella:
#
# 1. end-of-test invariant sweep (opt-OUT, VLSAN=0 kills it): after
#    every test, the budgets/registries the test touched must balance —
#    sched leases, StagingCache bytes, bloom-bank charges, event-bus
#    subscriptions, journal accounting, admission pools, non-daemon
#    threads, no negative counters.  The runtime twin of the static
#    tools/vlint/balance.py checker.
# 2. the lock-order sanitizer (opt-IN, VLINT_LOCK_ORDER=1): wraps every
#    threading.Lock constructed inside victorialogs_tpu with an
#    acquisition-order-recording shim; at session end the observed
#    graph must stay acyclic when merged with the static lock-order
#    graph — the race suites and the static analyzer validate each
#    other.  `make race` runs the concurrency suites with both on.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import sys  # noqa: E402

if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from tools.vlint import vlsan as _vlsan  # noqa: E402

_VLINT_SANITIZER = _vlsan.install_lock_order()
_VLSAN = _vlsan.Sanitizer() if _vlsan.enabled() else None

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _result_cache_isolation():
    """Start every test with a cold per-part result cache.  Warm
    entries stay CORRECT across tests (keys are immutable part uids,
    kept alive here by module-scoped storage fixtures), but a replayed
    part stages nothing and dispatches nothing — which silently zeroes
    the staging-hit / device-call counts older suites assert.  Cheap
    no-op when the module was never imported."""
    rc = sys.modules.get("victorialogs_tpu.engine.standing.resultcache")
    if rc is not None:
        rc.reset_for_tests()
    yield


@pytest.fixture(autouse=True)
def _vlsan_sweep():
    """End-of-test invariant sweep (VLSAN=0 disables).  Baselines are
    captured after higher-scoped fixtures exist, so a module-scoped
    live server never reads as a leak — only what THIS test failed to
    release does."""
    if _VLSAN is None:
        yield
        return
    _VLSAN.begin_test()
    yield
    problems = _VLSAN.sweep()
    if problems:
        pytest.fail("vlsan: " + "; ".join(problems), pytrace=False)


def pytest_sessionfinish(session, exitstatus):
    if _VLINT_SANITIZER is None:
        return
    problems = _vlsan.lock_order_problems(_VLINT_SANITIZER, _REPO_ROOT)
    n_edges = len(_VLINT_SANITIZER.edges)
    if problems:
        print("\nvlint lock-order sanitizer FAILED "
              f"({n_edges} observed edge(s)):")
        for p in problems:
            print(f"  {p}")
        session.exitstatus = 1
    else:
        print(f"\nvlint lock-order sanitizer: {n_edges} observed "
              "acquisition edge(s), consistent with the static graph")
