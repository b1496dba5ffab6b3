"""The benchmark's own tests (benchmark/tests) as tier-1 cases: the
harness decides every PR, so a change that breaks it fails here.

The harness's modules are written to run from `benchmark/` with
top-level names (`gen`, `run`, `traffic`, `reference`, `bytes`,
`xplane`, `readers`, ...).  Every xdist worker imports this file to
collect it, and `--dist loadfile` runs it in one: so the names are
imported here, taken out of `sys.modules` and `sys.path` again before
collection goes on, and put back only around this file's tests.  No
other test file ever sees them.  Nothing under benchmark/ is changed:
each test function there is a case here, parametrisation and fixtures
included.
"""

import importlib
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
_PATHS = [BENCH, os.path.join(BENCH, "tests")]
_TEST_MODULES = ("test_yardstick", "test_trace_readers", "test_schema_seam",
                 "test_new_schema_as_files", "test_cpu_readers")


def _harness_modules() -> dict:
    """name -> module of everything imported from under benchmark/."""
    return {n: m for n, m in sys.modules.items()
            if (getattr(m, "__file__", None) or "").startswith(
                BENCH + os.sep)}


def _import_then_hide() -> dict:
    assert not _harness_modules(), "a harness module is already imported"
    path = list(sys.path)
    pytest.register_assert_rewrite(*_TEST_MODULES)
    sys.path[:0] = _PATHS
    try:
        for name in _TEST_MODULES:
            importlib.import_module(name)
    finally:
        sys.path[:] = path
    own = _harness_modules()
    for name in own:
        del sys.modules[name]
    return own


_OWN = _import_then_hide()

for _name in _TEST_MODULES:
    for _attr, _obj in vars(_OWN[_name]).items():
        # the test functions and the fixtures they ask for
        if _attr.startswith("test_") or hasattr(
                _obj, "_fixture_function_marker"):
            assert _attr not in globals(), _attr
            globals()[_attr] = _obj


@pytest.fixture(scope="module", autouse=True)
def _harness_importable():
    """The harness imports its schema, reader and control modules by
    name while it runs."""
    path = list(sys.path)
    sys.path[:0] = _PATHS
    sys.modules.update(_OWN)
    yield
    for name in _harness_modules():
        del sys.modules[name]
    sys.path[:] = path
