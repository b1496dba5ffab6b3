"""Device plane: staging + JAX/Pallas kernels.

Importing this package is the program's first JAX use, so the persistent
compilation cache is placed here, once, before any jit runs.  pad_bucket
x filter tree x pack size gives dozens of distinct programs per query
mix; without the cache every server start recompiles all of them.

Placement comes from OUTSIDE when ``JAX_COMPILATION_CACHE_DIR`` is set
(jax reads it itself — nothing is set in code); otherwise the cache lives
at the fixed path ``<checkout>/.jax_cache``.  Never a temp name, pid or
timestamp: the directory is part of the cache key.

A process pinned to jax-CPU on purpose (``JAX_PLATFORMS`` names cpu: the
tests, make check) gets no default directory: XLA:CPU reloads cached AOT
results with a machine-feature mismatch error per program, and nothing
measured runs there.
"""

import os
import threading

import jax


# vlint: allow-env-registry(JAX_PLATFORMS is jax's own variable, read to learn what jax was told — not a knob of this program)
def cpu_pinned() -> bool:
    """JAX_PLATFORMS names cpu and nothing else: someone asked for the
    jax-CPU device path on purpose (as opposed to JAX falling back to it,
    or cpu trailing an accelerator in the list)."""
    names = [p for p in os.environ.get("JAX_PLATFORMS", "").lower()
             .split(",") if p]
    return bool(names) and all(p == "cpu" for p in names)


# vlint: allow-env-registry(JAX_COMPILATION_CACHE_DIR is jax's own variable: when set, jax places the cache and this program sets nothing)
if not os.environ.get("JAX_COMPILATION_CACHE_DIR") and not cpu_pinned():
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"))
# keep every program, however quick to compile: the small pad-bucket
# programs are the many, and a threshold would make the entry count
# depend on compile-time jitter
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def compile_cache_dir() -> str | None:
    """Where this process keeps compiled programs (None: nowhere)."""
    return jax.config.jax_compilation_cache_dir


# What compilation costs this process, off jax's own monitoring events:
# every backend compile request (a persistent-cache hit is one too, just
# a short one), the seconds they took, and the cache's hits and misses.
# Served under /metrics as vl_tpu_* (BatchRunner.stats) so a run can tell
# compile time from run time and a warm cache from a cold one.
_compile_mu = threading.Lock()
_compile_counts = {"jit_compiles_total": 0,
                   "jit_compile_seconds_total": 0.0,
                   "compile_cache_hits_total": 0,
                   "compile_cache_misses_total": 0}


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        with _compile_mu:
            _compile_counts["jit_compiles_total"] += 1
            _compile_counts["jit_compile_seconds_total"] += duration


_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits_total",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses_total"}


def _on_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        with _compile_mu:
            _compile_counts[key] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def compile_stats() -> dict:
    with _compile_mu:
        return dict(_compile_counts)
