"""vlint: per-checker fixtures (violating / clean / annotated), the
runtime lock-order sanitizer, the CLI exit codes, and the tier-1 gate
asserting the repo itself is clean against the committed baseline."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.vlint.core import (load_baseline, new_findings, run_paths,
                              run_source)
from tools.vlint.runtime import (InstrumentedLock, LockOrderSanitizer,
                                 install, uninstall)


def lint(src: str, path: str = "victorialogs_tpu/mod.py"):
    return run_source(path, textwrap.dedent(src))


def checkers(findings):
    return {f.checker for f in findings}


# ---------------- lock discipline ----------------

LOCK_BASE = """
    import threading

    class C:
        def __init__(self):
            self._lock = threading.Lock()
            self.x = 0

        def good(self):
            with self._lock:
                self.x += 1
"""


def test_unguarded_write_flagged():
    out = lint(LOCK_BASE + """
        def bad(self):
            self.x = 5
    """)
    assert "lock-unguarded-write" in checkers(out)
    assert any("self.x" in f.message for f in out)


def test_unguarded_write_clean_and_init_exempt():
    assert "lock-unguarded-write" not in checkers(lint(LOCK_BASE))


def test_unguarded_write_annotated():
    out = lint(LOCK_BASE + """
        def bad(self):
            # vlint: allow-lock-unguarded-write(single-writer thread)
            self.x = 5
    """)
    assert "lock-unguarded-write" not in checkers(out)


def test_unguarded_write_through_private_helper():
    # a private method reached both locked and unlocked: the unlocked
    # path must flag (the indexdb._account_write class of race)
    out = lint(LOCK_BASE + """
        def _bump(self):
            self.x += 1

        def locked_path(self):
            with self._lock:
                self._bump()

        def unlocked_path(self):
            self._bump()
    """)
    assert "lock-unguarded-write" in checkers(out)


def test_blocking_call_under_lock_flagged():
    out = lint(LOCK_BASE + """
        def bad(self):
            with self._lock:
                with open("/tmp/f") as f:
                    return f.read()
    """)
    assert "lock-blocking-call" in checkers(out)


def test_blocking_call_outside_lock_clean():
    out = lint(LOCK_BASE + """
        def fine(self):
            with open("/tmp/f") as f:
                return f.read()
    """)
    assert "lock-blocking-call" not in checkers(out)


def test_blocking_call_annotated():
    out = lint(LOCK_BASE + """
        # vlint: allow-lock-blocking-call(durability by design)
        def bad(self):
            with self._lock:
                with open("/tmp/f") as f:
                    return f.read()
    """)
    assert "lock-blocking-call" not in checkers(out)


def test_os_path_join_not_blocking():
    out = lint(LOCK_BASE + """
        def fine(self):
            import os
            with self._lock:
                return os.path.join("a", "b")
    """)
    assert "lock-blocking-call" not in checkers(out)


def test_lock_order_cycle_flagged():
    out = lint("""
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def m1(self):
                with self._a:
                    with self._b:
                        pass

            def m2(self):
                with self._b:
                    with self._a:
                        pass
    """)
    assert "lock-order-cycle" in checkers(out)


def test_lock_order_consistent_clean():
    out = lint("""
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()
                self._b = threading.Lock()

            def m1(self):
                with self._a:
                    with self._b:
                        pass

            def m2(self):
                with self._a:
                    with self._b:
                        pass
    """)
    assert "lock-order-cycle" not in checkers(out)


def test_self_reacquire_flagged():
    out = lint(LOCK_BASE + """
        def bad(self):
            with self._lock:
                with self._lock:
                    pass
    """)
    assert "lock-order-cycle" in checkers(out)


# ---------------- hygiene ----------------

def test_broad_except_flagged():
    out = lint("""
        def f():
            try:
                return 1
            except Exception:
                return 0
    """)
    assert "broad-except" in checkers(out)


def test_broad_except_reraise_clean():
    out = lint("""
        def f():
            try:
                return 1
            except Exception:
                raise
    """)
    assert "broad-except" not in checkers(out)


def test_broad_except_annotated():
    out = lint("""
        def f():
            try:
                return 1
            # vlint: allow-broad-except(best-effort)
            except Exception:
                return 0
    """)
    assert "broad-except" not in checkers(out)


def test_mutable_default_flagged_and_clean():
    assert "mutable-default" in checkers(lint("def f(a, b=[]): pass"))
    assert "mutable-default" not in checkers(
        lint("def f(a, b=None, c=()): pass"))


def test_wall_clock_flagged_clean_annotated():
    assert "wall-clock" in checkers(lint("""
        import time
        def f():
            return time.time()
    """))
    assert "wall-clock" not in checkers(lint("""
        import time
        def f():
            return time.monotonic(), time.time_ns()
    """))
    assert "wall-clock" not in checkers(lint("""
        import time
        def f():
            # vlint: allow-wall-clock(persisted timestamp)
            return time.time()
    """))


def test_nondaemon_thread_flagged_and_clean():
    assert "nondaemon-thread" in checkers(lint("""
        import threading
        def f():
            threading.Thread(target=f).start()
    """))
    assert "nondaemon-thread" not in checkers(lint("""
        import threading
        def f():
            threading.Thread(target=f, daemon=True).start()
    """))


# ---------------- JAX hot path ----------------

def test_host_sync_flagged():
    out = lint("""
        import jax.numpy as jnp
        def f(a):
            x = jnp.sum(a)
            return float(x)
    """, path="victorialogs_tpu/tpu/mod.py")
    assert "jax-host-sync" in checkers(out)


def test_host_sync_out_of_scope_and_clean():
    src = """
        import jax.numpy as jnp
        def f(a):
            x = jnp.sum(a)
            return float(x)
    """
    # same code outside tpu/ or engine/ is not hot-path scoped
    assert "jax-host-sync" not in checkers(
        lint(src, path="victorialogs_tpu/storage/mod.py"))
    clean = """
        import jax.numpy as jnp
        def f(a):
            x = jnp.sum(a)
            return x
    """
    assert "jax-host-sync" not in checkers(
        lint(clean, path="victorialogs_tpu/tpu/mod.py"))


def test_host_sync_annotated_and_variants():
    out = lint("""
        import jax.numpy as jnp
        import numpy as np
        def f(a):
            x = jnp.sum(a)
            # vlint: allow-jax-host-sync(result boundary)
            return np.asarray(x)
    """, path="victorialogs_tpu/tpu/mod.py")
    assert "jax-host-sync" not in checkers(out)
    out = lint("""
        import jax.numpy as jnp
        def f(a):
            x = jnp.sum(a)
            if x:
                return x.item()
            return 0
    """, path="victorialogs_tpu/tpu/mod.py")
    msgs = [f.message for f in out if f.checker == "jax-host-sync"]
    assert any("truth test" in m for m in msgs)
    assert any(".item()" in m for m in msgs)


def test_jit_closure_flagged_and_clean():
    out = lint("""
        import jax
        state = {"k": 1}
        @jax.jit
        def f(x):
            return x + state["k"]
    """, path="victorialogs_tpu/tpu/mod.py")
    assert "jax-jit-closure" in checkers(out)
    out = lint("""
        import jax
        K = 2
        @jax.jit
        def f(x):
            return x + K
    """, path="victorialogs_tpu/tpu/mod.py")
    assert "jax-jit-closure" not in checkers(out)


_EAGER_SUBMIT_CASES = [
    # (case, source, path, flagged)
    ("scalar_in_submit", """
        import jax.numpy as jnp
        def fused_stats_submit(runner, layout):
            nrows = jnp.int32(layout.nrows)
            return nrows
     """, "victorialogs_tpu/tpu/fused.py", True),
    ("asarray_in_planner_method", """
        import jax.numpy as jnp
        class _Planner:
            def _scan_leaf(self, pat):
                return jnp.asarray(pat)
     """, "victorialogs_tpu/tpu/fused.py", True),
    ("array_in_launch_dotted_module", """
        import jax
        def _launch(runner, dispatch, x):
            return dispatch(jax.numpy.array(x))
     """, "victorialogs_tpu/tpu/fused.py", True),
    ("scalar_in_pipeline_nested_refill", """
        import jax.numpy as jnp
        def scan_device_stream(items):
            def refill():
                return jnp.uint32(len(items))
            return refill
     """, "victorialogs_tpu/tpu/pipeline.py", True),
    ("scalar_in_pipeline_submit", """
        import jax.numpy as jnp
        def _submit_pack_stats(runner, unit):
            return jnp.float32(1.0)
     """, "victorialogs_tpu/tpu/pipeline.py", True),
    ("jitted_body_is_not_submit_path", """
        import jax.numpy as jnp
        def _eval_tree_node(node, args, blk, rlp):
            return jnp.uint32(0), jnp.asarray(args[0])
        def _fused_local(prog, blk):
            return jnp.int32(0)
     """, "victorialogs_tpu/tpu/fused.py", False),
    ("numpy_and_block_are_clean", """
        import numpy as np
        import jax.numpy as jnp
        def fused_stats_submit(runner, planner, layout):
            off = planner.host_words(layout.nrows)
            return np.int32(off), planner.block(), jnp.zeros
     """, "victorialogs_tpu/tpu/fused.py", False),
    ("other_file_is_out_of_scope", """
        import jax.numpy as jnp
        def fused_stats_submit(runner, layout):
            return jnp.int32(layout.nrows)
     """, "victorialogs_tpu/tpu/batch.py", False),
    ("annotated_site_is_allowed", """
        import jax.numpy as jnp
        def fused_stats_submit(runner, layout):
            # vlint: allow-jax-eager-submit(one-off probe, not per dispatch)
            return jnp.int32(layout.nrows)
     """, "victorialogs_tpu/tpu/fused.py", False),
]


@pytest.mark.parametrize(
    "src,path,flagged", [c[1:] for c in _EAGER_SUBMIT_CASES],
    ids=[c[0] for c in _EAGER_SUBMIT_CASES])
def test_eager_submit_flagged_and_clean(src, path, flagged):
    """An eager jnp scalar/asarray/array on the submit path of
    tpu/fused.py or tpu/pipeline.py is an error; jitted bodies, numpy,
    other files and annotated sites are not."""
    out = lint(src, path=path)
    assert ("jax-eager-submit" in checkers(out)) is flagged


def test_eager_submit_clean_on_the_real_submit_path():
    """The shipped tpu/fused.py and tpu/pipeline.py hold no eager jnp
    constructor on the submit path, and no allow annotation hides one."""
    from tools.vlint import hotpath
    from tools.vlint.core import SourceFile
    for rel in ("victorialogs_tpu/tpu/fused.py",
                "victorialogs_tpu/tpu/pipeline.py"):
        sf = SourceFile.parse(os.path.join(REPO, rel), display_path=rel)
        assert hotpath._submit_path_re(rel) is not None
        assert not [f for f in hotpath.check(sf)
                    if f.checker == "jax-eager-submit"], rel
        assert "allow-jax-eager-submit" not in sf.text


def test_static_arg_flagged_and_clean():
    out = lint("""
        import jax
        from functools import partial
        n = 3
        @partial(jax.jit, static_argnums=n)
        def f(x):
            return x
    """, path="victorialogs_tpu/tpu/mod.py")
    assert "jax-static-arg" in checkers(out)
    out = lint("""
        import jax
        from functools import partial
        @partial(jax.jit, static_argnums=(0, 1),
                 static_argnames=("mode",))
        def f(x, n, mode=0):
            return x
    """, path="victorialogs_tpu/tpu/mod.py")
    assert "jax-static-arg" not in checkers(out)


# ---------------- baseline workflow ----------------

def test_baseline_absorbs_then_catches_new(tmp_path):
    from tools.vlint.core import write_baseline
    src = textwrap.dedent("""
        def f():
            try:
                return 1
            except Exception:
                return 0
    """)
    found = run_source("victorialogs_tpu/mod.py", src)
    assert found
    bl_path = str(tmp_path / "baseline.json")
    write_baseline(found, bl_path)
    assert new_findings(found, load_baseline(bl_path)) == []
    # a SECOND identical violation exceeds the baselined count
    src2 = src + textwrap.dedent("""
        def g():
            try:
                return 1
            except Exception:
                return 0
    """)
    found2 = run_source("victorialogs_tpu/mod.py", src2)
    fresh = new_findings(found2, load_baseline(bl_path))
    assert len(fresh) == 1


# ---------------- runtime lock-order sanitizer ----------------

def test_sanitizer_detects_inversion():
    san = LockOrderSanitizer()
    a = InstrumentedLock(san, "victorialogs_tpu/x.py:1")
    b = InstrumentedLock(san, "victorialogs_tpu/x.py:2")
    with a:
        with b:
            pass
    assert not san.violations
    with b:
        with a:
            pass
    assert san.violations, "A->B then B->A must be a violation"


def test_sanitizer_static_consistency():
    san = LockOrderSanitizer()
    a = InstrumentedLock(san, "victorialogs_tpu/x.py:1")
    b = InstrumentedLock(san, "victorialogs_tpu/x.py:2")
    with a:
        with b:
            pass
    site_map = {("victorialogs_tpu/x.py", 1): "C._a",
                ("victorialogs_tpu/x.py", 2): "C._b"}
    # observed a->b agrees with static a->b
    assert san.check_static_consistency({("C._a", "C._b")}, site_map) == []
    # observed a->b REVERSES a static b->a edge
    problems = san.check_static_consistency({("C._b", "C._a")}, site_map)
    assert problems


def test_sanitizer_install_scopes_to_repo(tmp_path):
    import threading

    import pytest

    from tools.vlint.runtime import get_sanitizer
    if get_sanitizer() is not None:
        pytest.skip("session-wide sanitizer active (VLINT_LOCK_ORDER=1);"
                    " uninstalling here would disarm it")
    try:
        san = install()
        # a lock created from repo code is instrumented ...
        from victorialogs_tpu.utils.cache import TwoGenCache
        c = TwoGenCache()
        assert isinstance(c._lock, InstrumentedLock)
        c.put("k", "v")
        assert c.get("k") == "v"
        # ... a lock created from non-repo code is not
        assert not isinstance(threading.Lock(), InstrumentedLock)
        assert not san.violations
    finally:
        uninstall()


def test_sanitizer_condition_wait_order():
    # Condition(instrumented lock): wait() releases out of LIFO order —
    # the held-stack bookkeeping must survive it
    import threading
    san = LockOrderSanitizer()
    lk = InstrumentedLock(san, "victorialogs_tpu/x.py:9")
    cond = threading.Condition(lk)
    with cond:
        cond.wait(timeout=0.01)
    assert not san.violations
    assert san._stack() == []


# ---------------- per-row-emit (columnar emit discipline) ----------------

EMIT_PATH = "victorialogs_tpu/server/mod.py"


def test_per_row_emit_dumps_in_loop_flagged():
    out = lint("""
        import json
        def encode(rows):
            out = []
            for r in rows:
                out.append(json.dumps(r))
            return out
    """, path=EMIT_PATH)
    assert "per-row-emit" in checkers(out)


def test_per_row_emit_dumps_in_comprehension_flagged():
    out = lint("""
        import json
        def encode(rows):
            return "\\n".join(json.dumps(r) for r in rows)
    """, path=EMIT_PATH)
    assert "per-row-emit" in checkers(out)


def test_per_row_emit_dict_comprehension_element_flagged():
    # a dict per iteration with no .append() call at all
    out = lint("""
        def build(br, names):
            return [{n: br.column(n)[i] for n in names}
                    for i in range(br.nrows)]
    """, path=EMIT_PATH)
    assert "per-row-emit" in checkers(out)


def test_per_row_emit_column_dict_clean():
    # ONE dict of columns (dict comprehension not nested in a list
    # comprehension) is the columnar shape — must not flag
    out = lint("""
        def build(br, names):
            return {n: br.column(n) for n in names}
    """, path=EMIT_PATH)
    assert "per-row-emit" not in checkers(out)


def test_per_row_emit_dict_append_flagged():
    # incl. the `append = out.append` bound-method alias
    out = lint("""
        def build(br, names):
            out = []
            append = out.append
            for i in range(br.nrows):
                append({n: br.column(n)[i] for n in names})
            return out
    """, path=EMIT_PATH)
    assert "per-row-emit" in checkers(out)


def test_per_row_emit_single_dumps_clean():
    out = lint("""
        import json
        def encode(obj):
            return json.dumps(obj)
    """, path=EMIT_PATH)
    assert "per-row-emit" not in checkers(out)


def test_per_row_emit_scope_excludes_other_dirs():
    src = """
        import json
        def encode(rows):
            return [json.dumps(r) for r in rows]
    """
    assert "per-row-emit" not in checkers(
        lint(src, path="victorialogs_tpu/logsql/mod.py"))
    assert "per-row-emit" in checkers(
        lint(src, path="victorialogs_tpu/engine/mod.py"))


def test_per_row_emit_annotated():
    out = lint("""
        import json
        def encode(rows):
            out = []
            for r in rows:
                # vlint: allow-per-row-emit(cold admin endpoint)
                out.append(json.dumps(r))
            return out
    """, path=EMIT_PATH)
    assert "per-row-emit" not in checkers(out)


# ---------------- the tier-1 gate + CLI ----------------

def test_hotpath_covers_pipeline_module():
    """The async pipeline (tpu/pipeline.py) is hot-path scoped: the
    checker must SEE the file (an unannotated sync there is flagged),
    the real module must run clean, and the single deliberate harvest
    sync must carry the allow-annotation with its rationale."""
    from tools.vlint import hotpath
    from tools.vlint.core import SourceFile

    # the file is in scope: a synthetic host sync at the same path flags
    out = lint("""
        import jax.numpy as jnp
        def harvest(window):
            x = jnp.zeros(8)
            return float(x)
    """, path="victorialogs_tpu/tpu/pipeline.py")
    assert "jax-host-sync" in checkers(out)

    # the real module runs clean under the full checker set
    path = os.path.join(REPO, "victorialogs_tpu", "tpu", "pipeline.py")
    sf = SourceFile.parse(path,
                          display_path="victorialogs_tpu/tpu/pipeline.py")
    found = [f for f in hotpath.check(sf)
             if not sf.allowed(f.checker, f.line)]
    assert found == [], [f.render() for f in found]

    # the ONE harvest sync point is annotated with a rationale
    assert "vlint: allow-jax-host-sync(" in sf.text
    assert sf.text.count("np.asarray") == 1   # a single sync site


def test_hotpath_covers_stats_seg_module():
    """The segment-major stats kernel module (tpu/stats_seg.py, PR 15)
    rides the tpu/ hot-path scope: the checker must SEE the file (an
    unannotated host sync there is flagged) and the real module must
    run clean — its kernels are traced inside the fused dispatch, so a
    hidden sync or jit-closure would stall every packed stats query."""
    from tools.vlint import hotpath
    from tools.vlint.core import SourceFile

    out = lint("""
        import jax.numpy as jnp
        def reduce_seg(x):
            return float(jnp.sum(x))
    """, path="victorialogs_tpu/tpu/stats_seg.py")
    assert "jax-host-sync" in checkers(out)

    path = os.path.join(REPO, "victorialogs_tpu", "tpu", "stats_seg.py")
    sf = SourceFile.parse(
        path, display_path="victorialogs_tpu/tpu/stats_seg.py")
    found = [f for f in hotpath.check(sf)
             if not sf.allowed(f.checker, f.line)]
    assert found == [], [f.render() for f in found]


def test_repo_is_clean_against_baseline():
    findings = run_paths([os.path.join(REPO, "victorialogs_tpu")],
                         root=REPO)
    fresh = new_findings(findings, load_baseline())
    assert fresh == [], "\n".join(f.render() for f in fresh)


def test_cli_exit_codes(tmp_path):
    env = dict(os.environ, PYTHONPATH=REPO)
    ok = subprocess.run(
        [sys.executable, "-m", "tools.vlint", "victorialogs_tpu"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    # one seeded violation per checker family must each fail the CLI
    seeds = {
        "locks.py": LOCK_BASE + """
        def bad(self):
            self.x = 5
        """,
        "hygiene.py": """
        def f():
            try:
                return 1
            except Exception:
                return 0
        """,
        os.path.join("tpu", "hot.py"): """
        import jax.numpy as jnp
        def f(a):
            return float(jnp.sum(a))
        """,
    }
    for rel, src in seeds.items():
        p = tmp_path / "pkg" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
        r = subprocess.run(
            [sys.executable, "-m", "tools.vlint", str(p.parent),
             "--no-baseline"],
            cwd=REPO, env=env, capture_output=True, text=True)
        assert r.returncode == 1, f"{rel}: {r.stdout}{r.stderr}"
        p.unlink()


# ---------------- span discipline (obs/tracing.py API) ----------------

SPAN_BAD_CTOR = """
    from victorialogs_tpu.obs.tracing import Span

    def f():
        sp = Span("query", {})
        return sp
"""

SPAN_BAD_OPEN = """
    from victorialogs_tpu.obs import tracing

    def f():
        sp = tracing.current_span().span("harvest")
        return sp
"""

SPAN_GOOD = """
    from victorialogs_tpu.obs import tracing

    def f():
        root = tracing.make_root("query")
        with tracing.activate(root):
            with tracing.current_span().span("harvest", unit=1) as h:
                h.add("rows", 5)
        return root.to_dict()
"""


def test_span_discipline_flags_direct_construction():
    out = lint(SPAN_BAD_CTOR)
    assert "span-discipline" in checkers(out)
    assert any("Span(...)" in f.message for f in out)


def test_span_discipline_flags_unclosed_open():
    out = lint(SPAN_BAD_OPEN)
    assert "span-discipline" in checkers(out)
    assert any("never close" in f.message for f in out)


def test_span_discipline_clean_and_annotated():
    assert "span-discipline" not in checkers(lint(SPAN_GOOD))
    annotated = """
        from victorialogs_tpu.obs import tracing

        def f():
            # vlint: allow-span-discipline(closed manually in a handle)
            sp = tracing.current_span().span("x")
            return sp
    """
    assert "span-discipline" not in checkers(lint(annotated))


@pytest.mark.parametrize("maker", ["make_root", "request_root",
                                   "make_child"])
def test_span_discipline_flags_dropped_detached_span(maker):
    arg = "parent, " if maker == "make_child" else ""
    bad = f"""
        from victorialogs_tpu.obs import tracing

        def f(parent):
            tracing.{maker}({arg}"query")
    """
    out = lint(bad)
    assert "span-discipline" in checkers(out)
    assert any("made and dropped" in f.message for f in out)
    kept = f"""
        from victorialogs_tpu.obs import tracing

        def f(parent):
            sp = tracing.{maker}({arg}"query")
            with tracing.activate(sp):
                pass
    """
    assert "span-discipline" not in checkers(lint(kept))


def test_span_discipline_skips_tracing_module():
    out = lint(SPAN_BAD_CTOR,
               path="victorialogs_tpu/obs/tracing.py")
    assert "span-discipline" not in checkers(out)


def test_span_discipline_repo_instrumentation_is_clean():
    """Every .span()/make_root call site the tracing wiring added must
    honor the context-manager discipline across all instrumented
    layers."""
    from tools.vlint.core import SourceFile
    from tools.vlint import spans
    for rel in ("engine/searcher.py", "storage/filterbank.py",
                "tpu/pipeline.py", "tpu/batch.py", "tpu/layout.py",
                "parallel/distributed.py", "server/cluster.py",
                "server/vlselect.py", "server/app.py",
                "sched/admission.py", "tpu/fused.py",
                "obs/stallwatch.py"):
        path = os.path.join(REPO, "victorialogs_tpu", rel)
        sf = SourceFile.parse(path,
                              display_path=f"victorialogs_tpu/{rel}")
        found = [f for f in spans.check(sf)
                 if not sf.allowed(f.checker, f.line)]
        assert found == [], [f.render() for f in found]


# ---------------- accounting discipline (obs/activity.py API) ----------------

ACCT_BAD_CTOR = """
    from victorialogs_tpu.obs.activity import QueryActivity

    def f():
        act = QueryActivity("1", "/x", "*", "0:0")
        return act
"""

ACCT_BAD_OPEN = """
    from victorialogs_tpu.obs import activity

    def f():
        act = activity.track("/select/logsql/query", "*", None)
        return act
"""

ACCT_GOOD = """
    from victorialogs_tpu.obs import activity

    def f(storage, run_query):
        with activity.track("/select/logsql/query", "*", None) as act:
            act.add("parts_scanned")
            run_query(storage)
        return activity.active_snapshot()
"""


def test_accounting_discipline_flags_direct_construction():
    out = lint(ACCT_BAD_CTOR)
    assert "accounting-discipline" in checkers(out)
    assert any("QueryActivity(...)" in f.message for f in out)


def test_accounting_discipline_flags_unclosed_track():
    out = lint(ACCT_BAD_OPEN)
    assert "accounting-discipline" in checkers(out)
    assert any("never deregister" in f.message for f in out)


def test_accounting_discipline_clean_and_annotated():
    assert "accounting-discipline" not in checkers(lint(ACCT_GOOD))
    annotated = """
        from victorialogs_tpu.obs import activity

        def f():
            # vlint: allow-accounting-discipline(deregistered in a handle)
            t = activity.track("/x", "*", None)
            return t
    """
    assert "accounting-discipline" not in checkers(lint(annotated))


def test_accounting_discipline_skips_activity_module():
    out = lint(ACCT_BAD_CTOR,
               path="victorialogs_tpu/obs/activity.py")
    assert "accounting-discipline" not in checkers(out)


def test_accounting_discipline_repo_instrumentation_is_clean():
    """Every track()/QueryActivity site the registry wiring added must
    honor the context-manager discipline across the registering
    layers."""
    from tools.vlint.core import SourceFile
    from tools.vlint import accounting
    for rel in ("engine/searcher.py", "server/vlselect.py",
                "server/cluster.py", "server/app.py",
                "server/vlagent.py", "tpu/pipeline.py"):
        path = os.path.join(REPO, "victorialogs_tpu", rel)
        sf = SourceFile.parse(path,
                              display_path=f"victorialogs_tpu/{rel}")
        found = [f for f in accounting.check(sf)
                 if not sf.allowed(f.checker, f.line)]
        assert found == [], [f.render() for f in found]


def test_accounting_discipline_flags_unclosed_reuse():
    out = lint("""
        from victorialogs_tpu.obs import activity

        def f():
            t = activity.reuse_or_track("/x", "*", None)
            return t
    """)
    assert "accounting-discipline" in checkers(out)


# ---------------- lease discipline (victorialogs_tpu/sched API) -------------

LEASE_BAD_CTOR = """
    from victorialogs_tpu.sched.scheduler import _SlotScope

    def f(s):
        scope = _SlotScope(s, None, "0:0")
        return scope
"""

LEASE_BAD_OPEN = """
    from victorialogs_tpu import sched

    def f():
        slots = sched.device_slots(None)
        slots.try_acquire()
        return slots
"""

LEASE_GOOD = """
    from victorialogs_tpu import sched

    def f(run_unit):
        with sched.device_slots(None) as slots:
            slots.acquire()
            try:
                run_unit()
            finally:
                slots.release()
"""


def test_lease_discipline_flags_direct_construction():
    out = lint(LEASE_BAD_CTOR)
    assert "lease-discipline" in checkers(out)
    assert any("_SlotScope(...)" in f.message for f in out)


def test_lease_discipline_flags_unclosed_scope():
    out = lint(LEASE_BAD_OPEN)
    assert "lease-discipline" in checkers(out)
    assert any("never drain" in f.message for f in out)


def test_lease_discipline_clean_and_annotated():
    assert "lease-discipline" not in checkers(lint(LEASE_GOOD))
    annotated = """
        from victorialogs_tpu import sched

        def f():
            # vlint: allow-lease-discipline(drained in a handle)
            slots = sched.device_slots(None)
            return slots
    """
    assert "lease-discipline" not in checkers(lint(annotated))


def test_lease_discipline_skips_sched_package():
    out = lint(LEASE_BAD_CTOR,
               path="victorialogs_tpu/sched/scheduler.py")
    assert "lease-discipline" not in checkers(out)


def test_lease_discipline_repo_instrumentation_is_clean():
    """The pipeline's slot leasing (the ONE consumer of device_slots)
    must honor the context-manager scope discipline, and the sched
    package itself must pass the lock-discipline pass."""
    from tools.vlint.core import SourceFile
    from tools.vlint import leases, locks
    for rel in ("tpu/pipeline.py", "engine/searcher.py",
                "server/app.py"):
        path = os.path.join(REPO, "victorialogs_tpu", rel)
        sf = SourceFile.parse(path,
                              display_path=f"victorialogs_tpu/{rel}")
        found = [f for f in leases.check(sf)
                 if not sf.allowed(f.checker, f.line)]
        assert found == [], [f.render() for f in found]
    for rel in ("sched/scheduler.py", "sched/admission.py"):
        path = os.path.join(REPO, "victorialogs_tpu", rel)
        sf = SourceFile.parse(path,
                              display_path=f"victorialogs_tpu/{rel}")
        found = [f for f in locks.check(sf)
                 if not sf.allowed(f.checker, f.line)]
        assert found == [], [f.render() for f in found]


# ---------------- net discipline ----------------

NET_BAD_URLOPEN = """
    import urllib.request

    def fetch(url):
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.read()
"""

NET_BAD_CONN = """
    import http.client

    def fetch(host):
        conn = http.client.HTTPConnection(host, 80)
        return conn
"""

NET_GOOD = """
    from . import netrobust

    def fetch(url):
        return netrobust.request(url, "/internal/insert", b"")
"""


def test_net_discipline_flags_raw_urlopen_in_server():
    out = lint(NET_BAD_URLOPEN,
               path="victorialogs_tpu/server/cluster.py")
    assert "net-discipline" in checkers(out)
    assert any("netrobust" in f.message for f in out)


def test_net_discipline_flags_direct_http_client():
    out = lint(NET_BAD_CONN,
               path="victorialogs_tpu/server/vlagent.py")
    assert "net-discipline" in checkers(out)


def test_net_discipline_scoped_to_server_package():
    # the same raw call OUTSIDE server/ is someone else's business
    # (tools, tests, benches talk to servers as plain HTTP clients)
    out = lint(NET_BAD_URLOPEN, path="victorialogs_tpu/cli/main.py")
    assert "net-discipline" not in checkers(out)


def test_net_discipline_skips_netrobust_module():
    out = lint(NET_BAD_CONN,
               path="victorialogs_tpu/server/netrobust.py")
    assert "net-discipline" not in checkers(out)


def test_net_discipline_clean_and_annotated():
    assert "net-discipline" not in checkers(
        lint(NET_GOOD, path="victorialogs_tpu/server/cluster.py"))
    annotated = """
        import urllib.request

        def probe(url):
            # vlint: allow-net-discipline(liveness probe, no policy wanted)
            return urllib.request.urlopen(url, timeout=1)
    """
    assert "net-discipline" not in checkers(
        lint(annotated, path="victorialogs_tpu/server/cluster.py"))


def test_net_discipline_repo_cluster_hops_are_clean():
    """Every cluster hop in server/ (cluster.py, vlagent.py, app.py)
    must ride the policy layer — zero raw-client findings."""
    from tools.vlint.core import SourceFile
    from tools.vlint import netdiscipline
    for rel in ("server/cluster.py", "server/vlagent.py",
                "server/app.py", "server/agent_http.py"):
        path = os.path.join(REPO, "victorialogs_tpu", rel)
        sf = SourceFile.parse(path,
                              display_path=f"victorialogs_tpu/{rel}")
        found = [f for f in netdiscipline.check(sf)
                 if not sf.allowed(f.checker, f.line)]
        assert found == [], [f.render() for f in found]


# ---------------- balance checker (acquire/release pairs) ----------------

def test_balance_pair_registry_inventory():
    """The declared registry covers every budgeted pair in the tree —
    the checker is driven by it, vlsan enforces the runtime_only rows."""
    from tools.vlint.balance import PAIRS
    names = {p.name for p in PAIRS}
    assert names == {"bloom-bank", "sched-lease", "admission",
                     "staging-cache", "events-subscription",
                     "journal-accounting", "net-probe", "insert-spool",
                     "result-cache", "standing-subscription",
                     "ingest-encoder-pool"}
    runtime = {p.name for p in PAIRS if p.runtime_only}
    assert runtime == {"staging-cache", "journal-accounting"}


def test_balance_double_release_sequence():
    """The PR 12 class seeded: a charge released twice drives the
    bank budget negative (= unbounded)."""
    out = lint("""
        from victorialogs_tpu.storage.filterbank import (
            _bank_release, _bank_try_charge)

        def seal(nbytes):
            if not _bank_try_charge(nbytes):
                return False
            _bank_release([nbytes])
            _bank_release([nbytes])
            return True
    """, path="victorialogs_tpu/storage/mod.py")
    assert "balance-double-release" in checkers(out)
    assert any("negative" in f.message for f in out)


def test_balance_double_release_except_plus_finally():
    out = lint("""
        from victorialogs_tpu.storage.filterbank import (
            _bank_release, _bank_try_charge)

        def seal(nbytes, build):
            if not _bank_try_charge(nbytes):
                return None
            try:
                return build()
            except RuntimeError:
                _bank_release([nbytes])
                raise
            finally:
                _bank_release([nbytes])
    """, path="victorialogs_tpu/storage/mod.py")
    assert "balance-double-release" in checkers(out)


def test_balance_release_in_loop_with_acquire_outside():
    out = lint("""
        from victorialogs_tpu.storage.filterbank import (
            _bank_release, _bank_try_charge)

        def seal(parts, nbytes):
            if not _bank_try_charge(nbytes):
                return
            try:
                for p in parts:
                    _bank_release([nbytes])
            finally:
                pass
    """, path="victorialogs_tpu/storage/mod.py")
    assert "balance-double-release" in checkers(out)


def test_balance_unguarded_acquire_flagged_and_finalize_clean():
    bad = lint("""
        from victorialogs_tpu.storage.filterbank import _bank_try_charge

        def charge(n, stage):
            if _bank_try_charge(n):
                stage(n)
    """, path="victorialogs_tpu/storage/mod.py")
    assert "balance-unguarded-acquire" in checkers(bad)
    good = lint("""
        import weakref

        from victorialogs_tpu.storage.filterbank import (
            _bank_release, _bank_try_charge)

        class Bank:
            def __init__(self):
                self._charged = []
                weakref.finalize(self, _bank_release, self._charged)

            def charge(self, n, stage):
                if _bank_try_charge(n):
                    self._charged.append(n)
                    stage(n)
    """, path="victorialogs_tpu/storage/mod.py")
    assert "balance-unguarded-acquire" not in checkers(good)
    guarded = lint("""
        from victorialogs_tpu.storage.filterbank import (
            _bank_release, _bank_try_charge)

        def charge(n, stage):
            if not _bank_try_charge(n):
                return
            try:
                stage(n)
            finally:
                _bank_release([n])
    """, path="victorialogs_tpu/storage/mod.py")
    assert "balance-unguarded-acquire" not in checkers(guarded)


def test_balance_sched_lease_outside_scope():
    bad = lint("""
        def f(scope):
            if scope.try_acquire():
                return True
    """, path="victorialogs_tpu/tpu/mod.py")
    assert "balance-unguarded-acquire" in checkers(bad)
    good = lint("""
        from victorialogs_tpu import sched

        def f(act, submit):
            with sched.device_slots(act) as slots:
                if slots.try_acquire():
                    submit()
    """, path="victorialogs_tpu/tpu/mod.py")
    assert "balance-unguarded-acquire" not in checkers(good)


def test_balance_admit_outside_with():
    bad = lint("""
        def f(pool):
            t = pool.admit("0:0", "/select/logsql/query")
            return t
    """, path="victorialogs_tpu/server/mod.py")
    assert "balance-ctx" in checkers(bad)
    good = lint("""
        def f(pool, run):
            with pool.admit("0:0", "/select/logsql/query"):
                return run()
    """, path="victorialogs_tpu/server/mod.py")
    assert "balance-ctx" not in checkers(good)


def test_balance_subscribe_needs_unsubscribe_in_file():
    bad = lint("""
        from victorialogs_tpu.obs import events

        class Watcher:
            def __init__(self):
                events.subscribe(self._on_event)

            def _on_event(self, ts_ns, event, fields):
                pass
    """, path="victorialogs_tpu/obs/mod.py")
    assert "balance-unguarded-acquire" in checkers(bad)
    good = lint("""
        from victorialogs_tpu.obs import events

        class Watcher:
            def __init__(self):
                events.subscribe(self._on_event)

            def _on_event(self, ts_ns, event, fields):
                pass

            def close(self):
                events.unsubscribe(self._on_event)
    """, path="victorialogs_tpu/obs/mod.py")
    assert "balance-unguarded-acquire" not in checkers(good)


def test_balance_net_probe_must_resolve():
    bad = lint("""
        def send(br, do_net):
            if not br.allow_insert():
                return None
            return do_net()
    """, path="victorialogs_tpu/server/mod.py")
    assert "balance-unguarded-acquire" in checkers(bad)
    good = lint("""
        def send(br, do_net):
            if not br.allow_insert():
                return None
            try:
                out = do_net()
                br.on_success()
                return out
            finally:
                br.abandon_probe()
    """, path="victorialogs_tpu/server/mod.py")
    assert "balance-unguarded-acquire" not in checkers(good)


def test_callable_identity_flagged_and_equality_clean():
    """The PR 8 class seeded: `is` against a bound method never
    matches — every unsubscribe leaked its subscription."""
    bad = lint("""
        class Journal:
            def _on_event(self, ts_ns, event, fields):
                pass

            def remove(self, subs):
                return tuple(s for s in subs
                             if s is not self._on_event)
    """)
    assert "callable-identity" in checkers(bad)
    good = lint("""
        class Journal:
            def _on_event(self, ts_ns, event, fields):
                pass

            def remove(self, subs):
                return tuple(s for s in subs
                             if s != self._on_event)
    """)
    assert "callable-identity" not in checkers(good)
    # `is` on plain data attributes stays legal (sentinel compares)
    sentinel = lint("""
        class C:
            def __init__(self, cb):
                self._cb = cb

            def same(self, other):
                return other is self._cb
    """)
    assert "callable-identity" not in checkers(sentinel)


# ---------------- config/metrics registry drift ----------------

def test_env_registry_flags_raw_read():
    out = lint("""
        import os

        def wire_typed():
            return os.environ.get("VL_WIRE_TYPED", "1") != "0"
    """)
    assert "env-registry" in checkers(out)
    out2 = lint("""
        import os

        def wire_typed():
            return os.getenv("VL_WIRE_TYPED")
    """)
    assert "env-registry" in checkers(out2)
    out3 = lint("""
        import os

        def wire_typed():
            return os.environ["VL_WIRE_TYPED"]
    """)
    assert "env-registry" in checkers(out3)


def test_env_registry_flags_undeclared_name():
    out = lint("""
        from victorialogs_tpu import config

        def f():
            return config.env("VL_TOTALLY_UNDECLARED")
    """)
    assert "env-registry" in checkers(out)
    good = lint("""
        from victorialogs_tpu import config

        def f():
            return config.env_flag("VL_SCHED")
    """)
    assert "env-registry" not in checkers(good)


def test_env_registry_repo_is_rerouted():
    """No raw environ read anywhere in victorialogs_tpu/ outside
    config.py (the CLI envflag mirror carries its annotation)."""
    found = run_paths([os.path.join(REPO, "victorialogs_tpu")],
                      root=REPO)
    raw = [f for f in found if f.checker == "env-registry"]
    assert raw == [], [f.render() for f in raw]


def test_metric_registry_flags_undeclared():
    out = lint("""
        def f(metrics):
            metrics.inc("vl_bogus_thing_total")
    """)
    assert "metric-registry" in checkers(out)
    out2 = lint("""
        from victorialogs_tpu.obs import hist

        H = hist.histogram("vl_bogus_hist_seconds", "nope", (1, 2))
    """)
    assert "metric-registry" in checkers(out2)
    good = lint("""
        def f(metrics):
            metrics.inc("vl_http_errors_total")
    """)
    assert "metric-registry" not in checkers(good)


def test_metric_double_roll_flagged():
    """The PR 4 / PR 6 class seeded: one event accumulated at two
    sites double-counts the series."""
    out = lint("""
        def cancel(metrics):
            metrics.inc("vl_queries_cancelled_total")

        def cancel_http(metrics):
            metrics.inc("vl_queries_cancelled_total")
    """)
    assert "metric-double-roll" in checkers(out)
    # multi-site counters that are DECLARED multi-site stay legal
    good = lint("""
        def a(metrics):
            metrics.inc("vl_http_errors_total")

        def b(metrics):
            metrics.inc("vl_http_errors_total")
    """)
    assert "metric-double-roll" not in checkers(good)


def test_canonical_helper_flags_inline_splitmix():
    """The PR 7/10/12 inline-copy-drift class seeded: a hand-copied
    splitmix64 finalizer outside utils/hashing.py."""
    out = lint("""
        def my_hash(x):
            x = (x + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
            z = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) \\
                & 0xFFFFFFFFFFFFFFFF
            return z
    """)
    assert "canonical-helper" in checkers(out)
    # the canonical module itself is exempt
    clean = lint("""
        def my_hash(x):
            return (x + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    """, path="victorialogs_tpu/utils/hashing.py")
    assert "canonical-helper" not in checkers(clean)


def test_canonical_helper_flags_inline_fastrange():
    out = lint("""
        import numpy as np

        def block_select(h, m):
            return (h * m) >> np.uint64(32)
    """)
    assert "canonical-helper" in checkers(out)
    clean = lint("""
        import numpy as np

        def block_select(h, m):
            return (h * m) >> np.uint64(32)
    """, path="victorialogs_tpu/storage/filterindex/sbbloom.py")
    assert "canonical-helper" not in checkers(clean)


def test_env_table_matches_registry():
    """README env table is byte-identical to the generated one —
    the same gate `make lint` runs."""
    from tools.vlint.__main__ import check_env_table
    assert check_env_table() == 0


def test_config_registry_shape():
    from tools.vlint.registry import config_module
    cfg = config_module()
    for m in cfg.metric_decls().values():
        if m.kind == "counter":
            assert m.name.endswith("_total"), m.name
        if m.kind == "gauge":
            assert not m.name.endswith("_total"), m.name
    for v in cfg.env_vars().values():
        assert v.doc and v.display, v.name
    import pytest
    with pytest.raises(cfg.UndeclaredEnvVar):
        cfg.env("VL_NOT_A_THING")


# ---------------- annotation hygiene ----------------

def test_bare_annotation_is_a_finding():
    out = lint("""
        # vlint: allow-wall-clock
        import time

        def f():
            return time.time()
    """)
    assert "annotation-reason" in checkers(out)
    # AND the bare form never suppressed the underlying finding
    assert "wall-clock" in checkers(out)


def test_empty_reason_is_a_finding():
    out = lint("""
        # vlint: allow-wall-clock( )
        import time

        def f():
            return time.time()
    """)
    assert "annotation-reason" in checkers(out)


def test_reasoned_annotation_is_clean():
    out = lint("""
        import time

        def f():
            # vlint: allow-wall-clock(row timestamps are wall time)
            return time.time()
    """)
    assert "annotation-reason" not in checkers(out)
    assert "wall-clock" not in checkers(out)


# ---------------- parallel runner + cache ----------------

def test_parallel_jobs_match_serial(tmp_path):
    src_ok = "x = 1\n"
    src_bad = ("import time\n\n\ndef f():\n"
               "    return time.time()\n")
    for i in range(4):
        (tmp_path / f"m{i}.py").write_text(src_bad if i % 2 else src_ok)
    serial = run_paths([str(tmp_path)], root=str(tmp_path), jobs=1)
    para = run_paths([str(tmp_path)], root=str(tmp_path), jobs=2)
    assert [f.render() for f in serial] == [f.render() for f in para]
    assert any(f.checker == "wall-clock" for f in serial)


def test_cache_roundtrip_and_invalidation(tmp_path):
    mod = tmp_path / "m.py"
    mod.write_text("import time\n\n\ndef f():\n    return time.time()\n")
    cache = str(tmp_path / "cache.json")
    first = run_paths([str(mod)], root=str(tmp_path), cache_path=cache)
    assert any(f.checker == "wall-clock" for f in first)
    assert os.path.exists(cache)
    # warm: identical findings straight from the cache
    warm = run_paths([str(mod)], root=str(tmp_path), cache_path=cache)
    assert [f.render() for f in first] == [f.render() for f in warm]
    # content change invalidates just that file
    mod.write_text("x = 1\n")
    third = run_paths([str(mod)], root=str(tmp_path), cache_path=cache)
    assert third == []


# ---------------- --explain CLI ----------------

def test_explain_cli(tmp_path, capsys):
    from tools.vlint.__main__ import main
    mod = tmp_path / "m.py"
    mod.write_text("import time\n\n\ndef f():\n    return time.time()\n")
    rc = main(["--json", "--no-baseline", "--no-cache", str(mod)])
    out = capsys.readouterr().out
    import json as _json
    finding = _json.loads(out)["findings"][0]
    assert rc == 1
    rc = main(["--explain", finding["fingerprint"], str(mod)])
    text = capsys.readouterr().out
    assert rc == 0
    assert "wall-clock" in text
    assert "allow-wall-clock(" in text        # the annotation recipe
    assert "tools/vlint/hygiene.py" in text   # the checker doc source
    # unknown fingerprint: clean error, exit 1
    rc = main(["--explain", "ffffffffffffffff", str(mod)])
    assert rc == 1


def test_baseline_stays_empty():
    """Fix-or-annotate discipline: the committed baseline has zero
    entries and the repo is clean against it."""
    baseline = load_baseline()
    assert baseline == {}


# ---------------- vlsan: end-of-test invariant sanitizer ----------------

def test_vlsan_clean_on_idle_process():
    from tools.vlint import vlsan
    san = vlsan.Sanitizer()
    san.begin_test()
    assert san.sweep() == []


def test_vlsan_detects_subscriber_leak():
    from tools.vlint import vlsan
    from victorialogs_tpu.obs import events
    san = vlsan.Sanitizer()
    san.begin_test()

    def cb(ts_ns, event, fields):
        pass

    events.subscribe(cb)
    try:
        problems = san.sweep()
        assert any("subscriber" in p for p in problems), problems
    finally:
        events.unsubscribe(cb)
    assert san.sweep() == []


def test_vlsan_detects_bank_double_release():
    """The historical negative-budget class, reproduced live: one
    release too many drives _bank_bytes negative and the sweep says
    so."""
    from tools.vlint import vlsan
    from victorialogs_tpu.storage import filterbank as fb
    san = vlsan.Sanitizer()
    san.begin_test()
    fb._bank_release([4096])         # release with no matching charge
    try:
        problems = san.sweep()
        assert any("bank" in p for p in problems), problems
    finally:
        assert fb._bank_try_charge(4096)   # restore the budget
    assert san.sweep() == []


def test_vlsan_detects_journal_imbalance():
    from tools.vlint import vlsan
    from victorialogs_tpu.obs import journal

    class _Sink:
        def must_add_rows(self, lr):
            pass

    san = vlsan.Sanitizer()
    san.begin_test()
    w = journal.JournalWriter(_Sink(), app="vlsan-test")
    try:
        ok, _ = w.check_balanced()
        assert ok
        w.accepted += 3                  # forge a torn counter
        problems = san.sweep()
        assert any("journal" in p for p in problems), problems
        w.accepted -= 3
    finally:
        w.close()
    assert san.sweep() == []


def test_vlsan_detects_sched_imbalance():
    from tools.vlint import vlsan
    from victorialogs_tpu import sched
    san = vlsan.Sanitizer()
    san.begin_test()
    scope = sched.device_slots(None, tenant="0:0")
    scope.__enter__()
    assert scope.try_acquire()
    try:
        problems = san.sweep()
        assert any("lease" in p for p in problems), problems
    finally:
        scope.__exit__(None, None, None)
    assert san.sweep() == []


def test_vlsan_kill_switch(monkeypatch):
    from tools.vlint import vlsan
    monkeypatch.setenv("VLSAN", "0")
    assert not vlsan.enabled()
    monkeypatch.delenv("VLSAN")
    assert vlsan.enabled()


# ---------------- post-review regressions ----------------

def test_journal_balance_survives_overflow_drops():
    """Queue-bound drops were never accepted — the invariant must hold
    through overflow, not just post-accept drops."""
    from victorialogs_tpu.obs import journal

    class _Sink:
        def must_add_rows(self, lr):
            pass

    w = journal.JournalWriter(_Sink(), max_queue=2, app="vlsan-test")
    try:
        for _ in range(5):
            w._on_event(1, "e", {})
        ok, detail = w.check_balanced()
        assert ok, detail
        assert w.stats()["dropped"] == 3     # public total unchanged
    finally:
        w.close()
    ok, detail = w.check_balanced()
    assert ok, detail


def test_scoped_run_preserves_cache(tmp_path):
    """A single-file run must not evict the rest of the repo's cache
    entries (only vanished files are pruned)."""
    for name in ("a.py", "b.py"):
        (tmp_path / name).write_text("x = 1\n")
    cache = str(tmp_path / "c.json")
    run_paths([str(tmp_path)], root=str(tmp_path), cache_path=cache)
    import json as _json
    with open(cache) as f:
        assert len(_json.load(f)["files"]) == 2
    run_paths([str(tmp_path / "a.py")], root=str(tmp_path),
              cache_path=cache)
    with open(cache) as f:
        kept = _json.load(f)["files"]
    assert set(kept) == {"a.py", "b.py"}
    (tmp_path / "b.py").unlink()
    run_paths([str(tmp_path / "a.py")], root=str(tmp_path),
              cache_path=cache)
    with open(cache) as f:
        assert set(_json.load(f)["files"]) == {"a.py"}


def test_explain_resolves_global_pass_fingerprint(tmp_path, capsys):
    """metric-double-roll / lock-order-cycle findings come from the
    cross-file passes — --explain must find their fingerprints too."""
    from tools.vlint.__main__ import main
    (tmp_path / "m.py").write_text(
        'def a(m):\n    m.inc("vl_queries_cancelled_total")\n\n\n'
        'def b(m):\n    m.inc("vl_queries_cancelled_total")\n')
    rc = main(["--json", "--no-baseline", "--no-cache", str(tmp_path)])
    import json as _json
    fnd = _json.loads(capsys.readouterr().out)["findings"]
    dbl = [f for f in fnd if f["checker"] == "metric-double-roll"]
    assert rc == 1 and dbl
    rc = main(["--explain", dbl[0]["fingerprint"], str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "metric-double-roll" in out and "registry.py" in out


def test_checker_module_map_covers_all_ids():
    """--explain cites the right checker source for every id the
    checkers can emit (the hygiene ids were once mis-keyed)."""
    from tools.vlint.core import checker_module_for
    for cid, mod in (("nondaemon-thread", "hygiene"),
                     ("broad-except", "hygiene"),
                     ("lock-order-cycle", "locks"),
                     ("jax-host-sync", "hotpath"),
                     ("per-row-emit", "hotpath"),
                     ("balance-double-release", "balance"),
                     ("callable-identity", "balance"),
                     ("metric-double-roll", "registry"),
                     ("env-registry", "registry"),
                     ("annotation-reason", "core"),
                     ("lock-blocking-deep", "effects"),
                     ("rpc-under-lock", "effects"),
                     ("hotpath-sync-deep", "effects"),
                     ("thread-lifecycle", "effects"),
                     ("wire-taint", "effects")):
        assert checker_module_for(cid) == mod, cid


# ---------------- v3 interprocedural graph passes ----------------
#
# The whole-program call graph (tools/vlint/callgraph.py) + effect
# propagation (tools/vlint/effects.py).  The first two tests pin the
# ISSUE acceptance fixtures: a >=3-call-deep transitive
# blocking-under-lock chain and a forged wire offset into frombuffer.

def test_lock_blocking_deep_three_deep_chain():
    """flush holds the lock and calls _compact -> _rewrite -> _settle
    -> time.sleep: blocking reachable at depth 3, crossing from the
    class into module helpers (which the per-file locks checker cannot
    see through)."""
    f = lint("""
        import threading
        import time


        def _settle():
            time.sleep(0.5)


        def _rewrite():
            _settle()


        class Store:
            def __init__(self):
                self._mu = threading.Lock()

            def flush(self):
                with self._mu:
                    self._compact()

            def _compact(self):
                _rewrite()
    """)
    deep = [x for x in f if x.checker == "lock-blocking-deep"]
    assert len(deep) == 1
    assert deep[0].symbol == "Store.flush"
    assert "Store._mu" in deep[0].message
    assert "depth 3" in deep[0].message
    assert "_rewrite -> _settle" in deep[0].message   # witness chain


def test_lock_blocking_deep_annotated():
    f = lint("""
        import threading
        import time


        def _settle():
            time.sleep(0.5)


        def _rewrite():
            _settle()


        class Store:
            def __init__(self):
                self._mu = threading.Lock()

            def flush(self):
                with self._mu:
                    # vlint: allow-lock-blocking-deep(bounded 0.5s settle)
                    self._compact()

            def _compact(self):
                _rewrite()
    """)
    assert not [x for x in f if x.checker == "lock-blocking-deep"]


def test_lock_blocking_deep_leaves_intraclass_to_locks():
    """A pure self.m() chain stays the per-file checker's finding —
    the graph pass must not double-report it."""
    f = lint("""
        import threading
        import time


        class Store:
            def __init__(self):
                self._mu = threading.Lock()

            def flush(self):
                with self._mu:
                    self._compact()

            def _compact(self):
                time.sleep(0.5)
    """)
    assert [x.checker for x in f] == ["lock-blocking-call"]


def test_rpc_under_lease_scope():
    """The ISSUE fixture: a scheduler dispatch lease held across a
    cluster RPC through a helper — a slow/partitioned peer now
    occupies a device slot for the full RPC deadline."""
    f = lint("""
        from . import netrobust
        from ..sched.scheduler import device_slots


        def _push(payload):
            return netrobust.request("POST", "http://n1/x", payload)


        def fan_out(payload):
            with device_slots(1):
                _push(payload)
    """, path="victorialogs_tpu/server/mod.py")
    rpc = [x for x in f if x.checker == "rpc-under-lock"]
    assert len(rpc) == 1
    assert rpc[0].symbol == "fan_out"
    assert "lease:device_slots" in rpc[0].message


def test_rpc_under_lock_direct_and_unheld_clean():
    held = lint("""
        import threading

        from . import netrobust


        class Agg:
            def __init__(self):
                self._mu = threading.Lock()

            def poll(self):
                with self._mu:
                    return netrobust.request("GET", "http://n1/x", None)
    """, path="victorialogs_tpu/server/mod.py")
    assert [x.checker for x in held] == ["rpc-under-lock"]
    free = lint("""
        from . import netrobust


        def _push(payload):
            return netrobust.request("POST", "http://n1/x", payload)


        def fan_out(payload):
            _push(payload)
    """, path="victorialogs_tpu/server/mod.py")
    assert not [x for x in free if x.checker == "rpc-under-lock"]


def test_thread_lifecycle_orphan_spawn():
    f = lint("""
        import threading


        def kick(fn):
            t = threading.Thread(target=fn)
            t.start()
    """)
    orphan = [x for x in f if x.checker == "thread-lifecycle"]
    assert len(orphan) == 1 and orphan[0].symbol == "kick"
    # joined / handed-off spawns are clean
    for tail in ("    t.join()\n", "    return t\n"):
        f = lint("import threading\n\n\ndef kick(fn):\n"
                 "    t = threading.Thread(target=fn)\n"
                 "    t.start()\n" + tail)
        assert not [x for x in f if x.checker == "thread-lifecycle"]


def test_thread_lifecycle_stored_thread_needs_owner_close():
    src = """
        import threading


        class Pump:
            def __init__(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def _run(self):
                pass
    """
    f = lint(src)
    missing = [x for x in f if x.checker == "thread-lifecycle"]
    assert len(missing) == 1 and "self._t" in missing[0].message
    f = lint(textwrap.dedent(src) +
             "\n    def close(self):\n        self._t.join()\n")
    assert not [x for x in f if x.checker == "thread-lifecycle"]


def test_thread_lifecycle_shutdown_order():
    """The declared VLServer teardown order (PR 8): usage poller, then
    journal, then super().close() — any inversion is two findings here
    (each adjacent pair violated)."""
    f = lint("""
        class VLServer:
            def close(self):
                super().close()
                self.journal.close()
                self.clusterstats.close()
    """, path="victorialogs_tpu/server/app.py")
    order = [x for x in f if x.checker == "thread-lifecycle"]
    assert len(order) == 2
    assert all("shutdown order" in x.message for x in order)


def test_wire_taint_forged_offset_caught():
    """The ISSUE fixture: a wire-decoded offset flows into frombuffer
    with no dominating bounds guard — the PR 9/12 forged-frame class."""
    f = lint("""
        import struct

        import numpy as np


        def parse(buf):
            (off,) = struct.unpack_from("<I", buf, 0)
            return np.frombuffer(buf, np.uint8, 16, off)
    """, path="victorialogs_tpu/server/wire.py")
    taint = [x for x in f if x.checker == "wire-taint"]
    assert len(taint) == 1
    assert "off" in taint[0].message and "guard" in taint[0].message


def test_wire_taint_guarded_and_out_of_scope_clean():
    guarded = """
        import struct

        import numpy as np


        def parse(buf):
            (off,) = struct.unpack_from("<I", buf, 0)
            if off > len(buf) - 16:
                raise ValueError("forged offset")
            return np.frombuffer(buf, np.uint8, 16, off)
    """
    f = lint(guarded, path="victorialogs_tpu/server/wire.py")
    assert not [x for x in f if x.checker == "wire-taint"]
    # same unguarded flow OUTSIDE the wire-decode scope: not wire data
    f = lint("""
        import struct

        import numpy as np


        def parse(buf):
            (off,) = struct.unpack_from("<I", buf, 0)
            return np.frombuffer(buf, np.uint8, 16, off)
    """, path="victorialogs_tpu/tpu/mod.py")
    assert not [x for x in f if x.checker == "wire-taint"]


_GRAPH_A = ("import threading\n\nimport b\n\n\nclass S:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n\n"
            "    def flush(self):\n        with self._mu:\n"
            "            b.rewrite()\n")
_GRAPH_B = ("import time\n\n\ndef settle():\n    time.sleep(1.0)\n\n\n"
            "def rewrite():\n    settle()\n")


def test_graph_pass_parallel_matches_serial(tmp_path):
    """The graph pass runs once over merged summaries — worker count
    must not change its findings (cross-FILE chain on purpose)."""
    (tmp_path / "a.py").write_text(_GRAPH_A)
    (tmp_path / "b.py").write_text(_GRAPH_B)
    serial = run_paths([str(tmp_path)], root=str(tmp_path), jobs=1)
    para = run_paths([str(tmp_path)], root=str(tmp_path), jobs=2)
    assert [f.render() for f in serial] == [f.render() for f in para]
    assert any(f.checker == "lock-blocking-deep" for f in serial)


def test_graph_cache_unrelated_change_and_path_change(tmp_path):
    """Graph-pass cache key is the hash of ALL merged summaries: an
    edit to an unrelated file (same summary) reuses the cached graph
    findings; an edit to a function ON a reported path re-runs the
    graph and drops the finding."""
    (tmp_path / "a.py").write_text(_GRAPH_A)
    (tmp_path / "b.py").write_text(_GRAPH_B)
    (tmp_path / "c.py").write_text("x = 1\n")
    cache = str(tmp_path / "cache.json")
    first = run_paths([str(tmp_path)], root=str(tmp_path),
                      cache_path=cache)
    assert any(f.checker == "lock-blocking-deep" for f in first)
    import json as _json
    with open(cache) as fh:
        got = _json.load(fh)
    assert got.get("graph", {}).get("findings")
    # unrelated edit: summaries unchanged -> warm graph equivalence
    (tmp_path / "c.py").write_text("x = 2\n")
    warm = run_paths([str(tmp_path)], root=str(tmp_path),
                     cache_path=cache)
    assert [f.render() for f in first] == [f.render() for f in warm]
    # fix the blocking primitive: b.py is on the reported path
    (tmp_path / "b.py").write_text(
        "def settle():\n    return 1\n\n\ndef rewrite():\n"
        "    settle()\n")
    third = run_paths([str(tmp_path)], root=str(tmp_path),
                      cache_path=cache)
    assert not [f for f in third if f.checker == "lock-blocking-deep"]


def test_explain_resolves_graph_pass_fingerprint(tmp_path, capsys,
                                                 monkeypatch):
    """--explain must find fingerprints minted by the graph passes and
    cite tools/vlint/effects.py as the checker source."""
    from tools.vlint.__main__ import main
    (tmp_path / "a.py").write_text(_GRAPH_A)
    (tmp_path / "b.py").write_text(_GRAPH_B)
    monkeypatch.chdir(tmp_path)     # main() resolves modules from cwd
    rc = main(["--json", "--no-baseline", "--no-cache", "."])
    import json as _json
    fnd = _json.loads(capsys.readouterr().out)["findings"]
    deep = [f for f in fnd if f["checker"] == "lock-blocking-deep"]
    assert rc == 1 and deep
    rc = main(["--explain", deep[0]["fingerprint"], "."])
    out = capsys.readouterr().out
    assert rc == 0
    assert "lock-blocking-deep" in out
    assert "allow-lock-blocking-deep(" in out
    assert "tools/vlint/effects.py" in out


def test_balance_release_through_same_file_helper_clean():
    """The v3 see-through rule in balance.py: a finally that drains
    the pair via a same-file helper counts as a guaranteed release."""
    f = lint("""
        from victorialogs_tpu.storage.filterbank import (
            _bank_release, _bank_try_charge)


        def _drop(n):
            _bank_release([n])


        def load(n):
            if not _bank_try_charge(n):
                return None
            try:
                return object()
            finally:
                _drop(n)
    """, path="victorialogs_tpu/storage/mod.py")
    assert not [x for x in f if x.checker == "balance-unguarded-acquire"]
