"""JAX hot-path checkers (scoped to tpu/ and engine/ sources).

One stray host sync inside a scan re-introduces a full dispatch round
trip per block and stalls the in-flight window (PERF.md).  These checkers
flag the statically detectable cases:

- jax-host-sync: float()/int()/bool()/.item()/.tolist()/np.asarray()
  on a value produced by jnp.*, a jit-wrapped callable, or a kernels
  module, and implicit truthiness (`if x:`) on such values.  Deliberate
  result readbacks carry `# vlint: allow-jax-host-sync(<why>)`.
- jax-jit-closure: a jit-compiled function reading `self.*` or a
  module-level mutable literal — the closure is baked in at trace time
  and silently goes stale when the state mutates.
- jax-static-arg: static_argnums/static_argnames that are not
  int/str literals (or tuples thereof) — unstable or unhashable
  statics retrigger compilation per call (the EWMA-poisoning
  compile-timing class of bug from the cost-gate hardening).
- jax-eager-submit: an eager `jnp.<scalar type>(...)`, `jnp.asarray(...)`
  or `jnp.array(...)` in a submit-path function of tpu/fused.py or
  tpu/pipeline.py (SUBMIT_PATH).  Outside a jitted body each is a
  host-to-device put plus an eagerly dispatched program on every
  dispatch (`jnp.int32(layout.nrows)` was the largest single item of
  the submit path, PERF.md PR 31); a small host operand rides the
  dispatch's operand block (_Planner.host_words / host_bytes) instead.
  A deliberate site carries `# vlint: allow-jax-eager-submit(<why>)`.
- per-row-emit (server/ and engine/ scope): json.dumps calls or
  dict-literal .append()s inside a loop — the per-row emit shape the
  columnar path (engine/emit.ndjson_block + BlockResult.emit_columns)
  replaced; cold paths carry `# vlint: allow-per-row-emit(<why>)`.
"""

from __future__ import annotations

import ast
import re

from .core import Finding, SourceFile
from .locks import _dotted, _module_jit_names

# obs/explain.py rides the same scope: the pricing pass runs at plan
# time on EVERY query (and explain=1 must stay zero-dispatch), so a
# hidden host sync or jit-closure there is a query-path regression.
# storage/filterindex/ too: its maplet/xor probes sit directly on the
# per-part prune path of every query over sealed parts.
# storage/block_build.py: the columnar values-encode/bloom builder is
# the ingest flush hot path — per-row Python work there is exactly the
# regression the sharded build exists to remove.
SCOPE_RE = re.compile(
    r"(^|/)(tpu|engine)(/|$)|(^|/)obs/explain\.py$"
    r"|(^|/)storage/filterindex(/|$)"
    r"|(^|/)storage/block_build\.py$")
# the emit-shape rule runs where response/row materialization lives
EMIT_SCOPE_RE = re.compile(r"(^|/)(server|engine)(/|$)")

# module names whose call results live on device in this repo
_DEVICE_MODULE_HINTS = ("kernels", "fused", "stats_device", "sort_device")

_SYNC_CASTS = {"float", "int", "bool"}

# The submit path: what runs on the query's thread between a unit's
# lease and its jitted call.  By name, a file at a time: the planner's
# methods and the three submit entries with what they call
# (tpu/fused.py), the unit stream and the submit/refill half of the
# window (tpu/pipeline.py).  Jitted bodies (_eval_tree_node,
# _fused_local, ...) are not on it: a jnp call there is traced once.
SUBMIT_PATH = {
    "tpu/fused.py": re.compile(
        r"^(_Planner\.\w+|fused_\w+_submit"
        r"|_stage_cand_mask|_launch)$"),
    "tpu/pipeline.py": re.compile(
        r"^(_submit\w*|_host_members|_count_pack|_get_pack"
        r"|_unit_stream(\.\w+)*|scan_device_stream(\.\w+)*)$"),
}
_EAGER_CALLS = {"asarray", "array", "bool_", "int8", "int16", "int32",
                "int64", "uint8", "uint16", "uint32", "uint64", "float16",
                "bfloat16", "float32", "float64"}


def _submit_path_re(path: str):
    for suffix, rx in SUBMIT_PATH.items():
        if path.endswith("/" + suffix) or path == suffix:
            return rx
    return None


def _check_eager_submit(fnode, sf, symbol, findings) -> None:
    """Eager jnp constructors in one submit-path function (nested defs
    are visited under their own symbol)."""
    stack = list(fnode.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        mod, _, attr = d.rpartition(".")
        if mod in ("jnp", "jax.numpy") and attr in _EAGER_CALLS:
            findings.append(Finding(
                "jax-eager-submit", sf.path, node.lineno, symbol,
                f"eager {d}() on the submit path: a device put and a "
                f"dispatched program on every call; ship host scalars "
                f"in the operand block (_Planner.host_words)"))


def _device_module_aliases(tree: ast.Module) -> set:
    """Local aliases of the device-kernel modules, e.g.
    `from . import kernels as K` -> {'K'}."""
    out: set = set()
    for node in tree.body:
        if isinstance(node, ast.ImportFrom):
            for a in node.names:
                name = a.asname or a.name
                if any(h in a.name for h in _DEVICE_MODULE_HINTS):
                    out.add(name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if any(h in a.name.split(".")[-1]
                       for h in _DEVICE_MODULE_HINTS):
                    out.add(a.asname or a.name.split(".")[0])
    return out


def _is_jit_call(node) -> bool:
    if not isinstance(node, ast.Call):
        return False
    d = _dotted(node.func)
    if d in ("jax.jit", "jit"):
        return True
    if d in ("partial", "functools.partial") and node.args:
        return _dotted(node.args[0]) in ("jax.jit", "jit")
    return False


class _FuncScope:
    """One-pass per-function tracking of device-valued names."""

    def __init__(self, sf, symbol, jit_names, dev_modules, findings):
        self.sf = sf
        self.symbol = symbol
        self.jit_names = set(jit_names)   # callables returning device
        self.dev_modules = dev_modules
        self.device_vars: set = set()
        self.findings = findings

    def _produces_device(self, node) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.device_vars
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            root = d.split(".")[0] if d else ""
            if root in ("jnp",) or d.startswith("jax.numpy."):
                return True
            if d in self.jit_names:
                return True
            if root in self.dev_modules and "." in d:
                return True
            return False
        if isinstance(node, ast.Subscript) or isinstance(node, ast.BinOp):
            inner = node.value if isinstance(node, ast.Subscript) \
                else node.left
            return self._produces_device(inner)
        return False

    def _flag(self, line: int, what: str) -> None:
        self.findings.append(Finding(
            "jax-host-sync", self.sf.path, line, self.symbol,
            f"implicit host sync: {what}"))

    def run(self, body: list) -> None:
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, node) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested defs get their own scope via check()
        if isinstance(node, ast.Assign):
            self._expr(node.value)
            if self._produces_device(node.value) or (
                    isinstance(node.value, ast.Call)
                    and _is_jit_call(node.value)):
                names = []
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.append(t.id)
                    elif isinstance(t, (ast.Tuple, ast.List)):
                        names.extend(e.id for e in t.elts
                                     if isinstance(e, ast.Name))
                if isinstance(node.value, ast.Call) and \
                        _is_jit_call(node.value):
                    self.jit_names.update(names)
                else:
                    self.device_vars.update(names)
            else:
                # reassignment to a host value clears the taint
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.device_vars.discard(t.id)
            return
        if isinstance(node, (ast.If, ast.While)):
            self._test(node.test)
            self._expr(node.test)
            for sub in node.body + node.orelse:
                self._stmt(sub)
            return
        if isinstance(node, (ast.For,)):
            self._expr(node.iter)
            for sub in node.body + node.orelse:
                self._stmt(sub)
            return
        if isinstance(node, (ast.With, ast.Try)):
            for sub in ast.iter_child_nodes(node):
                if isinstance(sub, ast.stmt):
                    self._stmt(sub)
                elif isinstance(sub, ast.withitem):
                    self._expr(sub.context_expr)
                elif isinstance(sub, ast.ExceptHandler):
                    for s2 in sub.body:
                        self._stmt(s2)
            return
        if isinstance(node, ast.Assert):
            self._test(node.test)
            self._expr(node.test)
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.stmt):
                self._stmt(child)
            elif isinstance(child, ast.expr):
                self._expr(child)

    def _test(self, test) -> None:
        names = [test] if isinstance(test, ast.Name) else (
            [v for v in test.values if isinstance(v, ast.Name)]
            if isinstance(test, ast.BoolOp) else [])
        for n in names:
            if n.id in self.device_vars:
                self._flag(n.lineno,
                           f"truth test on device value '{n.id}'")

    def _expr(self, node) -> None:
        if node is None:
            return
        for call in [n for n in ast.walk(node)
                     if isinstance(n, ast.Call)]:
            d = _dotted(call.func)
            if d in _SYNC_CASTS and len(call.args) == 1 and \
                    self._produces_device(call.args[0]):
                self._flag(call.lineno,
                           f"{d}() on device value")
            elif d in ("np.asarray", "np.array", "numpy.asarray",
                       "numpy.array") and call.args and \
                    self._produces_device(call.args[0]):
                self._flag(call.lineno, f"{d}() on device value")
            elif isinstance(call.func, ast.Attribute) and \
                    call.func.attr in ("item", "tolist") and \
                    self._produces_device(call.func.value):
                self._flag(call.lineno,
                           f".{call.func.attr}() on device value")


def _jit_decorated(node) -> bool:
    return any(_is_jit_call(d) or _dotted(d) in ("jax.jit", "jit")
               for d in node.decorator_list)


def _check_static_args(call: ast.Call, sf, symbol, findings) -> None:
    for kw in call.keywords:
        if kw.arg not in ("static_argnums", "static_argnames"):
            continue
        ok_types = (int,) if kw.arg == "static_argnums" else (str,)
        v = kw.value
        elts = v.elts if isinstance(v, ast.Tuple) else [v]
        good = all(isinstance(e, ast.Constant)
                   and isinstance(e.value, ok_types) for e in elts)
        if not good:
            findings.append(Finding(
                "jax-static-arg", sf.path, kw.value.lineno, symbol,
                f"{kw.arg} is not a literal — unstable statics "
                f"retrigger compilation per call"))


def _check_jit_closure(fnode, sf, symbol, module_mutables,
                       findings) -> None:
    params = {a.arg for a in fnode.args.args + fnode.args.kwonlyargs
              + fnode.args.posonlyargs}
    if fnode.args.vararg:
        params.add(fnode.args.vararg.arg)
    if fnode.args.kwarg:
        params.add(fnode.args.kwarg.arg)
    assigned = {n.id for n in ast.walk(fnode)
                if isinstance(n, ast.Name)
                and isinstance(n.ctx, (ast.Store,))}
    for node in ast.walk(fnode):
        attr_self = (isinstance(node, ast.Attribute)
                     and isinstance(node.value, ast.Name)
                     and node.value.id == "self")
        if attr_self:
            findings.append(Finding(
                "jax-jit-closure", sf.path, node.lineno, symbol,
                f"jit-compiled {fnode.name}() closes over mutable "
                f"self.{node.attr} — baked in at trace time"))
        elif isinstance(node, ast.Name) and \
                isinstance(node.ctx, ast.Load) and \
                node.id in module_mutables and \
                node.id not in params and node.id not in assigned:
            findings.append(Finding(
                "jax-jit-closure", sf.path, node.lineno, symbol,
                f"jit-compiled {fnode.name}() closes over module-level "
                f"mutable '{node.id}'"))


def _check_per_row_emit(sf: SourceFile, findings: list) -> None:
    """Flag the per-row emit shape inside loops: a json.dumps call per
    iteration, or a dict literal/comprehension materialized per
    iteration via .append()/.extend() — the exact pattern the columnar
    emit path (engine/emit.ndjson_block over BlockResult.emit_columns)
    replaced on the query hot path.  One finding per site, attributed
    to the innermost loop."""
    seen: set = set()

    def flag(node, msg: str, symbol: str) -> None:
        key = (node.lineno, node.col_offset, msg)
        if key in seen:
            return
        seen.add(key)
        findings.append(Finding("per-row-emit", sf.path, node.lineno,
                                symbol, msg))

    def scan_loop(loop, symbol: str) -> None:
        # a dict literal/comprehension AS the element of a comprehension
        # is a dict per iteration with no .append() call to catch below
        if isinstance(loop, (ast.ListComp, ast.SetComp,
                             ast.GeneratorExp)):
            for x in ast.walk(loop.elt):
                if isinstance(x, (ast.Dict, ast.DictComp)):
                    flag(x, "per-row dict materialization inside a "
                            "comprehension — build columns instead "
                            "(BlockResult.emit_columns)", symbol)
                    break
        for sub in ast.walk(loop):
            if not isinstance(sub, ast.Call):
                continue
            d = _dotted(sub.func)
            if d in ("json.dumps", "dumps"):
                flag(sub, "per-row json.dumps inside a loop — serialize "
                          "columnar (engine/emit.ndjson_block)", symbol)
            elif ((isinstance(sub.func, ast.Attribute)
                   and sub.func.attr in ("append", "extend"))
                  or (isinstance(sub.func, ast.Name)      # append = l.append
                      and sub.func.id in ("append", "extend"))) \
                    and sub.args \
                    and any(isinstance(x, (ast.Dict, ast.DictComp))
                            for x in ast.walk(sub.args[0])):
                flag(sub, "per-row dict materialization inside a loop — "
                          "build columns instead "
                          "(BlockResult.emit_columns)", symbol)

    def visit(node, symbol: str) -> None:
        for child in ast.iter_child_nodes(node):
            sym = symbol
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                sym = f"{symbol}.{child.name}" if symbol else child.name
            if isinstance(child, (ast.For, ast.While, ast.ListComp,
                                  ast.SetComp, ast.GeneratorExp)):
                scan_loop(child, sym)
            visit(child, sym)

    visit(sf.tree, "")


def check(sf: SourceFile) -> list[Finding]:
    findings: list[Finding] = []
    if EMIT_SCOPE_RE.search(sf.path):
        _check_per_row_emit(sf, findings)
    if not SCOPE_RE.search(sf.path):
        return findings
    tree = sf.tree
    jit_names = _module_jit_names(tree)
    dev_modules = _device_module_aliases(tree)
    submit_re = _submit_path_re(sf.path)
    # module-level mutable literals (jit closures over them go stale)
    module_mutables: set = set()
    for node in tree.body:
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, (ast.List, ast.Dict, ast.Set)):
            for t in node.targets:
                if isinstance(t, ast.Name) and not t.id.isupper():
                    module_mutables.add(t.id)

    def visit_funcs(node, symbol: str) -> None:
        for child in ast.iter_child_nodes(node):
            sym = symbol
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                sym = f"{symbol}.{child.name}" if symbol else child.name
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope = _FuncScope(sf, sym, jit_names, dev_modules,
                                   findings)
                scope.run(child.body)
                if submit_re is not None and submit_re.match(sym):
                    _check_eager_submit(child, sf, sym, findings)
                if _jit_decorated(child):
                    _check_jit_closure(child, sf, sym, module_mutables,
                                       findings)
                for d in child.decorator_list:
                    if isinstance(d, ast.Call):
                        _check_static_args(d, sf, sym, findings)
            visit_funcs(child, sym)

    visit_funcs(tree, "")
    # jax.jit(...) call sites anywhere (assignments, lambdas)
    for node in ast.walk(tree):
        if _is_jit_call(node):
            _check_static_args(node, sf, "", findings)
    return findings
