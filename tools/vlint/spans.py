"""Span-discipline checker (obs/tracing.py API hygiene).

The tracing API is context-manager-only: the with-block is what
guarantees every span closes on every exit path (QueryCancelled /
QueryTimeoutError unwinds included), which the no-open-spans trace
tests pin.  Two ways to break that discipline, both flagged:

- span-discipline: direct ``Span(...)`` construction anywhere outside
  victorialogs_tpu/obs/tracing.py — spans must come from
  ``tracing.make_root()`` (closed by ``tracing.activate``) or
  ``parent.span(...)`` (closed by its with-block);
- span-discipline: a ``.span(...)`` / ``start_trace(...)`` call that is
  not the context expression of a ``with`` item (assigned, passed,
  returned, or bare) — such a span would never close;
- span-discipline: a detached span made and dropped: a bare
  ``make_root(...)`` / ``request_root(...)`` / ``make_child(...)``
  statement.  Those hand back a span that only ``tracing.activate``
  closes (the request's root in server/app.py, the handler's ``query``
  span beneath it in server/vlselect.py); a result nobody keeps can
  never be activated, and under ``make_child`` it would hang open in
  its parent's tree for good.

Deliberate sites carry ``# vlint: allow-span-discipline(<why>)``, same
annotation + baseline discipline as every other checker.
"""

from __future__ import annotations

import ast

from .core import Finding, SourceFile, check_ctx_discipline

# the module that owns the Span class plays by its own rules
_TRACING_MODULE = "obs/tracing.py"

_CTORS = {
    "Span": "direct Span(...) construction — use tracing.make_root() "
            "or the context-manager parent.span(...) API",
}

# calls that OPEN a span and therefore must sit in a with-item
_OPENERS = {
    name: "{name}(...) outside a with-statement — the span would "
          "never close; open spans via `with parent.{name}(...) as "
          "sp:`"
    for name in ("span", "start_trace")
}


# calls that hand back a DETACHED span, closed only by tracing.activate
_MAKERS = ("make_root", "request_root", "make_child")


def _dropped_makers(sf: SourceFile) -> list[Finding]:
    out = []
    for node in ast.walk(sf.tree):
        if not (isinstance(node, ast.Expr)
                and isinstance(node.value, ast.Call)):
            continue
        func = node.value.func
        name = func.attr if isinstance(func, ast.Attribute) else \
            getattr(func, "id", "")
        if name in _MAKERS:
            out.append(Finding(
                "span-discipline", sf.path, node.lineno, "",
                f"{name}(...) made and dropped — only "
                f"`with tracing.activate(span)` closes a detached span; "
                f"keep the result and activate it"))
    return out


def check(sf: SourceFile) -> list[Finding]:
    if sf.path.replace("\\", "/").endswith(_TRACING_MODULE):
        return []
    return check_ctx_discipline(sf, "span-discipline", _CTORS,
                                _OPENERS) + _dropped_makers(sf)
