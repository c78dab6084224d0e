"""span-discipline: the query hot path must stay traceable.

Two invariants the profiling plane (obs/qprofile.py, ``?profile=true``)
depends on — a span-less stretch of the execute path is a blind spot in
every profile and every exported trace:

* the executor entry points — any function named exactly ``execute`` or
  starting with ``_batch_`` in exec/executor.py, cluster/dist.py, or
  cluster/client.py — must open at least one tracing span
  (``tracing.start_span(...)``), directly or via a ``with`` block;
* in a client class that owns the span-injecting transport layer (it
  defines ``_do_full``, which forwards the active trace context as HTTP
  headers), public methods must not place transport calls themselves
  (``urlopen``, ``HTTPConnection``/``HTTPSConnection``,
  ``self._pool.request``): a direct call skips header injection and
  deadline propagation, so the remote leg falls out of the trace tree.

Those two are scoped to the three hot-path files; helpers elsewhere may
be span-free by design.  A third holds everywhere under ``pilosa_tpu/``:

* every string literal passed to ``start_span`` / ``record_span`` is a
  name of the span table in ``pilosa_tpu/obs/tracing.py`` (``SPAN_TABLE``,
  read from the source, not imported).  A span under any other name fails
  at run time, ``/debug/vars`` has no row for it and no per-layer metric
  can read it.  Names computed from a registered family
  (``http.<route>``, ``executor.execute<Call>``) are not literals.
"""

from __future__ import annotations

import ast
import os

from tools.graftlint._astutil import dotted, walk_no_nested_functions
from tools.graftlint.engine import Finding

PASS_ID = "span-discipline"
DESCRIPTION = "execute paths open tracing spans; clients route via _do layer"

_SCOPE_SUFFIXES = ("exec/executor.py", "cluster/dist.py", "cluster/client.py")

_TRANSPORT_SUFFIXES = ("urlopen", "HTTPConnection", "HTTPSConnection")


_TRACING = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "pilosa_tpu", "obs", "tracing.py"
)
_table: frozenset[str] | None = None


def span_table() -> frozenset[str]:
    """The names of ``SPAN_TABLE``, parsed out of tracing.py."""
    global _table
    if _table is None:
        with open(_TRACING) as f:
            tree = ast.parse(f.read())
        rows = next(
            n.value for n in tree.body
            if isinstance(n, ast.Assign) and dotted(n.targets[0]) == "SPAN_TABLE"
        )
        _table = frozenset(row.elts[0].value for row in rows.elts)
    return _table


def _in_program(path: str) -> bool:
    return "pilosa_tpu/" in path.replace("\\", "/")


def applies(path: str) -> bool:
    return _in_program(path)


def _on_hot_path(path: str) -> bool:
    """The entry-point and transport rules: the three files, and anything
    checked from outside the program (the corpus)."""
    p = path.replace("\\", "/")
    return p.endswith(_SCOPE_SUFFIXES) or not _in_program(p)


def _is_span_entry(fn: ast.FunctionDef) -> bool:
    return fn.name == "execute" or fn.name.startswith("_batch_")


def _opens_span(fn: ast.FunctionDef) -> bool:
    """True when the function body (nested defs excluded — their spans
    open in a different dynamic extent) calls ``...start_span(...)``."""
    for node in walk_no_nested_functions(fn.body):
        if isinstance(node, ast.Call):
            d = dotted(node.func)
            if d is not None and d.rsplit(".", 1)[-1] == "start_span":
                return True
    return False


def _is_transport_call(node: ast.Call) -> bool:
    d = dotted(node.func)
    if d is None:
        return False
    if d.rsplit(".", 1)[-1] in _TRANSPORT_SUFFIXES:
        return True
    return d.endswith("._pool.request")


def _unregistered(path: str, tree: ast.AST) -> list[Finding]:
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args):
            continue
        d = dotted(node.func)
        if d is None or d.rsplit(".", 1)[-1] not in ("start_span", "record_span"):
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str) \
                and arg.value not in span_table():
            out.append(
                Finding(
                    path, node.lineno, node.col_offset, PASS_ID,
                    f"span name {arg.value!r} is not in the span table "
                    "(pilosa_tpu/obs/tracing.py SPAN_TABLE): it fails at "
                    "run time and no metric can read it",
                )
            )
    return out


def check(path: str, tree: ast.AST, lines: list[str]) -> list[Finding]:
    findings = _unregistered(path, tree)
    if not _on_hot_path(path):
        return findings

    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and _is_span_entry(node):
            if not _opens_span(node):
                findings.append(
                    Finding(
                        path, node.lineno, node.col_offset, PASS_ID,
                        f"{node.name}() is on the execute path but carries "
                        "no tracing span: this stretch is invisible to "
                        "?profile=true and trace export",
                    )
                )

    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        methods = [
            n for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        if not any(m.name == "_do_full" for m in methods):
            continue
        for m in methods:
            if m.name.startswith("_"):
                continue  # the _do layer itself and private helpers
            for node in walk_no_nested_functions(m.body):
                if isinstance(node, ast.Call) and _is_transport_call(node):
                    findings.append(
                        Finding(
                            path, node.lineno, node.col_offset, PASS_ID,
                            f"{cls.name}.{m.name}() bypasses the "
                            "span-injecting _do layer with a direct "
                            "transport call: the remote hop drops out of "
                            "the trace and ignores the deadline budget",
                        )
                    )
    return findings
