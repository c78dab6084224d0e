"""launch-discipline: every jit/kernel launch site reports to the
device cost ledger.

Invariant: XLA compiles and device launches are observable only because
every dispatch path books them into ``obs/devledger.py`` — via a launch
window (``site.launch()``), a post-hoc claim (``site.claim()``), or one
of the registered funnels that do it on the caller's behalf
(``kernels._note_dispatch`` / ``note_bsi_dispatch`` / ``note_transfer``).
A module that calls ``jax.jit`` / ``shard_map`` / ``pmap`` without any
ledger wiring dispatches invisible device work: its compiles land in the
unattributed bucket (or worse, get claimed by whichever instrumented
site runs next on the thread), recompile storms it causes cannot be
pinned to a site, and ``/debug/devcosts`` under-reports.

A module counts as *ledger-registered* when it references ``devledger``
(import or attribute use) or reports through one of the registered
funnel names above.  Jitted helpers that are only ever invoked beneath
another site's window may carry a per-line suppression instead — the
mandatory reason must say which site adopts their dispatches.

Scope: ``pilosa_tpu/`` only, excluding the ledger module.
"""

from __future__ import annotations

import ast

from tools.graftlint._astutil import dotted
from tools.graftlint.engine import Finding

PASS_ID = "launch-discipline"
DESCRIPTION = (
    "jax.jit/shard_map/pmap call sites live in ledger-registered "
    "modules (obs/devledger.py) or carry a reasoned suppression"
)

_JIT_DOTTED = {"jax.jit", "jit", "pjit", "jax.pjit"}
_PMAP_DOTTED = {"jax.pmap", "pmap"}
_PARTIAL_DOTTED = {"partial", "functools.partial"}

# funnels that book launches/compiles/transfers into the ledger for
# their callers (ops/kernels.py owns them)
_FUNNELS = {"_note_dispatch", "note_bsi_dispatch", "note_transfer"}

_JIT_MSG = (
    "direct jax.jit in a module with no device-cost-ledger wiring: "
    "compiles/launches here are invisible to /debug/devcosts (register "
    "a devledger site, report through a kernels funnel, or suppress "
    "with the adopting site named)"
)
_SHARD_MAP_MSG = (
    "direct shard_map in a module with no device-cost-ledger wiring: "
    "the collective launch escapes site/principal attribution (register "
    "a devledger site or report through a kernels funnel)"
)
_PMAP_MSG = (
    "direct pmap in a module with no device-cost-ledger wiring: the "
    "multi-device launch escapes site/principal attribution (register "
    "a devledger site or report through a kernels funnel)"
)


def applies(path: str) -> bool:
    p = path.replace("\\", "/")
    if "pilosa_tpu/" not in p:
        return False
    return not p.endswith("pilosa_tpu/obs/devledger.py")


def _is_registered(tree: ast.AST) -> bool:
    """Module references devledger or a registered kernels funnel."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.module and "devledger" in node.module:
                return True
            if any(a.name == "devledger" for a in node.names):
                return True
        elif isinstance(node, ast.Import):
            if any("devledger" in a.name for a in node.names):
                return True
        elif isinstance(node, ast.Name) and node.id == "devledger":
            return True
        elif isinstance(node, ast.Attribute) and node.attr in _FUNNELS:
            return True
        elif isinstance(node, ast.Name) and node.id in _FUNNELS:
            return True
    return False


def _jit_like(node: ast.AST) -> str | None:
    """Classify an expression as a launch-builder usage: returns the
    message for a finding, or None.  Handles the tree's idioms —
    ``@jax.jit``, ``jax.jit(fn)``, ``partial(jax.jit, ...)``,
    ``shard_map(local, mesh=...)``, ``jax.pmap(fn)``."""
    d = dotted(node)
    if d in _JIT_DOTTED:
        return _JIT_MSG
    if d in _PMAP_DOTTED:
        return _PMAP_MSG
    if d is not None and d.split(".")[-1] == "shard_map":
        return _SHARD_MAP_MSG
    return None


def check(path: str, tree: ast.AST, lines: list[str]) -> list[Finding]:
    if _is_registered(tree):
        return []
    findings: list[Finding] = []
    seen: set[tuple[int, int]] = set()

    def note(node: ast.AST, msg: str) -> None:
        key = (node.lineno, node.col_offset)
        if key not in seen:
            seen.add(key)
            findings.append(
                Finding(path, node.lineno, node.col_offset, PASS_ID, msg)
            )

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                target = dec
                # @partial(jax.jit, static_argnames=...) decorates via
                # its first positional argument
                if (
                    isinstance(dec, ast.Call)
                    and dotted(dec.func) in _PARTIAL_DOTTED
                    and dec.args
                ):
                    target = dec.args[0]
                elif isinstance(dec, ast.Call):
                    target = dec.func
                msg = _jit_like(target)
                if msg is not None:
                    note(dec, msg)
        elif isinstance(node, ast.Call):
            msg = _jit_like(node.func)
            if msg is not None:
                note(node, msg)
            # partial(jax.jit, ...) / partial(shard_map, ...) builders
            if dotted(node.func) in _PARTIAL_DOTTED and node.args:
                msg = _jit_like(node.args[0])
                if msg is not None:
                    note(node, msg)
    return findings
