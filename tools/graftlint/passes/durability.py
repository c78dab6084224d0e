"""durability: write-then-rename / write-then-close must fsync.

Invariant: storage/ promises the reference's crash durability (snapshot
rewrites are atomic temp-file+rename, the op log survives clean
shutdown).  ``os.replace``/``os.rename`` of freshly written bytes is
only atomic-AND-durable if those bytes were fsync'd first — otherwise a
power cut can leave the renamed file empty or torn.  Likewise a
``close()`` that hands a data-file handle back to the OS without fsync
leaves the tail of the op log in the page cache (the bug
FragmentFile.close once had; fixed in PR 1).

Heuristics, per function in storage/:

* calls ``os.replace``/``os.rename`` but never ``os.fsync`` (or a
  ``*sync*``-named helper) -> finding;
* is named ``close`` and closes a file-handle-looking ``self``
  attribute (``_fh``, ``_file``, ``fh``, ``_log`` ...) without an fsync
  on some path through the function -> finding.
"""

from __future__ import annotations

import ast
import re

from tools.graftlint._astutil import dotted
from tools.graftlint.engine import Finding

PASS_ID = "durability"
DESCRIPTION = "storage/: rename or data-file close without an os.fsync"

_RENAMES = {"os.replace", "os.rename"}
_HANDLE_ATTR_RE = re.compile(r"(^|_)(fh|file|log|wal)$")


def applies(path: str) -> bool:
    return "/storage/" in path


def _has_sync(calls: list[str]) -> bool:
    return any(
        d == "os.fsync" or d.split(".")[-1].find("sync") >= 0 for d in calls
    )


def check(path: str, tree: ast.AST, lines: list[str]) -> list[Finding]:
    findings: list[Finding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        calls: list[str] = []
        rename_node: ast.Call | None = None
        close_node: ast.Call | None = None
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            d = dotted(node.func)
            if d is not None:
                calls.append(d)
                if d in _RENAMES and rename_node is None:
                    rename_node = node
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "close"
                and isinstance(node.func.value, ast.Attribute)
                and _HANDLE_ATTR_RE.search(node.func.value.attr)
                and close_node is None
            ):
                close_node = node
        if _has_sync(calls):
            continue
        if rename_node is not None:
            findings.append(
                Finding(
                    path, rename_node.lineno, rename_node.col_offset, PASS_ID,
                    f"{dotted(rename_node.func)} in {fn.name!r} without an "
                    "os.fsync: the renamed bytes may not survive a power cut",
                )
            )
        if fn.name == "close" and close_node is not None:
            attr = close_node.func.value.attr  # type: ignore[union-attr]
            findings.append(
                Finding(
                    path, close_node.lineno, close_node.col_offset, PASS_ID,
                    f"close() releases self.{attr} without os.fsync: "
                    "page-cache tail of the data file can be lost on crash",
                )
            )
    return findings
