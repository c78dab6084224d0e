"""cache-discipline: the semantic result cache is driven through its
public protocol, never by poking its internals.

Invariant: ``ResultCache`` (pilosa_tpu/exec/rescache.py) keeps three
structures in lock-step under one lock — the LRU entry map, the
``(index, field) -> keys`` reverse map that makes ``note_write``
precise, and the hit/miss/invalidation counters that feed
``pilosa_rescache_*``.  Every legal mutation lives in rescache.py
behind ``lookup()``/``store()``/``note_write()``/``snapshot()``
(``entries()`` lists the table for a test that wants to look).
Touching a private attribute through a ``rescache`` receiver anywhere
else (``executor.rescache._entries.pop(...)``, reading
``.rescache._by_field`` without the lock) desynchronizes the maps — an
entry the reverse map no longer knows about survives invalidation and
serves stale results.  Hand-assigning a public counter
(``cache.hits += 1``) makes the operator surfaces lie about hit rate
without any stale serve to show for it.

Reads of the public counters and ``snapshot()``/``note_write()`` calls
are fine everywhere.

Scope: the whole tree except the cache itself.  Tests included: a test
that wants a cold cache constructs one (or sets ``rescache_entries=0``)
instead of emptying the private map.
"""

from __future__ import annotations

import ast

from tools.graftlint.engine import Finding

PASS_ID = "cache-discipline"
DESCRIPTION = (
    "ResultCache internals are touched only inside exec/rescache.py; "
    "use lookup()/store()/note_write()/snapshot()"
)

_OWNER = "pilosa_tpu/exec/rescache.py"

_PRIVATE_MSG = (
    "private ResultCache state accessed outside the cache: the entry "
    "map, the by-field reverse map, and the counters move together "
    "under one lock (use lookup()/store()/note_write()/snapshot() — "
    "exec/rescache.py owns this state)"
)
_COUNTER_MSG = (
    "hand-written ResultCache counter bypasses the cache's accounting: "
    "pilosa_rescache_* and the /debug/vars block would disagree with "
    "what the cache actually did (counters move only inside "
    "exec/rescache.py)"
)

# the public counters note_write/lookup/store maintain; assignment to
# any of them outside the cache is a lie on the operator surfaces
_COUNTERS = frozenset(
    {
        "hits",
        "misses",
        "invalidations",
        "promotions",
        "demotions",
        "maintained_hits",
        "uncopied_hits",
        "encoded_hits",
        "stores",
        "evictions",
    }
)


def applies(path: str) -> bool:
    return not path.replace("\\", "/").endswith(_OWNER)


def _is_rescache_receiver(node: ast.expr) -> bool:
    """True for ``<expr>.rescache`` and for names bound to a cache
    (``cache = ...ResultCache(...)`` conventions: rescache/rescache-ish
    locals are out of static reach, so the pass keys on the attribute
    spelling the codebase actually uses)."""
    return isinstance(node, ast.Attribute) and node.attr == "rescache"


def _assign_targets(node: ast.AST) -> list[ast.expr]:
    if isinstance(node, ast.Assign):
        return list(node.targets)
    if isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        return [node.target]
    return []


def check(path: str, tree: ast.AST, lines: list[str]) -> list[Finding]:
    findings: list[Finding] = []
    for node in ast.walk(tree):
        # any access (read or write) to a private attr of a .rescache
        # receiver: ex.rescache._entries, api.executor.rescache._lock
        if (
            isinstance(node, ast.Attribute)
            and node.attr.startswith("_")
            and _is_rescache_receiver(node.value)
        ):
            findings.append(
                Finding(path, node.lineno, node.col_offset, PASS_ID, _PRIVATE_MSG)
            )
        # writes to the public counters of a .rescache receiver
        for t in _assign_targets(node):
            for sub in ast.walk(t):
                if (
                    isinstance(sub, ast.Attribute)
                    and sub.attr in _COUNTERS
                    and _is_rescache_receiver(sub.value)
                ):
                    findings.append(
                        Finding(
                            path, sub.lineno, sub.col_offset, PASS_ID, _COUNTER_MSG
                        )
                    )
    return findings
