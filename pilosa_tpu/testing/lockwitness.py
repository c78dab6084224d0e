"""Runtime lockdep witness: observe lock acquisition order, trap
inversions live.

The static half (graftlint's ``lock-graph`` pass) proves ordering over
the paths it can resolve; this is the dynamic half, modeled on the Linux
kernel's lockdep *validator*: every ``threading.Lock``/``RLock``
allocated by project code is wrapped so each acquisition records an
edge ``held → acquired`` into a process-global order graph, and the
first acquisition that would create the REVERSE of an already-seen edge
— a two-lock inversion, i.e. a deadlock waiting for the right
interleaving — raises (or logs, configurable) *at the acquisition
site*, with both witness stacks.  Crucially, lockdep-style, the two
orders never have to deadlock to be caught: they only have to both
*happen*, even seconds apart, even on one thread.

Identity: locks are keyed by **allocation site** (file:line of the
``threading.Lock()`` call).  Every instance of a class maps to the same
key — the same per-class granularity the static pass uses for
``(Class, attr)`` fields — so static edges and runtime edges line up
for cross-checking: a static-only edge means a path tests never drive
(suppress it in the pass with the invariant as the reason); a
runtime-only edge means the static resolver missed an alias (fix the
pass).  Locks allocated outside the project scope (stdlib, jax) pass
through unwrapped: zero overhead and no third-party noise.

Semantics matched to real deadlock risk:

* re-acquiring a key already held by this thread records nothing (RLock
  re-entrancy; two same-class instances are indistinguishable by key,
  and same-key nesting is overwhelmingly the re-entrant case);
* non-blocking try-acquires record no edge (a failed/timed attempt
  cannot wait forever) but a SUCCESSFUL one still enters the held set —
  edges from it to later blocking acquisitions are real;
* ``Condition.wait`` releases and re-acquires through the wrapper's
  ``_release_save``/``_acquire_restore`` so the held set stays honest
  across waits.

Enable process-wide with :func:`install` (idempotent), or scoped with
``with lockwitness.active():`` in tests.  tests/conftest.py installs it
for the whole tier-1 run — every already-threaded test doubles as a
race probe — and asserts zero recorded inversions at session end.  Mode
comes from ``PILOSA_LOCKWITNESS`` (``raise`` | ``log`` | ``off``).
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import traceback

logger = logging.getLogger(__name__)

# Real (never-wrapped) primitives, captured at import time so witness
# internals and out-of-scope allocations are untouched.
_real_lock = threading.Lock
_real_rlock = threading.RLock

# Project scope: only locks allocated from files under these path
# fragments are witnessed.
_SCOPE = (f"{os.sep}pilosa_tpu{os.sep}", f"{os.sep}tools{os.sep}",
          f"{os.sep}tests{os.sep}")

# This module's own file plus the stdlib threading module: frames to
# skip when walking for the user-code allocation/acquisition site.
# Exact-path match — a substring test would also skip the witness's own
# test file (tests/test_lockwitness.py).
_SKIP_FILES = (os.path.abspath(__file__), threading.__file__)


class LockOrderInversion(Exception):
    """Two locks were acquired in both orders (potential deadlock)."""


class _State:
    """Process-global witness state (reset by tests)."""

    def __init__(self):
        # Re-entrant: a weakref finalizer (membudget.release on a dead
        # field) can run at any allocation, including one made under this
        # guard by a thread that holds a witnessed lock; its own lock
        # acquisition then comes back here on the same thread.
        self.guard = _real_rlock()
        # (a, b) -> short witness string for the first observed a-then-b
        self.edges: dict[tuple[str, str], str] = {}
        self.inversions: list[dict] = []
        self.inverted_pairs: set[frozenset] = set()
        self.mode = "off"
        self.installed = False
        self.acquires = 0  # observability: witnessed acquisitions
        self.tls = threading.local()

    def held(self) -> list:
        h = getattr(self.tls, "held", None)
        if h is None:
            h = self.tls.held = []
        return h


_state = _State()


def _alloc_site() -> str | None:
    """file:line of the project frame allocating the lock; None when the
    allocation is out of scope (stdlib/third-party)."""
    f = sys._getframe(2)
    while f is not None and f.f_code.co_filename in _SKIP_FILES:
        f = f.f_back
    if f is None:
        return None
    fn = f.f_code.co_filename
    if not any(s in fn for s in _SCOPE):
        return None
    # repo-relative, stable across checkouts
    for marker in ("pilosa_tpu", "tools", "tests"):
        idx = fn.find(f"{os.sep}{marker}{os.sep}")
        if idx >= 0:
            fn = fn[idx + 1:].replace(os.sep, "/")
            break
    return f"{fn}:{f.f_lineno}"


def _acquire_site() -> str:
    f = sys._getframe(2)
    while f is not None and f.f_code.co_filename in _SKIP_FILES:
        f = f.f_back
    if f is None:  # pragma: no cover - only if called from module top
        return "?"
    fn = f.f_code.co_filename
    for marker in ("pilosa_tpu", "tools", "tests"):
        idx = fn.find(f"{os.sep}{marker}{os.sep}")
        if idx >= 0:
            fn = fn[idx + 1:].replace(os.sep, "/")
            break
    return f"{fn}:{f.f_lineno}"


def _note_acquired(key: str, blocking: bool) -> None:
    st = _state
    held = st.held()
    if any(k == key for k, _site in held):
        held.append((key, None))  # re-entrant depth marker; no edges
        return
    site = _acquire_site()
    st.acquires += 1
    if blocking and held:
        new_edges = []
        inversion = None
        with st.guard:
            for hkey, hsite in held:
                if hsite is None or hkey == key:
                    continue
                edge = (hkey, key)
                if edge not in st.edges:
                    new_edges.append((edge, f"{hsite} then {site}"))
                rev = (key, hkey)
                if rev in st.edges and frozenset(edge) not in st.inverted_pairs:
                    inversion = {
                        "locks": (hkey, key),
                        "thread": threading.current_thread().name,
                        "this_order": f"{hsite} then {site}",
                        "prior_order": st.edges[rev],
                        "stack": "".join(traceback.format_stack(limit=12)),
                    }
                    st.inverted_pairs.add(frozenset(edge))
                    st.inversions.append(inversion)
            for edge, witness in new_edges:
                st.edges[edge] = witness
        if inversion is not None:
            msg = (
                "lock order inversion: "
                f"{inversion['locks'][0]} <-> {inversion['locks'][1]} — "
                f"this thread ({inversion['thread']}): "
                f"{inversion['this_order']}; prior order: "
                f"{inversion['prior_order']}"
            )
            if st.mode == "raise":
                raise LockOrderInversion(msg)
            logger.error("%s\n%s", msg, inversion["stack"])
    held.append((key, site))


def _note_released(key: str) -> None:
    held = _state.held()
    for i in range(len(held) - 1, -1, -1):
        if held[i][0] == key:
            del held[i]
            return


class _WitnessBase:
    """Wrapper delegating to a real lock, recording order."""

    __slots__ = ("_inner", "_key")

    def __init__(self, inner, key):
        self._inner = inner
        self._key = key

    def acquire(self, blocking=True, timeout=-1):
        ok = self._inner.acquire(blocking, timeout)
        if ok:
            try:
                # timeout-bounded acquisitions still count as blocking
                # intent: a thread CAN wait on them, which is what an
                # order edge models
                _note_acquired(self._key, blocking)
            except LockOrderInversion:
                # raise-mode trap: hand the lock back so the caller's
                # with-body never runs half-locked and peers can't hang
                self._inner.release()
                raise
        return ok

    def release(self):
        self._inner.release()
        _note_released(self._key)

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def locked(self):
        return self._inner.locked()

    def __repr__(self):  # pragma: no cover - debug aid
        return f"<witness {self._key} of {self._inner!r}>"


class _WitnessLock(_WitnessBase):
    pass


class _WitnessRLock(_WitnessBase):
    """RLock wrapper: Condition integration needs the _release_save /
    _acquire_restore / _is_owned trio to route through the witness so
    the held set stays honest across ``wait()``."""

    __slots__ = ()

    def _is_owned(self):
        return self._inner._is_owned()

    def _release_save(self):
        state = self._inner._release_save()
        _note_released(self._key)
        return state

    def _acquire_restore(self, state):
        self._inner._acquire_restore(state)
        _note_acquired(self._key, blocking=True)

    def _at_fork_reinit(self):  # pragma: no cover - fork safety passthrough
        self._inner._at_fork_reinit()


def _make_lock():
    inner = _real_lock()
    if _state.mode == "off":
        return inner
    key = _alloc_site()
    if key is None:
        return inner
    return _WitnessLock(inner, key)


def _make_rlock():
    inner = _real_rlock()
    if _state.mode == "off":
        return inner
    key = _alloc_site()
    if key is None:
        return inner
    return _WitnessRLock(inner, key)


# -- public API --------------------------------------------------------------


def install(mode: str | None = None) -> None:
    """Patch ``threading.Lock``/``RLock`` so project-allocated locks are
    witnessed.  ``mode``: ``raise`` (first inversion raises at the
    acquisition site), ``log`` (recorded + logged, execution continues),
    or ``off``; default from ``PILOSA_LOCKWITNESS`` (falling back to
    ``raise``).  Idempotent; wraps only locks allocated AFTER install.
    """
    if mode is None:
        mode = os.environ.get("PILOSA_LOCKWITNESS", "raise")
    if mode not in ("raise", "log", "off"):
        raise ValueError(f"unknown lockwitness mode {mode!r}")
    _state.mode = mode
    if mode == "off" or _state.installed:
        return
    threading.Lock = _make_lock
    threading.RLock = _make_rlock
    _state.installed = True


def uninstall() -> None:
    """Restore the real primitives.  Locks already wrapped keep working
    (their inner lock is real); they just stop being good witnesses once
    their peers are unwrapped."""
    threading.Lock = _real_lock
    threading.RLock = _real_rlock
    _state.installed = False
    _state.mode = "off"


class active:
    """``with lockwitness.active(mode="raise"):`` scoped install for
    tests; resets recorded state on entry, restores the previous
    install state (and clears the scope's recordings) on exit — safe
    inside a session conftest already runs under the witness."""

    def __init__(self, mode: str = "raise"):
        self.mode = mode
        self._prev: tuple[bool, str] | None = None

    def __enter__(self):
        self._prev = (_state.installed, _state.mode)
        reset()
        install(self.mode)
        return self

    def __exit__(self, *exc):
        installed, mode = self._prev
        if installed:
            _state.mode = mode
        else:
            uninstall()
        reset()
        return False


def findings() -> list[dict]:
    """Inversions recorded so far (log mode records without raising;
    raise mode records before raising, so a swallowed exception in a
    worker thread still shows up here)."""
    with _state.guard:
        return list(_state.inversions)


def order_graph() -> dict:
    """{(a, b): witness} — the live acquisition-order edges, for
    cross-checking against the static lock-graph pass."""
    with _state.guard:
        return dict(_state.edges)


def stats() -> dict:
    with _state.guard:
        return {
            "mode": _state.mode,
            "installed": _state.installed,
            "witnessedAcquires": _state.acquires,
            "edges": len(_state.edges),
            "inversions": len(_state.inversions),
        }


def reset() -> None:
    """Clear recorded edges/inversions (NOT the install state)."""
    with _state.guard:
        _state.edges.clear()
        _state.inversions.clear()
        _state.inverted_pairs.clear()
        _state.acquires = 0
