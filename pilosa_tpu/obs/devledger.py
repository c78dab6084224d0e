"""Process-wide device cost ledger: compile / launch / transfer accounting.

Every jit or kernel launch site in the tree registers a :class:`Site`
(``ledger.site("executor.stack_launch")``) and reports through it, so the
server can answer two questions the rest of the observability plane cannot:

* **what did the device work cost** — XLA compile count and wall-time
  (new-compile vs cache-hit), launch counts and wall/device time, H2D/D2H
  bytes, and (opt-in) ``cost_analysis()`` FLOPs/bytes per compiled program;
* **who caused it** — attribution along two axes: the *site* (which launch
  path) and the *principal* ``(tenant, index, op_class)``, with the tenant
  read from the ``X-Pilosa-Tenant`` request header and threaded
  http → api → batcher → executor via a contextvar (default tenant ``"-"``).

Compile detection rides ``jax.monitoring``: a jit call served from the
in-process cache emits no events, while a backend compile request emits
``backend_compile_duration`` exactly once (plus trace/lowering durations),
synchronously in the calling thread.  That event wraps jax's
``compile_or_get_cached``, so with a persistent compilation cache
(pilosa_tpu/jaxcache.py) it also fires when the executable was only read
back from disk; jax announces those with ``/jax/compilation_cache/cache_hits``
just before, and the listener books them apart as ``persistentCacheHits``
— a retrieval is not a compile, and must trip neither the storm detector
nor the benchmark's ``window_compiles`` check.
The listener attributes each event to the innermost active *launch window*
(``with site.launch(sig=...)``) on that thread; sites that report after the
fact (the ops.kernels dispatch funnel) claim the thread's stashed events
instead.  A **recompile-storm detector** (>= N new compiles inside a sliding
window, after warmup) freezes the offending sites/shapes into a bundle and
fans out to registered callbacks (the node wires this to the flight
recorder's incident engine).

The ledger is process-global by design — compile caches and devices are
process-global — matching the precedent of ``kernels.kernel_stats`` and the
residency/membudget singletons.  ``reset()`` exists for tests and benches.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque
from contextvars import ContextVar

TENANT_HEADER = "X-Pilosa-Tenant"
# THE canonical tenantless principal: every spelling of "no tenant"
# (missing header, empty string, whitespace, the legacy "-") lands
# here, so batcher admission, ledger rows and SLO accounting agree on
# one identity for untagged traffic (ISSUE 18 satellite).
DEFAULT_TENANT = "(default)"
_LEGACY_TENANTLESS = ("-",)

# Reserved site name for compile events no window or claim ever adopted
# (e.g. module-import-time warmers on threads that never dispatch).
UNATTRIBUTED = "(unattributed)"

# Principal tables are label sets headed for /metrics: bound cardinality.
_MAX_PRINCIPALS = 512
_OVERFLOW_PRINCIPAL = ("~overflow", "-", "-")
_MAX_TENANT_LEN = 64
_MAX_TRACKED = 8192  # per-site identity set cap

# jax.monitoring event keys.  backend_compile fires once per backend
# compile request — a new compile unless a persistent-cache hit was
# announced on the same thread just before; the other durations under the
# prefix are folded into compile wall-time.
_EV_BACKEND = "/jax/core/compile/backend_compile_duration"
_EV_COMPILE_PREFIX = "/jax/core/compile/"
_EV_PERSISTENT_HIT = "/jax/compilation_cache/cache_hits"

_tenant: ContextVar[str] = ContextVar("devledger_tenant", default=DEFAULT_TENANT)
# (index, op_class) bound by the api layer once both are known.
_binding: ContextVar[tuple] = ContextVar("devledger_binding", default=("-", "-"))
# Weighted principal list — set by the batcher around a shared flight so one
# launch is split across every tenant that rode it.
_weights: ContextVar[tuple] = ContextVar("devledger_weights", default=())


def active_window_site():
    """The site of this thread's innermost launch window, or None.  Lets
    shared funnels (``kernels.note_transfer``) book under the wrapping
    site — an ingest-upload window adopts the fragment sync's H2D bytes."""
    w = _tls.windows
    return w[-1].site if w else None


def clean_tenant(raw) -> str:
    """Sanitize a tenant label from the wire: printable, bounded,
    non-empty — and NORMALIZED: every tenantless spelling (None, "",
    whitespace, legacy "-") maps to the one canonical
    :data:`DEFAULT_TENANT` so per-tenant accounting never splits
    untagged traffic across aliases."""
    if not raw:
        return DEFAULT_TENANT
    t = "".join(c for c in str(raw).strip() if c.isprintable() and c not in '{}",\\')
    t = t[:_MAX_TENANT_LEN]
    if not t or t in _LEGACY_TENANTLESS:
        return DEFAULT_TENANT
    return t


def current_tenant() -> str:
    return _tenant.get()


def current_principal() -> tuple:
    idx, cls = _binding.get()
    return (_tenant.get(), idx, cls)


def ambient_weights() -> tuple:
    """The weighted principal list launches should book against:
    the batcher's flight-level split when set, else the single ambient
    principal at weight 1."""
    w = _weights.get()
    if w:
        return w
    return ((current_principal(), 1.0),)


@contextlib.contextmanager
def tenant_scope(tenant):
    tok = _tenant.set(clean_tenant(tenant))
    try:
        yield
    finally:
        _tenant.reset(tok)


@contextlib.contextmanager
def principal_scope(index="-", op_class="-"):
    tok = _binding.set((str(index or "-"), str(op_class or "-")))
    try:
        yield
    finally:
        _binding.reset(tok)


@contextlib.contextmanager
def weighted_scope(pairs):
    """``pairs`` is an iterable of ((tenant, index, op_class), weight); used
    by the batcher so one shared flight launch is attributed fractionally to
    every principal whose queries rode it."""
    tok = _weights.set(tuple(pairs))
    try:
        yield
    finally:
        _weights.reset(tok)


class _Accum:
    """One row of the cost table (a site, a principal, or the totals)."""

    __slots__ = (
        "compiles",
        "compile_ms",
        "launches",
        "mesh_launches",
        "launch_ms",
        "device_ms",
        "h2d_bytes",
        "d2h_bytes",
        "flops",
        "bytes_accessed",
        "cache_hits",
    )

    def __init__(self):
        self.compiles = 0
        self.compile_ms = 0.0
        self.launches = 0
        self.mesh_launches = 0  # of launches: operands on more than one device
        self.launch_ms = 0.0
        self.device_ms = 0.0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.cache_hits = 0

    def to_dict(self, uptime=None):
        d = {
            "compiles": self.compiles,
            "compileMs": round(self.compile_ms, 3),
            "cacheHits": self.cache_hits,
            "launches": self.launches,
            "meshLaunches": self.mesh_launches,
            "launchMs": round(self.launch_ms, 3),
            "deviceMs": round(self.device_ms, 3),
            "h2dBytes": self.h2d_bytes,
            "d2hBytes": self.d2h_bytes,
        }
        if self.flops or self.bytes_accessed:
            d["flops"] = self.flops
            d["bytesAccessed"] = self.bytes_accessed
        if uptime and uptime > 0:
            d["launchesPerSec"] = round(self.launches / uptime, 3)
            d["transferBytesPerSec"] = round(
                (self.h2d_bytes + self.d2h_bytes) / uptime, 1
            )
        return d


class _Window:
    """One active launch window on a thread's window stack.  The monitoring
    listener folds compile events into the innermost window; the window's
    exit books them against its site and the ambient principals."""

    __slots__ = ("site", "sig", "muted", "mesh", "compiles", "compile_ms")

    def __init__(self, site, sig, muted=False):
        self.site = site
        self.sig = sig
        self.muted = muted
        # set by whoever dispatches inside the window (a kernels-funnel
        # dispatch, or the window's owner) once an operand is seen to lie
        # on more than one device; the window's own launch then books as
        # a mesh launch too
        self.mesh = False
        self.compiles = 0
        self.compile_ms = 0.0


class _TLS(threading.local):
    def __init__(self):
        self.windows = []
        # compile events that fired with no window active on this thread,
        # waiting for the next Site.claim() (the kernels dispatch funnel
        # notes launches post-hoc); bounded so a non-dispatching thread
        # cannot grow it forever.
        self.stash_compiles = 0
        self.stash_ms = 0.0
        # persistent-cache hits announced on this thread whose
        # backend_compile_duration has not fired yet
        self.pending_retrievals = 0


_tls = _TLS()


class Site:
    """One registered launch site.  Cheap to hold; all mutation funnels
    through the owning ledger's lock except window bookkeeping, which is
    thread-local until the window exits."""

    __slots__ = ("name", "ledger", "acc", "_seen", "recent_sigs", "sig_ms")

    def __init__(self, name, ledger):
        self.name = name
        self.ledger = ledger
        self.acc = _Accum()
        self._seen = set()  # tracked callable/shape identities
        self.recent_sigs = deque(maxlen=8)
        # sig class (first token of the launch sig, e.g. "count" of
        # "count B8 S4") -> [launches, EWMA device-ms per launch]: the
        # measured price list the flight planner's lane chooser reads
        # instead of hardcoded warm-up heuristics (exec/planner.py)
        self.sig_ms: dict[str, list] = {}

    # -- identity tracking ------------------------------------------------
    def track(self, fn, key=()) -> bool:
        """Track a lowered/compiled callable identity (the function object
        plus a shape/static key).  Returns True the first time an identity
        is seen — the site-local compile-vs-cache-hit signal that backs the
        monitoring listener.  Records a cache hit otherwise."""
        return self.track_key((id(fn), key))

    def track_key(self, key) -> bool:
        """``track`` for callers that already hold a stable hashable
        identity (e.g. the kernels funnel's (kernel, lane, shape-sig))."""
        with self.ledger._lock:
            if key in self._seen:
                self.acc.cache_hits += 1
                return False
            if len(self._seen) < _MAX_TRACKED:
                self._seen.add(key)
        return True

    # -- direct recording -------------------------------------------------
    def record_compile(self, wall_s=0.0, sig=None, flops=None, bytes_accessed=None):
        self.ledger._book_compile(self, 1, wall_s * 1e3, sig)
        if flops or bytes_accessed:
            self.record_cost(flops or 0.0, bytes_accessed or 0.0)

    def record_launch(self, wall_s=0.0, n=1, device_s=None):
        self.ledger._book_launch(self, n, wall_s * 1e3, (device_s or wall_s) * 1e3)

    def record_mesh_launch(self, n=1):
        """``n`` of the launches just booked ran over more than one
        device (one SPMD program); the windows open on this thread
        wrapped such a dispatch and will book theirs the same."""
        for w in _tls.windows:
            w.mesh = True
        self.ledger._book_mesh_launch(self, n)

    def record_transfer(self, nbytes, direction="h2d"):
        self.ledger._book_transfer(self, int(nbytes), direction)

    def record_cost(self, flops, bytes_accessed):
        with self.ledger._lock:
            self.acc.flops += float(flops)
            self.acc.bytes_accessed += float(bytes_accessed)

    # -- windows & claims -------------------------------------------------
    @contextlib.contextmanager
    def launch(self, sig=None, n=1, muted=False):
        """Wrap one device dispatch: measures launch wall time and adopts
        any XLA compile events that fire inside (same thread).  ``muted``
        windows swallow events without booking them — used around the
        opt-in cost_analysis AOT compile so it cannot double-count."""
        w = _Window(self, sig, muted=muted)
        _tls.windows.append(w)
        t0 = time.perf_counter()
        try:
            yield w
        finally:
            wall_ms = (time.perf_counter() - t0) * 1e3
            _tls.windows.pop()
            if not muted:
                if w.compiles:
                    self.ledger._book_compile(self, w.compiles, w.compile_ms, sig)
                # compile-carrying windows stay out of the per-sig price
                # list: the lane chooser wants the steady-state launch
                # cost, not the one-time trace+compile spike
                self.ledger._book_launch(
                    self, n, wall_ms, wall_ms,
                    sig=None if w.compiles else sig,
                )
                if w.mesh:
                    self.ledger._book_mesh_launch(self, n)

    def claim(self, sig=None):
        """Adopt compile events this thread saw since the last claim —
        called by post-hoc dispatch funnels such as
        ``kernels._note_dispatch`` right after the jit call returns.
        Inside an enclosing window (a mesh dispatch wrapping kernel
        dispatches) the claim takes the window's pending events, so the
        most specific site wins; otherwise it drains the thread stash."""
        windows = _tls.windows
        if windows:
            w = windows[-1]
            n, ms = w.compiles, w.compile_ms
            if n or ms:
                w.compiles = 0
                w.compile_ms = 0.0
                if not w.muted:
                    self.ledger._book_compile(self, n, ms, sig)
            return 0 if w.muted else n
        n, ms = _tls.stash_compiles, _tls.stash_ms
        if n or ms:
            _tls.stash_compiles = 0
            _tls.stash_ms = 0.0
            self.ledger._book_compile(self, n, ms, sig)
        return n

    def snapshot(self, uptime=None):
        with self.ledger._lock:
            d = self.acc.to_dict(uptime)
            d["trackedIdentities"] = len(self._seen)
            d["recentCompileSigs"] = [s for s in self.recent_sigs]
        return d


class Ledger:
    def __init__(self):
        self._lock = threading.Lock()
        self._sites = {}
        self._principals = {}
        self.totals = _Accum()
        self.unattributed = _Accum()
        # executables read back from jax's persistent compilation cache
        self.persistent_cache_hits = 0
        self.started = time.monotonic()
        # storm detector
        self.storm_threshold = 8
        self.storm_window_s = 60.0
        self.warmup_s = 0.0
        self._warm_mark = False
        self._storm_events = deque()
        self._storm_cool_until = 0.0
        self.storms = deque(maxlen=8)
        self._storm_callbacks = []
        self._listener_installed = False

    # -- registration -----------------------------------------------------
    def site(self, name) -> Site:
        with self._lock:
            s = self._sites.get(name)
            if s is None:
                s = self._sites[name] = Site(name, self)
        self._ensure_listener()
        return s

    def on_storm(self, cb):
        """Register ``cb(bundle_dict)`` to run when a recompile storm
        trips.  Callbacks must not raise; failures are swallowed."""
        with self._lock:
            if cb not in self._storm_callbacks:
                self._storm_callbacks.append(cb)

    def configure_storm(self, threshold=None, window_s=None, warmup_s=None):
        with self._lock:
            if threshold is not None:
                self.storm_threshold = max(1, int(threshold))
            if window_s is not None:
                self.storm_window_s = float(window_s)
            if warmup_s is not None:
                self.warmup_s = float(warmup_s)

    def mark_warm(self):
        self._warm_mark = True

    def measured_ms(self, site_name, sig_class):
        """(launches, EWMA device-ms per launch) for one site's sig class,
        or None before any non-compiling launch booked there — the flight
        planner's lane chooser treats None as "no price yet, keep the
        heuristic" (exec/planner.py)."""
        with self._lock:
            s = self._sites.get(site_name)
            if s is None:
                return None
            row = s.sig_ms.get(str(sig_class))
            if row is None:
                return None
            return (row[0], row[1])

    @property
    def warm(self) -> bool:
        if self._warm_mark:
            return True
        return (time.monotonic() - self.started) >= self.warmup_s > 0

    def reset(self):
        """Zero every table and re-arm the storm detector (tests/benches).
        Registered sites and callbacks survive; the listener stays."""
        with self._lock:
            for s in self._sites.values():
                s.acc = _Accum()
                s._seen.clear()
                s.recent_sigs.clear()
                s.sig_ms.clear()
            self._principals.clear()
            self.totals = _Accum()
            self.unattributed = _Accum()
            self.persistent_cache_hits = 0
            self.started = time.monotonic()
            self._warm_mark = False
            self._storm_events.clear()
            self._storm_cool_until = 0.0
            self.storms.clear()
        _tls.stash_compiles = 0
        _tls.stash_ms = 0.0

    # -- principal table --------------------------------------------------
    def _principal_row(self, principal) -> _Accum:
        # caller holds self._lock
        row = self._principals.get(principal)
        if row is None:
            if len(self._principals) >= _MAX_PRINCIPALS:
                principal = _OVERFLOW_PRINCIPAL
                row = self._principals.get(principal)
                if row is None:
                    row = self._principals[principal] = _Accum()
            else:
                row = self._principals[principal] = _Accum()
        return row

    # -- booking ----------------------------------------------------------
    def _book_compile(self, site, n, ms, sig):
        weights = ambient_weights()
        with self._lock:
            site.acc.compiles += n
            site.acc.compile_ms += ms
            if sig is not None:
                site.recent_sigs.append(str(sig))
            self.totals.compiles += n
            self.totals.compile_ms += ms
            for principal, w in weights:
                row = self._principal_row(principal)
                row.compiles += n  # compiles are indivisible; book whole
                row.compile_ms += ms * w
        self._note_storm(site.name, sig, n)

    # per-site sig-class price rows kept (first-come; real sig vocabularies
    # are a handful of op classes) and the EWMA smoothing factor
    _MAX_SIG_CLASSES = 32
    _SIG_EWMA_ALPHA = 0.25

    def _book_launch(self, site, n, wall_ms, device_ms, sig=None):
        weights = ambient_weights()
        with self._lock:
            site.acc.launches += n
            site.acc.launch_ms += wall_ms
            site.acc.device_ms += device_ms
            if sig is not None:
                cls = str(sig).split(None, 1)[0]
                row = site.sig_ms.get(cls)
                per = device_ms / max(n, 1)
                if row is not None:
                    row[0] += n
                    row[1] += self._SIG_EWMA_ALPHA * (per - row[1])
                elif len(site.sig_ms) < self._MAX_SIG_CLASSES:
                    site.sig_ms[cls] = [n, per]
            self.totals.launches += n
            self.totals.launch_ms += wall_ms
            self.totals.device_ms += device_ms
            for principal, w in weights:
                row = self._principal_row(principal)
                row.launches += max(1, round(n * w)) if n else 0
                row.launch_ms += wall_ms * w
                row.device_ms += device_ms * w

    def _book_mesh_launch(self, site, n):
        with self._lock:
            site.acc.mesh_launches += n
            self.totals.mesh_launches += n

    def _book_transfer(self, site, nbytes, direction):
        weights = ambient_weights()
        with self._lock:
            if direction == "d2h":
                site.acc.d2h_bytes += nbytes
                self.totals.d2h_bytes += nbytes
            else:
                site.acc.h2d_bytes += nbytes
                self.totals.h2d_bytes += nbytes
            for principal, w in weights:
                row = self._principal_row(principal)
                if direction == "d2h":
                    row.d2h_bytes += int(nbytes * w)
                else:
                    row.h2d_bytes += int(nbytes * w)

    def _book_unattributed(self, n, ms):
        with self._lock:
            self.unattributed.compiles += n
            self.unattributed.compile_ms += ms
            self.totals.compiles += n
            self.totals.compile_ms += ms
        self._note_storm(UNATTRIBUTED, None, n)

    # -- storm detector ---------------------------------------------------
    def _note_storm(self, site_name, sig, n=1):
        if not n or not self.warm:
            return
        now = time.monotonic()
        bundle = None
        with self._lock:
            for _ in range(n):
                self._storm_events.append((now, site_name, sig))
            horizon = now - self.storm_window_s
            while self._storm_events and self._storm_events[0][0] < horizon:
                self._storm_events.popleft()
            if (
                len(self._storm_events) >= self.storm_threshold
                and now >= self._storm_cool_until
            ):
                by_site = {}
                shapes = []
                for _, s, g in self._storm_events:
                    by_site[s] = by_site.get(s, 0) + 1
                    if g is not None:
                        shapes.append(str(g))
                bundle = {
                    "type": "recompile-storm",
                    "atUnix": time.time(),
                    "count": len(self._storm_events),
                    "threshold": self.storm_threshold,
                    "windowSec": self.storm_window_s,
                    "sites": dict(
                        sorted(by_site.items(), key=lambda kv: -kv[1])
                    ),
                    "shapes": shapes[-16:],
                }
                self.storms.append(bundle)
                # re-arm only after a quiet window so one storm emits one
                # incident, not one per compile past the threshold
                self._storm_cool_until = now + self.storm_window_s
                cbs = list(self._storm_callbacks)
        if bundle is not None:
            for cb in cbs:
                try:
                    cb(bundle)
                except Exception:  # graftlint: disable=exception-hygiene -- storm callbacks are best-effort; a broken sink must not break accounting
                    pass

    # -- jax.monitoring bridge --------------------------------------------
    def _ensure_listener(self):
        if self._listener_installed:
            return
        with self._lock:
            if self._listener_installed:
                return
            self._listener_installed = True
        try:
            from jax import monitoring as _mon

            _mon.register_event_duration_secs_listener(self._on_event)
            _mon.register_event_listener(self._on_plain_event)
        except Exception:
            # no jax / no monitoring API: sites still work via explicit
            # record_compile / track(); only automatic detection is lost
            self._listener_installed = True

    def _on_plain_event(self, key, **kw):
        """jax.monitoring plain-event listener: remembers a persistent
        cache hit until the backend_compile_duration that closes the
        same request arrives on this thread.  Must never raise."""
        if key == _EV_PERSISTENT_HIT:
            _tls.pending_retrievals += 1

    def _on_event(self, key, seconds, **kw):
        """jax.monitoring duration listener.  Fires synchronously in the
        compiling thread, so the thread's window stack and the request
        contextvars are the right attribution context.  Must never raise."""
        try:
            if not key.startswith(_EV_COMPILE_PREFIX):
                return
            ms = seconds * 1e3
            is_compile = key == _EV_BACKEND
            if is_compile and _tls.pending_retrievals:
                _tls.pending_retrievals -= 1
                is_compile = False
                with self._lock:
                    self.persistent_cache_hits += 1
            windows = _tls.windows
            if windows:
                w = windows[-1]
                if w.muted:
                    return
                if is_compile:
                    w.compiles += 1
                w.compile_ms += ms
                site_name = w.site.name
                sig = w.sig
            else:
                if is_compile:
                    _tls.stash_compiles += 1
                _tls.stash_ms += ms
                site_name = None
                sig = None
                if is_compile and _tls.stash_compiles > 64:
                    # stranded stash: fold into the reserved bucket so the
                    # totals stay honest even on never-dispatching threads
                    n, tot = _tls.stash_compiles, _tls.stash_ms
                    _tls.stash_compiles = 0
                    _tls.stash_ms = 0.0
                    self._book_unattributed(n, tot)
            if is_compile:
                self._annotate_span(site_name, sig, ms)
        except Exception:  # graftlint: disable=exception-hygiene -- a listener raise would propagate into XLA's compile path
            pass

    @staticmethod
    def _annotate_span(site_name, sig, ms):
        try:
            from pilosa_tpu.obs import tracing

            sp = tracing.active_span()
            if sp is not None:
                sp.set_tag("xlaCompiles", int(sp.tags.get("xlaCompiles", 0)) + 1)
                sp.set_tag(
                    "xlaCompileMs",
                    round(float(sp.tags.get("xlaCompileMs", 0.0)) + ms, 3),
                )
                sp.tags.setdefault("xlaCompileSites", []).append(
                    f"{site_name or UNATTRIBUTED} "
                    f"{sig if sig is not None else '-'}"
                )
        except Exception:  # graftlint: disable=exception-hygiene -- span annotation is advisory; tracing must never fail a compile
            pass

    # -- opt-in AOT cost analysis -----------------------------------------
    def analyze_cost(self, site, fn, *args, sig=None, **kwargs):
        """Best-effort ``cost_analysis()`` FLOPs/bytes for ``fn(*args)``.
        On this backend ``fn.lower().compile()`` does NOT share the jit call
        cache, so this pays a duplicate compile — gated behind
        PILOSA_DEVCOST_ANALYSIS=1 and run inside a muted window so the
        duplicate never pollutes compile counts or the storm detector."""
        if os.environ.get("PILOSA_DEVCOST_ANALYSIS", "") != "1":
            return None
        try:
            with site.launch(sig=sig, muted=True):
                compiled = fn.lower(*args, **kwargs).compile()
            costs = compiled.cost_analysis()
            if isinstance(costs, (list, tuple)):
                costs = costs[0] if costs else {}
            flops = float(costs.get("flops", 0.0))
            nbytes = float(costs.get("bytes accessed", 0.0))
            site.record_cost(flops, nbytes)
            return {"flops": flops, "bytesAccessed": nbytes}
        except Exception:
            return None

    # -- exposition -------------------------------------------------------
    def counters(self) -> dict:
        """Flat counter map for cheap before/after deltas (loadgen,
        flight recorder segments)."""
        with self._lock:
            out = {
                "compiles": self.totals.compiles,
                "compileMs": round(self.totals.compile_ms, 3),
                "launches": self.totals.launches,
                "deviceMs": round(self.totals.device_ms, 3),
                "h2dBytes": self.totals.h2d_bytes,
                "d2hBytes": self.totals.d2h_bytes,
                "persistentCacheHits": self.persistent_cache_hits,
                "storms": len(self.storms),
            }
            for name, s in self._sites.items():
                out[f"site.{name}.compiles"] = s.acc.compiles
                out[f"site.{name}.launches"] = s.acc.launches
                out[f"site.{name}.transferBytes"] = (
                    s.acc.h2d_bytes + s.acc.d2h_bytes
                )
        return out

    def tenant_totals(self) -> dict:
        """Per-TENANT aggregation over the principal table — the QoS
        governor's debt read-side (server/qos.py debits weighted-fair
        queues by these measured device-ms, not by query counts)."""
        with self._lock:
            out: dict = {}
            for (tenant, _idx, _cls), row in self._principals.items():
                t = out.get(tenant)
                if t is None:
                    t = out[tenant] = {
                        "deviceMs": 0.0,
                        "compileMs": 0.0,
                        "launches": 0,
                        "transferBytes": 0,
                    }
                t["deviceMs"] += row.device_ms
                t["compileMs"] += row.compile_ms
                t["launches"] += row.launches
                t["transferBytes"] += row.h2d_bytes + row.d2h_bytes
        for t in out.values():
            t["deviceMs"] = round(t["deviceMs"], 3)
            t["compileMs"] = round(t["compileMs"], 3)
        return out

    def snapshot(self) -> dict:
        uptime = max(time.monotonic() - self.started, 1e-9)
        with self._lock:
            sites = {}
            for name, s in sorted(self._sites.items()):
                d = s.acc.to_dict(uptime)
                d["trackedIdentities"] = len(s._seen)
                if s.recent_sigs:
                    d["recentCompileSigs"] = list(s.recent_sigs)
                if s.sig_ms:
                    d["measuredMs"] = {
                        cls: {"launches": row[0], "ewmaMs": round(row[1], 4)}
                        for cls, row in sorted(s.sig_ms.items())
                    }
                sites[name] = d
            principals = []
            for (tenant, idx, cls), row in sorted(self._principals.items()):
                p = row.to_dict(uptime)
                p["tenant"] = tenant
                p["index"] = idx
                p["opClass"] = cls
                principals.append(p)
            snap = {
                "uptimeSec": round(uptime, 3),
                "warm": self.warm,
                "totals": self.totals.to_dict(uptime),
                "persistentCacheHits": self.persistent_cache_hits,
                "unattributed": {
                    "compiles": self.unattributed.compiles,
                    "compileMs": round(self.unattributed.compile_ms, 3),
                },
                "sites": sites,
                "principals": principals,
                "storm": {
                    "threshold": self.storm_threshold,
                    "windowSec": self.storm_window_s,
                    "warmupSec": self.warmup_s,
                    "recent": list(self.storms),
                },
            }
        return snap

    def prometheus_text(self) -> str:
        out = []

        def emit(metric, help_text, rows):
            out.append(f"# HELP pilosa_{metric} {help_text}")
            out.append(f"# TYPE pilosa_{metric} counter")
            for labels, value in rows:
                lbl = ",".join(f'{k}="{v}"' for k, v in labels)
                out.append(f"pilosa_{metric}{{{lbl}}} {value}")

        with self._lock:
            site_rows = [(n, s.acc) for n, s in sorted(self._sites.items())]
            prin_rows = sorted(self._principals.items())
            unat = self.unattributed.compiles
        emit(
            "dev_compiles",
            "XLA compiles per ledger site",
            [((("site", n),), a.compiles) for n, a in site_rows]
            + [((("site", UNATTRIBUTED),), unat)],
        )
        emit(
            "dev_compile_ms",
            "XLA compile wall milliseconds per ledger site",
            [((("site", n),), round(a.compile_ms, 3)) for n, a in site_rows],
        )
        emit(
            "dev_launches",
            "device launches per ledger site",
            [((("site", n),), a.launches) for n, a in site_rows],
        )
        emit(
            "dev_device_ms",
            "device launch milliseconds per ledger site",
            [((("site", n),), round(a.device_ms, 3)) for n, a in site_rows],
        )
        emit(
            "dev_transfer_bytes",
            "host<->device bytes per ledger site",
            [
                ((("site", n), ("direction", "h2d")), a.h2d_bytes)
                for n, a in site_rows
            ]
            + [
                ((("site", n), ("direction", "d2h")), a.d2h_bytes)
                for n, a in site_rows
            ],
        )
        emit(
            "dev_tenant_launches",
            "device launches per principal",
            [
                (
                    (("tenant", t), ("index", i), ("op_class", c)),
                    a.launches,
                )
                for (t, i, c), a in prin_rows
            ],
        )
        emit(
            "dev_tenant_device_ms",
            "device milliseconds per principal",
            [
                (
                    (("tenant", t), ("index", i), ("op_class", c)),
                    round(a.device_ms, 3),
                )
                for (t, i, c), a in prin_rows
            ],
        )
        emit(
            "dev_tenant_transfer_bytes",
            "host<->device bytes per principal",
            [
                (
                    (("tenant", t), ("index", i), ("op_class", c)),
                    a.h2d_bytes + a.d2h_bytes,
                )
                for (t, i, c), a in prin_rows
            ],
        )
        emit("dev_storms", "recompile storm incidents", [((("kind", "recompile"),), len(self.storms))])
        return "\n".join(out) + "\n"


_LEDGER = Ledger()


def ledger() -> Ledger:
    return _LEDGER


def site(name) -> Site:
    return _LEDGER.site(name)


def snapshot() -> dict:
    return _LEDGER.snapshot()


def counters() -> dict:
    return _LEDGER.counters()


def tenant_totals() -> dict:
    return _LEDGER.tenant_totals()


def prometheus_text() -> str:
    return _LEDGER.prometheus_text()


def reset() -> None:
    _LEDGER.reset()


def measured_ms(site_name, sig_class):
    return _LEDGER.measured_ms(site_name, sig_class)


def mark_warm() -> None:
    _LEDGER.mark_warm()


def configure_storm(threshold=None, window_s=None, warmup_s=None) -> None:
    _LEDGER.configure_storm(threshold, window_s, warmup_s)


def on_storm(cb) -> None:
    _LEDGER.on_storm(cb)
