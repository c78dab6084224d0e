"""Distributed tracing (reference: tracing/tracing.go:22-50 Tracer/Span
interface + global tracer, tracing/opentracing/opentracing.go:31-76
Jaeger adapter with HTTP header inject/extract for cross-node traces).

The reference instruments ~80 spans across the executor, fragment
imports, API, and syncers via ``tracing.StartSpanFromContext``. Here the
active span is carried in a ``contextvars.ContextVar`` (the Python
analogue of ctx-carried spans), with explicit header inject/extract at
the node boundary so a query fanned out over HTTP appears as one trace:

    coordinator: api.query span  ─ inject → X-Trace-Id/X-Span-Id headers
    remote node: extract → handler span (child, same trace id)

Backends: :class:`NopTracer` (zero-cost default, like the reference's
default no-op tracer) and :class:`RecordingTracer` (in-process ring
buffer — the stand-in for the Jaeger agent exporter, which needs
network egress; spans can be dumped for offline analysis).
"""

from __future__ import annotations

import contextvars
import random
import sys
import threading
import time
from collections import deque

from pilosa_tpu.obs import qprofile

TRACE_HEADER = "X-Pilosa-Trace-Id"
SPAN_HEADER = "X-Pilosa-Span-Id"
TRACEPARENT_HEADER = "traceparent"

# Id minting (W3C trace-context widths: 128-bit trace ids, 64-bit span
# ids).  A per-process RNG — NOT a counter — so two nodes never mint the
# same trace id; ``seed_ids`` re-seeds it for deterministic tests.
# ``getrandbits`` is one C call under the GIL, so the span path takes no
# lock to mint.
_id_rng = random.Random()


def seed_ids(seed: int | None) -> None:
    """Re-seed the id generator (tests); ``None`` restores entropy."""
    _id_rng.seed(seed)


def new_trace_id() -> int:
    while True:
        tid = _id_rng.getrandbits(128)
        if tid:  # the zero id is invalid on the wire (W3C §3.2.2.3)
            return tid


def _new_span_id() -> int:
    while True:
        sid = _id_rng.getrandbits(64)
        if sid:
            return sid


_new_trace_id = new_trace_id  # the name tests/test_tracestore.py seeds by

# A span reads the monotonic clock at each end, and its thread's CPU clock
# beside it where the span table says so (``CPU_TREE``).  Its wall-clock
# start is the monotonic reading plus this anchor, taken once per
# process, so an exporter never derives it from the time of export.
_UNIX_ANCHOR_NS = time.time_ns() - time.monotonic_ns()

_get_ident = threading.get_ident

_active_span: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "pilosa_active_span", default=None
)

# Optional span sink: called with every finished span AFTER the tracer's
# own ``_record``.  This is how the per-node TraceStore observes spans
# without replacing the configured tracer (obs/tracestore.py installs
# itself here at import-time of the store module).
_span_sink = None


def set_span_sink(sink) -> None:
    global _span_sink
    _span_sink = sink


# ---------------------------------------------------------------------------
# The span table: every span name the program may open, with its layer
# (PERF.md section 3) and the per-layer metric of BENCHMARK.json it is
# for.  ``Span.finish`` adds to the name's row; ``/debug/vars`` serves
# the rows as the block ``spans`` (every name there from process start,
# at zero), docs/observability.md prints them, and a span opened under a
# name that is not here is an error.  Names are ``<layer>.<what>``.
# ---------------------------------------------------------------------------

TRACE_ONLY = "trace only"

# Marks, a fourth entry of a row of the table.  The thread's CPU clock is
# a system call, and where it costs microseconds (PERF.md section 3: 5.6
# us a read on the chip's host, in 10 ms ticks, and under one GIL three
# to four times its own time in reads/s) a span cannot read it by
# default.  It is read by every span below a ``CPU_TREE`` root (the one
# dispatcher's two) but a ``CPU_LEAF`` (a funnel of microseconds opened
# dozens of times a flight: what it runs stays in its parent's
# ``self_cpu_seconds``), and by a ``DEVICE_WAIT`` span: the served path
# waits for a device result under that name, what such a span's thread
# did not run is its device wait, and that is carried up the tree
# (``device_wait_seconds`` of every row above it).  A handler's root
# ``http.<route>`` reads none: the handler books the request's CPU, which
# it reads anyway, into it (``book_cpu``).
CPU_TREE = "cpu clock, its tree"
CPU_LEAF = "no cpu clock"
DEVICE_WAIT = "cpu clock, device wait"

_LISTENER = "listener"
_BATCHER = "QoS / batcher"
_PLANNER = "planner / rescache"
_LANES = "executor lanes"
_KERNELS = "kernels"
_CLUSTER = "cluster"
_INGEST = "ingest"

_HOST_MS = "executor.host_ms_per_flight"
_STALLED_READ = "listener.stalled_ms_per_read"
# its wall time, and the three it is the sum of; the executor's shares of
# a flight divide by its seconds or its count
_FLIGHT = (
    "batcher.flight_busy_pct, batcher.cpu_ms_per_flight,"
    " batcher.device_wait_ms_per_flight, batcher.stalled_ms_per_flight, "
    + _HOST_MS + ", executor.bsi_pct_of_flight, executor.percall_pct_of_flight"
)

SPAN_TABLE = (
    # name, layer, every metric that reads the row[, mark]
    ("http.query", _LISTENER, "listener.ms_per_read, " + _STALLED_READ),
    ("http.decode", _LISTENER, "listener.ms_per_read"),
    ("api.parse", _LISTENER, "listener.ms_per_read"),
    ("http.encode", _LISTENER, "listener.ms_per_read"),
    ("qos.admit", _BATCHER, "qos.admit_ms_per_read"),
    ("qos.tick", _BATCHER, TRACE_ONLY),
    ("rescache.probe", _PLANNER, TRACE_ONLY),
    ("batcher.queueWait", _BATCHER, "batcher.queue_wait_ms, " + _STALLED_READ),
    ("batcher.dispatch", _BATCHER, _STALLED_READ),
    ("batcher.collect", _BATCHER, TRACE_ONLY, CPU_TREE),
    ("batcher.flight", _BATCHER, _FLIGHT, CPU_TREE),
    ("planner.plan", _PLANNER, _HOST_MS),
    ("executor.Execute", _LANES, _HOST_MS),
    ("executor.ExecuteBatch", _LANES, _HOST_MS),
    ("executor.batchPairCount", _LANES, _HOST_MS),
    ("executor.batchCountTree", _LANES, _HOST_MS),
    ("executor.batchBitmapTree", _LANES, _HOST_MS),
    ("executor.batchBSI", _LANES, "executor.bsi_pct_of_flight"),
    ("executor.bsiRangeBatch", _LANES, _HOST_MS),
    ("executor.bsiRangeCountBatch", _LANES, _HOST_MS),
    ("executor.bsiFilteredCountBatch", _LANES, _HOST_MS),
    ("executor.bsiSumBatch", _LANES, _HOST_MS),
    ("executor.bsiSumPull", _LANES, _HOST_MS),
    ("executor.batchGroupBy", _LANES, _HOST_MS),
    ("executor.groupByBatch", _LANES, _HOST_MS),
    ("executor.groupByKLevel", _LANES, _HOST_MS),
    ("executor.stackBuild", _LANES, _HOST_MS),
    ("stacks.refresh", _LANES, "stacks.refresh_ms_per_import"),
    ("executor.bsiSplit", _LANES, _HOST_MS),
    ("executor.demux", _LANES, _HOST_MS, CPU_LEAF),
    ("executor.mapReduce", _CLUSTER, _HOST_MS),
    ("kernels.h2d", _KERNELS, TRACE_ONLY, CPU_LEAF),
    ("kernels.enqueue", _KERNELS, TRACE_ONLY, CPU_LEAF),
    ("kernels.pull", _KERNELS, "kernels.pull_ms_per_launch", DEVICE_WAIT),
    ("dist.fanout", _CLUSTER, TRACE_ONLY),
    ("dist.httpFanout", _CLUSTER, TRACE_ONLY),
    ("dist.meshDispatch", _CLUSTER, TRACE_ONLY),
    ("holderSyncer.SyncHolder", _CLUSTER, TRACE_ONLY),
    ("field.Import", _INGEST, TRACE_ONLY),
)


# What a span does with the CPU clock (``Span._timed``): nothing; read it
# and book the rest of its time as device wait; read it and have every
# span below read it.  A row says what its spans do before their parents'
# say (``_LEAF``: nothing, whatever the parent).
_LEAF, _UNTIMED, _TIMED_WAIT, _TIMED_TREE = -1, 0, 1, 2
_ROW_TIMED = {"": _UNTIMED, CPU_TREE: _TIMED_TREE, CPU_LEAF: _LEAF, DEVICE_WAIT: _TIMED_WAIT}


class _Row:
    """One name's totals.  Plain adds: a span finishes on the thread that
    ran it, and between reading and storing an int attribute CPython
    switches no thread, so the path takes no lock."""

    __slots__ = (
        "name", "layer", "metric", "mark", "timed", "count", "ns",
        "self_ns", "items", "cpu_ns", "self_cpu_ns", "device_wait_ns",
    )

    def __init__(self, name: str, layer: str, metric: str, mark: str = ""):
        self.name = name
        self.layer = layer
        self.metric = metric
        self.mark = mark
        self.timed = _ROW_TIMED[mark]
        self.count = 0
        self.ns = 0
        self.self_ns = 0
        self.items = 0
        self.cpu_ns = 0
        self.self_cpu_ns = 0
        self.device_wait_ns = 0


_rows: dict[str, _Row] = {}


def register(name: str, layer: str, metric: str = TRACE_ONLY, mark: str = "") -> None:
    """Add one name to the table (idempotent).  A name has exactly two
    segments; the first is the block it is served under."""
    parts = name.split(".")
    if len(parts) != 2 or not all(parts):
        raise ValueError(f"span name {name!r} is not <layer>.<what>")
    if name not in _rows:
        _rows[name] = _Row(name, layer, metric, mark)


def register_family(prefix: str, whats, layer: str, metric: str = TRACE_ONLY) -> None:
    """Names an owner derives from a list of its own (``http.<route>``,
    ``executor.execute<Call>``), registered where that list lives."""
    for what in whats:
        register(f"{prefix}{what}", layer, metric)


for _entry in SPAN_TABLE:
    register(*_entry)


def registered() -> list[tuple[str, str, str]]:
    """(name, layer, metric) of every registered name, in table order;
    a row's mark stands after its metric."""
    return [
        (r.name, r.layer, f"{r.metric}; {r.mark}" if r.mark else r.metric)
        for r in _rows.values()
    ]


def spans_snapshot() -> dict:
    """The table for ``/debug/vars``: ``{first segment: {second segment:
    {count, seconds, self_seconds, items, cpu_seconds, self_cpu_seconds,
    device_wait_seconds}}}``.  For any row ``seconds - cpu_seconds -
    device_wait_seconds`` is what its threads neither ran nor waited for
    the device: runnable without the interpreter, or waiting for a lock,
    an upload or a queue."""
    out: dict = {}
    for r in list(_rows.values()):
        block, what = r.name.split(".")
        out.setdefault(block, {})[what] = {
            "count": r.count,
            "seconds": r.ns * 1e-9,
            "self_seconds": r.self_ns * 1e-9,
            "items": r.items,
            "cpu_seconds": r.cpu_ns * 1e-9,
            "self_cpu_seconds": r.self_cpu_ns * 1e-9,
            "device_wait_seconds": r.device_wait_ns * 1e-9,
        }
    return out


def table_markdown(rows=None) -> str:
    """The table as docs/observability.md prints it (a test holds the
    document to this)."""
    lines = ["| span | layer | metric |", "|---|---|---|"]
    lines += [
        f"| `{n}` | {layer} | {m} |" for n, layer, m in rows or registered()
    ]
    return "\n".join(lines)


# ``jax.profiler.TraceAnnotation``, found once JAX is loaded and never
# imported from here: a process without JAX opens spans without it.
_annotation = None


def _find_annotation():
    global _annotation
    prof = sys.modules.get("jax.profiler")
    if prof is not None:
        _annotation = prof.TraceAnnotation
    return _annotation


class SpanContext:
    """Wire-propagatable identity of a span.  ``remote`` marks a context
    extracted from incoming headers: a span whose parent is remote is a
    *local root* — the first span of this trace on this node — which is
    where tail-sampling decisions attach."""

    __slots__ = ("trace_id", "span_id", "remote")

    def __init__(self, trace_id: int, span_id: int, remote: bool = False):
        self.trace_id = trace_id
        self.span_id = span_id
        self.remote = remote


def trace_root(trace_id: int, local_root: bool = True) -> SpanContext:
    """The context under which a span opens parentless in a trace whose
    id is already minted (the batcher's flight: ``collect`` and ``flight``
    are siblings in one trace, and the flight, which ends last, is its
    local root)."""
    return SpanContext(trace_id, 0, remote=local_root)


class Span:
    """One timed operation (reference tracing.Span :44-50).

    At ``finish`` a span adds its duration to its parent's ``child_ns``
    and to its name's row of the span table, self time (duration minus
    what its children covered) included, so neither needs the tree.

    ``cpu_ns`` is what its thread ran between its two ends (same-thread
    children included) where the span reads that clock (``CPU_TREE``
    above; a handler's root is given its request's), and goes up as ``child_cpu_ns`` only to a parent opened on
    that thread: a fan-out leg's CPU is not in its parent's clock.  A
    span that finishes on another thread than it opened on, or was built
    after the fact (``record_span``), books none: it is a wait by
    construction.  ``wait_ns`` is the device wait of its tree on its
    thread: that of the ``DEVICE_WAIT`` spans under it, carried up the
    same way."""

    __slots__ = (
        "tracer", "name", "parent_id", "local_root", "context", "start_ns",
        "duration", "tags", "child_ns", "cpu_ns", "child_cpu_ns", "wait_ns",
        "_parent", "_row", "_tid", "_timed", "_cpu0", "_token", "_phandle", "_ann",
    )

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        parent: SpanContext | None,
        parent_span: "Span | None" = None,
    ):
        row = _rows.get(name)
        if row is None:
            raise ValueError(
                f"span name {name!r} is not in the span table"
                " (pilosa_tpu/obs/tracing.py)"
            )
        self._row = row
        self.tracer = tracer
        self.name = name
        self.parent_id = parent.span_id if parent else 0
        # local root = no parent at all, or a parent extracted from the
        # wire (the first span of the trace on THIS node)
        self.local_root = parent is None or parent.remote
        trace_id = parent.trace_id if parent else new_trace_id()
        self.context = SpanContext(trace_id, _new_span_id())
        self._parent = parent_span
        self.child_ns = 0
        self.cpu_ns = 0
        self.child_cpu_ns = 0
        self.wait_ns = 0
        self._tid = _get_ident()
        timed = row.timed
        if timed == _UNTIMED:
            if parent_span is not None and parent_span._timed == _TIMED_TREE:
                timed = _TIMED_TREE
        elif timed == _LEAF:
            timed = _UNTIMED
        self._timed = timed
        self.start_ns = time.monotonic_ns()
        self._cpu0 = time.thread_time_ns() if timed else 0
        self.duration = None
        self.tags: dict = {}
        self._token = None
        self._phandle = None
        self._ann = None

    @property
    def start(self) -> float:
        return self.start_ns * 1e-9

    @property
    def start_unix_ns(self) -> int:
        return _UNIX_ANCHOR_NS + self.start_ns

    def set_tag(self, key: str, value) -> "Span":
        self.tags[key] = value
        return self

    def finish(self, end_ns: int | None = None) -> None:
        if self.duration is None:
            tid = self._tid
            timed = self._timed
            cpu = 0
            if end_ns is None and _get_ident() == tid:
                ns = time.monotonic_ns() - self.start_ns
                if timed:
                    cpu = self.cpu_ns = time.thread_time_ns() - self._cpu0
            else:  # built after the fact, or ended by another thread
                ns = (end_ns if end_ns is not None else time.monotonic_ns()) - self.start_ns
                timed = tid = 0
            self.duration = ns * 1e-9
            row = self._row
            wait = self.wait_ns
            if timed == _TIMED_WAIT and ns > cpu:
                # (a tick clock can read more CPU than a short span lasted)
                wait = self.wait_ns = wait + ns - cpu
            row.count += 1
            row.ns += ns
            row.cpu_ns += cpu
            # children that ran side by side (fan-out legs) can cover
            # more than the parent's own time
            own = ns - self.child_ns
            if own > 0:
                row.self_ns += own
            own = cpu - self.child_cpu_ns
            if own > 0:
                row.self_cpu_ns += own
            if wait:
                row.device_wait_ns += wait
            n = self.tags.get("n")
            if n:
                row.items += n
            parent = self._parent
            if parent is not None:
                parent.child_ns += ns
                if parent._tid == tid:
                    parent.child_cpu_ns += cpu
                    parent.wait_ns += wait
                self._parent = None  # a kept span keeps no tree alive
            self.tracer._record(self)
            if _span_sink is not None:
                _span_sink(self)

    # context-manager + ambient-activation protocol.  While a profiler
    # session runs in the process (the program starts none), entering a
    # span also enters a profiler annotation of the same name, so the
    # session holds the program's spans on its own clock; with no session
    # that is one native test.  Under ``?profile=true`` the span is
    # mirrored into the active QueryProfile as well, whatever the tracer.
    def __enter__(self) -> "Span":
        self._token = _active_span.set(self)
        if qprofile.profiling():
            self._phandle = qprofile.span_enter(self.name)
        cls = _annotation or _find_annotation()
        if cls is not None and cls.is_enabled():
            ann = self._ann = cls(self.name)
            ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        ann = self._ann
        if ann is not None:
            ann.__exit__(None, None, None)
            self._ann = None
        if self._phandle is not None:
            qprofile.span_exit(self._phandle, self.tags)
            self._phandle = None
        if self._token is not None:
            _active_span.reset(self._token)
            self._token = None
        self.finish()


class Tracer:
    """reference tracing.Tracer :32-41."""

    def start_span(
        self, name: str, child_of: SpanContext | None = None
    ) -> Span:
        if child_of is None:
            parent = _active_span.get()
            if parent is not None:
                return Span(self, name, parent.context, parent)
        return Span(self, name, child_of)

    def inject_headers(self, ctx: SpanContext, headers: dict) -> None:
        """opentracing.go:58-66 InjectHTTPHeaders — native headers plus a
        W3C ``traceparent`` (version 00, sampled flag set) for interop."""
        headers[TRACE_HEADER] = str(ctx.trace_id)
        headers[SPAN_HEADER] = str(ctx.span_id)
        headers[TRACEPARENT_HEADER] = format_traceparent(ctx)

    def extract_headers(self, headers) -> SpanContext | None:
        """opentracing.go:68-76 ExtractHTTPHeaders.  Native headers win;
        falls back to W3C ``traceparent``."""
        trace_id = headers.get(TRACE_HEADER)
        span_id = headers.get(SPAN_HEADER)
        if trace_id and span_id:
            try:
                return SpanContext(int(trace_id), int(span_id), remote=True)
            except ValueError:
                return None
        return parse_traceparent(headers.get(TRACEPARENT_HEADER))

    def _record(self, span: Span) -> None:
        pass


class NopTracer(Tracer):
    pass


class RecordingTracer(Tracer):
    """Ring-buffer recorder (Jaeger-exporter stand-in)."""

    def __init__(self, capacity: int = 4096):
        self._lock = threading.Lock()
        self.spans: deque[Span] = deque(maxlen=capacity)

    def _record(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)

    def finished(self, name: str | None = None) -> list[Span]:
        with self._lock:
            return [s for s in self.spans if name is None or s.name == name]

    def traces(self) -> dict[int, list[Span]]:
        """Finished spans grouped by trace id, in finish order."""
        with self._lock:
            out: dict[int, list[Span]] = {}
            for s in self.spans:
                out.setdefault(s.context.trace_id, []).append(s)
            return out


class ExportingTracer(RecordingTracer):
    """Samples spans at the root and forwards finished spans to an
    exporter (reference tracing/opentracing/opentracing.go:31-76 Jaeger
    adapter + sampler config server/config.go:139-145).

    Sampling is head-based per trace: the root span's trace id decides,
    so a trace is exported whole or not at all."""

    def __init__(self, exporter, sample_rate: float = 1.0, capacity: int = 4096):
        super().__init__(capacity)
        self.exporter = exporter
        self.sample_rate = max(0.0, min(1.0, sample_rate))

    def _sampled(self, trace_id: int) -> bool:
        if self.sample_rate >= 1.0:
            return True
        if self.sample_rate <= 0.0:
            return False
        # cheap deterministic hash of the trace id -> [0, 1)
        return ((trace_id * 2654435761) & 0xFFFFFFFF) / 2**32 < self.sample_rate

    def _record(self, span: Span) -> None:
        super()._record(span)
        if self._sampled(span.context.trace_id):
            self.exporter.export(span)

    def close(self) -> None:
        self.exporter.close()


def format_traceparent(ctx: SpanContext) -> str:
    """W3C trace-context header: 00-<32hex trace>-<16hex span>-<flags>."""
    return f"00-{ctx.trace_id & (2**128 - 1):032x}-{ctx.span_id & (2**64 - 1):016x}-01"


def parse_traceparent(value) -> SpanContext | None:
    """Parse a W3C ``traceparent`` header; ``None`` on anything invalid
    (wrong field widths, non-hex, all-zero ids, reserved version ff)."""
    if not value:
        return None
    parts = value.strip().split("-")
    if len(parts) < 4:
        return None
    version, trace_hex, span_hex = parts[0], parts[1], parts[2]
    if len(version) != 2 or len(trace_hex) != 32 or len(span_hex) != 16:
        return None
    if version.lower() == "ff":
        return None
    try:
        int(version, 16)
        trace_id = int(trace_hex, 16)
        span_id = int(span_hex, 16)
    except ValueError:
        return None
    if not trace_id or not span_id:
        return None
    return SpanContext(trace_id, span_id, remote=True)


# Global tracer (reference tracing.GlobalTracer :22-29).
_global = Tracer.__new__(NopTracer)  # type: ignore[assignment]


def get_tracer() -> Tracer:
    return _global


def set_tracer(t: Tracer) -> None:
    global _global
    _global = t


def start_span(name: str, child_of: SpanContext | None = None) -> Span:
    """reference tracing.StartSpanFromContext — ambient parenting via the
    context variable when ``child_of`` is not given."""
    return _global.start_span(name, child_of)


def record_span(
    name: str, start_ns: int, end_ns: int, tags: dict | None = None
) -> Span:
    """A span built after the fact, under the active span, from two
    ``time.monotonic_ns`` readings taken elsewhere (the batcher's
    dispatcher times a member's queue wait and dispatch; the member
    records them on wake-up).  It reaches the table, the store and an
    active profile like any span; it was never entered, so the
    profiler's trace does not hold it, and it books no CPU: it is a
    wait."""
    span = start_span(name)
    span.start_ns = start_ns
    if tags:
        span.tags.update(tags)
    qprofile.annotate(name, (end_ns - start_ns) * 1e-6, **span.tags)
    span.finish(end_ns)
    return span


def book_cpu(span: Span, cpu_ns: int) -> None:
    """Thread CPU read elsewhere for a finished span that read none: the
    HTTP handler's own pair around a request (server/http.py
    ``handle_one_request``: from before the request's line to the
    response written and booked, a little more than the root span
    covers), so that a request costs no clock read beside those."""
    span.cpu_ns = cpu_ns
    row = span._row
    row.cpu_ns += cpu_ns
    own = cpu_ns - span.child_cpu_ns
    if own > 0:
        row.self_cpu_ns += own


def active_span() -> Span | None:
    return _active_span.get()
