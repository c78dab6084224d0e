"""HTTP transport (reference: http/handler.go, 1702 LoC).

Route surface mirrors the reference's public router (handler.go:276-314):

    GET  /                               -> redirect note
    GET  /version /status /info /schema
    POST /schema
    POST /index/{index}                  create index
    GET  /index/{index}
    DELETE /index/{index}
    POST /index/{index}/query            PQL body -> {"results": [...]}
    POST /index/{index}/field/{field}    create field
    GET/DELETE /index/{index}/field/{field}
    POST /index/{index}/field/{field}/import           JSON batch
    POST /index/{index}/field/{field}/import-roaring/{shard}  binary roaring
    GET  /export?index=&field=           CSV
    GET  /internal/shards/max
    POST /internal/translate/keys

JSON replaces the reference's protobuf codec (encoding/proto) as this
framework's wire format; the roaring import payload is binary-compatible
with reference clients. Long-running queries log at a threshold like the
reference's long-query-time (handler.go:246-248).
"""

from __future__ import annotations

import gzip as gzip_mod
import json
import logging
import math
import re
import ssl
import threading
import time
from http import HTTPStatus
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlsplit

from pilosa_tpu import deadline
from pilosa_tpu.deadline import DeadlineExceeded
from pilosa_tpu.obs import devledger, slo, tracestore, tracing
from pilosa_tpu.server.api import API, ApiError, encode_json
from pilosa_tpu.server.qos import ShedError

logger = logging.getLogger(__name__)

# SLO op class by route, for routes whose class is knowable from the
# path alone; query routes are classified by the API layer (it has the
# parsed call tree) via slo.note_class, which takes precedence.
_SLO_ROUTE_CLASS = {
    "query": slo.OP_READ_OTHER,
    "import_": slo.OP_IMPORT,
    "import_roaring": slo.OP_IMPORT,
    "translate_keys": slo.OP_TRANSLATE,
    "translate_ids": slo.OP_TRANSLATE,
}

# GET /debug discoverability index: every registered debug surface with
# a one-line description (there are 10+ — nobody remembers them all).
_DEBUG_ENDPOINTS: list[tuple[str, str]] = [
    ("/debug/vars",
     "expvar-style dump: counters, histograms, kernels, device budget"),
    ("/debug/history",
     "ring-buffer metrics history (?series=glob&since=&step=&cluster=true)"),
    ("/debug/slo",
     "per-op-class latency quantiles, error budgets, burn-rate alerts"),
    ("/debug/qos",
     "cost-governed admission: per-tenant queues, shed/degrade ladder"),
    ("/debug/events",
     "typed cluster event journal (?since= cursor, ?cluster=true merge)"),
    ("/debug/traces",
     "tail-sampled trace store (?id= spans, ?cluster=true assembly)"),
    ("/debug/incidents",
     "flight-recorder bundles: alert edges, 504 spikes, trend incidents"),
    ("/debug/postmortem",
     "sealed crash bundles from the black box (?id=, ?cluster=true merge)"),
    ("/debug/devcosts",
     "device cost ledger: compiles/launches/transfers per site+tenant"),
    ("/debug/slow-queries",
     "bounded worst-offender log with full execution profiles"),
    ("/debug/jobs",
     "background-job progress: resize, anti-entropy, import drains"),
    ("/debug/fragments",
     "per-fragment container stats, op-log length, device residency"),
    ("/debug/threads", "per-thread stack dump"),
    ("/debug/profile",
     "sampled CPU profile, flamegraph-collapsed (?seconds=&interval_ms=)"),
    ("/debug/memory", "RSS, host mirror bytes, HBM budget, GC state"),
]

_ROUTES: list[tuple[str, re.Pattern, str]] = [
    ("GET", re.compile(r"^/$"), "root"),
    ("GET", re.compile(r"^/version$"), "version"),
    ("GET", re.compile(r"^/status$"), "status"),
    ("GET", re.compile(r"^/info$"), "info"),
    ("GET", re.compile(r"^/schema$"), "get_schema"),
    ("POST", re.compile(r"^/schema$"), "post_schema"),
    ("GET", re.compile(r"^/metrics$"), "metrics"),
    ("GET", re.compile(r"^/debug$"), "debug_index"),
    ("GET", re.compile(r"^/debug/vars$"), "debug_vars"),
    ("GET", re.compile(r"^/debug/history$"), "debug_history"),
    ("GET", re.compile(r"^/debug/slo$"), "debug_slo"),
    ("GET", re.compile(r"^/debug/qos$"), "debug_qos"),
    ("GET", re.compile(r"^/debug/slow-queries$"), "debug_slow_queries"),
    ("GET", re.compile(r"^/debug/threads$"), "debug_threads"),
    ("GET", re.compile(r"^/debug/profile$"), "debug_profile"),
    ("GET", re.compile(r"^/debug/memory$"), "debug_memory"),
    ("GET", re.compile(r"^/debug/events$"), "debug_events"),
    ("GET", re.compile(r"^/debug/traces$"), "debug_traces"),
    ("GET", re.compile(r"^/debug/incidents$"), "debug_incidents"),
    ("GET", re.compile(r"^/debug/postmortem$"), "debug_postmortem"),
    ("GET", re.compile(r"^/debug/devcosts$"), "debug_devcosts"),
    ("GET", re.compile(r"^/debug/jobs$"), "debug_jobs"),
    ("GET", re.compile(r"^/debug/fragments$"), "debug_fragments"),
    ("GET", re.compile(r"^/internal/diagnostics$"), "diagnostics"),  # graftlint: disable=dispatch-parity -- operator debug endpoint (curl/monitoring), never called node-to-node
    ("GET", re.compile(r"^/export$"), "export"),
    ("POST", re.compile(r"^/index/(?P<index>[^/]+)/query$"), "query"),
    ("POST", re.compile(r"^/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import$"), "import_"),
    ("POST", re.compile(r"^/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import-roaring/(?P<shard>\d+)$"), "import_roaring"),
    ("POST", re.compile(r"^/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)$"), "create_field"),
    ("GET", re.compile(r"^/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)$"), "get_field"),
    ("DELETE", re.compile(r"^/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)$"), "delete_field"),
    ("POST", re.compile(r"^/index/(?P<index>[^/]+)$"), "create_index"),
    ("GET", re.compile(r"^/index/(?P<index>[^/]+)$"), "get_index"),
    ("DELETE", re.compile(r"^/index/(?P<index>[^/]+)$"), "delete_index"),
    ("GET", re.compile(r"^/internal/shards/max$"), "shards_max"),
    ("POST", re.compile(r"^/internal/translate/keys$"), "translate_keys"),
    ("POST", re.compile(r"^/internal/translate/ids$"), "translate_ids"),
    ("GET", re.compile(r"^/internal/translate/log$"), "translate_log"),
    ("POST", re.compile(r"^/internal/translate/restore$"), "translate_restore"),
    ("POST", re.compile(r"^/cluster/resize/set-coordinator$"), "set_coordinator"),
    ("POST", re.compile(r"^/cluster/resize/abort$"), "resize_abort"),
    ("POST", re.compile(r"^/cluster/resize/remove-node$"), "remove_node"),
    ("POST", re.compile(r"^/recalculate-caches$"), "recalculate_caches"),
    ("POST", re.compile(r"^/internal/cluster/message$"), "cluster_message"),
    ("GET", re.compile(r"^/internal/attr/blocks$"), "attr_blocks"),
    ("POST", re.compile(r"^/internal/attr/block/data$"), "attr_block_data"),
    ("GET", re.compile(r"^/internal/fragment/blocks$"), "fragment_blocks"),
    ("POST", re.compile(r"^/internal/fragment/block/data$"), "fragment_block_data"),
    ("GET", re.compile(r"^/internal/fragment/data$"), "fragment_data"),
    ("GET", re.compile(r"^/internal/fragments$"), "fragments"),
    ("POST", re.compile(r"^/internal/resize/fetch$"), "resize_fetch"),
    ("POST", re.compile(r"^/internal/migrate/begin$"), "migrate_begin"),
    ("GET", re.compile(r"^/internal/migrate/chunk$"), "migrate_chunk"),
    ("POST", re.compile(r"^/internal/migrate/delta$"), "migrate_delta"),
    ("POST", re.compile(r"^/internal/migrate/end$"), "migrate_end"),
    ("POST", re.compile(r"^/internal/migrate/fetch$"), "migrate_fetch"),
    ("POST", re.compile(r"^/internal/migrate/finalize$"), "migrate_finalize"),
    ("POST", re.compile(r"^/cluster/resize/resume$"), "resize_resume"),
    ("GET", re.compile(r"^/internal/nodes$"), "nodes"),
]

# one span name per route (``http.<route>``), registered from the route
# table itself; ``http.query`` and its children are in tracing's own table
tracing.register_family(
    "http.", dict.fromkeys(name for _, _, name in _ROUTES), "listener"
)


# the stdlib's limits on a request's head (http.client._MAXLINE,
# _MAXHEADERS; the blank line that ends the head counts as one of the 100)
_MAX_LINE = 65536
_MAX_HEAD_LINES = 100
# a response up to this size is joined to its head and sent as one buffer
# (under a kilobyte the copy costs 0.3 us less than a second buffer does);
# past it head and body go to the kernel as two buffers of one sendmsg and
# the body is not copied
_JOIN_MAX_BYTES = 2048


class HeadRefused(Exception):
    """A request's head passes one of the listener's limits."""


class Headers:
    """A request's header fields as the handler reads them: ``get``
    ignores the name's case and answers the field's first value."""

    __slots__ = ("_first",)

    def __init__(self, first: dict[str, str]):
        self._first = first

    def get(self, name: str, default=None):
        return self._first.get(name.lower(), default)


def read_headers(rfile) -> Headers:
    """The header lines of a request, read up to the blank line.

    What ``http.client.parse_headers`` and the ``email`` parser under it
    make of the same bytes, without the ``Message``: a field is
    ``name:`` at the start of a line, the name of printable ASCII without
    a space; a line that starts with a space or a tab continues the field
    above it; a line that is neither ends the fields (the rest is read
    and dropped).  A value loses the blanks after the colon and the line
    ending; latin-1, as the stdlib decodes it.  Lines end at LF alone:
    the ``email`` parser also breaks one at a bare CR, VT, FF and the
    like, which lets a value smuggle a field in."""
    first: dict[str, str] = {}
    # the field a continuation line extends: the one just read, unless an
    # earlier field of the same name already gave ``get`` its answer
    name = None
    open_ = True  # no line that is not a field has been seen
    lines = 0
    while True:
        raw = rfile.readline(_MAX_LINE + 1)
        if len(raw) > _MAX_LINE:
            raise HeadRefused(
                "Line too long",
                "got more than %d bytes when reading header line" % _MAX_LINE,
            )
        lines += 1
        if lines > _MAX_HEAD_LINES:
            raise HeadRefused(
                "Too many headers",
                "got more than %d headers" % _MAX_HEAD_LINES,
            )
        if raw in (b"\r\n", b"\n", b""):
            break
        if not open_:
            continue
        line = raw.decode("iso-8859-1")
        if line[0] in " \t":
            if name is not None:
                first[name] += line
            continue
        name = None
        key, colon, rest = line.partition(":")
        if not (colon and key.isascii() and key.isprintable()) or " " in key:
            # an envelope line ("From ...") is skipped, as the stdlib
            # skips it; any other line that names no field ends them
            open_ = line.startswith("From ")
        elif key:  # a line that starts with the colon is dropped
            key = key.lower()
            if key not in first:
                first[key] = rest
                name = key
    for key, value in first.items():
        first[key] = value.lstrip(" \t\r\n").rstrip("\r\n")
    return Headers(first)


class _HandlerCpu:
    """Thread CPU the handlers spent on requests, and how many requests:
    one cell a connection, written by that connection's thread alone, so a
    request takes no lock for it; summed on read."""

    class Cell:
        __slots__ = ("seconds", "requests")

        def __init__(self):
            self.seconds = 0.0
            self.requests = 0

    def __init__(self):
        self._lock = threading.Lock()
        self._open: set = set()
        self._closed = self.Cell()

    def open(self) -> "_HandlerCpu.Cell":
        cell = self.Cell()
        with self._lock:
            self._open.add(cell)
        return cell

    def close(self, cell) -> None:
        with self._lock:
            self._open.discard(cell)
            self._closed.seconds += cell.seconds
            self._closed.requests += cell.requests

    def snapshot(self) -> dict:
        with self._lock:
            cells = [self._closed, *self._open]
            return {
                "handlerCpuSeconds": sum(c.seconds for c in cells),
                "requests": sum(c.requests for c in cells),
            }


# process totals (thread CPU is the process's interpreter, whichever
# listener of an in-process cluster the thread serves)
handler_cpu = _HandlerCpu()


class Handler(BaseHTTPRequestHandler):
    api: API = None  # set by make_server
    long_query_time: float = 0.0
    default_deadline: float = 0.0  # seconds; 0 = no default deadline
    protocol_version = "HTTP/1.1"
    # TCP_NODELAY on accepted sockets (socketserver applies this in
    # StreamRequestHandler.setup): with keep-alive connections (the
    # pooled internal client), Nagle + the peer's delayed ACK would add
    # ~40 ms to every small response
    disable_nagle_algorithm = True

    # -- plumbing -----------------------------------------------------------

    def log_message(self, fmt, *args):  # route through logging, not stderr
        logger.debug(fmt, *args)

    # gzip floor: tiny bodies cost more in header + CPU than they save
    _GZIP_MIN_BYTES = 512

    # -- framing: a request's head in, one buffer out ----------------------

    def setup(self):
        super().setup()
        self._cpu = handler_cpu.open()

    def finish(self):
        handler_cpu.close(self._cpu)
        super().finish()

    def handle_one_request(self):
        """The stdlib's, between two reads of the thread's CPU clock: one
        before the request's line is waited for (a blocked thread spends
        none), one after the response is written and booked."""
        t0 = time.thread_time()
        self.raw_requestline = b""
        self._root_span = None
        super().handle_one_request()
        if self.raw_requestline:
            cpu = time.thread_time() - t0
            self._cpu.seconds += cpu
            self._cpu.requests += 1
            if self._root_span is not None:
                # the route's span reads no CPU clock of its own
                tracing.book_cpu(self._root_span, int(cpu * 1e9))

    def parse_request(self) -> bool:
        """The request's line as the stdlib splits it, then the fields by
        ``read_headers``: no ``email`` parser, no ``Message``.  The same
        refusals (400, 505, 431), keep-alive rules and ``Expect``."""
        self.command = None  # set in case of error on the first line
        self.request_version = version = self.default_request_version
        self.close_connection = True
        self.requestline = requestline = str(
            self.raw_requestline, "iso-8859-1"
        ).rstrip("\r\n")
        words = requestline.split()
        if not words:
            return False
        if len(words) >= 3:  # enough to determine the protocol version
            version = words[-1]
            try:
                if not version.startswith("HTTP/"):
                    raise ValueError
                base_version_number = version.split("/", 1)[1]
                numbers = base_version_number.split(".")
                # one ".", digits only, of a reasonable length
                if len(numbers) != 2 or not all(
                    c.isdigit() and len(c) <= 10 for c in numbers
                ):
                    raise ValueError
                version_number = int(numbers[0]), int(numbers[1])
            except ValueError:
                self.send_error(
                    HTTPStatus.BAD_REQUEST,
                    "Bad request version (%r)" % version,
                )
                return False
            if version_number >= (1, 1):
                self.close_connection = False
            if version_number >= (2, 0):
                self.send_error(
                    HTTPStatus.HTTP_VERSION_NOT_SUPPORTED,
                    "Invalid HTTP version (%s)" % base_version_number,
                )
                return False
            self.request_version = version
        if not 2 <= len(words) <= 3:
            self.send_error(
                HTTPStatus.BAD_REQUEST,
                "Bad request syntax (%r)" % requestline,
            )
            return False
        command, path = words[:2]
        if len(words) == 2:
            self.close_connection = True
            if command != "GET":
                self.send_error(
                    HTTPStatus.BAD_REQUEST,
                    "Bad HTTP/0.9 request type (%r)" % command,
                )
                return False
        # a target that starts with "//" would read as a URI without a
        # scheme further on (gh-87389)
        if path.startswith("//"):
            path = "/" + path.lstrip("/")
        self.command, self.path = command, path
        try:
            self.headers = read_headers(self.rfile)
        except HeadRefused as e:
            self.send_error(
                HTTPStatus.REQUEST_HEADER_FIELDS_TOO_LARGE, *e.args
            )
            return False
        conntype = self.headers.get("Connection", "").lower()
        if conntype == "close":
            self.close_connection = True
        elif conntype == "keep-alive":
            self.close_connection = False
        if (
            self.headers.get("Expect", "").lower() == "100-continue"
            and self.request_version >= "HTTP/1.1"
        ):
            return self.handle_expect_100()
        return True

    # status line and ``Server`` by code; ``Date`` as of the second it was
    # last formatted in: (second, line), replaced in one assignment
    _status_lines: dict[int, str] = {}
    _date_line: tuple[int, str] = (0, "")

    def _head(
        self, code: int, content_type: str, length: int, headers: dict | None
    ) -> bytes:
        if self.request_version == "HTTP/0.9":
            return b""  # a request without a version gets the body alone
        status = self._status_lines.get(code)
        if status is None:
            status = self._status_lines[code] = "%s %d %s\r\nServer: %s\r\n" % (
                self.protocol_version,
                code,
                self.responses[code][0] if code in self.responses else "",
                self.version_string(),
            )
        now = int(time.time())
        second, date = Handler._date_line
        if second != now:
            date = f"Date: {self.date_time_string(now)}\r\n"
            Handler._date_line = (now, date)
        extra = "".join(f"{k}: {v}\r\n" for k, v in headers.items()) if headers else ""
        return (
            f"{status}{date}Content-Type: {content_type}\r\n"
            f"Content-Length: {length}\r\n{extra}\r\n"
        ).encode("latin-1", "strict")

    def _send(
        self,
        code: int,
        body: bytes,
        content_type: str = "application/json",
        headers: dict | None = None,
        gzip_ok: bool = False,
    ) -> None:
        """Every response leaves here, head and body in one write."""
        if (
            gzip_ok
            and len(body) >= self._GZIP_MIN_BYTES
            and "gzip" in (self.headers.get("Accept-Encoding") or "")
        ):
            body = gzip_mod.compress(body, compresslevel=1)
            headers = dict(headers or {})
            headers["Content-Encoding"] = "gzip"
        head = self._head(code, content_type, len(body), headers)
        conn = self.connection
        if len(body) <= _JOIN_MAX_BYTES or isinstance(conn, ssl.SSLSocket):
            # joining a small body costs less than a second buffer does,
            # and a TLS connection has no sendmsg
            conn.sendall(head + body)
            return
        sent = conn.sendmsg((head, body))
        if sent < len(head):
            conn.sendall(head[sent:])
            sent = len(head)
        if sent < len(head) + len(body):
            conn.sendall(memoryview(body)[sent - len(head):])

    def _send_json(
        self,
        code: int,
        obj,
        headers: dict | None = None,
        gzip_ok: bool = False,
    ) -> None:
        self._send(code, encode_json(obj), headers=headers, gzip_ok=gzip_ok)

    def _body(self) -> bytes:
        length = int(self.headers.get("Content-Length") or 0)
        return self.rfile.read(length) if length else b""

    def _json_body(self) -> dict:
        raw = self._body()
        if not raw:
            return {}
        try:
            return json.loads(raw)
        except json.JSONDecodeError as e:
            raise ApiError(f"invalid json: {e}")

    def _request_budget(self) -> float | None:
        """Deadline budget for this request, by precedence: explicit
        ``timeout=`` query param (seconds) > ``X-Pilosa-Deadline`` header
        (remaining budget forwarded by an upstream node) > the server's
        configured default.  None/0 disables the deadline — malformed
        values fall through rather than erroring, matching header
        semantics (a bad deadline must not reject the request)."""
        raw = self.query_params.get("timeout", [None])[0]
        budget = deadline.from_header(raw)
        if budget is None:
            budget = deadline.from_header(self.headers.get(deadline.HEADER))
        if budget is None and self.default_deadline > 0:
            budget = self.default_deadline
        return budget

    def _dispatch(self, method: str) -> None:
        if getattr(type(self), "paused", None) is not None and type(self).paused.is_set():
            # Fault injection: emulate a paused process (reference uses
            # pumba pause in internal/clustertests) — drop the connection
            # without responding so clients see timeouts/resets.
            self.close_connection = True
            try:
                self.connection.close()
            except OSError:
                pass
            return
        target = self.path
        if not target.startswith("/"):
            # absolute form (a proxy's): drop scheme and authority
            target = urlsplit(target)._replace(scheme="", netloc="").geturl()
        path, _, query = target.partition("?")
        self.query_params = parse_qs(query) if query else {}
        for m, rx, name in _ROUTES:
            if m != method:
                continue
            match = rx.match(path)
            if match:
                t0 = time.monotonic()
                # Route this request's spans into THIS node's trace
                # store (contextvar: in-process multi-node clusters share
                # the process-global tracer but not their stores).
                store_token = tracestore._active_store.set(
                    getattr(self.api.holder, "traces", None)
                )
                # Join an incoming cross-node trace, or root a new one
                # (reference http/handler.go extracts opentracing headers).
                parent = tracing.get_tracer().extract_headers(self.headers)
                span = tracing.start_span(f"http.{name}", child_of=parent)
                span.set_tag("method", method).set_tag("path", path)
                self._root_span = span
                # Error budget: server-attributed failures only.  504s
                # (deadline/batcher expiry) and 500s burn budget; 4xx
                # client mistakes don't.
                slo_error = False
                # span lifecycle is manual (not `with span:`) so the
                # op-class and error verdict — known only after the
                # handler ran — are tagged BEFORE finish(): the tail-
                # sampling decision at root completion reads both.
                span.__enter__()
                try:
                    # Tenant attribution: the device cost ledger books
                    # every launch this request causes under the header's
                    # tenant (canonical "(default)" when untagged); the
                    # contextvar rides into the api/executor layers and
                    # batcher flight snapshots.
                    with devledger.tenant_scope(
                        self.headers.get(devledger.TENANT_HEADER)
                    ), deadline.scope(self._request_budget()):
                        getattr(self, "r_" + name)(**match.groupdict())
                except ShedError as e:
                    # QoS load shed (server/qos.py stage 3): explicit
                    # 429 + Retry-After, NEVER a silent 504 — and a 4xx,
                    # so backpressure does not burn the error budget it
                    # exists to protect.
                    retry = max(1, math.ceil(e.retry_after))
                    self.api.holder.stats.count_with_tags(
                        "http_shed", 1, 1.0, (f"tenant:{e.tenant}",)
                    )
                    self._send_json(
                        429,
                        {"error": str(e), "retryAfter": retry},
                        headers={"Retry-After": str(retry)},
                    )
                except DeadlineExceeded as e:
                    # Distinct from ApiError (400-family): a spent budget
                    # is a timeout, not a client mistake (reference maps
                    # context.DeadlineExceeded similarly).
                    slo_error = True
                    self.api.holder.stats.count(
                        "http_deadline_exceeded", 1, 1.0
                    )
                    self._send_json(504, {"error": f"deadline exceeded: {e}"})
                except ApiError as e:
                    slo_error = e.code >= 500
                    self._send_json(e.code, {"error": str(e)})
                except BrokenPipeError:
                    pass
                except Exception as e:  # internal error
                    slo_error = True
                    logger.exception("internal error")
                    self._send_json(500, {"error": f"internal: {e}"})
                finally:
                    elapsed = time.monotonic() - t0
                    op_class = slo.take_class() or _SLO_ROUTE_CLASS.get(
                        name, slo.OP_OTHER
                    )
                    span.set_tag("op_class", op_class)
                    if slo_error:
                        span.set_tag("error", True)
                    span.__exit__(None, None, None)
                    tracestore._active_store.reset(store_token)
                    # Per-tenant SLO dimension: the request also lands
                    # under "op_class@tenant" (obs/slo.py) so a single
                    # tenant's objective/error budget is trackable —
                    # the QoS ladder's per-victim pressure signal.
                    tenant = devledger.clean_tenant(
                        self.headers.get(devledger.TENANT_HEADER)
                    )
                    self.api.holder.slo.observe(
                        op_class, elapsed, slo_error, tenant=tenant
                    )
                    self.api.holder.stats.count_with_tags(
                        "http_requests", 1, 1.0, (f"route:{name}",)
                    )
                    self.api.holder.stats.timing("http_request", elapsed)
                    if self.long_query_time and elapsed > self.long_query_time:
                        logger.warning(
                            "long query %.3fs: %s %s", elapsed, method, self.path
                        )
                return
        self._send_json(404, {"error": "not found"})

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def do_DELETE(self):
        self._dispatch("DELETE")

    # -- routes -------------------------------------------------------------

    def r_root(self):
        self._send_json(200, {"message": "pilosa-tpu server. See /schema, /status, /index/{index}/query."})

    def r_version(self):
        self._send_json(200, self.api.version())

    def r_status(self):
        self._send_json(200, self.api.status())

    def r_info(self):
        self._send_json(200, self.api.info())

    def r_get_schema(self):
        self._send_json(200, self.api.schema())

    def r_metrics(self):
        """Prometheus text exposition (reference http/handler.go:282).
        Kernel-dispatch telemetry lives in its own process-global
        registry (ops/kernels.kernel_stats) so it is visible even when
        the holder uses a NopStatsClient; both registries are rendered
        into the one scrape."""
        from pilosa_tpu import __version__
        from pilosa_tpu.core import membudget, residency, translate
        from pilosa_tpu.obs import sysinfo
        from pilosa_tpu.obs.stats import prometheus_text
        from pilosa_tpu.ops import kernels

        # Device-budget occupancy refreshes at scrape time — gauges, not
        # counters, so no background poller is needed.
        stats = self.api.holder.stats
        if hasattr(stats, "gauge"):
            # process self-metrics refresh at scrape time (satellites of
            # the black-box plane: a restarted process is visible as a
            # start-time jump + uptime reset without any poller race)
            info = sysinfo.SystemInfo()
            stats.gauge(
                "process_uptime_seconds", round(info.process_uptime(), 3)
            )
            stats.gauge(
                "process_start_time_seconds", info.process_start_time()
            )
            dev = membudget.default_budget().snapshot()
            stats.gauge("device_used_bytes", dev["usedBytes"])
            stats.gauge("device_cap_bytes", dev["capBytes"] or 0)
            stats.gauge("device_entries", dev["entries"])
            stats.gauge("device_evictions", dev["evictions"])
            # residency tiers: query-path hit/miss, predictive-prefetch
            # yield, and the pin working set (core/residency.py)
            res = residency.default_tracker().snapshot()
            stats.gauge("device_hits", res["deviceHits"])
            stats.gauge("device_misses", res["deviceMisses"])
            stats.gauge("device_prefetch_issued", res["prefetchIssued"])
            stats.gauge("device_prefetch_useful", res["prefetchUseful"])
            stats.gauge("device_pins", dev["pins"])
            stats.gauge("device_pinned_entries", dev["pinnedEntries"])
            stats.gauge("device_pinned_bytes", dev["pinnedBytes"])
        # Kernel + key-translation telemetry live in process-global
        # registries (visible under NopStatsClient holders); the SLO
        # plane renders its own pilosa_slo_* series from the tracker.
        # Histogram buckets expose OpenMetrics exemplars filtered to
        # traces the tail sampler actually kept, so every exemplar id
        # resolves at /debug/traces?id=.
        kept = self.api.holder.traces.kept_ids()
        filt = kept.__contains__
        text = (
            prometheus_text(self.api.holder.stats, exemplar_filter=filt)
            + prometheus_text(kernels.kernel_stats, exemplar_filter=filt)
            + prometheus_text(translate.translate_stats)
            + self.api.holder.slo.prometheus_text(exemplar_filter=filt)
            + devledger.prometheus_text()
            + sysinfo.build_info_text(__version__)
        )
        self._send(
            200,
            text.encode(),
            content_type="text/plain; version=0.0.4",
            gzip_ok=True,
        )

    def r_debug_vars(self):
        """expvar-style dump (reference http/handler.go:281), including
        the executor's serving-cache counters (the analogue of the
        reference's cache stats, cache.go/stats)."""
        stats = self.api.holder.stats
        snap = dict(stats.snapshot()) if hasattr(stats, "snapshot") else {}
        ex = getattr(self.api, "executor", None)
        if ex is not None:
            snap["serving_cache"] = {
                "gram_hits": ex.stacks.gram_hits,
                "rowcount_hits": ex.stacks.rowcount_hits,
                "crossgram_hits": ex.stacks.crossgram_hits,
                "bsi_agg_hits": ex.stacks.bsi_agg_hits,
                "stack_rebuilds": ex.stacks.rebuilds,
                "stack_incremental": ex.stacks.incremental,
                # the incremental refreshes: bytes written on the device,
                # bytes of them gathered on the host and shipped, bytes of
                # them one chip sent another, and refreshes that copied a
                # stack a reader held on lease
                "stack_refresh_bytes": ex.stacks.refresh_bytes,
                "stack_refresh_host_bytes": ex.stacks.refresh_host_bytes,
                "stack_refresh_peer_bytes": ex.stacks.refresh_peer_bytes,
                "stack_refresh_out_of_place": ex.stacks.refresh_out_of_place,
                "bsi_stack_launches": ex.bsi_stack_launches,
                # the GroupBy lane: calls it took, pulls it made, levels
                # enqueued and not yet pulled summed over those pulls,
                # levels its byte bound held back (exec/executor.py)
                **{
                    f"groupby_lane_{k}": v
                    for k, v in ex.groupby_lane.items()
                },
                # the Sum lane: filtered Sums whose filter was built on
                # the device, those it left to the per-call path, and its
                # launches
                **{f"sum_lane_{k}": v for k, v in ex.sum_lane.items()},
                # stacks not built, by reason (exec/stacks.py), and
                # flight items a batch lane handed back to the per-call
                # path, by lane and reason (exec/executor.py)
                "stack_refusals": dict(ex.stacks.refusals),
                "lane_declines": {
                    lane: dict(by_reason)
                    for lane, by_reason in ex.lane_declines.items()
                },
            }
            # semantic result cache: hit/miss/invalidation counters plus
            # promotion state of the maintained TopN/GroupBy views
            # (exec/rescache.py)
            snap["rescache"] = ex.rescache.snapshot()
            # flight planner: CSE sharing, reorder, and measured lane
            # decisions, plus both lanes' live price list
            # (exec/planner.py)
            snap["planner"] = ex.planner.snapshot()
        from pilosa_tpu.core import membudget, residency, translate
        from pilosa_tpu.ops import kernels

        snap["kernels"] = kernels.telemetry_snapshot()
        # the budget's ledger, its bytes by owner, and beside them what
        # the backend itself says is in use (read here, never on a
        # request's path)
        snap["device"] = dict(
            membudget.default_budget().snapshot(), **membudget.backend_memory()
        )
        # the span table (obs/tracing.py): count, seconds, self seconds
        # and items per span name, every name present from the start
        snap["spans"] = tracing.spans_snapshot()
        # residency-tier counters: hit/miss rates, prefetch yield, pin
        # policy outcomes (core/residency.py)
        snap["residency"] = residency.default_tracker().snapshot()
        snap["devledger"] = devledger.snapshot()
        snap["events"] = self.api.holder.events.snapshot_summary()
        snap["slo"] = self.api.holder.slo.summary()
        # thread CPU the handlers spent from a request's line to its
        # response written, and the requests it was spent on
        snap["http"] = handler_cpu.snapshot()
        snap["translate"] = translate.telemetry_snapshot()
        batcher = getattr(self.api, "batcher", None)
        if batcher is not None:
            # serving-plane block: queue depth, window knobs, flights
            snap["batcher"] = batcher.snapshot()
        if getattr(self.api, "qos", None) is not None:
            # cost-governed admission: per-tenant WFQ + ladder stages
            snap["qos"] = self.api.qos_snapshot()
        ingest = getattr(self.api, "ingest", None)
        if ingest is not None:
            # ingest-plane block: pool depth/inflight, staging occupancy,
            # upload overlap — the pipeline's live tuning signals
            snap["ingest"] = ingest.snapshot()
        migrations = getattr(self.api, "migrations", None)
        if migrations is not None:
            # source-side migration sessions: per-fragment pending
            # delta ops = live catch-up lag during an online resize
            snap["migrations"] = migrations.snapshot_summary()
        dist = getattr(self.api, "dist", None)
        if dist is not None:
            # cluster-on-mesh routing: the placement map plus recent
            # per-call partition decisions (mesh vs HTTP vs local)
            snap["dist"] = dist.snapshot()
        from pilosa_tpu import __version__
        from pilosa_tpu.obs import sysinfo

        # process identity block: pid/version/uptime — distinct from the
        # host report in /info (sysinfo.py reports host uptime there)
        snap["process"] = sysinfo.SystemInfo().process_block(__version__)
        # which native libraries this process loaded (or why not): the
        # latency tier and the roaring codec fall back to numpy without
        # them (nativelib.py)
        from pilosa_tpu import nativelib

        snap["native"] = nativelib.status()
        blackbox = getattr(self.api, "blackbox", None)
        if blackbox is not None:
            # black-box writer self-accounting: checkpoint counts/cost,
            # spool size, crash-loop state (obs/blackbox.py)
            snap["blackbox"] = blackbox.stats()
        self._send_json(200, snap)

    def r_debug_slo(self):
        """Live SLO state: per-op-class latency quantiles, windowed
        availability, burn rates, alert firing, pass/fail verdicts."""
        self._send_json(200, self.api.slo_snapshot())

    def r_debug_qos(self):
        """Cost-governed admission state: per-tenant weighted-fair
        queues (debt, cost estimate, effective weight), pressure-ladder
        stages, shed/degraded counters and recent transitions
        (server/qos.py)."""
        self._send_json(200, self.api.qos_snapshot())

    def r_debug_index(self):
        """Debug-surface directory: every /debug/* endpoint with a
        one-line description."""
        self._send_json(200, {
            "endpoints": [
                {"path": p, "desc": d} for p, d in _DEBUG_ENDPOINTS
            ],
        })

    def r_debug_history(self):
        """Ring-buffer metrics history (obs/history.py): ?series= glob
        filter, ?since= base-seq cursor (gap-honest `truncated` flag),
        ?step= downsampling (tier selection + mean buckets),
        ?cluster=true merges every peer's series into one wall-clock-
        aligned timeline with per-node attribution."""
        series = self.query_params.get("series", [None])[0]
        try:
            since_raw = self.query_params.get("since", [None])[0]
            since = int(since_raw) if since_raw is not None else None
            step_raw = self.query_params.get("step", [None])[0]
            step = float(step_raw) if step_raw is not None else None
            limit_raw = self.query_params.get("limit", [None])[0]
            limit = int(limit_raw) if limit_raw is not None else None
        except ValueError:
            self._send_json(400, {"error": "bad since/step/limit"})
            return
        if self.query_params.get("cluster", ["false"])[0].lower() in (
            "1", "true", "yes",
        ):
            self._send_json(
                200, self.api.cluster_history(series=series, step=step),
                gzip_ok=True,
            )
            return
        snap = self.api.history_query(
            series=series, since=since, step=step, limit=limit
        )
        if snap is None:
            self._send_json(404, {"error": "metrics history disabled"})
            return
        self._send_json(200, snap, gzip_ok=True)

    def r_debug_events(self):
        """Event journal past ?since=<seq> (gap-free cursor resume);
        ?cluster=true fans out to every peer and merges the journals
        into one cluster timeline."""
        try:
            since = int(self.query_params.get("since", ["0"])[0])
            limit_raw = self.query_params.get("limit", [None])[0]
            limit = int(limit_raw) if limit_raw is not None else None
        except ValueError:
            self._send_json(400, {"error": "bad since/limit"})
            return
        if self.query_params.get("cluster", ["false"])[0].lower() in (
            "1", "true", "yes",
        ):
            self._send_json(200, self.api.cluster_events(since))
            return
        self._send_json(200, self.api.events_since(since, limit))

    def r_debug_traces(self):
        """Tail-sampled trace store: kept-trace list, ?id=<32hex> span
        detail, ?cluster=true coordinator fan-out (with id: assemble one
        trace's spans from every node; without: merge kept summaries)."""
        trace_id = self.query_params.get("id", [None])[0]
        try:
            limit = int(self.query_params.get("limit", ["100"])[0])
        except ValueError:
            self._send_json(400, {"error": "bad limit"})
            return
        if self.query_params.get("cluster", ["false"])[0].lower() in (
            "1", "true", "yes",
        ):
            if trace_id:
                self._send_json(
                    200, self.api.cluster_trace(trace_id), gzip_ok=True
                )
            else:
                self._send_json(
                    200, self.api.cluster_traces(limit), gzip_ok=True
                )
            return
        if trace_id:
            if self.query_params.get("spans", ["false"])[0].lower() in (
                "1", "true", "yes",
            ):
                # peer leg of cluster assembly: raw local spans, kept
                # OR recent, 200 even when empty
                self._send_json(
                    200, self.api.trace_spans(trace_id), gzip_ok=True
                )
                return
            detail = self.api.trace_detail(trace_id)
            if detail is None:
                self._send_json(404, {"error": f"trace {trace_id} not kept"})
            else:
                self._send_json(200, detail, gzip_ok=True)
            return
        self._send_json(200, self.api.traces_snapshot(limit), gzip_ok=True)

    def r_debug_incidents(self):
        """Flight-recorder incident bundles (alert-edge / 504-spike
        auto-captures): list, or full bundle with ?id=."""
        incident_id = self.query_params.get("id", [None])[0]
        if incident_id:
            detail = self.api.incident_detail(incident_id)
            if detail is None:
                self._send_json(
                    404, {"error": f"incident {incident_id} not found"}
                )
            else:
                self._send_json(200, detail)
            return
        self._send_json(200, self.api.incidents_snapshot())

    def r_debug_postmortem(self):
        """Sealed crash bundles from the black box (obs/blackbox.py):
        bare GET returns retained summaries + the newest bundle in
        full; ?id= one bundle; ?cluster=true merges every peer's
        summaries at the coordinator."""
        if self.query_params.get("cluster", ["false"])[0].lower() in (
            "1", "true", "yes",
        ):
            self._send_json(
                200, self.api.cluster_postmortems(), gzip_ok=True
            )
            return
        pm_id = self.query_params.get("id", [None])[0]
        snap = self.api.postmortem_snapshot(pm_id)
        if snap is None:
            if pm_id:
                self._send_json(
                    404, {"error": f"postmortem {pm_id} not found"}
                )
            else:
                self._send_json(
                    404, {"error": "black box disabled (no data dir)"}
                )
            return
        self._send_json(200, snap, gzip_ok=True)

    def r_debug_devcosts(self):
        """Device cost ledger: per-site and per-(tenant, index, op_class)
        compile/launch/transfer accounting with rates, plus recompile-
        storm state (obs/devledger.py)."""
        self._send_json(200, devledger.snapshot())

    def r_debug_jobs(self):
        """Background-job records: active + bounded history, with phase,
        progress counters, rates and ETA (?kind= filters)."""
        kind = self.query_params.get("kind", [None])[0]
        self._send_json(200, self.api.jobs_snapshot(kind))

    def r_debug_fragments(self):
        """Per-fragment storage/residency introspection
        (?index=&field= filter)."""
        index = self.query_params.get("index", [None])[0]
        field = self.query_params.get("field", [None])[0]
        self._send_json(200, self.api.fragment_details(index, field))

    def r_debug_slow_queries(self):
        """Bounded worst-offender log of queries over the server's
        slow-query threshold (reference's long-query-time logging,
        handler.go:246-248, upgraded to a structured endpoint: each
        entry keeps the full execution profile of the offending
        query)."""
        self._send_json(200, self.api.slow_queries.snapshot())

    def r_debug_threads(self):
        """Per-thread stack dump — the pprof goroutine-profile analogue
        (reference mounts net/http/pprof, http/handler.go:280)."""
        import sys
        import traceback

        frames = sys._current_frames()
        out = []
        for t in threading.enumerate():
            frame = frames.get(t.ident)
            out.append(
                {
                    "name": t.name,
                    "daemon": t.daemon,
                    "stack": traceback.format_stack(frame) if frame else [],
                }
            )
        self._send_json(200, {"threads": out, "count": len(out)})

    def r_debug_profile(self):
        """CPU sampling profile of every thread for ?seconds=N (cap 30);
        flamegraph-collapsed stacks — the net/http/pprof profile-
        endpoint role (reference http/handler.go:280).  The request
        thread does the sampling; the threaded server keeps serving."""
        import math

        from pilosa_tpu.obs import profile

        try:
            seconds = float(self.query_params.get("seconds", ["2"])[0])
            interval = (
                float(self.query_params.get("interval_ms", ["5"])[0]) / 1e3
            )
            if not (math.isfinite(seconds) and math.isfinite(interval)):
                raise ValueError
            if seconds <= 0 or interval <= 0:
                raise ValueError
        except ValueError:
            self._send_json(400, {"error": "bad seconds/interval_ms"})
            return
        # clamp BOTH ways: a huge interval would park this server thread
        # in time.sleep far past the seconds cap
        interval = min(max(0.001, interval), 1.0)
        # The sampler blocks this request thread for the whole window:
        # cap it by the caller's remaining deadline budget (at 90%, so
        # serialization still fits) instead of sampling into a 504.
        deadline.check("debug/profile")
        rem = deadline.remaining()
        if rem is not None:
            seconds = min(seconds, max(0.05, rem * 0.9))
        self._send_json(200, profile.sample(seconds, interval))

    def r_debug_memory(self):
        """Heap/memory snapshot: RSS, host mirror bytes by index, HBM
        budget accounting, GC state — the pprof heap-profile role
        shaped to this runtime's actual memory owners."""
        from pilosa_tpu.obs import profile

        self._send_json(200, profile.memory_snapshot(self.api.holder))

    def r_diagnostics(self):
        """Diagnostics snapshot (reference diagnostics.go payload; local
        endpoint replaces the reference's phone-home POST)."""
        diag = getattr(self.api, "diagnostics", None)
        if diag is None:
            self._send_json(404, {"error": "diagnostics not enabled"})
            return
        self._send_json(200, diag.snapshot())

    def r_post_schema(self):
        self.api.apply_schema(self._json_body())
        self._send_json(200, {})

    def r_query(self, index: str):
        """Accepts either a raw PQL body or a JSON envelope
        ``{"query": ..., "shards": [...], "remote": bool}`` — the latter
        is the node↔node fan-out form (reference QueryRequest,
        internal/public.proto)."""
        with tracing.start_span("http.decode") as sp:
            body = self._body()
            sp.set_tag("bytes", len(body))
            remote = False
            profile = False
            shards = None
            pql = body.decode()
            if self.headers.get("Content-Type", "").startswith(
                "application/json"
            ):
                try:
                    obj = json.loads(pql or "{}")
                except json.JSONDecodeError:
                    obj = None  # raw PQL sent with a JSON content type
                if isinstance(obj, dict):
                    pql = obj.get("query", "")
                    shards = obj.get("shards")
                    remote = bool(obj.get("remote"))
                    profile = bool(obj.get("profile"))
        if "shards" in self.query_params:
            shards = [
                int(s)
                for part in self.query_params["shards"]
                for s in part.split(",")
                if s
            ]
        if self.query_params.get("profile", [""])[0].lower() in ("1", "true"):
            profile = True
        resp = self.api.query_encoded(
            index, pql, shards=shards, remote=remote, profile=profile
        )
        with tracing.start_span("http.encode"):
            if isinstance(resp, bytes):
                # a result-cache hit whose body an earlier hit encoded
                self._send(200, resp)
            else:
                self._send_json(200, resp)

    def r_create_index(self, index: str):
        body = self._json_body()
        self._send_json(200, self.api.create_index(index, body.get("options", {})))

    def r_get_index(self, index: str):
        self._send_json(200, self.api.index_info(index))

    def r_delete_index(self, index: str):
        self.api.delete_index(index)
        self._send_json(200, {})

    def r_create_field(self, index: str, field: str):
        body = self._json_body()
        self._send_json(200, self.api.create_field(index, field, body.get("options", {})))

    def r_get_field(self, index: str, field: str):
        self._send_json(200, self.api.field_info(index, field))

    def r_delete_field(self, index: str, field: str):
        self.api.delete_field(index, field)
        self._send_json(200, {})

    def r_import_(self, index: str, field: str):
        ctype = self.headers.get("Content-Type", "")
        if ctype.startswith("application/octet-stream"):
            from pilosa_tpu.cluster import wire

            body = self._body()  # transport faults keep their own path
            try:
                req = wire.decode_import(body)
            except Exception as e:
                # malformed client input, not a server fault (the JSON
                # path 400s the same way via _json_body)
                raise ApiError(f"bad binary import payload: {e}")
        else:
            req = self._json_body()
        self.api.import_bits(index, field, req)
        self._send_json(200, {})

    def r_import_roaring(self, index: str, field: str, shard: str):
        clear = self.query_params.get("clear", ["false"])[0] == "true"
        remote = self.query_params.get("remote", ["false"])[0] == "true"
        view = self.query_params.get("view", ["standard"])[0]
        result = self.api.import_roaring(
            index, field, int(shard), self._body(), clear=clear, view=view,
            remote=remote,
        )
        self._send_json(200, result)

    def r_fragments(self):
        self._send_json(200, {"fragments": self.api.fragment_inventory()})

    def r_resize_fetch(self):
        self._send_json(200, self.api.resize_fetch(self._json_body()))

    def r_migrate_begin(self):
        self._send_json(200, self.api.migrate_begin(self._json_body()))

    def r_migrate_chunk(self):
        p = {k: v[0] for k, v in self.query_params.items()}
        data = self.api.migrate_chunk(p["token"], int(p.get("offset", 0)))
        self._send(200, data, content_type="application/octet-stream")

    def r_migrate_delta(self):
        body = self._json_body()
        frame = self.api.migrate_delta(body.get("token", ""))
        self._send(200, frame, content_type="application/octet-stream")

    def r_migrate_end(self):
        body = self._json_body()
        self._send_json(200, self.api.migrate_end(body.get("token", "")))

    def r_migrate_fetch(self):
        self._send_json(200, self.api.migrate_fetch(self._json_body()))

    def r_migrate_finalize(self):
        self._send_json(200, self.api.migrate_finalize(self._json_body()))

    def r_resize_resume(self):
        self._send_json(200, self.api.resize_resume())

    def r_cluster_message(self):
        self._send_json(200, self.api.receive_message(self._json_body()))

    def r_nodes(self):
        self._send_json(200, self.api.hosts())

    def r_attr_blocks(self):
        p = {k: v[0] for k, v in self.query_params.items()}
        self._send_json(
            200, self.api.attr_blocks(p["index"], p.get("field") or None)
        )

    def r_attr_block_data(self):
        self._send_json(200, self.api.attr_block_data(self._json_body()))

    def r_fragment_blocks(self):
        p = {k: v[0] for k, v in self.query_params.items()}
        self._send_json(
            200,
            self.api.fragment_blocks(
                p["index"], p["field"], p.get("view", "standard"), int(p["shard"])
            ),
        )

    def r_fragment_block_data(self):
        body = self._json_body()
        # Binary when the peer accepts it (packed roaring positions);
        # JSON fallback for unencodable row ids or legacy peers.
        if "application/octet-stream" in (self.headers.get("Accept") or ""):
            data = self.api.fragment_block_data_binary(body)
            if data is not None:
                self._send(200, data, content_type="application/octet-stream")
                return
        self._send_json(200, self.api.fragment_block_data(body))

    def r_fragment_data(self):
        p = {k: v[0] for k, v in self.query_params.items()}
        data = self.api.fragment_data(
            p["index"], p["field"], p.get("view", "standard"), int(p["shard"])
        )
        self._send(200, data, content_type="application/octet-stream")

    def r_export(self):
        index = self.query_params.get("index", [None])[0]
        field = self.query_params.get("field", [None])[0]
        if not index or not field:
            raise ApiError("index and field query params required")
        shard = self.query_params.get("shard", [None])[0]
        csv = self.api.export_csv(index, field, int(shard) if shard else None)
        self._send(200, csv.encode(), content_type="text/csv")

    def r_shards_max(self):
        self._send_json(200, self.api.shards_max())

    def r_translate_keys(self):
        body = self._json_body()
        ids = self.api.translate_keys(
            body.get("index", ""), body.get("field", ""), body.get("keys", [])
        )
        self._send_json(200, {"ids": ids})

    def r_translate_ids(self):
        body = self._json_body()
        keys = self.api.translate_ids(
            body.get("index", ""), body.get("field", ""), body.get("ids", [])
        )
        self._send_json(200, {"keys": keys})

    def r_translate_log(self):
        offset = int(self.query_params.get("offset", ["0"])[0])
        self._send_json(200, self.api.translate_log(offset))

    def r_translate_restore(self):
        body = self._json_body()
        self._send_json(
            200, self.api.translate_restore(body.get("entries", []))
        )

    def r_set_coordinator(self):
        body = self._json_body()
        self._send_json(200, self.api.set_coordinator(body.get("id", "")))

    def r_resize_abort(self):
        self._send_json(200, self.api.resize_abort())

    def r_remove_node(self):
        body = self._json_body()
        self._send_json(200, self.api.resize_remove_node(body.get("id", "")))

    def r_recalculate_caches(self):
        # reference POST /recalculate-caches; counts here are exact and
        # maintained, so there is nothing to rebuild (docs/parity.md)
        self._send_json(200, {})


class Server:
    """HTTP server wrapper: bind, serve in background, close.

    With ``tls_cert``/``tls_key`` the listener speaks HTTPS (reference
    TLS config server/config.go:36-152; node URIs become https://)."""

    def __init__(
        self,
        api: API,
        host: str = "localhost",
        port: int = 10101,
        long_query_time: float = 0.0,
        tls_cert: str | None = None,
        tls_key: str | None = None,
        default_deadline: float = 0.0,
        slow_query_time: float = 0.0,
    ):
        if slow_query_time > 0:
            api.slow_queries.threshold = slow_query_time
        handler = type(
            "BoundHandler",
            (Handler,),
            {
                "api": api,
                "long_query_time": long_query_time,
                "default_deadline": default_deadline,
                "paused": threading.Event(),
            },
        )

        class _Listener(ThreadingHTTPServer):
            # The serving plane holds ~1k concurrent clients parked on
            # the batcher; socketserver's default listen backlog of 5
            # resets connections the accept loop hasn't reached yet.
            request_queue_size = 1024

        self.httpd = _Listener((host, port), handler)
        self.tls = bool(tls_cert)
        if tls_cert:
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(tls_cert, tls_key)
            self.httpd.socket = ctx.wrap_socket(
                self.httpd.socket, server_side=True
            )
        self.api = api
        self._thread: threading.Thread | None = None

    def pause(self) -> None:
        """Stop answering requests (connections drop) until resume() —
        fault injection mirroring pumba pause in the reference's
        internal/clustertests."""
        self.httpd.RequestHandlerClass.paused.set()

    def resume(self) -> None:
        self.httpd.RequestHandlerClass.paused.clear()

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def serve_background(self) -> None:
        self._thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        self._thread.start()

    def serve_forever(self) -> None:
        self.httpd.serve_forever()

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self.api.close()
