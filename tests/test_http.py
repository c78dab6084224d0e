"""HTTP/transport tests against a live in-process server (the reference's
http/handler_test.go + client_test.go pattern over test.MustRunCluster)."""

import json
import re
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.server.api import API
from pilosa_tpu.server.http import Server
from pilosa_tpu.storage import roaring
from pilosa_tpu.storage.disk import HolderStore
from pilosa_tpu.shardwidth import SHARD_WIDTH


@pytest.fixture()
def srv(tmp_path):
    holder = Holder()
    store = HolderStore(holder, str(tmp_path / "data"))
    store.open()
    api = API(holder, store)
    server = Server(api, port=0)  # port 0: auto-bind (reference test/pilosa.go:54-83)
    server.serve_background()
    yield server
    server.close()


def call(srv, method, path, body=None, content_type="application/json", raw=False):
    url = f"http://localhost:{srv.port}{path}"
    data = body if isinstance(body, (bytes, type(None))) else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    req.add_header("Content-Type", content_type)
    with urllib.request.urlopen(req, timeout=10) as resp:
        payload = resp.read()
        return payload if raw else (json.loads(payload) if payload.strip() else {})


def test_version_status_info(srv):
    assert "version" in call(srv, "GET", "/version")
    st = call(srv, "GET", "/status")
    assert st["state"] == "NORMAL"
    assert len(st["nodes"]) == 1
    assert call(srv, "GET", "/info")["shardWidth"] == SHARD_WIDTH


def test_index_field_lifecycle(srv):
    call(srv, "POST", "/index/myidx", {"options": {}})
    call(srv, "POST", "/index/myidx/field/myfield", {"options": {"type": "set"}})
    schema = call(srv, "GET", "/schema")
    names = [i["name"] for i in schema["indexes"]]
    assert "myidx" in names
    info = call(srv, "GET", "/index/myidx/field/myfield")
    assert info["options"]["type"] == "set"
    # conflict
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "POST", "/index/myidx")
    assert e.value.code == 409
    call(srv, "DELETE", "/index/myidx/field/myfield")
    call(srv, "DELETE", "/index/myidx")
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "GET", "/index/myidx")
    assert e.value.code == 404


def test_query_roundtrip(srv):
    call(srv, "POST", "/index/i")
    call(srv, "POST", "/index/i/field/f")
    r = call(srv, "POST", "/index/i/query", b"Set(10, f=1)", content_type="text/plain")
    assert r == {"results": [True]}
    r = call(srv, "POST", "/index/i/query", b"Row(f=1)")
    assert r["results"][0]["columns"] == [10]
    r = call(srv, "POST", "/index/i/query", b"Count(Row(f=1))")
    assert r["results"] == [1]


def test_query_error_shapes(srv):
    call(srv, "POST", "/index/i")
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "POST", "/index/i/query", b"Row(nofield=1)")
    assert e.value.code == 400
    body = json.loads(e.value.read())
    assert "error" in body
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "POST", "/index/nope/query", b"Row(f=1)")
    assert e.value.code == 400


def test_json_import_and_export(srv):
    call(srv, "POST", "/index/i")
    call(srv, "POST", "/index/i/field/f")
    call(
        srv,
        "POST",
        "/index/i/field/f/import",
        {"rowIDs": [1, 1, 2], "columnIDs": [5, SHARD_WIDTH + 6, 7]},
    )
    r = call(srv, "POST", "/index/i/query", b"Row(f=1)")
    assert r["results"][0]["columns"] == [5, SHARD_WIDTH + 6]
    csv = call(srv, "GET", "/export?index=i&field=f", raw=True).decode()
    lines = set(csv.strip().splitlines())
    assert lines == {"1,5", f"1,{SHARD_WIDTH + 6}", "2,7"}


def test_import_values(srv):
    call(srv, "POST", "/index/i")
    call(
        srv,
        "POST",
        "/index/i/field/v",
        {"options": {"type": "int", "min": -10, "max": 100}},
    )
    call(srv, "POST", "/index/i/field/v/import", {"columnIDs": [1, 2], "values": [7, -3]})
    r = call(srv, "POST", "/index/i/query", b"Sum(field=v)")
    assert r["results"][0] == {"value": 4, "count": 2}


def test_import_roaring_binary(srv):
    call(srv, "POST", "/index/i")
    call(srv, "POST", "/index/i/field/f")
    # row 3, cols {1, 9}: positions 3*width + {1, 9}
    width = SHARD_WIDTH
    payload = roaring.serialize(
        np.array([3 * width + 1, 3 * width + 9], dtype=np.uint64)
    )
    r = call(
        srv,
        "POST",
        "/index/i/field/f/import-roaring/0",
        payload,
        content_type="application/octet-stream",
    )
    assert r == {"changed": 2}
    q = call(srv, "POST", "/index/i/query", b"Row(f=3)")
    assert q["results"][0]["columns"] == [1, 9]


def test_keys_over_http(srv):
    call(srv, "POST", "/index/ki", {"options": {"keys": True}})
    call(srv, "POST", "/index/ki/field/f", {"options": {"keys": True}})
    call(srv, "POST", "/index/ki/query", b'Set("a", f="x")')
    r = call(srv, "POST", "/index/ki/query", b'Row(f="x")')
    assert r["results"][0]["keys"] == ["a"]
    ids = call(
        srv, "POST", "/internal/translate/keys", {"index": "ki", "field": "", "keys": ["a"]}
    )
    assert ids == {"ids": [1]}


def test_shards_max(srv):
    call(srv, "POST", "/index/i")
    call(srv, "POST", "/index/i/field/f")
    call(srv, "POST", "/index/i/query", f"Set({SHARD_WIDTH * 2 + 1}, f=1)".encode())
    r = call(srv, "GET", "/internal/shards/max")
    assert r["standard"]["i"] == 2


def test_persistence_across_server_restart(tmp_path):
    holder = Holder()
    store = HolderStore(holder, str(tmp_path / "data"))
    store.open()
    api = API(holder, store)
    server = Server(api, port=0)
    server.serve_background()
    call(server, "POST", "/index/i")
    call(server, "POST", "/index/i/field/f")
    call(server, "POST", "/index/i/query", b"Set(42, f=7)")
    port = server.port
    server.close()

    holder2 = Holder()
    store2 = HolderStore(holder2, str(tmp_path / "data"))
    store2.open()
    api2 = API(holder2, store2)
    server2 = Server(api2, port=0)
    server2.serve_background()
    try:
        r = call(server2, "POST", "/index/i/query", b"Row(f=7)")
        assert r["results"][0]["columns"] == [42]
    finally:
        server2.close()


def test_state_gating(srv):
    from pilosa_tpu.server.api import STATE_STARTING

    srv.api.state = STATE_STARTING
    # status still works
    assert call(srv, "GET", "/status")["state"] == "STARTING"
    # queries gated
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "POST", "/index/i/query", b"Row(f=1)")
    assert e.value.code == 503
    srv.api.state = "NORMAL"


def test_cli_check_and_inspect(tmp_path, capsys):
    from pilosa_tpu import cli

    good = tmp_path / "good"
    good.write_bytes(roaring.serialize(np.array([1, 2, 3], dtype=np.uint64)))
    bad = tmp_path / "bad"
    bad.write_bytes(b"\x00bogus\x00\x00\x00\x00")
    assert cli.main(["check", str(good)]) == 0
    assert cli.main(["check", str(bad)]) == 1
    assert cli.main(["inspect", str(good)]) == 0
    out = capsys.readouterr().out
    assert "bits: 3" in out


def test_cli_generate_config(capsys):
    from pilosa_tpu import cli

    assert cli.main(["generate-config"]) == 0
    cfg = json.loads(capsys.readouterr().out)
    assert cfg["bind"] == "localhost:10101"


# ---------------------------------------------------------------------------
# Binary import payloads (cluster/wire.py encode_import/decode_import)
# ---------------------------------------------------------------------------


class TestBinaryImport:
    def test_bits_roundtrip(self):
        import numpy as np
        from pilosa_tpu.cluster import wire

        rng = np.random.default_rng(3)
        width = 1 << 14
        rows = rng.integers(0, 50, 5000).astype(np.uint64)
        cols = rng.integers(0, 4 * width, 5000).astype(np.uint64)
        req = {"rowIDs": rows, "columnIDs": cols, "_width": width}
        body = wire.encode_import(dict(req, remote=True))
        assert body is not None
        out = wire.decode_import(body)
        assert out["remote"] is True and out["clear"] is False
        # without the sender's marker, the decoded request routes like a
        # public JSON import (it must NOT forge remote=True)
        assert wire.decode_import(wire.encode_import(req))["remote"] is False
        want = sorted(set(zip(rows.tolist(), cols.tolist())))
        got = sorted(zip(out["rowIDs"].tolist(), out["columnIDs"].tolist()))
        assert got == want

    def test_values_roundtrip_and_clear_flag(self):
        import numpy as np
        from pilosa_tpu.cluster import wire

        cols = np.array([5, 9, 1 << 40], np.uint64)
        vals = np.array([-3, 0, 2**40], np.int64)
        body = wire.encode_import(
            {"columnIDs": cols, "values": vals, "clear": True}
        )
        out = wire.decode_import(body)
        assert out["clear"] is True
        assert out["columnIDs"].tolist() == cols.tolist()
        assert out["values"].tolist() == vals.tolist()

    def test_json_fallback_cases(self):
        import numpy as np
        from pilosa_tpu.cluster import wire

        base = {
            "rowIDs": np.array([1], np.uint64),
            "columnIDs": np.array([2], np.uint64),
            "_width": 1 << 14,
        }
        assert wire.encode_import(dict(base, timestamps=["2020-01-01T00"])) is None
        assert wire.encode_import(dict(base, rowKeys=["k"])) is None
        assert wire.encode_import({"columnIDs": [1]}) is None  # no rows/width
        # row ids too large for position arithmetic
        huge = dict(base, rowIDs=np.array([2**62], np.uint64))
        assert wire.encode_import(huge) is None

    def test_binary_at_least_10x_smaller_than_json_for_1m_bits(self):
        import json

        import numpy as np
        from pilosa_tpu.cluster import wire

        rng = np.random.default_rng(7)
        width = 1 << 20
        n = 1_000_000
        # realistic ingest slice: a handful of rows over a bounded
        # column range (dense enough for bitmap containers, the shape a
        # steady event stream produces)
        rows = rng.integers(0, 8, n).astype(np.uint64)
        cols = rng.integers(0, width // 4, n).astype(np.uint64)
        req = {"rowIDs": rows, "columnIDs": cols, "_width": width}
        body = wire.encode_import(req)
        json_body = json.dumps(
            {"rowIDs": rows.tolist(), "columnIDs": cols.tolist()}
        ).encode()
        assert len(body) * 10 <= len(json_body), (
            len(body), len(json_body)
        )
        out = wire.decode_import(body)
        assert len(out["columnIDs"]) == len(set(zip(rows.tolist(), cols.tolist())))

    def test_http_binary_import_end_to_end(self, srv):
        """POST /import with octet-stream body applies like JSON."""
        from pilosa_tpu.cluster import wire
        from pilosa_tpu.shardwidth import SHARD_WIDTH

        call(srv, "POST", "/index/bi")
        call(srv, "POST", "/index/bi/field/f")
        width = SHARD_WIDTH
        rows = np.array([0, 0, 1], np.uint64)
        cols = np.array([3, width + 5, 9], np.uint64)
        body = wire.encode_import(
            {"rowIDs": rows, "columnIDs": cols, "_width": width}
        )
        call(srv, "POST", "/index/bi/field/f/import", body,
             content_type="application/octet-stream")
        r = call(srv, "POST", "/index/bi/query",
                 b"Count(Row(f=0))Count(Row(f=1))",
                 content_type="text/plain")
        assert r["results"] == [2, 1]


def test_debug_profile_and_memory_under_load(srv):
    """/debug/profile samples a live serving process (non-empty stacks
    while queries run) and /debug/memory accounts the host mirrors —
    the net/http/pprof role (reference http/handler.go:280)."""
    import threading

    call(srv, "POST", "/index/p", {"options": {}})
    call(srv, "POST", "/index/p/field/f", {"options": {"type": "set"}})
    call(srv, "POST", "/index/p/query", b"Set(1, f=1) Set(2, f=2)",
         content_type="text/plain")

    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            call(srv, "POST", "/index/p/query",
                 b"Count(Intersect(Row(f=1), Row(f=2)))",
                 content_type="text/plain")

    t = threading.Thread(target=hammer, daemon=True)
    t.start()
    try:
        prof = call(srv, "GET", "/debug/profile?seconds=0.6&interval_ms=2")
    finally:
        stop.set()
        t.join(timeout=10)
    assert prof["samples"] > 0
    assert prof["stacks"], "no stacks sampled"
    # the hammer thread must be visible in at least one collapsed stack
    joined = "\n".join(prof["stacks"])
    assert "executor" in joined or "http" in joined, joined[:500]

    mem = call(srv, "GET", "/debug/memory")
    assert mem["rss_bytes"] > 0
    assert mem["host_mirrors"]["fragments"] >= 1
    assert mem["host_mirrors"]["total_bytes"] > 0
    assert mem["host_mirrors"]["by_index"]["p"] > 0
    assert "hbm_budget" in mem and "used_bytes" in mem["hbm_budget"]
    # bad params are a 400, not a 500
    import urllib.error
    with pytest.raises(urllib.error.HTTPError) as e:
        call(srv, "GET", "/debug/profile?seconds=abc")
    assert e.value.code == 400


# ---------------------------------------------------------------------------
# Framing: the listener's own parser of a request's head, held to the
# stdlib's (http.client.parse_headers + BaseHTTPRequestHandler.parse_request)
# ---------------------------------------------------------------------------

_ASKED = (
    "Content-Length", "content-type", "CONNECTION", "Expect", "Accept-Encoding",
    "X-Pilosa-Deadline", "X-Pilosa-Tenant", "X-A", "X-B", "X-Missing", "From",
    "bad name", "no colon", "",
)

_HEADS = {
    "mixed_case": b"POST /index/i/query HTTP/1.1\r\ncOnTeNt-LeNgTh: 12\r\n"
                  b"CONTENT-TYPE: Text/Plain\r\nx-pilosa-tenant: Team\r\n\r\n",
    "duplicate_header": b"GET /status HTTP/1.1\r\nX-A: first\r\nx-a: second\r\nX-A: third\r\n\r\n",
    "folded_line": b"GET /status HTTP/1.1\r\nX-A: one\r\n two\r\n\tthree\r\nX-B: b\r\n\r\n",
    "folded_duplicate": b"GET /status HTTP/1.1\r\nX-A: one\r\nX-A: two\r\n more\r\n\r\n",
    "fold_before_any_field": b"GET /status HTTP/1.1\r\n stray\r\nX-A: a\r\n\r\n",
    "no_colon": b"GET /status HTTP/1.1\r\nX-A: kept\r\nno colon\r\nX-B: dropped\r\n\r\n",
    "name_with_space": b"GET /status HTTP/1.1\r\nX-A: kept\r\nbad name: x\r\nX-B: dropped\r\n\r\n",
    "space_before_colon": b"GET /status HTTP/1.1\r\nX-A : x\r\nX-B: dropped\r\n\r\n",
    "colon_first": b"GET /status HTTP/1.1\r\n: nameless\r\n folded\r\nX-B: kept\r\n\r\n",
    "envelope_line": b"GET /status HTTP/1.1\r\nX-A: a\r\nFrom someone Mon\r\nFrom: me\r\nX-B: b\r\n\r\n",
    "no_headers": b"GET /status HTTP/1.1\r\n\r\n",
    "empty_value": b"GET /status HTTP/1.1\r\nX-A:\r\nX-B:   \r\nAccept-Encoding: \t gzip \r\n\r\n",
    "latin1_value": b"GET /status HTTP/1.1\r\nX-A: caf\xe9\r\nX-\xe9: odd name\r\nX-B: dropped\r\n\r\n",
    "lf_line_ends": b"GET /status HTTP/1.1\nX-A: a\nX-B: b\n\n",
    "end_of_stream": b"GET /status HTTP/1.1\r\nX-A: a\r\n",
    "http10": b"GET /status HTTP/1.0\r\nX-A: a\r\n\r\n",
    "http10_keep_alive": b"GET /status HTTP/1.0\r\nConnection: Keep-Alive\r\n\r\n",
    "http11": b"GET /status HTTP/1.1\r\nX-A: a\r\n\r\n",
    "http11_close": b"GET /status HTTP/1.1\r\nconnection: CLOSE\r\n\r\n",
    "http11_other_connection": b"GET /status HTTP/1.1\r\nConnection: upgrade\r\n\r\n",
    "expect_continue": b"POST /index/i/query HTTP/1.1\r\nExpect: 100-Continue\r\nContent-Length: 3\r\n\r\n",
    "expect_continue_http10": b"POST /index/i/query HTTP/1.0\r\nExpect: 100-continue\r\n\r\n",
    "line_65536_bytes": b"GET /status HTTP/1.1\r\nX-A: " + b"a" * (65536 - 7) + b"\r\n\r\n",
    "line_65537_bytes": b"GET /status HTTP/1.1\r\nX-A: " + b"a" * (65537 - 7) + b"\r\n\r\n",
    "headers_99": b"GET /status HTTP/1.1\r\n" + b"".join(b"X-%d: v\r\n" % i for i in range(99)) + b"\r\n",
    "headers_100": b"GET /status HTTP/1.1\r\n" + b"".join(b"X-%d: v\r\n" % i for i in range(100)) + b"\r\n",
    "headers_101": b"GET /status HTTP/1.1\r\n" + b"".join(b"X-%d: v\r\n" % i for i in range(101)) + b"\r\n",
    "bad_version": b"GET /status HTTP/1.x\r\nX-A: a\r\n\r\n",
    "bad_version_no_slash": b"GET /status FTP1.1\r\n\r\n",
    "bad_version_long": b"GET /status HTTP/1.12345678901\r\n\r\n",
    "version_2": b"GET /status HTTP/2.0\r\n\r\n",
    "version_1_9": b"GET /status HTTP/1.9\r\n\r\n",
    "bad_line_four_words": b"GET /a b HTTP/1.1\r\n\r\n",
    "one_word_line": b"GET\r\n\r\n",
    "blank_line": b"\r\n",
    "http09_get": b"GET /status\r\n\r\n",
    "http09_post": b"POST /status\r\n\r\n",
    "double_slash_target": b"GET //evil.example/x?y=1 HTTP/1.1\r\n\r\n",
}


def _parse_head(cls, head: bytes):
    """``cls.parse_request`` over ``head`` without a socket: what it
    returned, what it left on the handler, what it wrote."""
    import io

    h = cls.__new__(cls)
    h.client_address = ("test", 0)
    h.rfile = io.BytesIO(head)
    h.wfile = io.BytesIO()
    h.raw_requestline = h.rfile.readline(65537)
    ok = h.parse_request()
    wrote = h.wfile.getvalue()
    seen = {
        "ok": ok,
        "close_connection": h.close_connection,
        "command": h.command,
        "request_version": h.request_version,
        "status_line": wrote.split(b"\r\n", 1)[0],
        # the stdlib's error page names the code (a refusal made before the
        # request's version is known goes out without a head)
        "refused": [int(c) for c in re.findall(rb"Error code: (\d+)", wrote)],
        "unread": h.rfile.read(),
    }
    if ok:
        seen["path"] = h.path
        seen["get"] = {n: h.headers.get(n) for n in _ASKED}
        seen["get_default"] = {n: h.headers.get(n, "dflt") for n in _ASKED}
    return seen


@pytest.mark.parametrize("case", sorted(_HEADS))
def test_head_parser_is_held_to_the_stdlibs(case):
    from http.server import BaseHTTPRequestHandler

    from pilosa_tpu.server.http import Handler

    class Stdlib(BaseHTTPRequestHandler):
        protocol_version = Handler.protocol_version

        def log_message(self, fmt, *args):
            pass

    # guard the guard: the reference really is the stdlib's email-based parser
    assert Stdlib.parse_request is BaseHTTPRequestHandler.parse_request
    assert Handler.parse_request is not BaseHTTPRequestHandler.parse_request
    want = _parse_head(Stdlib, _HEADS[case])
    got = _parse_head(Handler, _HEADS[case])
    assert got == want
    if case in ("line_65537_bytes", "headers_100", "headers_101"):
        assert want["status_line"].startswith(b"HTTP/1.1 431 ") and want["refused"] == [431]
    if case.startswith("bad_") or case == "http09_post":
        assert want["refused"] == [400] and want["ok"] is False
    if case == "version_2":
        assert want["refused"] == [505]
    if case == "expect_continue":
        assert want["status_line"] == b"HTTP/1.1 100 Continue" and want["ok"]


def test_listener_takes_nothing_from_email():
    import ast
    import inspect

    from pilosa_tpu.server import http as listener

    tree = ast.parse(inspect.getsource(listener))
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.add(node.module)
    assert not {m for m in imported if m and m.split(".")[0] == "email"}
    names = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    assert "parse_headers" not in names
    # one place writes a response: no stdlib framing call, no wfile
    assert not names & {"send_response", "send_header", "end_headers", "wfile"}


# ---------------------------------------------------------------------------
# Framing over a real socket: a response is one write
# ---------------------------------------------------------------------------


class _CountingSocket:
    """The handler's connection with every send counted; ``cut`` makes the
    first ``sendmsg`` a partial one."""

    def __init__(self, sock, calls, cut=None):
        self._sock, self._calls, self._cut = sock, calls, cut

    def sendall(self, data):
        self._calls.append(("sendall", len(data)))
        return self._sock.sendall(data)

    def send(self, data):
        self._calls.append(("send", len(data)))
        return self._sock.send(data)

    def sendmsg(self, buffers):
        buffers = list(buffers)
        self._calls.append(("sendmsg", sum(len(b) for b in buffers)))
        if self._cut is not None:
            cut, self._cut = self._cut, None
            return self._sock.send(b"".join(buffers)[:cut])
        return self._sock.sendmsg(buffers)

    def __getattr__(self, name):
        return getattr(self._sock, name)


class _CountingWriter:
    def __init__(self, wfile, calls):
        self._wfile, self._calls = wfile, calls

    def write(self, data):
        self._calls.append(("wfile.write", len(data)))
        return self._wfile.write(data)

    def __getattr__(self, name):
        return getattr(self._wfile, name)


def _count_sends(server, cut=None) -> list:
    """Every later connection of ``server`` counts its sends into the list."""
    calls: list = []
    handler = server.httpd.RequestHandlerClass
    setup = handler.setup

    def counting_setup(self):
        setup(self)
        self.connection = _CountingSocket(self.connection, calls, cut)
        self.wfile = _CountingWriter(self.wfile, calls)

    handler.setup = counting_setup
    return calls


def _exchange(srv, method, path, body=None, headers=None, conn=None):
    import http.client

    c = conn or http.client.HTTPConnection("localhost", srv.port, timeout=30)
    try:
        c.request(method, path, body=body, headers=headers or {})
        resp = c.getresponse()
        return resp.status, resp.headers, resp.read()
    finally:
        if conn is None:
            c.close()


def _standard_headers(headers, body: bytes, content_type="application/json"):
    import email.utils

    assert headers.get_all("Server") == ["BaseHTTP/0.6 Python/%d.%d.%d" % sys.version_info[:3]]
    (date,) = headers.get_all("Date")
    # the stdlib's form, and about now
    then = email.utils.parsedate_to_datetime(date).timestamp()
    assert date == email.utils.formatdate(then, usegmt=True) and abs(time.time() - then) < 60
    assert headers.get_all("Content-Type") == [content_type]
    assert headers.get_all("Content-Length") == [str(len(body))]


def test_query_answer_is_one_send(srv):
    call(srv, "POST", "/index/i")
    call(srv, "POST", "/index/i/field/f")
    call(srv, "POST", "/index/i/query", b"Set(10, f=1)", content_type="text/plain")
    calls = _count_sends(srv)
    status, headers, body = _exchange(srv, "POST", "/index/i/query", b"Count(Row(f=1))")
    assert status == 200 and json.loads(body) == {"results": [1]}
    _standard_headers(headers, body)
    assert len(calls) == 1 and calls[0][0] == "sendall", calls
    # an error answer goes the same way
    del calls[:]
    status, headers, body = _exchange(srv, "POST", "/index/nope/query", b"Row(f=1)")
    assert status == 400 and "error" in json.loads(body)
    _standard_headers(headers, body)
    assert len(calls) == 1, calls
    del calls[:]
    status, headers, body = _exchange(srv, "GET", "/no/such/route")
    assert status == 404
    _standard_headers(headers, body)
    assert len(calls) == 1, calls


@pytest.mark.parametrize("cut", [None, 10, 150, 5000], ids=lambda c: f"cut_{c}")
def test_megabyte_answer_arrives_whole(srv, cut):
    """Head and body of a large answer are two buffers of one ``sendmsg``;
    where the kernel takes part of them the rest follows."""
    big = {"indexes": [{"name": "x" * 1000, "n": i} for i in range(1000)]}
    srv.api.schema = lambda: big
    calls = _count_sends(srv, cut)
    status, headers, body = _exchange(srv, "GET", "/schema")
    assert status == 200 and len(body) > 1_000_000 and json.loads(body) == big
    _standard_headers(headers, body)
    assert calls[0][0] == "sendmsg" and calls[0][1] > len(body)
    assert len(calls) == (1 if cut is None else 2 if cut >= 150 else 3), calls
    assert all(kind != "wfile.write" for kind, _ in calls)


def test_shed_answer_carries_the_callers_header(srv):
    from pilosa_tpu.server.qos import ShedError

    def shed():
        raise ShedError("team", 1.5)

    srv.api.status = shed
    calls = _count_sends(srv)
    status, headers, body = _exchange(srv, "GET", "/status")
    assert status == 429 and json.loads(body)["retryAfter"] == 2
    assert headers.get_all("Retry-After") == ["2"]
    _standard_headers(headers, body)
    assert len(calls) == 1, calls


def test_two_requests_on_one_keep_alive_connection(srv):
    import http.client

    calls = _count_sends(srv)
    c = http.client.HTTPConnection("localhost", srv.port, timeout=30)
    try:
        for _ in range(2):
            status, headers, body = _exchange(srv, "GET", "/status", conn=c)
            assert status == 200 and json.loads(body)["state"] == "NORMAL"
            assert headers.get("Connection") is None
        sock = c.sock
        status, _, _ = _exchange(srv, "GET", "/version", conn=c)
        assert status == 200 and c.sock is sock, "the connection was not reused"
        # a query string still reaches its route
        status, _, body = _exchange(srv, "GET", "/debug/events?since=0&limit=1", conn=c)
        assert status == 200 and "events" in json.loads(body)
    finally:
        c.close()
    assert [kind for kind, _ in calls] == ["sendall"] * 4


def test_http10_and_versionless_requests(srv):
    import socket

    def raw(request: bytes) -> bytes:
        with socket.create_connection(("localhost", srv.port), timeout=30) as s:
            s.sendall(request)
            out = b""
            while chunk := s.recv(65536):
                out += chunk
            return out

    # HTTP/1.0 without keep-alive: answered, then closed by the server
    out = raw(b"GET /version HTTP/1.0\r\n\r\n")
    head, _, body = out.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 200 OK\r\n") and "version" in json.loads(body)
    # no version at all: the body alone, as the stdlib answers HTTP/0.9
    assert "version" in json.loads(raw(b"GET /version\r\n\r\n"))
    # the refusals are the stdlib's
    assert b"Error code: 505" in raw(b"GET /version HTTP/3.0\r\n\r\n")
    assert raw(b"GET /version HTTP/1.1\r\n" + b"X: y\r\n" * 101 + b"\r\n").startswith(b"HTTP/1.1 431 ")
    assert raw(b"BREW /version HTTP/1.1\r\n\r\n").startswith(b"HTTP/1.1 501 ")


def test_gzip_request_is_still_gzipped(srv):
    import gzip

    calls = _count_sends(srv)
    status, headers, body = _exchange(srv, "GET", "/metrics", headers={"Accept-Encoding": "gzip"})
    assert status == 200 and headers.get_all("Content-Encoding") == ["gzip"]
    _standard_headers(headers, body, content_type="text/plain; version=0.0.4")
    assert b"pilosa_" in gzip.decompress(body)
    status, headers, plain = _exchange(srv, "GET", "/metrics")
    assert headers.get("Content-Encoding") is None and b"pilosa_" in plain
    assert len(calls) == 2 and all(kind != "wfile.write" for kind, _ in calls)


def test_tls_listener_answers(tmp_path):
    import datetime
    import ssl

    x509 = pytest.importorskip("cryptography.x509")
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import ec

    key = ec.generate_private_key(ec.SECP256R1())
    name = x509.Name([x509.NameAttribute(x509.NameOID.COMMON_NAME, "localhost")])
    now = datetime.datetime.now(datetime.timezone.utc)
    cert = (
        x509.CertificateBuilder().subject_name(name).issuer_name(name)
        .public_key(key.public_key()).serial_number(x509.random_serial_number())
        .not_valid_before(now - datetime.timedelta(days=1))
        .not_valid_after(now + datetime.timedelta(days=1))
        .add_extension(x509.SubjectAlternativeName([x509.DNSName("localhost")]), critical=False)
        .sign(key, hashes.SHA256())
    )
    (tmp_path / "cert.pem").write_bytes(cert.public_bytes(serialization.Encoding.PEM))
    (tmp_path / "key.pem").write_bytes(key.private_bytes(
        serialization.Encoding.PEM, serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption()))
    server = Server(API(Holder()), port=0, tls_cert=str(tmp_path / "cert.pem"),
                    tls_key=str(tmp_path / "key.pem"))
    server.serve_background()
    try:
        big = {"indexes": [{"name": "x" * 1000, "n": i} for i in range(300)]}
        server.api.schema = lambda: big
        ctx = ssl.create_default_context(cafile=str(tmp_path / "cert.pem"))
        import http.client

        c = http.client.HTTPSConnection("localhost", server.port, timeout=30, context=ctx)
        try:
            status, headers, body = _exchange(server, "GET", "/status", conn=c)
            assert status == 200 and json.loads(body)["state"] == "NORMAL"
            _standard_headers(headers, body)
            # past the size that is joined: a TLS connection has no sendmsg
            status, headers, body = _exchange(server, "GET", "/schema", conn=c)
            assert status == 200 and json.loads(body) == big
            _standard_headers(headers, body)
        finally:
            c.close()
    finally:
        server.close()


def test_debug_vars_counts_handler_cpu(srv):
    before = call(srv, "GET", "/debug/vars")["http"]
    assert set(before) == {"handlerCpuSeconds", "requests"}
    for _ in range(50):
        call(srv, "GET", "/status")
    after = call(srv, "GET", "/debug/vars")["http"]
    # each urlopen is a connection of its own: closed cells are kept
    assert after["requests"] - before["requests"] >= 51
    assert after["handlerCpuSeconds"] > before["handlerCpuSeconds"]
    # thread CPU, not wall: 51 tiny requests cannot have cost a second each
    assert after["handlerCpuSeconds"] - before["handlerCpuSeconds"] < 51
