"""Crash-durable black box (obs/blackbox.py): spool rotation/caps,
torn-write recovery, dirty-vs-clean marker lifecycle, crash-loop
counting, postmortem assembly equivalence against the live /debug
surfaces, SIGTERM-is-clean — plus a real kill -9 → restart → postmortem
round-trip through the subprocess harness (test_cluster_process.py
style), including a SIGABRT last-words stack dump and a SIGTERM
exit-0 cycle that must produce NO new postmortem."""

from __future__ import annotations

import gzip
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.obs import events as ev
from pilosa_tpu.obs.blackbox import BlackBox
from pilosa_tpu.server.node import NodeServer

# -- spool mechanics (bare holder, no server) --------------------------------


def _bb(tmp_path, **kw) -> BlackBox:
    kw.setdefault("node_id", "t")
    return BlackBox(Holder(), str(tmp_path), **kw)


def test_spool_rotation_count_cap(tmp_path):
    bb = _bb(tmp_path, max_segments=3)
    assert bb.open() is None  # first boot: nothing to assemble
    for _ in range(6):
        bb.checkpoint("test")
    files = bb._seg_files()
    assert len(files) == 3
    # the NEWEST segments survive rotation
    seqs = sorted(int(os.path.basename(p)[4:12]) for p in files)
    assert seqs == [4, 5, 6]
    bb.close(clean=True)


def test_spool_rotation_byte_cap(tmp_path):
    bb = _bb(tmp_path, max_segments=100)
    bb.open()
    bb.checkpoint("seed")
    seg_size = os.path.getsize(bb._seg_files()[0])
    # cap below two segments: only the newest may survive
    bb.max_bytes = int(seg_size * 1.5)
    for _ in range(4):
        bb.checkpoint("test")
    files = bb._seg_files()
    assert len(files) == 1
    assert int(os.path.basename(files[0])[4:12]) == 5
    bb.close(clean=True)


def test_dirty_vs_clean_marker_lifecycle(tmp_path):
    # life 1: clean close -> life 2 sees a clean marker, no postmortem
    bb1 = _bb(tmp_path)
    assert bb1.open() is None
    bb1.checkpoint("work")
    bb1.close(clean=True)
    bb2 = _bb(tmp_path)
    assert bb2.open() is None
    assert bb2.postmortems()["postmortems"] == []
    # life 2 dies dirty (no close) -> life 3 assembles a postmortem
    bb2.checkpoint("work")
    bb3 = _bb(tmp_path)
    pm = bb3.open()
    assert pm is not None
    assert pm["crashLoop"] == 1
    assert pm["segments"] >= 1
    # the spool was consumed into the sealed bundle
    assert bb3._seg_files() == []
    got = bb3.postmortems()
    assert got["latest"] == pm["id"]
    assert got["postmortem"]["id"] == pm["id"]
    assert bb3.postmortem_detail(pm["id"])["id"] == pm["id"]
    bb3.close(clean=True)
    bb1.close()
    bb2.close(clean=False)


def test_crash_loop_counting_and_reset(tmp_path):
    boxes = []
    for expect in (1, 2, 3):
        bb = _bb(tmp_path)
        pm = bb.open()
        if expect == 1:
            assert pm is None  # first boot
        else:
            assert pm is not None and pm["crashLoop"] == expect - 1
        bb.checkpoint("work")
        boxes.append(bb)  # never closed: every life dies dirty
    clean = _bb(tmp_path)
    pm = clean.open()
    assert pm is not None and pm["crashLoop"] == 3
    clean.close(clean=True)
    after = _bb(tmp_path)
    assert after.open() is None  # clean marker: no postmortem...
    after.checkpoint("work")
    final = _bb(tmp_path)
    pm = final.open()
    assert pm is not None
    assert pm["crashLoop"] == 1  # ...and the loop counter was reset
    final.close(clean=True)
    for bb in boxes:
        bb.close(clean=False)
    after.close(clean=False)


def test_torn_write_recovery(tmp_path):
    bb = _bb(tmp_path)
    bb.open()
    holder = bb.holder
    holder.events.record("test-event", n=1)
    bb.checkpoint("one")
    holder.events.record("test-event", n=2)
    bb.checkpoint("two")
    files = bb._seg_files()
    assert len(files) == 2
    # tear the NEWEST segment mid-write (crash during the tmp write
    # would leave no segment at all; this models a torn filesystem)
    with open(files[-1], "r+b") as f:
        f.truncate(os.path.getsize(files[-1]) // 2)
    bb2 = _bb(tmp_path)
    pm = bb2.open()
    assert pm is not None
    assert pm["torn"] == 1
    assert pm["segments"] == 1  # the intact older segment still counts
    # evidence from the surviving segment made it into the bundle
    assert any(e["type"] == "test-event" for e in pm["events"])
    bb2.close(clean=True)
    bb.close(clean=False)


# -- postmortem assembly vs live surfaces (real NodeServer) ------------------


def _mknode(tmp_path, **kw) -> NodeServer:
    kw.setdefault("blackbox_interval", 60.0)  # manual checkpoints only
    kw.setdefault("flightrec_segment_seconds", 0.2)
    kw.setdefault("flightrec_sample_interval", 0.02)
    kw.setdefault("history_cadence", 0.2)
    kw.setdefault("rescache_entries", 0)
    kw.setdefault("trace_baseline_n", 1)  # keep every trace
    node = NodeServer(data_dir=str(tmp_path), port=0, **kw)
    node.start()
    return node


def _wait_for(predicate, timeout: float, what: str):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.05)
    pytest.fail(f"timed out waiting for {what}")


def _post(uri: str, path: str, body: bytes = b""):
    req = urllib.request.Request(uri + path, data=body, method="POST")
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read() or b"{}")


def test_postmortem_assembly_matches_live_surfaces(tmp_path):
    node = _mknode(tmp_path)
    try:
        node.api.create_index("bi", {})
        node.api.create_field("bi", "bf", {})
        # over HTTP: tracing roots live in the HTTP layer
        _post(node.uri, "/index/bi/query", b"Set(1, bf=1)")
        for _ in range(5):
            _post(node.uri, "/index/bi/query", b"Count(Row(bf=1))")
        # a history sample and a flightrec segment must exist
        time.sleep(0.6)
        node.flightrec.capture_incident({"type": "test", "note": "bb"})
        _wait_for(
            lambda: node.api.incidents_snapshot()["incidents"],
            5, "incident to freeze",
        )
        live_incidents = {
            b["id"] for b in node.api.incidents_snapshot()["incidents"]
        }
        live_traces = {
            t["traceId"] for t in node.holder.traces.summaries(32)
        }
        node.blackbox.checkpoint("test")
        live_last_seq = node.holder.events.last_seq

        # a second life opens the same spool while the first still holds
        # a "running" marker: exactly what a post-crash restart sees
        bb2 = BlackBox(Holder(), str(tmp_path), node_id="life2")
        pm = bb2.open()
        assert pm is not None
        assert {b["id"] for b in pm["incidents"]} == live_incidents
        assert live_incidents  # the equivalence must not be vacuous
        got_traces = {
            t["traceId"] for t in pm["traces"]["summaries"]
        }
        assert got_traces == live_traces and live_traces
        assert pm["flightrecSegments"]
        assert pm["history"]["series"]  # pre-crash series survived
        seqs = {e["seq"] for e in pm["events"]}
        # every event up to the checkpoint is in the bundle (node-start,
        # schema, incident) — the tail the operator reads first
        assert set(range(1, live_last_seq + 1)) <= seqs
        assert pm["slo"] is not None
        bb2.close(clean=False)
    finally:
        node.stop()


def test_sigterm_graceful_is_clean(tmp_path):
    node = _mknode(tmp_path)
    node.api.create_index("gi", {})
    node.shutdown_graceful()
    assert node._stopped
    # node-stop landed on the journal before teardown, so the final
    # black-box checkpoint carried it
    types = [
        e["type"] for e in node.holder.events.since(0)["events"]
    ]
    assert ev.EVENT_NODE_STOP in types
    node.stop()  # double-stop must be a no-op
    # restart on the same data dir: clean marker -> NO postmortem
    node2 = _mknode(tmp_path)
    try:
        assert node2.postmortem is None
        assert node2.api.postmortem_snapshot()["postmortems"] == []
    finally:
        node2.stop()


def test_dirty_restart_journals_crash_event(tmp_path):
    node = _mknode(tmp_path)
    node.blackbox.checkpoint("work")
    # simulate the crash: tear the node down WITHOUT the clean path
    node.blackbox._closed = True  # the writer must not reseal the marker
    node.blackbox._disarm_faulthandler()
    node.stop()
    node2 = _mknode(tmp_path)
    try:
        assert node2.postmortem is not None
        events = node2.holder.events.since(0)["events"]
        crash = [e for e in events if e["type"] == ev.EVENT_NODE_CRASH]
        assert crash and crash[0]["data"]["crashLoop"] == 1
        assert crash[0]["data"]["postmortem"] == node2.postmortem["id"]
    finally:
        node2.stop()


# -- gzip on debug endpoints + process self-metrics --------------------------


def _get(uri: str, path: str, headers: dict | None = None):
    req = urllib.request.Request(uri + path, headers=headers or {})
    resp = urllib.request.urlopen(req, timeout=10)
    body = resp.read()
    enc = resp.headers.get("Content-Encoding")
    if enc == "gzip":
        body = gzip.decompress(body)
    return resp, body, enc


def test_gzip_and_process_metrics(tmp_path):
    node = _mknode(tmp_path)
    try:
        node.api.create_index("gz", {})
        node.api.create_field("gz", "f", {})
        # over HTTP so traces are kept (baseline_n=1) and the traces
        # payload is reliably past the gzip floor
        for i in range(8):
            _post(node.uri, "/index/gz/query", f"Set({i}, f=1)".encode())
            _post(node.uri, "/index/gz/query", b"Count(Row(f=1))")
        time.sleep(0.5)  # a couple of history samples
        # gzip negotiated on the large debug surfaces
        for path in ("/metrics", "/debug/history", "/debug/traces"):
            resp, body, enc = _get(
                node.uri, path, {"Accept-Encoding": "gzip"}
            )
            assert enc == "gzip", path
            assert len(body) > 512, path
        # no Accept-Encoding -> identity (curl without -H must not
        # receive binary)
        _, body, enc = _get(node.uri, "/debug/history")
        assert enc is None
        json.loads(body)
        # the internal client decodes transparently
        hist = node.client.debug_history(node.uri)
        assert hist["series"]
        pm = node.client.debug_postmortem(node.uri)
        assert pm["postmortems"] == []
        # process self-metrics in /metrics
        _, body, _ = _get(node.uri, "/metrics")
        text = body.decode()
        assert "pilosa_process_uptime_seconds" in text
        assert "pilosa_process_start_time_seconds" in text
        assert 'pilosa_build_info{version="' in text
        # process + blackbox blocks in /debug/vars
        _, body, _ = _get(node.uri, "/debug/vars")
        snap = json.loads(body)
        assert snap["process"]["pid"] == os.getpid()
        assert snap["process"]["uptimeSeconds"] >= 0
        assert "checkpoints" in snap["blackbox"]
    finally:
        node.stop()


# -- real kill -9 / SIGABRT / SIGTERM round-trip (subprocess harness) --------

_WORKER = r"""
import json, os, sys, threading

os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH", "13")
os.environ["JAX_PLATFORMS"] = "cpu"  # several processes, one machine

sys.path.insert(0, os.environ["REPO"])
from pilosa_tpu.server.node import NodeServer

pid = int(sys.argv[1])
ports = json.loads(os.environ["PORTS"])
data_dir = os.path.join(os.environ["DATA"], f"node{pid}")

srv = NodeServer(
    data_dir=data_dir, host="127.0.0.1", port=ports[pid],
    blackbox_interval=0.3,
    flightrec_segment_seconds=0.2,
    flightrec_sample_interval=0.02,
    flightrec_spike_504=1,
    history_cadence=0.2,
)
assert srv.install_signal_handlers()  # SIGTERM must drain and exit 0
srv.start()
print("READY", flush=True)
threading.Event().wait()
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _http(port: int, method: str, path: str, body=None, timeout=5.0):
    data = (
        None if body is None
        else (body if isinstance(body, bytes) else json.dumps(body).encode())
    )
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method
    )
    if data is not None and not isinstance(body, bytes):
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        out = resp.read()
        return json.loads(out) if out.strip() else {}


def _wait(predicate, timeout: float, what: str):
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            if predicate():
                return
        except Exception as e:  # noqa: BLE001 - node is flapping on purpose
            last = e
        time.sleep(0.25)
    pytest.fail(f"timed out waiting for {what} (last error: {last})")


def _launch(tmp_path, port: int) -> subprocess.Popen:
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    data_dir = tmp_path / "node0"
    data_dir.mkdir(exist_ok=True)
    (data_dir / ".id").write_text("node0")
    env = dict(
        os.environ,
        REPO=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        PORTS=json.dumps([port]),
        DATA=str(tmp_path),
        JAX_PLATFORMS="cpu",
    )
    env.pop("XLA_FLAGS", None)
    log = open(tmp_path / "node0.log", "ab")
    proc = subprocess.Popen(
        [sys.executable, str(script), "0"],
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )
    log.close()
    _wait(lambda: _http(port, "GET", "/version"), 60, "node to serve")
    return proc


def test_kill9_restart_postmortem_roundtrip(tmp_path):
    port = _free_port()
    proc = _launch(tmp_path, port)
    try:
        # ---- life 1: real load + a frozen incident --------------------
        _http(port, "POST", "/index/ci", {})
        _http(port, "POST", "/index/ci/field/cf", {})
        for i in range(8):
            _http(
                port, "POST", "/index/ci/query",
                f"Set({i * 7}, cf=1)".encode(),
            )
            _http(port, "POST", "/index/ci/query", b"Count(Row(cf=1))")
        # deadline-504 spike: tiny ?timeout= budgets trip the flight
        # recorder's spike trigger (spike_504=1)
        for _ in range(6):
            try:
                _http(
                    port, "POST", "/index/ci/query?timeout=0.000001",
                    b"Count(Row(cf=1))",
                )
            except urllib.error.HTTPError:
                pass
        _wait(
            lambda: _http(port, "GET", "/debug/incidents")["incidents"],
            30, "incident to freeze",
        )
        incident_ids = {
            b["id"]
            for b in _http(port, "GET", "/debug/incidents")["incidents"]
        }
        # the sync incident flush must have reached the spool before we
        # pull the plug — that is the whole point of the black box
        _wait(
            lambda: _http(port, "GET", "/debug/vars")["blackbox"][
                "syncFlushes"] >= 1,
            10, "incident flushed to spool",
        )
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)

        # ---- life 2: postmortem carries the dead life's evidence ------
        proc = _launch(tmp_path, port)
        got = _http(port, "GET", "/debug/postmortem")
        assert got["latest"] is not None
        pm = got["postmortem"]
        assert pm["crashLoop"] == 1
        assert incident_ids <= {b["id"] for b in pm["incidents"]}
        assert pm["flightrecSegments"]
        assert pm["history"]["series"]
        assert pm["traces"]["summaries"] is not None
        assert any(
            e["type"] == "node-start" for e in pm["events"]
        )
        # ?id= serves the same sealed bundle; ?cluster=true merges it
        detail = _http(
            port, "GET", f"/debug/postmortem?id={pm['id']}"
        )
        assert detail["id"] == pm["id"]
        merged = _http(port, "GET", "/debug/postmortem?cluster=true")
        assert any(s["id"] == pm["id"] for s in merged["postmortems"])
        # the crash itself is on the journal
        events = _http(port, "GET", "/debug/events")["events"]
        assert any(e["type"] == "node-crash-detected" for e in events)

        # ---- life 2 dies by SIGABRT: faulthandler last words ----------
        proc.send_signal(signal.SIGABRT)
        proc.wait(timeout=10)
        assert proc.returncode != 0
        proc = _launch(tmp_path, port)
        got = _http(port, "GET", "/debug/postmortem")
        assert len(got["postmortems"]) == 2
        pm2 = got["postmortem"]
        assert pm2["crashLoop"] == 2
        assert pm2["lastWords"]  # all-thread stack dump made it to disk
        assert "Thread" in pm2["lastWords"] or "File" in pm2["lastWords"]

        # ---- life 3 exits via SIGTERM: clean, NO new postmortem -------
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=30)
        assert proc.returncode == 0
        proc = _launch(tmp_path, port)
        got = _http(port, "GET", "/debug/postmortem")
        assert len(got["postmortems"]) == 2  # unchanged
        assert got["latest"] == pm2["id"]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
