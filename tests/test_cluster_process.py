"""Out-of-process cluster fault harness: three REAL node processes form
a cluster over HTTP; the test SIGKILLs one, asserts reads fail over and
the cluster degrades, restarts it from its data dir, and asserts
re-convergence — the reference's docker+pumba clustertests
(internal/clustertests/cluster_test.go:68-92) without containers."""

from __future__ import annotations

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

_WORKER = r"""
import json, os, sys, threading

os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH", "13")
os.environ["JAX_PLATFORMS"] = "cpu"  # several processes, one machine

sys.path.insert(0, os.environ["REPO"])
from pilosa_tpu.server.node import NodeServer
from pilosa_tpu.cluster.antientropy import AntiEntropyLoop

pid = int(sys.argv[1])
ports = json.loads(os.environ["PORTS"])
data_dir = os.path.join(os.environ["DATA"], f"node{pid}")

srv = NodeServer(
    data_dir=data_dir, host="127.0.0.1", port=ports[pid], replica_n=2
)
srv.client.timeout = 2.0  # fail fast against a killed peer
srv.start()
members = [(f"node{i}", f"http://127.0.0.1:{p}") for i, p in enumerate(ports)]
srv.join_static(members, "node0")
# fast probes so the test sees DEGRADED within seconds (reference gossip
# probe tuning + confirmNodeDown, cluster.go:1699-1768)
srv.start_membership(
    probe_interval=0.3, confirm_retries=2, confirm_interval=0.1
)
# interval overridable so the join-handshake test can park the loop far
# in the future and prove convergence WITHOUT it
AntiEntropyLoop(
    srv.syncer(), float(os.environ.get("AE_INTERVAL", "2.0"))
).start()
print("READY", flush=True)
threading.Event().wait()
"""


def _free_ports(n: int) -> list[int]:
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _http(port: int, method: str, path: str, body=None, timeout=5.0):
    data = (
        None
        if body is None
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


def _query(port: int, index: str, pql: str):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/index/{index}/query",
        data=pql.encode(),
        method="POST",
    )
    req.add_header("Content-Type", "text/plain")
    with urllib.request.urlopen(req, timeout=5.0) as resp:
        return json.loads(resp.read())


def _wait(predicate, timeout: float, what: str):
    deadline = time.time() + timeout
    last = None
    while time.time() < deadline:
        try:
            if predicate():
                return
        except Exception as e:  # noqa: BLE001 - peers flap during the test
            last = e
        time.sleep(0.25)
    pytest.fail(f"timed out waiting for {what} (last error: {last})")


class _Procs:
    def __init__(self, tmp_path, ports):
        self.tmp_path = tmp_path
        self.ports = ports
        self.script = tmp_path / "worker.py"
        self.script.write_text(_WORKER)
        self.env = dict(
            os.environ,
            REPO=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            PORTS=json.dumps(ports),
            DATA=str(tmp_path),
            JAX_PLATFORMS="cpu",
        )
        self.env.pop("XLA_FLAGS", None)
        self.procs: dict[int, subprocess.Popen] = {}

    def launch(self, pid: int) -> None:
        data_dir = self.tmp_path / f"node{pid}"
        data_dir.mkdir(exist_ok=True)
        (data_dir / ".id").write_text(f"node{pid}")
        # log to a file, not a pipe: an undrained pipe would block a
        # chatty node mid-test
        log = open(self.tmp_path / f"node{pid}.log", "ab")
        self.procs[pid] = subprocess.Popen(
            [sys.executable, str(self.script), str(pid)],
            env=self.env,
            stdout=log,
            stderr=subprocess.STDOUT,
        )
        log.close()
        _wait(
            lambda: _http(self.ports[pid], "GET", "/version"),
            60,
            f"node{pid} to serve",
        )

    def kill(self, pid: int) -> None:
        self.procs[pid].send_signal(signal.SIGKILL)
        self.procs[pid].wait(timeout=10)

    def stop_all(self) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
        for p in self.procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass


def test_kill_and_reconverge(tmp_path):
    ports = _free_ports(3)
    procs = _Procs(tmp_path, ports)
    try:
        for pid in range(3):
            procs.launch(pid)
        for pid in range(3):
            _wait(
                lambda p=pid: _http(ports[p], "GET", "/status")["state"]
                == "NORMAL",
                30,
                f"node{pid} NORMAL",
            )

        # schema + data through the coordinator; replica_n=2 so every
        # shard survives one node loss
        _http(ports[0], "POST", "/index/ci", {})
        _http(ports[0], "POST", "/index/ci/field/cf", {})
        width = 1 << 13  # the workers' PILOSA_TPU_SHARD_WIDTH exponent
        cols = [(i * 37) % (3 * width) for i in range(300)]
        _http(
            ports[0],
            "POST",
            "/index/ci/field/cf/import",
            {"rowIDs": [1] * len(cols), "columnIDs": cols},
        )
        expected = len(set(cols))
        for pid in range(3):
            got = _query(ports[pid], "ci", "Count(Row(cf=1))")["results"][0]
            assert got == expected, f"node{pid} before fault"

        # ---- kill a non-coordinator node ------------------------------
        procs.kill(1)
        _wait(
            lambda: _http(ports[0], "GET", "/status")["state"] == "DEGRADED",
            30,
            "coordinator to see DEGRADED",
        )
        # reads fail over to the surviving replica of every shard
        for pid in (0, 2):
            got = _query(ports[pid], "ci", "Count(Row(cf=1))")["results"][0]
            assert got == expected, f"node{pid} during outage"

        # ---- restart from the same data dir ---------------------------
        procs.launch(1)
        _wait(
            lambda: _http(ports[0], "GET", "/status")["state"] == "NORMAL",
            30,
            "cluster to re-converge to NORMAL",
        )
        # the revived node serves correct counts again (its fragments
        # reloaded from snapshot+op-log; cross-shard reads fan out)
        _wait(
            lambda: _query(ports[1], "ci", "Count(Row(cf=1))")["results"][0]
            == expected,
            30,
            "revived node to serve correct counts",
        )

        # normal operation after recovery: a write lands everywhere
        _query(ports[2], "ci", f"Set({3 * width - 1}, cf=2)")
        for pid in range(3):
            _wait(
                lambda p=pid: _query(ports[p], "ci", "Count(Row(cf=2))")[
                    "results"
                ][0]
                == 1,
                15,
                f"node{pid} sees post-recovery write",
            )
    finally:
        procs.stop_all()


def test_rejoin_handshake_serves_schema_before_anti_entropy(tmp_path):
    """A restarted node pulls the coordinator's NodeStatus (schema +
    available shards) in join_static itself, so a field created WHILE IT
    WAS DOWN is queryable immediately — the anti-entropy loop is parked
    600 s out and cannot be the healer here (reference gossip.go:321-357
    join-time push/pull state exchange)."""
    ports = _free_ports(2)
    procs = _Procs(tmp_path, ports)
    procs.env["AE_INTERVAL"] = "600"
    try:
        for pid in range(2):
            procs.launch(pid)
        for pid in range(2):
            _wait(
                lambda p=pid: _http(ports[p], "GET", "/status")["state"]
                == "NORMAL",
                30,
                f"node{pid} NORMAL",
            )
        _http(ports[0], "POST", "/index/ci", {})
        _http(ports[0], "POST", "/index/ci/field/cf", {})
        _query(ports[0], "ci", "Set(5, cf=1)")

        procs.kill(1)
        _wait(
            lambda: _http(ports[0], "GET", "/status")["state"] == "DEGRADED",
            30,
            "coordinator to see DEGRADED",
        )
        # schema mutations while node1 is down: a whole new field, and a
        # second index — both must reach the rejoiner via the handshake
        _http(ports[0], "POST", "/index/ci/field/nf", {})
        _http(ports[0], "POST", "/index/ci2", {})
        _http(ports[0], "POST", "/index/ci2/field/g", {})

        t0 = time.time()
        # _Procs.launch returns on the first /version poll, which can
        # precede join_static's handshake by a few ms: wait for a NEW
        # READY line (the log is append-mode across launches; READY
        # prints AFTER join_static) so the query below proves the
        # HANDSHAKE healed the schema, not luck — anti-entropy stays
        # parked either way
        log_path = tmp_path / "node1.log"
        ready_before = log_path.read_bytes().count(b"READY")
        procs.launch(1)
        _wait(
            lambda: log_path.read_bytes().count(b"READY") > ready_before,
            30,
            "rejoined worker past join_static",
        )
        # the rejoined node answers a query on the down-time field
        # CORRECTLY (0, not field-not-found) straight away
        got = _query(ports[1], "ci", "Count(Row(nf=7))")["results"][0]
        assert got == 0, got
        schema = _http(ports[1], "GET", "/schema")
        names = {i["name"]: {f["name"] for f in i.get("fields", [])}
                 for i in schema["indexes"]}
        assert "nf" in names.get("ci", set()), names
        assert "g" in names.get("ci2", set()), names
        # and pre-fault data still serves
        assert _query(ports[1], "ci", "Count(Row(cf=1))")["results"][0] == 1
        elapsed = time.time() - t0
        assert elapsed < 590, "test outlived the parked anti-entropy loop"
    finally:
        procs.stop_all()
