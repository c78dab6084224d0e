"""CI smoke check for the crash-durable black box (obs/blackbox.py).

Boots a REAL two-node cluster as separate OS processes, then proves the
postmortem plane end to end with an actual crash:

* drives a deadline-504 spike on node B so the flight recorder freezes
  an incident, and waits for the black box's synchronous incident flush
  to reach the on-disk spool;
* ``kill -9``s node B (no atexit, no signal handler — nothing runs);
* restarts node B from the SAME data dir and asserts
  ``GET /debug/postmortem`` serves the dead life's sealed bundle: the
  frozen incident, flight-recorder segments, the trailing history
  window, and a crash-loop count of 1;
* asserts the crash landed on the event journal as
  ``node-crash-detected``;
* asserts the coordinator's ``GET /debug/postmortem?cluster=true``
  merges node B's bundle into the cluster-wide view;
* SIGTERMs node B and asserts the graceful spine: exit status 0, and a
  restart finds NO new postmortem (clean marker honored).

Exit status 0 on success; any assertion/exception fails the CI step.
Run as ``python -m tools.smoke_postmortem``.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

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
    data_dir=data_dir, host="127.0.0.1", port=ports[pid], replica_n=2,
    blackbox_interval=0.3,
    flightrec_segment_seconds=0.2,
    flightrec_sample_interval=0.02,
    flightrec_spike_504=1,
    history_cadence=0.2,
)
srv.client.timeout = 2.0
srv.install_signal_handlers()
srv.start()
members = [(f"node{i}", f"http://127.0.0.1:{p}") for i, p in enumerate(ports)]
srv.join_static(members, "node0")
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
        except Exception as e:  # noqa: BLE001 - node B flaps on purpose
            last = e
        time.sleep(0.25)
    raise SystemExit(f"FAIL: timed out waiting for {what} (last: {last})")


def _launch(tmp: str, ports: list[int], pid: int) -> subprocess.Popen:
    script = os.path.join(tmp, "worker.py")
    with open(script, "w") as f:
        f.write(_WORKER)
    data_dir = os.path.join(tmp, f"node{pid}")
    os.makedirs(data_dir, exist_ok=True)
    with open(os.path.join(data_dir, ".id"), "w") as f:
        f.write(f"node{pid}")
    env = dict(
        os.environ,
        REPO=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        PORTS=json.dumps(ports),
        DATA=tmp,
        JAX_PLATFORMS="cpu",
    )
    env.pop("XLA_FLAGS", None)
    log = open(os.path.join(tmp, f"node{pid}.log"), "ab")
    proc = subprocess.Popen(
        [sys.executable, script, str(pid)],
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )
    log.close()
    _wait(
        lambda: _http(ports[pid], "GET", "/version"),
        60, f"node{pid} to serve",
    )
    return proc


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="pilosa-smoke-pm-")
    ports = _free_ports(2)
    procs: dict[int, subprocess.Popen] = {}
    try:
        procs[0] = _launch(tmp, ports, 0)
        procs[1] = _launch(tmp, ports, 1)
        a, b = ports

        # schema + load through the coordinator; reads against B
        _http(a, "POST", "/index/ci", {})
        _http(a, "POST", "/index/ci/field/cf", {})
        for i in range(8):
            _http(b, "POST", "/index/ci/query", f"Set({i * 7}, cf=1)".encode())
            _http(b, "POST", "/index/ci/query", b"Count(Row(cf=1))")
        print("ok: 2-node cluster up, data written")

        # deadline-504 spike on B -> flight recorder freezes an incident
        for _ in range(6):
            try:
                _http(
                    b, "POST", "/index/ci/query?timeout=0.000001",
                    b"Count(Row(cf=1))",
                )
            except urllib.error.HTTPError:
                pass
        _wait(
            lambda: _http(b, "GET", "/debug/incidents")["incidents"],
            30, "incident to freeze on node B",
        )
        incident_ids = {
            bun["id"]
            for bun in _http(b, "GET", "/debug/incidents")["incidents"]
        }
        _wait(
            lambda: _http(b, "GET", "/debug/vars")["blackbox"]["syncFlushes"]
            >= 1,
            10, "incident flush to reach the spool",
        )
        print(f"ok: incident frozen + flushed ({sorted(incident_ids)})")

        # the crash: nothing graceful runs
        procs[1].send_signal(signal.SIGKILL)
        procs[1].wait(timeout=10)
        procs[1] = _launch(tmp, ports, 1)

        got = _http(b, "GET", "/debug/postmortem")
        assert got["latest"], "no postmortem after kill -9"
        pm = got["postmortem"]
        assert pm["crashLoop"] == 1, pm["crashLoop"]
        assert incident_ids <= {bun["id"] for bun in pm["incidents"]}
        assert pm["flightrecSegments"], "no flight-recorder segments"
        assert pm["history"] and pm["history"]["series"], "no history window"
        events = _http(b, "GET", "/debug/events")["events"]
        assert any(e["type"] == "node-crash-detected" for e in events)
        print(f"ok: postmortem {pm['id']} served after restart")

        # coordinator merges the dead life into the cluster view
        merged = _http(a, "GET", "/debug/postmortem?cluster=true")
        ids = {s["id"] for s in merged["postmortems"]}
        assert pm["id"] in ids, (ids, merged.get("unreachable"))
        print("ok: coordinator ?cluster=true merged node B's bundle")

        # graceful spine: SIGTERM drains, exits 0, leaves a clean marker
        procs[1].send_signal(signal.SIGTERM)
        procs[1].wait(timeout=30)
        assert procs[1].returncode == 0, procs[1].returncode
        procs[1] = _launch(tmp, ports, 1)
        got = _http(b, "GET", "/debug/postmortem")
        assert len(got["postmortems"]) == 1, got["postmortems"]
        print("ok: SIGTERM exit 0, no new postmortem on clean restart")
        print("smoke_postmortem: PASS")
        return 0
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)


if __name__ == "__main__":
    sys.exit(main())
