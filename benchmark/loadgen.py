"""A load-generator process: loads data and drives connections.

Started by ``run.py`` (several of them, so the generator's interpreter
lock is never the server's).  Reads one JSON command per line on stdin and
answers each with one JSON line on stdout:

``load``   import this worker's share of the load stage
``run``    drive connections from ``start_at`` for ``seconds`` (the warm-up
           or the window), or until the file ``stop_file`` appears, and
           pickle the per-request log to ``log``
``stream`` import the slabs of ``slabs`` on one connection, slab ``i`` from
           ``start_at + i * every_s`` and never before, until they are all
           acknowledged or ``stop_file`` appears; the per-request log is in
           the answer (a worker that streams drives no readers)
``quit``

It is fed only what the generator makes from the seed; it imports nothing
from the program.
"""

from __future__ import annotations

import http.client
import json
import os
import pickle
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import datagen  # noqa: E402
import generator  # noqa: E402

REQUEST_TIMEOUT = 300.0


class Conn:
    """One keep-alive connection.  ``request`` returns (status, body); a
    transport error is status 0."""

    def __init__(self, port: int, timeout: float = REQUEST_TIMEOUT):
        self.port, self.timeout = port, timeout
        self.conn: http.client.HTTPConnection | None = None

    def request(self, method: str, path: str, body: bytes | None = None,
                ctype: str = "text/plain") -> tuple[int, bytes]:
        for attempt in (0, 1):
            fresh = self.conn is None
            if fresh:
                self.conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=self.timeout)
            try:
                self.conn.request(method, path, body=body, headers={"Content-Type": ctype})
                resp = self.conn.getresponse()
                return resp.status, resp.read()
            except (http.client.HTTPException, OSError):
                self.close()
                if fresh or attempt:  # an idle keep-alive the server dropped is retried once
                    break
        return 0, b""

    def send(self, path: str, body: bytes, ctype: str = "text/plain") -> tuple[int, bytes]:
        return self.request("POST", path, body, ctype)

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


# ---------------------------------------------------------------------------
# load stage
# ---------------------------------------------------------------------------


def load_shards(cfg: dict, seed: int, port: int, shards: list[int]) -> dict:
    """One import-roaring request per field and shard, the whole shard's
    columns in it (an int field as its ``bsig_`` view)."""
    index = cfg["index"]
    c = Conn(port)
    bits = requests = 0
    t_first = time.monotonic()
    for shard in shards:
        slabs = [datagen.gen_slab(cfg, seed, shard, k) for k in range(datagen.slabs_per_shard(cfg))]
        for f in datagen.stored_fields(cfg):
            values = np.concatenate([s[f["name"]] for s in slabs])
            view, blob, n = datagen.slab_import(cfg, f, values, 0)
            status, body = c.send(f"/index/{index}/field/{f['name']}/import-roaring/{shard}{view}",
                                  blob, "application/octet-stream")
            if status != 200:
                raise RuntimeError(f"load import {f['name']}/{shard} -> {status}: {body[:200]!r}")
            bits += n
            requests += 1
    c.close()
    return {"bits": bits, "requests": requests, "t_first": t_first, "t_last": time.monotonic()}


# ---------------------------------------------------------------------------
# the write stream
# ---------------------------------------------------------------------------


def slab_requests(cfg: dict, seed: int, shard: int, slab: int) -> list[tuple[str, str, bytes, int]]:
    """(field, path, body, bits) of the import-roaring requests that carry
    one slab, one per field, at the slab's own columns of the shard."""
    values = datagen.gen_slab(cfg, seed, shard, slab)
    col0 = slab * int(cfg["slab_rides"])
    out = []
    for f in datagen.stored_fields(cfg):
        view, blob, n = datagen.slab_import(cfg, f, values[f["name"]], col0)
        out.append((f["name"], f"/index/{cfg['index']}/field/{f['name']}/import-roaring/{shard}{view}",
                    blob, n))
    return out


def send_slab(c: Conn, requests: list, k: int, shard: int, slab: int, due: float) -> list[dict]:
    """The slab's requests one after another; every one logged on the clock
    the readers log on.  A request that fails is logged and the rest still go."""
    out = []
    for field, path, blob, bits in requests:
        t_send = time.monotonic()
        status, _ = c.send(path, blob, "application/octet-stream")
        out.append({"k": k, "field": field, "shard": shard, "slab": slab, "due": due, "bits": bits,
                    "t_send": t_send, "t_ack": time.monotonic(), "status": status})
    return out


def stream(job: dict, cfg: dict) -> dict:
    c = Conn(job["port"])
    imports: list[dict] = []
    for i, (k, shard, slab) in enumerate(job["slabs"]):
        due = job["start_at"] + i * job["every_s"]
        requests = slab_requests(cfg, job["seed"], shard, slab)  # made ahead of its time
        while not (stopped := os.path.exists(job["stop_file"])) and time.monotonic() < due:
            time.sleep(max(0.0, min(due - time.monotonic(), 0.02)))
        if stopped:
            break
        imports += send_slab(c, requests, k, shard, slab, due)
    c.close()
    return {"imports": imports}


# ---------------------------------------------------------------------------
# a run: connections between two instants
# ---------------------------------------------------------------------------


def drive_connection(job: dict, mix: generator.Mix, conn_id: int, out: list) -> None:
    """Closed loop: the next request goes when the last has answered."""
    c = Conn(job["port"])
    path = f"/index/{job['index']}/query"
    one_in = int(job["check_one_in"])
    end = job["start_at"] + job["seconds"]
    seen: set[str] = set()  # a connection's first request of each class is always kept
    _sleep_until(job["start_at"])
    stream = mix.stream(job["seed"], job["phase"], conn_id)
    for n, (cls, pql) in enumerate(stream):
        t_send = time.monotonic()
        if t_send >= end or os.path.exists(job["stop_file"]):
            break
        status, body = c.send(path, pql.encode())
        t_recv = time.monotonic()
        keep = (status != 200 or cls not in seen
                or generator.sampled(job["seed"], conn_id, n, one_in))
        seen.add(cls)
        out.append({"conn": conn_id, "n": n, "cls": cls, "t_send": t_send,
                    "t_recv": t_recv, "status": status,
                    "pql": pql if keep else None, "body": body if keep else None})
    c.close()


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))


def run(job: dict, cfg: dict, mix_data: dict) -> dict:
    mix = generator.Mix(cfg, mix_data)
    reads: list = []
    threads = [threading.Thread(target=drive_connection, args=(job, mix, cid, reads), daemon=True)
               for cid in job["conns"]]
    cpu0, t0 = os.times(), time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=job["seconds"] + REQUEST_TIMEOUT + 60)
    alive = sum(t.is_alive() for t in threads)
    cpu1, t1 = os.times(), time.monotonic()
    with open(job["log"], "wb") as f:
        pickle.dump(reads, f)
    busy = (cpu1.user - cpu0.user) + (cpu1.system - cpu0.system)
    return {"requests": len(reads), "stuck_threads": alive, "cpu_share": busy / max(t1 - t0, 1e-9)}


def main() -> int:
    setup = json.loads(sys.stdin.readline())
    cfg, mix_data = setup["config"], setup["mix"]
    for line in sys.stdin:
        job = json.loads(line)
        try:
            if job["cmd"] == "quit":
                break
            if job["cmd"] == "load":
                out = load_shards(cfg, job["seed"], job["port"], job["shards"])
            elif job["cmd"] == "run":
                out = run(job, cfg, mix_data)
            elif job["cmd"] == "stream":
                out = stream(job, cfg)
            else:
                raise ValueError(f"unknown command {job['cmd']!r}")
            out["ok"] = True
        except Exception as e:  # reported to the parent, which ends the run
            out = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        sys.stdout.write(json.dumps(out) + "\n")
        sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
