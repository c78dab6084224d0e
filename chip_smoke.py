"""chip_smoke.py — the served path, end to end, on the chip.

    python3 chip_smoke.py

Starts ``python -m pilosa_tpu.cli server`` as a child, loads BASELINE.json's
"10B-bit index" through the import routes a user's client calls (set field
``f``: 64 rows x 160 shards x 2^20 columns = 10.7e9 bits, a 1.34 GiB dense
stack in HBM; set field ``g``: 8 rows; int field ``v``: 0..10^6, depth 20),
drives every batched lane over HTTP from concurrent connections, and checks
each answer against a plain numpy reference kept here.  Then it reads the
server's own surfaces to establish that the DEVICE did the work (every
device a TPU, Pallas and XLA launches, gram gates proven, no demotion, no
mesh fallback, HBM budget probed and filled), SIGTERMs the server, starts it
again on the same data dir, reads acknowledged writes and bulk data back,
shows the second start was served by the persistent compile cache, and runs
``tools/kernel_census.py`` in a second child.

This process never imports jax: one process owns the chip at a time.  The
full report (shape, load, starts, census, failures) is one ``report {...}``
line; the last line of stdout is the result and nothing else,
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``, with
``"ok": true`` and exit code 0 only when every phase passed on a TPU at the
full shape.  Where JAX finds no accelerator the script exits 2 and prints no
result.  ``--rehearsal`` (how this script itself is debugged under
``JAX_PLATFORMS=cpu`` at a tiny shape) runs the same stages, says so, and is
never a pass: ``"ok": false``, exit 3.
"""

from __future__ import annotations

import argparse
import glob
import http.client
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
INDEX = "smoke"
F_ROWS, G_ROWS = 64, 8
V_MAX = 1_000_000  # depth 20
FULL_SHARDS = 160
FULL_WIDTH = 1 << 20
PAIR_OPS = ("Intersect", "Union", "Difference", "Xor")
# a few dense rows and a long sparse tail; the device footprint (rows x
# shards x 128 KiB) does not depend on these, only the load time does
F_DENSITY = [0.05] * 4 + [0.005] * 12 + [0.0002] * 48
G_DENSITY = [0.05, 0.02, 0.01, 0.005, 0.002, 0.001, 0.0005, 0.0002]
# verdict keys a CPU rehearsal cannot satisfy by construction
CPU_EXPECTED = {"platform", "lanes.pallas", "gate.self", "gate.cross", "device.cap"}


def log(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


_T0 = time.monotonic()


class SmokeFailure(Exception):
    """A phase failed; the message says what and why."""


# ---------------------------------------------------------------------------
# numpy reference: packed bits per (shard, row), values per column
# ---------------------------------------------------------------------------


class Reference:
    """The same PQL semantics in plain numpy, independent of pilosa_tpu:
    one bit per (row, column) packed little-endian per shard, and the int
    field as parallel (column, value) arrays."""

    def __init__(self, shards: int, width: int):
        self.shards, self.width = shards, width
        self.f = np.zeros((shards, F_ROWS, width // 8), np.uint8)
        self.g = np.zeros((shards, G_ROWS, width // 8), np.uint8)
        self.v_cols = np.zeros(0, np.int64)
        self.v_vals = np.zeros(0, np.int64)
        self._lock = threading.Lock()

    def field(self, name: str) -> np.ndarray:
        return {"f": self.f, "g": self.g}[name]

    def load_shard(self, shard: int, name: str, rows: np.ndarray, cols: np.ndarray) -> None:
        dense = np.zeros((self.field(name).shape[1], self.width), bool)
        dense[rows, cols] = True
        self.field(name)[shard] = np.packbits(dense, axis=1, bitorder="little")

    def load_values(self, cols: np.ndarray, vals: np.ndarray) -> None:
        with self._lock:
            self.v_cols = np.concatenate([self.v_cols, cols])
            self.v_vals = np.concatenate([self.v_vals, vals])

    def row(self, name: str, r: int) -> np.ndarray:
        return self.field(name)[:, r]

    def set_bit(self, name: str, r: int, col: int, on: bool) -> None:
        s, c = divmod(col, self.width)
        byte, bit = divmod(c, 8)
        if on:
            self.field(name)[s, r, byte] |= np.uint8(1 << bit)
        else:
            self.field(name)[s, r, byte] &= np.uint8(~(1 << bit) & 0xFF)

    def get_bit(self, name: str, r: int, col: int) -> bool:
        s, c = divmod(col, self.width)
        return bool(self.field(name)[s, r, c // 8] >> (c % 8) & 1)

    @staticmethod
    def count(words: np.ndarray) -> int:
        return int(np.bitwise_count(words).sum(dtype=np.int64))

    def combine(self, op: str, rows: list[np.ndarray]) -> np.ndarray:
        out = rows[0]
        for r in rows[1:]:
            if op == "Intersect":
                out = out & r
            elif op == "Union":
                out = out | r
            elif op == "Difference":
                out = out & ~r
            elif op == "Xor":
                out = out ^ r
            else:
                raise ValueError(op)
        return out

    def row_counts(self, name: str, filt: np.ndarray | None = None) -> np.ndarray:
        bits = self.field(name)
        out = np.zeros(bits.shape[1], np.int64)
        for s in range(self.shards):  # per shard: bounded temporaries
            blk = bits[s] if filt is None else bits[s] & filt[s][None, :]
            out += np.bitwise_count(blk).sum(axis=1, dtype=np.int64)
        return out

    def shard_columns(self, name: str, r: int, s: int) -> np.ndarray:
        """Global ids of the columns row ``r`` has set in shard ``s``."""
        on = np.flatnonzero(np.unpackbits(self.field(name)[s, r], bitorder="little"))
        return on + s * self.width

    def columns(self, name: str, r: int) -> list[int]:
        return np.concatenate(
            [self.shard_columns(name, r, s) for s in range(self.shards)]
        ).tolist()

    def value_mask_bits(self, filt_name: str, r: int) -> np.ndarray:
        """Which int-field columns have bit (filt_name, r) set."""
        s, c = np.divmod(self.v_cols, self.width)
        return (self.field(filt_name)[s, r, c // 8] >> (c % 8).astype(np.uint8)) & 1 == 1


# ---------------------------------------------------------------------------
# data: made from the seed, one shard at a time
# ---------------------------------------------------------------------------


def gen_shard(seed: int, shard: int, width: int):
    """((f_rows, f_cols), (g_rows, g_cols), (v_cols_global, v_vals)) of
    one shard, shard-local columns for the set fields."""
    rng = np.random.default_rng([seed, shard])

    def set_field(density):
        rows, cols = [], []
        for r, d in enumerate(density):
            c = np.unique(rng.integers(0, width, max(1, int(d * width))))
            rows.append(np.full(len(c), r, np.int64))
            cols.append(c)
        return np.concatenate(rows), np.concatenate(cols)

    f = set_field(F_DENSITY)
    g = set_field(G_DENSITY)
    v_cols = np.unique(rng.integers(0, width, max(8, width // 512)))
    v_vals = rng.integers(0, V_MAX + 1, len(v_cols))
    if shard == 0:  # both ends of the range, so the field reaches depth 20
        v_vals[0], v_vals[-1] = 0, V_MAX
    return f, g, (v_cols + shard * width, v_vals)


# ---------------------------------------------------------------------------
# HTTP client and the server child
# ---------------------------------------------------------------------------


class Client:
    """One keep-alive connection; every request either answers 200 with
    JSON or raises SmokeFailure."""

    def __init__(self, port: int, timeout: float = 900.0):
        self.port, self.timeout = port, timeout
        self.conn: http.client.HTTPConnection | None = None

    def request(self, method: str, path: str, body: bytes | None = None,
                ctype: str = "text/plain"):
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=self.timeout
                )
            try:
                self.conn.request(
                    method, path, body=body, headers={"Content-Type": ctype}
                )
                resp = self.conn.getresponse()
                data = resp.read()
                break
            except (http.client.HTTPException, OSError) as e:
                self.close()  # an idle keep-alive the server dropped: once more
                if attempt:
                    raise SmokeFailure(f"{method} {path}: {type(e).__name__}: {e}")
        if resp.status != 200:
            raise SmokeFailure(
                f"{method} {path} -> {resp.status}: {data[:300].decode('utf-8', 'replace')}"
            )
        return json.loads(data) if data else None

    def get(self, path: str):
        return self.request("GET", path)

    def post_json(self, path: str, obj):
        return self.request("POST", path, json.dumps(obj).encode(), "application/json")

    def query(self, pql: str) -> list:
        return self.request("POST", f"/index/{INDEX}/query", pql.encode())["results"]

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Server:
    """``python -m pilosa_tpu.cli server`` as a child process."""

    def __init__(self, data_dir: str, port: int, log_path: str, env: dict | None = None):
        self.data_dir, self.port, self.log_path = data_dir, port, log_path
        self.env = dict(os.environ if env is None else env)
        self.env["PYTHONPATH"] = REPO + os.pathsep + self.env.get("PYTHONPATH", "")
        self.proc: subprocess.Popen | None = None
        self.cfg = os.path.join(os.path.dirname(log_path), "smoke_config.json")
        with open(self.cfg, "w") as f:
            # the in-memory stats client, so /debug/vars carries the
            # holder's counters (dist_mesh_fallback_total among them)
            json.dump({"metric": {"service": "expvar"}}, f)

    def start(self, ready_timeout: float = 300.0) -> float:
        """Spawn and wait until /status answers; seconds that took."""
        t0 = time.monotonic()
        self._log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "pilosa_tpu.cli", "server", "-d", self.data_dir,
             "--bind", f"127.0.0.1:{self.port}", "-c", self.cfg],
            cwd=REPO, env=self.env, stdout=self._log, stderr=subprocess.STDOUT,
        )
        c = Client(self.port, timeout=5.0)
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise SmokeFailure(
                    f"server exited with code {rc} before serving:\n{self.log_tail()}"
                )
            try:
                c.get("/status")
                c.close()
                return time.monotonic() - t0
            except SmokeFailure:
                c.close()
            if time.monotonic() - t0 > ready_timeout:
                self.kill()
                raise SmokeFailure(
                    f"server not ready after {ready_timeout:.0f}s:\n{self.log_tail()}"
                )
            time.sleep(0.25)

    def terminate(self, timeout: float = 120.0) -> int:
        """SIGTERM and wait; the exit code (0 = drained cleanly)."""
        assert self.proc is not None
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise SmokeFailure(f"server ignored SIGTERM for {timeout:.0f}s")
        self._log.close()
        self.proc = None
        return rc

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        self.proc = None

    def log_tail(self, n: int = 4000) -> str:
        try:
            with open(self.log_path, "rb") as f:
                f.seek(0, os.SEEK_END)
                f.seek(max(0, f.tell() - n))
                return f.read().decode("utf-8", "replace")
        except OSError:
            return ""


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_concurrent(port: int, jobs: list) -> list:
    """Run ``jobs[i](client)`` on its own connection and thread, all
    released at once so the requests meet in the batcher's window."""
    barrier = threading.Barrier(len(jobs))
    out: list = [None] * len(jobs)

    def work(i):
        c = Client(port)
        try:
            barrier.wait(timeout=60)
            out[i] = jobs[i](c)
        except Exception as e:  # surfaced by the caller below
            out[i] = e
        finally:
            c.close()

    threads = [threading.Thread(target=work, args=(i,), daemon=True) for i in range(len(jobs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=1200)
    for t, o in zip(threads, out):
        if t.is_alive():
            raise SmokeFailure("a concurrent request never returned")
        if isinstance(o, Exception):
            raise o if isinstance(o, SmokeFailure) else SmokeFailure(repr(o))
    return out


# ---------------------------------------------------------------------------
# stages
# ---------------------------------------------------------------------------


def stage_schema(c: Client) -> None:
    c.post_json(f"/index/{INDEX}", {})
    c.post_json(f"/index/{INDEX}/field/f", {})
    c.post_json(f"/index/{INDEX}/field/g", {})
    c.post_json(
        f"/index/{INDEX}/field/v",
        {"options": {"type": "int", "min": 0, "max": V_MAX}},
    )


def stage_load(port: int, ref: Reference, seed: int, loaders: int = 4) -> dict:
    """Every shard through ``import-roaring`` (f, g) and the JSON
    ``import`` route with ``values`` (v), from a few client threads."""
    from pilosa_tpu.storage import roaring  # the client-side encoder only

    width = ref.width
    todo = list(range(ref.shards))
    lock = threading.Lock()
    stats = {"bits": 0, "values": 0, "roaring_bytes": 0}
    errors: list = []

    def work():
        c = Client(port)
        try:
            while True:
                with lock:
                    if not todo or errors:
                        return
                    shard = todo.pop(0)
                (fr, fc), (gr, gc), (vc, vv) = gen_shard(seed, shard, width)
                ref.load_shard(shard, "f", fr, fc)
                ref.load_shard(shard, "g", gr, gc)
                ref.load_values(vc, vv)
                sent = 0
                for name, rows, cols in (("f", fr, fc), ("g", gr, gc)):
                    pos = np.sort((rows * width + cols).astype(np.uint64))
                    blob = roaring.serialize(pos)
                    c.request(
                        "POST", f"/index/{INDEX}/field/{name}/import-roaring/{shard}",
                        blob, "application/octet-stream",
                    )
                    sent += len(blob)
                c.post_json(
                    f"/index/{INDEX}/field/v/import",
                    {"columnIDs": vc.tolist(), "values": vv.tolist()},
                )
                with lock:
                    stats["bits"] += len(fc) + len(gc)
                    stats["values"] += len(vc)
                    stats["roaring_bytes"] += sent
        except Exception as e:
            errors.append(e)
        finally:
            c.close()

    t0 = time.monotonic()
    threads = [threading.Thread(target=work, daemon=True) for _ in range(loaders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise SmokeFailure(f"load failed: {errors[0]!r}")
    stats["seconds"] = time.monotonic() - t0
    stats["bits_per_s"] = stats["bits"] / stats["seconds"]
    return stats


class Checker:
    """Counts requests per class and collects mismatches."""

    def __init__(self):
        self.requests: dict[str, int] = {}
        self.mismatches: list[str] = []
        self._lock = threading.Lock()

    def check(self, cls: str, what: str, got, want) -> None:
        with self._lock:
            if got != want:
                self.mismatches.append(f"{cls}: {what}: got {got!r}, want {want!r}"[:400])

    def sent(self, cls: str, n: int = 1) -> None:
        with self._lock:
            self.requests[cls] = self.requests.get(cls, 0) + n


def pair_flight(rng, rows: int, conns: int, calls: int) -> list[list[tuple]]:
    """``conns`` requests of ``calls`` distinct (op, a, b) pair queries
    over the first ``rows`` rows of f."""
    seen, out = set(), []
    for i in range(conns):
        req = []
        while len(req) < calls:
            a, b = (int(x) for x in rng.choice(rows, 2, replace=False))
            q = (PAIR_OPS[(i + len(req)) % 4], a, b)
            if q not in seen:
                seen.add(q)
                req.append(q)
        out.append(req)
    return out


def run_pair_flight(port: int, ref: Reference, chk: Checker, flight, cls="pair_count") -> float:
    def job(req):
        def run(c):
            pql = " ".join(f"Count({op}(Row(f={a}), Row(f={b})))" for op, a, b in req)
            got = c.query(pql)
            chk.sent(cls)
            for (op, a, b), g in zip(req, got, strict=True):
                want = ref.count(ref.combine(op, [ref.row("f", a), ref.row("f", b)]))
                chk.check(cls, f"Count({op}(f={a}, f={b}))", g, want)
        return run

    t0 = time.monotonic()
    run_concurrent(port, [job(r) for r in flight])
    return time.monotonic() - t0


def check_topn(chk: Checker, what: str, got: list, counts: np.ndarray, n: int) -> None:
    """Ties may come back in any order: the count sequence must be the
    reference's top-n, and every id must carry its own count."""
    want = sorted((int(x) for x in counts if x > 0), reverse=True)[:n]
    chk.check("topn", f"{what} counts", [p["count"] for p in got], want)
    for p in got:
        chk.check("topn", f"{what} id {p['id']}", p["count"], int(counts[p["id"]]))


def stage_reads(port: int, ref: Reference, chk: Checker, seed: int) -> dict:
    rng = np.random.default_rng([seed, 1 << 20])
    c = Client(port)
    timings = {}

    # distinct pair counts from 16 connections: first over 8 rows (the
    # gather-fused gram at its row floor), then over all 64 (full gram)
    narrow = pair_flight(rng, 8, 16, 2)
    wide = pair_flight(rng, F_ROWS, 16, 4)
    timings["first_flight_s"] = run_pair_flight(port, ref, chk, narrow)
    run_pair_flight(port, ref, chk, wide)
    run_pair_flight(port, ref, chk, pair_flight(rng, F_ROWS, 16, 4))

    # TopN, unfiltered and filtered
    check_topn(chk, "TopN(f, n=10)", c.query("TopN(f, n=10)")[0],
               ref.row_counts("f"), 10)
    by_g = [ref.row_counts("f", ref.row("g", j)) for j in range(G_ROWS)]
    for grow, n in ((0, 10), (3, 5)):
        got = c.query(f"TopN(f, Row(g={grow}), n={n})")[0]
        check_topn(chk, f"TopN(f, Row(g={grow}), n={n})", got, by_g[grow], n)
    chk.sent("topn", 3)

    # GroupBy over two fields: the cross gram
    got = c.query("GroupBy(Rows(f), Rows(g))")[0]
    chk.sent("groupby")
    got_map = {
        (grp["group"][0]["rowID"], grp["group"][1]["rowID"]): grp["count"] for grp in got
    }
    want_map = {
        (i, j): int(x) for j, counts in enumerate(by_g) for i, x in enumerate(counts) if x
    }
    chk.check("groupby", "GroupBy(Rows(f), Rows(g))", got_map, want_map)

    # BSI aggregates, then a burst of distinct range predicates
    vals = ref.v_vals
    got = c.query("Sum(field=v) Min(field=v) Max(field=v)")
    chk.sent("bsi_aggregate")
    chk.check("bsi_aggregate", "Sum", got[0], {"value": int(vals.sum()), "count": len(vals)})
    chk.check("bsi_aggregate", "Min", got[1]["value"], int(vals.min()))
    chk.check("bsi_aggregate", "Max", got[2]["value"], int(vals.max()))
    m = ref.value_mask_bits("g", 0)
    got = c.query("Sum(Row(g=0), field=v)")
    chk.sent("bsi_aggregate")
    chk.check("bsi_aggregate", "Sum(Row(g=0))", got[0],
              {"value": int(vals[m].sum()), "count": int(m.sum())})

    def bsi_job(xs):
        def run(cc):
            got = cc.query(" ".join(f"Count(Row(v {op} {x}))" for op, x in xs))
            chk.sent("bsi_range")
            for (op, x), g in zip(xs, got, strict=True):
                want = int((vals < x).sum() if op == "<" else (vals > x).sum())
                chk.check("bsi_range", f"Count(Row(v {op} {x}))", g, want)
        return run

    for _ in range(2):  # the first burst warms the stack, the second batches
        xs = rng.choice(V_MAX, 32, replace=False)
        run_concurrent(port, [
            bsi_job([("<" if i % 2 else ">", int(xs[2 * i])), ("<", int(xs[2 * i + 1]))])
            for i in range(16)
        ])

    # 3-way Intersect and 4-way Union: the compiled-AST lane
    def ast_job(i):
        def run(cc):
            a = [int(x) for x in rng2[i]]
            q3 = f"Count(Intersect(Row(f={a[0]}), Row(f={a[1]}), Row(f={a[2]})))"
            q4 = "Count(Union(" + ", ".join(f"Row(f={r})" for r in a[:4]) + "))"
            got = cc.query(q3 + " " + q4)
            chk.sent("ast_count")
            chk.check("ast_count", q3, got[0],
                      ref.count(ref.combine("Intersect", [ref.row("f", r) for r in a[:3]])))
            chk.check("ast_count", q4, got[1],
                      ref.count(ref.combine("Union", [ref.row("f", r) for r in a[:4]])))
        return run

    rng2 = [rng.choice(16, 4, replace=False) for _ in range(8)]
    run_concurrent(port, [ast_job(i) for i in range(8)])
    c.close()
    timings["wide_flight"] = wide
    return timings


def stage_writes(port: int, ref: Reference, chk: Checker, seed: int, wide) -> list[tuple]:
    """Acknowledged Set/Clear, read back; then the same pair counts again
    (every cache between the write and the answer must have noticed)."""
    rng = np.random.default_rng([seed, 2 << 20])
    c = Client(port)
    n_cols = ref.shards * ref.width
    writes = []
    for i in range(8):
        row = int(rng.integers(0, F_ROWS))
        col = int(rng.integers(0, n_cols))
        while ref.get_bit("f", row, col):
            col = int(rng.integers(0, n_cols))
        chk.check("write", f"Set({col}, f={row})", c.query(f"Set({col}, f={row})")[0], True)
        ref.set_bit("f", row, col, True)
        writes.append(("f", row, col, True))
        # clear a bit the bulk load set
        row = int(rng.integers(0, F_ROWS))
        col = int(rng.choice(ref.shard_columns("f", row, int(rng.integers(0, ref.shards)))))
        chk.check("write", f"Clear({col}, f={row})", c.query(f"Clear({col}, f={row})")[0], True)
        ref.set_bit("f", row, col, False)
        writes.append(("f", row, col, False))
    chk.sent("write", 16)
    stage_readback(c, ref, chk, writes)
    c.close()
    run_pair_flight(port, ref, chk, wide, cls="pair_count_after_write")
    return writes


def stage_readback(c: Client, ref: Reference, chk: Checker, writes) -> None:
    """Each written row's count, and a sparse row column for column."""
    for name, row, col, on in writes:
        got = c.query(f"Count(Row({name}={row}))")[0]
        chk.check("readback", f"Count(Row({name}={row})) after write", got,
                  ref.count(ref.row(name, row)))
    chk.sent("readback", len(writes))
    sparse = F_ROWS - 1
    got = c.query(f"Row(f={sparse})")[0]["columns"]
    chk.sent("readback")
    chk.check("readback", f"Row(f={sparse}) columns", got, ref.columns("f", sparse))


# ---------------------------------------------------------------------------
# verdict: did the device do the work?  (pure; unit-tested on canned payloads)
# ---------------------------------------------------------------------------


def verdict(dbg: dict, diag: dict, expect: dict) -> list[tuple[str, str]]:
    """(key, message) for everything in the server's own surfaces that
    says the chip did NOT do the work.  ``dbg`` is /debug/vars, ``diag``
    /internal/diagnostics; ``expect`` carries ``stack_bytes`` (the dense
    stacks' size) and ``loaded_bytes``."""
    bad: list[tuple[str, str]] = []
    devices = (diag.get("system") or {}).get("devices") or []
    if not devices:
        bad.append(("platform", "server reports no devices"))
    for d in devices:
        if d.get("platform") != "tpu":
            bad.append(("platform", f"device {d.get('id')} is {d.get('platform')!r}, not tpu"))

    k = dbg.get("kernels") or {}
    lanes = k.get("dispatch_lanes") or {}
    for lane in ("pallas", "xla"):
        if not lanes.get(lane):
            bad.append((f"lanes.{lane}", f"no {lane} launches (dispatch_lanes={lanes})"))
    gates = k.get("gram_gates") or {}
    multi = len(devices) > 1
    for name in ("self", "cross"):
        g = gates.get(name) or {}
        # on a mesh the cross gram has no Pallas program (it runs as XLA
        # inside shard_map), so its gate stays unprobed there
        ok_wanted = (True, None) if (multi and name == "cross") else (True,)
        if g.get("ok") not in ok_wanted or g.get("fails", 0) != 0:
            bad.append((f"gate.{name}", f"gram_gates.{name} = {g}"))
    if k.get("pallas_fallbacks", 0) != 0:
        bad.append(("fallbacks", f"pallas_fallbacks = {k.get('pallas_fallbacks')}"))
    for label, v in (k.get("counters") or {}).items():
        if label.startswith("kernel_demotions") and v:
            bad.append(("demoted", f"{label} = {v}"))

    for label, v in (dbg.get("counters") or {}).items():
        if label.startswith("dist_mesh_fallback_total") and v:
            bad.append(("mesh_fallback", f"{label} = {v}"))
    if (dbg.get("dist") or {}).get("meshFallbacks", 0):
        bad.append(("mesh_fallback", f"dist.meshFallbacks = {dbg['dist']['meshFallbacks']}"))

    dev = dbg.get("device") or {}
    if not isinstance(dev.get("capBytes"), int) or dev["capBytes"] <= 0:
        bad.append(("device.cap", f"device budget cap is {dev.get('capBytes')!r}, not a number"))
    if dev.get("usedBytes", 0) < expect["stack_bytes"]:
        bad.append(("device.used",
                    f"device usedBytes {dev.get('usedBytes')} < stacks {expect['stack_bytes']}"))
    h2d = (k.get("transfer_bytes") or {}).get("h2d", 0)
    if h2d < expect["loaded_bytes"]:
        bad.append(("h2d", f"h2d bytes {h2d} < loaded {expect['loaded_bytes']}"))
    up = ((dbg.get("ingest") or {}).get("uploader") or {})
    if not up.get("uploads"):
        bad.append(("ingest", f"ingest uploader ran no uploads ({up})"))
    if not (dbg.get("batcher") or {}).get("coalesced"):
        bad.append(("batcher", "no requests coalesced in the batcher"))
    sites = (dbg.get("devledger") or {}).get("sites") or {}
    for site in ("ops.kernels", "ops.bsi", "exec.astbatch", "ingest.upload"):
        if not (sites.get(site) or {}).get("launches"):
            bad.append(("devledger", f"device ledger booked no launches at {site}"))

    for stem in ("libpilosa_hostops", "libpilosa_native"):
        n = (dbg.get("native") or {}).get(stem)
        if not n or not n.get("path"):
            bad.append(("native", f"{stem} not loaded: {n}"))
    return bad


def per_device_bytes(diag: dict) -> list[int | None]:
    return [d.get("bytesInUse") for d in (diag.get("system") or {}).get("devices") or []]


def placement_failures(diag: dict) -> list[tuple[str, str]]:
    """On a multi-device host every device holds its share: a device
    carrying more than twice the lightest one's bytes is the 'device 0
    takes the whole ingest' symptom."""
    used = [b for b in per_device_bytes(diag) if b is not None]
    if len(used) > 1 and max(used) > 2 * max(min(used), 1):
        return [("placement", f"per-device bytes in use uneven: {used}")]
    return []


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def cache_entries() -> int | None:
    from pilosa_tpu import jaxcache  # imports jax only inside configure()

    try:
        return len(os.listdir(os.environ.get(jaxcache.ENV_VAR) or jaxcache.DEFAULT_DIR))
    except OSError:
        return None


def ledger_counts(dbg: dict) -> dict:
    """Compiles and persistent-cache retrievals of one server life."""
    led = dbg.get("devledger") or {}
    return {
        "compiles": (led.get("totals") or {}).get("compiles"),
        "persistent_cache_hits": led.get("persistentCacheHits"),
    }


def evidence(dbg: dict, diag: dict) -> dict:
    """What the server's surfaces said, for the printed record."""
    k = dbg.get("kernels") or {}
    return {
        "per_device_bytes_in_use": per_device_bytes(diag),
        "dispatch_lanes": k.get("dispatch_lanes"),
        "gram_gates": k.get("gram_gates"),
        "kernel_dispatch": {
            label[len("kernel_dispatch"):]: v
            for label, v in (k.get("counters") or {}).items()
            if label.startswith("kernel_dispatch{")
        },
        "ledger_launches": {
            name: site.get("launches")
            for name, site in ((dbg.get("devledger") or {}).get("sites") or {}).items()
        },
        "batcher": dbg.get("batcher"),
        "serving_cache": dbg.get("serving_cache"),
        "device_budget": {
            key: (dbg.get("device") or {}).get(key) for key in ("capBytes", "usedBytes")
        },
        "h2d_bytes": (k.get("transfer_bytes") or {}).get("h2d"),
        "native": {
            stem: {"built_here": v.get("built"), "path": os.path.basename(v.get("path") or "")}
            for stem, v in (dbg.get("native") or {}).items()
        },
    }


def stage_restarted(port: int, ref: Reference, chk: Checker, seed: int, writes) -> dict:
    """Against the restarted server: the first start's first flight again
    (same shapes, so the compile cache can serve them), the acknowledged
    writes, and a sample of the bulk data."""
    rng = np.random.default_rng([seed, 1 << 20])  # stage_reads' stream
    flight_s = run_pair_flight(port, ref, chk, pair_flight(rng, 8, 16, 2),
                               cls="pair_count_restarted")
    c = Client(port)
    stage_readback(c, ref, chk, writes)
    check_topn(chk, "TopN(f, n=64) after restart",
               c.query(f"TopN(f, n={F_ROWS})")[0], ref.row_counts("f"), F_ROWS)
    chk.check("restart", "Sum(field=v)", c.query("Sum(field=v)")[0],
              {"value": int(ref.v_vals.sum()), "count": len(ref.v_vals)})
    chk.sent("restart", 2)
    dbg = c.get("/debug/vars")
    c.close()
    return {"first_flight_s": flight_s, **ledger_counts(dbg)}


def run_census(env: dict, log_path: str) -> dict:
    """tools/kernel_census.py in its own child (the server has exited)."""
    with open(log_path, "wb") as err:
        p = subprocess.run(
            [sys.executable, "-m", "tools.kernel_census"],
            cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=err, timeout=900,
        )
    lines = p.stdout.decode("utf-8", "replace").splitlines()
    for line in lines:
        if line.startswith("census "):
            print(line[:300], flush=True)
    try:
        out = json.loads(lines[-1])
    except (IndexError, ValueError):
        raise SmokeFailure(f"census printed no result (exit {p.returncode})")
    out["exit"] = p.returncode
    return out


def result_line(ok: bool, device: dict) -> str:
    """The last line of stdout: exactly ``ok`` and ``device``, the device as
    JAX reported it to the server.  Everything else is in the report line."""
    return json.dumps({
        "ok": bool(ok),
        "device": {
            "platform": str(device["platform"]), "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--shards", type=int, default=FULL_SHARDS,
                    help="cut the shard count (printed under 'reduced'; "
                         "never the shard width or the row count)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="debug this script without a chip: tiny shape, "
                         "labelled, never a pass")
    ap.add_argument("--keep", action="store_true", help="keep the work dir")
    args = ap.parse_args(argv)

    if not os.path.exists(os.path.join(REPO, "pilosa_tpu", "cli.py")):
        print("chip_smoke: the program is not here (no pilosa_tpu/cli.py "
              f"beside {os.path.basename(__file__)}); nothing to run", file=sys.stderr)
        return 2
    if "PILOSA_TPU_SHARD_WIDTH" in os.environ and not args.rehearsal:
        print("chip_smoke: PILOSA_TPU_SHARD_WIDTH is set; the smoke runs at "
              "the default shard width only", file=sys.stderr)
        return 2
    shards = args.shards if not args.rehearsal else min(args.shards, 8)

    # Built from what git would commit: no native library found on disk is
    # ever loaded — drop them all and let this machine build its own.
    removed = glob.glob(os.path.join(REPO, "native", "*.so"))
    for path in removed:
        os.unlink(path)
    log(f"removed {len(removed)} native libraries found on disk; they are rebuilt here")

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    data_dir = os.path.join(work, "data")
    srv_log = os.path.join(work, "server.log")
    port = free_port()
    srv = Server(data_dir, port, srv_log)
    out: dict = {"ok": False, "seed": args.seed}
    failures: list[str] = []
    try:
        # ---- first start -------------------------------------------------
        cache0 = cache_entries()
        try:
            start1 = srv.start()
        except SmokeFailure as e:
            # no backend, no chip free, no program: not a result either way
            print(f"chip_smoke: {e}", file=sys.stderr)
            return 2
        c = Client(port)
        diag = c.get("/internal/diagnostics")
        devices = (diag.get("system") or {}).get("devices") or []
        on_chip = bool(devices) and all(d.get("platform") == "tpu" for d in devices)
        if not on_chip and not args.rehearsal:
            print("chip_smoke: the server found no accelerator (devices: "
                  f"{[d.get('platform') for d in devices]}); this is not a "
                  "chip run and there is no stand-in", file=sys.stderr)
            srv.terminate()
            return 2
        if on_chip and args.rehearsal:
            print("chip_smoke: --rehearsal is for machines without a chip", file=sys.stderr)
            srv.terminate()
            return 2
        out["device"] = {
            "platform": devices[0]["platform"], "kind": devices[0]["kind"],
            "count": len(devices),
        }
        info = c.get("/info")
        width = int(info["shardWidth"])
        if on_chip and width != FULL_WIDTH:
            raise SmokeFailure(f"shard width {width}, not 2^20")
        proc = c.get("/debug/vars")["process"]
        out["versions"] = {"jax": proc.get("jax"), "python": proc.get("python")}
        stack_bytes = shards * (F_ROWS + G_ROWS + 22) * (width // 8)
        out["shape"] = {
            "shards": shards, "shard_width": width, "f_rows": F_ROWS, "g_rows": G_ROWS,
            "v_depth": 20, "index_bits": shards * F_ROWS * width,
            "stack_bytes": stack_bytes,
        }
        if shards != FULL_SHARDS or width != FULL_WIDTH:
            out["reduced"] = {"shards": shards, "of": FULL_SHARDS, "shard_width": width}
        log(f"server up in {start1:.1f}s on {out['device']}; shape {out['shape']}")

        ref = Reference(shards, width)
        stage_schema(c)
        load = stage_load(port, ref, args.seed)
        out["load"] = {k: round(v, 1) if isinstance(v, float) else v for k, v in load.items()}
        log(f"loaded {load['bits']} bits + {load['values']} values in "
            f"{load['seconds']:.1f}s ({load['bits_per_s']:.0f} bits/s)")

        chk = Checker()
        reads = stage_reads(port, ref, chk, args.seed)
        log(f"reads done; first flight {reads['first_flight_s']:.1f}s; "
            f"{len(chk.mismatches)} mismatches")
        writes = stage_writes(port, ref, chk, args.seed, reads["wide_flight"])
        log(f"writes read back; {len(chk.mismatches)} mismatches")

        dbg = c.get("/debug/vars")
        diag = c.get("/internal/diagnostics")
        c.close()
        out["versions"]["libtpu"] = _libtpu_version()
        expect = {
            "stack_bytes": stack_bytes,
            "loaded_bytes": shards * F_ROWS * (width // 8),
        }
        bad = verdict(dbg, diag, expect) + placement_failures(diag)
        out.update(evidence(dbg, diag))
        first = {"ready_s": start1, "first_flight_s": reads["first_flight_s"],
                 **ledger_counts(dbg), "cache_entries_before": cache0}

        # ---- guarantee: SIGTERM, restart on the same data, read back --------
        rc = srv.terminate()
        if rc != 0:
            failures.append(f"server exit code {rc} on SIGTERM (want 0)")
        first["cache_entries_after"] = cache_entries()
        start2 = srv.start()
        chk2 = Checker()
        second = {"ready_s": start2, **stage_restarted(port, ref, chk2, args.seed, writes),
                  "cache_entries_after": cache_entries()}
        rc = srv.terminate()
        if rc != 0:
            failures.append(f"restarted server exit code {rc} on SIGTERM (want 0)")
        for life in (first, second):
            # the first start's load sits between ready and its first flight
            # and is not part of this sum
            life["time_to_first_answer_s"] = life["ready_s"] + life["first_flight_s"]
        out["starts"] = {
            name: {k2: round(v2, 1) if isinstance(v2, float) else v2 for k2, v2 in life.items()}
            for name, life in (("first", first), ("second", second))
        }
        if not second["persistent_cache_hits"]:
            failures.append("second start reports no persistent-cache hits "
                            f"({second['persistent_cache_hits']})")
        log(f"restart: ready {start2:.1f}s, first flight {second['first_flight_s']:.1f}s, "
            f"cache hits {second['persistent_cache_hits']}, compiles {second['compiles']}")

        requests = dict(chk.requests)
        for k2, v2 in chk2.requests.items():
            requests[k2] = requests.get(k2, 0) + v2
        out["requests"] = requests
        mismatches = chk.mismatches + chk2.mismatches
        failures += [f"mismatch: {m}" for m in mismatches]
        keys_bad = [(k2, m) for k2, m in bad
                    if not (args.rehearsal and k2 in CPU_EXPECTED)]
        failures += [f"verdict[{k2}]: {m}" for k2, m in keys_bad]
        if args.rehearsal:
            out["not_checked_on_cpu"] = sorted({k2 for k2, _ in bad if k2 in CPU_EXPECTED})

        # ---- kernel census, in a second child ------------------------------
        census = run_census(srv.env, os.path.join(work, "census_err.log"))
        out["census"] = {k2: census[k2] for k2 in ("ok", "rehearsal", "cases", "failed", "exit")}
        out["dispatch_round_trip_ms"] = census["dispatch_round_trip_ms"]
        out["census"]["table"] = [
            f"{r['kernel']}{tuple(r['shape'])} {r['params']}: {r['status']}"
            + (f" {r['seconds']}s" if "seconds" in r else "")
            for r in census["results"]
        ]
        if census["failed"] or census["exit"] not in ((3,) if args.rehearsal else (0,)):
            failures.append(f"census: exit {census['exit']}, failed {census['failed']}")
    except SmokeFailure as e:
        failures.append(str(e))
        log("server log tail:\n" + srv.log_tail())
    finally:
        srv.kill()
        if args.keep:
            log(f"work dir kept: {work}")
        else:
            shutil.rmtree(work, ignore_errors=True)

    out["failures"] = failures[:50]
    if args.rehearsal:
        out["rehearsal"] = True
        out["stages_ok"] = not failures
    else:
        out["ok"] = not failures and out.get("device", {}).get("platform") == "tpu" \
            and "reduced" not in out
    for f in failures[:50]:
        log("FAIL " + f)
    log("rehearsal (not a pass)" if args.rehearsal else ("PASS" if out["ok"] else "FAIL"))
    print("report " + json.dumps(out), flush=True)
    if "device" not in out:
        # died before the server named its devices: nothing to report on
        return 1
    print(result_line(out["ok"], out["device"]), flush=True)
    if args.rehearsal:
        return 3
    return 0 if out["ok"] else 1


def _libtpu_version():
    from importlib import metadata

    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        return None


if __name__ == "__main__":
    sys.exit(main())
