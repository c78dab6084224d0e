"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``: start the server child, make the
schema, load the configuration's data from the seed through the import
route, warm up every shape of the cell's mix, drive the mix for the
window (its readers and, where the mix has a ``stream`` block, a paced
stream of imports beside them), read back, stop the server, judge the
window's own answers against the numpy reference, and print one result
line built from the manifest.

This process never imports JAX: the server child holds the chip.  The
cell's configuration, traffic mix and per-layer metrics are files found by
the names in ``BENCHMARK.json`` (``README.md`` beside this file).

No process this one starts outlives it, however it ends, and it ends by
itself inside ``RUN_LIMIT_S`` (``README.md``, "How a run ends").

``--rehearsal`` drives the same stages on the CPU at the tiny shape each
configuration file gives under ``rehearsal``; it says so, exits 3 and can
never print a passing result.  ``--control lossy`` and ``--control stale``
judge the reference with a stated guarantee broken in the program's place,
which has to come out as not correct.
"""

from __future__ import annotations

import argparse
import ctypes
import importlib.util
import json
import math
import os
import pickle
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import manifest as mf  # noqa: E402
from datagen import stored_fields  # noqa: E402  (numpy only)
from loadgen import Conn  # noqa: E402  (numpy only)

TRACE_CAP_S = 10.0      # a traced run's window: traces are large and tracing slows the host
WARM_POLL_S = 1.0
WARM_QUIET_S = 10.0     # warm-up ends after this long with nothing compiled or retrieved
WARM_LIMIT_S = 900.0
LOADERS = 4             # processes that import the load stage, each its share of the shards
# Changed shards of one field's device copy that the warm-up makes the program refresh at once
# (``sweep_refresh``).  A window meets 1: the stream sends a field's import once in ``every_s`` and the
# readers read every field the mix names many times in between.  It meets 2 where no reader got to
# the field between two slabs, a stall of the dispatcher as long as the stream's gap.  3 would take
# a stall of two gaps; a window that meets one says so (``window_compiles``, the program's name in the log).
SWEEP_SHARDS = (1, 2)
# Spawn to result line.  Three times the slowest whole run the ledger holds (cold
# cache: set-up 205.58 s, window 50, reference 11-13, trace reduction); a cell whose
# cold run does not fit is too large for the grid (README.md, "How a run ends").
RUN_LIMIT_S = 900.0
CALLER_SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)
SERVER_LOG = "server.log"  # in the work directory
PR_SET_PDEATHSIG = 1    # <linux/prctl.h>
KILL_WAIT_S = 60.0      # how long a killed child may take to be gone before the run ends without it


class RunFailure(Exception):
    """The run cannot give a result; the message says why."""


class RunCut(BaseException):
    """The run is ended from outside the stage it is in: by a signal, or by
    the alarm of its limit.  Not an ``Exception``, so that no stage takes it
    for a failure of its own and goes on."""

    def __init__(self, signum: int):
        super().__init__(signum)
        self.signum = signum


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.monotonic()


# ---------------------------------------------------------------------------
# processes
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Procs:
    """Every process the run starts, so that none outlives it.  Each asks
    the kernel, between ``fork`` and ``exec``, for SIGKILL when the thread
    that spawned it dies (the setting survives ``execve``), which holds
    even where this process is killed and runs no ``finally``.  They stay
    in this process's group: a caller that signals the group reaches them."""

    def __init__(self):
        self.all: list[subprocess.Popen] = []
        self._preexec = None
        if sys.platform.startswith("linux"):
            # bound before any fork: the child may only make the two calls
            prctl = ctypes.CDLL(None, use_errno=True).prctl
            prctl.restype = ctypes.c_int
            prctl.argtypes = [ctypes.c_int] + [ctypes.c_ulong] * 4
            parent = os.getpid()

            def die_with_parent() -> None:
                prctl(PR_SET_PDEATHSIG, signal.SIGKILL, 0, 0, 0)
                if os.getppid() != parent:  # it died before the call: no signal will come
                    os.kill(os.getpid(), signal.SIGKILL)

            self._preexec = die_with_parent
        else:
            log(f"no parent-death signal on {sys.platform}: a killed run can leave its children")

    def spawn(self, argv: list[str], **kw) -> subprocess.Popen:
        proc = subprocess.Popen(argv, cwd=REPO, preexec_fn=self._preexec, **kw)
        self.all.append(proc)
        return proc

    def kill(self) -> None:
        """SIGKILL whatever still runs, and wait until it is gone: an
        abandoned run has nothing to keep, and whoever looks once this
        process has ended must find none of them.  A server child that holds
        four chips and 28 GB of rows took 20-24 s to go (my chip runs, PR 28)."""
        alive = [p for p in self.all if p.poll() is None]
        for p in alive:
            p.kill()
        end = time.monotonic() + KILL_WAIT_S
        for p in alive:
            try:
                p.wait(timeout=max(0.0, end - time.monotonic()))
            except subprocess.TimeoutExpired:
                log(f"process {p.pid} ({p.args[1]}) is still there {KILL_WAIT_S:g} s after SIGKILL")


class Watch:
    """The run's one clock: the stages it has been through, its limit, and
    the signals that end it.  SIGTERM, SIGINT, SIGHUP and the limit's alarm
    all raise ``RunCut`` into the main thread, wherever it blocks; a second
    one is ignored while the first is cleaned up after.  Outside the main
    thread no handler can be installed and the run has no limit."""

    def __init__(self, limit_s: float):
        self.limit_s = limit_s
        self.stages: list[tuple[str, float]] = []
        self._old: dict = {}
        self._t_cut = 0.0

    def __enter__(self) -> "Watch":
        if threading.current_thread() is threading.main_thread():
            self._old[signal.SIGALRM] = signal.signal(signal.SIGALRM, self._cut)
            for s in CALLER_SIGNALS:
                # one the caller had ignored (nohup, a shell's background job) stays ignored
                if signal.getsignal(s) != signal.SIG_IGN:
                    self._old[s] = signal.signal(s, self._cut)
            signal.setitimer(signal.ITIMER_REAL, self.limit_s)
        else:
            log("not in the main thread: no limit on this run, and a signal ends it as it would any process")
        return self

    def __exit__(self, *exc) -> None:
        if self._old:
            signal.setitimer(signal.ITIMER_REAL, 0)
            for s, handler in self._old.items():
                signal.signal(s, signal.SIG_DFL if handler is None else handler)

    def _cut(self, signum: int, frame) -> None:
        signal.setitimer(signal.ITIMER_REAL, 0)
        for s in self._old:
            signal.signal(s, signal.SIG_IGN)
        self._t_cut = time.monotonic()
        raise RunCut(signum)

    def stage(self, name: str) -> None:
        self.stages.append((name, time.monotonic()))
        log(f"stage {name}")

    def cut_message(self, cut: RunCut) -> str:
        ends = [t for _, t in self.stages[1:]] + [self._t_cut]
        times = ", ".join(f"{name} {end - t:.1f}s" for (name, t), end in zip(self.stages, ends))
        why = (f"the run passed its limit of {self.limit_s:g} s" if cut.signum == signal.SIGALRM
               else f"the run was ended by {signal.Signals(cut.signum).name}")
        return f"{why} in stage {self.stages[-1][0] if self.stages else 'start'}; stage times: {times}"


class Http(Conn):
    """The parent's own connection to the server (schema, /debug/vars,
    read-back): a transport error ends the run."""

    def request(self, method: str, path: str, body: bytes | None = None,
                ctype: str = "application/json") -> tuple[int, bytes]:
        status, data = super().request(method, path, body, ctype)
        if status == 0:
            raise RunFailure(f"{method} {path}: the server did not answer")
        return status, data

    def json(self, method: str, path: str, obj=None):
        body = None if obj is None else json.dumps(obj).encode()
        status, data = self.request(method, path, body)
        if status != 200:
            raise RunFailure(f"{method} {path} -> {status}: {data[:300]!r}")
        return json.loads(data) if data else None


class ServerChild:
    def __init__(self, procs: Procs, work: str, env: dict, script: str):
        self.port, self.control_port = free_port(), free_port()
        self.log_path = os.path.join(work, SERVER_LOG)
        cfg = os.path.join(work, "server_config.json")
        with open(cfg, "w") as f:
            # shipped options; the in-memory stats client, so /debug/vars carries the counters
            json.dump({"metric": {"service": "expvar"}}, f)
        self._log = open(self.log_path, "ab")
        self.t_spawn = time.monotonic()
        self.proc = procs.spawn(
            [sys.executable, script, "--data-dir", os.path.join(work, "data"),
             "--bind", f"127.0.0.1:{self.port}", "--config", cfg,
             "--control-port", str(self.control_port)],
            env=env, stdout=self._log, stderr=subprocess.STDOUT)
        self._ctl = None

    def wait_ready(self, timeout: float = 600.0) -> float:
        c = Http(self.port, timeout=5.0)
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise RunFailure(f"server exited with code {rc} before serving:\n{file_tail(self.log_path)}")
            try:
                status, _ = c.request("GET", "/status")
                if status == 200:
                    c.close()
                    return time.monotonic() - self.t_spawn
            except RunFailure:
                pass
            if time.monotonic() - self.t_spawn > timeout:
                raise RunFailure(f"server not ready after {timeout:.0f}s:\n{file_tail(self.log_path)}")
            time.sleep(0.1)

    def control(self, verb: str, timeout: float = 600.0) -> dict:
        if self._ctl is None:
            self._ctl = socket.create_connection(("127.0.0.1", self.control_port), timeout=timeout)
            self._ctl_file = self._ctl.makefile("rwb")
        self._ctl_file.write(verb.encode() + b"\n")
        self._ctl_file.flush()
        line = self._ctl_file.readline()
        out = json.loads(line) if line else {"ok": False, "error": "control socket closed"}
        if not out.pop("ok", False):
            raise RunFailure(f"control {verb.split()[0]}: {out.get('error')}")
        return out

    def stop(self, timeout: float = 120.0) -> None:
        """The orderly end of a run that went well; any other is ``Procs.kill``."""
        if self._ctl is not None:
            self._ctl.close()
            self._ctl = None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._log.close()


def file_tail(path: str, n: int = 3000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode("utf-8", "replace")
    except OSError:
        return ""


class Worker:
    """A ``loadgen.py`` process."""

    def __init__(self, procs: Procs, wid: int, work: str, cfg: dict, mix: dict):
        self.wid, self.work = wid, work
        self.proc = procs.spawn([sys.executable, os.path.join(HERE, "loadgen.py")],
                                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.send({"config": cfg, "mix": mix})
        self.conns: list[int] = []

    def send(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def reply(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RunFailure(f"load generator {self.wid} died (exit {self.proc.poll()})")
        out = json.loads(line)
        if not out.get("ok"):
            raise RunFailure(f"load generator {self.wid}: {out.get('error')}")
        return out

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send({"cmd": "quit"})
                self.proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# stages
# ---------------------------------------------------------------------------


def load_cell(manifest: dict, workload: str, rehearsal: bool) -> tuple[dict, dict, dict]:
    cell = mf.cell(manifest, workload)
    cfg = mf.read_json(mf.config_entry(manifest, cell["config"])["file"])
    if rehearsal:
        cfg.update(cfg.get("rehearsal", {}))
        for f in cfg["fields"]:  # a field's keys replaced at the tiny shape, by name
            f.update(cfg.get("rehearsal_fields", {}).get(f["name"], {}))
    mix = mf.read_json(os.path.join("benchmark", "traffic", cell["traffic"] + ".json"))
    if rehearsal:
        mix.update(mix.get("rehearsal", {}))
    return cell, cfg, mix


def make_schema(c: Http, cfg: dict) -> None:
    c.json("POST", f"/index/{cfg['index']}", {})
    for f in stored_fields(cfg):
        opts = {"type": "int", "min": f["min"], "max": f["max"]} if f["kind"] == "int" else {}
        c.json("POST", f"/index/{cfg['index']}/field/{f['name']}", {"options": opts})


def ledger(dbg: dict) -> tuple[int, int]:
    """(programs compiled, programs retrieved from the persistent cache) so far."""
    return int(dbg["devledger"]["totals"]["compiles"]), int(dbg["devledger"]["persistentCacheHits"])


def new_programs(before: dict, after: dict) -> list[str]:
    """Which ledger sites compiled between two /debug/vars readings."""
    out = []
    for site, s1 in after["devledger"]["sites"].items():
        n = s1.get("compiles", 0) - before["devledger"]["sites"].get(site, {}).get("compiles", 0)
        if n:
            out.append(f"{site} compiled {n}; recent signatures {s1.get('recentCompileSigs')}")
    return out


class Driver:
    """Sends ``run`` jobs to the workers and gathers their logs."""

    def __init__(self, workers: list[Worker], work: str, base: dict, streamer: Worker | None = None,
                 every_s: float = 0.0):
        self.workers, self.work, self.base = workers, work, base
        self.streamer, self.every_s = streamer, every_s
        self.n_jobs = 0

    def start(self, phase: str, seconds: float, lead: float = 0.15, slabs: list | None = None) -> dict:
        """Send the job to every worker; ``finish`` gathers the logs.  The
        workers stop at ``seconds`` or when ``stop`` is called.  ``slabs``
        go to the worker that streams, slab ``i`` due ``i * every_s`` after
        the readers' start."""
        start_at = time.monotonic() + lead
        self.stop_file = os.path.join(self.work, f"stop_{self.n_jobs}")
        active = []
        if slabs:
            self.streamer.send(dict(self.base, cmd="stream", start_at=start_at, every_s=self.every_s,
                                    slabs=slabs, stop_file=self.stop_file))
        for w in self.workers:
            if not w.conns:
                continue
            self.n_jobs += 1
            job = dict(self.base, cmd="run", phase=phase, start_at=start_at, seconds=seconds,
                       conns=w.conns, stop_file=self.stop_file,
                       log=os.path.join(self.work, f"log_{self.n_jobs}.pkl"))
            w.send(job)
            active.append((w, job))
        return {"start_at": start_at, "end_at": start_at + seconds, "phase": phase, "active": active,
                "slabs_due": len(slabs or [])}

    def stop(self) -> None:
        open(self.stop_file, "w").close()

    def finish(self, run: dict) -> dict:
        out = dict(run, cpu_share=[], reads=[], imports=[])
        if out["slabs_due"]:  # the last acknowledgement is awaited
            out["imports"] = self.streamer.reply()["imports"]
        for w, job in out.pop("active"):
            rep = w.reply()
            if rep["stuck_threads"]:
                raise RunFailure(f"{rep['stuck_threads']} connections never returned in {run['phase']}")
            out["cpu_share"].append(round(rep["cpu_share"], 3))
            with open(job["log"], "rb") as f:
                out["reads"] += pickle.load(f)  # written by our own worker
            os.unlink(job["log"])
        return out


class Stream:
    """The write stream of a run: the mix, its plan (``generator.Mix.slabs``:
    the free slabs in the order they go), how far it has got, and the log of
    every import request sent, by whom ever."""

    def __init__(self, m, seed: int, seconds: float):
        self.mix = m
        self.every_s = float(m.streams["every_s"])
        self.sweep = [k for k in SWEEP_SHARDS if k <= int(m.cfg["shards"])]
        self.plan = m.slabs(seed)
        self.next = 0
        self.imports: list[dict] = []
        # slab i is due i * every_s after the start; a window takes those whose whole period lies in it
        self.in_window = int(seconds / self.every_s + 1e-9)
        if not self.in_window:
            raise RunFailure(f"a window of {seconds:g} s is shorter than the stream's period of {self.every_s:g} s")
        if sum(self.sweep) + self.in_window > len(self.plan):
            raise RunFailure(f"the stream runs out of free columns before the window ends: "
                             f"{len(self.plan)} free slabs, the warm-up's sweep imports {sum(self.sweep)} "
                             f"and a window of {seconds:g} s is due {self.in_window}")

    def take(self, n: int) -> list:
        self.next += n
        return self.plan[self.next - n:self.next]

    def sent(self, imports: list[dict]) -> None:
        """Log a phase's requests; slabs taken for it and never sent are free again."""
        self.imports += imports
        self.next = 1 + self.imports[-1]["k"]


def sweep_refresh(c: Http, seed: int, stream: Stream, largest: int, peak_bytes) -> None:
    """The warm-up's requests for a mix that streams: import a slab into
    each of k distinct shards, for every k of ``SWEEP_SHARDS``, and after
    each k send the requests of ``Mix.every_variant``.  The
    program re-uploads the changed shards of a field's device copy on the
    field's next read, in one program per number of changed shards; and an
    import drops every cached answer over its field, so the reads that
    follow are misses again, as many to a flight as there are connections:
    the batches go from ``largest`` calls after the first k down by halves."""
    import loadgen

    m, cfg = stream.mix, stream.mix.cfg
    path = f"/index/{cfg['index']}/query"
    for round_, k in enumerate(stream.sweep):
        for n, shard, slab in stream.take(k):
            sent = loadgen.send_slab(c, loadgen.slab_requests(cfg, seed, shard, slab), n, shard, slab,
                                     time.monotonic())
            stream.sent(sent)
            bad = [x for x in sent if x["status"] != 200]
            if bad:
                raise RunFailure(f"warm-up import {bad[0]['field']}/{shard} slab {slab} -> {bad[0]['status']}")
        for cls, calls in m.every_variant(seed, round_, max(1, largest >> round_)):
            status, body = c.request("POST", path, " ".join(calls).encode(), "text/plain")
            if status != 200:
                raise RunFailure(f"warm-up request of class {cls} after imports into {k} shards -> "
                                 f"{status}: {body[:300]!r}")
        log(f"warm-up: imported into {k} shard(s) and read every variant; device memory peak so far "
            f"{peak_bytes()}")


def warm_up(drv: Driver, c: Http, cfg: dict, mix: dict, seed: int, seconds: float,
            stream: Stream | None = None, peak_bytes=None) -> dict:
    """Two passes.  The sweep sends every variant of every class as one
    request of 1, 2, 4, ... calls (``generator.Mix.sweep``): the shapes a
    flight can take, in a fixed order; then one call twice in a request
    (``Mix.twins``: what two connections with the same question make of a
    flight); for a mix that streams, then the imports and reads of
    ``sweep_refresh``.  Then the whole mix runs at the
    window's concurrency, its stream beside it for as many slabs as a window
    is due, until nothing has compiled or been retrieved from the cache for
    a while: ``WARM_QUIET_S``, or a window's length in a process that has
    compiled (a checkout's first runs), since a shape that rare still has to
    be in the cache before a window meets it."""
    from generator import Mix  # numpy only

    t0 = time.monotonic()
    largest = 1
    while largest < int(mix["connections"]):
        largest *= 2
    path = f"/index/{cfg['index']}/query"
    m = Mix(cfg, mix)

    def send(requests) -> None:
        for cls, calls in requests:
            status, body = c.request("POST", path, " ".join(calls).encode(), "text/plain")
            if status != 200:
                raise RunFailure(f"warm-up request of class {cls} -> {status}: {body[:300]!r}")

    send(m.sweep(seed, largest))
    before = ledger(c.json("GET", "/debug/vars"))
    send(m.twins(seed, largest))
    after = ledger(c.json("GET", "/debug/vars"))
    log(f"warm-up twins (one call twice in a request): compiles {after[0] - before[0]}, "
        f"retrievals {after[1] - before[1]}")
    slabs = None
    if stream is not None:
        sweep_refresh(c, seed, stream, largest, peak_bytes)
        # what the window meets, the warm-up met; the window's own slabs are kept for it
        slabs = stream.take(min(stream.in_window, len(stream.plan) - stream.next - stream.in_window))
    dbg = c.json("GET", "/debug/vars")
    swept = ledger(dbg)
    sweep_s = time.monotonic() - t0
    log(f"warm-up sweep: {sweep_s:.1f}s, compiles {swept[0]}, retrievals {swept[1]}")
    seen = swept
    run = drv.start("warm", WARM_LIMIT_S, slabs=slabs)
    t_new = run["start_at"]
    while True:
        time.sleep(WARM_POLL_S)
        dbg, before = c.json("GET", "/debug/vars"), dbg
        now = ledger(dbg)
        for line in new_programs(before, dbg):
            log(f"warm-up +{time.monotonic() - t0:.0f}s, mixed flights: " + line)
        if now != seen:
            seen, t_new = now, time.monotonic()
        if time.monotonic() - t_new >= (max(WARM_QUIET_S, seconds) if seen[0] else WARM_QUIET_S):
            break
        if time.monotonic() - t0 > WARM_LIMIT_S:  # the run ends on it, and its workers with it
            raise RunFailure(f"warm-up still compiling after {WARM_LIMIT_S:.0f}s "
                             f"(compiles {seen[0]}, retrievals {seen[1]})")
    drv.stop()
    run = drv.finish(run)
    if stream is not None:
        stream.sent(run["imports"])
        log(f"warm-up: {stream.next} slabs imported so far, {len(slabs)} at most beside the mixed flights")
    return {"seconds": time.monotonic() - t0, "sweep_s": sweep_s, "compiles": seen[0],
            "persistent_cache_hits": seen[1], "after_sweep": [seen[0] - swept[0], seen[1] - swept[1]]}


def percentile(sorted_vals: list[float], q: float) -> float:
    return sorted_vals[max(0, math.ceil(q * len(sorted_vals)) - 1)]


def delta(after, before):
    """after - before over every number two /debug/vars trees share."""
    if isinstance(after, dict) and isinstance(before, dict):
        return {k: delta(v, before[k]) for k, v in after.items() if k in before}
    if isinstance(after, (int, float)) and isinstance(before, (int, float)) \
            and not isinstance(after, bool):
        return after - before
    return None


def window_numbers(win: dict, setup_s: float):
    """The window's requests reduced: (reads sent, reads answered inside the
    window, counts for the per-layer readers, end-to-end metrics, failed).
    Every read sent in the window counts in the percentiles, the ones
    answered after its end too; a failed read misses every limit."""
    t0, t1 = win["start_at"], win["end_at"]
    reads = win["reads"]
    failed = sum(r["status"] != 200 for r in reads)
    done = [r for r in reads if r["status"] == 200 and r["t_recv"] <= t1]
    if not done:
        raise RunFailure("no read completed inside the window")
    lat = sorted((r["t_recv"] - r["t_send"]) * 1e3 if r["status"] == 200 else 1e3 * (t1 - t0 + 600)
                 for r in reads)
    window = {"seconds": t1 - t0, "reads": len(done)}
    log("read latency ms: " + ", ".join(
        f"p{int(q * 100)} {percentile(lat, q):.1f}" for q in (0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99)))
    by_class: dict[str, list[float]] = {}
    for r in reads:
        by_class.setdefault(r["cls"], []).append((r["t_recv"] - r["t_send"]) * 1e3)
    log("per class n/p50/p95 ms: " + ", ".join(
        f"{k} {len(v)}/{percentile(sorted(v), 0.5):.0f}/{percentile(sorted(v), 0.95):.0f}"
        for k, v in sorted(by_class.items())))
    e2e = {"read_qps": len(done) / (t1 - t0), "read_p50_ms": percentile(lat, 0.50),
           "read_p95_ms": percentile(lat, 0.95), "setup_s": setup_s}
    return reads, done, window, e2e, failed


def stream_numbers(win: dict, window: dict) -> tuple[dict, int]:
    """The window's import log reduced: counts into ``window`` (``imports``,
    ``import_bits``: requests acknowledged inside it), the stream's own
    numbers, and how many of its slabs were not acknowledged inside it."""
    t1 = win["end_at"]
    acked = [m for m in win["imports"] if m["status"] == 200 and m["t_ack"] <= t1]
    if not acked:
        raise RunFailure("no import was acknowledged inside the window")
    window["imports"], window["import_bits"] = len(acked), sum(m["bits"] for m in acked)
    slabs: dict[int, list[dict]] = {}
    for m in win["imports"]:
        slabs.setdefault(m["k"], []).append(m)
    whole = sum(all(m["status"] == 200 and m["t_ack"] <= t1 for m in ms) for ms in slabs.values())
    due = win["slabs_due"]
    ack = sorted((m["t_ack"] - m["t_send"]) * 1e3 for m in win["imports"])
    late = [(min(m["t_send"] for m in ms) - ms[0]["due"]) * 1e3 for ms in slabs.values()]
    slab_s = [max(m["t_ack"] for m in ms) - min(m["t_send"] for m in ms) for ms in slabs.values()]
    out = {"slabs": whole, "ack_p50_ms": percentile(ack, 0.50), "ack_p95_ms": percentile(ack, 0.95),
           "late_mean_ms": sum(late) / len(late), "late_max_ms": max(late),
           "slab_mean_s": sum(slab_s) / len(slab_s), "slab_max_s": max(slab_s)}
    log(f"stream: {whole} of {due} slabs due acknowledged in the window ({len(acked)} requests, "
        f"{window['import_bits']} bits); " + ", ".join(f"{k} {v:.3f}" for k, v in out.items() if k != "slabs"))
    return out, due - whole


def read_back(c: Http, m, seed: int) -> list[dict]:
    """After the last acknowledgement: one request of every class of the
    mix ``m``, ``TopN`` of every set field and ``Sum`` of every int field, for
    the judge to hold against the reference with every import applied."""
    rng = np.random.default_rng([int(seed), 0xBAC])
    asks = [m.request(rng, cls) for cls in m.classes]
    asks += [f"TopN({f['name']})" if f["kind"] == "set" else f"Sum(field={f['name']})"
             for f in stored_fields(m.cfg)]
    path = f"/index/{m.cfg['index']}/query"
    out = []
    for pql in asks:
        status, body = c.request("POST", path, pql.encode(), "text/plain")
        out.append({"pql": pql, "body": body if status == 200 else b""})
    return out


def log_spans(spans: dict | None, top: int = 14) -> None:
    """The window's span table (``/debug/vars`` ``spans``, after minus before):
    the rows with the most seconds, where the host's time went by name."""
    rows = sorted(((row["seconds"], f"{block}.{name}", row) for block, names in (spans or {}).items()
                   for name, row in names.items() if row.get("count")), reverse=True)[:top]
    if rows:
        log("spans of the window, count / seconds / self seconds: " + ", ".join(
            f"{name} {row['count']} / {sec:.3f} / {row['self_seconds']:.3f}" for sec, name, row in rows))


def check_sample(reads: list[dict], seed: int, check_max: int) -> list[dict]:
    """The answers to judge: those the connections kept (a sample drawn
    from the seed), cut to ``check_max`` so that the reference stays shorter
    than the window; the seed picks which, every class still among them."""
    sample = sorted((r for r in reads if r["status"] == 200 and r["body"] is not None),
                    key=lambda r: (r["conn"], r["n"]))
    if len(sample) <= check_max:
        return sample
    first: dict[str, int] = {}
    for i, r in enumerate(sample):
        first.setdefault(r["cls"], i)
    rng = np.random.default_rng([seed, 0xC4EC])
    rest = [i for i in rng.permutation(len(sample)) if i not in first.values()]
    return [sample[i] for i in sorted(list(first.values()) + rest[:check_max - len(first)])]


# ---------------------------------------------------------------------------
# per-layer readers
# ---------------------------------------------------------------------------


def lookup(ctx: dict, path: str):
    node = ctx
    for part in path.split("."):
        node = node[part]
    if isinstance(node, bool) or not isinstance(node, (int, float)):
        raise KeyError(f"{path} is {node!r}, not a number")
    return node


def read_layer_metric(name: str, ctx: dict) -> float:
    """``layer_metrics/<name>.json`` is pure data: ``{"value": path}`` or
    ``{"num": [paths], "den": [paths], "scale": k}`` over the run's
    context (sums of paths; an empty denominator reads 0: nothing
    happened).  ``layer_metrics/<name>.py`` is code: ``read(ctx)``."""
    base = os.path.join(HERE, "layer_metrics", name)
    if os.path.exists(base + ".json"):
        with open(base + ".json") as f:
            spec = json.load(f)
        if "value" in spec:
            return lookup(ctx, spec["value"])
        num = sum(lookup(ctx, p) for p in spec["num"])
        den = sum(lookup(ctx, p) for p in spec["den"])
        return float(spec.get("scale", 1)) * num / den if den else 0.0
    if os.path.exists(base + ".py"):
        modspec = importlib.util.spec_from_file_location("layer_metric_" + name.replace(".", "_"),
                                                         base + ".py")
        mod = importlib.util.module_from_spec(modspec)
        modspec.loader.exec_module(mod)
        return mod.read(ctx)
    raise mf.ManifestError(f"per-layer metric {name} has no reader under benchmark/layer_metrics/")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None, child_script: str | None = None, manifest: dict | None = None) -> int:
    """``child_script`` and ``manifest`` are for the tests: a server child
    with the timed path broken, or one that never serves; ``BENCHMARK.json``
    with a cell beside it that the grid does not hold yet."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny shape, labelled, never a pass")
    ap.add_argument("--control", choices=("lossy", "stale"),
                    help="judge the reference with a guarantee broken in the program's place")
    ap.add_argument("--limit", type=float, default=RUN_LIMIT_S,
                    help="seconds from spawn to result line; for tests and for rehearsing a "
                         "new cell (the driver's command does not pass it)")
    args = ap.parse_args(argv)

    if not os.path.exists(os.path.join(REPO, "pilosa_tpu", "cli.py")):
        print("benchmark: the program is not here (no pilosa_tpu/cli.py); nothing to run",
              file=sys.stderr)
        return 2
    manifest = manifest or mf.load()
    procs, work, cut = Procs(), None, None
    with Watch(args.limit) as watch:
        try:
            work = tempfile.mkdtemp(prefix="pilosa_bench_")
            rc, line = one_run(args, manifest, procs, watch, work,
                               child_script or os.path.join(HERE, "serve_child.py"))
        except RunCut as e:
            cut, rc, line = e, 1, None
        finally:
            procs.kill()  # before any message: stderr may be a pipe that nobody reads any more
            if cut is not None:
                print(f"benchmark: {watch.cut_message(cut)}\n--- server log tail ---\n"
                      f"{file_tail(os.path.join(work or '', SERVER_LOG))}", file=sys.stderr)
            if work is not None:
                shutil.rmtree(work, ignore_errors=True)
    if line is not None:  # only past the watch: a run that was cut prints no result
        print(line, flush=True)
    return rc


def one_run(args, manifest: dict, procs: Procs, watch: Watch, work: str,
            child_script: str) -> tuple[int, str | None]:
    """The stages of a run; (exit code, result line).  Only a run that went
    well stops its children itself, in order; ``main`` kills what any other
    leaves."""
    cell, cfg, mix = load_cell(manifest, args.workload, args.rehearsal)
    traced = bool(args.trace)
    seconds = float(args.seconds if args.seconds is not None else manifest["run_seconds"])
    if traced:
        seconds = min(seconds, TRACE_CAP_S)
    from generator import Mix  # numpy only

    try:
        stream = Stream(Mix(cfg, mix), args.seed, seconds) if "stream" in mix else None
        if args.control == "stale" and stream is None:
            raise RunFailure(f"--control stale: the mix {cell['traffic']} streams nothing to be stale about")
    except RunFailure as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1, None

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        env["PILOSA_TPU_SHARD_WIDTH"] = str(cfg["shard_width_exp"])
    else:
        env.pop("PILOSA_TPU_SHARD_WIDTH", None)

    watch.stage("ready")
    srv = ServerChild(procs, work, env, child_script)
    c = Http(srv.port)
    try:
        try:
            ready_s = srv.wait_ready()
            device = srv.control("device")
        except RunFailure as e:
            print(f"benchmark: {e}", file=sys.stderr)
            return 2, None
        on_chip = device["platform"] == "tpu"
        if on_chip == args.rehearsal or device["count"] < int(cell["chips"]):
            print(f"benchmark: found {device['count']} x {device['platform']}; the cell asks for "
                  f"{cell['chips']} chip(s)" + (" and --rehearsal is for machines without one"
                                               if args.rehearsal else "; there is no stand-in"),
                  file=sys.stderr)
            return 2, None
        info = c.json("GET", "/info")
        if int(info["shardWidth"]) != 1 << int(cfg["shard_width_exp"]):
            raise RunFailure(f"shard width {info['shardWidth']}, not 2^{cfg['shard_width_exp']}")
        log(f"server ready in {ready_s:.1f}s on {device['count']} x {device['kind']}"
            + ("  ** REHEARSAL: CPU, tiny shape, not a result **" if args.rehearsal else ""))

        # ---- set-up: schema, load, warm-up ---------------------------------
        watch.stage("schema")
        make_schema(c, cfg)
        watch.stage("load")
        n_read = int(mix["processes"])
        n_load = min(LOADERS, int(cfg["shards"]))
        workers = [Worker(procs, i, work, cfg, mix) for i in range(max(n_read, n_load))]
        # the stream has a process to itself: its pace is not the readers' interpreter's
        streamer = Worker(procs, len(workers), work, cfg, mix) if stream else None
        for cid in range(int(mix["connections"])):
            workers[cid % n_read].conns.append(cid)
        for i, w in enumerate(workers[:n_load]):
            w.send({"cmd": "load", "seed": args.seed, "port": srv.port,
                    "shards": list(range(int(cfg["shards"])))[i::n_load]})
        loads = [w.reply() for w in workers[:n_load]]
        load_s = max(r["t_last"] for r in loads) - min(r["t_first"] for r in loads)
        load_bits = sum(r["bits"] for r in loads)
        log(f"loaded {load_bits} bits in {load_s:.1f}s ({load_bits / load_s:.0f} bits/s) "
            f"through {sum(r['requests'] for r in loads)} import requests")

        watch.stage("warm-up")
        drv = Driver(workers, work, {"seed": args.seed, "port": srv.port, "index": cfg["index"],
                                     "check_one_in": int(mix.get("check_one_in", 40))},
                     streamer, stream.every_s if stream else 0.0)
        warm = warm_up(drv, c, cfg, mix, args.seed, seconds, stream,
                       lambda: srv.control("device")["memory_peak_bytes"])
        log(f"warm-up: {warm['seconds']:.1f}s, compiles {warm['compiles']}, retrievals "
            f"{warm['persistent_cache_hits']}; after the sweep {warm['after_sweep']}")

        # ---- the window: taken once; a program that compiles in it fails the run
        watch.stage("window")
        trace_dir = os.path.join(work, "trace")
        if traced:
            srv.control(f"trace_start {trace_dir}")
            t_trace0 = time.monotonic()
        vars0 = c.json("GET", "/debug/vars")
        win = drv.finish(drv.start("window", seconds, lead=0.3,
                                   slabs=stream.take(stream.in_window) if stream else None))
        if stream is not None:
            stream.sent(win["imports"])
        vars1 = c.json("GET", "/debug/vars")
        if traced:
            window_s = time.monotonic() - t_trace0
            srv.control("trace_stop")
        compiles0, hits0 = ledger(vars0)
        compiles1, hits1 = ledger(vars1)
        for line in new_programs(vars0, vars1):  # named for whoever has to warm them up
            log("compiled inside the window: " + line)
        setup_s = win["start_at"] - srv.t_spawn
        log(f"window of {seconds:.1f}s done; generator CPU share per process {win['cpu_share']}")
        device = srv.control("device")
        readback = read_back(c, stream.mix, args.seed) if stream else []

        watch.stage("stop")
        c.close()
        for w in workers + ([streamer] if streamer else []):
            w.stop()
        srv.stop()
    except RunFailure as e:
        print(f"benchmark: {e}\n--- server log tail ---\n{file_tail(srv.log_path)}", file=sys.stderr)
        return 1, None

    try:
        # ---- reduce: the window's requests ---------------------------------
        watch.stage("reduce")
        reads, done, window, e2e, failed = window_numbers(win, setup_s)
        trace = None
        if traced:
            red = procs.spawn([sys.executable, os.path.join(HERE, "trace_reduce.py"), trace_dir],
                              env=dict(env, JAX_PLATFORMS="cpu"), stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
            try:
                red_out, red_err = red.communicate(timeout=300)
            except subprocess.TimeoutExpired:
                raise RunFailure("trace reduction took over 300 s") from None
            if red.returncode != 0:
                raise RunFailure(f"trace reduction failed: {red_err[-600:]}")
            trace = json.loads(red_out.strip().splitlines()[-1])
            trace["window_s"] = window_s
            log(f"trace: {trace['op_count']} device ops, busy {trace['busy_s']:.3f}s of "
                f"{window_s:.3f}s; lines {trace.get('lines')}")
        ctx = {"setup": {"ready_s": ready_s, "load_s": load_s, "warm_s": warm["seconds"],
                         "sweep_s": warm["sweep_s"], "compiles": compiles0,
                         "persistent_cache_hits": hits0, "load_bits_per_s": load_bits / load_s},
               "window": window, "vars": delta(vars1, vars0), "vars_start": vars0,
               "trace": trace, "e2e": e2e}
        log_spans(ctx["vars"].get("spans"))
        if stream is not None:
            ctx["stream"], short = stream_numbers(win, window)

        # ---- judge: the window's own answers against the reference ---------
        watch.stage("judge")
        from compare import CONTROLS, judge_readback, judge_reads
        from reference import Reference

        def reference(kind=Reference):
            """At the state of the load; a stream writes past the load's columns."""
            ref = kind(cfg, args.seed, extent=(1 << int(cfg["shard_width_exp"])) if stream else None)
            ref.load()
            return ref

        t_ref = time.monotonic()
        sample = check_sample(reads, args.seed, int(mix.get("check_max", 128)))
        imports = stream.imports if stream else []
        ref = reference()
        verdict = judge_reads(ref, sample, imports)
        compared = {
            "read_mismatches": [verdict["mismatches"], 0],
            "window_compiles": [compiles1 - compiles0, 0],
            "failed_requests": [failed, 0],
            # read classes that answered in the window and had no answer judged exactly
            "classes_unjudged": [len({r["cls"] for r in done} - set(verdict["per_class"])), 0],
        }
        examples = verdict["examples"]
        if stream is not None:
            back = judge_readback(ref, imports, readback)
            examples += back["examples"]
            compared.update({
                "imports_failed": [sum(m["status"] != 200 for m in imports), 0],
                "readback_mismatches": [back["mismatches"], 0],
                # slabs due in the window and not acknowledged in it
                "stream_slabs_short": [short, 0],
            })
            log(f"imports: {len(imports)} requests in the run, {verdict['certain']} certain for the last "
                f"judged read; reads by imports in flight beside them {verdict['in_flight']}, "
                f"{verdict['unjudged']} of them not judged; read-back of {back['compared']} answers")
        if args.control:
            cv = judge_reads(reference(), sample, imports, control=reference(CONTROLS[args.control]))
            compared["control_mismatches"] = [cv["mismatches"], 0]
            examples += ["control " + args.control + ": " + x for x in cv["examples"][:3]]
        if args.rehearsal:
            compared["rehearsal"] = [1, 0]
        correct = all(v <= limit for v, limit in compared.values())
        log(f"judged {verdict['compared']} window reads (per class {verdict['per_class']}) in "
            f"{time.monotonic() - t_ref:.1f}s; window retrievals {hits1 - hits0}")

        dev = {"platform": device["platform"], "kind": device["kind"], "count": device["count"],
               "memory_peak_bytes": device["memory_peak_bytes"]}
        breakdown = None
        if traced:
            dev["busy_s"], dev["window_s"] = trace["busy_s"], window_s
            breakdown = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}

        def read_metric(m: dict) -> float:
            return read_layer_metric(m["name"], ctx) if traced else e2e[m["name"]]

        line = mf.result_line(manifest, args.workload, traced, read_metric, correct=correct,
                              attempted=len(reads), failed=failed, device=dev, compared=compared,
                              breakdown=breakdown)
    except (RunFailure, mf.ManifestError, KeyError) as e:
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 1, None

    log("setup " + json.dumps({k: round(v, 3) for k, v in ctx["setup"].items()})
        + " window " + json.dumps(window) + " e2e " + json.dumps({k: round(v, 4) for k, v in e2e.items()}))
    for x in examples:
        print("mismatch: " + x, file=sys.stderr)
    if args.rehearsal:
        print("REHEARSAL: CPU, tiny shape; never a result", file=sys.stderr)
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    return (3 if args.rehearsal else 0), line


if __name__ == "__main__":
    sys.exit(main())
