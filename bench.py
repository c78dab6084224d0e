"""Benchmark: PQL Count/TopN over a ~10-billion-bit index on one TPU chip.

Mirrors BASELINE.json config 2/4: a dense bitmap index of
S shards x R rows x 2^20 columns (~10.7e9 bits at full size), querying

* ``Count(op(Row, Row))`` — the headline PQL shape — measured batched
  through the framework's MXU gram kernel (one index scan answers the
  whole query batch; pilosa_tpu/ops/kernels.py pair_gram),
  sequentially (one dispatch per query, latency mode), and
  cache-served (repeat singles answered from the cached host gram —
  the executor's warm steady state, zero device work per query), and
* ``TopN`` — a popcount scan of every row + top_k, and
* BSI ``Range`` and ingest.

Baseline: the same computation in single-core numpy
(``np.bitwise_count``) on the host, timed on a shard subset and scaled.
The reference publishes no absolute numbers (BASELINE.md) and no Go
toolchain exists in this image, so vectorized-numpy-popcount stands in
for the reference's roaring word-loop kernels (roaring.go:568
intersectionCountBitmapBitmap is the same AND+popcount word loop).

Timing discipline: dispatch is asynchronous, so every timed region ends
in ``block_until_ready`` (``_sync``).  Throughput numbers pipeline many
launches and wait once at the end (the device executes in order);
latency numbers wait per dispatch.  ``dispatch_rtt_ms`` records one
host-synchronised round trip of a trivial program.

There is no stand-in for the device: without an accelerator the script
fails unless ``JAX_PLATFORMS=cpu`` was given explicitly (a CI rehearsal
at toy sizes, ``platform: cpu`` in the record), and a lane that raises
is recorded under ``lane_failures`` and the run exits non-zero.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

import jax

from pilosa_tpu import jaxcache

jaxcache.configure()
if (
    jax.devices()[0].platform == "cpu"
    and os.environ.get("JAX_PLATFORMS") != "cpu"
):
    sys.exit(
        "bench.py: no accelerator found; set JAX_PLATFORMS=cpu explicitly "
        "for a toy-size CPU rehearsal"
    )

# lanes that raised: the record keeps them and the exit code says so
_LANE_FAILURES: list[dict] = []


def _lane_failed(lane: str, exc: Exception) -> None:
    import traceback

    _LANE_FAILURES.append({"lane": lane, "error": f"{type(exc).__name__}: {exc}"})
    print(f"error: {lane} lane failed:", file=sys.stderr)
    traceback.print_exception(exc, file=sys.stderr)


import jax.numpy as jnp
from jax import lax

from pilosa_tpu.ops import kernels


def _on_accelerator() -> bool:
    return jax.devices()[0].platform not in ("cpu",)


def _sync(x):
    """End of a timed region: wait for the device to finish ``x``."""
    return jax.block_until_ready(x)


def _devcost_mark() -> dict:
    """Flat device-ledger counters at a lane boundary (obs/devledger.py)."""
    from pilosa_tpu.obs import devledger

    return dict(devledger.counters())


def _devcost_delta(mark: dict, lane: str, forbid_compiles: bool = False) -> dict:
    """Ledger delta since ``mark`` for a lane's BENCH JSON block.

    With ``forbid_compiles`` the lane asserts its warm steady state: ANY
    post-warmup XLA compile fails the lane loudly, naming the sites that
    compiled — a silent recompile-per-request bug would otherwise flatter
    itself as throughput spread."""
    from pilosa_tpu.obs import devledger

    cur = devledger.counters()
    compiles = cur["compiles"] - mark.get("compiles", 0)
    out = {
        "compiles": compiles,
        "launches": cur["launches"] - mark.get("launches", 0),
        "transfer_bytes": (
            cur["h2dBytes"] + cur["d2hBytes"]
            - mark.get("h2dBytes", 0) - mark.get("d2hBytes", 0)
        ),
    }
    if forbid_compiles and compiles > 0:
        suffix = ".compiles"
        sites = sorted(
            (k[len("site."):-len(suffix)], cur[k] - mark.get(k, 0))
            for k in cur
            if k.startswith("site.") and k.endswith(suffix)
            and cur[k] - mark.get(k, 0) > 0
        )
        raise RuntimeError(
            f"{lane} lane: {compiles} XLA compile(s) after warmup "
            f"(per site: {sites or 'unattributed'})"
        )
    return out


def _bsi_range_fn(depth, value):
    """Jitted all-shards BSI `field < value` count using the framework's
    plane-scan kernel (pilosa_tpu/ops/bsi.py) vmapped over shards."""
    from pilosa_tpu.ops import bsi

    bounds, oob = bsi._bound_args(abs(value), depth)

    @jax.jit
    def run(planes, exists, sign, salt):
        mask = jax.vmap(
            lambda p, e, s: bsi._range_lt_kernel(
                p ^ salt, e, s, bounds, oob, negative=False, depth=depth,
                allow_eq=True,
            )
        )(planes, exists, sign)
        return jnp.sum(lax.population_count(mask).astype(jnp.int32))

    return run


# Load-generator subprocess for the served-concurrency sweep: argv is
# host, port, n_threads, per_client.  One keep-alive HTTPConnection per
# thread; prints one JSON report (latencies, errors, wall clock).
_SWEEP_CLIENT_SRC = """
import http.client, json, sys, threading, time
host, port = sys.argv[1], int(sys.argv[2])
clients, per_client = int(sys.argv[3]), int(sys.argv[4])
q = b"Count(Intersect(Row(f=0), Row(f=1)))"
lats = [[] for _ in range(clients)]
errors = []
def worker(ci):
    conn = None
    try:
        conn = http.client.HTTPConnection(host, port, timeout=60)
        conn.connect()
    except Exception as e:
        errors.append(repr(e))
        return
    try:
        for _ in range(per_client):
            t0 = time.perf_counter()
            conn.request("POST", "/index/swp/query", body=q)
            resp = conn.getresponse()
            data = resp.read()
            lats[ci].append(time.perf_counter() - t0)
            if resp.status != 200:
                errors.append(data[:120].decode("utf-8", "replace"))
        conn.close()
    except Exception as e:
        errors.append(repr(e))
ts = [threading.Thread(target=worker, args=(ci,), daemon=True)
      for ci in range(clients)]
t0 = time.perf_counter()
for t in ts:
    t.start()
for t in ts:
    t.join()
wall = time.perf_counter() - t0
print(json.dumps({
    "lats": [x for lat in lats for x in lat],
    "errors": errors[:3],
    "n_errors": len(errors),
    "wall": wall,
}))
"""


def _served_concurrency_sweep() -> dict:
    """Serving-plane lane: a concurrency sweep through the REAL HTTP
    path (the engine answers a batch in one launch while one-at-a-time
    HTTP requests each pay a dispatch; the admission batcher exists to
    close that gap for *concurrent* callers).

    Boots one NodeServer (admission batcher on), warms the pair-count
    serving cache, then drives it with 1/32/256/1000 keep-alive clients
    — one ``http.client.HTTPConnection`` per client thread, so the
    sweep measures request coalescing, not TCP handshakes.  Per level:
    achieved qps, p50/p99 latency.  The level-1 row is the
    single-client floor the window must not regress (the batcher closes
    "empty" with zero dead time when nobody else is queued); the 1000-
    client row is the throughput headline.  Also returns the
    batch-size histogram and window-close counters accumulated across
    the sweep, so the JSON shows HOW the throughput was achieved."""
    from pilosa_tpu.server.node import NodeServer

    # rescache off: the sweep repeats ONE query, so with the semantic
    # cache live every request past the first would demux as a cache
    # hit and the lane would stop measuring the admission batcher
    srv = NodeServer(
        port=0, batch_window=0.002, batch_max_size=128, rescache_entries=0
    )
    srv.start()
    try:
        api = srv.api
        api.create_index("swp")
        api.create_field("swp", "f")
        rng = np.random.default_rng(7)
        width = api.holder.n_words * 32
        writes = [
            f"Set({int(c)}, f={row})"
            for row in range(8)
            for c in rng.integers(0, width, size=200)
        ]
        api.query("swp", " ".join(writes))
        q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        want = api.query("swp", q.decode())["results"]
        # warm the serving cache: the sweep measures the serving plane's
        # steady state, not the one-time gram build
        for _ in range(40):
            api.query("swp", q.decode())
        # warm steady state is ASSERTED below: zero XLA compiles across
        # the whole sweep after this mark
        devmark = _devcost_mark()
        host, port = srv.host, srv.server.port

        def run_level(clients: int, per_client: int) -> dict:
            # Load is generated from SUBPROCESSES (up to 4, splitting the
            # client threads) so the load generator does not share the
            # server's GIL — 1000 in-process client threads measure the
            # generator, not the serving plane.  Each subprocess reports
            # its own thread-start→join wall; qps uses the slowest one
            # (they launch together; python startup is outside the wall).
            n_procs = min(4, clients)
            split = [clients // n_procs] * n_procs
            for i in range(clients % n_procs):
                split[i] += 1
            procs = [
                subprocess.Popen(
                    [
                        sys.executable, "-c", _SWEEP_CLIENT_SRC,
                        host, str(port), str(nc), str(per_client),
                    ],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.DEVNULL,
                )
                for nc in split
            ]
            reports = []
            for p in procs:
                out, _ = p.communicate(timeout=300)
                reports.append(json.loads(out))
            flat = sorted(x for r in reports for x in r["lats"])
            errors = [e for r in reports for e in r["errors"]]
            n_errors = sum(r["n_errors"] for r in reports)
            wall = max(r["wall"] for r in reports)
            n = len(flat)
            return {
                "clients": clients,
                "requests": n,
                "errors": n_errors,
                "error_sample": errors[:3],
                "qps": round(n / wall, 1) if wall > 0 else None,
                "p50_ms": round(flat[n // 2] * 1e3, 2) if n else None,
                "p99_ms": (
                    round(flat[min(n - 1, (99 * n) // 100)] * 1e3, 2)
                    if n
                    else None
                ),
            }

        snap0 = api.batcher.snapshot()
        levels = []
        for clients in (1, 32, 256, 1000):
            # >=2000 requests per level so p99 means something; at high
            # concurrency keep >=8 per client so the steady state
            # outweighs the 1000-connection setup herd
            levels.append(run_level(clients, max(8, 2000 // clients)))
        snap1 = api.batcher.snapshot()
        stats_snap = api.holder.stats.snapshot()
        hist = next(
            (
                v
                for k, v in stats_snap.get("histograms", {}).items()
                if "batcher_batch_size" in k
            ),
            None,
        )
        closes = {
            k: v
            for k, v in stats_snap.get("counters", {}).items()
            if "batcher_window_close" in k
        }
        # correctness spot check after the storm: same answer as before
        got = api.query("swp", q.decode())["results"]
        if got != want:
            raise RuntimeError(f"served sweep corrupted results: {got} != {want}")
        return {
            "levels": levels,
            "window_s": api.batcher.window,
            "max_batch": api.batcher.max_batch,
            "batches": snap1["batches"] - snap0["batches"],
            "coalesced": snap1["coalesced"] - snap0["coalesced"],
            "window_closes": closes,
            "batch_size_hist": hist,
            "devledger": _devcost_delta(
                devmark, "served_sweep", forbid_compiles=True
            ),
        }
    finally:
        srv.stop()


def _recorder_overhead_lane() -> dict:
    """Flight-recorder overhead lane (BENCH_r06 follow-up): the same
    single-client served query loop against two freshly booted nodes —
    one with the always-on incident plane live (flight recorder sampling
    stacks + tail-sampled trace store observing every request, the
    serving default) and one with both off — so the JSON pins what the
    observability plane costs the hot path.  Target: <= 5% qps."""
    import http.client

    from pilosa_tpu.server.node import NodeServer

    def boot(recorder: bool):
        # rescache off: a cache hit skips the execution the recorder
        # observes, so the overhead under test would vanish from the
        # measured path
        srv = NodeServer(port=0, flight_recorder=recorder, rescache_entries=0)
        srv.start()
        api = srv.api
        if not recorder:
            # tail sampling off too: a None store makes the span
            # sink and the per-request store binding no-ops
            api.holder.traces = None
        api.create_index("rec")
        api.create_field("rec", "f")
        rng = np.random.default_rng(13)
        width = api.holder.n_words * 32
        writes = [
            f"Set({int(c)}, f={row})"
            for row in range(4)
            for c in rng.integers(0, width, size=150)
        ]
        api.query("rec", " ".join(writes))
        conn = http.client.HTTPConnection(
            srv.host, srv.server.port, timeout=60
        )
        body = b"Count(Intersect(Row(f=0), Row(f=1)))"

        def once() -> None:
            conn.request("POST", "/index/rec/query", body=body)
            resp = conn.getresponse()
            data = resp.read()
            if resp.status != 200:
                raise RuntimeError(
                    f"recorder lane HTTP {resp.status}: {data[:120]!r}"
                )

        return srv, conn, once

    # Single-client qps drifts +-10% run to run on a shared host, so the
    # two configs are measured in INTERLEAVED blocks and compared on
    # their best block — drift hits both sides, the best block of each
    # is the closest thing to the machine's uncontended service rate.
    srv_on, conn_on, once_on = boot(True)
    srv_off, conn_off, once_off = boot(False)
    try:
        for once in (once_on, once_off):
            for _ in range(50):
                once()
        reps, best_on, best_off = 200, 0.0, 0.0
        for _ in range(5):
            for once, which in ((once_off, "off"), (once_on, "on")):
                t0 = time.perf_counter()
                for _ in range(reps):
                    once()
                qps = reps / (time.perf_counter() - t0)
                if which == "on":
                    best_on = max(best_on, qps)
                else:
                    best_off = max(best_off, qps)
        conn_on.close()
        conn_off.close()
    finally:
        srv_on.stop()
        srv_off.stop()
    return {
        "qps_recorder_on": round(best_on, 1),
        "qps_recorder_off": round(best_off, 1),
        "overhead_frac": (
            round(1.0 - best_on / best_off, 4) if best_off else None
        ),
    }


def _history_overhead_lane() -> dict:
    """Metrics-history overhead lane (recorder-lane shape): the same
    served query loop against two freshly booted nodes — one with the
    ring-TSDB sampler + trend detectors live (obs/history.py, the
    serving default; cadence pinned at 2x production so the lane
    exercises the sampler rather than the gap between ticks) and one
    with the history plane off — interleaved blocks, best-block compare.
    Target: <= 5% qps."""
    import http.client

    from pilosa_tpu.server.node import NodeServer

    def boot(history: bool):
        # rescache off for the same reason as the recorder lane: a
        # cache hit skips the execution whose planes the sampler reads
        srv = NodeServer(
            port=0,
            history_enabled=history,
            history_cadence=0.5,
            rescache_entries=0,
        )
        srv.start()
        api = srv.api
        api.create_index("hist")
        api.create_field("hist", "f")
        rng = np.random.default_rng(17)
        width = api.holder.n_words * 32
        writes = [
            f"Set({int(c)}, f={row})"
            for row in range(4)
            for c in rng.integers(0, width, size=150)
        ]
        api.query("hist", " ".join(writes))
        conn = http.client.HTTPConnection(
            srv.host, srv.server.port, timeout=60
        )
        body = b"Count(Intersect(Row(f=0), Row(f=1)))"

        def once() -> None:
            conn.request("POST", "/index/hist/query", body=body)
            resp = conn.getresponse()
            data = resp.read()
            if resp.status != 200:
                raise RuntimeError(
                    f"history lane HTTP {resp.status}: {data[:120]!r}"
                )

        return srv, conn, once

    srv_on, conn_on, once_on = boot(True)
    srv_off, conn_off, once_off = boot(False)
    try:
        for once in (once_on, once_off):
            for _ in range(50):
                once()
        reps, best_on, best_off = 200, 0.0, 0.0
        for _ in range(5):
            for once, which in ((once_off, "off"), (once_on, "on")):
                t0 = time.perf_counter()
                for _ in range(reps):
                    once()
                qps = reps / (time.perf_counter() - t0)
                if which == "on":
                    best_on = max(best_on, qps)
                else:
                    best_off = max(best_off, qps)
        sampler = (
            srv_on.history.stats() if srv_on.history is not None else None
        )
        conn_on.close()
        conn_off.close()
    finally:
        srv_on.stop()
        srv_off.stop()
    return {
        "qps_history_on": round(best_on, 1),
        "qps_history_off": round(best_off, 1),
        "overhead_frac": (
            round(1.0 - best_on / best_off, 4) if best_off else None
        ),
        "sampler": sampler,
    }


def _blackbox_overhead_lane() -> dict:
    """Black-box overhead lane (recorder-lane shape): the same served
    query loop against two freshly booted DISK-BACKED nodes — one with
    the crash-durable spool writer live (obs/blackbox.py) checkpointing
    every 0.2s (25x the production 5s cadence, so the lane exercises
    the writer rather than the gap between ticks) and one with the
    black box off — interleaved blocks, best-block compare.  The
    writer's self-accounting (checkpoints taken, seconds spent) rides
    along so a regression is attributable.  Target: <= 5% qps."""
    import http.client
    import tempfile

    from pilosa_tpu.server.node import NodeServer

    def boot(blackbox: bool, data_dir: str):
        # rescache off for the same reason as the recorder lane: a
        # cache hit skips the execution whose planes the writer spools
        srv = NodeServer(
            port=0,
            data_dir=data_dir,
            blackbox_enabled=blackbox,
            blackbox_interval=0.2,
            rescache_entries=0,
        )
        srv.start()
        api = srv.api
        api.create_index("bb")
        api.create_field("bb", "f")
        rng = np.random.default_rng(23)
        width = api.holder.n_words * 32
        writes = [
            f"Set({int(c)}, f={row})"
            for row in range(4)
            for c in rng.integers(0, width, size=150)
        ]
        api.query("bb", " ".join(writes))
        conn = http.client.HTTPConnection(
            srv.host, srv.server.port, timeout=60
        )
        body = b"Count(Intersect(Row(f=0), Row(f=1)))"

        def once() -> None:
            conn.request("POST", "/index/bb/query", body=body)
            resp = conn.getresponse()
            data = resp.read()
            if resp.status != 200:
                raise RuntimeError(
                    f"blackbox lane HTTP {resp.status}: {data[:120]!r}"
                )

        return srv, conn, once

    with tempfile.TemporaryDirectory() as tmp_on, \
            tempfile.TemporaryDirectory() as tmp_off:
        srv_on, conn_on, once_on = boot(True, tmp_on)
        srv_off, conn_off, once_off = boot(False, tmp_off)
        try:
            for once in (once_on, once_off):
                for _ in range(50):
                    once()
            reps, best_on, best_off = 200, 0.0, 0.0
            for _ in range(5):
                for once, which in ((once_off, "off"), (once_on, "on")):
                    t0 = time.perf_counter()
                    for _ in range(reps):
                        once()
                    qps = reps / (time.perf_counter() - t0)
                    if which == "on":
                        best_on = max(best_on, qps)
                    else:
                        best_off = max(best_off, qps)
            writer = (
                srv_on.blackbox.stats()
                if srv_on.blackbox is not None else None
            )
            conn_on.close()
            conn_off.close()
        finally:
            srv_on.stop()
            srv_off.stop()
    return {
        "qps_blackbox_on": round(best_on, 1),
        "qps_blackbox_off": round(best_off, 1),
        "overhead_frac": (
            round(1.0 - best_on / best_off, 4) if best_off else None
        ),
        "writer": writer,
    }


def _mesh_dist_lane() -> dict:
    """Cluster-on-mesh lane: distributed Count/TopN/Range on an in-mesh
    8-way InProcessCluster — every owner's fragments are slices of the
    local serving mesh, so the whole fan-out is ONE jit-sharded launch
    (cluster/dist.py + cluster/meshexec.py) — against the same data on a
    single holder.  Zero HTTP subrequests is ASSERTED, not assumed: the
    lane counts ``client.query_node`` calls across all eight nodes and
    fails if any leg left the process.  Both sides ride the same
    admission-batcher API path and are measured in interleaved
    best-of-3 blocks (drift hits both sides; see the recorder lane)."""
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.testing import InProcessCluster

    def seed(target):
        target.create_index("md")
        target.create_field("md", "f")
        target.create_field(
            "md", "v", {"type": "int", "min": 0, "max": 1_000_000}
        )
        rng = np.random.default_rng(29)
        bits = [
            (r, s * SHARD_WIDTH + int(c))
            # distinct per-row sizes keep TopN free of count ties, so
            # the two sides' orderings are comparable bit for bit
            for r in range(4)
            for s in range(16)
            for c in rng.integers(0, SHARD_WIDTH, size=40 + 10 * r)
        ]
        target.import_bits("md", "f", bits)
        cols = sorted(
            {
                s * SHARD_WIDTH + int(c)
                for s in range(16)
                for c in rng.integers(0, SHARD_WIDTH, size=60)
            }
        )
        target.import_values("md", "v", cols, [c % 999_983 for c in cols])

    queries = {
        "count": "Count(Row(f=1))",
        "topn": "TopN(f, n=5)",
        "range": "Count(Row(v > 500000))",
    }
    http_calls = []
    # rescache off on both sides: the lane repeats three fixed queries,
    # and a cache hit would bypass the mesh dispatch under test
    with InProcessCluster(
        8, replica_n=1, rescache_entries=0
    ) as mesh_c, InProcessCluster(1, rescache_entries=0) as solo_c:
        seed(mesh_c)
        seed(solo_c)
        qi = next(
            i
            for i, n in enumerate(mesh_c.nodes)
            if n.node_id == mesh_c.coordinator_id
        )
        api_m = mesh_c.nodes[qi].api
        api_s = solo_c.nodes[0].api
        for n in mesh_c.nodes:
            orig = n.client.query_node

            def wrap(*a, _o=orig, **k):
                http_calls.append(a)
                return _o(*a, **k)

            n.client.query_node = wrap
        # warmup doubles as the parity gate: both sides must agree
        # before either is timed
        for q in queries.values():
            want = api_s.query("md", q)["results"]
            got = api_m.query("md", q)["results"]
            if got != want:
                raise RuntimeError(
                    f"mesh lane parity broke for {q}: {got} != {want}"
                )
        # push both sides past the executor's single-query warm gates so
        # every timed rep rides its steady-state lane, then assert zero
        # XLA compiles across the timed blocks
        for q in queries.values():
            for _ in range(8):
                api_s.query("md", q)
                api_m.query("md", q)
        devmark = _devcost_mark()
        reps = {"count": 60, "topn": 30, "range": 30}
        best = {k: {"mesh": 0.0, "solo": 0.0} for k in queries}
        for _ in range(3):
            for key, q in queries.items():
                for side, api in (("solo", api_s), ("mesh", api_m)):
                    n_reps = reps[key]
                    t0 = time.perf_counter()
                    for _ in range(n_reps):
                        api.query("md", q)
                    qps = n_reps / (time.perf_counter() - t0)
                    best[key][side] = max(best[key][side], qps)
        snap = api_m.dist.snapshot()
        devcosts = _devcost_delta(devmark, "mesh_dist", forbid_compiles=True)
    if http_calls:
        raise RuntimeError(
            f"mesh lane issued {len(http_calls)} HTTP subrequests"
        )
    return {
        "mesh_dist_count_qps": round(best["count"]["mesh"], 1),
        "mesh_dist_topn_qps": round(best["topn"]["mesh"], 1),
        "mesh_dist_range_qps": round(best["range"]["mesh"], 1),
        "single_holder_count_qps": round(best["count"]["solo"], 1),
        "single_holder_topn_qps": round(best["topn"]["solo"], 1),
        "single_holder_range_qps": round(best["range"]["solo"], 1),
        # the acceptance ratio: mesh-dispatched distributed Count vs the
        # single-holder batched path over identical data (>= 0.5 keeps
        # it within the 2x bar)
        "mesh_dist_vs_single_holder": (
            round(best["count"]["mesh"] / best["count"]["solo"], 3)
            if best["count"]["solo"]
            else None
        ),
        "http_subrequests": len(http_calls),
        "nodes": 8,
        "mesh_dispatches": snap["meshDispatches"],
        "mesh_fallbacks": snap["meshFallbacks"],
        "devledger": devcosts,
    }


def _residency_lane() -> dict:
    """Tiered-residency lane: the SAME zipfian stack workload through the
    in-process batched API twice — fully resident (uncapped budget; the
    prefetcher no-ops by design) vs an HBM budget sized to hold ~1/6 of
    the field stacks (6x oversubscribed), where the flight-driven
    prefetcher (server/prefetch.py) must keep the zipfian head resident
    and stage the warm tail ahead of its flights.  Acceptance bars
    (docs/residency.md): oversubscribed qps >= 25%% of fully resident,
    and prefetch_useful/prefetch_issued >= 0.5.

    Queries are ``Count(Intersect(Row, Row))`` trees — the shape the
    batched dispatch compiles over field stacks (exec/astbatch.py; bare
    ``Count(Row)`` rides the host segment path and never touches HBM
    residency).  Concurrency comes from in-process threads: the lane
    measures the residency tier, not the HTTP listener (that is the
    served sweep's job)."""
    import random as _random
    import threading as _threading

    from pilosa_tpu.core import membudget, residency
    from pilosa_tpu.server.api import API

    # 36 fields at 1/6 cap = 6 resident stacks: oversubscription is an
    # INDEX-level property, while a single flight's working set (~8
    # concurrent callers, zipfian) must still be coverable or the flight
    # self-thrashes before any policy can help
    n_fields = 36
    n_threads = 8
    per_thread = 40
    rounds = 3
    weights = [1.0 / (fi + 1) ** 1.3 for fi in range(n_fields)]

    def run_phase(cap_of_total):
        # rescache off: the zipfian repeats would otherwise be served
        # from the result cache without ever touching HBM residency
        api = API(batch_window=0.004, batch_max_size=64, rescache_entries=0)
        try:
            api.create_index("ri")
            rng = np.random.default_rng(31)
            width = api.holder.n_words * 32
            for fi in range(n_fields):
                api.create_field("ri", f"f{fi}")
                writes = [
                    f"Set({int(c)}, f{fi}={row})"
                    for row in (3, 4)
                    for c in rng.integers(0, width, size=64)
                ]
                api.query("ri", " ".join(writes))
            stack_bytes = 2 * api.holder.n_words * 4  # S=1, R=2 rows
            total = n_fields * stack_bytes
            cap = None if cap_of_total is None else max(
                stack_bytes, int(total * cap_of_total)
            )
            membudget.configure(cap)
            residency.configure()

            def worker(seed, out):
                r = _random.Random(seed)
                t0 = time.perf_counter()
                for _ in range(per_thread):
                    fi = r.choices(range(n_fields), weights=weights)[0]
                    api.query(
                        "ri",
                        f"Count(Intersect(Row(f{fi}=3), Row(f{fi}=4)))",
                    )
                out.append(time.perf_counter() - t0)

            best_qps = 0.0
            for rnd in range(rounds):
                walls: list = []
                ts = [
                    _threading.Thread(target=worker, args=(rnd * 97 + i, walls))
                    for i in range(n_threads)
                ]
                t0 = time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                wall = time.perf_counter() - t0
                best_qps = max(best_qps, n_threads * per_thread / wall)
            time.sleep(0.2)  # let trailing prefetch uploads settle
            return {
                "qps": best_qps,
                "cap_bytes": cap,
                "total_stack_bytes": total,
                "residency": residency.default_tracker().snapshot(),
                "budget": membudget.default_budget().snapshot(),
            }
        finally:
            api.close()

    prev_cap = membudget.default_budget().cap
    try:
        resident = run_phase(None)
        oversub = run_phase(1 / 6)
    finally:
        membudget.configure(prev_cap)
        residency.configure()
    res = oversub["residency"]
    ratio = (
        round(oversub["qps"] / resident["qps"], 3) if resident["qps"] else None
    )
    useful_frac = res["prefetchUsefulFrac"]
    return {
        "resident_qps": round(resident["qps"], 1),
        "oversubscribed_qps": round(oversub["qps"], 1),
        "oversubscribed_vs_resident": ratio,
        "oversubscription_factor": round(
            oversub["total_stack_bytes"] / oversub["cap_bytes"], 1
        ),
        "prefetch_issued": res["prefetchIssued"],
        "prefetch_useful": res["prefetchUseful"],
        "prefetch_useful_frac": useful_frac,
        "device_hit_rate": res["hitRate"],
        "evictions": oversub["budget"]["evictions"],
        "auto_pins": oversub["budget"]["pins"],
        # fully-resident phase must show ZERO prefetch traffic (the
        # uncapped fast path is what keeps unbudgeted lanes regression-
        # free)
        "resident_prefetch_issued": resident["residency"]["prefetchIssued"],
        "pass_qps_ratio": ratio is not None and ratio >= 0.25,
        "pass_useful_frac": useful_frac >= 0.5,
    }


def _rescache_lane(serving_floor_ms: float) -> dict:
    """Semantic result cache lane (docs/caching.md): the SAME zipfian
    repeat-heavy read schedule with interleaved writes through the
    in-process batched API twice — cache on (the serving default) vs
    ``rescache_entries=0`` — over identical data.  The write traffic is
    mostly to a field no read template touches, which is the point:
    version-precise invalidation keeps the pool's entries live under
    unrelated writes, while the periodic writes that DO hit a read
    field force invalidate-then-refill (and maintained-view refresh for
    the promoted TopN).  Acceptance bars: cache-served read p50 below
    the uncached serving-cache floor, and cached/uncached qps >= 5x."""
    import random as _random

    from pilosa_tpu.server.api import API

    n_ops = 480
    pool_theta = 1.2

    def seed(api):
        api.create_index("rc")
        api.create_field("rc", "f")
        api.create_field("rc", "g")
        api.create_field("rc", "v", {"type": "int", "min": 0, "max": 1_000_000})
        api.create_field("rc", "w")
        rng = np.random.default_rng(17)
        width = api.holder.n_words * 32
        writes = []
        for row in range(8):
            for c in rng.integers(0, width, size=100):
                writes.append(f"Set({int(c)}, f={row})")
        for row in range(4):
            for c in rng.integers(0, width, size=60):
                writes.append(f"Set({int(c)}, g={row})")
        for c in sorted({int(c) for c in rng.integers(0, width, size=200)}):
            writes.append(f"Set({c}, v={c % 999_983})")
        api.query("rc", " ".join(writes))

    # zipfian head first: the hot templates are the expensive shapes,
    # the dashboard-refresh pattern the cache exists for
    pool = [
        "GroupBy(Rows(f), Rows(g))",
        "TopN(f, n=5)",
        "Count(Intersect(Row(f=0), Row(f=1)))",
        "Count(Row(v < 500000))",
        "Sum(field=v)",
        "Count(Union(Row(f=2), Row(g=1)))",
        "TopN(g, n=3)",
        "Count(Difference(Row(f=0), Row(g=0)))",
        "Count(Row(v > 250000))",
        "Min(field=v)",
        "Max(field=v)",
        "Count(Row(f=3))",
    ]
    weights = [1.0 / (i + 1) ** pool_theta for i in range(len(pool))]

    # both blocks' schedules are pre-drawn from ONE seeded stream so the
    # two sides replay byte-identical traffic and the timed loops hold
    # nothing but api.query
    r = _random.Random(23)
    n_hit = 400
    hit_reads = [r.choices(pool, weights=weights)[0] for _ in range(n_hit)]
    mixed_reads = [r.choices(pool, weights=weights)[0] for _ in range(n_ops)]

    def run_side(entries: int) -> dict:
        api = API(
            batch_window=0.004, batch_max_size=64, rescache_entries=entries
        )
        try:
            seed(api)
            # warm both sides identically: fills the cache on the
            # cached side, and on the uncached side pushes every pool
            # template past the executor's single-query warm gates so
            # the hit block rides the device steady state
            for q in pool:
                for _ in range(8):
                    api.query("rc", q)
            devmark = _devcost_mark()
            # hit block: pure zipfian repeats over the warm pool — on
            # the cached side every read is cache-served, so this pair
            # of walls IS the hit-qps vs uncached-qps ratio
            lats: list[float] = []
            t0 = time.perf_counter()
            for q in hit_reads:
                tq = time.perf_counter()
                api.query("rc", q)
                lats.append(time.perf_counter() - tq)
            hit_wall = time.perf_counter() - t0
            # the headline block must be recompile-free on BOTH sides:
            # cache-served reads launch nothing, uncached reads replay
            # programs compiled during warmup
            hit_devcosts = _devcost_delta(
                devmark, f"rescache(entries={entries})", forbid_compiles=True
            )
            # mixed block: the same reads with interleaved writes — the
            # invalidation-under-traffic realism the hit block omits
            snap0 = api.executor.rescache.snapshot()
            wcol = 0
            t0 = time.perf_counter()
            for i, q in enumerate(mixed_reads):
                if i % 8 == 7:
                    wcol += 1
                    if (i // 8) % 5 == 4:
                        # every 5th write lands on a read field:
                        # invalidate (or maintained-refresh) + refill
                        api.query("rc", f"Set({wcol}, f=6)")
                    else:
                        api.query("rc", f"Set({wcol}, w=1)")
                else:
                    api.query("rc", q)
            mixed_wall = time.perf_counter() - t0
            snap1 = api.executor.rescache.snapshot()
            lats.sort()
            return {
                "hit_qps": n_hit / hit_wall,
                "hit_p50_ms": lats[len(lats) // 2] * 1e3,
                "mixed_qps": n_ops / mixed_wall,
                "devledger": hit_devcosts,
                "delta": {
                    k: snap1[k] - snap0[k]
                    for k in (
                        "hits", "misses", "invalidations", "promotions",
                        "maintainedHits",
                    )
                },
            }
        finally:
            api.close()

    cached = run_side(512)
    uncached = run_side(0)
    d = cached["delta"]
    reads = d["hits"] + d["misses"]
    ratio = (
        round(cached["hit_qps"] / uncached["hit_qps"], 2)
        if uncached["hit_qps"]
        else None
    )
    return {
        "rescache_hit_qps": round(cached["hit_qps"], 1),
        "uncached_qps": round(uncached["hit_qps"], 1),
        "rescache_hit_vs_uncached": ratio,
        "hit_p50_ms": round(cached["hit_p50_ms"], 4),
        "uncached_p50_ms": round(uncached["hit_p50_ms"], 4),
        "serving_floor_ms": round(serving_floor_ms, 4),
        # mixed-block context: blended throughput and the cache's own
        # accounting while writes invalidate / refresh underneath
        "mixed_qps_cached": round(cached["mixed_qps"], 1),
        "mixed_qps_uncached": round(uncached["mixed_qps"], 1),
        # hit-block ledger deltas: the cached side serves from the
        # result cache (zero device launches is the design), the
        # uncached side replays warm programs (launches, no compiles)
        "devledger_cached": cached["devledger"],
        "devledger_uncached": uncached["devledger"],
        "hit_rate": round(d["hits"] / reads, 3) if reads else None,
        **{f"cache_{k}": v for k, v in d.items()},
        "pass_hit_p50": cached["hit_p50_ms"] < serving_floor_ms,
        "pass_hit_ratio": ratio is not None and ratio >= 5.0,
    }


def _planner_lane() -> dict:
    """Flight-level query planner lane (docs/serving.md "Flight
    planning"): the SAME zipfian repeat-heavy flight schedule through
    the in-process batched API twice — planner on (the serving default)
    vs ``planner_enabled=False`` — over identical data.  Every flight
    is one multi-call query whose calls land in a single
    ``execute_batch`` shard group, with >=50% of the calls embedding
    one shared canonical subtree (drawn zipfian from a template pool,
    one occurrence commutatively flipped to exercise canonicalization).
    The shared subtrees carry BSI range conditions, which keeps them
    off the compiled tree-count path — so the unplanned side pays the
    host evaluation once PER CALL while the planned side pays it once
    PER FLIGHT.  The result cache is pinned OFF on BOTH sides (and
    asserted empty) so the speedup is attributable to cross-query CSE
    alone, not caching.  Acceptance bars: planner-on/planner-off qps
    >= 1.5x and zero post-warmup XLA compiles on either side."""
    import random as _random

    from pilosa_tpu.server.api import API

    n_flights = 96
    pool_theta = 1.2

    def seed(api):
        api.create_index("pl")
        api.create_field("pl", "f")
        api.create_field("pl", "g")
        api.create_field("pl", "v", {"type": "int", "min": 0, "max": 1_000_000})
        rng = np.random.default_rng(29)
        width = api.holder.n_words * 32
        writes = []
        for row in range(8):
            for c in rng.integers(0, width, size=100):
                writes.append(f"Set({int(c)}, f={row})")
        for row in range(4):
            for c in rng.integers(0, width, size=60):
                writes.append(f"Set({int(c)}, g={row})")
        for c in sorted({int(c) for c in rng.integers(0, width, size=240)}):
            writes.append(f"Set({c}, v={c % 999_983})")
        api.query("pl", " ".join(writes))

    # template pool: each entry is a (BSI lo, BSI hi, set row) triple
    # defining one shared subtree; flights draw zipfian so the head
    # templates dominate, the dashboard-burst pattern the planner
    # exists for
    templates = [
        (100_000, 800_000, 0),
        (250_000, 750_000, 1),
        (50_000, 500_000, 2),
        (400_000, 900_000, 3),
        (10_000, 300_000, 4),
        (600_000, 990_000, 5),
    ]
    weights = [1.0 / (i + 1) ** pool_theta for i in range(len(templates))]

    def flight(rng) -> str:
        lo, hi, row = rng.choices(templates, weights=weights)[0]
        shared = f"Intersect(Row(v > {lo}), Row(v < {hi}), Row(f={row}))"
        # same canonical form, different child order
        flipped = f"Intersect(Row(f={row}), Row(v > {lo}), Row(v < {hi}))"
        r2, r3 = rng.randrange(4), rng.randrange(8)
        # 4 of 6 calls consume the shared subtree (>= 50% per flight)
        return " ".join(
            [
                f"Count({shared})",
                f"Count(Union({flipped}, Row(g={r2})))",
                f"Count(Difference({shared}, Row(f={r3})))",
                f"Count(Intersect({shared}, Row(g={r2})))",
                f"Count(Row(f={r3}))",
                f"Count(Row(g={r2}))",
            ]
        )

    # one seeded stream, pre-drawn: both sides replay byte-identical
    # flight traffic and the timed loop holds nothing but api.query
    r = _random.Random(31)
    flights = [flight(r) for _ in range(n_flights)]
    calls_per_flight = 6

    def run_side(enabled: bool) -> dict:
        api = API(
            batch_window=0.004,
            batch_max_size=64,
            rescache_entries=0,
            planner_enabled=enabled,
        )
        try:
            seed(api)
            # warm with the full schedule once: all shapes compile here,
            # single-query warm gates open, so the timed replay below is
            # the steady state on both sides
            for q in flights:
                api.query("pl", q)
            devmark = _devcost_mark()
            t0 = time.perf_counter()
            for q in flights:
                api.query("pl", q)
            wall = time.perf_counter() - t0
            devcosts = _devcost_delta(
                devmark,
                f"planner({'on' if enabled else 'off'})",
                forbid_compiles=True,
            )
            # the lane's isolation invariant: the result cache is pinned
            # off, so NOTHING here is cache-served
            rc = api.executor.rescache.snapshot()
            if rc["entries"] != 0 or rc["hits"] != 0:
                raise RuntimeError(
                    f"planner lane: rescache leaked into the measurement "
                    f"(entries={rc['entries']} hits={rc['hits']})"
                )
            return {
                "qps": n_flights * calls_per_flight / wall,
                "devledger": devcosts,
                "planner": api.executor.planner.snapshot(),
            }
        finally:
            api.close()

    on = run_side(True)
    off = run_side(False)
    ratio = round(on["qps"] / off["qps"], 2) if off["qps"] else None
    psnap = on["planner"]
    return {
        "planner_on_qps": round(on["qps"], 1),
        "planner_off_qps": round(off["qps"], 1),
        "planner_on_vs_off": ratio,
        # planner accounting on the on side (warm + timed replays):
        # every flight shares one canonical subtree 4 ways, so hits
        # run ~3 per flight
        "cse_hits": psnap["cseHits"],
        "cse_shared": psnap["cseShared"],
        "reorders": psnap["reorders"],
        "lane_overrides": psnap["laneOverrides"],
        "planner_errors": psnap["errors"],
        "devledger_on": on["devledger"],
        "devledger_off": off["devledger"],
        "rescache_entries": 0,
        "pass_ratio": ratio is not None and ratio >= 1.5,
    }


def _np_bsi_lt(planes, exists, sign, value, depth):
    """CPU baseline: the same bit-sliced scan in vectorized numpy."""
    lt = np.zeros_like(exists)
    eq = exists & ~sign
    for k in reversed(range(depth)):
        p = planes[:, k]
        if (value >> k) & 1:
            lt |= eq & ~p
            eq = eq & p
        else:
            eq = eq & ~p
    return int(np.bitwise_count((lt | eq) | (exists & sign)).sum())


def main() -> int:
    accel = _on_accelerator()
    # Full size on the TPU chip (~10.7e9 bits = 1.34 GiB); small on CPU CI.
    if accel:
        S, R, W = 160, 64, 32768
    else:
        S, R, W = 16, 32, 2048

    key = jax.random.PRNGKey(7)
    # ~25% density via AND of two uniform word tensors, generated on device
    # (no host->device transfer of the index itself).
    k1, k2 = jax.random.split(key)
    bits = jax.random.bits(k1, (S, R, W), dtype=jnp.uint32) & jax.random.bits(
        k2, (S, R, W), dtype=jnp.uint32
    )
    _sync(bits)
    n_bits = S * R * W * 32

    rng = np.random.default_rng(3)
    B = 1024 if accel else 64
    ras = rng.integers(0, R, size=B).astype(np.int64)
    rbs = rng.integers(0, R, size=B).astype(np.int64)

    # -- batched Count(Intersect): the framework's serving path ------------
    # One MXU gram launch per batch answers all B queries (the same
    # gram+formula path Executor._batch_pair_counts runs).  Launches are
    # issued device-side first (true pipelining: the pull of batch r
    # overlaps the compute of batch r+1), then each batch's [R, R] gram
    # is pulled and the per-query formula lookups run on the host —
    # both included in the measured time.  The salt XOR that varies the
    # data across reps lives INSIDE the jitted program: on the fused
    # Pallas gram path the XOR'd copy is a program-local intermediate
    # (one index-sized transient per EXECUTING launch, freed on
    # completion — queued launches hold none), and on the XLA fallback
    # it fuses into the scan outright.
    gram_salted = jax.jit(lambda b, s: kernels.gram_matrix_traced(b ^ s))
    salts = [jnp.uint32(i) for i in range(9)]
    reps = 4
    # compile BOTH programs outside the timed region (the gram and the
    # stack-of-reps used for the single batched pull)
    _sync(jnp.stack([gram_salted(bits, salts[-1]) for _ in range(reps)]))
    t0 = time.perf_counter()
    grams = [gram_salted(bits, salts[r]) for r in range(reps)]
    # ONE pull for all reps' [R, R] grams: per-rep pulls would serialize
    # a host synchronisation each — the host-side answer extraction
    # still runs per rep below
    grams_np = np.asarray(jnp.stack(grams)).astype(np.int64)
    counts = [
        kernels.pair_counts_from_gram(g, ras, rbs, "intersect")
        for g in grams_np
    ]
    batched_t = (time.perf_counter() - t0) / reps
    batched_qps = B / batched_t
    checksum = int(counts[-1].sum())

    # -- sequential Count(Intersect): cold latency mode, END TO END --------
    # One lone query at a time through Executor.execute (parse included)
    # against a REAL full-size index, with the warm-up threshold pushed
    # out of reach so EVERY query is served cold — this measures the
    # host latency tier (fragment host mirrors + fused native
    # and+popcount, native/hostops.cpp), the framework's designed path
    # for a lone cold query (the reference's executor.go:1792 through
    # roaring.go:568).  No cache is consulted or installed.
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.core.view import VIEW_STANDARD
    from pilosa_tpu.exec.executor import Executor as _Executor

    h_seq = Holder(n_words=W)
    idx_seq = h_seq.create_index("seq")
    f_seq = idx_seq.create_field("f")
    v_seq = f_seq.create_view_if_not_exists(VIEW_STANDARD)
    seq_rng = np.random.default_rng(13)
    sub_shards = max(1, S // 16)
    sub = None  # first sub_shards kept for the CPU baseline
    for s in range(S):
        words = seq_rng.integers(
            0, 2**32, size=(R, W), dtype=np.uint32
        ) & seq_rng.integers(0, 2**32, size=(R, W), dtype=np.uint32)
        frag = v_seq.create_fragment_if_not_exists(s)
        for r in range(R):
            frag.set_row_words(r, words[r])
        if s == 0:
            sub = np.empty((sub_shards, R, W), dtype=np.uint32)
        if s < sub_shards:
            sub[s] = words
    ex_seq = _Executor(h_seq)
    ex_seq._PAIR_SINGLE_WARM = 10**9  # keep every query cold
    # 30 timed pairs off one permutation: every row appears at most
    # once across the whole timed loop (60 of R=64 rows), so no query
    # finds its operands in LLC from an earlier one — the same
    # cache-cold footing the CPU baseline below is held to.  The warm
    # call and its ground-truth check use the 2 leftover rows, touching
    # nothing the timed loop reads.
    seq_perm = np.random.default_rng(29).permutation(R)
    # every timed pair distinct on BOTH configs: 30 pairs fit R=64's 62
    # non-warm rows; the CPU-CI shape (R=32) gets 15
    n_seq = min(30, (R - 2) // 2)
    wa, wb = int(seq_perm[-2]), int(seq_perm[-1])
    q0 = f"Count(Intersect(Row(f={wa}), Row(f={wb})))"
    got0 = ex_seq.execute("seq", q0)[0]  # build native lib / warm once
    # end-to-end by construction: the result must equal ground truth
    # computed straight from the fragment host mirrors (any cache- or
    # stub-serving regression fails loudly instead of flattering qps)
    _want0 = 0
    for _s in range(S):
        _fr = v_seq.fragment(_s)
        _want0 += int(
            np.bitwise_count(
                _fr.row_words_host(wa) & _fr.row_words_host(wb)
            ).sum(dtype=np.uint64)
        )
    if got0 != _want0:
        raise RuntimeError(f"cold path wrong: {got0} != {_want0}")
    seq_lat = []
    for i in range(n_seq):
        qa, qb = int(seq_perm[2 * i]), int(seq_perm[2 * i + 1])
        t0 = time.perf_counter()
        ex_seq.execute(
            "seq", f"Count(Intersect(Row(f={qa}), Row(f={qb})))"
        )
        seq_lat.append(time.perf_counter() - t0)
    seq_lat.sort()
    # qps from the MEDIAN query: every query does identical work (2 rows
    # x S shards, distinct row pairs), so spread comes from the host —
    # scheduler quota throttling in sandboxed runs inflates the MEAN by
    # parking the process mid-burst (r03/r04 driver runs recorded 3-5x
    # the manual numbers this way).  Median is robust to those parks yet
    # still a full end-to-end Executor.execute round trip; min/mean/p90
    # are all recorded below so nothing hides.
    seq_qps = 1.0 / seq_lat[n_seq // 2]
    # per-phase breakdown of the same cold path (VERDICT r04 ask):
    # parse alone, then the fused native fan alone (addresses
    # precomputed), so the recorded JSON shows where a slow run's time
    # went without rerunning anything by hand.
    from pilosa_tpu.ops import _hostops as _ho
    from pilosa_tpu.pql import parser as _pql_parser

    # same pair schedule as the timed loop (cold-for-cold: a different
    # schedule could ride LLC-warm repeated rows and read lower than the
    # loop it decomposes); trailing space dodges the parse cache so
    # parse_ms measures real parses
    t0 = time.perf_counter()
    for i in range(n_seq):
        _pql_parser.parse(
            f"Count(Intersect(Row(f={int(seq_perm[2 * i])}),"
            f" Row(f={int(seq_perm[2 * i + 1])}))) "
        )
    parse_ms = (time.perf_counter() - t0) / n_seq * 1e3
    _view0 = idx_seq.field("f").view(VIEW_STANDARD)
    t0 = time.perf_counter()
    for i in range(n_seq):
        ex_seq._host_pair_count(
            _view0, int(seq_perm[2 * i]), int(seq_perm[2 * i + 1]),
            "intersect", list(range(S)),
        )
    host_fan_ms = (time.perf_counter() - t0) / n_seq * 1e3
    seq_breakdown = {
        "native_hostops": _ho.load() is not None,
        "cpu_count": os.cpu_count(),
        "bytes_per_query": S * 2 * W * 4,
        "parse_ms": round(parse_ms, 3),
        "host_fan_ms": round(host_fan_ms, 3),
        "lat_min_ms": round(seq_lat[0] * 1e3, 2),
        "lat_p50_ms": round(seq_lat[n_seq // 2] * 1e3, 2),
        "lat_mean_ms": round(sum(seq_lat) / n_seq * 1e3, 2),
        "lat_p90_ms": round(seq_lat[-(-9 * n_seq // 10) - 1] * 1e3, 2),
        "lat_max_ms": round(seq_lat[-1] * 1e3, 2),
    }

    # -- cache-served sequential: the executor's steady-state for repeat
    # singles, measured as FULL Executor.execute round trips (parse
    # included).  After warm-up the stack+gram investment engages and
    # every lone Count(op(Row,Row)) is answered from the cached HOST
    # gram — zero device work per query (the reference's ranked cache
    # serving counts from memory, cache.go).  Per-query cost is
    # index-size-independent by design (that is the point of the
    # cache), so the warm-up runs over a shard subset to keep the
    # one-time stack upload bounded.
    srv_shards = list(range(sub_shards))
    qwarm = f"Count(Intersect(Row(f={int(ras[0])}), Row(f={int(rbs[0])})))"
    ex_srv = _Executor(h_seq)
    for _ in range(ex_srv._PAIR_SINGLE_WARM + 2):
        ex_srv.execute("seq", qwarm, shards=srv_shards)
    n_sv = 400
    t0 = time.perf_counter()
    for i in range(n_sv):
        j = i % B
        ex_srv.execute(
            "seq",
            f"Count(Intersect(Row(f={int(ras[j])}), Row(f={int(rbs[j])})))",
            shards=srv_shards,
        )
    seq_served_qps = n_sv / (time.perf_counter() - t0)

    # -- TopN p50: executor round trips with a write before EVERY query.
    # The first TopN counts each fragment's host mirror once (the
    # reference recounts its cache on restore the same way,
    # fragment.go:459-498); after that, point writes carry the counts
    # as deltas and no query rescans anything.
    ex_seq.execute("seq", "TopN(f, n=10)")  # one-time count build
    lat = []
    wrng = np.random.default_rng(17)
    for i in range(9):
        col = int(wrng.integers(0, S)) * W * 32 + int(
            wrng.integers(0, W * 32)
        )
        ex_seq.execute("seq", f"Set({col}, f={int(wrng.integers(0, R))})")
        t0 = time.perf_counter()
        ex_seq.execute("seq", "TopN(f, n=10)")
        lat.append(time.perf_counter() - t0)
    topn_p50_ms = sorted(lat)[len(lat) // 2] * 1e3

    # -- TopN scan throughput ----------------------------------------------
    # the cold device row-scan kernel, repeat launches
    # over the SAME resident tensor (each launch re-reads HBM; no salt
    # copy, so bytes-moved == index size and the GB/s figure is honest)
    scan = jax.jit(kernels.row_counts_per_shard_xla)
    _sync(scan(bits))
    # dispatch round trip: the fixed cost every host-synchronised
    # launch pays; recorded so launch-bound numbers are attributable
    tiny = jax.jit(lambda: jnp.zeros((8,), jnp.uint32))
    _sync(tiny())
    rtts = []
    for _ in range(3):  # best-of, same discipline as every latency figure
        t0 = time.perf_counter()
        _sync(tiny())
        rtts.append(time.perf_counter() - t0)
    dispatch_rtt_ms = min(rtts) * 1e3
    n_scan = 24
    t0 = time.perf_counter()
    outs = [scan(bits) for _ in range(n_scan)]
    _sync(outs[-1])
    scan_t = (time.perf_counter() - t0) / n_scan
    scan_gbps = (n_bits / 8) / scan_t / 1e9

    # -- BSI range (BASELINE config 3: int-field Range + count) -------------
    D = 16
    kp = jax.random.split(key, 3)
    planes = jax.random.bits(kp[0], (S, D, W), dtype=jnp.uint32) & jax.random.bits(
        kp[1], (S, D, W), dtype=jnp.uint32
    )
    exists = jnp.full((S, W), jnp.uint32(0xFFFFFFFF))
    sign = jnp.zeros((S, W), jnp.uint32)
    run_range = _bsi_range_fn(D, 12345)
    _sync(run_range(planes, exists, sign, jnp.uint32(0)))  # compile
    n_rq = 20

    def _seq_pass():
        outs = [
            run_range(planes, exists, sign, jnp.uint32(i)) for i in range(n_rq)
        ]
        _sync(outs[-1])

    # baseline over the FULL shard set: the old 1/16-subset-times-16
    # extrapolation undercounted numpy's per-call fixed costs (allocation
    # of the lt/eq temporaries, bitwise_count reduction setup), inflating
    # bsi_range_vs_baseline at CPU-CI sizes where S//16 == 1 shard.
    planes_np = np.asarray(planes)
    ex_np = np.asarray(exists)
    sg_np = np.asarray(sign)
    t0 = time.perf_counter()
    _np_bsi_lt(planes_np, ex_np, sg_np, 12345, D)
    cpu_bsi_t = time.perf_counter() - t0

    # -- BSI range, query-batched lane --------------------------------------
    # A full Q-bucket of predicates coalesced into ONE launch via the
    # borrow-accumulator batch kernel (ops/bsi.py range_count_batch):
    # the per-dispatch overhead the sequential lane pays per query is
    # paid once per flight, so the lane measures the coalescing win the
    # serving-plane batcher buys.  Host-side bound encoding and the
    # int64 combine are inside the timed region — this is the
    # end-to-end per-flight cost, same discipline as bsi_qps.  Both BSI
    # lanes are timed as best-of over interleaved rounds so the
    # reported ratio compares like conditions on noisy shared hosts.
    from pilosa_tpu.ops import bsi as _bsi

    n_bq = 128  # one full pow2 Q-bucket: no padded slots in the launch
    # thresholds spread across the in-band value range: every query
    # runs the real plane scan (no out-of-band shortcuts)
    batch_bounds = [
        _bsi.condition_bounds("<=", int((i + 0.5) * (1 << D) / n_bq))
        for i in range(n_bq)
    ]
    _bsi.range_count_batch(planes, exists, sign, batch_bounds, depth=D)
    best_seq = best_batch = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _seq_pass()
        best_seq = min(best_seq, time.perf_counter() - t0)
        t0 = time.perf_counter()
        _bsi.range_count_batch(planes, exists, sign, batch_bounds, depth=D)
        best_batch = min(best_batch, time.perf_counter() - t0)
    bsi_qps = n_rq / best_seq
    bsi_batched_qps = n_bq / best_batch
    bsi_vs = bsi_qps * cpu_bsi_t

    # -- end-to-end executor serving (warm caches) --------------------------
    # A modest REAL index served through Executor.execute: repeat queries
    # against unchanged fields hit the per-snapshot host caches (gram /
    # row counts / cross gram / BSI scalars — the reference's ranked
    # cache role, cache.go) with zero device work per query.  Measured
    # as full PQL round trips, parse included.
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.exec.executor import Executor as _Executor

    _h = Holder()
    _idx = _h.create_index("bench")
    _idx.create_field("f")
    _idx.create_field("g")
    _idx.create_field("v", FieldOptions(field_type="int", min_=0, max_=10**6))
    # rescache off: these numbers are the UNCACHED serving floor the
    # semantic-cache lane compares its hit path against (a repeat query
    # would otherwise measure a cache hit, not the executor)
    _ex = _Executor(_h, rescache_entries=0)
    srv_rng = np.random.default_rng(5)
    srv_width = _h.n_words * 32
    srv_writes = []
    for row in range(8):
        for col in srv_rng.integers(0, 2 * srv_width, size=120):
            srv_writes.append(f"Set({int(col)}, f={row})")
    for row in range(4):
        for col in srv_rng.integers(0, 2 * srv_width, size=80):
            srv_writes.append(f"Set({int(col)}, g={row})")
    for col in srv_rng.choice(2 * srv_width, size=400, replace=False):
        srv_writes.append(f"Set({int(col)}, v={int(srv_rng.integers(0, 10**6))})")
    _ex.execute("bench", " ".join(srv_writes))

    def _served_ms(q, warmups=8, reps=20):
        for _ in range(warmups):
            _ex.execute("bench", q)
        t0 = time.perf_counter()
        for _ in range(reps):
            _ex.execute("bench", q)
        return (time.perf_counter() - t0) / reps * 1e3

    serving = {
        "serving_count_pair_ms": _served_ms(
            "Count(Intersect(Row(f=0), Row(f=1)))"
        ),
        "serving_topn_ms": _served_ms("TopN(f, n=5)"),
        "serving_groupby_ms": _served_ms("GroupBy(Rows(f), Rows(g))"),
        "serving_sum_ms": _served_ms("Sum(field=v)"),
        "serving_range_count_ms": _served_ms("Count(Row(v < 500000))"),
    }

    # -- served concurrency sweep: the continuous-batching plane through
    # the real HTTP listener (one keep-alive connection per client)
    served_sweep = _served_concurrency_sweep()

    # -- flight-recorder overhead: served qps with the incident plane
    # on vs off
    recorder_lane = None
    try:
        recorder_lane = _recorder_overhead_lane()
    except Exception as e:
        _lane_failed("recorder overhead", e)

    # -- metrics-history overhead: served qps with the ring-TSDB
    # sampler + trend detectors on vs off
    history_lane = None
    try:
        history_lane = _history_overhead_lane()
    except Exception as e:
        _lane_failed("history overhead", e)

    # -- black-box overhead: served qps with the crash-durable spool
    # writer on vs off at 25x cadence
    blackbox_lane = None
    try:
        blackbox_lane = _blackbox_overhead_lane()
    except Exception as e:
        _lane_failed("blackbox overhead", e)

    # -- cluster-on-mesh lane: distributed Count/TopN/Range answered as
    # one jit-sharded launch over an in-mesh 8-way cluster, vs the same
    # data on a single holder
    mesh_dist_lane = None
    try:
        mesh_dist_lane = _mesh_dist_lane()
    except Exception as e:
        _lane_failed("mesh_dist", e)

    # -- tiered-residency lane: zipfian stack workload fully resident vs
    # 6x HBM-oversubscribed with flight-driven prefetch
    residency_lane = None
    try:
        residency_lane = _residency_lane()
    except Exception as e:
        _lane_failed("residency", e)

    # -- semantic result cache lane: zipfian repeat-heavy reads with
    # interleaved writes, cache on vs off over identical data; the
    # floor is the cheapest uncached serving number above
    rescache_lane = None
    try:
        rescache_lane = _rescache_lane(min(serving.values()))
    except Exception as e:
        _lane_failed("rescache", e)

    # -- flight planner lane: zipfian repeat-heavy flights whose calls
    # share canonical subtrees, planner on vs off over identical data
    # with the result cache pinned off on both sides — the speedup is
    # cross-query CSE, not caching
    planner_lane = None
    try:
        planner_lane = _planner_lane()
    except Exception as e:
        _lane_failed("planner", e)

    # -- SLO harness lane: a short seeded mixed-workload burst through
    # the full HTTP path with the server's error-budget tracker live
    # (tools/loadharness.py is the long-form version; this lane pins the
    # per-class p99 + budget burn numbers into the bench record, and
    # best-effort writes the full SLO_r*.json next to BENCH_r*.json)
    slo_lane = None
    try:
        from pilosa_tpu import loadgen

        slo_report = loadgen.run_harness(
            loadgen.WorkloadConfig(seed=42, n_cols=10_000),
            [
                loadgen.StageSpec("warm", 1.0, 60.0, 4),
                loadgen.StageSpec("mix", 2.0, 120.0, 8),
                # shared-subtree dashboard flights: the stage's report
                # entry carries the flight planner's per-stage
                # cseHits/reorders deltas (docs/serving.md)
                loadgen.StageSpec(
                    "sharedflight", 1.0, 80.0, 4, shared_pool=6
                ),
            ],
            nodes=1,
            cluster_kwargs={
                "slo_burn_rules": [
                    {"name": "fast", "long": 60.0, "short": 10.0,
                     "factor": 14.4},
                    {"name": "slow", "long": 300.0, "short": 60.0,
                     "factor": 1.0},
                ],
                "slo_slot_seconds": 1.0,
                "slo_latency_window": 60.0,
            },
            preload_bits=1024,
        )
        loadgen.validate_report(slo_report)
        slo_lane = {
            "throughput_ops_s": round(slo_report["throughputOpsPerSec"], 1),
            "total_ops": slo_report["totalOps"],
            "client_errors": slo_report["clientErrors"],
            "pass": slo_report["pass"],
            "fingerprint": slo_report["sequenceFingerprint"][:16],
            "p99_ms": {
                cls: round(c["p99Ms"], 2)
                for cls, c in slo_report["ops"].items()
                if c["p99Ms"] is not None
            },
        }
        try:
            slo_path = loadgen.next_report_path(".")
            with open(slo_path, "w") as sf:
                json.dump(slo_report, sf, indent=1, sort_keys=True)
                sf.write("\n")
            slo_lane["report_path"] = slo_path
        except OSError as e:
            print(f"warning: SLO report not written: {e}", file=sys.stderr)
    except Exception as e:
        _lane_failed("slo harness", e)

    # -- ingest: cold bulk import + sustained steady-state ------------------
    # Cold: one vectorized bulk import + HBM upload (fragment.import_bits).
    # Sustained: multi-batch run with the op-log store attached — each
    # batch appends WAL records, may trigger background snapshots, and
    # refreshes the device copy (the reference's hardest-benched path,
    # fragment_internal_test.go:709-2190).
    from pilosa_tpu.core.fragment import Fragment
    from pilosa_tpu.storage.fragmentfile import FragmentFile, SnapshotQueue

    n_pos = 2_000_000 if accel else 200_000
    ing_rng = np.random.default_rng(11)
    ing_rows = ing_rng.integers(0, 64, size=n_pos).astype(np.uint64)
    ing_cols = ing_rng.integers(0, W * 32, size=n_pos)
    # compile the device-sync programs outside the timed region (XLA
    # program compilation is process state, not ingest work; the anchor
    # has no compiler to warm)
    warm = Fragment(n_words=W)
    # enough positions to hit all 64 row ids, so the warmed program has
    # the same [64, W] shape as the measured fragment
    warm.import_bits(ing_rows[:4096], ing_cols[:4096])
    _sync(warm.device_bits())
    del warm
    # best of 2 bursts: a shared-host wall clock is noisy upward, never
    # downward (same discipline as the CPU query baseline)
    ingest_bits_s = 0.0
    for _ in range(2):
        with tempfile.TemporaryDirectory() as d0:
            sq0 = SnapshotQueue(workers=2)
            frag = Fragment(n_words=W)
            store0 = FragmentFile(frag, os.path.join(d0, "frag"), sq0)
            store0.open()
            frag.store = store0
            t0 = time.perf_counter()
            frag.import_bits(ing_rows, ing_cols)
            frag.device_bits()  # include the HBM upload in the ingest cost
            sq0.await_all()
            ingest_bits_s = max(
                ingest_bits_s, n_pos / (time.perf_counter() - t0)
            )
            sq0.stop()
            store0.close()

    # Sustained: multi-batch run through the full durability path —
    # op-record WAL appends (checksummed, one fsync per batch),
    # background snapshots, and ONE final device refresh (the serving
    # copy syncs lazily on the next query; that is the design, so the
    # steady state pays it once per convergence, not per batch).
    n_batches, batch = (8, 500_000) if accel else (4, 50_000)
    srows = ing_rng.integers(0, 64, size=n_batches * batch).astype(np.uint64)
    scols = ing_rng.integers(0, W * 32, size=n_batches * batch)
    # best of 2 full runs (same noise discipline as the cold burst: the
    # shared host's bandwidth swings 2-10 GB/s between minutes and this
    # path is bandwidth-heavy)
    sustained_nodev_bits_s = 0.0
    sustained_bits_s = 0.0
    # ledger deltas across the whole sustained lane: the open in-bench
    # sensitivity item (ROADMAP S3) needs to know whether the slow
    # in-bench runs hide recompiles or extra transfers
    sustained_devmark = _devcost_mark()
    for _ in range(2):
        with tempfile.TemporaryDirectory() as d:
            sq = SnapshotQueue(workers=2)
            frag2 = Fragment(n_words=W)
            store = FragmentFile(frag2, os.path.join(d, "frag"), sq)
            store.open()
            frag2.store = store
            t0 = time.perf_counter()
            for bi in range(n_batches):
                sl = slice(bi * batch, (bi + 1) * batch)
                frag2.import_bits(srows[sl], scols[sl])
            sq.await_all()  # snapshots are part of the steady-state cost
            # durable-on-host rate: the comparison point for the
            # reference anchor (the reference is CPU-only; the device
            # refresh below is EXTRA work it does not do)
            nodev = (n_batches * batch) / (time.perf_counter() - t0)
            frag2.device_bits()  # converge the serving copy once
            withdev = (n_batches * batch) / (time.perf_counter() - t0)
            # both rates from the SAME (best-nodev) run: maxing them
            # independently could mix runs and distort the implied
            # device-refresh cost
            if nodev > sustained_nodev_bits_s:
                sustained_nodev_bits_s = nodev
                sustained_bits_s = withdev
            sq.stop()
            store.close()
    sustained_devcosts = _devcost_delta(sustained_devmark, "sustained_ingest")

    # -- pipelined ingest: the staged pipeline (native zero-copy decode
    # -> coalesced apply on the worker pool -> double-buffered device
    # upload) vs the lock-step path (decode, merge, device sync, one
    # batch at a time on one thread) over the SAME pre-serialized
    # roaring segments, round-robined across shards so uploads of one
    # fragment overlap applies of another.
    from pilosa_tpu.ingest import IngestPipeline
    from pilosa_tpu.server.importpool import ImportPool
    from pilosa_tpu.storage import roaring as _roaring

    # Few shards + many queued batches: the shape that starves the
    # lock-step path (a device sync per batch, serialized behind every
    # apply) and that the pipeline's group-commit exists for — queued
    # same-fragment batches coalesce into one merged apply and pending
    # device syncs dedup, so the HBM refresh cost is paid per
    # convergence, not per batch.  Both paths keep the serving copy
    # device-resident across the run (every applied batch is synced).
    from pilosa_tpu.shardwidth import SHARD_WORDS as _pW

    # Production shard width for BOTH paths (the bench's CPU-scaled W
    # would shrink the per-batch HBM refresh to noise and hide exactly
    # the cost the pipeline amortizes).
    n_shards_p = 2
    p_batches, p_batch = (64, 200_000) if accel else (64, 50_000)
    width64 = np.uint64(_pW * 32)
    pip_rng = np.random.default_rng(23)
    p_blobs = []
    p_total = 0
    for bi in range(p_batches):
        pos = np.unique(
            pip_rng.integers(0, 64 * _pW * 32, size=p_batch).astype(np.uint64)
        )
        p_total += len(pos)
        p_blobs.append((bi % n_shards_p, _roaring.serialize(pos)))

    def _lockstep_run():
        frags = {s: Fragment(n_words=_pW) for s in range(n_shards_p)}
        t0 = time.perf_counter()
        for shard, blob in p_blobs:
            positions = _roaring.deserialize(blob)
            frags[shard].import_bits(
                positions // width64,
                (positions % width64).astype(np.int64),
            )
            frags[shard].device_bits()  # serialized per-batch upload
        return p_total / (time.perf_counter() - t0)

    def _pipelined_run():
        frags = {s: Fragment(n_words=_pW) for s in range(n_shards_p)}
        pool = ImportPool(workers=2, depth=2 * p_batches)
        # staging sized to the batch (the 1M-position default would
        # lazily fault ~0.5GB across 64 buffers and swamp the timing)
        pipe = IngestPipeline(
            pool,
            staging_buffers=p_batches,
            staging_capacity=1 << 18 if accel else 1 << 17,
            upload_slots=2,
        )
        t0 = time.perf_counter()
        # decode stage runs as a prefetch: every blob lands in staging
        # before the drain is awaited, so the apply stage sees the whole
        # backlog and group-commit merges it per fragment (interleaving
        # decode with the drain instead leaves coalescing at the mercy
        # of worker scheduling — the merged-apply count, and with it the
        # measured rate, becomes a coin flip)
        staged = [(s, pipe.decode_roaring(blob)) for s, blob in p_blobs]
        handles = []
        for shard, buf in staged:
            frag = frags[shard]

            # same shape as ApiServer.import_roaring's group apply:
            # per-payload merges under one pool job, one device sync
            def apply_group(payloads, _frag=frag):
                changed = 0
                for b in payloads:
                    positions = b.positions
                    changed += _frag.import_bits(
                        positions // width64,
                        (positions % width64).astype(np.int64),
                    )
                return changed, _frag

            handles.append(
                pipe.submit_segment(
                    id(frag), buf, apply_group, release=lambda b: b.release()
                )
            )
        pipe.drain(handles)
        pipe.uploader.flush()
        rate = p_total / (time.perf_counter() - t0)
        frac = pipe.overlap_frac
        pipe.close()
        pool.close()
        return rate, frac

    # warm the production-width device-sync programs outside the timed
    # region (the cold burst above compiled the CPU-scaled W shapes)
    _pwarm = Fragment(n_words=_pW)
    _pwarm.import_bits(ing_rows[:4096], ing_cols[:4096] % (_pW * 32))
    _sync(_pwarm.device_bits())
    del _pwarm

    # best-of-2 each, symmetric noise discipline; overlap is best
    # observed across runs (whether the last upload catches the other
    # fragment's apply is scheduler timing — a miss is noise downward)
    lockstep_ingest_bits_s = max(_lockstep_run() for _ in range(2))
    _p_runs = [_pipelined_run() for _ in range(2)]
    pipelined_ingest_bits_s = max(r for r, _ in _p_runs)
    ingest_overlap_frac = max(f for _, f in _p_runs)

    # CPU anchor for ingest (vs_baseline): the same semantic work —
    # dedup + mirror merge + changed-position extraction + checksummed
    # WAL append with per-batch fsync + snapshot rewrite past MaxOpN —
    # in straightforward single-stream vectorized numpy + stdlib IO,
    # standing in for the reference's Go import path
    # (fragment.go:1995-2280 bulkImport -> roaring.go:1463
    # ImportRoaringBits + op log) like the query baseline's numpy
    # popcount stands in for its roaring word loops.
    def _cpu_anchor_ingest(rows, cols, n_batches, batch, W):
        import zlib

        width = W * 32
        mirror = np.zeros((64, W), dtype=np.uint32)
        ops_since_snap = 0
        with tempfile.TemporaryDirectory() as d2:
            path = os.path.join(d2, "anchor")
            fh = open(path, "wb")
            t0 = time.perf_counter()
            for bi in range(n_batches):
                sl = slice(bi * batch, (bi + 1) * batch)
                r = rows[sl].astype(np.int64)
                c = cols[sl]
                key = r * width + c
                ukey = np.unique(key)
                ur = ukey // width
                uc = ukey % width
                w = (uc >> 5).astype(np.int64)
                bit = np.uint32(1) << (uc & 31).astype(np.uint32)
                pre = mirror[ur, w]
                newly = (pre & bit) == 0
                np.bitwise_or.at(mirror, (ur, w), bit)
                positions = ukey[newly].astype(np.uint64)
                payload = positions.tobytes()
                fh.write(
                    len(payload).to_bytes(8, "little")
                    + zlib.crc32(payload).to_bytes(4, "little")
                    + payload
                )
                # reference durability: op appends are NOT fsynced
                # (roaring.go:1655 writeOp) — only snapshot files are;
                # the repo side now runs the same policy
                # (PILOSA_TPU_WAL_FSYNC default "snapshot")
                fh.flush()
                ops_since_snap += len(positions)
                if ops_since_snap > 10_000:  # MaxOpN snapshot rewrite
                    snap = os.path.join(d2, "anchor.snap")
                    with open(snap, "wb") as sf:
                        packed = np.nonzero(
                            np.unpackbits(
                                mirror.view(np.uint8), bitorder="little"
                            )
                        )[0].astype(np.uint64)
                        sf.write(packed.tobytes())
                        sf.flush()
                        os.fsync(sf.fileno())
                    ops_since_snap = 0
                    fh.close()
                    fh = open(path, "wb")
            fh.close()
            return (n_batches * batch) / (time.perf_counter() - t0)

    # best-of-2, same discipline as the repo side it anchors
    cpu_ingest_bits_s = max(
        _cpu_anchor_ingest(srows, scols, n_batches, batch, W)
        for _ in range(2)
    )

    # -- reference anchors (VERDICT r04 #2): the compiled C++ port of
    # the reference's own semantic work (native/refanchor.cpp — roaring
    # containers, AddN sorted-merge, per-row CountRange cache update,
    # snapshot serialize+fsync; see tools/ref_anchor.py for the full
    # benchmark-by-benchmark table) run on the SAME data as the repo
    # paths above.  None when no toolchain exists in the sandbox.
    ref_sustained_bits_s = None
    ref_seq_qps = None
    try:
        from pilosa_tpu.ops import _refanchor

        if _refanchor.load() is not None:
            # sustained ingest: every batch's changed bits (~500k) trip
            # MaxOpN=10000, so the reference pays a full snapshot per
            # batch (fragment.go:2283-2293 incrementOpN -> snapshot)
            width64 = np.uint64(W * 32)
            ref_sustained_bits_s = 0.0
            for _ in range(2):  # best-of, symmetric with the repo side
                with tempfile.TemporaryDirectory() as dr:
                    with _refanchor.RefBitmap() as rb:
                        opw = open(os.path.join(dr, "ops"), "ab")
                        t0 = time.perf_counter()
                        for bi in range(n_batches):
                            sl = slice(bi * batch, (bi + 1) * batch)
                            pos = np.unique(
                                srows[sl] * width64
                                + scols[sl].astype(np.uint64)
                            )
                            rb.addn_sorted(pos)
                            # the reference also appends an
                            # opTypeAddBatch record per AddN
                            # (roaring.go:248-265, 8 bytes per changed
                            # bit, page-cache only)
                            opw.write(pos.tobytes())
                            opw.flush()
                            for r in np.unique(srows[sl]):
                                rb.count_range(
                                    int(r) * W * 32,
                                    (int(r) + 1) * W * 32,
                                )
                            rb.snapshot(os.path.join(dr, "snap"))
                        ref_sustained_bits_s = max(
                            ref_sustained_bits_s,
                            (n_batches * batch)
                            / (time.perf_counter() - t0),
                        )
                        opw.close()
            # sequential query: S pseudo-shards of the real row pair
            # (25% density -> bitmap containers; one query walks the
            # same ~42 MB the host tier streams), counted in ONE native
            # crossing like the reference's in-process shard fan.  The
            # host L3 is 260 MB, so the working set is explicitly
            # EVICTED between reps — the repo's cold loop reads
            # distinct rows of a 1.3 GB index and gets no cache help;
            # the anchor must not either.
            def _row_positions(words, row):
                bits = np.unpackbits(
                    words.view(np.uint8), bitorder="little"
                )
                return np.nonzero(bits)[0].astype(np.uint64) + np.uint64(
                    row
                ) * np.uint64(W * 32)

            pos_a = _row_positions(sub[0, wa], 0)
            pos_b = _row_positions(sub[0, wb], 1)
            with _refanchor.RefBitmap() as rb:
                for k in range(S):
                    off = np.uint64(2 * k) * np.uint64(W * 32)
                    rb.addn_sorted(pos_a + off)
                    rb.addn_sorted(pos_b + off)
                rows_a = np.arange(S, dtype=np.uint64) * 2
                rows_b = rows_a + 1
                evict = np.zeros(40 * 1024 * 1024, dtype=np.uint64)
                ref_ts = []
                for _ in range(3):
                    evict[:] = 1  # 320 MB write pass flushes L3
                    t0 = time.perf_counter()
                    rb.intersection_count_many(rows_a, rows_b, W * 32)
                    ref_ts.append(time.perf_counter() - t0)
                del evict
                ref_seq_qps = 1.0 / min(ref_ts)
    except Exception as e:
        _lane_failed("refanchor", e)

    # -- CPU baseline (numpy popcount on a shard subset, scaled) ------------
    # ``sub`` is the host-generated shard subset of the sequential index
    # (same shape/density as the device tensor), so the baseline and the
    # host latency tier run against identical data.
    S_sub = sub_shards
    # per-query: AND + popcount of two rows across all shards; best-of-5
    # over pairs drawn from a PERMUTATION so no row repeats across reps
    # (caches hold rows, not pairs: a re-read row would serve from
    # L2/L3 and flatter the baseline — the real index streams from
    # DRAM, and the measured path above is charged that way); min
    # because wall clock on a shared host is noisy upward, never down
    perm = np.random.default_rng(23).permutation(R)
    times = []
    for k in range(5):
        qa, qb = int(perm[2 * k]), int(perm[2 * k + 1])
        t0 = time.perf_counter()
        int(np.bitwise_count(sub[:, qa] & sub[:, qb]).sum())
        times.append(time.perf_counter() - t0)
    cpu_query_t = min(times) * (S / S_sub)
    cpu_qps = 1.0 / cpu_query_t
    t0 = time.perf_counter()
    np.bitwise_count(sub).sum(axis=(0, 2))
    cpu_topn_ms = (time.perf_counter() - t0) * (S / S_sub) * 1e3

    result = {
        "metric": "count_intersect_qps_per_chip",
        "value": round(batched_qps, 1),
        "unit": f"Count(Intersect) queries/sec/chip, batched, {n_bits/1e9:.1f}e9-bit index",
        "vs_baseline": round(batched_qps / cpu_qps, 1),
        "sequential_qps": round(seq_qps, 1),
        "sequential_vs_baseline": round(seq_qps / cpu_qps, 1),
        "sequential_served_qps": round(seq_served_qps, 1),
        "sequential_served_vs_baseline": round(seq_served_qps / cpu_qps, 1),
        "topn_p50_ms": round(topn_p50_ms, 2),
        "topn_mode": (
            "Executor.execute round trip, one write landed before every "
            "query (maintained counts); baseline = single-core numpy "
            "full rescan, the cache-less CPU cost"
        ),
        "topn_vs_baseline": round(cpu_topn_ms / topn_p50_ms, 1),
        "topn_scan_gbytes_s": round(scan_gbps, 1),
        "bsi_range_qps": round(bsi_qps, 1),
        "bsi_range_vs_baseline": round(bsi_vs, 1),
        "bsi_range_batched_qps": round(bsi_batched_qps, 1),
        "bsi_batched_vs_sequential": round(bsi_batched_qps / bsi_qps, 1),
        "ingest_bits_s": round(ingest_bits_s, 0),
        "ingest_vs_baseline": round(ingest_bits_s / cpu_ingest_bits_s, 1),
        "sustained_ingest_bits_s": round(sustained_bits_s, 0),
        "sustained_ingest_vs_baseline": round(
            sustained_bits_s / cpu_ingest_bits_s, 1
        ),
        # compile/transfer accounting for the sustained lane (the
        # in-bench sensitivity item, ROADMAP S3: recompiles or transfer
        # inflation would show here)
        "sustained_ingest_devledger": sustained_devcosts,
        # staged-pipeline lane (pilosa_tpu/ingest/): same roaring
        # segments through the pipeline vs the lock-step path;
        # overlap_frac = fraction of H2D bytes whose upload ran while an
        # apply was in flight
        "pipelined_ingest_bits_s": round(pipelined_ingest_bits_s, 0),
        "lockstep_ingest_bits_s": round(lockstep_ingest_bits_s, 0),
        "pipelined_ingest_vs_lockstep": round(
            pipelined_ingest_bits_s / lockstep_ingest_bits_s, 2
        ),
        "ingest_overlap_frac": round(ingest_overlap_frac, 3),
        "cpu_ingest_bits_s": round(cpu_ingest_bits_s, 0),
        "cpu_baseline_qps": round(cpu_qps, 1),
        "platform": jax.devices()[0].platform,
        "index_bits": n_bits,
        # size-normalized figures so CPU-fallback rounds compare against
        # TPU rounds: work per second per billion index bits
        "batched_qps_per_gbit": round(batched_qps / (n_bits / 1e9), 2),
        "cpu_qps_per_gbit": round(cpu_qps / (n_bits / 1e9), 2),
        "batch_size": B,
        "batched_checksum": checksum,
        "seq_breakdown": seq_breakdown,
        "dispatch_rtt_ms": round(dispatch_rtt_ms, 3),
        # vs the compiled reference-anchor (same semantic work, same
        # data; None when no C++ toolchain in the sandbox)
        "refanchor_available": ref_sustained_bits_s is not None,
        "sustained_ingest_nodevice_bits_s": round(sustained_nodev_bits_s, 0),
        "sustained_ingest_vs_reference": (
            round(sustained_nodev_bits_s / ref_sustained_bits_s, 2)
            if ref_sustained_bits_s
            else None
        ),
        "reference_sustained_bits_s": (
            round(ref_sustained_bits_s, 0) if ref_sustained_bits_s else None
        ),
        "sequential_vs_reference": (
            round(seq_qps / ref_seq_qps, 2) if ref_seq_qps else None
        ),
        "reference_seq_qps": (
            round(ref_seq_qps, 1) if ref_seq_qps else None
        ),
        **{k: round(v, 3) for k, v in serving.items()},
        # HTTP-path concurrency sweep (continuous-batching serving
        # plane): per-level qps + p50/p99, batch-size histogram, and
        # window-close counters — levels[0] is the single-client floor,
        # levels[-1] the 1000-client throughput headline
        "served_http_sweep": served_sweep,
        "served_http_qps_1_client": served_sweep["levels"][0]["qps"],
        "served_http_qps_1k_clients": served_sweep["levels"][-1]["qps"],
        # cluster-on-mesh lane: distributed queries over an in-mesh
        # 8-way cluster with ZERO HTTP subrequests (asserted), vs the
        # single-holder batched path (docs/serving.md "Cluster on the
        # mesh")
        "mesh_dist": mesh_dist_lane,
        "mesh_dist_count_qps": (
            (mesh_dist_lane or {}).get("mesh_dist_count_qps")
        ),
        "mesh_dist_vs_single_holder": (
            (mesh_dist_lane or {}).get("mesh_dist_vs_single_holder")
        ),
        # SLO harness lane (short seeded mixed burst; the full report is
        # in the SLO_r*.json it writes — see docs/observability.md)
        "slo_harness": slo_lane,
        # incident-plane cost: overhead_frac is (1 - on/off); the
        # acceptance bar for the always-on recorder is <= 0.05
        "recorder_overhead": recorder_lane,
        # metrics-history cost (obs/history.py sampler + trend
        # detectors at 2x production cadence): same <= 0.05 bar
        "history_overhead": history_lane,
        # crash-durable black-box cost (obs/blackbox.py spool writer at
        # 25x production cadence, disk-backed nodes): same <= 0.05 bar;
        # "writer" carries the spool's own checkpoint self-accounting
        "blackbox_overhead": blackbox_lane,
        # tiered-residency lane: oversubscribed_vs_resident >= 0.25 and
        # prefetch_useful_frac >= 0.5 are the working-set manager's bars
        # (docs/residency.md)
        "residency": residency_lane,
        "residency_oversubscribed_vs_resident": (
            (residency_lane or {}).get("oversubscribed_vs_resident")
        ),
        "residency_prefetch_useful_frac": (
            (residency_lane or {}).get("prefetch_useful_frac")
        ),
        # semantic result cache lane: cache-served p50 must undercut the
        # uncached serving floor and cached/uncached qps >= 5x are the
        # cache's bars (docs/caching.md)
        "rescache": rescache_lane,
        "rescache_hit_vs_uncached": (
            (rescache_lane or {}).get("rescache_hit_vs_uncached")
        ),
        "rescache_hit_p50_ms": ((rescache_lane or {}).get("hit_p50_ms")),
        # flight planner lane: planner-on/off qps >= 1.5x on shared-
        # subtree flights with the result cache off is the planner's
        # bar (docs/serving.md "Flight planning")
        "planner": planner_lane,
        "planner_on_vs_off": ((planner_lane or {}).get("planner_on_vs_off")),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
        "lane_failures": _LANE_FAILURES,
        # dispatch-lane / compile-cache / transfer accounting for the
        # whole run: says WHICH lane produced the numbers above (a
        # pallas-demoted round is not comparable to a pallas round)
        "kernel_telemetry": kernels.telemetry_snapshot(),
    }
    print(json.dumps(result))
    return 1 if _LANE_FAILURES else 0


if __name__ == "__main__":
    sys.exit(main())
