"""One span tree per read (pilosa_tpu/obs/tracing.py, server/batcher.py).

(a) The thread hop: a read through the batcher leaves a request trace
    whose ``batcher.dispatch`` span names the flight's own trace, and
    ``/debug/traces?id=`` renders the flight's spans under it.
(b) The span table: every registered name in ``/debug/vars`` at zero, self
    time, items, no unregistered name, no literal outside the table.
(c) The shared clock: inside a profiler session the host plane of the
    ``.xplane.pb`` holds the program's spans, nested as the program nested
    them, in the test's own process.
(d) Cost as a count: a span with no store, no profile and no session takes
    no lock and mints one id per span and one more per trace.
(e) The thread's CPU clock beside the wall clock: ``cpu_seconds``,
    ``self_cpu_seconds`` and ``device_wait_seconds`` of a row, read by every
    span under the dispatcher's two and by ``kernels.pull``, and booked into a
    handler's root from the pair the handler reads; ``cpuMs`` in a kept trace.

Every test that starts a server, a batcher thread or a profiler session
has a time limit of its own (``time_limit``).
"""

from __future__ import annotations

import ast
import contextvars
import functools
import glob
import json
import os
import re
import signal
import threading
import time
import urllib.request

import pytest

from pilosa_tpu import pql
from pilosa_tpu.obs import qprofile, tracestore, tracing
from pilosa_tpu.server.batcher import QueryBatcher
from pilosa_tpu.testing.cluster import InProcessCluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def time_limit(seconds: float):
    """Fail the test, not the run, when it takes longer (SIGALRM; pytest and
    its xdist workers run tests on the main thread)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if threading.current_thread() is not threading.main_thread():
                return fn(*args, **kwargs)

            def on_alarm(signum, frame):
                raise TimeoutError(f"{fn.__name__} took over {seconds}s")

            old = signal.signal(signal.SIGALRM, on_alarm)
            signal.setitimer(signal.ITIMER_REAL, seconds)
            try:
                return fn(*args, **kwargs)
            finally:
                signal.setitimer(signal.ITIMER_REAL, 0)
                signal.signal(signal.SIGALRM, old)

        return wrapper

    return deco


def _post(uri, path, body):
    req = urllib.request.Request(uri + path, data=body.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=20) as resp:
        return json.loads(resp.read())


def _get(uri, path):
    with urllib.request.urlopen(uri + path, timeout=20) as resp:
        return json.loads(resp.read())


def _wait_for(fn, seconds=5.0):
    deadline = time.monotonic() + seconds
    while True:
        out = fn()
        if out or time.monotonic() > deadline:
            return out
        time.sleep(0.02)


@pytest.fixture
def node():
    # every trace kept, no result cache: each read rides a flight
    with InProcessCluster(1, trace_baseline_n=1, rescache_entries=0) as c:
        c.create_index("sp")
        c.create_field("sp", "f")
        c.import_bits("sp", "f", [(r, col) for r in (1, 2, 3) for col in range(r, 90, r)])
        yield c.nodes[0]


def _children(spans, parent):
    return [s for s in spans if s["parentId"] == parent["spanId"]]


def _descendants(spans, parent):
    out = []
    for c in _children(spans, parent):
        out += [c] + _descendants(spans, c)
    return out


# -- (a) the hop ---------------------------------------------------------------


@time_limit(90)
def test_batched_read_is_one_tree_over_http(node):
    before = _get(node.uri, "/debug/vars")["spans"]  # the table is the process's
    out = _post(node.uri, "/index/sp/query",
                "Count(Intersect(Row(f=1), Row(f=2))) Count(Intersect(Row(f=2), Row(f=3)))")
    assert out["results"] == [len(set(range(1, 90)) & set(range(2, 90, 2))),
                              len(set(range(2, 90, 2)) & set(range(3, 90, 3)))]
    summaries = _wait_for(
        lambda: [t for t in _get(node.uri, "/debug/traces")["traces"] if t["root"] == "http.query"])
    assert len(summaries) == 1
    # a flight is not listed as a trace of its own (every request is kept here)
    assert {t["root"] for t in _get(node.uri, "/debug/traces")["traces"]} <= {
        "http.query", "http.debug_vars", "http.debug_traces"}
    d = _get(node.uri, f"/debug/traces?id={summaries[0]['traceId']}")
    spans = d["spans"]
    root = next(s for s in spans if s["name"] == "http.query")
    under_root = {s["name"] for s in _children(spans, root)}
    assert {"http.decode", "api.parse", "qos.admit", "rescache.probe", "batcher.queueWait",
            "batcher.dispatch", "http.encode"} <= under_root
    dispatch = next(s for s in spans if s["name"] == "batcher.dispatch")
    flight_id = dispatch["tags"]["flight"]
    assert flight_id != d["traceId"]
    under_dispatch = {s["name"] for s in _children(spans, dispatch)}
    assert under_dispatch == {"batcher.collect", "batcher.flight"}
    flight = next(s for s in spans if s["name"] == "batcher.flight")
    assert flight["traceId"] == flight_id and flight["tags"]["n"] == 1
    below = {s["name"] for s in _descendants(spans, flight)}
    assert {"executor.ExecuteBatch", "planner.plan", "executor.batchPairCount",
            "kernels.enqueue", "kernels.pull", "executor.demux"} <= below
    assert all(s["traceId"] == flight_id for s in _descendants(spans, flight))
    # nothing of the served path was built and dropped: the table's counts
    # are the store's
    table = _get(node.uri, "/debug/vars")["spans"]

    def moved(block, what, key="count"):
        return table[block][what][key] - before[block][what][key]

    assert moved("executor", "ExecuteBatch") == 1
    assert moved("batcher", "flight") == 1 and moved("batcher", "flight", "items") == 1
    assert _get(node.uri, "/debug/traces")["store"]["stats"]["flights"] == 1
    # the stack the gram ran over is in the budget under its owner kind
    dev = _get(node.uri, "/debug/vars")["device"]
    assert dev["byOwner"].get("stack_set", 0) > 0
    assert sum(dev["byOwner"].values()) == dev["usedBytes"]
    assert moved("executor", "stackBuild") == 1 and moved("kernels", "h2d") >= 1


@time_limit(90)
def test_two_members_of_one_flight_link_the_same_flight(node):
    batcher = node.api.batcher
    inner = batcher.executor.execute_batch
    gate, entered = threading.Event(), threading.Event()

    def gated(index, queries):
        entered.set()
        gate.wait(20)
        return inner(index, queries)

    batcher.executor.execute_batch = gated
    try:
        results = {}

        def read(tag, q):
            results[tag] = _post(node.uri, f"/index/sp/query?tag={tag}", q)

        first = threading.Thread(target=read, args=("warm", "Count(Row(f=3))"))
        first.start()
        assert entered.wait(20)  # the dispatcher is parked inside the first flight
        pair = [threading.Thread(target=read, args=(f"m{i}", f"Count(Row(f={i}))")) for i in (1, 2)]
        for t in pair:
            t.start()
        assert _wait_for(lambda: batcher.snapshot()["depth"] == 3)
        gate.set()
        for t in [first] + pair:
            t.join(20)
    finally:
        batcher.executor.execute_batch = inner
    assert results["m1"]["results"] == [len(range(1, 90))]
    assert results["m2"]["results"] == [len(range(2, 90, 2))]

    def flights():
        out = {}
        for t in _get(node.uri, "/debug/traces")["traces"]:
            if t["root"] != "http.query":
                continue
            d = _get(node.uri, f"/debug/traces?id={t['traceId']}")
            disp = [s for s in d["spans"] if s["name"] == "batcher.dispatch"]
            out[d["traceId"]] = (disp[0]["tags"]["flight"], d)
        return out if len(out) == 3 else None

    by_trace = _wait_for(flights)
    assert by_trace and len(by_trace) == 3
    linked = [f for f, _ in by_trace.values()]
    # two of the three requests rode one flight, the parked one its own
    assert sorted(linked.count(f) for f in set(linked)) == [1, 2]
    shared = next(f for f in set(linked) if linked.count(f) == 2)
    for f, d in by_trace.values():
        if f != shared:
            continue
        flight = next(s for s in d["spans"] if s["name"] == "batcher.flight")
        assert flight["traceId"] == shared and flight["tags"]["n"] == 2
        wait = next(s for s in d["spans"] if s["name"] == "batcher.queueWait")
        assert wait["tags"]["batchSize"] == 2


@time_limit(60)
def test_flight_spans_reach_the_members_store_without_http():
    """The hop by itself: ``QueryBatcher`` with a stub executor that opens a
    span; the submitter's store receives the flight's trace."""

    class Stub:
        def execute_batch(self, index, queries):
            with tracing.start_span("executor.ExecuteBatch").set_tag("queries", len(queries)):
                return [[f"r:{q}"] for q, _ in queries]

    store = tracestore.TraceStore(baseline_n=1)
    b = QueryBatcher(Stub(), window=0.001)
    try:
        prof = qprofile.QueryProfile("i", "q")
        with tracestore.activate(store), qprofile.activate(prof):
            with tracing.start_span("http.query") as root:
                assert b.submit("i", pql.parse("Count(Row(f=1))")) == ["r:Count(Row(f=1))"]
    finally:
        b.close()
    d = store.detail(f"{root.context.trace_id:032x}")
    names = [s["name"] for s in d["spans"]]
    assert names.index("batcher.dispatch") < names.index("batcher.flight") < names.index("http.query")
    dispatch = next(s for s in d["spans"] if s["name"] == "batcher.dispatch")
    batch = next(s for s in d["spans"] if s["name"] == "executor.ExecuteBatch")
    flight = next(s for s in d["spans"] if s["name"] == "batcher.flight")
    assert batch["parentId"] == flight["spanId"] and flight["parentId"] == dispatch["spanId"]
    assert store.snapshot()["stats"]["flights"] == 1 and store.snapshot()["stats"]["completed"] == 1
    # ?profile=true renders the same two spans (tests/test_batcher.py holds the tags)
    tree = {c.name: c for c in prof.root.children[0].children}
    assert tree["batcher.queueWait"].tags["batchSize"] == 1
    assert tree["batcher.dispatch"].tags["flight"] == flight["traceId"]
    assert tree["batcher.dispatch"].duration_ms == pytest.approx(dispatch["durationMs"], abs=0.01)


# -- (b) the table --------------------------------------------------------------


_ROW_KEYS = {"count", "seconds", "self_seconds", "items", "cpu_seconds", "self_cpu_seconds",
             "device_wait_seconds"}


def _leaves(block, path=()):
    for k, v in block.items():
        if isinstance(v, dict) and set(v) != _ROW_KEYS:
            yield from _leaves(v, path + (k,))
        else:
            yield path + (k,), v


@time_limit(60)
def test_every_registered_name_is_served_at_zero_before_any_request():
    code = (
        "import json, sys\n"
        "from pilosa_tpu.server.node import NodeServer\n"
        "from pilosa_tpu.obs import tracing\n"
        "import urllib.request\n"
        "n = NodeServer(host='127.0.0.1', port=0)\n"
        "n.start()\n"
        "try:\n"
        "    v = json.loads(urllib.request.urlopen(n.uri + '/debug/vars', timeout=20).read())\n"
        "finally:\n"
        "    n.stop()\n"
        "print(json.dumps({'spans': v['spans'], 'names': [r[0] for r in tracing.registered()],\n"
        "                  'device': v['device']}))\n"
    )
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=50)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    served = {".".join(path): row for path, row in _leaves(out["spans"])}
    assert set(served) == set(out["names"])
    for name in ("http.query", "http.decode", "api.parse", "http.encode", "qos.admit", "rescache.probe",
                 "batcher.queueWait", "batcher.dispatch", "batcher.collect", "batcher.flight",
                 "planner.plan", "executor.ExecuteBatch", "executor.batchBSI", "executor.stackBuild",
                 "executor.bsiSplit", "executor.demux", "executor.executeTopN", "kernels.h2d",
                 "kernels.enqueue", "kernels.pull", "dist.fanout", "field.Import", "http.debug_vars"):
        assert name in served, name
    # the request that read the table is the only one that ran
    busy = {n for n, row in served.items() if row["count"]}
    assert busy <= {"http.debug_vars"} and all(
        row == {"count": 0, "seconds": 0.0, "self_seconds": 0.0, "items": 0, "cpu_seconds": 0.0,
                "self_cpu_seconds": 0.0, "device_wait_seconds": 0.0}
        for n, row in served.items() if n not in busy)
    # device bytes by owner, and the backend's own figure beside them
    dev = out["device"]
    assert dev["byOwner"] == {} and dev["platform"] == "cpu" and dev["bytesInUse"] is None


def test_self_seconds_items_and_child_time_on_a_hand_built_tree():
    def row(name):
        block, what = name.split(".")
        return tracing.spans_snapshot()[block][what]

    before = {n: row(n) for n in ("test.parent", "test.child", "test.op")}
    with tracing.start_span("test.parent") as parent:
        parent.set_tag("n", 5)
        with tracing.start_span("test.child") as c1:
            time.sleep(0.01)
        with tracing.start_span("test.child") as c2:
            c2.set_tag("n", 2)
            with tracing.start_span("test.op") as leaf:
                time.sleep(0.005)
    # built after the fact, under the parent that is active
    with tracing.start_span("test.parent") as late:
        t0 = late.start_ns
        time.sleep(0.004)
        rec = tracing.record_span("test.op", t0 + 1_000_000, t0 + 3_000_000, {"n": 7})
    assert rec.duration == pytest.approx(0.002) and rec.parent_id == late.context.span_id
    assert parent.child_ns == round((c1.duration + c2.duration) * 1e9)
    assert c2.child_ns == round(leaf.duration * 1e9) and c1.child_ns == 0

    def moved(name, key):
        return row(name)[key] - before[name][key]

    assert moved("test.parent", "count") == 2 and moved("test.child", "count") == 2
    assert moved("test.parent", "items") == 5 and moved("test.child", "items") == 2
    assert moved("test.op", "items") == 7
    assert moved("test.parent", "seconds") == pytest.approx(parent.duration + late.duration, abs=1e-6)
    assert moved("test.parent", "self_seconds") == pytest.approx(
        parent.duration - c1.duration - c2.duration + late.duration - rec.duration, abs=1e-6)
    assert moved("test.child", "self_seconds") == pytest.approx(
        c1.duration + c2.duration - leaf.duration, abs=1e-6)
    assert moved("test.op", "self_seconds") == pytest.approx(leaf.duration + rec.duration, abs=1e-6)


@pytest.mark.parametrize("name", ["executor.notThere", "nosegment", "three.part.name", "http.", ".x"])
def test_a_name_outside_the_table_fails(name):
    with pytest.raises(ValueError):
        tracing.start_span(name)
    if len([p for p in name.split(".") if p]) != 2 or name.count(".") != 1:
        with pytest.raises(ValueError):
            tracing.register(name, "tests")


def test_no_name_is_both_a_leaf_and_a_prefix():
    names = [n for n, _, _ in tracing.registered()]
    assert len(names) == len(set(names))
    assert all(re.fullmatch(r"[A-Za-z_][\w]*\.[A-Za-z_][\w]*", n) for n in names), names
    for n in names:
        assert not any(o.startswith(n + ".") for o in names)
    # the two families are registered from their owners' lists
    from pilosa_tpu.server import http

    assert {f"executor.execute{c}" for c in pql.CALL_NAMES} <= set(names)
    assert {f"http.{r}" for _, _, r in http._ROUTES} <= set(names)
    layers = {layer for _, layer, _ in tracing.registered()}
    assert {"listener", "QoS / batcher", "planner / rescache", "executor lanes", "kernels"} <= layers


def _span_literals():
    """(file, line, literal or None) of every ``start_span`` / ``record_span``
    call under pilosa_tpu/."""
    for path in glob.glob(os.path.join(REPO, "pilosa_tpu", "**", "*.py"), recursive=True):
        tree = ast.parse(open(path).read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("start_span", "record_span") and node.args):
                continue
            if isinstance(node.func.value, ast.Name) and node.func.value.id in ("_global", "self"):
                continue  # tracing.py's own forwarding
            arg = node.args[0]
            yield path, node.lineno, arg.value if isinstance(arg, ast.Constant) else None


def test_every_span_literal_in_the_program_is_registered():
    names = {n for n, _, _ in tracing.registered()}
    sites = list(_span_literals())
    assert len(sites) > 40
    computed = [(p, ln) for p, ln, lit in sites if lit is None]
    # the two families compute their names, from registered lists
    assert {os.path.basename(p) for p, _ in computed} <= {"executor.py", "dist.py", "http.py", "tracing.py"}
    bad = [(p, ln, lit) for p, ln, lit in sites if lit is not None and lit not in names]
    assert not bad, bad


def test_the_documents_span_table_is_the_programs():
    from pilosa_tpu.server import http  # noqa: F401  (registers its routes)

    doc = open(os.path.join(REPO, "docs", "observability.md")).read()
    program = [r for r in tracing.registered() if r[1] != "tests"]
    assert tracing.table_markdown(program) in doc


# -- (c) the shared clock ---------------------------------------------------------


def _host_events(xplane: str) -> dict[str, list[tuple[str, int, int]]]:
    """{thread line: [(name, start_ns, end_ns)]} of the host plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane)
    out = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)) for e in line.events]
            if evs:
                out[f"{i}:{line.name}"] = evs  # thread names repeat
    return out


@time_limit(120)
def test_profiler_session_holds_the_programs_spans_nested(tmp_path):
    import jax

    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.exec.executor import Executor

    holder = Holder()
    idx = holder.create_index("pc")
    idx.create_field("f")
    ex = Executor(holder, rescache_entries=0)
    ex.execute("pc", " ".join(f"Set({c}, f={r})" for r in (1, 2, 3) for c in range(r, 40, r)))
    q = "Count(Intersect(Row(f=1), Row(f=2))) Count(Intersect(Row(f=2), Row(f=3)))"
    b = QueryBatcher(ex, window=0.001)
    try:
        assert b.submit("pc", pql.parse(q)) == [19, 6]  # warm: compiles stay out of the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # as benchmark/serve_child.py starts its session
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            # new rows, so that the gram is computed again
            ex.execute("pc", "Set(41, f=1) Set(41, f=2)")
            assert b.submit("pc", pql.parse(q)) == [20, 6]
        finally:
            jax.profiler.stop_trace()
    finally:
        b.close()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert len(files) == 1
    lines = _host_events(files[0])
    ours = {ln: [e for e in evs if e[0].split(".")[0] in ("batcher", "executor", "kernels", "planner", "stacks")]
            for ln, evs in lines.items()}
    dispatcher = [ln for ln, evs in ours.items() if any(e[0] == "batcher.flight" for e in evs)]
    assert len(dispatcher) == 1, {ln: sorted({e[0] for e in evs}) for ln, evs in ours.items() if evs}
    evs = ours[dispatcher[0]]
    names = {e[0] for e in evs}
    assert {"batcher.collect", "batcher.flight", "executor.ExecuteBatch", "executor.batchPairCount",
            "stacks.refresh", "kernels.enqueue", "kernels.pull"} <= names

    def inside(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    flight = next(e for e in evs if e[0] == "batcher.flight")
    batch = next(e for e in evs if e[0] == "executor.ExecuteBatch")
    lane = next(e for e in evs if e[0] == "executor.batchPairCount")
    # the write made the lane's stack stale: it is refreshed where the lane takes it
    refresh = next(e for e in evs if e[0] == "stacks.refresh")
    assert inside(batch, flight) and inside(lane, batch) and inside(refresh, batch)
    for leaf in ("kernels.enqueue", "kernels.pull"):
        assert all(inside(e, lane) or inside(e, refresh) for e in evs if e[0] == leaf), leaf
    collect = next(e for e in evs if e[0] == "batcher.collect")
    assert collect[2] <= flight[1]  # siblings: the window closes before the flight starts
    # the member-side spans were built after the fact: the table has them, the trace has not
    assert not any(e[0] in ("batcher.queueWait", "batcher.dispatch") for es in lines.values() for e in es)


# -- (d) cost as a count ------------------------------------------------------------


class _CountingRandom:
    def __init__(self, inner):
        self.inner, self.calls = inner, []

    def getrandbits(self, k):
        self.calls.append(k)
        return self.inner.getrandbits(k)


@pytest.mark.parametrize("depth,want", [(1, [128, 64]), (3, [128, 64, 64, 64])])
def test_a_span_nobody_reads_takes_no_lock_and_mints_one_id(monkeypatch, depth, want):
    """No store, no profile, no profiler session: what a span does is count."""
    assert tracestore.current() is None and not qprofile.profiling()
    assert not hasattr(tracing, "_id_lock")
    rng = _CountingRandom(tracing._id_rng)
    monkeypatch.setattr(tracing, "_id_rng", rng)
    clock_reads = []
    real_ns = time.monotonic_ns
    monkeypatch.setattr(tracing.time, "monotonic_ns", lambda: clock_reads.append(1) or real_ns())
    locks = []
    real_lock, real_rlock = threading.Lock, threading.RLock
    monkeypatch.setattr(threading, "Lock", lambda *a: locks.append("Lock") or real_lock(*a))
    monkeypatch.setattr(threading, "RLock", lambda *a: locks.append("RLock") or real_rlock(*a))
    profile_calls = []
    monkeypatch.setattr(qprofile, "span_enter", lambda name: profile_calls.append(name))
    monkeypatch.setattr(qprofile, "span_exit", lambda *a: profile_calls.append("exit"))

    spans = []

    def nest(k):
        with tracing.start_span("test.op") as s:
            spans.append(s)
            if k > 1:
                nest(k - 1)

    cpu_reads = []
    real_cpu = time.thread_time_ns
    monkeypatch.setattr(tracing.time, "thread_time_ns", lambda: cpu_reads.append(1) or real_cpu())

    nest(depth)
    assert rng.calls == want  # one trace id for the root, one span id a span
    assert len(clock_reads) == 2 * depth  # one clock read at each end
    assert cpu_reads == []  # the thread's CPU clock is a system call: only where the table says
    assert locks == [] and profile_calls == []
    assert all(s.duration is not None and s.tags == {} for s in spans)
    assert len({s.context.trace_id for s in spans}) == 1


# -- (e) the thread's CPU clock ------------------------------------------------------


def _burn(ms: float) -> None:
    """Run this thread until its CPU clock has moved by ``ms`` (a clock that
    ticks moves by a whole tick)."""
    t0 = time.thread_time_ns()
    while time.thread_time_ns() - t0 < ms * 1e6:
        pass


def _on_another_thread(fn) -> None:
    """Under a copy of this context, as the fan-out pools run their legs."""
    t = threading.Thread(target=contextvars.copy_context().run, args=(fn,))
    t.start()
    t.join(20)


def _table_row(name):
    block, what = name.split(".")
    return tracing.spans_snapshot()[block][what]


@time_limit(60)
def test_cpu_and_device_wait_on_a_hand_built_tree_under_the_dispatchers_root():
    names = ("batcher.flight", "test.parent", "test.child", "test.other", "test.op", "kernels.pull")
    before = {n: _table_row(n) for n in names}
    legs = []

    def leg():  # opened and finished on a worker, under the parent: a fan-out leg
        with tracing.start_span("test.child") as s:
            _burn(40)
        legs.append(s)

    with tracing.start_span("batcher.flight") as flight:  # every span below reads the CPU clock
        with tracing.start_span("test.parent") as parent:
            _burn(30)
            with tracing.start_span("kernels.pull") as pull:  # the row that waits for the device
                time.sleep(0.03)
            _on_another_thread(leg)
            stray = tracing.start_span("test.other")  # opened here, finished by another thread
            _burn(5)
            _on_another_thread(stray.finish)
            rec = tracing.record_span("test.op", parent.start_ns, parent.start_ns + 2_000_000)
    (leg_span,) = legs
    ns = lambda span: round(span.duration * 1e9)  # noqa: E731
    # the pull's thread ran next to nothing of its 30 ms: the rest is its device wait
    assert pull.wait_ns == ns(pull) - pull.cpu_ns >= 15_000_000
    # the parent ran its own 35 ms and the pull's; the worker's 40 ms are not in its clock
    assert 35_000_000 <= parent.cpu_ns < 65_000_000 and leg_span.cpu_ns >= 40_000_000
    assert parent.child_cpu_ns == pull.cpu_ns and parent.child_ns >= ns(pull) + ns(leg_span)
    # waits by construction book no CPU, and carry nothing up
    assert stray.duration is not None and stray.cpu_ns == 0 and rec.cpu_ns == 0
    assert parent.wait_ns == flight.wait_ns == pull.wait_ns and leg_span.wait_ns == 0
    assert flight.child_cpu_ns == parent.cpu_ns <= flight.cpu_ns
    assert flight.cpu_ns + flight.wait_ns <= ns(flight)  # what is left is neither

    def moved(name, key):
        return _table_row(name)[key] - before[name][key]

    for name, span in (("batcher.flight", flight), ("test.parent", parent), ("test.child", leg_span),
                       ("kernels.pull", pull)):
        assert moved(name, "cpu_seconds") == pytest.approx(span.cpu_ns * 1e-9, abs=1e-9), name
        assert moved(name, "self_cpu_seconds") == pytest.approx(
            (span.cpu_ns - span.child_cpu_ns) * 1e-9, abs=1e-9), name
    for name in ("batcher.flight", "test.parent", "kernels.pull"):
        assert moved(name, "device_wait_seconds") == pytest.approx(pull.wait_ns * 1e-9, abs=1e-9), name
    for name in ("test.other", "test.op"):
        assert moved(name, "count") == 1 and moved(name, "seconds") > 0
    for name in ("test.child", "test.other", "test.op"):
        assert moved(name, "device_wait_seconds") == 0, name
    for name in ("test.other", "test.op"):
        assert moved(name, "cpu_seconds") == moved(name, "self_cpu_seconds") == 0, name
    # seconds = cpu + device wait + what is left, for any row
    for name in names:
        assert moved(name, "cpu_seconds") + moved(name, "device_wait_seconds") <= moved(name, "seconds") + 1e-9


@time_limit(60)
def test_elsewhere_only_the_pull_reads_the_cpu_clock_and_a_handler_books_its_own():
    """Where the clock is a system call of microseconds a span cannot read it by
    default: outside the dispatcher's trees only a pull does, its device wait is
    carried up to the root, and the root is given the CPU its handler read."""
    before = {n: _table_row(n) for n in ("test.root", "test.child")}
    with tracing.start_span("test.root") as root:
        with tracing.start_span("test.child") as child:
            _burn(10)
            with tracing.start_span("kernels.pull") as pull:
                time.sleep(0.02)
        with tracing.start_span("batcher.collect") as tree:  # the mark is the row's, wherever it opens
            with tracing.start_span("test.op") as below:
                _burn(10)
    assert root.cpu_ns == 0 and child.cpu_ns == 0 and child.child_cpu_ns == pull.cpu_ns
    assert below.cpu_ns >= 10_000_000 and tree.child_cpu_ns == below.cpu_ns
    assert pull.wait_ns >= 10_000_000 and child.wait_ns == root.wait_ns == pull.wait_ns
    assert root.child_cpu_ns == tree.cpu_ns  # what the children that read the clock ran
    # server/http.py reads the thread's clock around a request anyway, and books it
    tracing.book_cpu(root, 25_000_000)
    assert root.cpu_ns == 25_000_000
    moved = {k: _table_row("test.root")[k] - before["test.root"][k]
             for k in ("cpu_seconds", "self_cpu_seconds", "device_wait_seconds")}
    assert moved["cpu_seconds"] == pytest.approx(0.025, abs=1e-9)
    assert moved["self_cpu_seconds"] == pytest.approx((25_000_000 - tree.cpu_ns) * 1e-9, abs=1e-9)
    assert moved["device_wait_seconds"] == pytest.approx(pull.wait_ns * 1e-9, abs=1e-9)
    assert _table_row("test.child")["cpu_seconds"] == before["test.child"]["cpu_seconds"]
    marks = {name: metric for name, _, metric in tracing.registered()}
    assert marks["kernels.pull"].endswith("; " + tracing.DEVICE_WAIT)
    assert marks["batcher.flight"].endswith("; " + tracing.CPU_TREE)
    assert marks["batcher.collect"].endswith("; " + tracing.CPU_TREE)
    leaves = {n for n, m in marks.items() if m.endswith("; " + tracing.CPU_LEAF)}
    assert leaves == {"executor.demux", "kernels.h2d", "kernels.enqueue"}
    assert sum(";" in m for m in marks.values()) == 6


@time_limit(90)
def test_a_kept_trace_renders_cpu_ms_beside_duration_ms_over_http(node):
    _post(node.uri, "/index/sp/query", "Count(Intersect(Row(f=1), Row(f=3)))")
    summaries = _wait_for(
        lambda: [t for t in _get(node.uri, "/debug/traces")["traces"] if t["root"] == "http.query"])
    # the handler books the request's CPU into its root once the response is out
    spans = _wait_for(lambda: [
        d for d in [_get(node.uri, f"/debug/traces?id={summaries[0]['traceId']}")["spans"]]
        if next(s for s in d if s["name"] == "http.query")["cpuMs"] > 0][:1])[0]
    assert all(isinstance(s["cpuMs"], float) and s["cpuMs"] >= 0 for s in spans)
    by_name = {s["name"]: s for s in spans}
    # the two waits of a member were built after the fact: no CPU
    assert by_name["batcher.queueWait"]["cpuMs"] == by_name["batcher.dispatch"]["cpuMs"] == 0
    # the handler ran, and waited for its flight: less CPU than wall, and (the clock
    # may tick) not none over both trees
    root, flight = by_name["http.query"], by_name["batcher.flight"]
    assert root["cpuMs"] <= root["durationMs"] + 10 and flight["cpuMs"] <= flight["durationMs"] + 10
    # a handler's children read no CPU clock of their own; the flight's tree does
    assert by_name["http.decode"]["cpuMs"] == 0
    table = _get(node.uri, "/debug/vars")["spans"]
    assert table["http"]["query"]["cpu_seconds"] > 0
    assert table["batcher"]["flight"]["cpu_seconds"] > 0
    assert table["kernels"]["pull"]["device_wait_seconds"] > 0
    assert table["batcher"]["flight"]["device_wait_seconds"] > 0
