"""SLO plane (pilosa_tpu/obs/slo.py + HTTP wiring): query
classification into op classes, ring-window availability accounting,
bucketed latency quantiles, multi-window multi-burn-rate alerting, and
the live /debug/slo + pilosa_slo_* + /debug/vars exposition — including
the error-attribution contract (deadline 504s burn budget, 4xx client
mistakes do not) and the translate-path telemetry riding along."""

import json
import math
import random
import sys
import threading
import urllib.error
import urllib.request

import pytest

from pilosa_tpu import pql
from pilosa_tpu.obs import slo
from pilosa_tpu.obs.slo import (
    LATENCY_BOUNDS,
    BurnRule,
    Objective,
    SLOTracker,
    _bucket_of,
    _N_BUCKETS,
    _quantile,
    _Ring,
    classify_query,
    objectives_from_dict,
)
from pilosa_tpu.testing.cluster import InProcessCluster

# Burn rules small enough that a test's observations all land inside
# every window (observe() stamps wall-now; only _Ring takes a fake clock).
FAST_RULES = (
    BurnRule("fast", long=60.0, short=10.0, factor=14.4),
    BurnRule("slow", long=300.0, short=60.0, factor=1.0),
)


def _get(uri, path):
    return json.load(urllib.request.urlopen(uri + path, timeout=10))


def _get_text(uri, path):
    with urllib.request.urlopen(uri + path, timeout=10) as resp:
        return resp.read().decode()


def _post(uri, path, body, ctype="text/plain"):
    req = urllib.request.Request(
        uri + path, data=body.encode(), method="POST",
        headers={"Content-Type": ctype},
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, json.loads(resp.read())


# -- classification -----------------------------------------------------------


@pytest.mark.parametrize(
    "text,want",
    [
        ("Count(Row(f=1))", slo.OP_READ_COUNT),
        ("Count(Intersect(Row(f=1), Row(f=2)))", slo.OP_READ_COUNT),
        ("TopN(f, n=5)", slo.OP_READ_TOPN),
        ("Row(f=1)", slo.OP_READ_ROW),
        ("GroupBy(Rows(f))", slo.OP_READ_GROUPBY),
        ("Union(Row(f=1), Row(f=2))", slo.OP_READ_OTHER),
        ("Set(1, f=1)", slo.OP_WRITE),
        ("Clear(1, f=1)", slo.OP_WRITE),
    ],
)
def test_classify_query(text, want):
    assert classify_query(pql.parse(text)) == want


def test_any_write_call_makes_the_request_a_write():
    q = pql.parse("Row(f=1) Set(2, f=2)")
    assert classify_query(q) == slo.OP_WRITE


def test_note_take_class_round_trip_and_reset():
    # drain anything a prior in-thread direct api.query call noted (the
    # HTTP layer's finally is what consumes it in production)
    slo.take_class()
    assert slo.take_class() is None
    slo.note_class(slo.OP_IMPORT)
    assert slo.take_class() == slo.OP_IMPORT
    # taking clears: the next request on this thread starts clean
    assert slo.take_class() is None


# -- buckets and quantiles ----------------------------------------------------


def test_latency_bounds_are_strictly_increasing_and_sub_ms():
    assert list(LATENCY_BOUNDS) == sorted(LATENCY_BOUNDS)
    assert len(set(LATENCY_BOUNDS)) == len(LATENCY_BOUNDS)
    # resolution below 1 ms: the 0.07-0.16 ms serving floor must not
    # collapse into one bucket
    assert sum(1 for b in LATENCY_BOUNDS if b < 0.001) >= 5


def test_bucket_of_maps_bounds_and_overflow():
    assert _bucket_of(0.0) == 0
    assert _bucket_of(LATENCY_BOUNDS[0]) == 0
    assert _bucket_of(LATENCY_BOUNDS[-1]) == len(LATENCY_BOUNDS) - 1
    assert _bucket_of(LATENCY_BOUNDS[-1] + 1.0) == _N_BUCKETS - 1


def test_quantile_empty_and_overflow_floor():
    assert _quantile([0] * _N_BUCKETS, 0.5) is None
    only_overflow = [0] * _N_BUCKETS
    only_overflow[-1] = 10
    # overflow reports the top bound (a floor, not an estimate)
    assert _quantile(only_overflow, 0.5) == LATENCY_BOUNDS[-1]


def test_quantile_interpolates_within_bucket():
    counts = [0] * _N_BUCKETS
    counts[5] = 100
    lo, hi = LATENCY_BOUNDS[4], LATENCY_BOUNDS[5]
    q50 = _quantile(counts, 0.5)
    assert lo < q50 <= hi
    assert _quantile(counts, 0.01) < q50 < _quantile(counts, 0.99)


# -- ring windows -------------------------------------------------------------


def test_ring_expires_observations_outside_window():
    r = _Ring(window=60.0, slot_seconds=5.0)
    r.observe(0.0, error=True, bucket=3)
    assert r.sum_window(30.0, 60.0) == (1, 1)
    # 2 minutes later the slot is outside every 60 s window
    assert r.sum_window(120.0, 60.0) == (0, 0)
    r.observe(118.0, error=False, bucket=3)
    assert r.sum_window(120.0, 60.0) == (1, 0)
    assert r.merged_buckets(120.0, 60.0)[3] == 1


def test_ring_slot_reuse_resets_stale_counts():
    r = _Ring(window=10.0, slot_seconds=1.0)
    r.observe(0.5, error=True, bucket=0)
    n = len(r.buckets)
    # land in the SAME physical slot one full ring revolution later:
    # stale totals must not leak into the new slice
    r.observe(0.5 + n, error=False, bucket=0)
    total, errors = r.sum_window(0.5 + n, 1.0)
    assert (total, errors) == (1, 0)


class _WalkRing:
    """The ring as it was before the arrays: a list of ``[idx, total,
    errors, counts]`` walked slot by slot.  Kept here as the plain
    twin that the array ring is held to."""

    def __init__(self, window, slot_seconds):
        n = max(2, int(math.ceil(window / slot_seconds)) + 1)
        self.slot_seconds = slot_seconds
        self.slots = [[-1, 0, 0, None] for _ in range(n)]

    def observe(self, now, error, bucket):
        idx = int(now / self.slot_seconds)
        slot = self.slots[idx % len(self.slots)]
        if slot[0] != idx:
            slot[:] = [idx, 0, 0, None]
        slot[1] += 1
        if error:
            slot[2] += 1
        if bucket is not None:
            if slot[3] is None:
                slot[3] = [0] * _N_BUCKETS
            slot[3][bucket] += 1

    def _walk(self, now, window):
        lo = int((now - window) / self.slot_seconds) + 1
        hi = int(now / self.slot_seconds)
        n = len(self.slots)
        if hi - lo + 1 < n:
            for idx in range(lo, hi + 1):
                slot = self.slots[idx % n]
                if slot[0] == idx:
                    yield slot
        else:
            for slot in self.slots:
                if lo <= slot[0] <= hi:
                    yield slot

    def sum_window(self, now, window):
        total = errors = 0
        for slot in self._walk(now, window):
            total += slot[1]
            errors += slot[2]
        return total, errors

    def merged_buckets(self, now, window):
        out = [0] * _N_BUCKETS
        for slot in self._walk(now, window):
            if slot[3] is not None:
                for i, c in enumerate(slot[3]):
                    out[i] += c
        return out


# (ring window, slot seconds, windows asked): the default ring with the
# default rules' four windows and one longer than the ring, and the
# smallest rings, of 2 and 3 slots
_DEFAULT_WINDOWS = sorted(
    {w for r in slo.DEFAULT_BURN_RULES for w in (r.long, r.short)}
)
_TWIN_RINGS = {
    "default-3d-5s": (259200.0, 5.0, _DEFAULT_WINDOWS + [400000.0]),
    "hour-1s": (3600.0, 1.0, [1.0, 60.0, 300.0, 3599.0, 3600.0, 3601.0, 7200.0]),
    "2-slots": (0.004, 0.005, [0.001, 0.004, 0.005, 0.01, 1.0]),
    "3-slots": (0.010, 0.005, [0.004, 0.005, 0.010, 0.015, 1.0]),
}


@pytest.mark.parametrize("start", [0.0, 1234.5, 5e6], ids=["t0", "t1234", "t5e6"])
@pytest.mark.parametrize("shape", sorted(_TWIN_RINGS))
def test_ring_windows_match_a_plain_walk(shape, start):
    """The same random stream into both rings; every window equal at
    every step, through bursts inside one slot, steps of a few slots,
    wrap-around, and idle stretches longer than the ring (``start``
    puts the clock before, inside and far past the first revolution)."""
    window, slot_seconds, asked = _TWIN_RINGS[shape]
    ring, twin = _Ring(window, slot_seconds), _WalkRing(window, slot_seconds)
    n = len(twin.slots)
    assert len(ring.idx) == len(ring.total) == len(ring.errors) == len(ring.buckets) == n
    assert n == {"2-slots": 2, "3-slots": 3}.get(shape, n)
    rng = random.Random(f"{shape}/{start}")
    now = start
    for step in range(400 if n < 10000 else 120):
        now += rng.choice([
            0.0, 0.0, rng.random() * slot_seconds, rng.random() * slot_seconds,
            slot_seconds * rng.randint(1, 4),
            slot_seconds * rng.randint(n // 3, n - 1),  # most of a revolution
            slot_seconds * n,                           # the very same position
            slot_seconds * (n + rng.randint(1, 2 * n)),  # idle past the ring
        ])
        for _ in range(rng.randint(1, 4)):
            bucket = rng.choice([None, rng.randrange(_N_BUCKETS)])
            error = rng.random() < 0.3
            ring.observe(now, error, bucket)
            twin.observe(now, error, bucket)
        at = now + rng.choice([0.0, rng.random() * slot_seconds, slot_seconds * n / 2,
                               -slot_seconds * rng.randint(1, 3)])  # a reader's older clock
        at = max(at, 0.0)
        for w in asked:
            got = ring.sum_window(at, w)
            assert got == twin.sum_window(at, w), (step, at, w)
            assert all(type(v) is int for v in got)
            merged = ring.merged_buckets(at, w)
            assert merged == twin.merged_buckets(at, w), (step, at, w)
            assert all(type(v) is int for v in merged) and len(merged) == _N_BUCKETS
    assert ring.sum_window(now, asked[-1])[0] > 0


# -- tracker ------------------------------------------------------------------


def test_tracker_all_success_is_ok_and_alert_free():
    t = SLOTracker(burn_rules=FAST_RULES, latency_window=60.0)
    for _ in range(50):
        t.observe(slo.OP_READ_COUNT, 0.002)
    c = t.snapshot()["classes"][slo.OP_READ_COUNT]
    assert c["total"] == 50 and c["errors"] == 0
    assert c["windows"]["1m"]["availability"] == 1.0
    assert c["windows"]["1m"]["burnRate"] == 0.0
    assert not any(c["alerts"].values())
    assert c["latencyOk"] is True  # 2 ms << the 50 ms objective
    assert c["ok"] is True
    # quantiles resolve inside the 2.5 ms bucket
    assert 1.0 <= c["latency"]["p50Ms"] <= 2.5


def test_tracker_sustained_errors_fire_both_burn_windows():
    t = SLOTracker(burn_rules=FAST_RULES)
    for i in range(100):
        t.observe(slo.OP_WRITE, 0.001, error=(i % 2 == 0))
    c = t.snapshot()["classes"][slo.OP_WRITE]
    # 50% errors against a 0.1% budget: burn 500x in every window
    assert c["alerts"]["fast"] and c["alerts"]["slow"]
    assert c["ok"] is False
    assert c["windows"]["10s"]["burnRate"] > 14.4
    assert 0 < c["windows"]["10s"]["budgetConsumed"]


def test_tracker_alert_needs_traffic_in_both_windows():
    # a class with an objective but zero traffic must not page
    t = SLOTracker(burn_rules=FAST_RULES)
    c = t.snapshot()["classes"][slo.OP_READ_COUNT]
    assert not any(c["alerts"].values())
    assert c["total"] == 0


def test_tracker_latency_blowout_fails_ok_without_alert():
    t = SLOTracker(burn_rules=FAST_RULES, latency_window=60.0)
    for _ in range(50):
        t.observe(slo.OP_READ_COUNT, 0.4)  # way past the 50 ms p99 target
    c = t.snapshot()["classes"][slo.OP_READ_COUNT]
    assert not any(c["alerts"].values())  # no availability burn
    assert c["latencyOk"] is False
    assert c["ok"] is False


def test_tracker_objectiveless_class_never_verdicts():
    t = SLOTracker(burn_rules=FAST_RULES)
    for i in range(10):
        t.observe(slo.OP_INTERNAL, 0.001, error=(i == 0))
    c = t.snapshot()["classes"][slo.OP_INTERNAL]
    assert c["objective"] is None
    assert c["ok"] is None
    assert "burnRate" not in c["windows"]["10s"]
    assert not any(c["alerts"].values())


def _pressure_from_snapshot(snap):
    """The two lists as ``pressure()`` derived them from ``snapshot()``
    before it had a path of its own."""
    alerts, latency = [], []
    for name, c in snap["classes"].items():
        if c["objective"] is None:
            continue
        for rule, firing in c["alerts"].items():
            if firing:
                alerts.append((name, rule))
        if c["latencyOk"] is False:
            latency.append(name)
    return {"alerts": alerts, "latency": latency}


@pytest.mark.parametrize("seed", range(6))
def test_pressure_equals_snapshot_derivation(seed, monkeypatch):
    """Random traffic with errors and slow requests over base, tenant
    and objectiveless classes, under a clock the test steps: at every
    step ``pressure()`` is what the snapshot says, alerts and latency
    violations both occurring along the way."""
    rng = random.Random(seed)
    clock = [1000.0 * seed]
    monkeypatch.setattr(slo.time, "monotonic", lambda: clock[0])
    objs = objectives_from_dict({
        "read.topn": {"availability": 0.9, "latencyP99Ms": 40.0},
        "tenants": {"v": {"read.count": {"availability": 0.999, "latencyP99Ms": 5.0}}},
    })
    rules = (BurnRule("fast", long=8.0, short=1.0, factor=14.4),
             BurnRule("slow", long=40.0, short=8.0, factor=1.0))
    t = SLOTracker(objectives=objs, burn_rules=rules, slot_seconds=0.5, latency_window=4.0)
    classes = [slo.OP_READ_COUNT, slo.OP_READ_TOPN, slo.OP_WRITE, slo.OP_INTERNAL, slo.OP_IMPORT]
    seen_alert = seen_latency = seen_quiet = False
    for _ in range(120):
        clock[0] += rng.choice([0.0, 0.1, 0.6, 2.0, 9.0, 50.0])
        bad = rng.random() < 0.5  # a stretch of errors and slow answers, or a clean one
        for _ in range(rng.randint(0, 12)):
            t.observe(
                rng.choice(classes),
                rng.choice([0.001, 0.02, 0.3]) if bad else 0.001,
                error=bad and rng.random() < 0.4,
                tenant=rng.choice([None, "v", "w"]),
            )
        snap = t.snapshot()
        got = t.pressure()
        assert got == _pressure_from_snapshot(snap)
        assert json.loads(json.dumps(got)) == {
            "alerts": [list(a) for a in got["alerts"]], "latency": got["latency"]}
        seen_alert |= bool(got["alerts"])
        seen_latency |= bool(got["latency"])
        seen_quiet |= not (got["alerts"] or got["latency"])
    assert seen_alert and seen_latency and seen_quiet
    assert any("@v" in name for name in t.snapshot()["classes"])


def _count_sum_window(monkeypatch, tracker):
    """Every ``_Ring.sum_window`` call from here on, as (class, window)."""
    calls = []
    names = {id(st.ring): name for name, st in tracker._classes.items()}
    real = slo._Ring.sum_window

    def counted(ring, now, window):
        calls.append((names[id(ring)], window))
        return real(ring, now, window)

    monkeypatch.setattr(slo._Ring, "sum_window", counted)
    return calls


def test_snapshot_sums_each_window_once_a_class(monkeypatch):
    t = SLOTracker(burn_rules=FAST_RULES)
    for name in (slo.OP_READ_COUNT, slo.OP_WRITE, slo.OP_INTERNAL):
        t.observe(name, 0.001)
    calls = _count_sum_window(monkeypatch, t)
    t.snapshot()
    # FAST_RULES name three distinct windows (60 s is long and short)
    assert sorted(calls) == sorted(
        (name, w) for name in (slo.OP_READ_COUNT, slo.OP_WRITE, slo.OP_INTERNAL)
        for w in (10.0, 60.0, 300.0))


def test_observers_and_readers_on_many_threads_lose_nothing():
    """Handlers observe while the governor's tick and /debug/slo read:
    every observation is in the lifetime totals and in the longest
    window at the end, whoever interleaved with whom."""
    t = SLOTracker(burn_rules=FAST_RULES, slot_seconds=0.01)
    writers, each, stop = 12, 1500, threading.Event()

    def write(k):
        for i in range(each):
            t.observe(slo.OP_READ_COUNT, 0.001, error=(i % 10 == 0), tenant=f"t{k % 3}")

    def read():
        while not stop.is_set():
            t.pressure()
            t.snapshot()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=read) for _ in range(2)]
        threads = [threading.Thread(target=write, args=(k,)) for k in range(writers)]
        for th in readers + threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        stop.set()
        for th in readers:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads + readers)
    finally:
        stop.set()
        sys.setswitchinterval(old)
    c = t.snapshot()["classes"]
    n = writers * each
    assert (c[slo.OP_READ_COUNT]["total"], c[slo.OP_READ_COUNT]["errors"]) == (n, n // 10)
    assert c[slo.OP_READ_COUNT]["windows"]["5m"]["total"] == n
    assert c[slo.OP_READ_COUNT]["windows"]["5m"]["errors"] == n // 10
    assert sum(c[f"read.count@t{k}"]["windows"]["5m"]["total"] for k in range(3)) == n
    assert c[slo.OP_READ_COUNT]["latency"]["count"] == n


def test_tracker_prometheus_text_series():
    t = SLOTracker(burn_rules=FAST_RULES)
    t.observe(slo.OP_READ_COUNT, 0.003)
    t.observe(slo.OP_READ_COUNT, 0.003, error=True)
    text = t.prometheus_text()
    assert 'pilosa_slo_requests_total{class="read.count"} 2' in text
    assert 'pilosa_slo_errors_total{class="read.count"} 1' in text
    assert 'pilosa_slo_availability{class="read.count",window="1m"}' in text
    assert 'pilosa_slo_burn_rate{class="read.count",window="10s"}' in text
    assert 'pilosa_slo_latency_seconds{class="read.count",quantile="0.99"}' in text
    assert 'pilosa_slo_alert{class="read.count",rule="fast"}' in text
    assert "# TYPE pilosa_slo_requests_total counter" in text


def test_summary_is_compact_verdict_view():
    t = SLOTracker(burn_rules=FAST_RULES)
    t.observe(slo.OP_WRITE, 0.001)
    s = t.summary()
    assert s["classes"][slo.OP_WRITE]["total"] == 1
    assert "windows" not in s["classes"][slo.OP_WRITE]


def test_objectives_from_dict_overrides_and_drops():
    objs = objectives_from_dict(
        {
            "write": {"availability": 0.95, "latencyP99Ms": 500},
            "import": None,
        }
    )
    assert objs["write"].availability == 0.95
    assert objs["write"].latency_p99 == 0.5
    assert "import" not in objs
    # untouched defaults survive
    assert objs["read.count"].availability == 0.999


def test_objective_rejects_degenerate_targets():
    with pytest.raises(ValueError):
        Objective(1.0)
    with pytest.raises(ValueError):
        Objective(0.0)


# -- HTTP integration ---------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    with InProcessCluster(
        1,
        with_disk=True,  # a real translate log, so logAppends moves
        slo_burn_rules=[
            {"name": "fast", "long": 60.0, "short": 10.0, "factor": 14.4},
            {"name": "slow", "long": 300.0, "short": 60.0, "factor": 1.0},
        ],
        slo_slot_seconds=1.0,
        slo_latency_window=60.0,
    ) as c:
        c.create_index("slotest")
        c.create_field("slotest", "f")
        c.create_index("slokeys", {"keys": True})
        c.create_field("slokeys", "tag", {"keys": True})
        yield c


def test_http_requests_classified_into_op_classes(cluster):
    uri = cluster.nodes[0].uri
    _post(uri, "/index/slotest/query", "Set(1, f=1)")
    _post(uri, "/index/slotest/query", "Count(Row(f=1))")
    _post(uri, "/index/slotest/query", "TopN(f, n=2)")
    # the SLO observation lands in the handler's finally AFTER the
    # response bytes go out, so briefly retry the snapshot rather than
    # race the recording of the last request
    import time as _time

    for _ in range(100):
        snap = _get(uri, "/debug/slo")
        classes = snap["classes"]
        if classes.get("read.topn", {}).get("total", 0) >= 1:
            break
        _time.sleep(0.01)
    assert classes["write"]["total"] >= 1
    assert classes["read.count"]["total"] >= 1
    assert classes["read.topn"]["total"] >= 1
    assert classes["read.count"]["latency"]["p50Ms"] is not None
    # snapshot shape: burn rules + windows named from the short config
    assert {r["name"] for r in snap["burnRules"]} == {"fast", "slow"}
    assert "1m" in classes["read.count"]["windows"]


def test_client_errors_do_not_burn_budget(cluster):
    uri = cluster.nodes[0].uri
    before = _get(uri, "/debug/slo")["classes"]["read.other"]["errors"]
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(uri, "/index/slotest/query", "Nonsense(((")
    assert ei.value.code == 400
    after = _get(uri, "/debug/slo")["classes"]["read.other"]["errors"]
    assert after == before  # a parse error is the client's problem


def test_deadline_504_burns_error_budget(cluster):
    uri = cluster.nodes[0].uri

    def total_errors():
        return sum(
            c["errors"] for c in _get(uri, "/debug/slo")["classes"].values()
        )

    before = total_errors()
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(
            uri,
            "/index/slotest/query?timeout=0.000000001",
            "Count(Row(f=1))",
        )
    assert ei.value.code == 504
    # the budget can expire before the API layer classifies the query,
    # in which case the 504 lands on the route's fallback class — either
    # way it burns exactly one request of budget.  The observation lands
    # in the handler's finally AFTER the 504 goes out (behind the span's
    # tail-sampling bookkeeping), so briefly retry rather than race it.
    import time as _time

    for _ in range(100):
        if total_errors() == before + 1:
            break
        _time.sleep(0.01)
    assert total_errors() == before + 1


def test_metrics_carry_slo_and_translate_series(cluster):
    uri = cluster.nodes[0].uri
    # put translation on the hot path (keyed row + column)
    _post(uri, "/index/slokeys/query", 'Set("u1", tag="hot")')
    _post(uri, "/index/slokeys/query", 'Count(Row(tag="hot"))')
    _post(
        uri,
        "/internal/translate/keys",
        json.dumps({"index": "slokeys", "field": "", "keys": ["u1", "u2"]}),
        ctype="application/json",
    )
    text = _get_text(uri, "/metrics")
    assert "pilosa_slo_requests_total" in text
    assert "pilosa_slo_availability" in text
    assert "pilosa_translate_keys_created" in text
    assert "pilosa_translate_keys_found" in text
    assert "pilosa_translate_lookup_seconds_bucket" in text
    snap = _get(uri, "/debug/slo")
    assert snap["classes"]["translate"]["total"] >= 1


def test_debug_vars_carry_slo_and_translate_blocks(cluster):
    uri = cluster.nodes[0].uri
    _post(uri, "/index/slotest/query", "Count(Row(f=1))")
    v = _get(uri, "/debug/vars")
    assert v["slo"]["classes"]["read.count"]["total"] >= 1
    assert "burnRules" in v["slo"]
    t = v["translate"]
    assert t["keysCreated"] >= 1
    assert t["logAppends"] >= 1
