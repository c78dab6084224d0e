"""Cost-governed multi-tenant QoS (server/qos.py + wiring): weighted-
fair virtual-time admission, ledger-debited debt accounting, the
three-stage pressure ladder (deprioritize -> degraded tier -> shed),
and the cross-plane tenant plumbing — tenantless requests normalize to
one canonical ``(default)`` principal across batcher, devledger, and
SLO accounting; sheds surface as 429 + Retry-After (never a silent
504); degraded responses are explicitly marked and bit-identical to
their cache source; every ladder transition is journaled and each
pressure episode captures exactly one incident bundle.

Ladder tests drive ``tick(now=...)`` with injected slo/ledger/journal
taps so escalation timing is deterministic; HTTP tests ride a live
InProcessCluster with relax frozen (huge ``qos_relax_hold``) so
manually-staged tenants hold their stage for the duration.
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu import deadline
from pilosa_tpu.deadline import DeadlineExceeded
from pilosa_tpu.obs import devledger, slo
from pilosa_tpu.obs.stats import MemStatsClient
from pilosa_tpu.server import qos as qos_mod
from pilosa_tpu.server.qos import ADMIT, DEGRADE, QosGovernor, ShedError
from pilosa_tpu.testing.cluster import InProcessCluster


class _Flight:
    """Minimal stand-in for batcher._Flight: the governor only reads
    ``principal``."""

    def __init__(self, tenant: str):
        self.principal = (tenant, "i", "read.count")


class _Stop:
    """No ``principal`` attribute -> the governor treats it as the
    batcher's stop sentinel."""


class _FakeTracker:
    def __init__(self):
        self.value = {"alerts": [], "latency": []}

    def pressure(self):
        return self.value

    def burning(self, on: bool) -> None:
        self.value = (
            {"alerts": [("read.count", "fast")], "latency": ["read.count"]}
            if on
            else {"alerts": [], "latency": []}
        )


class _FakeJournal:
    def __init__(self):
        self.events: list[dict] = []

    def record(self, type, **data):
        self.events.append({"type": type, **data})


def _drain(gov, timeout=0.2):
    import queue as queue_mod

    out = []
    while True:
        try:
            out.append(gov.get(timeout=timeout))
        except queue_mod.Empty:
            return out


# -- tenantless normalization (the canonical "(default)" principal) ----------


def test_clean_tenant_normalizes_to_default():
    for raw in (None, "", "   ", "-", "\t"):
        assert devledger.clean_tenant(raw) == devledger.DEFAULT_TENANT
    assert devledger.clean_tenant("acme") == "acme"


def test_governor_maps_missing_tenant_to_default():
    gov = QosGovernor(enabled=True)
    assert gov.admit(None) == ADMIT
    assert gov.admit("") == ADMIT
    snap = gov.snapshot()
    assert list(snap["tenants"]) == [devledger.DEFAULT_TENANT]
    assert snap["tenants"][devledger.DEFAULT_TENANT]["admitted"] == 2


def test_slo_default_tenant_gets_no_duplicate_class():
    tr = slo.SLOTracker(slot_seconds=1.0)
    tr.observe("read.count", 0.01, tenant=devledger.DEFAULT_TENANT)
    tr.observe("read.count", 0.01, tenant="acme")
    classes = tr.snapshot()["classes"]
    assert "read.count@acme" in classes
    assert not any("@(default)" in name for name in classes)


# -- weighted-fair queueing ---------------------------------------------------


def test_wfq_every_nonempty_queue_drains():
    """Starvation-freedom: even a stage-2 (weight-crushed) tenant's
    queue fully drains once the others stop arriving."""
    gov = QosGovernor(enabled=True, weights={"a": 8.0, "b": 1.0})
    with gov._cond:
        ts_c = gov._state_locked("c", time.monotonic())
        ts_c.stage = 2  # deprioritized twice: weight / down_factor**2
    n = 40
    for _ in range(n):
        for t in ("a", "b", "c"):
            gov.put(_Flight(t))
    popped = _drain(gov)
    assert len(popped) == 3 * n
    by_tenant = {}
    for f in popped:
        by_tenant[f.principal[0]] = by_tenant.get(f.principal[0], 0) + 1
    assert by_tenant == {"a": n, "b": n, "c": n}
    assert gov.empty()


def test_wfq_share_tracks_weights():
    """With equal per-query cost, a weight-3 tenant gets ~3x the pops
    of a weight-1 tenant over any service prefix."""
    gov = QosGovernor(enabled=True, weights={"heavy": 3.0, "light": 1.0})
    for _ in range(200):
        gov.put(_Flight("heavy"))
        gov.put(_Flight("light"))
    first = [gov.get(timeout=0.2) for _ in range(100)]
    heavy = sum(1 for f in first if f.principal[0] == "heavy")
    assert 68 <= heavy <= 82, f"heavy got {heavy}/100, want ~75"
    _drain(gov)


def test_stop_sentinel_replayed_after_drain():
    gov = QosGovernor(enabled=True)
    gov.put(_Flight("a"))
    stop = _Stop()
    gov.put(stop)
    assert not gov.empty()
    assert gov.get(timeout=0.2).principal[0] == "a"
    # the sentinel only surfaces once the queues are empty, then replays
    assert gov.get(timeout=0.2) is stop
    assert gov.get(timeout=0.2) is stop


# -- debt accounting ----------------------------------------------------------


def test_debt_conserves_measured_device_ms():
    """Every measured millisecond lands in exactly one tenant's debt:
    sum(debt_ms) == sum of the ledger deltas fed in."""
    totals = {}

    def ledger():
        return totals

    gov = QosGovernor(enabled=True, ledger_fn=ledger)
    totals = {"a": {"deviceMs": 5.0}, "b": {"deviceMs": 2.0}}
    gov.tick()
    totals = {"a": {"deviceMs": 12.5}, "b": {"deviceMs": 2.0}}
    gov.tick()
    totals = {"a": {"deviceMs": 12.5}, "b": {"deviceMs": 8.25}}
    gov.tick()
    snap = gov.snapshot()["tenants"]
    assert snap["a"]["debtMs"] == 12.5
    assert snap["b"]["debtMs"] == 8.25
    fed = sum(row["deviceMs"] for row in totals.values())
    assert snap["a"]["debtMs"] + snap["b"]["debtMs"] == fed


def test_observe_ledger_returns_total_debited():
    gov = QosGovernor(enabled=True)
    total = gov.observe_ledger({"a": 3.0, "b": 1.5, "quiet": 0.0})
    assert total == 4.5
    snap = gov.snapshot()["tenants"]
    assert snap["a"]["debtMs"] == 3.0
    assert snap["b"]["debtMs"] == 1.5
    assert "quiet" not in snap  # zero-ms rows create no tenant state


# -- pressure ladder ----------------------------------------------------------


def _ladder_rig(**over):
    tracker = _FakeTracker()
    journal = _FakeJournal()
    incidents: list[dict] = []
    kwargs = dict(
        enabled=True,
        stage_hold=0.3,
        relax_hold=0.5,
        tick_interval=1e9,  # freeze maybe_tick: only explicit tick(now)
        retry_after=2.0,
        slo_fn=lambda: tracker,
        journal_fn=lambda: journal,
        incident_fn=incidents.append,
    )
    kwargs.update(over)
    return QosGovernor(**kwargs), tracker, journal, incidents


def test_single_tenant_never_escalates():
    gov, tracker, _journal, incidents = _ladder_rig()
    tracker.burning(True)
    base = time.monotonic()
    for i in range(5):
        for _ in range(10):
            gov.admit("solo")
        gov.tick(base + 0.5 * (i + 1))
    snap = gov.snapshot()
    assert snap["tenants"]["solo"]["stage"] == 0
    assert snap["episodes"] == 0
    assert incidents == []


def test_ladder_escalates_sheds_relaxes_one_incident():
    gov, tracker, journal, incidents = _ladder_rig()
    base = time.monotonic()

    def offer():
        for _ in range(10):
            try:
                gov.admit("aggressor")
            except ShedError:
                pass
        gov.admit("victim")

    offer()
    tracker.burning(True)
    gov.tick(base + 0.5)
    offer()
    gov.tick(base + 0.9)
    offer()
    gov.tick(base + 1.3)
    snap = gov.snapshot()["tenants"]
    assert snap["aggressor"]["stage"] == 3
    assert snap["victim"]["stage"] == 0, "ladder must never touch the victim"

    # stage 3: admission raises ShedError carrying the Retry-After hint;
    # the victim is still admitted at full weight
    with pytest.raises(ShedError) as e:
        gov.admit("aggressor")
    assert e.value.retry_after == 2.0
    assert e.value.tenant == "aggressor"
    assert gov.admit("victim") == ADMIT

    # the aggressor keeps hammering while shed: stickiness holds, no
    # further transitions, and crucially the victim stays at stage 0
    offer()
    gov.tick(base + 1.7)
    assert gov.snapshot()["tenants"]["victim"]["stage"] == 0

    # exactly ONE incident for the whole episode
    assert len(incidents) == 1
    assert incidents[0]["type"] == "qos-pressure"
    assert incidents[0]["tenant"] == "aggressor"

    # pressure clears -> relax one rung per relax_hold, down to normal,
    # and the episode-clear record is journaled
    tracker.burning(False)
    for i in range(3):
        gov.tick(base + 2.1 + 0.6 * i)
    snap = gov.snapshot()
    assert snap["tenants"]["aggressor"]["stage"] == 0
    assert snap["episodeActive"] is False
    assert snap["episodes"] == 1
    kinds = [(e["tenant"], e["fromStage"], e["toStage"]) for e in journal.events]
    assert ("aggressor", "normal", "deprioritized") in kinds
    assert ("aggressor", "degraded", "shedding") in kinds
    assert ("aggressor", "shedding", "degraded") in kinds
    assert ("*", "episode", "clear") in kinds
    assert len(incidents) == 1, "relax must not capture more incidents"


def test_ghost_neighbor_never_enables_escalation():
    """A tenant that stopped offering load (but is still inside
    active_window) must not count as the second party of a contest —
    otherwise the sole live tenant of the NEXT workload phase gets
    designated aggressor against nobody and shed."""
    gov, tracker, _journal, incidents = _ladder_rig()
    base = time.monotonic()
    # "ghost" was active once, then goes silent; "live" keeps offering.
    gov.admit("ghost")
    gov.tick(base + 0.5)
    for i in range(5):
        for _ in range(10):
            gov.admit("live")
        gov.tick(base + 0.5 + 0.5 * (i + 1))
    tracker.burning(True)
    for i in range(5):
        for _ in range(10):
            gov.admit("live")
        gov.tick(base + 3.0 + 0.5 * (i + 1))
    snap = gov.snapshot()
    assert snap["tenants"]["live"]["stage"] == 0
    assert snap["episodes"] == 0
    assert incidents == []


def test_ladder_stands_down_when_contest_ends_under_pressure():
    """Pressure persists but every neighbor went quiet: the governor
    relaxes the designated aggressor anyway — residual pressure with no
    victim to defend is not the ladder's to fix."""
    gov, tracker, journal, _incidents = _ladder_rig()
    base = time.monotonic()

    def offer():
        for _ in range(10):
            gov.admit("noisy")
        gov.admit("victim")

    offer()
    tracker.burning(True)
    gov.tick(base + 0.5)
    offer()
    gov.tick(base + 0.9)
    assert gov.snapshot()["tenants"]["noisy"]["stage"] == 2
    # both tenants stop; pressure stays on (some unrelated slow class)
    for i in range(4):
        gov.tick(base + 1.5 + 0.6 * i)
    snap = gov.snapshot()
    assert snap["tenants"]["noisy"]["stage"] == 0
    assert snap["episodeActive"] is False
    reasons = [e.get("reason", "") for e in journal.events]
    assert any("standing down" in r for r in reasons), reasons


def test_stage2_admit_degrades_only_degradable_queries():
    gov, tracker, _journal, _incidents = _ladder_rig()
    with gov._cond:
        gov._state_locked("dash", time.monotonic()).stage = 2
    assert gov.admit("dash", can_degrade=True) == DEGRADE
    assert gov.admit("dash", can_degrade=False) == ADMIT


def test_disabled_governor_never_sheds():
    gov = QosGovernor(enabled=False)
    with gov._cond:
        gov._state_locked("t", time.monotonic()).stage = 3
    assert gov.admit("t") == ADMIT


# -- per-tenant SLO classes ---------------------------------------------------


def test_objectives_from_dict_tenant_subspec():
    objs = slo.objectives_from_dict(
        {"tenants": {"victim": {"read.count": {
            "availability": 0.99, "latencyP99Ms": 500.0,
        }}}}
    )
    assert "read.count@victim" in objs
    assert objs["read.count@victim"].latency_p99 == 0.5
    # base defaults survive alongside
    assert "read.count" in objs


def test_pressure_sees_tenant_scoped_latency_violation():
    objs = slo.objectives_from_dict(
        {"tenants": {"v": {"read.count": {
            "availability": 0.999, "latencyP99Ms": 0.001,
        }}}}
    )
    tr = slo.SLOTracker(objectives=objs, slot_seconds=1.0)
    for _ in range(20):
        tr.observe("read.count", 0.05, tenant="v")
    p = tr.pressure()
    assert "read.count@v" in p["latency"]


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


def test_a_tick_sums_each_burn_window_once_and_takes_no_snapshot(monkeypatch):
    """What one governor tick reads of the SLO plane, as counts (no
    clock: ROADMAP D9): of every class with an objective and traffic,
    each distinct burn window once; of the others, nothing; and never
    the /debug/slo payload."""
    from pilosa_tpu.obs import tracing

    tr = slo.SLOTracker()  # the default rules: 5 m, 1 h, 6 h, 3 d
    for name in ("read.count", "read.topn", "internal"):
        tr.observe(name, 0.4, error=True)
    tr.observe("read.count", 0.4, tenant="v")  # "read.count@v": no objective
    calls = _count_sum_window(monkeypatch, tr)

    def no_snapshot():
        raise AssertionError("a tick built the /debug/slo payload")

    monkeypatch.setattr(tr, "snapshot", no_snapshot)
    gov, _tracker, _journal, _incidents = _ladder_rig(slo_fn=lambda: tr)
    ticks = tracing.spans_snapshot()["qos"]["tick"]["count"]
    gov.admit("a")
    gov.admit("b")
    assert gov._under_pressure() is True
    del calls[:]
    gov.tick()
    windows = sorted({w for r in slo.DEFAULT_BURN_RULES for w in (r.long, r.short)})
    assert len(windows) == 4
    assert sorted(calls) == [(n, w) for n in ("read.count", "read.topn") for w in windows]
    assert tracing.spans_snapshot()["qos"]["tick"]["count"] == ticks + 1
    # and it is the tracker's own answer the ladder acted on
    assert tr.pressure() == {
        "alerts": [(n, r) for n in ("read.count", "read.topn") for r in ("fast", "slow")],
        "latency": ["read.count", "read.topn"],
    }


# -- batcher expiry accounting (per tenant, per reason) -----------------------


def test_admission_expiry_counts_tenant_and_reason():
    from pilosa_tpu.server.batcher import QueryBatcher

    class _NopExec:
        def execute(self, index, query, shards=None):
            return ["ok"]

        def execute_batch(self, index, queries):
            return [["ok"] for _ in queries]

    stats = MemStatsClient()
    b = QueryBatcher(_NopExec(), stats=stats, window=0.01, max_batch=4)
    try:
        with deadline.scope(1e-6):
            time.sleep(0.005)
            with pytest.raises(DeadlineExceeded):
                b.submit("i", "q")
    finally:
        b.close()
    counters = stats.snapshot()["counters"]
    key = "batcher_expired_by{reason:admission,tenant:(default)}"
    assert counters.get(key) == 1, counters


# -- HTTP plane: 429 path, degraded marking, /debug/qos, default tenant -------


def _call(uri, method, path, body=None, headers=None, raw=False):
    data = (
        body
        if isinstance(body, (bytes, type(None)))
        else json.dumps(body).encode()
    )
    req = urllib.request.Request(uri + path, data=data, method=method)
    req.add_header("Content-Type", "application/json")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=10) as resp:
        payload = resp.read()
        if raw:
            return resp, payload
        return json.loads(payload) if payload.strip() else {}


@pytest.fixture(scope="module")
def qcluster():
    # relax frozen so manually-staged tenants hold for the test body
    with InProcessCluster(1, qos_relax_hold=1e9) as cl:
        cl.create_index("qi")
        cl.create_field("qi", "f")
        cl.import_bits("qi", "f", [(r, c) for r in range(3) for c in range(8)])
        yield cl


def _stage(cluster, tenant, stage):
    gov = cluster.nodes[0].api.qos
    assert gov is not None, "batcher-enabled node must carry a governor"
    with gov._cond:
        gov._state_locked(tenant, time.monotonic()).stage = stage


def test_http_shed_is_429_with_retry_after(qcluster):
    uri = qcluster.nodes[0].uri
    _stage(qcluster, "flooder", 3)
    with pytest.raises(urllib.error.HTTPError) as e:
        _call(uri, "POST", "/index/qi/query", b"Count(Row(f=1))",
              headers={"X-Pilosa-Tenant": "flooder"})
    assert e.value.code == 429
    assert e.value.headers.get("Retry-After") is not None
    body = json.loads(e.value.read())
    assert body["retryAfter"] >= 1
    # an un-headered client is untouched by the flooder's stage
    ok = _call(uri, "POST", "/index/qi/query", b"Count(Row(f=1))")
    assert "results" in ok and "degraded" not in ok
    _stage(qcluster, "flooder", 0)
    snap = _call(uri, "GET", "/debug/qos")
    assert snap["tenants"]["flooder"]["shed"] >= 1
    # shed visible in prometheus exposition with the tenant label
    req = urllib.request.Request(uri + "/metrics")
    with urllib.request.urlopen(req, timeout=10) as resp:
        metrics = resp.read().decode()
    assert 'pilosa_qos_shed{tenant="flooder"}' in metrics


def test_http_degraded_tier_marked_and_identical(qcluster):
    uri = qcluster.nodes[0].uri
    q = b"TopN(f, n=3)"
    # prime the semantic cache with the healthy answer
    healthy = _call(uri, "POST", "/index/qi/query", q)
    for _ in range(2):
        again = _call(uri, "POST", "/index/qi/query", q)
        assert again["results"] == healthy["results"]
    assert "degraded" not in healthy
    _stage(qcluster, "dash", 2)
    try:
        degraded = _call(uri, "POST", "/index/qi/query", q,
                         headers={"X-Pilosa-Tenant": "dash"})
    finally:
        _stage(qcluster, "dash", 0)
    assert degraded.get("degraded") is True, degraded
    # bit-identical to the cache source (same canonical call, version
    # check waived but nothing wrote in between)
    assert degraded["results"] == healthy["results"]
    snap = _call(uri, "GET", "/debug/qos")
    assert snap["tenants"]["dash"]["degraded"] >= 1


def test_http_default_tenant_lands_everywhere(qcluster):
    uri = qcluster.nodes[0].uri
    _call(uri, "POST", "/index/qi/query", b"Count(Row(f=0))")
    # governor: tenantless admission under the canonical principal
    snap = _call(uri, "GET", "/debug/qos")
    assert devledger.DEFAULT_TENANT in snap["tenants"]
    # devledger: per-tenant totals key the same canonical name
    totals = devledger.tenant_totals()
    assert devledger.DEFAULT_TENANT in totals
    # SLO: base class carries the traffic; no duplicate @(default) row
    slo_snap = _call(uri, "GET", "/debug/slo")
    assert "read.count" in slo_snap["classes"]
    assert not any("@(default)" in c for c in slo_snap["classes"])


def test_debug_qos_shape(qcluster):
    snap = _call(qcluster.nodes[0].uri, "GET", "/debug/qos")
    assert snap["enabled"] is True
    for key in ("vtime", "episodes", "episodeActive", "config",
                "tenants", "transitions"):
        assert key in snap, key
    cfg = snap["config"]
    for key in ("downFactor", "stageHold", "relaxHold", "tickInterval",
                "retryAfter", "aggressorShare"):
        assert key in cfg, key
    # /debug/vars carries the same block for one-stop snapshots
    dbg = _call(qcluster.nodes[0].uri, "GET", "/debug/vars")
    assert dbg["qos"]["enabled"] is True


# -- degraded lookup is bit-identical to its cache source ---------------------


def test_rescache_lookup_stale_returns_copy_of_source():
    from pilosa_tpu import pql
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.exec.executor import Executor

    h = Holder()
    h.create_index("i")
    h.index("i").create_field("f")
    ex = Executor(h)
    ex.execute("i", "Set(1, f=1) Set(2, f=1) Set(3, f=2)")
    healthy = ex.execute("i", "TopN(f, n=2)")  # the miss stores the entry
    q = pql.parse("TopN(f, n=2)")
    a = ex.rescache_degraded("i", q)
    b = ex.rescache_degraded("i", q)
    # bit-identical to the cache source, but fresh COPIES each time:
    # degraded callers can't mutate the cache's source of truth
    assert a == b == healthy
    assert a is not b
    assert ex.rescache.degraded_hits == 2
    # a call the cache never saw has no last-known answer
    assert ex.rescache_degraded("i", pql.parse("TopN(f, n=1)")) is None
