"""Incident flight-recorder plane: tail-sampled trace store
(pilosa_tpu/obs/tracestore.py), metric exemplars, the flight recorder's
alert-triggered incident capture (pilosa_tpu/obs/flightrec.py), and the
HTTP wiring (/debug/traces, /debug/incidents, exemplars in /metrics) —
including cross-node trace assembly with ?cluster=true."""

import json
import re
import time
import urllib.error
import urllib.request

import pytest

from pilosa_tpu.obs import slo, tracestore, tracing
from pilosa_tpu.obs.slo import Objective, SLOTracker
from pilosa_tpu.obs.tracestore import TraceStore, baseline_kept
from pilosa_tpu.testing.cluster import InProcessCluster

# Small burn windows so a test's error burst fires alerts immediately
# (same shape as tests/test_slo.py FAST_RULES, as plain-dict knobs).
FAST_RULE_SPECS = [
    {"name": "fast", "long": 60.0, "short": 10.0, "factor": 14.4},
    {"name": "slow", "long": 300.0, "short": 60.0, "factor": 1.0},
]


def _get(uri, path):
    return json.load(urllib.request.urlopen(uri + path, timeout=10))


def _get_text(uri, path):
    with urllib.request.urlopen(uri + path, timeout=10) as resp:
        return resp.read().decode()


def _post(uri, path, body):
    req = urllib.request.Request(
        uri + path, data=body.encode(), method="POST",
        headers={"Content-Type": "text/plain"},
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _span(store, name="test.root", op_class="read.count", error=False,
          sleep=0.0):
    """Finish one root span routed into ``store``."""
    with tracestore.activate(store):
        with tracing.start_span(name) as s:
            if sleep:
                time.sleep(sleep)
            if op_class:
                s.set_tag("op_class", op_class)
            if error:
                s.set_tag("error", True)


# -- ids and traceparent ------------------------------------------------------


def test_ids_are_random_and_seedable():
    tracing.seed_ids(7)
    try:
        a = [tracing._new_trace_id() for _ in range(3)]
        tracing.seed_ids(7)
        b = [tracing._new_trace_id() for _ in range(3)]
        assert a == b
        assert len(set(a)) == 3
        assert all(0 < t < 2 ** 128 for t in a)
        assert 0 < tracing._new_span_id() < 2 ** 64
    finally:
        tracing.seed_ids(None)


@pytest.mark.parametrize(
    "bad",
    [
        None,
        "",
        "00",
        "00-abc-def-01",                                # wrong widths
        "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",      # reserved version
        "00-" + "0" * 32 + "-" + "b" * 16 + "-01",      # zero trace id
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",      # zero span id
        "00-" + "g" * 32 + "-" + "b" * 16 + "-01",      # non-hex
    ],
)
def test_parse_traceparent_rejects(bad):
    assert tracing.parse_traceparent(bad) is None


def test_traceparent_round_trip_marks_remote():
    ctx = tracing.SpanContext(0xABC, 0xDEF)
    got = tracing.parse_traceparent(tracing.format_traceparent(ctx))
    assert (got.trace_id, got.span_id) == (0xABC, 0xDEF)
    assert got.remote is True
    # traceparent alone is enough to join a trace (no native headers)
    joined = tracing.get_tracer().extract_headers(
        {tracing.TRACEPARENT_HEADER: tracing.format_traceparent(ctx)}
    )
    assert joined.trace_id == 0xABC and joined.remote is True


# -- tail policy --------------------------------------------------------------


def test_baseline_kept_is_deterministic_1_in_n():
    assert baseline_kept(123, 0) is False
    assert baseline_kept(123, 1) is True
    hits = sum(baseline_kept(t, 8) for t in range(1, 4001))
    # Fibonacci-hash mix: close to 1-in-8 over a dense id range
    assert 300 <= hits <= 700


def test_error_root_is_kept():
    store = TraceStore(baseline_n=0)
    _span(store, error=True)
    snap = store.snapshot()
    assert snap["stats"]["kept_error"] == 1
    assert store.summaries()[0]["reason"] == "error"
    assert store.summaries()[0]["error"] is True


def test_slow_root_is_kept_against_its_class_objective():
    tracker = SLOTracker()
    tracker.objectives = {"read.count": Objective(0.999, latency_p99=0.001)}
    store = TraceStore(slo=tracker, baseline_n=0)
    _span(store, sleep=0.005)
    assert store.summaries()[0]["reason"] == "slow"
    # same duration under a lenient objective: dropped
    tracker.objectives = {"read.count": Objective(0.999, latency_p99=10.0)}
    _span(store, sleep=0.005)
    assert store.snapshot()["stats"]["dropped"] == 1


def test_fast_root_is_dropped_and_baseline_keeps_everything_at_1():
    store = TraceStore(baseline_n=0)
    _span(store)
    snap = store.snapshot()
    assert snap["stats"] == {
        **snap["stats"], "completed": 1, "kept": 0, "dropped": 1,
    }
    store.baseline_n = 1
    _span(store)
    assert store.summaries()[0]["reason"] == "baseline"


def test_dropped_trace_spans_stay_in_recent_for_assembly():
    store = TraceStore(baseline_n=0)
    with tracestore.activate(store):
        with tracing.start_span("test.root") as root:
            with tracing.start_span("test.child"):
                pass
            root.set_tag("op_class", "read.count")
    tid = f"{root.context.trace_id:032x}"
    assert store.detail(tid) is None  # fast: not kept
    spans = store.spans_for(tid)     # ...but assemblable
    assert {s["name"] for s in spans} == {"test.root", "test.child"}
    assert all(s["traceId"] == tid for s in spans)


def test_kept_detail_carries_spans_and_capacity_bounds():
    store = TraceStore(baseline_n=1, capacity=4)
    tids = []
    for _ in range(8):
        with tracestore.activate(store):
            with tracing.start_span("test.root") as s:
                s.set_tag("op_class", "read.count")
        tids.append(f"{s.context.trace_id:032x}")
    assert len(store.kept_ids()) == 4
    detail = store.detail(tids[-1])
    assert detail["reason"] == "baseline"
    assert detail["spans"][0]["spanId"]
    assert store.detail(tids[0]) is None  # evicted
    assert store.detail("zz") is None     # non-hex id


def test_on_keep_hook_fires_with_class_and_hex_id():
    seen = []
    store = TraceStore(baseline_n=1)
    store.on_keep = lambda cls, secs, tid: seen.append((cls, tid))
    _span(store)
    assert seen and seen[0][0] == "read.count"
    assert re.fullmatch(r"[0-9a-f]{32}", seen[0][1])


# -- HTTP plane ---------------------------------------------------------------


def _seed(cluster, index="ti"):
    cluster.create_index(index)
    cluster.create_field(index, "f")
    cluster.import_bits(index, "f", [(1, 3)])


def test_debug_traces_and_exemplars_over_http():
    # a 1 us p99 objective makes every read.count a tail-kept "slow"
    with InProcessCluster(
        1,
        slo_objectives={
            "read.count": {"availability": 0.999, "latencyP99Ms": 0.001}
        },
        trace_baseline_n=0,
        flightrec_segment_seconds=0.2,
    ) as c:
        uri = c.nodes[0].uri
        _seed(c)
        status, _ = _post(uri, "/index/ti/query", "Count(Row(f=1))")
        assert status == 200
        # the root span finishes after the response's last byte: settle
        for _ in range(100):
            out = _get(uri, "/debug/traces")
            if out["store"]["stats"]["kept_slow"] >= 1:
                break
            time.sleep(0.02)
        assert out["store"]["stats"]["kept_slow"] >= 1
        top = out["traces"][0]
        assert top["reason"] == "slow" and top["opClass"] == "read.count"
        detail = _get(uri, f"/debug/traces?id={top['traceId']}")
        names = {s["name"] for s in detail["spans"]}
        assert "http.query" in names
        # a 504 (deadline exceeded) is server-attributed: kept as error
        status, _ = _post(
            uri, "/index/ti/query?timeout=0.000001", "Count(Row(f=1))"
        )
        assert status == 504
        reasons = {t["reason"] for t in _get(uri, "/debug/traces")["traces"]}
        assert "error" in reasons
        # exemplars: the SLO latency histogram cites a kept trace id
        metrics = _get_text(uri, "/metrics")
        m = re.search(
            r'pilosa_slo_request_duration_seconds_bucket\{[^}]*\}'
            r' \d+ # \{trace_id="([0-9a-f]{32})"\}',
            metrics,
        )
        assert m, "no exemplar in /metrics"
        assert _get(uri, f"/debug/traces?id={m.group(1)}")["traceId"] == m.group(1)
        # bad limit is a 400, not a 500
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(uri, "/debug/traces?limit=x")
        assert ei.value.code == 400


def test_cluster_true_assembles_spans_from_every_node():
    # mesh_dispatch=False: the assertions want a dist.fanout leg plus the
    # remote node's http.query handler span; mesh dispatch has neither
    with InProcessCluster(
        2,
        slo_objectives={
            "read.count": {"availability": 0.999, "latencyP99Ms": 0.001}
        },
        trace_baseline_n=0,
        mesh_dispatch=False,
    ) as c:
        _seed(c)  # shard 0 only
        owner = c.owner_of("ti", 0)
        querier = next(n for n in c.nodes if n is not owner)
        status, out = _post(querier.uri, "/index/ti/query", "Count(Row(f=1))")
        assert status == 200 and out["results"][0] == 1
        # the remote handler span finishes on another thread; settle
        time.sleep(0.3)
        listing = _get(querier.uri, "/debug/traces")
        tid = listing["traces"][0]["traceId"]
        merged = _get(querier.uri, f"/debug/traces?cluster=true&id={tid}")
        nodes_seen = {s["node"] for s in merged["spans"]}
        assert len(nodes_seen) == 2, merged
        names = {s["name"] for s in merged["spans"]}
        # the coordinator's fan-out leg AND the remote node's handler
        assert "dist.fanout" in names
        assert "http.query" in names
        assert merged["traceId"] == tid
        # cluster listing polled both nodes without errors
        all_traces = _get(querier.uri, "/debug/traces?cluster=true")
        assert all_traces["nodes"] == 2
        assert all_traces["unreachable"] == []
        assert any(t["traceId"] == tid for t in all_traces["traces"])


# -- flight recorder ----------------------------------------------------------


def test_slo_burn_under_injected_faults_captures_one_incident():
    # mesh_dispatch=False: the burn is driven by faulted HTTP legs to the
    # owner; mesh dispatch would answer locally and never hit the fault
    with InProcessCluster(
        2,
        slo_burn_rules=FAST_RULE_SPECS,
        slo_slot_seconds=1.0,
        flightrec_segment_seconds=0.1,
        trace_baseline_n=0,
        mesh_dispatch=False,
    ) as c:
        _seed(c)
        owner = c.owner_of("ti", 0)
        querier = next(n for n in c.nodes if n is not owner)
        assert _get(querier.uri, "/debug/incidents")["incidents"] == []
        # every fan-out leg to the owner now stalls past the caller's
        # deadline -> 504s on the querier (server-attributed: burns
        # budget) -> burn alert edge on the querier
        c.inject_fault(
            "slow", node=c.nodes.index(owner), route="/index/*", delay=30.0
        )
        deadline = time.monotonic() + 15.0
        incidents = []
        while time.monotonic() < deadline:
            status, _ = _post(
                querier.uri, "/index/ti/query?timeout=0.05", "Count(Row(f=1))"
            )
            assert status == 504
            incidents = _get(querier.uri, "/debug/incidents")["incidents"]
            if incidents:
                break
            time.sleep(0.1)
        assert len(incidents) == 1, incidents
        assert incidents[0]["trigger"]["type"] == "slo-alert"
        # the alert keeps firing: the SAME burn episode must not stack
        # a second bundle
        for _ in range(5):
            _post(
                querier.uri, "/index/ti/query?timeout=0.05", "Count(Row(f=1))"
            )
            time.sleep(0.1)
        after = _get(querier.uri, "/debug/incidents")["incidents"]
        assert len(after) == 1
        # terminal bundle: segments + kept traces + slow-query log
        detail = _get(
            querier.uri, f"/debug/incidents?id={incidents[0]['id']}"
        )
        assert detail["segments"], "bundle has no flight-recorder segments"
        assert detail["segments"][-1]["profile"]["samples"] >= 0
        assert "traces" in detail and "slowQueries" in detail
        # journaled as a control-plane event
        kinds = [
            e["type"]
            for e in _get(querier.uri, "/debug/events")["events"]
        ]
        assert "incident" in kinds
        # unknown id is a 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(querier.uri, "/debug/incidents?id=nope")
        assert ei.value.code == 404


def test_504_spike_captures_incident_when_no_alerts_configured():
    with InProcessCluster(
        1,
        slo_burn_rules=[],  # no alerting: exercises the spike trigger
        flightrec_segment_seconds=0.1,
        flightrec_spike_504=3,
        trace_baseline_n=0,
    ) as c:
        uri = c.nodes[0].uri
        _seed(c)
        for _ in range(4):
            status, _ = _post(
                uri, "/index/ti/query?timeout=0.000001", "Count(Row(f=1))"
            )
            assert status == 504
        deadline = time.monotonic() + 5.0
        incidents = []
        while time.monotonic() < deadline and not incidents:
            incidents = _get(uri, "/debug/incidents")["incidents"]
            time.sleep(0.05)
        assert incidents, "504 spike never captured"
        assert incidents[0]["trigger"]["type"] == "deadline-504-spike"
        assert incidents[0]["trigger"]["count"] >= 3


def test_flight_recorder_segments_accumulate_and_stop_is_clean():
    with InProcessCluster(
        1, flightrec_segment_seconds=0.1, flight_recorder=True
    ) as c:
        rec = c.nodes[0].flightrec
        time.sleep(0.5)
        segs = rec.segments_snapshot(limit=5)
        assert segs and segs[-1]["profile"]["samples"] >= 1
        assert segs[-1]["seconds"] > 0
        snap = rec.incidents_snapshot()
        assert snap["enabled"] is True and snap["incidents"] == []
    # recorder disabled: endpoint still serves
    with InProcessCluster(1, flight_recorder=False) as c:
        out = _get(c.nodes[0].uri, "/debug/incidents")
        assert out == {"enabled": False, "incidents": []}
