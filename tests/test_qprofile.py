"""Per-query profiling plane tests: the qprofile collector, OTLP wall-
clock anchoring, Prometheus histogram bucket exposition, distributed
profile merge across an InProcessCluster fan-out, the slow-query log,
and the kernel telemetry series."""

import json
import time
import urllib.request

from pilosa_tpu.obs import qprofile, tracing
from pilosa_tpu.obs.export import _otlp_span
from pilosa_tpu.obs.stats import MemStatsClient, prometheus_text
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.testing import InProcessCluster


# -- collector unit behavior ------------------------------------------------


def _walk(node, subs, kerns):
    for sp in node.get("subprofiles", []):
        subs.append(sp)
    kerns.extend(node.get("kernels", []))
    for c in node.get("children", []):
        _walk(c, subs, kerns)


def test_profile_tree_nesting_and_kernels():
    prof = qprofile.QueryProfile("i", "Count(Row(f=1))", node_id="n0")
    with qprofile.activate(prof):
        with qprofile.span("outer", index="i"):
            with qprofile.span("inner"):
                qprofile.record_kernel(kernel="row_counts", lane="xla")
                qprofile.incr("gram_cache_hits")
    prof.finish(0.5)
    d = prof.to_dict()
    assert d["node"] == "n0" and d["duration_ms"] == 500.0
    [outer] = d["tree"]["children"]
    assert outer["name"] == "outer" and outer["tags"] == {"index": "i"}
    [inner] = outer["children"]
    assert inner["kernels"] == [{"kernel": "row_counts", "lane": "xla"}]
    assert inner["stats"] == {"gram_cache_hits": 1}


def test_no_active_profile_is_a_noop():
    # collectors sit on the hot path; without ?profile=true they must
    # do nothing rather than accumulate into a global
    qprofile.record_kernel(kernel="x", lane="host")
    qprofile.incr("y")
    with qprofile.span("z"):
        pass
    assert not qprofile.profiling()


def test_kernel_record_cap():
    prof = qprofile.QueryProfile("i", "q")
    with qprofile.activate(prof):
        for _ in range(qprofile.MAX_KERNEL_RECORDS + 10):
            qprofile.record_kernel(kernel="k", lane="host")
    prof.finish(0.0)
    d = prof.to_dict()
    assert len(d["tree"]["kernels"]) == qprofile.MAX_KERNEL_RECORDS
    assert d["kernelRecordsDropped"] == 10


def test_slow_query_log_threshold_and_bound():
    log = qprofile.SlowQueryLog(threshold=0.1, capacity=3)
    assert log.enabled
    for i in range(6):
        p = qprofile.QueryProfile("i", f"q{i}")
        p.finish(0.05 if i == 0 else 0.2 + i * 0.01)  # q0 under threshold
        log.observe(p)
    snap = log.snapshot()
    assert snap["count"] == 3  # bounded, q0 excluded
    elapsed = [q["elapsed_ms"] for q in snap["queries"]]
    assert elapsed == sorted(elapsed, reverse=True)  # worst offenders kept
    assert all(q["query"] != "q0" for q in snap["queries"])


# -- satellite: OTLP wall-clock anchoring -----------------------------------


def test_otlp_span_anchored_at_start_not_export():
    with tracing.start_span("test.op") as s:
        s.set_tag("index", "i").set_tag("logs", ["hidden"])
    anchor = s.start_unix_ns
    # the span may sit in the export queue arbitrarily long; the payload
    # must reflect when it STARTED, not when it was serialized
    time.sleep(0.02)
    payload = _otlp_span(s)
    assert payload["startTimeUnixNano"] == str(anchor)
    end = int(payload["endTimeUnixNano"])
    assert end == anchor + int((s.duration or 0.0) * 1e9)
    assert len(payload["traceId"]) == 32 and len(payload["spanId"]) == 16
    keys = [a["key"] for a in payload["attributes"]]
    assert "index" in keys and "logs" not in keys


def test_spans_mirror_into_active_profile():
    prof = qprofile.QueryProfile("i", "q")
    with qprofile.activate(prof):
        with tracing.start_span("executor.Execute") as s:
            s.set_tag("index", "i")
    prof.finish(0.0)
    [child] = prof.to_dict()["tree"]["children"]
    assert child["name"] == "executor.Execute"
    assert child["tags"] == {"index": "i"}
    assert child["duration_ms"] >= 0


# -- satellite: histogram bucket exposition ---------------------------------


def test_prometheus_histogram_buckets():
    stats = MemStatsClient()
    stats.timing("query", 0.003)
    stats.timing("query", 0.2)
    stats.timing("query", 99.0)  # beyond the largest bound: +Inf only
    text = prometheus_text(stats)
    assert "# TYPE pilosa_query_seconds histogram" in text
    assert 'pilosa_query_seconds_bucket{le="0.005"} 1' in text
    assert 'pilosa_query_seconds_bucket{le="0.25"} 2' in text
    assert 'pilosa_query_seconds_bucket{le="60.0"} 2' in text
    assert 'pilosa_query_seconds_bucket{le="+Inf"} 3' in text
    assert "pilosa_query_seconds_count 3" in text


def test_prometheus_histogram_buckets_with_tags():
    stats = MemStatsClient()
    stats.with_tags("route:query").timing("rpc", 0.004)
    text = prometheus_text(stats)
    assert 'pilosa_rpc_seconds_bucket{route="query",le="0.005"} 1' in text
    assert 'pilosa_rpc_seconds_bucket{route="query",le="+Inf"} 1' in text


# -- profile merge across a real fan-out ------------------------------------


def _remote_shard(cl, index):
    """A shard whose primary is NOT the query node (node 0) — shard
    placement hashes random node ids, so probe instead of hard-coding."""
    for s in range(64):
        if cl.owner_of(index, s) is not cl.nodes[0]:
            return s
    raise AssertionError("no shard maps to the other node")


def test_distributed_profile_merges_remote_subprofiles():
    # mesh_dispatch=False: this test asserts the REMOTE node's sub-profile
    # comes back over the HTTP relay; mesh dispatch profiles locally
    with InProcessCluster(2, mesh_dispatch=False) as cl:
        cl.create_index("i")
        cl.create_field("i", "f")
        rs = _remote_shard(cl, "i")
        cl.import_bits(
            "i",
            "f",
            [(0, 0), (0, rs * SHARD_WIDTH + 5), (1, 3), (1, rs * SHARD_WIDTH + 5)],
        )
        resp = cl.query(0, "i", "GroupBy(Rows(f))", profile=True)
        assert resp["results"]  # the query itself worked
        prof = resp["profile"]
        assert prof["query"] == "GroupBy(Rows(f))"
        subs, kerns = [], []
        _walk(prof["tree"], subs, kerns)
        # the remote node's execution came back as a nested sub-profile
        assert subs, "no sub-profile merged from the fan-out"
        other_ids = {n.node_id for n in cl.nodes} - {cl.nodes[0].node_id}
        assert {sp["node"] for sp in subs} <= other_ids
        assert any(sp["node"] in other_ids for sp in subs)
        # sub-profiles are full trees: collect their kernels too
        for sp in subs:
            if sp.get("profile"):
                _walk(sp["profile"]["tree"], [], kerns)
        assert any(
            k.get("lane") in ("pallas", "xla", "host") for k in kerns
        ), f"no kernel record with a dispatch lane: {kerns}"


def test_unprofiled_query_has_no_profile_key():
    with InProcessCluster(1) as cl:
        cl.create_index("i")
        cl.create_field("i", "f")
        resp = cl.query(0, "i", "Count(Row(f=0))")
        assert "profile" not in resp


# -- slow-query log over a real cluster -------------------------------------


def test_slow_query_log_captures_faulted_fanout():
    # mesh_dispatch=False: the slowness is injected on the HTTP hop to the
    # owner; mesh dispatch would bypass the faulted transport entirely
    with InProcessCluster(2, slow_query_time=0.05, mesh_dispatch=False) as cl:
        cl.create_index("i")
        cl.create_field("i", "f")
        rs = _remote_shard(cl, "i")
        remote_node = cl.nodes.index(cl.owner_of("i", rs))
        cl.import_bits("i", "f", [(0, 0), (0, rs * SHARD_WIDTH + 5)])
        # fast queries must NOT land in the log — but the first couple
        # of distributed Counts also pay one-time jit compilation, which
        # on a cold process can cross the 50 ms bar on its own; warm
        # until that is paid, then assert the warm fast path stays out
        # of the log
        for _ in range(3):
            cl.query(0, "i", "Count(Row(f=0))")
        base_count = cl.nodes[0].api.slow_queries.snapshot()["count"]
        cl.query(0, "i", "Count(Row(f=0))")
        assert (
            cl.nodes[0].api.slow_queries.snapshot()["count"] == base_count
        )
        # stall the coordinator->owner hop past the threshold
        cl.inject_fault("slow", node=remote_node, delay=0.2)
        cl.query(0, "i", "Count(Row(f=0))")
        uri = cl.nodes[0].uri + "/debug/slow-queries"
        snap = json.load(urllib.request.urlopen(uri, timeout=10))
        assert snap["threshold"] == 0.05
        assert snap["count"] >= 1
        worst = snap["queries"][0]
        assert worst["elapsed_ms"] >= 50
        assert worst["index"] == "i"
        assert worst["profile"]["tree"]["children"]


# -- kernel telemetry exposure ----------------------------------------------


def test_kernel_series_in_metrics_and_debug_vars():
    with InProcessCluster(1) as cl:
        cl.create_index("i")
        cl.create_field("i", "f")
        cl.query(0, "i", "Set(3, f=1)")
        cl.query(0, "i", "Count(Row(f=1))")
        base = cl.nodes[0].uri
        text = (
            urllib.request.urlopen(base + "/metrics", timeout=10)
            .read()
            .decode()
        )
        assert "pilosa_kernel_dispatch" in text
        assert 'lane="' in text
        dv = json.load(
            urllib.request.urlopen(base + "/debug/vars", timeout=10)
        )
        k = dv["kernels"]
        assert sum(k["dispatch_lanes"].values()) >= 1
        assert "gram_gates" in k and "pallas_fallbacks" in k
