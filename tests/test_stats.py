"""Stats subsystem tests (reference: stats/stats_test.go, prometheus/,
http/handler.go:281-282 expvar + /metrics routes)."""

import json
import time
import urllib.request

import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.obs.stats import (
    NOP,
    MemStatsClient,
    NopStatsClient,
    prometheus_text,
)


def test_mem_counters_and_tags():
    s = MemStatsClient()
    s.count("ops")
    s.count("ops", 4)
    tagged = s.with_tags("index:i")
    tagged.count("ops")
    snap = s.snapshot()
    assert snap["counters"]["ops"] == 5
    assert snap["counters"]["ops{index:i}"] == 1


def test_with_tags_shares_storage_and_merges():
    s = MemStatsClient()
    a = s.with_tags("index:i")
    b = a.with_tags("field:f")
    b.count("set_bit")
    snap = s.snapshot()
    assert snap["counters"]["set_bit{field:f,index:i}"] == 1


def test_gauge_histogram_set():
    s = MemStatsClient()
    s.gauge("goroutines", 12)
    s.timing("snapshot", 0.5)
    s.timing("snapshot", 1.5)
    s.set_value("index", "foo")
    s.set_value("index", "foo")
    s.set_value("index", "bar")
    snap = s.snapshot()
    assert snap["gauges"]["goroutines"] == 12
    h = snap["histograms"]["snapshot_seconds"]
    assert h["count"] == 2 and h["sum"] == 2.0 and h["min"] == 0.5 and h["max"] == 1.5
    assert snap["sets"]["index"] == 2


def test_prometheus_text_rendering():
    s = MemStatsClient()
    s.with_tags("index:i", "field:f").count("set_bit", 3)
    s.gauge("maps", 7)
    s.timing("query", 0.25)
    text = prometheus_text(s)
    assert '# TYPE pilosa_set_bit counter' in text
    assert 'pilosa_set_bit{field="f",index="i"} 3' in text
    assert "pilosa_maps 7" in text
    assert "pilosa_query_seconds_count 1" in text
    assert prometheus_text(NOP) == ""


def test_nop_interface_complete():
    n = NopStatsClient()
    n.count("x")
    n.count_with_tags("x", 1, 1.0, ["a:b"])
    n.gauge("x", 1)
    n.histogram("x", 1)
    n.set_value("x", "v")
    n.timing("x", 1)
    assert n.with_tags("a:b") is n


def test_holder_wires_stats_through_creation_chain():
    h = Holder()
    mem = MemStatsClient()
    h.set_stats(mem)
    idx = h.create_index("i", track_existence=False)
    f = idx.create_field("f")
    f.set_bit(1, 1)
    f.set_bit(1, 1)  # unchanged, not counted
    f.clear_bit(1, 1)
    snap = mem.snapshot()
    assert snap["counters"]["set_bit{field:f,index:i}"] == 1
    assert snap["counters"]["clear_bit{field:f,index:i}"] == 1


def test_set_stats_retags_existing_indexes():
    h = Holder()
    idx = h.create_index("i", track_existence=False)
    f = idx.create_field("f")
    mem = MemStatsClient()
    h.set_stats(mem)  # after creation — must re-tag
    f.set_bit(0, 0)
    assert mem.snapshot()["counters"]["set_bit{field:f,index:i}"] == 1


def test_executor_query_counts():
    h = Holder()
    mem = MemStatsClient()
    h.set_stats(mem)
    idx = h.create_index("i", track_existence=False)
    idx.create_field("f").set_bit(1, 2)
    ex = Executor(h)
    ex.execute("i", 'Count(Row(f=1))')
    ex.execute("i", 'Row(f=1)')
    snap = mem.snapshot()
    # Only top-level calls are counted, matching the reference where
    # nested bitmap calls go through executeBitmapCallShard, not
    # executeCall (executor.go:298-339, :653-680).
    assert snap["counters"]["query_total{call:Count,index:i}"] == 1
    assert snap["counters"]["query_total{call:Row,index:i}"] == 1


def test_http_metrics_and_debug_vars(tmp_path):
    from pilosa_tpu.server.node import NodeServer

    # rescache off: the test asserts gram-cache counters move on repeat
    # queries, which the semantic result cache would serve first
    node = NodeServer(port=0, rescache_entries=0)
    node.start()
    try:
        base = node.uri
        node.api.create_index("i")
        node.api.create_field("i", "f")
        # go through HTTP so http_requests is exercised
        req = urllib.request.Request(
            base + "/index/i/query", data=b"Set(5, f=1)", method="POST"
        )
        urllib.request.urlopen(req, timeout=10).read()
        # request counters fire after the response bytes are sent, so a
        # fetch on another connection can race them — poll briefly
        text = ""
        for _ in range(100):
            with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
                text = r.read().decode()
            if "pilosa_http_requests" in text:
                break
            time.sleep(0.02)
        assert "pilosa_set_bit" in text
        assert "pilosa_http_requests" in text
        with urllib.request.urlopen(base + "/debug/vars", timeout=10) as r:
            snap = json.loads(r.read())
        assert any(k.startswith("set_bit") for k in snap["counters"])
        # serving-cache counters ride along (the reference's cache
        # stats analogue) and move when repeat queries hit the caches
        assert snap["serving_cache"]["gram_hits"] == 0
        q = b"Count(Intersect(Row(f=1), Row(f=1)))"
        for _ in range(12):
            req = urllib.request.Request(
                base + "/index/i/query", data=q, method="POST"
            )
            urllib.request.urlopen(req, timeout=10).read()
        with urllib.request.urlopen(base + "/debug/vars", timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["serving_cache"]["gram_hits"] >= 1
    finally:
        node.stop()


def test_parse_statsd_host_forms():
    """IPv4/hostname/IPv6 statsd host parsing (ADVICE r4: "::1" was
    mangled into host ":" port 1, bracketed forms kept brackets)."""
    from pilosa_tpu.cli import _parse_statsd_host

    assert _parse_statsd_host("10.0.0.9:9125") == ("10.0.0.9", 9125)
    assert _parse_statsd_host("statsd.local") == ("statsd.local", 8125)
    assert _parse_statsd_host("statsd.local:77") == ("statsd.local", 77)
    assert _parse_statsd_host("::1") == ("::1", 8125)
    assert _parse_statsd_host("2001:db8::2") == ("2001:db8::2", 8125)
    assert _parse_statsd_host("[::1]:9125") == ("::1", 9125)
    assert _parse_statsd_host("[2001:db8::2]") == ("2001:db8::2", 8125)
    assert _parse_statsd_host("") == ("127.0.0.1", 8125)
    assert _parse_statsd_host("host:notaport") == ("host", 8125)


def test_histogram_snapshot_carries_inf_overflow_bucket():
    from pilosa_tpu.obs.stats import HISTOGRAM_BUCKETS

    s = MemStatsClient()
    s.timing("op", 0.002)
    s.timing("op", 9999.0)  # past the largest bound: overflow only
    h = s.snapshot()["histograms"]["op_seconds"]
    buckets = h["buckets"]
    assert buckets["+Inf"] == 2  # cumulative: every observation lands here
    assert buckets[str(HISTOGRAM_BUCKETS[-1])] == 1  # overflow excluded
    # the overflow observation is recoverable: +Inf minus the top bound
    assert buckets["+Inf"] - buckets[str(HISTOGRAM_BUCKETS[-1])] == 1


def test_histogram_buckets_resolve_sub_millisecond():
    from pilosa_tpu.obs.stats import HISTOGRAM_BUCKETS

    # host-served reads finish well under a millisecond; bucket edges
    # below 1 ms keep those observations distinguishable
    sub_ms = [b for b in HISTOGRAM_BUCKETS if b < 0.001]
    assert len(sub_ms) >= 4
    assert min(HISTOGRAM_BUCKETS) <= 0.00005
    s = MemStatsClient()
    s.timing("fast", 0.00007)
    s.timing("fast", 0.00090)
    buckets = s.snapshot()["histograms"]["fast_seconds"]["buckets"]
    # cumulative counts: the 0.07 ms observation is visible below the
    # 0.25 ms edge, separated from the 0.9 ms one
    assert buckets["0.0001"] == 1
    assert buckets["0.001"] == 2


def test_prometheus_label_values_escaped_hostile_tenant():
    # a hostile tenant name must not be able to forge metric lines or
    # break strict exposition parsers
    s = MemStatsClient()
    s.with_tags('tenant:evil"} 1\nforged_metric 9').count("shed", 2)
    s.with_tags("tenant:back\\slash").count("shed")
    text = prometheus_text(s)
    assert (
        'pilosa_shed{tenant="evil\\"} 1\\nforged_metric 9"} 2' in text
    ), text
    assert 'pilosa_shed{tenant="back\\\\slash"} 1' in text
    # no forged line escaped into the exposition
    assert not any(
        line.startswith("forged_metric") for line in text.splitlines()
    )
    # every payload line stays "name{labels} value" shaped
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert line.rsplit(" ", 1)[1] != "", line


def test_prometheus_le_labels_escape_and_order():
    s = MemStatsClient()
    s.with_tags('tenant:q"ote').timing("op", 0.002)
    text = prometheus_text(s)
    bucket_lines = [
        l for l in text.splitlines()
        if l.startswith("pilosa_op_seconds_bucket")
    ]
    assert bucket_lines, text
    assert all('tenant="q\\"ote"' in l for l in bucket_lines)
    assert all('le="' in l for l in bucket_lines)


def test_prometheus_help_precedes_type_for_registered_families():
    from pilosa_tpu.obs.stats import describe

    s = MemStatsClient()
    s.count("set_bit", 1)
    s.count("some_unregistered_counter", 1)
    text = prometheus_text(s)
    lines = text.splitlines()
    i = lines.index("# TYPE pilosa_set_bit counter")
    assert lines[i - 1].startswith("# HELP pilosa_set_bit "), lines[i - 1]
    # unregistered families stay byte-identical: TYPE but no HELP
    j = lines.index("# TYPE pilosa_some_unregistered_counter counter")
    assert not lines[j - 1].startswith(
        "# HELP pilosa_some_unregistered_counter"
    )
    # registration is live and HELP text is newline-escaped
    describe("pilosa_some_unregistered_counter", "now\ndocumented")
    text = prometheus_text(s)
    assert (
        "# HELP pilosa_some_unregistered_counter now\\ndocumented" in text
    )
