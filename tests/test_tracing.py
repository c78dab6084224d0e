"""Tracing tests (reference: tracing/tracing.go Tracer/Span global
instance, tracing/opentracing/opentracing.go HTTP inject/extract,
cross-node trace propagation through the internal client)."""

import pytest

from pilosa_tpu.obs import tracing
from pilosa_tpu.obs.tracing import (
    SPAN_HEADER,
    TRACE_HEADER,
    NopTracer,
    RecordingTracer,
    SpanContext,
)


@pytest.fixture
def recorder():
    old = tracing.get_tracer()
    rec = RecordingTracer()
    tracing.set_tracer(rec)
    yield rec
    tracing.set_tracer(old)


def test_span_records_on_finish(recorder):
    with tracing.start_span("test.op") as s:
        s.set_tag("k", "v")
    spans = recorder.finished("test.op")
    assert len(spans) == 1
    assert spans[0].tags["k"] == "v"
    assert spans[0].duration >= 0


def test_ambient_parenting(recorder):
    with tracing.start_span("test.parent") as p:
        with tracing.start_span("test.child") as c:
            assert c.parent_id == p.context.span_id
            assert c.context.trace_id == p.context.trace_id
    # after both exit, a new span roots a fresh trace
    with tracing.start_span("test.other") as o:
        assert o.parent_id == 0
        assert o.context.trace_id != p.context.trace_id


def test_inject_extract_roundtrip():
    t = NopTracer()
    ctx = SpanContext(42, 99)
    headers: dict = {}
    t.inject_headers(ctx, headers)
    # native headers plus the W3C traceparent twin
    assert headers[TRACE_HEADER] == "42"
    assert headers[SPAN_HEADER] == "99"
    assert headers[tracing.TRACEPARENT_HEADER] == (
        "00-" + "0" * 30 + "2a-" + "0" * 14 + "63-01"
    )
    got = t.extract_headers(headers)
    assert (got.trace_id, got.span_id) == (42, 99)
    assert got.remote is True
    assert t.extract_headers({}) is None
    assert t.extract_headers({TRACE_HEADER: "x", SPAN_HEADER: "1"}) is None


def test_executor_emits_spans(recorder):
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.exec.executor import Executor

    h = Holder()
    idx = h.create_index("t", track_existence=False)
    idx.create_field("f").set_bit(1, 2)
    Executor(h).execute("t", "Count(Row(f=1))")
    names = {s.name for s in recorder.finished()}
    assert "executor.Execute" in names
    assert "executor.executeCount" in names
    # nested call span parents under the Execute span
    exec_span = recorder.finished("executor.Execute")[0]
    count_span = recorder.finished("executor.executeCount")[0]
    assert count_span.context.trace_id == exec_span.context.trace_id


def test_cross_node_trace_joins(recorder):
    """A distributed query fans out over HTTP; the remote node's handler
    span must join the coordinator's trace via the injected headers."""
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.testing import InProcessCluster

    # this test is ABOUT the HTTP relay's header propagation; mesh-local
    # dispatch would answer in-process with no hop to join
    with InProcessCluster(2, mesh_dispatch=False) as c:
        c.create_index("tr")
        c.create_field("tr", "f")
        c.import_bits("tr", "f", [(1, 3)])  # shard 0 only
        # query from the node that does NOT own shard 0 → guaranteed hop
        owner = c.owner_of("tr", 0)
        non_owner = next(i for i, n in enumerate(c.nodes) if n is not owner)
        recorder.spans.clear()
        out = c.query(non_owner, "tr", "Count(Row(f=1))")
        assert out["results"][0] == 1
        # the remote handler span finishes in another thread right before
        # the coordinator gets its response; give it a beat
        import time

        time.sleep(0.2)
    by_trace = recorder.traces()
    # the coordinator's executor trace must contain the REMOTE node's
    # http.query handler span, joined via the injected headers
    for spans in by_trace.values():
        names = [s.name for s in spans]
        if "executor.mapReduce" in names and "http.query" in names:
            break
    else:
        pytest.fail(
            f"no joined cross-node trace: "
            f"{[[s.name for s in v] for v in by_trace.values()]}"
        )


def test_field_import_span(recorder):
    from pilosa_tpu.core.holder import Holder

    h = Holder()
    f = h.create_index("imp", track_existence=False).create_field("f")
    f.import_bits([1, 2], [10, 20])
    spans = recorder.finished("field.Import")
    assert len(spans) == 1 and spans[0].tags["bits"] == 2
