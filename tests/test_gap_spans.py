"""tools/gap_spans.py on a trace recorded on a v5e with the program's
annotations in it, and on made-up spans.

``tests/testdata/spans_tpu.xplane.pb`` (PR 25, ``python tools/gap_spans.py
--record``): one in-process node, three connections sending pair counts, a
filtered TopN and BSI range counts for a few rounds.  Three concurrent range
counts met a batch size the warm-up had not, so one flight compiled inside
the session: the longest device gap is that compile, on ``kernels.enqueue``
under the BSI lane.
"""

import os

import pytest

from tools import gap_spans

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata", "spans_tpu.xplane.pb")


def test_gaps_are_the_benchmarks_and_named_by_the_innermost_span():
    import trace_reduce  # the benchmark's, as the tool imports it

    out = gap_spans.gap_spans(TRACE, top=10)
    reduced = trace_reduce.reduce_trace(TRACE)
    assert out["stand_in"] is False and reduced["stand_in"] is False
    # the same gaps as the benchmark's breakdown, longest first
    assert [round(g["seconds"], 9) for g in out["gaps"]] == [round(s, 9) for _, s in reduced["idle_gaps"]]
    assert len(out["dispatcher"]) == 1
    assert out["gap_seconds"] == pytest.approx(sum(out["by_span"].values()))
    first = out["gaps"][0]
    assert first["span"] == "kernels.enqueue" and first["covered"] > 0.9
    assert first["chain"] == ["batcher.flight", "executor.ExecuteBatch", "executor.batchBSI",
                              "executor.bsiRangeCountBatch", "kernels.enqueue"]
    named = {g["span"] for g in out["gaps"]}
    assert {"idle", "executor.bsiSplit"} <= named  # the dispatcher in queue.get; a lane's host code
    seen = out["spans_seen"][out["dispatcher"][0]]
    assert {"batcher.collect", "batcher.flight", "executor.batchPairCount", "executor.executeTopN",
            "kernels.h2d", "kernels.enqueue", "kernels.pull", "executor.demux"} <= set(seen)
    # the handler threads' spans are in the trace too, on lines of their own
    assert any("http.query" in names and "batcher.flight" not in names for names in out["spans_seen"].values())


@pytest.mark.parametrize("gap,want", [
    ((1.0, 2.0), ("kernels.pull", 1.0, ["batcher.flight", "executor.batchBSI", "kernels.pull"])),
    ((2.5, 3.5), ("executor.batchBSI", 0.9, ["batcher.flight", "executor.batchBSI"])),  # the pull covers under half
    ((4.8, 6.0), ("idle", 0.0, [])),  # the flight covers a sixth of it: queue.get
    ((3.5, 4.5), ("batcher.flight", 1.0, ["batcher.flight"])),
])
def test_innermost_span_open_through_most_of_the_gap(gap, want):
    spans = [("batcher.flight", 0.0, 5.0), ("executor.batchBSI", 0.5, 3.4), ("kernels.pull", 0.9, 2.9),
             ("kernels.h2d", 0.6, 0.7)]
    name, share, chain = gap_spans.name_gap(gap, spans)
    assert (name, chain) == (want[0], want[2]) and share == pytest.approx(want[1])
