"""The reduction from trace to device numbers, on a recorded trace and on
made-up planes."""

import os

import trace_reduce as tr

TPU_TRACE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                         "testdata", "small_tpu.xplane.pb")


def test_union_and_gaps():
    busy, gaps = tr.union_seconds([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)])
    assert busy == 3.0
    assert [round(g, 6) for g, _ in gaps] == [1.0]


def test_per_op_sums_average_over_devices():
    planes = [("/device:TPU:0", [("a", 0.0, 1.0), ("b", 1.0, 1.5), ("a", 4.0, 5.0)]),
              ("/device:TPU:1", [("a", 0.0, 1.0)])]
    out = tr.reduce_planes(planes, stand_in=False)
    assert out["busy_s"] == (2.5 + 1.0) / 2 and out["busy_s_busiest"] == 2.5
    assert out["device_ops"][0] == ["a", 1.5] and out["op_count"] == 4
    assert out["idle_gaps"][0] == ["before a", 2.5]


def test_empty_trace_gives_zeros_not_missing_keys(tmp_path):
    out = tr.reduce_trace(str(tmp_path))
    assert out["busy_s"] == 0.0 and out["op_seconds"] == 0.0 and out["op_count"] == 0
    assert out["device_ops"] == [] and out["idle_gaps"] == []


def test_recorded_tpu_trace():
    """Recorded on a v5e (PR 24): three rounds of a 512x512 bf16 matmul
    and a popcount over 65,536 words, each a few microseconds of device
    time with milliseconds of sleep between them."""
    out = tr.reduce_trace(TPU_TRACE)
    assert out["stand_in"] is False and len(out["devices"]) == 1
    assert out["op_count"] >= 6
    assert 0 < out["busy_s"] <= out["op_seconds"] * 1.0001 + 1e-9
    assert out["busy_s"] < 0.005  # microseconds of work; the sleeps are not busy time
    assert out["idle_gaps"][0][1] > 0.001
