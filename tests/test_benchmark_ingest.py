"""The grid's cell beside a write stream, ``taxi.ingest-serve``, rehearsed on
the suite's CPU through the benchmark's own command: the configuration is
``taxi``'s record while it is still being imported, the readers are
``dashboard-c32``'s, and a rehearsal's line is the manifest's, with every
per-layer reader of the cell returning: among them the two that read the
in-place refresh of a field's stack (``stacks.refresh`` and its host bytes).
The judge is the benchmark's own (``benchmark/reference.py``, every sampled
read held to "an acknowledged import is visible").  A rehearsal is never a
pass: exit 3, ``correct`` false, and ``rehearsal`` over its limit: alone, or
with ``stream_slabs_short``, which the clock of a loaded CPU decides."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import manifest as mf  # noqa: E402

CELL = "taxi.ingest-serve"
# the cells beside a stream: this one, and since PR 40 the four-chip server's
STREAM_CELLS = [CELL, "taxi-x4.ingest-serve"]
MANIFEST = mf.load()
# what a stream adds to a cell's per-layer metrics (each lists the stream's cells alone)
STREAMED = [
    "ingest.import_ack_p95_ms", "ingest.stream_late_ms",
    "rescache.invalidations_per_import", "stacks.refreshes_per_import",
    "stacks.rebuild_share_pct", "stacks.refresh_ms_per_import",
    "stacks.refresh_host_mb_per_import",
]
# PR 42: a flight's wall time in three, from the span table's CPU clock
FLIGHT_MS = ["batcher.cpu_ms_per_flight", "batcher.device_wait_ms_per_flight",
             "batcher.stalled_ms_per_flight"]
# PR 43: the share of filtered Sums whose filter was built on the device
SUM_DEVICE = "executor.sum_filter_device_pct"


def over_limit(line: dict) -> dict:
    """What a rehearsal's line has over its limit, less the one count the clock
    decides: a 3 s traced window on a loaded CPU acknowledges 2 of its 3 slabs."""
    over = {k: v for k, (v, limit) in line["compared"].items() if v > limit}
    over.pop("stream_slabs_short", None)
    return over


def flight_ms_of_the_log(stderr: str):
    """``spans.batcher.flight`` seconds a flight of the window, in ms, from the
    run's own log of the window's span table (count / seconds / self seconds),
    as what ``pytest.approx`` holds a sum to: the log rounds to a millisecond."""
    count, seconds = re.search(r"batcher\.flight (\d+) / ([\d.]+) /", stderr).groups()
    return pytest.approx(1000.0 * float(seconds) / int(count), abs=0.51 / int(count))


def test_the_configuration_is_taxis_record_half_loaded():
    taxi = mf.read_json("benchmark/configs/taxi.json")
    ingest = mf.read_json(mf.config_entry(MANIFEST, "taxi-ingest")["file"])
    differ = {k for k in taxi.keys() | ingest.keys() if taxi.get(k) != ingest.get(k)}
    assert differ == {"name", "source", "deployment", "columns", "reduced_why", "assumed",
                      "rehearsal"}
    assert ingest["assumed"][:len(taxi["assumed"])] == taxi["assumed"]
    assert ingest["shards"] == taxi["shards"] == 8 and ingest["reduced"] == ["shards"]
    assert ingest["columns"] * 2 == 1 << ingest["shard_width_exp"]  # the other half is the stream's
    assert ingest["guarantees"] == taxi["guarantees"]
    assert ingest["source"] == mf.config_entry(MANIFEST, "taxi-ingest")["source"] != taxi["source"]


def test_the_readers_are_dashboard_c32s_and_the_pace_is_the_issues():
    dash = mf.read_json("benchmark/traffic/dashboard-c32.json")
    mix = mf.read_json("benchmark/traffic/ingest-serve-c32.json")
    differ = {k for k in dash.keys() | mix.keys() if dash.get(k) != mix.get(k)}
    assert differ == {"name", "stream", "rehearsal"}
    assert mix["stream"]["every_s"] == 4.0
    cell = mf.cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("taxi-ingest", mix["name"], 1)


@pytest.mark.parametrize("cell", STREAM_CELLS)
def test_the_cells_own_metrics_list_it_alone_and_have_data_readers(cell):
    """The stream's seven list the stream's cells and no other, in the order
    the cells entered the grid, and each has a reader that is data."""
    own = [m for m in MANIFEST["per_layer"]
           if cell in m.get("workloads", ()) and m["name"] in STREAMED]
    assert [m["name"] for m in own] == STREAMED
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in own:
        assert m["workloads"] == STREAM_CELLS and m["moves"] in e2e
        assert os.path.exists(os.path.join(REPO, "benchmark", "layer_metrics", m["name"] + ".json"))
    mix = mf.cell(MANIFEST, cell)["traffic"]
    assert "stream" in mf.read_json(f"benchmark/traffic/{mix}.json")
    # and no cell without a stream lists one of them
    for m in MANIFEST["per_layer"]:
        if m["name"] in STREAMED:
            assert set(m["workloads"]) == set(STREAM_CELLS)


def _row(count=0, seconds=0.0, **more):
    return dict({"count": count, "seconds": seconds, "self_seconds": seconds, "items": 0}, **more)


def _spans(flight, query, queue_wait=0.0, dispatch=0.0):
    n = query["count"]
    return {"batcher": {"flight": flight, "queueWait": _row(n, queue_wait), "dispatch": _row(n, dispatch)},
            "http": {"query": query}}


# 40 flights of 0.5 s: 0.2 s of interpreter, 0.25 s of device wait, 0.05 s of neither; 300 reads of
# 0.1 s: 1 ms of interpreter, 95 ms in the queue and the flight, 4 ms of neither
_TIMED = dict(cpu_seconds=8.0, self_cpu_seconds=1.0, device_wait_seconds=10.0)
_FLOWN = _spans(_row(40, 20.0, **_TIMED), _row(300, 30.0, cpu_seconds=0.3, self_cpu_seconds=0.3,
                                               device_wait_seconds=0.0), queue_wait=16.5, dispatch=12.0)
_IDLE = _spans(_row(0, 0.0, cpu_seconds=0.0, self_cpu_seconds=0.0, device_wait_seconds=0.0),
               _row(0, 0.0, cpu_seconds=0.0, self_cpu_seconds=0.0, device_wait_seconds=0.0))


@pytest.mark.parametrize("spans, column", [
    (None, 0), (_spans(_row(40, 20.0), _row(300, 30.0), 16.5, 12.0), 0), (_IDLE, 0), (_FLOWN, 1),
], ids=["no-table", "no-column", "no-flight", "ratio"])
@pytest.mark.parametrize("name, layer, reads", [
    (FLIGHT_MS[0], "QoS / batcher", 200.0), (FLIGHT_MS[1], "QoS / batcher", 250.0),
    (FLIGHT_MS[2], "QoS / batcher", 50.0), ("listener.stalled_ms_per_read", "listener", 4.0),
])
def test_the_cpu_clocks_readers_read_zero_without_the_column_and_a_ratio_with_it(name, layer, reads, spans,
                                                                                 column):
    """PR 42's four, which every cell reports: readers that are code, so that a
    tree whose rows lack the column (the parent, under this overlay) reads 0."""
    import run

    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert "workloads" not in entry and entry["unit"] == "ms"
    assert (entry["layer"], entry["moves"], entry["source"], entry["better"]) == (
        layer, "read_qps", "program_span", "lower")
    served = {} if spans is None else {"spans": spans}
    assert run.read_layer_metric(name, {"vars": served, "window": {"reads": 300}}) == pytest.approx(
        reads * column)


def test_the_three_of_a_flight_add_up_and_the_counters_readers_are_data():
    import run

    ctx = {"vars": {"spans": _FLOWN, "serving_cache": {
        "stack_refresh_out_of_place": 3, "groupby_lane_budget_waits": 6, "groupby_lane_pulls": 48}},
        "window": {"imports": 252}}
    flight = _FLOWN["batcher"]["flight"]
    assert sum(run.read_layer_metric(n, ctx) for n in FLIGHT_MS) == pytest.approx(
        1000.0 * flight["seconds"] / flight["count"])
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    out_of_place, waits = "stacks.refresh_out_of_place_per_import", "executor.groupby_budget_waits_per_pull"
    assert by_name[out_of_place]["workloads"] == STREAM_CELLS and "workloads" not in by_name[waits]
    assert (by_name[out_of_place]["moves"], by_name[waits]["moves"]) == ("read_p95_ms", "read_qps")
    for name in (out_of_place, waits):  # counters the parent keeps too: data is enough
        assert by_name[name]["source"] == "program_counter" and by_name[name]["better"] == "lower"
        assert os.path.exists(os.path.join(REPO, "benchmark", "layer_metrics", name + ".json"))
    assert run.read_layer_metric(out_of_place, ctx) == pytest.approx(3 / 252)
    assert run.read_layer_metric(waits, ctx) == pytest.approx(0.125)
    ctx["vars"]["serving_cache"]["groupby_lane_pulls"] = 0  # a window in which the lane pulled nothing
    assert run.read_layer_metric(waits, ctx) == 0.0
    # PR 42's six entries in the order they were appended, then PR 43's one
    assert [m["name"] for m in MANIFEST["per_layer"][-7:]] == FLIGHT_MS + [
        "listener.stalled_ms_per_read", out_of_place, waits, SUM_DEVICE]


def test_the_sum_lanes_share_reads_its_two_counters_and_zero_where_there_are_none():
    import run

    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == SUM_DEVICE)
    assert entry == {"name": SUM_DEVICE, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "executor lanes", "moves": "read_qps"}  # no list: every cell sends filtered Sums
    assert os.path.exists(os.path.join(REPO, "benchmark", "layer_metrics", SUM_DEVICE + ".py"))

    def read(cache):
        return run.read_layer_metric(SUM_DEVICE, {"vars": {"serving_cache": cache}})

    assert read({"sum_lane_device_filters": 57, "sum_lane_host_filters": 3}) == pytest.approx(95.0)
    assert read({"sum_lane_device_filters": 8, "sum_lane_host_filters": 0}) == 100.0
    assert read({"sum_lane_device_filters": 0, "sum_lane_host_filters": 4}) == 0.0
    assert read({"sum_lane_device_filters": 0, "sum_lane_host_filters": 0}) == 0.0  # no filtered Sum
    assert read({"groupby_lane_pulls": 9}) == 0.0  # a program without the counter
    assert run.read_layer_metric(SUM_DEVICE, {"vars": {}}) == 0.0


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_line_is_the_manifests(tmp_path, trace):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--rehearsal",
         "--workload", CELL, "--seed", "29", "--seconds", "3", "--trace", str(trace),
         "--limit", "300"],
        cwd=REPO, env=dict(os.environ, TMPDIR=str(tmp_path),
                           XLA_FLAGS="--xla_force_host_platform_device_count=1"),
        capture_output=True, text=True)
    err = p.stderr[-3000:]
    assert p.returncode == 3, err
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert mf.validate_line(MANIFEST, CELL, bool(trace), line) == []
    want = [m["name"] for m in mf.metrics_for(MANIFEST, CELL, bool(trace))]
    assert list(line["metrics"]) == want and len(want) == (34 if trace else 3)
    assert line["correct"] is False
    compared = {k: v for k, (v, _) in line["compared"].items()}
    assert over_limit(line) == {"rehearsal": 1}, err
    assert compared["read_mismatches"] == compared["readback_mismatches"] == 0
    assert {"imports_failed", "stream_slabs_short", "classes_unjudged", "window_compiles",
            "failed_requests"} <= set(compared)
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    if trace:
        value = {k: v["value"] for k, v in line["metrics"].items()}
        # the stream's own, then what later PRs gave every cell
        assert [n for n in want if n in STREAMED] == STREAMED
        assert want[-9:-7] == ["listener.cpu_ms_per_read", "executor.groupby_inflight_per_pull"]
        assert value[want[-9]] > 0 and value[want[-8]] >= 1, err  # groupby3 rode the lane
        assert want[-7:-3] == FLIGHT_MS + ["listener.stalled_ms_per_read"]
        assert want[-3:] == ["stacks.refresh_out_of_place_per_import",
                             "executor.groupby_budget_waits_per_pull", SUM_DEVICE]
        assert value[want[-3]] >= 0 and value[want[-2]] == 0  # the tiny shape is far under the lane's bound
        # every filtered Sum of the window had its filter built on the device (PR 43)
        assert value[SUM_DEVICE] == 100, err
        # a flight's wall time in three: interpreter, device wait, and the rest
        assert value[FLIGHT_MS[0]] > 0 and value[FLIGHT_MS[1]] > 0, err
        assert sum(value[n] for n in FLIGHT_MS) == flight_ms_of_the_log(p.stderr)
        # counts, not times: stacks were refreshed, none was rebuilt, and on one
        # device every block came from a fragment's device copy
        assert value["stacks.refreshes_per_import"] > 0, err
        assert value["stacks.rebuild_share_pct"] == 0
        assert value["stacks.refresh_ms_per_import"] > 0
        assert value["stacks.refresh_host_mb_per_import"] == 0
    assert os.listdir(tmp_path) == [], "the run left its work directory"
