"""The grid's four-chip cell beside a write stream, ``taxi-x4.ingest-serve``,
rehearsed on the suite's CPU devices through the benchmark's own command: the
configuration is ``taxi-x4``'s record at six shards a chip, half loaded, the
traffic is ``taxi.ingest-serve``'s file, and a rehearsal's line is the
manifest's, with every per-layer reader of the cell returning over a serving
mesh: among them the three that say which route a stack's refresh took
(``stacks.refresh`` and its host and peer bytes: over a mesh, as on one
device, every block is gathered on the chip that holds the shard).  The judge
is the benchmark's own (``benchmark/reference.py``, every sampled read held to
"an acknowledged import is visible").  A rehearsal is never a pass: exit 3,
``correct`` false, and ``rehearsal`` over its limit: alone, or with
``stream_slabs_short``, which the clock of a loaded CPU decides."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import manifest as mf  # noqa: E402
from test_benchmark_ingest import FLIGHT_MS, SUM_DEVICE, flight_ms_of_the_log, over_limit  # noqa: E402

CELL = "taxi-x4.ingest-serve"
MANIFEST = mf.load()
# what the cell reads that no cell of one chip without a stream does
STREAMED = [
    "ingest.import_ack_p95_ms", "ingest.stream_late_ms",
    "rescache.invalidations_per_import", "stacks.refreshes_per_import",
    "stacks.rebuild_share_pct", "stacks.refresh_ms_per_import",
    "stacks.refresh_host_mb_per_import",
]
MESHED = [
    "mesh.sharded_launch_pct", "executor.lane_declines_per_read",
    "device.busy_spread_pct",
]
OWN = "stacks.refresh_peer_mb_per_import"
# PR 42: a counter both stream cells had and no run logged
OUT_OF_PLACE = "stacks.refresh_out_of_place_per_import"


def test_the_configuration_is_taxi_x4s_record_half_loaded_at_six_shards_a_chip():
    x4 = mf.read_json("benchmark/configs/taxi-x4.json")
    ingest = mf.read_json("benchmark/configs/taxi-ingest.json")
    cfg = mf.read_json(mf.config_entry(MANIFEST, "taxi-x4-ingest")["file"])
    differ = {k for k in x4.keys() | cfg.keys() if x4.get(k) != cfg.get(k)}
    assert differ == {"name", "source", "deployment", "shards", "columns", "reduced_why",
                      "assumed", "rehearsal"}
    assert cfg["assumed"][:len(x4["assumed"])] == x4["assumed"]
    assert cfg["columns"] * 2 == 1 << cfg["shard_width_exp"]  # the other half is the stream's
    assert cfg["columns"] == ingest["columns"] and cfg["slab_rides"] == ingest["slab_rides"]
    # ISSUE 40 asked for 32 with 24 and 16 as fallbacks decided by a cold run: 24 (reduced_why)
    assert cfg["shards"] == 24 and cfg["shards"] % cfg["chips"] == 0
    assert "731 s" in cfg["reduced_why"] and "NOT MEASURED at 24: the parent" in cfg["reduced_why"]
    assert cfg["chips"] == 4 and cfg["rehearsal"]["shards"] == 4
    assert cfg["guarantees"] == ingest["guarantees"] == x4["guarantees"]
    assert cfg["published"]["shards"] == 1049 and cfg["reduced"] == ["shards"]
    entry = mf.config_entry(MANIFEST, "taxi-x4-ingest")
    assert cfg["source"] == entry["source"] and entry["reduced"] == ["shards"]
    assert len({cfg["source"], x4["source"], ingest["source"]}) == 3


def test_the_mixs_three_level_groupby_keeps_its_batch_path_at_this_size():
    """What 32 shards met (my chip runs, PR 40): the k-level GroupBy held its
    prefix masks ``[C, S, W]`` to a budget of the whole array, though they are
    split over the mesh as the stack is, and past it a call takes the recursive
    path, 10 s of the dispatcher each on the chip.  The budget is one chip's
    share now: the mix's deepest call, rows(passenger_count) x
    rows(pickup_year) prefixes after its second level, fits at the committed
    size and at the 32 ISSUE 40 asked for, by the program's own reckoning."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from pilosa_tpu.exec.executor import Executor

    cfg = mf.read_json(mf.config_entry(MANIFEST, "taxi-x4-ingest")["file"])
    mix = mf.read_json("benchmark/traffic/ingest-serve-c32.json")
    deepest = "GroupBy(Rows(passenger_count), Rows(pickup_year), Rows(dist_miles))"
    assert deepest in mix["classes"]["groupby3"]["variants"]
    rows = {f["name"]: f["rows"] for f in cfg["fields"] if f["kind"] == "set"}
    prefixes = rows["passenger_count"] * rows["pickup_year"]
    words = (1 << cfg["shard_width_exp"]) // 32
    over = NamedSharding(
        Mesh(np.array(jax.devices()[:cfg["chips"]]), ("shards",)),
        PartitionSpec("shards", None, None),
    )
    for shards in (cfg["shards"], 32):
        stack = jax.ShapeDtypeStruct((shards, 1, words), np.uint32, sharding=over)
        assert Executor._groupby_prefix_max(stack) >= 2 * prefixes


def test_the_cell_is_the_new_configuration_under_the_stream_cells_traffic_on_four_chips():
    cell = mf.cell(MANIFEST, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "taxi-x4-ingest", "ingest-serve-c32", 4)
    assert mf.cell(MANIFEST, "taxi.ingest-serve")["traffic"] == cell["traffic"]
    four = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert four == ["taxi-x4.dashboard-c32", CELL]
    assert len(four) <= len(MANIFEST["workloads"]) // 2


def test_the_cells_metrics_are_the_streams_the_meshs_and_its_own():
    listed = [m["name"] for m in MANIFEST["per_layer"] if CELL in m.get("workloads", ())]
    assert listed == MESHED + STREAMED + [OWN, OUT_OF_PLACE]
    own = next(m for m in MANIFEST["per_layer"] if m["name"] == OWN)
    assert own["workloads"] == [CELL]  # the entry exists and lists the cell alone
    assert (own["layer"], own["moves"], own["source"], own["better"], own["unit"]) == (
        "executor lanes", "read_p95_ms", "program_counter", "lower", "MB")
    # a reader that is code, so that a tree without the counter reads 0
    assert os.path.exists(os.path.join(REPO, "benchmark", "layer_metrics", OWN + ".py"))
    for m in MANIFEST["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL  # appended, nothing else moved


def test_the_peer_reader_reads_zero_on_a_tree_without_the_counter():
    import run

    window = {"imports": 252}
    assert run.read_layer_metric(OWN, {"vars": {"serving_cache": {}}, "window": window}) == 0.0
    assert run.read_layer_metric(OWN, {"vars": {}, "window": window}) == 0.0
    assert run.read_layer_metric(
        OWN, {"vars": {"serving_cache": {"stack_refresh_peer_bytes": 504e6}}, "window": window}
    ) == pytest.approx(2.0)
    assert run.read_layer_metric(
        OWN, {"vars": {"serving_cache": {"stack_refresh_peer_bytes": 5}}, "window": {"imports": 0}}
    ) == 0.0


@pytest.mark.parametrize("lane, reads", [
    (None, 0.0),  # a tree without the counter
    ({"groupby_lane_inflight_sum": 0, "groupby_lane_pulls": 0}, 0.0),  # a window without a pull
    ({"groupby_lane_inflight_sum": 57, "groupby_lane_pulls": 12}, 4.75),
], ids=["no-counter", "no-pull", "ratio"])
def test_the_groupby_lanes_reader_reads_levels_in_flight_a_pull(lane, reads):
    """PR 41's metric, which every cell reports (no ``workloads`` list: it
    moves ``read_qps``): a reader that is code, 1.0 the call-by-call order."""
    import run

    name = "executor.groupby_inflight_per_pull"
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert "workloads" not in entry and (entry["layer"], entry["moves"], entry["source"], entry["better"]) == (
        "executor lanes", "read_qps", "program_counter", "higher")
    served = {} if lane is None else {"serving_cache": dict(lane, stack_rebuilds=3)}
    assert run.read_layer_metric(name, {"vars": served, "window": {"reads": 300}}) == pytest.approx(reads)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_line_is_the_manifests(tmp_path, trace):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"), "--rehearsal",
         "--workload", CELL, "--seed", "29", "--seconds", "3", "--trace", str(trace),
         "--limit", "300"],
        cwd=REPO, env=dict(os.environ, TMPDIR=str(tmp_path)), capture_output=True, text=True)
    err = p.stderr[-3000:]
    assert p.returncode == 3, err
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert mf.validate_line(MANIFEST, CELL, bool(trace), line) == []
    want = [m["name"] for m in mf.metrics_for(MANIFEST, CELL, bool(trace))]
    assert list(line["metrics"]) == want and len(want) == (38 if trace else 3)
    assert line["correct"] is False
    compared = {k: v for k, (v, _) in line["compared"].items()}
    assert over_limit(line) == {"rehearsal": 1}, err
    assert compared["read_mismatches"] == compared["readback_mismatches"] == 0
    assert {"imports_failed", "stream_slabs_short", "classes_unjudged", "window_compiles",
            "failed_requests"} <= set(compared)
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 4
    if trace:
        value = {k: v["value"] for k, v in line["metrics"].items()}
        assert [n for n in want if n in STREAMED] == STREAMED
        assert [n for n in want if n in MESHED] == MESHED and OWN in want
        # counts, not times: stacks laid over the mesh were refreshed, none was
        # rebuilt, and every block was gathered on the chip that keeps it
        assert value["stacks.refreshes_per_import"] > 0, err
        assert value["stacks.rebuild_share_pct"] == 0
        assert value["stacks.refresh_ms_per_import"] > 0
        assert value["stacks.refresh_host_mb_per_import"] == 0
        assert value[OWN] == 0 and value[OUT_OF_PLACE] >= 0
        # a flight's wall time in three: interpreter, device wait, and the rest
        assert value[FLIGHT_MS[0]] > 0 and value[FLIGHT_MS[1]] > 0, err
        assert sum(value[n] for n in FLIGHT_MS) == flight_ms_of_the_log(p.stderr)
        # beside the stream and over the mesh, every filtered Sum's filter was built on the device (PR 43)
        assert want[-1] == SUM_DEVICE and value[SUM_DEVICE] == 100, err
        # the reads ran over the mesh; a refresh's launch is one chip's
        assert 50 < value["mesh.sharded_launch_pct"] <= 100, err
    assert os.listdir(tmp_path) == [], "the run left its work directory"
