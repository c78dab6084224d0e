"""The grid's cell beside a write stream, ``taxi.ingest-serve``, rehearsed on
the suite's CPU through the benchmark's own command: the configuration is
``taxi``'s record while it is still being imported, the readers are
``dashboard-c32``'s, and a rehearsal's line is the manifest's, with every
per-layer reader of the cell returning: among them the two that read the
in-place refresh of a field's stack (``stacks.refresh`` and its host bytes).
The judge is the benchmark's own (``benchmark/reference.py``, every sampled
read held to "an acknowledged import is visible").  A rehearsal is never a
pass: exit 3, ``correct`` false, and ``rehearsal`` the one number over its
limit."""

import json
import os
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
    assert list(line["metrics"]) == want and len(want) == (27 if trace else 3)
    assert line["correct"] is False
    compared = {k: v for k, (v, _) in line["compared"].items()}
    assert {k: v for k, (v, limit) in line["compared"].items() if v > limit} == {"rehearsal": 1}, err
    assert compared["read_mismatches"] == compared["readback_mismatches"] == 0
    assert {"imports_failed", "stream_slabs_short", "classes_unjudged", "window_compiles",
            "failed_requests"} <= set(compared)
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    if trace:
        value = {k: v["value"] for k, v in line["metrics"].items()}
        # the stream's own, then what later PRs gave every cell
        assert [n for n in want if n in STREAMED] == STREAMED
        assert want[-2:] == ["listener.cpu_ms_per_read", "executor.groupby_inflight_per_pull"]
        assert value[want[-2]] > 0 and value[want[-1]] >= 1, err  # groupby3 rode the lane
        # counts, not times: stacks were refreshed, none was rebuilt, and on one
        # device every block came from a fragment's device copy
        assert value["stacks.refreshes_per_import"] > 0, err
        assert value["stacks.rebuild_share_pct"] == 0
        assert value["stacks.refresh_ms_per_import"] > 0
        assert value["stacks.refresh_host_mb_per_import"] == 0
    assert os.listdir(tmp_path) == [], "the run left its work directory"
