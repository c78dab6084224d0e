"""The grid's four-chip cell, ``taxi-x4.dashboard-c32``, rehearsed on the
suite's CPU devices through the benchmark's own command: the configuration is
``taxi``'s record on another layout, and a rehearsal's line is the manifest's,
with every per-layer reader (the three this cell adds among them) returning
over a serving mesh.  A rehearsal is never a pass: exit 3, ``correct`` false,
and ``rehearsal`` the one number over its limit."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import manifest as mf  # noqa: E402
from test_benchmark_ingest import FLIGHT_MS, SUM_DEVICE, flight_ms_of_the_log  # noqa: E402

CELL = "taxi-x4.dashboard-c32"
MANIFEST = mf.load()


def test_the_configuration_is_taxis_record_on_another_layout():
    taxi = mf.read_json("benchmark/configs/taxi.json")
    x4 = mf.read_json(mf.config_entry(MANIFEST, "taxi-x4")["file"])
    differ = {k for k in taxi.keys() | x4.keys() if taxi.get(k) != x4.get(k)}
    assert differ == {"name", "source", "deployment", "chips", "shards", "reduced_why",
                      "assumed", "rehearsal"}
    assert x4["assumed"][:len(taxi["assumed"])] == taxi["assumed"]
    assert x4["chips"] == mf.cell(MANIFEST, CELL)["chips"] == 4
    assert x4["shards"] % x4["chips"] == 0 and x4["rehearsal"]["shards"] == 4
    assert x4["published"]["shards"] == 1049 and x4["reduced"] == ["shards"]
    assert x4["source"] == mf.config_entry(MANIFEST, "taxi-x4")["source"] != taxi["source"]


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
    assert list(line["metrics"]) == want and len(want) == (29 if trace else 3)
    assert line["correct"] is False
    over = {k: v for k, (v, limit) in line["compared"].items() if v > limit}
    assert over == {"rehearsal": 1}, err
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 4
    if trace:
        # counts, not times: the window's launches ran over the mesh
        assert line["metrics"]["mesh.sharded_launch_pct"]["value"] > 75, err
        # a flight's wall time in three: interpreter, device wait, and the rest
        value = {k: v["value"] for k, v in line["metrics"].items()}
        assert value[FLIGHT_MS[0]] > 0 and value[FLIGHT_MS[1]] > 0, err
        assert sum(value[n] for n in FLIGHT_MS) == flight_ms_of_the_log(p.stderr)
        # the mesh form of a filtered Sum's program took every one of the window's (PR 43)
        assert want[-1] == SUM_DEVICE and value[SUM_DEVICE] == 100, err
    assert os.listdir(tmp_path) == [], "the run left its work directory"
