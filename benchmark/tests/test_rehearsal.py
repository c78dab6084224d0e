"""Every cell, both kinds of run, end to end on the CPU at the tiny shape.

The result line of each is held to ``BENCHMARK.json`` by the validator
``run.py`` itself uses: the exact key set, every metric the manifest lists
for the cell and the kind of run with value and unit, ``busy_s`` within
``window_s`` in a traced run.  A rehearsal is never a pass: ``correct`` is
false, the exit code is 3, and the one number over its limit is
``rehearsal``.  Every class of the mix has an answer judged against the
reference (``classes_unjudged`` 0), none disagrees, and nothing compiled
inside the window (``window_compiles`` 0).  And a run that ends well leaves
no process behind (``test_processes.py`` has the runs that end otherwise).
``test_ingest.py`` holds a cell that the grid does not have yet to the same.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

import manifest as mf
import run
from proctools import children_of

MANIFEST = mf.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def rehearse(capfd, *extra, child_script=None, manifest=None):
    rc = run.main(["--seed", "11", "--seconds", "3", "--rehearsal", *extra], child_script=child_script,
                  manifest=manifest)
    out, err = capfd.readouterr()
    assert children_of(os.getpid()) == [], "the run left a process"
    lines = out.strip().splitlines()
    assert lines, f"no result line (exit {rc}):\n{err[-3000:]}"
    return rc, json.loads(lines[-1]), err


def rehearsed_line(capfd, manifest, workload, trace):
    """(line, stderr) of a rehearsal that came out as ``manifest`` says."""
    rc, line, err = rehearse(capfd, "--workload", workload, "--trace", str(trace), manifest=manifest)
    assert rc == 3 and line["correct"] is False
    assert mf.validate_line(manifest, workload, bool(trace), line) == []
    want = [m["name"] for m in mf.metrics_for(manifest, workload, bool(trace))]
    assert list(line["metrics"]) == want and want
    assert list(line)[-1] == "compared"
    over = {k: v for k, (v, limit) in line["compared"].items() if v > limit}
    assert over == {"rehearsal": 1}, err[-3000:]
    assert line["device"]["platform"] == "cpu"
    return line, err


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_result_line_is_the_manifests(capfd, workload, trace):
    rehearsed_line(capfd, MANIFEST, workload, trace)


def test_no_program_no_result(tmp_path, capfd):
    """In a directory with only BENCHMARK.json and benchmark/: exit non-zero, print nothing."""
    shutil.copy(os.path.join(mf.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(mf.HERE, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path, capture_output=True)
    assert p.returncode != 0 and p.stdout == b""
