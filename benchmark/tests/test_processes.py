"""No process that ``run.py`` started outlives it, however it ends; and it
ends by itself inside its limit.

Each case starts ``benchmark/run.py`` as a process of its own with
``TMPDIR`` set to the test's directory, which every process of that run
inherits: that is how its children, and theirs, are found in ``/proc``
(``proctools.alive_with``), and where its work directory lies.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

import run
from proctools import alive_with, children_of, left_after

HERE = os.path.dirname(os.path.abspath(__file__))
STUCK = os.path.join(HERE, "stuck_child.py")
CELL = "taxi.dashboard-c32"


class Run:
    """``run.py --rehearsal`` of the cell as a process, its stderr gathered by a thread."""

    def __init__(self, tmp_path, *extra, child_script=None):
        self.tmp = str(tmp_path)
        argv = ["--workload", CELL, "--seed", "11", "--seconds", "3", "--trace", "0",
                "--rehearsal", *extra]
        if child_script is None:
            cmd = [sys.executable, os.path.join(run.HERE, "run.py"), *argv]
        else:  # run.py takes a child script only through main()
            cmd = [sys.executable, "-c", "import sys, run; sys.exit(run.main(sys.argv[2:], "
                   "child_script=sys.argv[1]))", child_script, *argv]
        env = dict(os.environ, TMPDIR=self.tmp, PYTHONPATH=run.HERE)
        self.proc = subprocess.Popen(cmd, cwd=run.REPO, env=env, stdout=subprocess.PIPE,
                                     stderr=subprocess.PIPE, text=True)
        self.err: list[str] = []
        self._reader = threading.Thread(target=lambda: self.err.extend(self.proc.stderr), daemon=True)
        self._reader.start()

    def wait_for(self, text: str, within: float) -> None:
        end = time.monotonic() + within
        while not any(text in line for line in self.err):
            assert self.proc.poll() is None, f"run.py ended before {text!r}:\n{self.stderr()}"
            assert time.monotonic() < end, f"no {text!r} within {within}s:\n{self.stderr()}"
            time.sleep(0.05)

    def wait_children(self, *scripts: str, within: float = 10) -> None:
        """Until a live process of this run names each of ``scripts``."""
        end = time.monotonic() + within
        while not all(any(s in line for line in alive_with(self.tmp)) for s in scripts):
            assert time.monotonic() < end, f"no {scripts} among {alive_with(self.tmp)}"
            time.sleep(0.02)

    def ended(self, within: float) -> tuple[int, str]:
        """(exit code, stdout) of a run that has to end by itself within ``within`` seconds."""
        try:
            rc = self.proc.wait(timeout=within)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            pytest.fail(f"run.py still ran after {within}s:\n{self.stderr()}")
        out = self.proc.stdout.read()
        self._reader.join(timeout=10)
        return rc, out

    def stderr(self) -> str:
        return "".join(self.err)[-3000:]

    def work_dirs(self) -> list[str]:
        return [d for d in os.listdir(self.tmp) if d.startswith("pilosa_bench_")]

    def close(self) -> None:
        """Whatever the case left, so that a failure does not leak into the next."""
        if self.proc.poll() is None:
            self.proc.kill()
        for line in alive_with(self.tmp):
            os.kill(int(line.split(":")[0]), signal.SIGKILL)


@pytest.fixture
def started(tmp_path):
    runs = []

    def start(*extra, child_script=None) -> Run:
        runs.append(Run(tmp_path, *extra, child_script=child_script))
        return runs[-1]

    yield start
    for r in runs:
        r.close()


def test_sigkill_in_the_load_stage_takes_the_children(started):
    r = started()
    r.wait_for("stage load", 120)
    r.wait_children("serve_child.py", "loadgen.py")  # the workers start in this stage
    r.proc.kill()
    r.proc.wait(timeout=5)
    assert left_after(5, r.tmp) == []
    assert r.proc.stdout.read() == ""


def test_sigterm_in_warm_up_is_an_orderly_end(started):
    r = started()
    r.wait_for("stage warm-up", 120)
    r.wait_children("run.py", "serve_child.py", "loadgen.py")
    r.proc.send_signal(signal.SIGTERM)
    rc, out = r.ended(15)
    assert rc == 1 and out == "", r.stderr()
    assert "ended by SIGTERM in stage warm-up" in r.stderr()
    assert alive_with(r.tmp) == [] and r.work_dirs() == []


def test_limit_ends_a_run_whose_server_never_serves(started):
    r = started("--limit", "5", child_script=STUCK)
    t0 = time.monotonic()
    rc, out = r.ended(15)
    assert rc == 1 and out == "", r.stderr()
    assert time.monotonic() - t0 >= 4
    assert "passed its limit of 5 s in stage ready; stage times: ready 5." in r.stderr()
    assert alive_with(r.tmp) == [] and r.work_dirs() == []


@pytest.mark.parametrize("signum", [signal.SIGINT, signal.SIGHUP, signal.SIGTERM])
def test_a_signal_ends_the_run_and_its_child(started, signum):
    r = started(child_script=STUCK)
    r.wait_for("stage ready", 30)
    r.wait_children("stuck_child.py")
    r.proc.send_signal(signum)
    rc, out = r.ended(15)
    assert rc == 1 and out == "", r.stderr()
    assert f"ended by {signal.Signals(signum).name} in stage ready" in r.stderr()
    assert alive_with(r.tmp) == [] and r.work_dirs() == []


def test_sigkill_takes_a_child_that_never_serves(started):
    r = started(child_script=STUCK)
    r.wait_for("stage ready", 30)
    r.wait_children("stuck_child.py")
    r.proc.kill()
    r.proc.wait(timeout=5)
    assert left_after(5, r.tmp) == []


def test_in_process_the_handlers_are_restored(capfd, tmp_path, monkeypatch):
    """The rehearsal tests call ``run.main`` inside pytest: what it installs, it takes away."""
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    signals = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP, signal.SIGALRM)
    before = [signal.getsignal(s) for s in signals]
    rc = run.main(["--workload", CELL, "--rehearsal", "--limit", "2"], child_script=STUCK)
    out, err = capfd.readouterr()
    assert rc == 1 and out == "" and "passed its limit of 2 s in stage ready" in err
    assert [signal.getsignal(s) for s in signals] == before
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert children_of(os.getpid()) == [] and os.listdir(tmp_path) == []
