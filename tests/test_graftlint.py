"""graftlint self-tests: every pass fires on its bad corpus and stays
silent on the good twin; suppression reasons are mandatory; the real
tree is clean (zero unsuppressed findings)."""

import json
import os
import subprocess
import sys

import pytest

from tools.graftlint import engine
from tools.graftlint.passes import ALL_PASSES, BY_ID

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = os.path.join(REPO, "tools", "graftlint", "corpus")

PER_FILE = [
    "tpu_purity",
    "dtype_discipline",
    "lock_discipline",
    "durability",
    "exception_hygiene",
    "timeout_discipline",
    "span_discipline",
    "log_discipline",
    "queue_discipline",
    "residency_discipline",
    "cache_discipline",
    "launch_discipline",
]


def _check_corpus_file(pass_mod, kind):
    path = os.path.join(CORPUS, pass_mod, f"{kind}.py")
    tree, lines, err = engine.parse_file(path)
    assert err is None, err
    p = BY_ID[pass_mod.replace("_", "-")]
    return p.check(path, tree, lines)


@pytest.mark.parametrize("pass_mod", PER_FILE)
def test_bad_corpus_fires(pass_mod):
    findings = _check_corpus_file(pass_mod, "bad")
    assert findings, f"{pass_mod} found nothing in its bad corpus"
    assert all(f.pass_id == pass_mod.replace("_", "-") for f in findings)


@pytest.mark.parametrize("pass_mod", PER_FILE)
def test_good_corpus_clean(pass_mod):
    assert _check_corpus_file(pass_mod, "good") == []


class TestBadCorpusCoverage:
    """The bad files must exercise every violation *class*, not just
    trip the pass once."""

    def _msgs(self, pass_mod):
        return [f.message for f in _check_corpus_file(pass_mod, "bad")]

    def test_tpu_purity_classes(self):
        msgs = " | ".join(self._msgs("tpu_purity"))
        assert "host numpy" in msgs
        assert "Python If" in msgs
        assert "int() coercion" in msgs
        assert "float() coercion" in msgs
        assert ".item()" in msgs

    def test_dtype_classes(self):
        msgs = " | ".join(self._msgs("dtype_discipline"))
        assert "jnp.int64" in msgs
        assert "dtype=np.uint64" in msgs
        assert "dtype='int64'" in msgs

    def test_lock_classes(self):
        msgs = " | ".join(self._msgs("lock_discipline"))
        assert "send_message" in msgs
        assert "time.sleep" in msgs
        assert "fh.write" in msgs

    def test_durability_classes(self):
        msgs = " | ".join(self._msgs("durability"))
        assert "os.replace" in msgs
        assert "close() releases" in msgs

    def test_exception_classes(self):
        msgs = " | ".join(self._msgs("exception_hygiene"))
        assert "bare except" in msgs
        assert "except Exception" in msgs

    def test_timeout_classes(self):
        msgs = " | ".join(self._msgs("timeout_discipline"))
        assert "urlopen" in msgs
        assert "HTTPConnection" in msgs
        assert "HTTPSConnection" in msgs
        assert "create_connection" in msgs

    def test_span_classes(self):
        msgs = " | ".join(self._msgs("span_discipline"))
        assert "no tracing span" in msgs
        assert "bypasses the span-injecting" in msgs
        assert "'executor.groupByNotInTheTable' is not in the span table" in msgs

    def test_span_table_is_read_from_the_source(self):
        from pilosa_tpu.obs import tracing

        p = BY_ID["span-discipline"]
        assert p.span_table() == {row[0] for row in tracing.SPAN_TABLE}
        assert p.applies("pilosa_tpu/server/batcher.py")
        assert not p.applies("tests/test_tracing.py")

    def test_log_classes(self):
        msgs = " | ".join(self._msgs("log_discipline"))
        assert "print() bypasses" in msgs
        assert "must take __name__" in msgs
        assert "inside a function" in msgs

    def test_queue_classes(self):
        msgs = " | ".join(self._msgs("queue_discipline"))
        assert "defaults to maxsize=0" in msgs
        assert "maxsize=0) is unbounded" in msgs
        assert "maxsize=-1) is unbounded" in msgs
        assert "SimpleQueue" in msgs

    def test_residency_classes(self):
        findings = _check_corpus_file("residency_discipline", "bad")
        # plain, annotated, tuple-unpacked, and setattr forms all fire
        assert len(findings) == 5
        assert all(
            "bypasses the residency manager" in f.message for f in findings
        )

    def test_residency_manager_itself_exempt(self):
        p = BY_ID["residency-discipline"]
        assert not p.applies("pilosa_tpu/core/fragment.py")
        assert p.applies("pilosa_tpu/exec/executor.py")
        assert p.applies("tests/test_residency.py")

    def test_cache_classes(self):
        findings = _check_corpus_file("cache_discipline", "bad")
        msgs = " | ".join(f.message for f in findings)
        # private-state pokes (entry map, reverse map, lock) + both
        # counter-write forms (augmented and plain) all fire
        assert len(findings) == 5
        assert "private ResultCache state" in msgs
        assert "hand-written ResultCache counter" in msgs

    def test_launch_classes(self):
        findings = _check_corpus_file("launch_discipline", "bad")
        msgs = " | ".join(f.message for f in findings)
        # decorator, partial-decorator, call, shard_map, pmap all fire
        assert len(findings) == 5
        assert "direct jax.jit" in msgs
        assert "direct shard_map" in msgs
        assert "direct pmap" in msgs
        assert all("device-cost-ledger" in f.message for f in findings)

    def test_launch_ledger_exempt(self):
        p = BY_ID["launch-discipline"]
        assert not p.applies("pilosa_tpu/obs/devledger.py")
        assert p.applies("pilosa_tpu/ops/kernels.py")
        assert not p.applies("tools/bench.py")

    def test_cache_owner_itself_exempt(self):
        p = BY_ID["cache-discipline"]
        assert not p.applies("pilosa_tpu/exec/rescache.py")
        assert p.applies("pilosa_tpu/exec/executor.py")
        assert p.applies("tests/test_rescache.py")


class TestDispatchParity:
    def test_bad_tree_fires_both_halves(self):
        fs = engine.run([os.path.join(CORPUS, "dispatch_parity", "bad")])
        msgs = " | ".join(
            f.message for f in fs if f.pass_id == "dispatch-parity"
        )
        assert "parser special 'Zap'" in msgs
        assert "'/internal/orphan'" in msgs
        assert "BSI op class BSI_ORPHAN" in msgs
        assert "BSI op class BSI_RANGE" not in msgs

    def test_good_tree_clean(self):
        fs = engine.run([os.path.join(CORPUS, "dispatch_parity", "good")])
        assert [f for f in fs if f.pass_id == "dispatch-parity"] == []


class TestSuppression:
    def test_reason_is_mandatory(self):
        fs = engine.run([os.path.join(CORPUS, "suppression", "bad.py")])
        ids = sorted(f.pass_id for f in fs)
        # the reasonless disable does NOT suppress, and is itself flagged
        assert ids == ["bad-suppression", "exception-hygiene"]
        assert not any(f.suppressed for f in fs)

    def test_reasoned_disable_closes_finding(self):
        fs = engine.run([os.path.join(CORPUS, "suppression", "good.py")])
        [f] = fs
        assert f.pass_id == "exception-hygiene" and f.suppressed
        assert "advisory" in f.reason

    def test_bad_suppression_cannot_be_suppressed(self, tmp_path):
        p = tmp_path / "x.py"
        p.write_text(
            "# graftlint: disable-file=bad-suppression -- nope\n"
            "try:\n    pass\n"
            "except Exception:  # graftlint: disable=exception-hygiene\n"
            "    pass\n"
        )
        fs = engine.run([str(p)])
        bad = [f for f in fs if f.pass_id == "bad-suppression"]
        assert bad and not any(f.suppressed for f in bad)

    def test_docstring_mention_is_not_a_suppression(self, tmp_path):
        p = tmp_path / "x.py"
        p.write_text(
            '"""Docs may say # graftlint: disable=foo freely."""\n'
        )
        assert engine.run([str(p)]) == []


class TestTreeClean:
    def test_zero_unsuppressed_findings(self):
        roots = [os.path.join(REPO, d) for d in ("pilosa_tpu", "tests", "tools")]
        open_ = [f for f in engine.run(roots) if not f.suppressed]
        assert open_ == [], "\n".join(f.render() for f in open_)

    def test_every_suppression_has_reason(self):
        roots = [os.path.join(REPO, d) for d in ("pilosa_tpu", "tests", "tools")]
        for f in engine.run(roots):
            if f.suppressed:
                assert f.reason and f.reason.strip()


class TestCLI:
    def test_exit_codes_and_json(self, tmp_path):
        out = tmp_path / "report.json"
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftlint",
             "pilosa_tpu", "tests", "tools", "--json", str(out)],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        report = json.loads(out.read_text())
        assert report["open"] == 0
        assert all(f["suppressed"] for f in report["findings"])

    def test_nonzero_on_findings(self, tmp_path):
        bad = os.path.join(CORPUS, "exception_hygiene", "bad.py")
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftlint", bad],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 1

    def test_jobs_parallel_matches_serial(self, tmp_path):
        """--jobs N must produce byte-identical findings in the same
        order as the serial run (deterministic fold in input order)."""
        out1, out2 = tmp_path / "serial.json", tmp_path / "par.json"
        env = dict(os.environ, PYTHONPATH=REPO)
        for out, jobs in ((out1, "1"), (out2, "4")):
            r = subprocess.run(
                [sys.executable, "-m", "tools.graftlint", "pilosa_tpu",
                 "tests", "tools", "--jobs", jobs, "--json", str(out)],
                cwd=REPO, env=env, capture_output=True, text=True,
                timeout=300,
            )
            assert r.returncode == 0, r.stdout + r.stderr
        assert out1.read_text() == out2.read_text()

    def test_timings_go_to_stderr(self, tmp_path):
        p = tmp_path / "x.py"
        p.write_text("x = 1\n")
        env = dict(os.environ, PYTHONPATH=REPO)
        r = subprocess.run(
            [sys.executable, "-m", "tools.graftlint", str(p), "--timings"],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0
        assert "TOTAL (wall)" in r.stderr


def _lint_tree(root):
    """Project passes need a whole tree, not a single file; lint each
    corpus root separately so module names resolve as in the real tree."""
    return engine.run([root])


class TestLockGraph:
    def test_bad_tree_reports_cycle_with_witness(self):
        fs = [f for f in _lint_tree(os.path.join(CORPUS, "lock_graph", "bad"))
              if f.pass_id == "lock-graph"]
        assert len(fs) == 1, [f.render() for f in fs]
        msg = fs[0].message
        assert "lock-order cycle" in msg
        assert "Budget._lock" in msg and "Store._lock" in msg
        # witness path printed file:line -> file:line
        assert "budget.py:" in msg and "store.py:" in msg
        assert "\u2192" in msg

    def test_good_tree_clean(self):
        fs = [f for f in _lint_tree(os.path.join(CORPUS, "lock_graph", "good"))
              if f.pass_id == "lock-graph"]
        assert fs == []

    def test_cycle_needs_both_halves(self, tmp_path):
        """Either file alone carries only one edge — no cycle."""
        import shutil

        for keep in ("budget.py", "store.py"):
            d = tmp_path / f"only_{keep}"
            d.mkdir()
            shutil.copy(
                os.path.join(CORPUS, "lock_graph", "bad", keep), d / keep
            )
            fs = [f for f in _lint_tree(str(d)) if f.pass_id == "lock-graph"]
            assert fs == [], keep

    def test_module_level_lock_cycle(self, tmp_path):
        (tmp_path / "a.py").write_text(
            "import threading\nimport b\n"
            "_lk = threading.Lock()\n"
            "def f():\n"
            "    with _lk:\n"
            "        b.g()\n"
        )
        (tmp_path / "b.py").write_text(
            "import threading\nimport a\n"
            "_lk = threading.Lock()\n"
            "def g():\n"
            "    with _lk:\n"
            "        pass\n"
            "def h():\n"
            "    with _lk:\n"
            "        a.f()\n"
        )
        fs = [f for f in _lint_tree(str(tmp_path))
              if f.pass_id == "lock-graph"]
        assert len(fs) == 1
        assert "a._lk" in fs[0].message and "b._lk" in fs[0].message


class TestThreadBoundary:
    def test_bad_tree_fires_on_thread_and_submit(self):
        fs = [f for f in _lint_tree(
            os.path.join(CORPUS, "thread_boundary", "bad"))
            if f.pass_id == "thread-boundary"]
        msgs = " | ".join(f.message for f in fs)
        assert len(fs) == 2, [f.render() for f in fs]
        assert "Thread target" in msgs and "submit target" in msgs
        assert "_budget" in msgs  # names the contextvar it reaches

    def test_good_tree_clean_and_suppression_counts(self):
        fs = [f for f in _lint_tree(
            os.path.join(CORPUS, "thread_boundary", "good"))
            if f.pass_id == "thread-boundary"]
        open_ = [f for f in fs if not f.suppressed]
        assert open_ == [], [f.render() for f in open_]
        # the boot_monitor suppression is exercised, not dead
        assert any(f.suppressed for f in fs)


class TestCallGraph:
    """Unit tests for the project-wide def/call index on a synthetic
    mini-tree (written to tmp_path so commonpath rooting is exercised
    the same way corpus trees are)."""

    def _graph(self, tmp_path, files):
        from tools.graftlint.callgraph import CallGraph

        for name, src in files.items():
            p = tmp_path / name
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(src)
        parsed = {}
        for path in engine.walk_files([str(tmp_path)]):
            tree, lines, err = engine.parse_file(path)
            assert err is None, err
            parsed[path] = (tree, lines)
        return CallGraph(parsed)

    def test_qualnames_and_method_indexing(self, tmp_path):
        g = self._graph(tmp_path, {
            # top-level file pins the commonpath root at tmp_path so the
            # package prefix survives in module names
            "other.py": "x = 1\n",
            "pkg/__init__.py": "",
            "pkg/mod.py": (
                "class C:\n"
                "    def m(self):\n"
                "        def inner():\n"
                "            pass\n"
                "        inner()\n"
                "def top():\n"
                "    pass\n"
            ),
        })
        assert "pkg.mod:C.m" in g.functions
        assert "pkg.mod:top" in g.functions
        assert "pkg.mod:C.m.inner" in g.functions
        assert "C" in {c.name for c in g.classes.values()}

    def test_self_and_module_call_resolution(self, tmp_path):
        g = self._graph(tmp_path, {
            "m.py": (
                "import helper\n"
                "class C:\n"
                "    def a(self):\n"
                "        self.b()\n"
                "        helper.h()\n"
                "    def b(self):\n"
                "        pass\n"
            ),
            "helper.py": "def h():\n    pass\n",
        })
        a = g.functions["m:C.a"]
        targets = sorted(t.qualname for _c, t in g.callees(a))
        assert targets == ["helper:h", "m:C.b"]

    def test_attr_type_and_constructor_resolution(self, tmp_path):
        g = self._graph(tmp_path, {
            "m.py": (
                "import dep\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._d = dep.D()\n"
                "    def go(self):\n"
                "        self._d.run()\n"
            ),
            "dep.py": (
                "class D:\n"
                "    def __init__(self):\n"
                "        pass\n"
                "    def run(self):\n"
                "        pass\n"
            ),
        })
        init = g.functions["m:C.__init__"]
        # dep.D() resolves to the constructor
        assert any(t.qualname == "dep:D.__init__"
                   for _c, t in g.callees(init))
        go = g.functions["m:C.go"]
        assert any(t.qualname == "dep:D.run" for _c, t in g.callees(go))

    def test_inherited_method_via_mro(self, tmp_path):
        g = self._graph(tmp_path, {
            "m.py": (
                "import base\n"
                "class C(base.B):\n"
                "    def go(self):\n"
                "        self.inherited()\n"
            ),
            "base.py": (
                "class B:\n"
                "    def inherited(self):\n"
                "        pass\n"
            ),
        })
        go = g.functions["m:C.go"]
        assert any(t.qualname == "base:B.inherited"
                   for _c, t in g.callees(go))

    def test_reachable_chain_is_shortest(self, tmp_path):
        g = self._graph(tmp_path, {
            "m.py": (
                "def a():\n"
                "    b()\n"
                "def b():\n"
                "    c()\n"
                "def c():\n"
                "    pass\n"
            ),
        })
        r = g.reachable(g.functions["m:a"])
        assert set(r) == {"m:a", "m:b", "m:c"}
        assert len(r["m:c"]) == 2  # a->b, b->c call sites

    def test_unresolved_calls_do_not_explode(self, tmp_path):
        g = self._graph(tmp_path, {
            "m.py": (
                "import os\n"
                "def f(x):\n"
                "    os.getpid()\n"
                "    x.anything()\n"
                "    unknown()\n"
            ),
        })
        assert g.callees(g.functions["m:f"]) == []
