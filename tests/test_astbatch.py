"""General AST one-launch path: arbitrary Row/op/Not trees compile into
one traced program per AST shape over the field stacks and must return
exactly what the per-fragment segment path returns (SURVEY §7 "one XLA
program per query shape"; reference semantics executor.go:653-680)."""

import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import astbatch
from pilosa_tpu.exec.executor import Executor


@pytest.fixture()
def setup():
    h = Holder()
    idx = h.create_index("i", track_existence=True)
    idx.create_field("f")
    idx.create_field("g")
    # rescache off: this file asserts the batch-compile layer's launch
    # accounting on repeat queries, which the semantic result cache
    # would otherwise short-circuit (it has its own tests)
    ex = Executor(h, rescache_entries=0)
    rng = np.random.default_rng(9)
    writes = []
    pool = rng.integers(0, 3 * h.n_words * 32, size=150)
    for row in range(6):
        for col in rng.choice(pool, size=60, replace=False):
            writes.append(f"Set({int(col)}, f={row})")
    for row in range(3):
        for col in rng.choice(pool, size=40, replace=False):
            writes.append(f"Set({int(col)}, g={row})")
    ex.execute("i", " ".join(writes))
    return h, ex


def _fresh_executor(h, like=None):
    """An executor whose batch paths are disabled — the ground-truth
    per-fragment segment path.  ``like`` shares its key translator (keyed
    indexes translate ids back to keys at the result edge)."""
    ex = Executor(h, translator=like.translator if like is not None else None)
    ex._batch_pair_counts = lambda *a, **k: None
    ex._batch_general = lambda *a, **k: None
    return ex


TREES = [
    "Intersect(Row(f=0), Row(f=1), Row(f=2))",
    "Union(Row(f=0), Row(f=1), Row(f=2), Row(f=3))",
    "Difference(Row(f=0), Row(f=1), Row(f=2))",
    "Xor(Row(f=0), Row(f=4))",
    "Union(Intersect(Row(f=0), Row(g=1)), Difference(Row(f=2), Row(g=0)))",
    "Not(Row(f=3))",
    "Intersect(Row(f=1), Not(Union(Row(f=2), Row(g=2))))",
    # absent rows ride through as zero rows
    "Union(Row(f=0), Row(f=999))",
    "Difference(Row(f=0), Row(f=999))",
]


@pytest.mark.parametrize("tree", TREES)
def test_count_tree_matches_segment_path(setup, tree):
    h, ex = setup
    q = f"Count({tree})Count({tree})"  # x2: meets the stack-demand policy
    got = ex.execute("i", q)
    want = _fresh_executor(h).execute("i", q)
    assert got == want
    assert got[0] == got[1]


@pytest.mark.parametrize("tree", TREES)
def test_bitmap_tree_matches_segment_path(setup, tree):
    h, ex = setup
    q = f"{tree}{tree}"
    got = ex.execute("i", q)
    want = _fresh_executor(h).execute("i", q)
    for g, w in zip(got, want):
        assert sorted(g.columns().tolist()) == sorted(w.columns().tolist())
        assert g.count() == w.count()


def test_count_batch_is_one_launch(setup):
    _, ex = setup
    # warm the stacks + compile cache
    ex.execute("i", "Count(Intersect(Row(f=0), Row(f=1), Row(f=2)))" * 2)
    before = astbatch.launches
    q = "".join(
        f"Count(Intersect(Row(f={a}), Row(f={b}), Row(f={c})))"
        for a, b, c in [(0, 1, 2), (3, 4, 5), (1, 3, 5), (0, 2, 4)]
    )
    res = ex.execute("i", q)
    assert astbatch.launches == before + 1  # 4 Counts, ONE launch
    assert len(res) == 4 and any(r >= 0 for r in res)


def test_union4_bitmap_is_one_launch(setup):
    _, ex = setup
    ex.execute("i", "Union(Row(f=0), Row(f=1))" * 2)  # warm stack
    before = astbatch.launches
    res = ex.execute("i", "Union(Row(f=0), Row(f=1), Row(f=2), Row(f=3))")
    assert astbatch.launches == before + 1
    assert res[0].count() > 0


def test_shape_cache_reuses_programs(setup):
    _, ex = setup
    ex.execute("i", "Count(Intersect(Row(f=0), Row(f=1), Row(f=2)))" * 2)
    info_before = astbatch.compiled.cache_info()
    # same shape, different rows: no new compile entry
    ex.execute("i", "Count(Intersect(Row(f=3), Row(f=1), Row(f=5)))" * 2)
    info_after = astbatch.compiled.cache_info()
    assert info_after.misses == info_before.misses
    assert info_after.hits > info_before.hits


def test_cold_single_call_stays_on_segment_path(setup):
    h, ex = setup
    # a field the batcher has never stacked, one lone call -> must not
    # engage (stack builds are full-field uploads)
    idx = h.index("i")
    idx.create_field("lonely")
    ex.execute("i", "Set(7, lonely=0)")
    before = astbatch.launches
    res = ex.execute("i", "Union(Row(lonely=0), Row(lonely=0))")
    assert astbatch.launches == before
    assert res[0].count() == 1


def test_write_barrier_blocks_batching(setup):
    h, ex = setup
    before = astbatch.launches
    # the Count AFTER the write must observe the write; batch path would
    # observe pre-write state, so it must not engage past the barrier
    res = ex.execute(
        "i",
        "Set(1048570, f=0)"
        "Count(Union(Row(f=0), Row(f=1), Row(f=2)))"
        "Count(Union(Row(f=0), Row(f=1), Row(f=2)))",
    )
    want = _fresh_executor(h).execute(
        "i", "Count(Union(Row(f=0), Row(f=1), Row(f=2)))"
    )
    assert res[1] == res[2] == want[0]


def test_mixed_count_and_bitmap_share_stacks(setup):
    h, ex = setup
    q = (
        "Count(Intersect(Row(f=0), Row(f=1), Row(g=0)))"
        "Union(Row(f=0), Row(g=1), Row(g=2))"
        "Count(Intersect(Row(f=2), Row(f=3), Row(g=1)))"
    )
    got = ex.execute("i", q)
    want = _fresh_executor(h).execute("i", q)
    assert got[0] == want[0] and got[2] == want[2]
    assert sorted(got[1].columns().tolist()) == sorted(
        want[1].columns().tolist()
    )


class TestTimeRangeBatch:
    """Time-range Rows expand into per-view union leaves and ride the
    compiled one-launch path (reference executor.go:1515-1531 treats
    time views as ordinary fragments)."""

    @pytest.fixture()
    def ex_time(self, setup):
        from pilosa_tpu.core.field import FieldOptions

        h, ex = setup
        h.index("i").create_field(
            "t", FieldOptions(field_type="time", time_quantum="YMDH")
        )
        ex.execute("i", "Set(1, t=9, 2017-01-02T03:00)")
        ex.execute("i", "Set(2, t=9, 2017-01-02T04:00)")
        ex.execute("i", "Set(3, t=9, 2017-03-01T00:00)")
        ex.execute("i", "Set(2, t=5, 2017-01-02T04:00)")
        return h, ex

    def test_count_time_range_matches_segment_path(self, ex_time):
        h, ex = ex_time
        q = (
            "Count(Union(Row(t=9, from=2017-01-02T00:00, to=2017-01-03T00:00),"
            " Row(t=5, from=2017-01-01T00:00, to=2017-02-01T00:00)))"
        ) * 2
        got = ex.execute("i", q)
        want = _fresh_executor(h).execute("i", q)
        assert got == want and got[0] == 2  # cols 1, 2

    def test_time_range_batch_is_one_launch(self, ex_time):
        _, ex = ex_time
        q = (
            "Count(Intersect(Row(t=9, from=2017-01-01T00:00, to=2017-04-01T00:00),"
            " Row(f=0)))"
        )
        ex.execute("i", q * 2)  # warm per-view stacks
        before = astbatch.launches
        res = ex.execute("i", q * 3)
        assert astbatch.launches == before + 1
        assert len(res) == 3 and res[0] == res[1] == res[2]

    def test_absent_cover_views_are_zero_leaves(self, ex_time):
        h, ex = ex_time
        # a window whose cover includes months with no data at all
        q = (
            "Count(Union(Row(t=9, from=2017-01-01T00:00, to=2017-06-01T00:00),"
            " Row(t=9, from=2017-02-01T00:00, to=2017-03-01T00:00)))"
        ) * 2
        got = ex.execute("i", q)
        want = _fresh_executor(h).execute("i", q)
        assert got == want and got[0] == 3

    def test_rolling_window_reuses_compiled_program(self, ex_time):
        """Same cover SHAPE with different view names (a rolling window)
        must not trace a fresh XLA program — sigs are canonicalized to
        stack ordinals."""
        _, ex = ex_time
        q1 = "Count(Union(Row(t=9, from=2017-01-02T03:00, to=2017-01-02T05:00), Row(f=0)))"
        ex.execute("i", q1 * 2)
        info_before = astbatch.compiled.cache_info()
        # shifted window: same number of hourly cover views, new names
        q2 = "Count(Union(Row(t=9, from=2017-03-01T00:00, to=2017-03-01T02:00), Row(f=0)))"
        ex.execute("i", q2 * 2)
        info_after = astbatch.compiled.cache_info()
        assert info_after.misses == info_before.misses
        assert info_after.hits > info_before.hits


class TestDifferentialFuzz:
    """Randomized trees evaluated through the compiled one-launch path
    must equal the per-fragment segment path — the executor analogue of
    the reference's per-container-type differential op matrix
    (roaring/roaring_internal_test.go)."""

    def _rand_tree(self, rng, depth):
        if depth == 0 or rng.random() < 0.35:
            f = rng.choice(["f", "g"])
            r = int(rng.integers(0, 8))  # some rows absent
            return f"Row({f}={r})"
        op = rng.choice(["Intersect", "Union", "Difference", "Xor", "Not"])
        if op == "Not":
            return f"Not({self._rand_tree(rng, depth - 1)})"
        n = int(rng.integers(2, 4))
        kids = ", ".join(self._rand_tree(rng, depth - 1) for _ in range(n))
        return f"{op}({kids})"

    def test_random_trees_match_segment_path(self, setup):
        h, ex = setup
        fresh = _fresh_executor(h)
        rng = np.random.default_rng(77)
        for trial in range(25):
            tree = self._rand_tree(rng, 3)
            q = f"Count({tree})Count({tree}){tree}"
            got = ex.execute("i", q)
            want = fresh.execute("i", q)
            assert got[0] == want[0] == got[1], (trial, tree)
            assert sorted(got[2].columns().tolist()) == sorted(
                want[2].columns().tolist()
            ), (trial, tree)


class TestKeyedBatch:
    """Keys translate to ids before the batch paths engage, so keyed
    queries ride the same compiled programs (reference
    executor.go:2613 translateCalls runs before execution)."""

    @pytest.fixture()
    def ex_keys(self):
        from pilosa_tpu.core.field import FieldOptions
        from pilosa_tpu.exec.executor import Executor

        h = Holder()
        h.create_index("ki", keys=True, track_existence=True)
        h.index("ki").create_field("f", FieldOptions(keys=True))
        ex = Executor(h)
        rng = np.random.default_rng(5)
        writes = []
        for name in ("one", "two", "three", "four"):
            for col in rng.integers(0, 2 * h.n_words * 32, size=40):
                writes.append(f'Set("c{int(col)}", f="{name}")')
        ex.execute("ki", " ".join(writes))
        return h, ex

    def test_keyed_counts_match_segment_path(self, ex_keys):
        h, ex = ex_keys
        q = (
            'Count(Intersect(Row(f="one"), Row(f="two"), Row(f="three")))'
            'Count(Union(Row(f="one"), Row(f="four")))'
            'Count(Intersect(Row(f="one"), Row(f="two"), Row(f="three")))'
        )
        got = ex.execute("ki", q)
        want = _fresh_executor(h, like=ex).execute("ki", q)
        assert got == want and got[0] == got[2]

    def test_keyed_bitmap_tree_returns_keys(self, ex_keys):
        h, ex = ex_keys
        q = 'Union(Row(f="one"), Row(f="two"))' * 2
        got = ex.execute("ki", q)
        want = _fresh_executor(h, like=ex).execute("ki", q)
        assert sorted(got[0].keys) == sorted(want[0].keys)
        assert len(got[0].keys) > 0


class TestFilteredRangeCountSigning:
    """``astbatch.match_bsi`` signs ``Count(Intersect(set rows, one int
    condition))`` for the BSI lane's filtered-count class, children in
    any order, and leaves everything else to the per-call path."""

    @pytest.fixture()
    def idx(self, setup):
        from pilosa_tpu.core.field import FieldOptions

        h, _ = setup
        idx = h.index("i")
        idx.create_field(
            "v", FieldOptions(field_type="int", min_=-100, max_=100)
        )
        idx.create_field(
            "w", FieldOptions(field_type="int", min_=0, max_=100)
        )
        idx.create_field(
            "t", FieldOptions(field_type="time", time_quantum="YMD")
        )
        idx.create_field("k", FieldOptions(keys=True))
        return idx

    @staticmethod
    def _call(q):
        import pilosa_tpu.pql as pql

        return pql.parse(q).calls[0]

    @pytest.mark.parametrize(
        "q,leaves",
        [
            ("Count(Intersect(Row(f=1), Row(v < 3)))", [("f", 1)]),
            ("Count(Intersect(Row(v < 3), Row(f=1)))", [("f", 1)]),
            ("Count(Intersect(Row(f=1), Range(v >< [1, 3])))", [("f", 1)]),
            ("Count(Intersect(Row(g=2), Row(v != null), Row(f=1)))",
             [("f", 1), ("g", 2)]),
            ("Count(Intersect(Row(f=4), Row(f=1), Row(-3 < v <= 3)))",
             [("f", 1), ("f", 4)]),
            ("Count(Intersect(Row(f=999), Row(v == 3)))", [("f", 999)]),
        ],
    )
    def test_signs(self, idx, q, leaves):
        m = astbatch.match_bsi(idx, self._call(q))
        assert m is not None, q
        op_class, field, cond, got = m
        assert op_class == astbatch.BSI_RANGE_COUNT_FILTERED
        assert op_class in astbatch.BSI_OP_CLASSES
        assert field.name == "v" and cond is not None
        assert [(f, r) for f, _, r in got] == leaves
        # the compiled-AST lane does not claim what this lane signs
        assert astbatch.match_count(idx, self._call(q), [], []) is None

    @pytest.mark.parametrize(
        "q",
        [
            "Count(Intersect(Row(f=1), Row(v < 3), Row(w > 1)))",
            "Count(Intersect(Row(f=1), Row(v < 3), Row(v > 1)))",
            "Count(Intersect(Union(Row(f=1), Row(f=2)), Row(v < 3)))",
            "Count(Intersect(Row(f=1), Not(Row(f=2)), Row(v < 3)))",
            "Count(Intersect(Row(t=1, from=2017-01-01T00:00,"
            " to=2017-02-01T00:00), Row(v < 3)))",
            "Count(Intersect(Row(f=1), Row(v == null)))",
            "Count(Intersect(Row(v < 3)))",
            "Count(Intersect(Row(f=1), Row(f=2)))",
            "Count(Union(Row(f=1), Row(v < 3)))",
            'Count(Intersect(Row(k="a"), Row(v < 3)))',
            "Count(Intersect(Row(nosuch=1), Row(v < 3)))",
            "Intersect(Row(f=1), Row(v < 3))",
        ],
    )
    def test_declines(self, idx, q):
        m = astbatch.match_bsi(idx, self._call(q))
        assert m is None or m[0] != astbatch.BSI_RANGE_COUNT_FILTERED, q

    def test_declines_a_planner_graft(self, idx):
        from pilosa_tpu.exec import planner
        from pilosa_tpu.exec.result import Row

        call = self._call("Count(Intersect(Row(f=1), Row(v < 3)))")
        call.children[0].children[0] = planner.make_shared(Row())
        assert astbatch.match_bsi(idx, call) is None

    def test_unfiltered_classes_carry_no_leaves(self, idx):
        for q, cls in [
            ("Row(v < 3)", astbatch.BSI_RANGE),
            ("Count(Row(v < 3))", astbatch.BSI_RANGE_COUNT),
            ("Sum(Row(f=1), field=v)", astbatch.BSI_SUM),
        ]:
            m = astbatch.match_bsi(idx, self._call(q))
            assert m[0] == cls and m[3] == (), q
