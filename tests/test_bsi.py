"""BSI kernel tests against numpy brute force (the reference validates the
same semantics in fragment_internal_test.go BSI/range sections)."""

import numpy as np
import pytest

from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.ops import bitops, bsi

DEPTH = 10


def make_fragment(values: dict[int, int]) -> Fragment:
    f = Fragment()
    cols = np.array(list(values), dtype=np.int64)
    vals = np.array([values[c] for c in cols], dtype=np.int64)
    f.import_values(cols, vals, DEPTH)
    return f


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    cols = np.unique(rng.integers(0, 4000, size=300))
    vals = rng.integers(-500, 500, size=len(cols))
    values = dict(zip(cols.tolist(), vals.tolist()))
    frag = make_fragment(values)
    planes, exists, sign = frag.bsi_tensors(DEPTH)
    return values, planes, exists, sign


def cols_of(words) -> set[int]:
    return set(bitops.unpack_columns(np.asarray(words)).tolist())


def test_range_eq(data):
    values, planes, exists, sign = data
    for target in [0, 7, -13, 499, list(values.values())[0]]:
        got = cols_of(
            bsi.range_eq(
                planes,
                exists,
                sign,
                value_abs=abs(target),
                negative=target < 0,
                depth=DEPTH,
            )
        )
        want = {c for c, v in values.items() if v == target}
        assert got == want, target


@pytest.mark.parametrize("bound", [-501, -500, -99, -1, 0, 1, 37, 499, 500])
@pytest.mark.parametrize("allow_eq", [False, True])
def test_range_lt(data, bound, allow_eq):
    values, planes, exists, sign = data
    got = cols_of(
        bsi.range_lt(planes, exists, sign, value=bound, depth=DEPTH, allow_eq=allow_eq)
    )
    want = {
        c for c, v in values.items() if (v <= bound if allow_eq else v < bound)
    }
    assert got == want


@pytest.mark.parametrize("bound", [-501, -500, -99, -1, 0, 1, 37, 499, 500])
@pytest.mark.parametrize("allow_eq", [False, True])
def test_range_gt(data, bound, allow_eq):
    values, planes, exists, sign = data
    got = cols_of(
        bsi.range_gt(planes, exists, sign, value=bound, depth=DEPTH, allow_eq=allow_eq)
    )
    want = {
        c for c, v in values.items() if (v >= bound if allow_eq else v > bound)
    }
    assert got == want


@pytest.mark.parametrize("lo,hi", [(-100, 100), (0, 0), (-500, 499), (5, 4), (-3, 3)])
def test_range_between(data, lo, hi):
    values, planes, exists, sign = data
    got = cols_of(bsi.range_between(planes, exists, sign, lo=lo, hi=hi, depth=DEPTH))
    want = {c for c, v in values.items() if lo <= v <= hi}
    assert got == want


def test_sum(data):
    values, planes, exists, sign = data
    ones = np.full_like(np.asarray(exists), 0xFFFFFFFF)
    total, count = bsi.sum_host(planes, exists, sign, ones, depth=DEPTH)
    assert total == sum(values.values())
    assert count == len(values)


def test_sum_filtered(data):
    values, planes, exists, sign = data
    keep = [c for c in values if c % 2 == 0]
    filt = bitops.pack_columns(np.array(keep), np.asarray(exists).shape[0])
    total, count = bsi.sum_host(planes, exists, sign, filt, depth=DEPTH)
    assert total == sum(values[c] for c in keep)
    assert count == len(keep)


def test_min_max(data):
    values, planes, exists, sign = data
    ones = np.full_like(np.asarray(exists), 0xFFFFFFFF)
    vmax, cmax = bsi.min_max_host(planes, exists, sign, ones, depth=DEPTH, maximal=True)
    vmin, cmin = bsi.min_max_host(planes, exists, sign, ones, depth=DEPTH, maximal=False)
    vals = list(values.values())
    assert vmax == max(vals)
    assert cmax == vals.count(max(vals))
    assert vmin == min(vals)
    assert cmin == vals.count(min(vals))


def test_min_max_all_negative():
    values = {1: -5, 2: -3, 3: -5}
    frag = make_fragment(values)
    planes, exists, sign = frag.bsi_tensors(DEPTH)
    ones = np.full_like(np.asarray(exists), 0xFFFFFFFF)
    assert bsi.min_max_host(planes, exists, sign, ones, depth=DEPTH, maximal=True) == (-3, 1)
    assert bsi.min_max_host(planes, exists, sign, ones, depth=DEPTH, maximal=False) == (-5, 2)


def test_min_max_empty():
    frag = Fragment()
    planes, exists, sign = frag.bsi_tensors(DEPTH)
    ones = np.full_like(np.asarray(exists), 0xFFFFFFFF)
    assert bsi.min_max_host(planes, exists, sign, ones, depth=DEPTH, maximal=True) == (0, 0)


@pytest.mark.parametrize("bound", [1 << DEPTH, (1 << DEPTH) + 5, -(1 << DEPTH), -(1 << DEPTH) - 5, 1 << 40])
def test_range_out_of_depth_bounds(data, bound):
    # Bounds whose magnitude exceeds 2^depth must not alias mod 2^depth
    # (regression: reference handles this in rangeLTUnsigned).
    values, planes, exists, sign = data
    for allow_eq in (False, True):
        got = cols_of(
            bsi.range_lt(planes, exists, sign, value=bound, depth=DEPTH, allow_eq=allow_eq)
        )
        want = {c for c, v in values.items() if (v <= bound if allow_eq else v < bound)}
        assert got == want
        got = cols_of(
            bsi.range_gt(planes, exists, sign, value=bound, depth=DEPTH, allow_eq=allow_eq)
        )
        want = {c for c, v in values.items() if (v >= bound if allow_eq else v > bound)}
        assert got == want
    got = cols_of(
        bsi.range_eq(
            planes, exists, sign, value_abs=abs(bound), negative=bound < 0, depth=DEPTH
        )
    )
    assert got == set()


def test_range_bound_does_not_recompile(data):
    # The bound is a traced input: querying many distinct bounds must reuse
    # one compiled kernel per (op, depth, sign, allow_eq).
    values, planes, exists, sign = data
    bsi.range_lt(planes, exists, sign, value=3, depth=DEPTH, allow_eq=False)
    misses0 = bsi._range_lt_kernel._cache_size()
    for bound in range(4, 40):
        bsi.range_lt(planes, exists, sign, value=bound, depth=DEPTH, allow_eq=False)
    assert bsi._range_lt_kernel._cache_size() == misses0


def test_extreme_mag_empty_candidates(data):
    values, planes, exists, sign = data
    zeros = np.zeros_like(np.asarray(exists))
    for maximal in (True, False):
        mag, c = bsi.extreme_mag(planes, zeros, depth=DEPTH, maximal=maximal)
        assert int(mag) == 0
        assert not np.asarray(c).any()


# ---------------------------------------------------------------------------
# BSI serving stacks: one launch per Range/Sum/Min/Max across all shards
# ---------------------------------------------------------------------------


class TestBSIStacks:
    @pytest.fixture()
    def ex3(self):
        """An int field spread over 3 shards with positive and negative
        values."""
        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.exec.executor import Executor
        from pilosa_tpu.core.field import FieldOptions

        h = Holder()
        idx = h.create_index("i")
        idx.create_field(
            "v", FieldOptions(field_type="int", min_=-1000, max_=1000)
        )
        ex = Executor(h)
        # these tests assert STACKED serving (launch counters / agg
        # caches); pin the BSI warm-up off so the stack engages on the
        # first lone query (the host latency tier has its own tests)
        ex._BSI_SINGLE_WARM = 0
        rng = np.random.default_rng(17)
        self.vals = {}
        width = h.n_words * 32
        for col in rng.choice(3 * width, size=200, replace=False):
            v = int(rng.integers(-1000, 1000))
            self.vals[int(col)] = v
            ex.execute("i", f"Set({int(col)}, v={v})")
        return h, ex

    def test_range_is_one_launch_and_exact(self, ex3):
        _, ex = ex3
        before = ex.bsi_stack_launches
        res = ex.execute("i", "Range(v < 250)")[0]
        assert ex.bsi_stack_launches == before + 1
        want = {c for c, v in self.vals.items() if v < 250}
        assert set(res.columns().tolist()) == want

    def test_aggregates_one_launch_each_and_exact(self, ex3):
        from pilosa_tpu.exec.result import ValCount

        _, ex = ex3
        before = ex.bsi_stack_launches
        s, mn, mx = ex.execute("i", "Sum(field=v)Min(field=v)Max(field=v)")
        assert ex.bsi_stack_launches == before + 3
        assert s.value == sum(self.vals.values())
        assert s.count == len(self.vals)
        lo, hi = min(self.vals.values()), max(self.vals.values())
        assert mn == ValCount(
            value=lo, count=sum(1 for v in self.vals.values() if v == lo)
        )
        assert mx == ValCount(
            value=hi, count=sum(1 for v in self.vals.values() if v == hi)
        )

    def test_filtered_sum_matches_fallback(self, ex3):
        _, ex = ex3
        idx_obj = ex.holder.index("i")
        idx_obj.create_field("tag")
        cols = sorted(self.vals)[:40]
        ex.execute("i", " ".join(f"Set({c}, tag=1)" for c in cols))
        got = ex.execute("i", "Sum(Row(tag=1), field=v)")[0]
        # fallback path: stack disabled
        ex2 = type(ex)(ex.holder)
        ex2.stacks.bsi = lambda *a, **k: None
        want = ex2.execute("i", "Sum(Row(tag=1), field=v)")[0]
        assert got == want
        assert got.value == sum(self.vals[c] for c in cols)

    def test_stack_declines_over_budget_falls_back(self, ex3, monkeypatch):
        from pilosa_tpu.exec import stacks

        _, ex = ex3
        monkeypatch.setattr(stacks, "STACK_BUDGET_BYTES", 0)
        # drop any cached stack
        idx_obj = ex.holder.index("i")
        f = idx_obj.field("v")
        stacks.drop(f)
        res = ex.execute("i", "Range(v >= 250)")[0]
        want = {c for c, v in self.vals.items() if v >= 250}
        assert set(res.columns().tolist()) == want

    def test_incremental_refresh_after_write(self, ex3):
        _, ex = ex3
        ex.execute("i", "Range(v < 0)")  # build stack
        ex.execute("i", "Set(5, v=-7)")
        self.vals[5] = -7
        res = ex.execute("i", "Range(v < 0)")[0]
        want = {c for c, v in self.vals.items() if v < 0}
        assert set(res.columns().tolist()) == want

    def test_depth_autogrow_purges_stale_stack(self, ex3):
        """The old-depth device stack must be released when autogrow
        re-keys the cache — not stranded under a dead key."""
        from pilosa_tpu.core.field import FieldOptions

        _, ex = ex3
        # an unbounded int field: bit_depth starts at observed values and
        # grows (reference field.go:1050-1067)
        ex.holder.index("i").create_field(
            "w", FieldOptions(field_type="int")
        )
        f = ex.holder.index("i").field("w")
        f.import_values([1, 2], [3, 7])  # depth grows to observed values
        shards = ex._shards_for(ex.holder.index("i"), None)
        view, small = f.bsi_view_name(), 2 + f.bit_depth
        ex.execute("i", "Range(w < 5)")  # build stack at small depth
        assert ex.stacks.cached(f, shards, view, small)
        f.import_values([3], [100000])  # depth grows (reference
        # field.go:1050-1067 bitDepth autogrow on import)
        res = ex.execute("i", "Range(w < 5)")[0]  # rebuild at grown depth
        assert set(res.columns().tolist()) == {1}
        assert 2 + f.bit_depth > small and ex.stacks.bsi_cached(f, shards)
        # old-depth entry purged
        assert not ex.stacks.cached(f, shards, view, small)


class TestBSIAggServing:
    """Repeat unfiltered Sum/Min/Max against an unchanged field must be
    served from the per-snapshot scalar cache with zero device work
    (the same ranked-cache analogue as the gram/row-count caches)."""

    @pytest.fixture()
    def ex3(self):
        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.exec.executor import Executor
        from pilosa_tpu.core.field import FieldOptions

        h = Holder()
        idx = h.create_index("i")
        idx.create_field(
            "v", FieldOptions(field_type="int", min_=-500, max_=500)
        )
        # rescache off: the class asserts scalar-cache hits on repeats,
        # which the semantic result cache would serve first
        ex = Executor(h, rescache_entries=0)
        rng = np.random.default_rng(23)
        self.vals = {}
        width = h.n_words * 32
        for col in rng.choice(2 * width, size=120, replace=False):
            v = int(rng.integers(-500, 500))
            self.vals[int(col)] = v
            ex.execute("i", f"Set({int(col)}, v={v})")
        return h, ex

    def test_repeat_aggregates_served_without_launches(self, ex3):
        _, ex = ex3
        first = ex.execute("i", "Sum(field=v)Min(field=v)Max(field=v)")
        launches = ex.bsi_stack_launches
        hits = ex.stacks.bsi_agg_hits
        for _ in range(3):
            again = ex.execute("i", "Sum(field=v)Min(field=v)Max(field=v)")
            assert again == first
        assert ex.bsi_stack_launches == launches  # no further device work
        assert ex.stacks.bsi_agg_hits >= hits + 9

    def test_write_invalidates_cached_aggregates(self, ex3):
        _, ex = ex3
        before = ex.execute("i", "Sum(field=v)")[0]
        ex.execute("i", "Sum(field=v)")  # cache it
        free = next(
            c for c in range(10_000) if c not in self.vals
        )
        ex.execute("i", f"Set({free}, v=7)")
        after = ex.execute("i", "Sum(field=v)")[0]
        assert after.value == before.value + 7
        assert after.count == before.count + 1

    def test_filtered_sum_bypasses_cache(self, ex3):
        _, ex = ex3
        ex.execute("i", "Sum(field=v)")
        ex.execute("i", "Sum(field=v)")  # cached now
        some = sorted(self.vals)[:40]
        filt_rows = " ".join(f"Set({c}, f=1)" for c in some)
        ex.holder.index("i").create_field("f")
        ex.execute("i", filt_rows)
        got = ex.execute("i", "Sum(Row(f=1), field=v)")[0]
        assert got.value == sum(self.vals[c] for c in some)
        assert got.count == len(some)


class TestRangeCountServing:
    """Repeat Count(Range(v < N)) — the dashboard histogram shape — must
    be served from the per-snapshot scalar cache after its first
    compute."""

    @pytest.fixture()
    def ex2(self):
        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.exec.executor import Executor
        from pilosa_tpu.core.field import FieldOptions

        h = Holder()
        idx = h.create_index("i")
        idx.create_field(
            "v", FieldOptions(field_type="int", min_=-300, max_=300)
        )
        # rescache off: same scalar-cache accounting as TestBSIAggServing
        ex = Executor(h, rescache_entries=0)
        ex._BSI_SINGLE_WARM = 0  # assert stacked serving from query 1
        rng = np.random.default_rng(31)
        self.vals = {}
        width = h.n_words * 32
        for col in rng.choice(2 * width, size=150, replace=False):
            v = int(rng.integers(-300, 300))
            self.vals[int(col)] = v
            ex.execute("i", f"Set({int(col)}, v={v})")
        return h, ex

    def test_repeat_range_counts_served(self, ex2):
        _, ex = ex2
        for op, want in [
            ("Count(Row(v < 50))", sum(1 for v in self.vals.values() if v < 50)),
            ("Count(Row(v >= -10))", sum(1 for v in self.vals.values() if v >= -10)),
            ("Count(Row(v == 7))", sum(1 for v in self.vals.values() if v == 7)),
        ]:
            assert ex.execute("i", op)[0] == want
        launches = ex.bsi_stack_launches
        hits = ex.stacks.bsi_agg_hits
        for op, want in [
            ("Count(Row(v < 50))", sum(1 for v in self.vals.values() if v < 50)),
            ("Count(Row(v >= -10))", sum(1 for v in self.vals.values() if v >= -10)),
            ("Count(Row(v == 7))", sum(1 for v in self.vals.values() if v == 7)),
        ]:
            for _ in range(2):
                assert ex.execute("i", op)[0] == want
        assert ex.bsi_stack_launches == launches
        assert ex.stacks.bsi_agg_hits >= hits + 6

    def test_distinct_bounds_cached_separately(self, ex2):
        _, ex = ex2
        for n in (-100, 0, 100):
            want = sum(1 for v in self.vals.values() if v < n)
            assert ex.execute("i", f"Count(Row(v < {n}))")[0] == want
        launches = ex.bsi_stack_launches
        for n in (-100, 0, 100):
            want = sum(1 for v in self.vals.values() if v < n)
            assert ex.execute("i", f"Count(Row(v < {n}))")[0] == want
        assert ex.bsi_stack_launches == launches

    def test_write_invalidates_range_count(self, ex2):
        _, ex = ex2
        q = "Count(Row(v < 1000))"  # everything
        before = ex.execute("i", q)[0]
        ex.execute("i", q)  # cached
        free = next(c for c in range(10_000) if c not in self.vals)
        ex.execute("i", f"Set({free}, v=1)")
        assert ex.execute("i", q)[0] == before + 1

    def test_bitmap_result_not_affected(self, ex2):
        """Only the COUNT is cached — Row(v < N) as a bitmap result must
        still return the exact columns."""
        _, ex = ex2
        ex.execute("i", "Count(Row(v < 50))")
        ex.execute("i", "Count(Row(v < 50))")  # count cached
        cols = set(ex.execute("i", "Row(v < 50)")[0].columns().tolist())
        assert cols == {c for c, v in self.vals.items() if v < 50}
