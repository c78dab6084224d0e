"""Batched Count(op(Row,Row)) fast path: one device launch per
(field, op) group must return exactly what the per-call path returns
(serving-mode analogue of reference executor.go:2454-2518 mapReduce)."""

import numpy as np
import pytest

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import stacks
from pilosa_tpu.exec.executor import Executor


@pytest.fixture()
def setup():
    h = Holder()
    idx = h.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    # rescache off: this file asserts gram/cross-gram serving-cache
    # behavior on repeats, below the semantic result cache
    ex = Executor(h, rescache_entries=0)
    rng = np.random.default_rng(4)
    writes = []
    # f and g draw columns from a shared pool so cross-field intersections
    # (GroupBy combos, filtered TopN) are non-trivial
    pool = rng.integers(0, 3 * h.n_words * 32, size=120)
    for row in range(6):
        for col in rng.choice(pool, size=50, replace=False):
            writes.append(f"Set({int(col)}, f={row})")
    for row in range(3):
        for col in rng.choice(pool, size=30, replace=False):
            writes.append(f"Set({int(col)}, g={row})")
    ex.execute("i", " ".join(writes))
    return h, ex


def _pairs_query(pairs, op="Intersect", field="f"):
    return " ".join(
        f"Count({op}(Row({field}={a}), Row({field}={b})))" for a, b in pairs
    )


def test_batch_matches_per_call(setup):
    _, ex = setup
    pairs = [(0, 1), (2, 3), (4, 5), (1, 1), (0, 5), (3, 2)]
    batched = ex.execute("i", _pairs_query(pairs))
    single = [ex.execute("i", _pairs_query([p]))[0] for p in pairs]
    assert batched == single
    assert any(c > 0 for c in batched)


@pytest.mark.parametrize("op", ["Intersect", "Union", "Difference", "Xor"])
def test_batch_ops_match(setup, op):
    _, ex = setup
    pairs = [(0, 1), (1, 2), (5, 0)]
    batched = ex.execute("i", _pairs_query(pairs, op=op))
    single = [ex.execute("i", _pairs_query([p], op=op))[0] for p in pairs]
    assert batched == single


def test_mixed_fields_and_ops_in_one_query(setup):
    _, ex = setup
    q = (
        "Count(Intersect(Row(f=0), Row(f=1))) "
        "Count(Union(Row(g=0), Row(g=1))) "
        "Count(Intersect(Row(g=1), Row(g=2))) "
        "Count(Row(f=2)) "
        "Count(Xor(Row(f=3), Row(f=4)))"
    )
    got = ex.execute("i", q)
    want = [ex.execute("i", part + ")")[0] for part in q.split(") ")[:-1]] + [
        ex.execute("i", "Count(Xor(Row(f=3), Row(f=4)))")[0]
    ]
    assert got == want


def test_missing_row_intersect_is_zero(setup):
    _, ex = setup
    got = ex.execute(
        "i",
        "Count(Intersect(Row(f=0), Row(f=99))) "
        "Count(Intersect(Row(f=1), Row(f=2)))",
    )
    assert got[0] == 0
    assert got[1] == ex.execute("i", _pairs_query([(1, 2)]))[0]


def test_missing_row_union_falls_back(setup):
    _, ex = setup
    got = ex.execute(
        "i",
        "Count(Union(Row(f=0), Row(f=99))) Count(Union(Row(f=1), Row(f=2)))",
    )
    want0 = ex.execute("i", "Count(Row(f=0))")[0]
    assert got[0] == want0
    assert got[1] == ex.execute("i", _pairs_query([(1, 2)], op="Union"))[0]


def test_cache_invalidated_by_write(setup):
    h, ex = setup
    q = _pairs_query([(0, 1), (2, 3)])
    before = ex.execute("i", q)
    f = h.index("i").field("f")
    frag = f.view("standard").fragments[0]
    assert not (frag.get_bit(0, 12345) and frag.get_bit(1, 12345))
    ex.execute("i", "Set(12345, f=0) Set(12345, f=1)")
    after = ex.execute("i", q)
    assert after[0] == before[0] + 1


def test_writes_before_counts_are_observed(setup):
    """In-order semantics: Counts after a write in the same query must see
    the write, so the batch fast path may only serve the pre-write prefix."""
    _, ex = setup
    col = 4321
    res = ex.execute(
        "i",
        f"Count(Intersect(Row(f=0), Row(f=1))) "
        f"Count(Intersect(Row(f=2), Row(f=3))) "
        f"Set({col}, f=0) Set({col}, f=1) "
        f"Count(Intersect(Row(f=0), Row(f=1))) "
        f"Count(Intersect(Row(f=2), Row(f=3)))",
    )
    pre01, pre23, s1, s2, post01, post23 = res
    assert post01 == pre01 + 1
    assert post23 == pre23


def test_options_wrapped_write_is_a_barrier(setup):
    """Options() can wrap a write; Counts after it must observe the write
    (the barrier walks descendants, not just top-level names)."""
    _, ex = setup
    col = 8765
    res = ex.execute(
        "i",
        f"Count(Intersect(Row(f=0), Row(f=1))) "
        f"Options(Set({col}, f=0), excludeColumns=false) "
        f"Options(Set({col}, f=1), excludeColumns=false) "
        f"Count(Intersect(Row(f=0), Row(f=1))) "
        f"Count(Intersect(Row(f=2), Row(f=3)))",
    )
    pre01, _, _, post01, _ = res
    assert post01 == pre01 + 1


def test_shards_argument_respected(setup):
    _, ex = setup
    q = _pairs_query([(0, 1), (2, 3)])
    all_shards = ex.execute("i", q)
    only0 = ex.execute("i", q, shards=[0])
    assert all(a >= b for a, b in zip(all_shards, only0))
    per = [ex.execute("i", _pairs_query([p]), shards=[0])[0] for p in [(0, 1), (2, 3)]]
    assert only0 == per


def test_interleaved_writes_update_stack_incrementally(setup):
    """A write batch touching one shard must refresh the cached stack via
    a device scatter of that shard block, not a full host restack
    (reference applies ops in place, fragment.go:2284-2293)."""
    h, ex = setup
    q = _pairs_query([(0, 1), (2, 3)])
    ex.execute("i", q)  # build + cache the stack
    rebuilds0 = ex.stacks.rebuilds
    width = h.n_words * 32
    for i in range(4):
        # rows 0/1 already exist in shard 0; no new rows => incremental
        ex.execute("i", f"Set({100 + i}, f=0) Set({100 + i}, f=1)")
        got = ex.execute("i", q)
        want = [ex.execute("i", _pairs_query([p]))[0] for p in [(0, 1), (2, 3)]]
        assert got == want
    assert ex.stacks.incremental >= 4
    assert ex.stacks.rebuilds == rebuilds0  # no full re-upload happened


def test_two_shard_sets_keep_separate_cache_entries(setup):
    """Alternating shards arguments must not evict each other (two cache
    entries per field)."""
    _, ex = setup
    q = _pairs_query([(0, 1), (2, 3)])
    ex.execute("i", q)
    ex.execute("i", q, shards=[0])
    r0 = ex.stacks.rebuilds
    # both entries warm: neither call rebuilds
    ex.execute("i", q)
    ex.execute("i", q, shards=[0])
    ex.execute("i", q)
    assert ex.stacks.rebuilds == r0


def test_new_row_forces_full_rebuild(setup):
    """A write creating a brand-new row changes the stack shape and must
    fall back to a full rebuild, still answering correctly."""
    _, ex = setup
    q = _pairs_query([(0, 1), (2, 3)])
    ex.execute("i", q)
    r0 = ex.stacks.rebuilds
    ex.execute("i", "Set(77, f=40)")  # row 40 did not exist
    got = ex.execute("i", q + " Count(Intersect(Row(f=40), Row(f=40)))")
    assert got[2] == 1
    assert ex.stacks.rebuilds == r0 + 1


def test_groupby_fast_path_matches_recursive(setup):
    _, ex = setup

    def norm(res):
        return [
            ([(fr.field, fr.row_id) for fr in gc.group], gc.count) for gc in res
        ]

    queries = [
        "GroupBy(Rows(f), Rows(g))",
        "GroupBy(Rows(g), Rows(f))",
        "GroupBy(Rows(f), Rows(f))",
        "GroupBy(Rows(f), Rows(g), limit=3)",
        # k-level + filter shapes (batched prefix-mask engine)
        "GroupBy(Rows(f), Rows(g), Rows(f))",
        "GroupBy(Rows(g), Rows(f), Rows(g), Rows(f))",
        "GroupBy(Rows(f), Rows(g), filter=Row(f=0))",
        "GroupBy(Rows(f), Rows(g), Rows(f), filter=Row(g=1))",
        "GroupBy(Rows(f), Rows(g), Rows(f), limit=5)",
    ]
    for q in queries:
        fast = ex.execute("i", q)[0]
        old_max = ex._GROUPBY_BATCH_MAX
        try:
            ex._GROUPBY_BATCH_MAX = 0  # force the recursive path
            slow = ex.execute("i", q)[0]
        finally:
            ex._GROUPBY_BATCH_MAX = old_max
        assert norm(fast) == norm(slow), q
        assert norm(fast), q  # non-trivial result


def test_filtered_topn_matches_per_fragment(setup):
    """Filtered TopN must match the per-fragment path bit-for-bit (one
    masked-count launch vs the old per-shard loop)."""
    h, ex = setup
    q = "TopN(f, Row(g=0), n=4)"
    fast = ex.execute("i", q)[0]
    # force the per-fragment path by disabling the stack
    field = h.index("i").field("f")
    old = stacks.Stacks.get
    try:
        stacks.Stacks.get = lambda self, f, s: None
        slow = ex.execute("i", q)[0]
    finally:
        stacks.Stacks.get = old
    assert [(p.id, p.count) for p in fast] == [(p.id, p.count) for p in slow]
    assert fast  # non-trivial


def test_filtered_topn_tanimoto_matches(setup):
    h, ex = setup
    q = "TopN(f, Row(g=1), n=6, tanimotoThreshold=5)"
    fast = ex.execute("i", q)[0]
    old = stacks.Stacks.get
    try:
        stacks.Stacks.get = lambda self, f, s: None
        slow = ex.execute("i", q)[0]
    finally:
        stacks.Stacks.get = old
    assert [(p.id, p.count) for p in fast] == [(p.id, p.count) for p in slow]


class TestGramCache:
    """The full-row gram caches on the stack entry (the ranked-cache
    analogue, reference cache.go): repeat batches answer from host
    memory, and any stack refresh drops it."""

    def test_repeat_batches_reuse_cached_gram(self, setup, monkeypatch):
        from pilosa_tpu.ops import kernels

        _, ex = setup
        calls = {"n": 0}
        orig = kernels.pair_gram

        def counting(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)

        monkeypatch.setattr(kernels, "pair_gram", counting)
        q = _pairs_query([(0, 1), (2, 3), (4, 5)])
        first = ex.execute("i", q)
        n_after_first = calls["n"]
        assert n_after_first >= 1
        second = ex.execute("i", q)
        assert calls["n"] == n_after_first  # cache hit: no new gram
        assert first == second

    def test_write_invalidates_cached_gram(self, setup):
        _, ex = setup
        q = _pairs_query([(0, 1), (2, 3)])
        before = ex.execute("i", q)
        ex.execute("i", "Set(123, f=0)Set(123, f=1)")
        after = ex.execute("i", q)
        assert after[0] == before[0] + 1  # new shared column counted

    def test_small_subsets_defer_full_gram_until_reuse(self, setup, monkeypatch):
        """Write-interleaved workloads must not pay full-row grams: the
        full gram is only invested after observed reuse on one
        snapshot."""
        from pilosa_tpu.ops import kernels

        _, ex = setup
        seen = []
        orig = kernels.pair_gram

        def recording(bits, rows, *a, **k):
            seen.append(len(rows))
            return orig(bits, rows, *a, **k)

        monkeypatch.setattr(kernels, "pair_gram", recording)
        monkeypatch.setattr(stacks, "GRAM_CACHE_MIN_REUSE", 2)
        q = _pairs_query([(0, 1), (1, 0)])  # 2 of 6 rows: a small subset
        ex.execute("i", q)
        assert seen and seen[-1] == 2  # subset gram, not full
        ex.execute("i", q)
        assert seen[-1] == 2  # still subset (second miss)
        ex.execute("i", q)
        assert seen[-1] == 6  # observed reuse: full gram invested
        n = len(seen)
        ex.execute("i", q)
        assert len(seen) == n  # cached: no further gram computation


class TestSinglePairServing:
    """Repeat LONE Count(op(Row,Row)) queries must warm up into the
    stack+gram path and then be served from the cached host gram with
    zero device work (the reference's ranked cache serving role,
    cache.go: repeat reads answered from memory)."""

    def test_singles_warm_then_serve_from_gram(self, setup):
        _, ex = setup
        q = "Count(Intersect(Row(f=0), Row(f=1)))"
        want = ex.execute("i", q)[0]
        # enough repeats to pass the warm-up threshold and the gram's
        # observed-reuse investment gate
        for _ in range(ex._PAIR_SINGLE_WARM + stacks.GRAM_CACHE_MIN_REUSE + 2):
            assert ex.execute("i", q)[0] == want
        assert ex.stacks.gram_hits >= 1
        hits, rebuilds = ex.stacks.gram_hits, ex.stacks.rebuilds
        # steady state: every further single is a pure host cache hit —
        # no stack rebuild, correct answers for other pairs too
        q2 = "Count(Union(Row(f=2), Row(f=3)))"
        want2 = ex.execute("i", _pairs_query([(2, 3)], op="Union"))[0]
        for _ in range(3):
            assert ex.execute("i", q)[0] == want
            assert ex.execute("i", q2)[0] == want2
        assert ex.stacks.gram_hits >= hits + 6
        assert ex.stacks.rebuilds == rebuilds

    def test_cold_singles_stay_on_per_call_path(self, setup):
        """A few one-off pair counts must NOT pay the stack build."""
        _, ex = setup
        q = "Count(Intersect(Row(f=0), Row(f=1)))"
        for _ in range(2):
            ex.execute("i", q)
        assert ex.stacks.rebuilds == 0

    def test_write_invalidates_served_gram(self, setup):
        """A write between served singles must be visible (the gram is
        keyed to the stack snapshot, never stale)."""
        _, ex = setup
        q = "Count(Intersect(Row(f=0), Row(f=1)))"
        for _ in range(ex._PAIR_SINGLE_WARM + stacks.GRAM_CACHE_MIN_REUSE + 2):
            before = ex.execute("i", q)[0]
        # add a column present in both rows: count must rise by 1
        free = 777_777
        ex.execute("i", f"Set({free}, f=0) Set({free}, f=1)")
        after = ex.execute("i", q)[0]
        assert after == before + 1


class TestTopNServing:
    """Unfiltered TopN is served from MAINTAINED per-fragment counts
    (host memory, no device work): writes carry the cached counts as
    deltas instead of invalidating them — the reference's incremental
    ranked-cache maintenance (cache.go:158, fragment.go:698-712)."""

    def test_topn_served_from_maintained_counts(self, setup, monkeypatch):
        """After the first TopN builds the counts, repeats (and repeats
        AFTER WRITES) must never launch the device count kernel nor
        recount the host mirror."""
        import pilosa_tpu.core.fragment as fragmod
        from pilosa_tpu.ops import kernels

        h, ex = setup
        want = ex.execute("i", "TopN(f, n=4)")[0]
        field = h.index("i").field("f")
        view = field.view("standard")
        assert all(
            f._counts is not None for f in view.fragments.values()
        )
        monkeypatch.setattr(
            kernels,
            "row_counts",
            lambda *a, **k: pytest.fail(
                "unfiltered TopN must not launch the device count kernel"
            ),
        )
        real_bc = fragmod.np.bitwise_count

        def no_recount(*a, **k):
            pytest.fail("maintained counts must not be recounted")

        for _ in range(3):
            monkeypatch.setattr(fragmod.np, "bitwise_count", no_recount)
            got = ex.execute("i", "TopN(f, n=4)")[0]
            monkeypatch.setattr(fragmod.np, "bitwise_count", real_bc)
            assert got == want
        # a write updates the maintained counts by delta — still no
        # recount on the next TopN
        top = want[0]
        # write into an EXISTING shard (a write creating a brand-new
        # fragment legitimately counts that one fragment from scratch)
        ex.execute("i", f"Set(9999, f={top.id})")
        monkeypatch.setattr(fragmod.np, "bitwise_count", no_recount)
        after = ex.execute("i", "TopN(f, n=4)")[0]
        monkeypatch.setattr(fragmod.np, "bitwise_count", real_bc)
        assert after[0].id == top.id and after[0].count == top.count + 1

    def test_maintained_counts_match_recount_after_imports(self, setup):
        """Import batches carry count deltas; the carried counts must
        equal a from-scratch recount."""
        import numpy as np

        from pilosa_tpu.shardwidth import SHARD_WIDTH

        h, ex = setup
        ex.execute("i", "TopN(f, n=4)")  # build counts
        idx = h.index("i")
        rng = np.random.default_rng(9)
        rows = rng.integers(0, 6, size=500).astype(np.uint64)
        cols = rng.integers(0, 3 * SHARD_WIDTH, size=500)
        idx.field("f").import_bits(rows, cols)
        view = idx.field("f").view("standard")
        for frag in view.fragments.values():
            if frag._counts is None:
                continue
            carried = frag._counts.copy()
            frag._counts = None
            _, recounted = frag.row_counts()
            assert np.array_equal(carried[: len(recounted)], recounted)

    def test_stack_row_counts_reuses_gram_diagonal(self, setup, monkeypatch):
        """The stack-level counts helper (used by the filtered/tanimoto
        throughput path) must reuse a cached gram's diagonal rather than
        launching the count kernel."""
        from pilosa_tpu.ops import kernels

        h, ex = setup
        # install the full gram via repeat batched pair-count queries
        q = _pairs_query([(a, b) for a in range(3) for b in range(3)])
        for _ in range(3):
            ex.execute("i", q)
        field = h.index("i").field("f")
        stack = ex.stacks.get(field, ex._shards_for(h.index("i"), None))
        bits = stack.bits
        gram = stack.get("gram", bits)
        assert gram is not None and stack.get("rowcounts", bits) is None
        monkeypatch.setattr(
            kernels,
            "row_counts",
            lambda *a, **k: pytest.fail(
                "must serve from the cached gram diagonal"
            ),
        )
        rc = ex.stacks.row_counts(stack, bits)
        assert np.array_equal(rc, np.diag(gram).astype(np.int64))

    def test_write_invalidates_served_topn(self, setup):
        _, ex = setup
        before = ex.execute("i", "TopN(f, n=1)")[0]
        ex.execute("i", "TopN(f, n=1)")  # cache the counts vector
        top_row, top_count = before[0].id, before[0].count
        free = 900_001
        ex.execute("i", f"Set({free}, f={top_row})")
        after = ex.execute("i", "TopN(f, n=1)")[0]
        assert after[0].id == top_row and after[0].count == top_count + 1


class TestGroupByCrossGramServing:
    """Repeat 2-level GroupBy across two unchanged fields must invest in
    the full cross-field gram once and then serve every combination
    matrix from host memory (zero device work per query)."""

    def test_repeat_groupby_served_from_cross_gram(self, setup):
        _, ex = setup
        q = "GroupBy(Rows(f), Rows(g))"
        want = ex.execute("i", q)[0]
        # warm past the observed-reuse investment gate
        for _ in range(stacks.GRAM_CACHE_MIN_REUSE + 2):
            assert ex.execute("i", q)[0] == want
        hits = ex.stacks.crossgram_hits
        for _ in range(3):
            assert ex.execute("i", q)[0] == want
        assert ex.stacks.crossgram_hits >= hits + 3
        # the reversed field order must serve from the SAME cached gram,
        # transposed, without a second device investment
        hits = ex.stacks.crossgram_hits
        rev = {
            tuple(sorted((fr.field, fr.row_id) for fr in gc.group)): gc.count
            for gc in ex.execute("i", "GroupBy(Rows(g), Rows(f))")[0]
        }
        fwd = {
            tuple(sorted((fr.field, fr.row_id) for fr in gc.group)): gc.count
            for gc in ex.execute("i", q)[0]
        }
        assert rev == fwd
        assert ex.stacks.crossgram_hits >= hits + 2

    def test_write_to_second_field_invalidates(self, setup):
        """The cross gram is keyed to BOTH snapshots: a write to the
        second field must be visible immediately."""
        h, ex = setup
        q = "GroupBy(Rows(f), Rows(g))"
        for _ in range(stacks.GRAM_CACHE_MIN_REUSE + 3):
            before = {
                tuple((fr.field, fr.row_id) for fr in gc.group): gc.count
                for gc in ex.execute("i", q)[0]
            }
        # find a column in f row 0 not in g row 0, add it to g row 0
        row_f0 = ex.execute("i", "Row(f=0)")[0].columns()
        row_g0 = set(ex.execute("i", "Row(g=0)")[0].columns())
        new_col = next(int(c) for c in row_f0 if int(c) not in row_g0)
        ex.execute("i", f"Set({new_col}, g=0)")
        after = {
            tuple((fr.field, fr.row_id) for fr in gc.group): gc.count
            for gc in ex.execute("i", q)[0]
        }
        key = (("f", 0), ("g", 0))
        assert after[key] == before[key] + 1

    def test_alternating_partners_keep_separate_slots(self, setup):
        """GroupBy(f, g) alternating with GroupBy(f, h) must keep one
        cached gram per partner — no thrash, no per-query full-device
        recompute."""
        h_, ex = setup
        idx = h_.index("i")
        idx.create_field("h")
        rng = np.random.default_rng(7)
        writes = []
        for row in range(3):
            for col in rng.integers(0, 2 * h_.n_words * 32, size=25):
                writes.append(f"Set({int(col)}, h={row})")
        ex.execute("i", " ".join(writes))
        qa, qb = "GroupBy(Rows(f), Rows(g))", "GroupBy(Rows(f), Rows(h))"
        wa = ex.execute("i", qa)[0]
        wb = ex.execute("i", qb)[0]
        for _ in range(stacks.GRAM_CACHE_MIN_REUSE + 2):
            assert ex.execute("i", qa)[0] == wa
            assert ex.execute("i", qb)[0] == wb
        hits = ex.stacks.crossgram_hits
        for _ in range(3):
            assert ex.execute("i", qa)[0] == wa
            assert ex.execute("i", qb)[0] == wb
        assert ex.stacks.crossgram_hits >= hits + 6  # both served

    def test_cached_cross_gram_does_not_pin_partner_stack(self, setup):
        """The slot holds the partner snapshot weakly: dropping the
        partner's stack entry must let its device array die, and the
        next GroupBy must recompute correctly."""
        import gc
        import weakref as wr

        h_, ex = setup
        q = "GroupBy(Rows(f), Rows(g))"
        want = ex.execute("i", q)[0]
        for _ in range(stacks.GRAM_CACHE_MIN_REUSE + 2):
            ex.execute("i", q)
        g_field = h_.index("i").field("g")
        gstack = ex.stacks.get(g_field, ex._shards_for(h_.index("i"), None))
        ref = wr.ref(gstack.bits)
        stacks.drop(g_field)  # as a budget eviction would
        del gstack
        gc.collect()
        assert ref() is None  # nothing pins the retired device stack
        assert ex.execute("i", q)[0] == want  # recomputes, still right


def test_recreated_fragment_never_aliases_cached_stack(setup):
    """A shard's fragment dropped (resize cleanup) and re-created
    restarts version at 0; if its mutation count coincides with the
    cached stack's recorded number, the stack must STILL rebuild — the
    epoch pins object identity (regression: versions compared by number
    alone could serve stale bits)."""
    h, ex = setup
    q = _pairs_query([(0, 1)])
    before = ex.execute("i", q + " " + _pairs_query([(2, 3)]))[0]
    f = h.index("i").field("f")
    view = f.view("standard")
    old = view.fragments[0]
    v_old = old.version
    rows_snapshot = old.to_host_rows()
    # replace with a NEW object: same bits plus one extra shared column,
    # then pad its version to EXACTLY the old recorded number with
    # cancelling scratch writes
    view.drop_fragment(0)
    frag = view.create_fragment_if_not_exists(0)
    frag.load_host_rows(rows_snapshot)  # version -> 1
    frag.set_bit(0, 999)
    frag.set_bit(1, 999)  # both rows share col 999 now: count + 1
    while frag.version < v_old - 1:
        frag.set_bit(63, 5)
        frag.clear_bit(63, 5)
    frag.set_bit(63, 7)  # land exactly on v_old (harmless row)
    while frag.version < v_old:
        frag.set_bit(63, 8)
    assert frag.version >= v_old
    after = ex.execute("i", q)[0]
    assert after == before + 1  # rebuilt from the NEW object's bits


class TestSpanningMeshDecline:
    """When row_counts_supported is False — a process-spanning mesh so
    tall (>2047 devices at full width) that even the chunked in-program
    psum would overflow int32 — the gram-declined batched scan lanes
    must fall through to the per-fragment paths, not launch anyway."""

    def _force_unsupported(self, monkeypatch):
        from pilosa_tpu.ops import kernels

        monkeypatch.setattr(kernels, "row_counts_supported", lambda bits: False)

        def boom(*a, **k):
            raise AssertionError(
                "batched pair scan must decline on an unsupported mesh"
            )

        monkeypatch.setattr(kernels, "pair_count_batched", boom)
        monkeypatch.setattr(kernels, "pair_count_two_batched", boom)

    def test_pair_scan_declines_to_per_call(self, setup, monkeypatch):
        _, ex = setup
        pairs = [(0, 1), (2, 3), (4, 5)]
        want = [ex.execute("i", _pairs_query([p]))[0] for p in pairs]
        # gram declines (as if > GRAM_MAX_ROWS distinct rows) ...
        monkeypatch.setattr(
            stacks.Stacks, "gram", lambda self, f, st, bits, uniq: (None, None)
        )
        # ... and the mocked mesh rejects the scan lane too
        self._force_unsupported(monkeypatch)
        assert ex.execute("i", _pairs_query(pairs)) == want

    def test_groupby_batch_declines_to_recursion(self, setup, monkeypatch):
        _, ex = setup
        q = "GroupBy(Rows(f), Rows(g))"
        want = ex.execute("i", q)[0]
        assert want  # non-trivial combos
        monkeypatch.setattr(
            stacks.Stacks, "gram", lambda self, f, st, bits, uniq: (None, None)
        )
        monkeypatch.setattr(stacks.Stacks, "cross_gram", lambda *a, **k: None)
        self._force_unsupported(monkeypatch)
        assert ex.execute("i", q)[0] == want


# ------------------------------------------------------- the GroupBy lane

from pilosa_tpu import pql  # noqa: E402
from pilosa_tpu.core.field import FieldOptions  # noqa: E402
from pilosa_tpu.ops import kernels  # noqa: E402

# what a flight is cut from, in this order: k-level calls under filters
# first, so that a flight of two already has two levels to put side by side
LANE_POOL = [
    "GroupBy(Rows(f), Rows(g), Rows(h), filter=Row(h=0))",
    "GroupBy(Rows(f), Rows(g), filter=Intersect(Union(Row(h=0), Row(h=1)), "
    "Union(Row(f=0), Row(f=1), Row(f=2))))",
    "GroupBy(Rows(g), Rows(h), filter=Intersect(Row(f=1), Row(v < 500)))",  # a BSI range leaf
    "GroupBy(Rows(f), Rows(g), Rows(h), Rows(f))",  # four levels
    "GroupBy(Rows(f), Rows(g), Rows(h), limit=4)",
    "GroupBy(Rows(f), Rows(g))",  # unfiltered two-level: the cross gram's
    "GroupBy(Rows(f), Rows(e), filter=Row(h=1))",  # an empty level
    "GroupBy(Rows(f), Rows(g), previous=[1, 0])",  # paged: per call
    "GroupBy(Rows(f), Rows(g), filter=Row(v > 200))",  # the BSI lane's
    "GroupBy(Rows(f), Rows(h), filter=Row(h=9))",  # a filter no column meets
    "GroupBy(Rows(g), Rows(f), Rows(h), filter=Union(Row(h=2), Row(h=3)))",
    "GroupBy(Rows(f))",  # one level: per call
    "Sum(Intersect(Row(f=1), Row(h=0)), field=v)",
    "Sum(Intersect(Row(f=2), Row(h=0)), field=v)",
]
# which of them the lane takes, and which of those on the k-level steps
LANE_TAKES = {0, 1, 2, 3, 4, 5, 6, 9, 10}
LANE_K_LEVEL = LANE_TAKES - {5}
UNKNOWN = "GroupBy(Rows(f), Rows(nope), filter=Row(h=0))"


def _groups(res):
    return [([(fr.field, fr.row_id) for fr in gc.group], gc.count) for gc in res] \
        if isinstance(res, list) else (res.value, res.count)


@pytest.fixture()
def grouped():
    h = Holder()
    idx = h.create_index("i")
    for name in "fghe":
        idx.create_field(name)
    idx.create_field("v", FieldOptions(field_type="int", min_=0, max_=1000))
    ex = Executor(h, rescache_entries=0)
    rng = np.random.default_rng(41)
    pool = rng.integers(0, 3 * h.n_words * 32, size=160)
    writes = [
        f"Set({int(c)}, {name}={row})"
        for name, rows in (("f", 6), ("g", 3), ("h", 4))
        for row in range(rows)
        for c in rng.choice(pool, size=70, replace=False)
    ]
    writes += [f"Set({int(c)}, v={int(rng.integers(0, 1000))})" for c in pool[:120]]
    ex.execute("i", " ".join(writes))
    ex.execute("i", "Set(1, e=0) Clear(1, e=0)")  # a view, and no row
    return h, idx, ex


def _call_by_call(ex, idx, q):
    """The per-call driver's answer: ``_execute_call`` of the one call."""
    call = pql.parse(q).calls[0].clone()
    ex._translate_call(idx, call)
    return _groups(ex._execute_call(idx, call, None))


def _flight(n):
    return [LANE_POOL[k % len(LANE_POOL)] for k in range(n)]


@pytest.mark.parametrize("squeeze", ["none", "prefix", "lane"])
@pytest.mark.parametrize("entry", ["execute_batch", "execute"])
@pytest.mark.parametrize("n", [1, 2, 6, 16])
def test_groupby_lane_answers_as_call_by_call_and_as_the_recursive_path(
    grouped, monkeypatch, n, entry, squeeze
):
    """Flights of 1, 2, 6 and 16 calls through both entries: every answer
    is the per-call driver's and the recursive path's; a query with an
    unknown field fails alone; with a prefix budget of four masks the
    calls past it take the recursive path once, inside the lane; with a
    byte bound of one byte the lane keeps the call-by-call order."""
    h, idx, ex = grouped
    qs = _flight(n)
    want = [_call_by_call(ex, idx, q) for q in qs]
    with monkeypatch.context() as m:
        m.setattr(Executor, "_GROUPBY_BATCH_MAX", 0)  # the recursive path
        assert [_call_by_call(ex, idx, q) for q in qs] == want
    assert all(w for q, w in zip(qs, want) if "e)" not in q and "h=9" not in q)
    if squeeze == "prefix":
        mask = Executor._groupby_mask_bytes(
            ex.stacks.get(idx.field("f"), ex._shards_for(idx, None)).bits
        )
        monkeypatch.setattr(Executor, "_GROUPBY_PREFIX_BUDGET_BYTES", 4 * mask)
    elif squeeze == "lane":
        monkeypatch.setattr(Executor, "_GROUPBY_LANE_BUDGET_BYTES", 1)
    recursive, per_call = [], []
    inner_rec, inner_call = ex._groupby_recursive, ex._execute_call
    monkeypatch.setattr(
        ex, "_groupby_recursive",
        lambda *a: recursive.append(1) or inner_rec(*a),
    )
    monkeypatch.setattr(
        ex, "_execute_call",
        lambda i, c, s: per_call.append(c.name) or inner_call(i, c, s),
    )
    before = dict(ex.groupby_lane)
    if entry == "execute_batch":
        out = ex.execute_batch("i", [(q, None) for q in qs] + [(UNKNOWN, None)])
        assert isinstance(out[-1], Exception) and "nope" in str(out[-1])
        got = [_groups(r[0]) for r in out[:-1]]
    else:
        got = [_groups(r) for r in ex.execute("i", " ".join(qs))]
    assert got == want
    lane = {k: ex.groupby_lane[k] - before[k] for k in before}
    flight = [k % len(LANE_POOL) for k in range(n)]
    taken = [k for k in flight if k in LANE_TAKES]
    k_level = [k for k in taken if k in LANE_K_LEVEL]
    assert lane["calls"] == len(taken)
    # the lane's calls never reach the per-call path, whatever answered
    # them: the paged call, the call of one level and the unknown field do
    left = sum(k in (7, 11) for k in flight)
    assert per_call.count("GroupBy") == left + (entry == "execute_batch")
    if squeeze == "prefix":
        # a first level of f is six masks, past the four; g's three fit,
        # g x f's survivors (10) do not.  The BSI lane's call (8) goes
        # through the same steps
        past = sum(k in (0, 1, 3, 4, 6, 8, 9, 10) for k in flight)
        assert len(recursive) == left + past > 0
    else:
        assert len(recursive) == left
    if squeeze == "lane":
        assert lane["inflight_sum"] == lane["pulls"]
        assert (lane["budget_waits"] > 0) == (len(k_level) > 1)
    elif squeeze == "none":
        assert lane["budget_waits"] == 0
        if len(k_level) > 1:
            assert lane["inflight_sum"] > lane["pulls"] >= len(k_level) - 2


@pytest.mark.parametrize("bound", [None, 1])
def test_groupby_lane_launches_every_first_level_before_it_awaits_one(
    grouped, monkeypatch, bound
):
    """The order of work: every admitted call's first level is launched
    before the first ``GroupBy`` pull, the BSI lane runs between the
    lane's start and its finish; under a byte bound of one byte one
    level is in flight at a time."""
    h, idx, ex = grouped
    qs = [q for k, q in enumerate(LANE_POOL) if k in (0, 1, 2, 3, 10, 12, 13)]
    want = [_call_by_call(ex, idx, q) for q in qs]
    if bound is not None:
        monkeypatch.setattr(Executor, "_GROUPBY_LANE_BUDGET_BYTES", bound)
    events = []
    for name in ("combo_counts_gram", "combo_counts"):
        inner = getattr(kernels, name)

        def launch(*a, _inner=inner, **kw):
            out = _inner(*a, **kw)
            if out is not None:
                events.append("launch")
            return out

        monkeypatch.setattr(kernels, name, launch)
    inner_pull, inner_bsi = kernels.pull, ex._batch_bsi

    def pull(out, kernel=""):
        if kernel in ("combo_gram", "combo_counts"):
            events.append("pull")
        return inner_pull(out, kernel)

    monkeypatch.setattr(kernels, "pull", pull)
    monkeypatch.setattr(
        ex, "_batch_bsi", lambda *a: events.append("bsi") or inner_bsi(*a)
    )
    waits = ex.groupby_lane["budget_waits"]
    out = ex.execute_batch("i", [(q, None) for q in qs])
    assert [_groups(r[0]) for r in out] == want
    assert events.count("bsi") == 1 and events.count("launch") == events.count("pull")
    at = events.index("bsi")
    assert "pull" not in events[:at]
    inflight = peak = 0
    for e in events:
        inflight += {"launch": 1, "pull": -1, "bsi": 0}[e]
        peak = max(peak, inflight)
    if bound is None:
        assert events[:at] == ["launch"] * 5  # five GroupBy calls, five first levels
        assert ex.groupby_lane["budget_waits"] == waits and peak == 5
    else:
        assert events[:at] == ["launch"] and peak == 1
        assert ex.groupby_lane["budget_waits"] > waits


def test_groupby_lane_trouble_on_the_recursive_path_fails_its_query_alone(
    grouped, monkeypatch
):
    """Steps that decline are answered by the recursive path inside the
    lane's per-item ``try``: trouble there is counted, the slot is left to
    the per-call path, and its error lands on the owning query alone."""
    h, idx, ex = grouped
    qs = [LANE_POOL[0], LANE_POOL[2], LANE_POOL[12]]  # f first: past four masks; g first: fits
    want = [_call_by_call(ex, idx, q) for q in qs]
    mask = Executor._groupby_mask_bytes(
        ex.stacks.get(idx.field("f"), ex._shards_for(idx, None)).bits
    )
    monkeypatch.setattr(Executor, "_GROUPBY_PREFIX_BUDGET_BYTES", 4 * mask)

    def broken(*a):
        raise RuntimeError("no rows today")

    monkeypatch.setattr(ex, "_groupby_recursive", broken)
    errors = ex.lane_declines["groupby"]["error"]
    out = ex.execute_batch("i", [(q, None) for q in qs])
    assert isinstance(out[0], RuntimeError) and "no rows today" in str(out[0])
    assert [_groups(r[0]) for r in out[1:]] == want[1:]
    assert ex.lane_declines["groupby"]["error"] == errors + 1
