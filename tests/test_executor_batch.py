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
