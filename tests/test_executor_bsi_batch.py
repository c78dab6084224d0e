"""Cross-request BSI batch lane (executor._batch_bsi): grouped
Range/Count/Sum/Min/Max/GroupBy flights must return exactly what the
per-call path returns, share launches, and demux per-query errors."""

import threading

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.server.batcher import QueryBatcher

PARTS = [
    "Row(v < 100)",
    "Row(v >= -50)",
    "Row(v >< [-10, 10])",
    "Row(v != 0)",
    "Row(v != null)",
    "Count(Row(v > 0))",
    "Count(Row(v <= -200))",
    "Sum(field=v)",
    "Sum(Row(v > 0), field=v)",
    "Sum(Row(v < 0), field=v)",
    "Min(field=v)",
    "Max(field=v)",
    "GroupBy(Rows(seg), filter=Row(v > 200))",
]


@pytest.fixture()
def setup():
    h = Holder()
    idx = h.create_index("i")
    idx.create_field(
        "v", FieldOptions(field_type="int", min_=-1000, max_=1000)
    )
    idx.create_field("seg")
    # rescache off: this file asserts BSI launch/agg-cache accounting on
    # repeats, below the semantic result cache
    ex = Executor(h, rescache_entries=0)
    rng = np.random.default_rng(9)
    writes = []
    for c in rng.choice(40_000, size=600, replace=False):
        writes.append(f"Set({int(c)}, v={int(rng.integers(-900, 900))})")
    for c in rng.choice(40_000, size=250, replace=False):
        writes.append(f"Set({int(c)}, seg={int(rng.integers(0, 4))})")
    ex.execute("i", " ".join(writes))
    return h, ex


def _norm(r):
    return sorted(r.columns()) if hasattr(r, "columns") else r


def _per_call_results(h, parts):
    """Ground truth through a fresh warm executor's per-call path."""
    ex = Executor(h)
    ex._BSI_SINGLE_WARM = 0
    return [ex.execute("i", p)[0] for p in parts]


def test_mixed_op_flight_matches_per_call(setup):
    h, ex = setup
    batched = ex.execute("i", " ".join(PARTS))
    singles = _per_call_results(h, PARTS)
    for p, a, b in zip(PARTS, batched, singles):
        na, nb = _norm(a), _norm(b)
        assert na == nb or str(na) == str(nb), p


def test_flight_shares_launches(setup):
    """5 range masks + 2 counts must not cost 7 dispatches: masks share
    one launch, counts share one."""
    _, ex = setup
    mask_parts = PARTS[:5]
    count_parts = PARTS[5:7]
    ex.execute("i", " ".join(mask_parts))  # builds the stack
    before = ex.bsi_stack_launches
    ex.execute("i", " ".join(mask_parts + count_parts))
    assert ex.bsi_stack_launches - before <= 2


def test_execute_batch_parity_and_demux(setup):
    h, ex = setup
    queries = [(p, None) for p in PARTS]
    queries.insert(3, ("Row(v == null)", None))  # invalid mid-flight
    out = ex.execute_batch("i", queries)
    bad = out.pop(3)
    assert isinstance(bad, Exception)
    singles = _per_call_results(h, PARTS)
    for p, a, b in zip(PARTS, out, singles):
        assert not isinstance(a, BaseException), (p, a)
        na, nb = _norm(a[0]), _norm(b)
        assert na == nb or str(na) == str(nb), p


def test_cold_lone_range_stays_off_device(setup):
    """A single cold Range must keep the per-call warm-up economics —
    the batch lane engages only on >= 2 flight-mates or a live stack."""
    h, _ = setup
    ex = Executor(h)
    before = ex.bsi_stack_launches
    ex.execute("i", "Row(v < 5)")
    assert ex.bsi_stack_launches == before


def test_range_count_served_from_agg_cache(setup):
    _, ex = setup
    q = "Count(Row(v < 77)) Count(Row(v > 5))"
    first = ex.execute("i", q)
    before = ex.bsi_stack_launches
    hits0 = ex.stacks.bsi_agg_hits
    second = ex.execute("i", q)
    assert second == first
    assert ex.bsi_stack_launches == before  # both served from cache
    assert ex.stacks.bsi_agg_hits > hits0


def test_batcher_coalesces_concurrent_bsi_reads(setup):
    """Concurrent single-query BSI requests through the serving plane
    must share a flight (batch_size > 1) and demux per request."""
    _, ex = setup
    ex.execute("i", " ".join(PARTS[:2]))  # warm the stack
    import pilosa_tpu.pql as pql

    batcher = QueryBatcher(ex, window=0.05, max_batch=16)
    try:
        gate = threading.Barrier(6)
        results: dict[int, object] = {}

        def worker(k, q):
            gate.wait(5)
            try:
                results[k] = batcher.submit("i", pql.parse(q))
            except Exception as e:  # pragma: no cover - diagnostic
                results[k] = e

        qs = [
            "Count(Row(v < 100))",
            "Count(Row(v > 100))",
            "Row(v >= 0)",
            "Sum(field=v)",
            "Count(Row(v < 100))",
            "Min(field=v)",
        ]
        threads = [
            threading.Thread(target=worker, args=(k, q), daemon=True)
            for k, q in enumerate(qs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert batcher.coalesced > 1, batcher.snapshot()
        for k, q in enumerate(qs):
            assert not isinstance(results[k], BaseException), (q, results[k])
        assert results[0] == results[4]
        direct = [ex.execute("i", q)[0] for q in qs]
        for k, q in enumerate(qs):
            got = results[k][0]
            assert _norm(got) == _norm(direct[k]) or str(got) == str(
                direct[k]
            ), q
    finally:
        batcher.close()


# ------------------------------------------------- filtered range counts
#
# Count(Intersect(Row(set field), ..., Row(int condition))) rides the BSI
# lane as one launch per (int field, filter stacks) group, its filter rows
# gathered on the device.  The lane serves single-device stacks, so these
# tests take the serving mesh down to one device.

_T0, _T1 = "2017-01-01T00:00", "2017-02-01T00:00"


@pytest.fixture(scope="module")
def fdata():
    """Three shards of columns with ``v`` (base 0, both signs), ``nb`` (a
    negative base), one or two ``seg`` rows, a ``g`` row, and the python
    record of each column for the numpy-side count."""
    h = Holder()
    idx = h.create_index("i")
    idx.create_field(
        "v", FieldOptions(field_type="int", min_=-1000, max_=1000)
    )
    idx.create_field(
        "nb", FieldOptions(field_type="int", min_=-900, max_=-100)
    )
    assert idx.field("nb").base == -100
    for name in ("seg", "g", "cold"):
        idx.create_field(name)
    idx.create_field("t", FieldOptions(field_type="time", time_quantum="YMD"))
    ex = Executor(h, rescache_entries=0, max_writes_per_request=0)
    rng = np.random.default_rng(26)
    cols: dict[int, dict] = {}
    writes = []
    for c in rng.choice(40_000, size=900, replace=False).tolist():
        rec = cols[c] = {"seg": set(), "g": None, "v": None, "nb": None}
        if rng.random() < 0.8:
            rec["v"] = int(rng.integers(-900, 900))
            writes.append(f"Set({c}, v={rec['v']})")
        if rng.random() < 0.5:
            rec["nb"] = int(rng.integers(-900, -99))
            writes.append(f"Set({c}, nb={rec['nb']})")
        for r in rng.choice(4, size=int(rng.integers(0, 3)), replace=False):
            rec["seg"].add(int(r))
            writes.append(f"Set({c}, seg={int(r)})")
            writes.append(f"Set({c}, cold={int(r)})")
        if rng.random() < 0.7:
            rec["g"] = int(rng.integers(0, 3))
            writes.append(f"Set({c}, g={rec['g']})")
        if rng.random() < 0.3:
            writes.append(f"Set({c}, t=1, 2017-01-05T00:00)")
    ex.execute("i", " ".join(writes))
    return h, cols


@pytest.fixture()
def lane(fdata):
    """(holder, executor, columns) on a one-device serving mesh, with the
    v / nb / seg / g stacks live."""
    from pilosa_tpu.parallel import mesh

    h, cols = fdata
    mesh.configure_serving(1)
    try:
        ex = Executor(h, rescache_entries=0)
        for f in ("v", "nb"):
            ex.execute(
                "i",
                f"Count(Intersect(Row(seg=0), Row(g=0), Row({f} < 0)))" * 2,
            )
        yield h, ex, cols
    finally:
        mesh.configure_serving(None)


def _per_call(h, ex, q):
    """The answer of the per-call path, whatever stacks are live."""
    import pilosa_tpu.pql as pql

    idx = h.index("i")
    call = pql.parse(q).calls[0].clone()
    ex._translate_call(idx, call)
    return ex._execute_call(idx, call, None)


def _span(name):
    from pilosa_tpu.obs import tracing

    row = tracing.spans_snapshot()["executor"][name]
    return row["count"], row["items"]


def _count(cols, pred):
    return sum(1 for rec in cols.values() if pred(rec))


def _has(field, op):
    """Predicate on a column's int value (None: no value, matches no
    comparison)."""
    return lambda rec: rec[field] is not None and op(rec[field])


def _seg(*rows):
    return lambda rec: all(r in rec["seg"] for r in rows)


def _both(*preds):
    return lambda rec: all(p(rec) for p in preds)


def _op_case(cond, op):
    """A flight of two filtered counts of one condition over two rows."""
    return [
        (f"Count(Intersect(Row(seg={r}), Row({cond})))",
         _both(_seg(r), _has("v", op)))
        for r in (1, 2)
    ]


# name -> ([(pql, numpy-side predicate)], filtered launches, other launches)
_LANE_CASES = {
    "lt": (_op_case("v < 37", lambda v: v < 37), 1, 0),
    "le": (_op_case("v <= -37", lambda v: v <= -37), 1, 0),
    "gt": (_op_case("v > -1", lambda v: v > -1), 1, 0),
    "ge": (_op_case("v >= 250", lambda v: v >= 250), 1, 0),
    "eq": (_op_case("v == 12", lambda v: v == 12), 1, 0),
    "ne": (_op_case("v != 0", lambda v: v != 0), 1, 0),
    "between": (_op_case("v >< [-100, 100]", lambda v: -100 <= v <= 100), 1, 0),
    "lt_lt": (_op_case("-50 < v < 50", lambda v: -50 < v < 50), 1, 0),
    "le_lt": (_op_case("-50 <= v < 50", lambda v: -50 <= v < 50), 1, 0),
    "lt_le": (_op_case("-50 < v <= 50", lambda v: -50 < v <= 50), 1, 0),
    "le_le": (_op_case("-50 <= v <= 50", lambda v: -50 <= v <= 50), 1, 0),
    "not_null": (_op_case("v != null", lambda v: True), 1, 0),
    "negative_base": (
        [
            ("Count(Intersect(Row(seg=1), Row(nb < -300)))",
             _both(_seg(1), _has("nb", lambda v: v < -300))),
            ("Count(Intersect(Row(seg=2), Row(nb >= -500)))",
             _both(_seg(2), _has("nb", lambda v: v >= -500))),
            ("Count(Intersect(Row(seg=3), Row(nb == -100)))",
             _both(_seg(3), _has("nb", lambda v: v == -100))),
        ], 1, 0,
    ),
    "out_of_band": (
        [
            ("Count(Intersect(Row(seg=1), Row(v < 5000)))",
             _both(_seg(1), _has("v", lambda v: True))),
            ("Count(Intersect(Row(seg=1), Row(v > 5000)))", lambda rec: False),
            ("Count(Intersect(Row(seg=2), Row(v >= -5000)))",
             _both(_seg(2), _has("v", lambda v: True))),
            ("Count(Intersect(Row(seg=2), Row(v == 4096)))", lambda rec: False),
        ], 1, 0,
    ),
    "absent_row": (
        [
            ("Count(Intersect(Row(seg=99), Row(v < 37)))", lambda rec: False),
            ("Count(Intersect(Row(seg=1), Row(v < 37)))",
             _both(_seg(1), _has("v", lambda v: v < 37))),
        ], 1, 0,
    ),
    "both_orders": (
        [
            ("Count(Intersect(Row(v < 10), Row(seg=1)))",
             _both(_seg(1), _has("v", lambda v: v < 10))),
            ("Count(Intersect(Row(seg=2), Range(v < 10)))",
             _both(_seg(2), _has("v", lambda v: v < 10))),
        ], 1, 0,
    ),
    "two_leaves": (
        [
            ("Count(Intersect(Row(seg=1), Row(g=2), Row(v > 0)))",
             _both(_seg(1), lambda rec: rec["g"] == 2,
                   _has("v", lambda v: v > 0))),
            ("Count(Intersect(Row(g=0), Row(v <= 0), Row(seg=3)))",
             _both(_seg(3), lambda rec: rec["g"] == 0,
                   _has("v", lambda v: v <= 0))),
            # both leaves in one field: a group of its own
            ("Count(Intersect(Row(seg=1), Row(seg=2), Row(v != null)))",
             _both(_seg(1, 2), _has("v", lambda v: True))),
        ], 2, 0,
    ),
    "mixed_with_unfiltered": (
        _op_case("v < 37", lambda v: v < 37)
        + [
            ("Count(Row(v > 3))", _has("v", lambda v: v > 3)),
            ("Count(Row(v <= -400))", _has("v", lambda v: v <= -400)),
        ], 1, 1,
    ),
    "one_launch_for_n": (
        [
            (f"Count(Intersect(Row(seg={k % 4}), Row(v < {k * 97 - 400})))",
             _both(_seg(k % 4), _has("v", lambda v, k=k: v < k * 97 - 400)))
            for k in range(9)
        ], 1, 0,
    ),
}


@pytest.mark.parametrize("name", sorted(_LANE_CASES))
def test_filtered_count_lane(lane, name):
    """The lane against the per-call path and a count over the python
    records; one launch a (int field, filter stacks) group, however many
    calls, and no ``executor.bsiSplit`` on its account."""
    h, ex, cols = lane
    items, n_filtered, n_other = _LANE_CASES[name]
    launches0 = ex.bsi_stack_launches
    span0, split0 = _span("bsiFilteredCountBatch"), _span("bsiSplit")
    got = ex.execute("i", " ".join(q for q, _ in items))
    span1, split1 = _span("bsiFilteredCountBatch"), _span("bsiSplit")
    assert ex.bsi_stack_launches - launches0 == n_filtered + n_other
    assert span1[0] - span0[0] == n_filtered
    # the span's items are the calls served
    assert span1[1] - span0[1] == len(items) - 2 * n_other
    assert split1[0] - split0[0] == n_other
    for (q, pred), n in zip(items, got):
        assert n == _count(cols, pred), q
        assert n == _per_call(h, ex, q), q


def _two_conditions(rec):
    return 1 in rec["seg"] and rec["v"] is not None and -10 < rec["v"] < 10


# name -> [(pql, predicate)]: flights the lane leaves to the per-call path
_DECLINE_CASES = {
    "two_conditions": [
        ("Count(Intersect(Row(seg=1), Row(v < 10), Row(v > -10)))",
         _two_conditions),
    ] * 2,
    "union_under_intersect": [
        ("Count(Intersect(Union(Row(seg=1), Row(seg=2)), Row(v < 10)))",
         lambda rec: bool(rec["seg"] & {1, 2})
         and rec["v"] is not None and rec["v"] < 10),
    ] * 2,
    "time_range_leaf": [
        (f"Count(Intersect(Row(t=1, from={_T0}, to={_T1}), Row(v < 10)))",
         None),
    ] * 2,
    # a filter stack nobody built, read by one call of the flight (the
    # unfiltered count keeps the int field's lane engaged)
    "cold_filter_stack": [
        ("Count(Intersect(Row(cold=1), Row(v < 10)))",
         _both(_seg(1), _has("v", lambda v: v < 10))),
        ("Count(Row(v < 10))", _has("v", lambda v: v < 10)),
    ],
}


@pytest.mark.parametrize("name", sorted(_DECLINE_CASES))
def test_filtered_count_declines_to_per_call(lane, name):
    h, ex, cols = lane
    items = _DECLINE_CASES[name]
    span0 = _span("bsiFilteredCountBatch")
    got = ex.execute("i", " ".join(q for q, _ in items))
    assert _span("bsiFilteredCountBatch") == span0
    for (q, pred), n in zip(items, got):
        assert n == _per_call(h, ex, q), q
        if pred is not None:
            assert n == _count(cols, pred), q


def test_filtered_count_rides_the_lane_on_a_mesh_sharded_stack(fdata):
    """Under the suite's eight-device serving mesh the stacks are sharded
    and the lane answers as one SPMD launch a group, with the same counts
    (tests/test_mesh_lanes.py holds the lane to a reference on four)."""
    import jax

    assert jax.local_device_count() > 1
    h, cols = fdata
    ex = Executor(h, rescache_entries=0)
    items = _op_case("v < 37", lambda v: v < 37)
    span0 = _span("bsiFilteredCountBatch")
    got = ex.execute("i", " ".join(q for q, _ in items) * 2)
    assert _span("bsiFilteredCountBatch")[0] > span0[0]
    assert ex.lane_declines["bsi_filtered_counts"]["mesh"] == 0
    for (q, pred), n in zip(items * 2, got):
        assert n == _count(cols, pred), q


def test_filtered_count_declines_a_planner_graft(lane):
    """Two reads of one subtree: the flight planner grafts the shared
    row under the Count, the lane does not sign it, and host algebra over
    the shared row answers both."""
    h, ex, cols = lane
    q = "Count(Intersect(Row(seg=1), Row(v < 37)))"
    want = _count(cols, _both(_seg(1), _has("v", lambda v: v < 37)))
    hits0 = ex.planner.cse_hits
    span0 = _span("bsiFilteredCountBatch")
    out = ex.execute_batch("i", [(q, None), (q, None)])
    assert ex.planner.cse_hits > hits0
    assert _span("bsiFilteredCountBatch") == span0
    assert out == [[want], [want]]


def test_filtered_count_null_equality_fails_alone(lane):
    """``== null`` stays unsigned and raises inside its own query; its
    flight-mates are answered by the lane."""
    h, ex, cols = lane
    items = _op_case("v < 37", lambda v: v < 37)
    queries = [(q, None) for q, _ in items]
    queries.insert(1, ("Count(Intersect(Row(seg=1), Row(v == null)))", None))
    span0 = _span("bsiFilteredCountBatch")
    out = ex.execute_batch("i", queries)
    bad = out.pop(1)
    assert isinstance(bad, Exception) and "null" in str(bad)
    span1 = _span("bsiFilteredCountBatch")
    assert (span1[0] - span0[0], span1[1] - span0[1]) == (1, 2)
    for (q, pred), res in zip(items, out):
        assert res == [_count(cols, pred)], q
