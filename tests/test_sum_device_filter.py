"""A filtered ``Sum``'s filter is built on the device (ISSUE 43): the BSI
lane signs the filter tree (``astbatch.match_sum_filter``: set rows, the
bitmap operators, ``Not``, a time-range leaf, and a pure BSI condition as a
leaf of its own), groups a flight's Sums by the tree's shape and launches a
group as ONE program over the summed field's raw stack and the filter
leaves' stacks; the launch is left in flight and pulled after the GroupBy
lane has drained.  Every answer is held to the per-call path's
(``Executor._execute_sum``) and to numpy's."""

import datetime as dt

import numpy as np
import pytest

import pilosa_tpu.pql as pql
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import astbatch
from pilosa_tpu.exec.executor import _UNSET, Executor
from pilosa_tpu.obs import devledger, tracing
from pilosa_tpu.ops import bsi, kernels
from pilosa_tpu.parallel import mesh

SHARDS = 3
T0 = dt.datetime(2017, 1, 1)

# set field -> rows; the names are the two mixes' (benchmark/traffic)
SETS = {
    "d_year": 7, "d_yearmonthnum": 12, "d_weeknuminyear": 10, "p_category": 5,
    "s_region": 5, "c_region": 5, "c_nation": 6, "s_nation": 6, "p_brand1": 9,
    "p_mfgr": 5, "passenger_count": 6, "pickup_year": 4, "cab_type": 3,
}
# int field -> (min, max) of the field's options and of what is stored
INTS = {
    "lo_discount": ((0, 10), (0, 10)),
    "lo_quantity": ((1, 50), (1, 50)),
    "lo_discount_amount": ((0, 1 << 24), (0, 1 << 20)),
    "lo_revenue": ((100, 1 << 23), (100, 1 << 20)),  # base 100
    "lo_supplycost": ((0, 1 << 17), (0, 1 << 16)),
    "total_amount": ((-5000, 60000), (-5000, 60000)),  # both signs
    "debt": ((-900, -100), (-900, -100)),  # base -100: every stored value <= 0
}


def _build(seed: int = 43):
    """(holder, index, columns by field): ``SHARDS`` shards, every column
    set in every field but ``t`` (a time field: a third of the columns, on
    one of 40 days) and ``debt`` (half of them)."""
    h = Holder()
    idx = h.create_index("i", track_existence=True)
    rng = np.random.default_rng(seed)
    n = SHARDS * h.n_words * 32
    cols = np.arange(n, dtype=np.uint64)
    data = {}
    for name, rows in SETS.items():
        data[name] = rng.integers(0, rows, n)
        idx.create_field(name).import_bits(data[name].astype(np.uint64), cols)
    for name, ((lo, hi), (vlo, vhi)) in INTS.items():
        f = idx.create_field(name, FieldOptions(field_type="int", min_=lo, max_=hi))
        data[name] = rng.integers(vlo, vhi + 1, n)
        has = np.ones(n, bool) if name != "debt" else rng.random(n) < 0.5
        data[name + "?"] = has
        f.import_values(cols[has], data[name][has])
    t = idx.create_field("t", FieldOptions(field_type="time", time_quantum="YMD"))
    timed = np.flatnonzero(rng.random(n) < 1 / 3)
    data["t_day"] = np.full(n, -1)
    data["t_day"][timed] = rng.integers(0, 40, len(timed))
    data["t"] = rng.integers(0, 3, n)
    t.import_bits(
        data["t"][timed].astype(np.uint64), cols[timed],
        timestamps=[T0 + dt.timedelta(days=int(d)) for d in data["t_day"][timed]],
    )
    idx.existence_field().import_bits(np.zeros(n, np.uint64), cols)
    return h, idx, data


def _day(d: int) -> str:
    return (T0 + dt.timedelta(days=d)).strftime("%Y-%m-%dT00:00")


# name -> (summed field, filter template, numpy mask of the same values, two
# draws of the template's values).  ``d`` is the columns by field.
def _between(d, f, lo, hi):
    return d[f + "?"] & (d[f] >= lo) & (d[f] <= hi)


CASES = {
    # benchmark/traffic/adhoc-c32.json: the three q1 and four group_sum variants
    "q1.year": (
        "lo_discount_amount",
        "Intersect(Row(d_year={0}), Row({1} <= lo_discount <= {2}), Row(lo_quantity < {3}))",
        lambda d, y, a, b, q: (d["d_year"] == y) & _between(d, "lo_discount", a, b)
        & (d["lo_quantity"] < q),
        [(3, 1, 3, 25), (5, 4, 6, 40)],
    ),
    "q1.month": (
        "lo_discount_amount",
        "Intersect(Row(d_yearmonthnum={0}), Row({1} <= lo_discount <= {2}), "
        "Row({3} <= lo_quantity <= {4}))",
        lambda d, m, a, b, k, l: (d["d_yearmonthnum"] == m) & _between(d, "lo_discount", a, b)
        & _between(d, "lo_quantity", k, l),
        [(4, 4, 6, 26, 35), (11, 1, 3, 5, 14)],
    ),
    "q1.week": (
        "lo_discount_amount",
        "Intersect(Row(d_weeknuminyear={0}), Row(d_year={1}), Row({2} <= lo_discount <= {3}), "
        "Row({4} <= lo_quantity <= {5}))",
        lambda d, w, y, a, b, k, l: (d["d_weeknuminyear"] == w) & (d["d_year"] == y)
        & _between(d, "lo_discount", a, b) & _between(d, "lo_quantity", k, l),
        [(6, 2, 5, 7, 26, 35), (1, 0, 2, 4, 1, 10)],
    ),
    "group_sum.q2": (
        "lo_revenue",
        "Intersect(Row(p_category={0}), Row(s_region={1}), Row(d_year={2}), Row(p_brand1={3}))",
        lambda d, c, sr, y, b: (d["p_category"] == c) & (d["s_region"] == sr)
        & (d["d_year"] == y) & (d["p_brand1"] == b),
        [(1, 2, 3, 4), (0, 0, 6, 8)],
    ),
    "group_sum.q3": (
        "lo_revenue",
        "Intersect(Row(c_region={0}), Row(s_region={1}), Row(c_nation={2}), Row(s_nation={3}), "
        "Row(d_year={4}))",
        lambda d, cr, sr, cn, sn, y: (d["c_region"] == cr) & (d["s_region"] == sr)
        & (d["c_nation"] == cn) & (d["s_nation"] == sn) & (d["d_year"] == y),
        [(1, 1, 2, 2, 3), (4, 0, 5, 1, 0)],
    ),
    "group_sum.q4": (
        "lo_revenue",
        "Intersect(Row(c_region={0}), Row(s_region={1}), Union(Row(p_mfgr={2}), Row(p_mfgr={3})), "
        "Row(d_year={4}), Row(c_nation={5}))",
        lambda d, cr, sr, fa, fb, y, cn: (d["c_region"] == cr) & (d["s_region"] == sr)
        & ((d["p_mfgr"] == fa) | (d["p_mfgr"] == fb)) & (d["d_year"] == y) & (d["c_nation"] == cn),
        [(1, 2, 0, 1, 3, 4), (3, 3, 2, 4, 5, 0)],
    ),
    "group_sum.q4.supplycost": (
        "lo_supplycost",
        "Intersect(Row(c_region={0}), Row(s_region={1}), Union(Row(p_mfgr={2}), Row(p_mfgr={3})), "
        "Row(d_year={4}), Row(c_nation={5}))",
        lambda d, cr, sr, fa, fb, y, cn: (d["c_region"] == cr) & (d["s_region"] == sr)
        & ((d["p_mfgr"] == fa) | (d["p_mfgr"] == fb)) & (d["d_year"] == y) & (d["c_nation"] == cn),
        [(0, 1, 1, 3, 2, 2), (2, 4, 0, 4, 6, 5)],
    ),
    # benchmark/traffic/dashboard-c32.json: sum_filtered
    "dashboard.passenger_count": (
        "total_amount", "Row(passenger_count={0})",
        lambda d, p: d["passenger_count"] == p, [(1,), (5,)],
    ),
    "dashboard.pickup_year": (
        "total_amount", "Row(pickup_year={0})", lambda d, y: d["pickup_year"] == y, [(0,), (3,)],
    ),
    "dashboard.cab_type": (
        "total_amount", "Row(cab_type={0})", lambda d, c: d["cab_type"] == c, [(2,), (1,)],
    ),
    # the rest of what the tree compiler signs
    "not": (
        "total_amount", "Intersect(Not(Row(cab_type={0})), Row(pickup_year={1}))",
        lambda d, c, y: (d["cab_type"] != c) & (d["pickup_year"] == y), [(0, 1), (2, 2)],
    ),
    "difference": (
        "lo_revenue", "Difference(Row(d_year={0}), Row(s_region={1}), Row(lo_quantity > {2}))",
        lambda d, y, sr, q: (d["d_year"] == y) & ~(d["s_region"] == sr) & ~(d["lo_quantity"] > q),
        [(1, 1, 30), (6, 4, 10)],
    ),
    "xor": (
        "lo_revenue", "Xor(Row(d_year={0}), Row(s_region={1}))",
        lambda d, y, sr: (d["d_year"] == y) ^ (d["s_region"] == sr), [(2, 2), (0, 3)],
    ),
    "time_range": (
        "total_amount", "Intersect(Row(t={0}, from={1}, to={2}), Row(cab_type={3}))",
        lambda d, r, a, b, c: (d["t"] == r) & (d["t_day"] >= (dt.datetime.fromisoformat(a) - T0).days)
        & (d["t_day"] >= 0) & (d["t_day"] < (dt.datetime.fromisoformat(b) - T0).days)
        & (d["cab_type"] == c),
        [(1, _day(3), _day(9), 0), (2, _day(3), _day(9), 2)],  # one cover: one shape
    ),
    "absent_row": (
        "lo_revenue", "Union(Row(d_year={0}), Row(p_brand1={1}))",
        lambda d, y, b: (d["d_year"] == y) | (d["p_brand1"] == b), [(99, 2), (1, 777)],
    ),
    "empty_intersection": (
        "lo_revenue", "Intersect(Row(d_year={0}), Row(d_year={1}))",
        lambda d, a, b: (d["d_year"] == a) & (d["d_year"] == b), [(1, 2), (3, 88)],
    ),
    "negative_base": (
        "debt", "Intersect(Row(cab_type={0}), Row(debt < {1}))",
        lambda d, c, v: (d["cab_type"] == c) & d["debt?"] & (d["debt"] < v),
        [(1, -400), (0, -150)],
    ),
    "negative_values": (
        "total_amount", "Intersect(Row({0} <= total_amount <= {1}), Row(pickup_year={2}))",
        lambda d, a, b, y: _between(d, "total_amount", a, b) & (d["pickup_year"] == y),
        [(-4000, 12, 0), (-1, 30000, 2)],
    ),
    "band_from_zero": (
        "lo_discount_amount", "Intersect(Row(d_year={0}), Row({1} <= lo_discount <= {2}))",
        lambda d, y, a, b: (d["d_year"] == y) & _between(d, "lo_discount", a, b),
        [(1, 0, 2), (4, 3, 5)],
    ),
    "bound_out_of_range": (
        "lo_discount_amount", "Intersect(Row(d_year={0}), Row({1} <= lo_quantity <= {2}))",
        lambda d, y, a, b: (d["d_year"] == y) & _between(d, "lo_quantity", a, b),
        [(1, 45, 5000), (4, -70000, 20)],
    ),
    "range_alone": (
        "total_amount", "Row(total_amount > {0})", lambda d, v: d["total_amount"] > v,
        [(0,), (-2500,)],
    ),
}


def _pql(name: str, draw: int) -> str:
    field, template, _, draws = CASES[name]
    return f"Sum({template.format(*draws[draw])}, field={field})"


def _numpy(data, name: str, draw: int):
    field, _, mask, draws = CASES[name]
    m = mask(data, *draws[draw]) & data[field + "?"]
    return (int(data[field][m].sum()), int(m.sum())) if m.any() else (0, 0)


def _per_call(plain: Executor, idx, q: str):
    call = pql.parse(q).calls[0]
    plain._translate_call(idx, call)
    got = plain._execute_sum(idx, call, None)
    return got.value, got.count


def _flight(ex: Executor, qs):
    """The answers of one flight, a request a query; an error is raised."""
    out = ex.execute_batch("i", [(q, None) for q in qs])
    for r in out:
        if isinstance(r, Exception):
            raise r
    return [(r[0].value, r[0].count) for r in out]


def _warm(ex: Executor):
    """Every case twice in a request: the cold stacks are built (two calls
    of a flight read them) and every shape's program is compiled."""
    for name in CASES:
        ex.execute("i", _pql(name, 0) + " " + _pql(name, 1))


@pytest.fixture(scope="module")
def world():
    h, idx, data = _build()
    ex = Executor(h, rescache_entries=0)
    _warm(ex)
    return h, idx, data, ex, Executor(h, rescache_entries=0)


def _lane(ex: Executor) -> dict:
    return dict(ex.sum_lane, declines=sum(ex.lane_declines["bsi_sums"].values()))


# ------------------------------------------------------------- (a) answers


@pytest.mark.parametrize("name", sorted(CASES))
def test_lane_answer_is_the_per_call_paths_and_numpys(world, name):
    """Both draws in one flight: one launch, both filters built on the
    device, each answer the per-call path's and numpy's."""
    h, idx, data, ex, plain = world
    qs = [_pql(name, 0), _pql(name, 1)]
    before = _lane(ex)
    got = _flight(ex, qs)
    for draw, (q, g) in enumerate(zip(qs, got)):
        assert g == _per_call(plain, idx, q) == _numpy(data, name, draw), q
    after = _lane(ex)
    assert after["device_filters"] - before["device_filters"] == 2
    assert after["launches"] - before["launches"] == 1
    assert after["host_filters"] == before["host_filters"]
    assert after["declines"] == before["declines"]


def test_some_case_reads_every_sign_and_an_empty_set(world):
    """The cases are not vacuous: sums of both signs, an empty filter."""
    _, _, data, _, _ = world
    sums = [_numpy(data, n, k) for n in CASES for k in (0, 1)]
    assert any(s < 0 for s, _ in sums) and any(s > 0 for s, _ in sums)
    assert (0, 0) in sums


# ------------------------------------------- (b) one launch, nothing uploaded


@pytest.fixture()
def spied(world, monkeypatch):
    """(executor, what crossed the host's edge while the test ran): bytes
    of every ``kernels.h2d``, the kernel of every ``kernels.pull``."""
    h, idx, data, ex, plain = world
    seen = {"h2d": [], "pull": []}
    inner_h2d, inner_pull = kernels.h2d, kernels.pull

    def h2d(host, sharding=None, dtype=None):
        if not hasattr(host, "devices"):
            seen["h2d"].append(np.asarray(host).nbytes)
        return inner_h2d(host, sharding, dtype)

    def pull(out, kernel=""):
        seen["pull"].append(kernel)
        return inner_pull(out, kernel)

    monkeypatch.setattr(kernels, "h2d", h2d)
    monkeypatch.setattr(kernels, "pull", pull)
    return ex, seen


def _span_count(name: str) -> int:
    block, leaf = name.split(".")
    return tracing.spans_snapshot()[block].get(leaf, {"count": 0})["count"]


@pytest.mark.parametrize("n,launches", [(1, 1), (4, 1), (5, 2), (11, 3)])
def test_a_flight_of_one_shape_is_one_launch_a_chunk_and_uploads_no_filter(
    world, spied, n, launches
):
    """N same-shape Sums: ceil(N / SUM_CHUNK) launches; only slots and
    bounds go up (never an ``[S, P, W]`` tensor, nor one row of it), one
    accumulator a launch comes back, no row segment is pulled and no
    ``executor.bsiSplit`` span opens.  A lone one rides as well."""
    h, idx, data, _, plain = world
    ex, seen = spied
    rng = np.random.default_rng(n)
    qs, want = [], []
    for _ in range(n):
        y, a, q = int(rng.integers(7)), int(rng.integers(0, 8)), int(rng.integers(2, 50))
        qs.append(
            f"Sum(Intersect(Row(d_year={y}), Row({a} <= lo_discount <= {a + 2}), "
            f"Row(lo_quantity < {q})), field=lo_discount_amount)"
        )
        m = (data["d_year"] == y) & _between(data, "lo_discount", a, a + 2) & (data["lo_quantity"] < q)
        want.append((int(data["lo_discount_amount"][m].sum()), int(m.sum())))
    before, splits = _lane(ex), _span_count("executor.bsiSplit")
    assert _flight(ex, qs) == want
    after = _lane(ex)
    assert after["launches"] - before["launches"] == launches
    assert after["device_filters"] - before["device_filters"] == n
    assert after["host_filters"] == before["host_filters"]
    one_row = SHARDS * h.n_words * 4  # a filter's words over the shards
    assert seen["h2d"] and max(seen["h2d"]) < one_row
    assert seen["pull"] == ["bsi_sum_filtered"] * launches
    assert _span_count("executor.bsiSplit") == splits


# ------------------------------------------------- (c) no drawn value compiles


def test_other_drawn_values_compile_nothing(world):
    """A second flight of every shape with other values, a band from 0 and
    bounds out of range among them, runs the programs the first compiled."""
    h, idx, data, ex, plain = world
    qs = [_pql(name, draw) for name in sorted(CASES) for draw in (1, 0)] + [
        # one bound where there were two; bounds past either end
        "Sum(Intersect(Row(d_year=2), Row(lo_discount >= 0), Row(lo_quantity < 9999)), "
        "field=lo_discount_amount)",
        "Sum(Intersect(Row(d_year=2), Row(lo_discount == 7), Row(lo_quantity != 3)), "
        "field=lo_discount_amount)",
    ]
    compiles = devledger.snapshot()["totals"]["compiles"]
    got = _flight(ex, qs)
    assert devledger.snapshot()["totals"]["compiles"] == compiles
    assert got == [_per_call(plain, idx, q) for q in qs]
    m = (data["d_year"] == 2) & (data["lo_discount"] == 7) & (data["lo_quantity"] != 3)
    assert got[-1] == (int(data["lo_discount_amount"][m].sum()), int(m.sum()))


def test_both_orders_of_a_commutative_node_sign_into_one_program(world):
    h, idx, *_ = world
    a = "Intersect(Row(d_year=1), Row(3 <= lo_discount <= 5), Union(Row(p_mfgr=1), Row(s_region=2)))"
    b = "Intersect(Union(Row(s_region=0), Row(p_mfgr=4)), Row(lo_discount < 2), Row(d_year=6))"
    sa, sb = (astbatch.match_sum_filter(idx, pql.parse(q).calls[0]) for q in (a, b))
    assert sa[0] == sb[0] and sa[1] == sb[1]
    assert [f.name for f, _ in sa[3]] == ["lo_discount"]
    # a Count's tree signs as it always did: children as they stand, no range leaf
    leaves, pairs = [], []
    sig = astbatch.match_tree(idx, pql.parse("Union(Row(s_region=0), Row(p_mfgr=4))").calls[0], leaves, pairs)
    assert sig == ("union", ("row", 0), ("row", 1)) and pairs[0][0] == "s_region"
    assert astbatch.match_tree(idx, pql.parse(a).calls[0], [], []) is None
    assert astbatch.match_count(idx, pql.parse(f"Count({a})").calls[0], [], []) is None


# -------------------------------------------------------------- (d) the mesh


@pytest.mark.parametrize("devices", [1, 4])
def test_sharded_stacks_give_the_same_answers_one_accumulator_a_device(monkeypatch, devices):
    """Four forced host devices (the suite's other tests run over its
    eight): the same program runs in the stacks' own layout, each device
    on its own shards, and the accumulators come back one a device.  On
    one device the program is the plain one and returns one."""
    h, idx, data = _build(seed=44)
    names = sorted(CASES)
    qs = [_pql(n, k) for n in names for k in (0, 1)]
    shapes = []
    inner = bsi.sum_pairs

    def sum_pairs(acc, **kw):
        shapes.append((tuple(acc.shape), len(acc.sharding.device_set)))
        return inner(acc, **kw)

    try:
        mesh.configure_serving(devices)
        ex = Executor(h, rescache_entries=0)
        _warm(ex)
        monkeypatch.setattr(bsi, "sum_pairs", sum_pairs)
        before = _lane(ex)
        launches0 = devledger.snapshot()["totals"]
        got = _flight(ex, qs)
        totals = devledger.snapshot()["totals"]
    finally:
        mesh.configure_serving(None)
    assert got == [_numpy(data, n, k) for n in names for k in (0, 1)]
    after = _lane(ex)
    assert after["device_filters"] - before["device_filters"] == len(qs)
    assert after["host_filters"] == before["host_filters"]
    assert after["declines"] == before["declines"]
    assert len(shapes) == len(names)
    for shape, on in shapes:
        assert on == devices
        assert shape[-1] == 2 * astbatch.SUM_CHUNK
        assert shape[:-2] == ((devices,) if devices > 1 else ())
    # whatever the flight launched ran over the mesh, where there is one
    assert totals["launches"] - launches0["launches"] == len(names)
    assert totals["meshLaunches"] - launches0["meshLaunches"] == (len(names) if devices > 1 else 0)


# ------------------------------------------ (e) the pull behind the GroupBy lane


GROUPBYS = [
    "GroupBy(Rows(d_year), Rows(s_region), Rows(p_mfgr), filter=Row(c_region=1))",
    "GroupBy(Rows(d_year), Rows(c_nation), Rows(p_mfgr), filter=Row(s_region=2))",
]


def test_the_sums_pull_comes_after_the_groupby_lanes_last(world, spied):
    h, idx, data, _, plain = world
    ex, seen = spied
    sums = [_pql("q1.year", 0), _pql("group_sum.q4", 1), _pql("q1.year", 1)]
    ex.execute_batch("i", [(q, None) for q in GROUPBYS])  # the lane's programs
    seen["pull"].clear()
    pulls0 = ex.groupby_lane["pulls"]
    out = ex.execute_batch("i", [(q, None) for q in [sums[0], GROUPBYS[0], sums[1], GROUPBYS[1], sums[2]]])
    assert ex.groupby_lane["pulls"] - pulls0 >= 2
    assert [(out[k][0].value, out[k][0].count) for k in (0, 2, 4)] == [
        _per_call(plain, idx, q) for q in sums
    ]
    lane = [k for k, kernel in enumerate(seen["pull"]) if kernel.startswith("combo_")]
    late = [k for k, kernel in enumerate(seen["pull"]) if kernel == "bsi_sum_filtered"]
    assert len(late) == 2 and lane and min(late) > max(lane)  # two shapes, two launches


def test_a_flight_without_a_groupby_pulls_at_the_same_place(world, monkeypatch):
    """One path: the BSI lane returns with nothing answered, and the
    scope around it pulls under a second ``executor.batchBSI``."""
    h, idx, data, ex, plain = world
    inner = ex._batch_bsi
    unanswered = []

    def batch_bsi(idx_, calls, shards, results, late):
        inner(idx_, calls, shards, results, late)
        unanswered.append((len(late), sum(r is _UNSET for r in results)))

    monkeypatch.setattr(ex, "_batch_bsi", batch_bsi)
    lanes, pulls = _span_count("executor.batchBSI"), _span_count("executor.bsiSumPull")
    qs = [_pql("dashboard.cab_type", 0), _pql("dashboard.cab_type", 1)]
    assert _flight(ex, qs) == [_per_call(plain, idx, q) for q in qs]
    assert unanswered == [(1, 2)]  # one launch in flight, both slots still unset
    assert _span_count("executor.batchBSI") == lanes + 2
    assert _span_count("executor.bsiSumPull") == pulls + 1


def test_a_raising_item_leaves_only_its_own_slot_to_the_per_call_path(world, monkeypatch):
    h, idx, data, ex, plain = world
    inner = Executor._bsi_stored_bounds

    def bounds(field, cond):
        if cond.op == "<" and cond.value == 13:
            raise ValueError("unlucky")
        return inner(field, cond)

    monkeypatch.setattr(Executor, "_bsi_stored_bounds", staticmethod(bounds))
    qs = [
        f"Sum(Intersect(Row(d_year={y}), Row(1 <= lo_discount <= 3), Row(lo_quantity < {q})), "
        "field=lo_discount_amount)" for y, q in [(1, 30), (2, 13), (3, 31)]
    ]
    before, errors = _lane(ex), ex.lane_declines["bsi_sums"]["error"]
    percall = _span_count("executor.executeSum")
    assert _flight(ex, qs) == [_per_call(plain, idx, q) for q in qs]
    after = _lane(ex)
    assert after["device_filters"] - before["device_filters"] == 2
    assert ex.lane_declines["bsi_sums"]["error"] == errors + 1
    assert _span_count("executor.executeSum") == percall + 1


def test_a_launch_whose_pull_fails_is_answered_per_call(world, monkeypatch):
    h, idx, data, ex, plain = world
    inner = bsi.sum_pairs

    def sum_pairs(acc, *, depth, n):
        if depth == idx.field("lo_supplycost").bit_depth:
            raise RuntimeError("the device said no")
        return inner(acc, depth=depth, n=n)

    monkeypatch.setattr(bsi, "sum_pairs", sum_pairs)
    qs = [_pql("group_sum.q4.supplycost", 0), _pql("group_sum.q4", 0), _pql("group_sum.q4.supplycost", 1)]
    errors, percall = ex.lane_declines["bsi_sums"]["error"], _span_count("executor.executeSum")
    assert _flight(ex, qs) == [_per_call(plain, idx, q) for q in qs]
    assert ex.lane_declines["bsi_sums"]["error"] == errors + 2
    assert _span_count("executor.executeSum") == percall + 2


# ------------------------------------------------------- what the lane declines


def test_an_unsigned_tree_is_answered_per_call(world, spied):
    """``Shift`` is outside the compiled set: such Sums are counted as
    declined by shape and as host filters, launch nothing in the lane and
    are each answered by the per-call path."""
    h, idx, data, _, plain = world
    ex, seen = spied
    qs = [f"Sum(Shift(Row(cab_type={c}), n=1), field=total_amount)" for c in (0, 1)]
    before, shape = _lane(ex), ex.lane_declines["bsi_sums"]["shape"]
    percall = _span_count("executor.executeSum")
    assert _flight(ex, qs) == [_per_call(plain, idx, q) for q in qs]
    after = _lane(ex)
    assert after["host_filters"] - before["host_filters"] == 2
    assert after["device_filters"] == before["device_filters"]
    assert after["launches"] == before["launches"]
    assert ex.lane_declines["bsi_sums"]["shape"] == shape + 2
    assert _span_count("executor.executeSum") == percall + 2
    assert "bsi_sum_filtered" not in seen["pull"]


def test_a_cold_filter_stack_is_built_for_two_and_declined_for_one():
    h, idx, data = _build(seed=45)
    ex = Executor(h, rescache_entries=0)
    ex.execute("i", "Sum(field=total_amount) Sum(field=total_amount)")  # the summed stack
    q = "Sum(Row(cab_type={0}), field=total_amount)"
    got = _flight(ex, [q.format(0)])
    assert _lane(ex) == {"device_filters": 0, "host_filters": 1, "launches": 0, "declines": 1}
    assert ex.lane_declines["bsi_sums"]["budget"] == 1
    both = _flight(ex, [q.format(1), q.format(2)])
    assert ex.sum_lane == {"device_filters": 2, "host_filters": 1, "launches": 1}
    assert _flight(ex, [q.format(0)]) == got  # live now: a lone one rides
    assert ex.sum_lane == {"device_filters": 3, "host_filters": 1, "launches": 2}
    m = [data["cab_type"] == c for c in range(3)]
    assert got + both == [(int(data["total_amount"][k].sum()), int(k.sum())) for k in m]


# --------------------------------------------------- (f) an import is visible


def test_an_import_between_two_flights_is_visible_to_the_second():
    """The filter rows come from the field stacks and the values from the
    BSI stack, both refreshed to every import before the launch."""
    h, idx, data = _build(seed=46)
    ex = Executor(h, rescache_entries=0)
    qs = [
        "Sum(Intersect(Row(cab_type=1), Row(total_amount > 50000)), field=total_amount)",
        "Sum(Intersect(Row(cab_type=2), Row(total_amount > 50000)), field=total_amount)",
    ]
    first = _flight(ex, qs)
    cab, amount = data["cab_type"].copy(), data["total_amount"].copy()

    def want(c):
        m = (cab == c) & (amount > 50000)
        return int(amount[m].sum()), int(m.sum())

    assert first == [want(1), want(2)]
    moved = np.flatnonzero(cab == 0)[:500]  # these rides were cab 0 and cheap
    idx.field("cab_type").import_bits(
        np.zeros(len(moved), np.uint64), moved.astype(np.uint64), clear=True
    )
    idx.field("cab_type").import_bits(np.ones(len(moved), np.uint64), moved.astype(np.uint64))
    idx.field("total_amount").import_values(moved.astype(np.uint64), np.full(len(moved), 55555))
    cab[moved], amount[moved] = 1, 55555
    launches = ex.sum_lane["launches"]
    second = _flight(ex, qs)
    assert second == [want(1), want(2)] and second[0] != first[0] and second[1] == first[1]
    assert ex.sum_lane["launches"] == launches + 1 and ex.sum_lane["host_filters"] == 0


# -------------------------------------------------------------- the counters


def test_the_counters_are_served_from_the_start(tmp_path):
    """``/debug/vars`` ``serving_cache`` has the three before any Sum ran
    (readers take deltas), and they move with the lane."""
    import json
    import urllib.request

    from pilosa_tpu.server.node import NodeServer

    node = NodeServer(data_dir=str(tmp_path), host="127.0.0.1", port=0, rescache_entries=0)
    node.start()
    try:
        def served():
            with urllib.request.urlopen(node.uri + "/debug/vars", timeout=10) as r:
                cache = json.loads(r.read())["serving_cache"]
            return {k: v for k, v in cache.items() if k.startswith("sum_lane_")}

        assert served() == {
            "sum_lane_device_filters": 0, "sum_lane_host_filters": 0, "sum_lane_launches": 0,
        }
        for path, body in [
            ("/index/i", b""), ("/index/i/field/f", b""),
            ("/index/i/field/v", b'{"options": {"type": "int", "min": 0, "max": 100}}'),
            ("/index/i/query", b"Set(1, f=1) Set(1, v=7) Set(2, f=2) Set(2, v=9)"),
            ("/index/i/query", b"Sum(Row(f=1), field=v) Sum(Row(f=2), field=v)"),
        ]:
            req = urllib.request.Request(node.uri + path, data=body, method="POST")
            out = urllib.request.urlopen(req, timeout=10).read()
        assert [r["value"] for r in json.loads(out)["results"]] == [7, 9]
        assert served() == {
            "sum_lane_device_filters": 2, "sum_lane_host_filters": 0, "sum_lane_launches": 1,
        }
    finally:
        node.stop()
