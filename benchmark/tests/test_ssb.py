"""What the harness learnt for a star schema (PR 39): set fields that follow
from another, fields that are generated and not stored, uniform, product and
scaled measures, the picks ``int_band``, ``row_run`` and ``row_under``; the
record ``ssb-flat`` and the mixes ``adhoc-c32`` and ``lone-c1``; since PR 44 the
mix ``groupsum-c32`` (one request a query, ``GroupBy(..., aggregate=Sum(field=))``;
``test_groupsum.py`` holds the judge's side of the form).  And that the
cells which were there send what they sent: ``gen_slab`` of the three ``taxi``
configurations and the request streams of ``dashboard-c32`` and
``ingest-serve-c32`` are bit for bit those of PR 38's code
(``golden_pr38.json``).

Where a cell of this PR is not in ``BENCHMARK.json`` its entries wait in
``staged_cells.json`` (PERF.md section 7 says why) and are laid over the
manifest here, in memory, so that the CPU rehearses it as it rehearses the
grid's (``test_rehearsal.py``, ``test_controls.py``, ``test_generator.py``).  An
entry the grid already has is skipped: the PR that moves a staged cell into
``BENCHMARK.json`` edits no file here.
``taxi.lone-c1`` never puts an operation on the device in a window (every
lone pair count is answered on the host's copy of the rows), so a traced run
of it has no device time to report: held here as the fact it is."""

import hashlib
import json
import os
import re

import numpy as np
import pytest

import compare
import datagen
import generator
import loadgen
import manifest as mf
import reference
import run

from test_rehearsal import MANIFEST as GRID, rehearse, rehearsed_line

HERE = os.path.dirname(os.path.abspath(__file__))
CELL, LONE, GROUPSUM = "ssb-flat.adhoc-c32", "taxi.lone-c1", "ssb-flat.groupsum-c32"
ENTRIES = ("configs", "workloads", "per_layer")


def laid_over(grid: dict, staged: dict) -> dict:
    """``grid`` with the staged entries it does not hold beside its own; a metric that is there gains the staged cells."""
    have = {k: {e["name"] for e in grid[k]} for k in ENTRIES}
    out = dict(grid, **{k: grid[k] + [e for e in staged[k] if e["name"] not in have[k]] for k in ENTRIES})
    out["per_layer"] = [dict(m, workloads=m["workloads"] + [c for c in staged["per_layer_workloads"][m["name"]]
                                                            if c not in m["workloads"]])
                        if m["name"] in staged["per_layer_workloads"] else m for m in out["per_layer"]]
    return out


STAGED_FILE = os.path.join(HERE, "staged_cells.json")
if os.path.exists(STAGED_FILE):
    with open(STAGED_FILE) as _f:
        STAGED = json.load(_f)
    MANIFEST = laid_over(GRID, STAGED)
else:
    STAGED, MANIFEST = None, GRID
SSB = mf.read_json("benchmark/configs/ssb-flat.json")
ADHOC = mf.read_json("benchmark/traffic/adhoc-c32.json")
SUMS = mf.read_json("benchmark/traffic/groupsum-c32.json")
MIXES = {"adhoc-c32": ADHOC, "groupsum-c32": SUMS}
AGGREGATE = re.compile(r", aggregate=Sum\(field=(lo_revenue|lo_supplycost)\)\)$")
FIELDS = datagen.fields_by_name(SSB)
# follower -> (parent, how many parent ids a follower id covers, or None where the map is listed)
HIERARCHY = {
    "c_nation": ("c_city", 10), "c_region": ("c_nation", 5),
    "s_nation": ("s_city", 10), "s_region": ("s_nation", 5),
    "p_category": ("p_brand1", 40), "p_mfgr": ("p_category", 5),
    "d_year": ("orderday", None), "d_yearmonthnum": ("orderday", None), "d_weeknuminyear": ("orderday", None),
}
UNSTORED = {"orderday", "p_price"}


def rehearsal_cfg():
    return run.load_cell(MANIFEST, CELL, rehearsal=True)[1]


# ---------------------------------------------------------------------------
# datagen: followers, fields that are not stored, the three measures
# ---------------------------------------------------------------------------


def test_the_files_hierarchies_are_the_sources():
    assert {f["name"] for f in SSB["fields"] if "follows" in f} == set(HIERARCHY)
    for name, (parent, div) in HIERARCHY.items():
        how = FIELDS[name]["follows"]
        assert how["field"] == parent and (how.get("div") == div if div else len(how["map"]) == 2406)
    # the order's day: 1992-01-01 .. 1998-08-02; 1998 stops after 214 days, in its 8th month and 31st week
    year, month, week = (np.asarray(FIELDS[n]["follows"]["map"]) for n in ("d_year", "d_yearmonthnum", "d_weeknuminyear"))
    assert np.bincount(year).tolist() == [366, 365, 365, 365, 366, 365, 214]
    assert np.array_equal(month // 12, year) and month.max() == 79 and len(np.unique(month)) == 80
    assert np.bincount(month)[:3].tolist() == [31, 29, 31] and week.max() == 52 and week[365] == 52 and week[366] == 0
    assert {f["name"] for f in SSB["fields"] if not f.get("stored", True)} == UNSTORED


@pytest.mark.parametrize("seed,shard,slab", [(1, 0, 0), (2**31 + 9, 11, 15), (3900000001, 7, 3)])
def test_every_hierarchy_holds_in_every_generated_column(seed, shard, slab):
    s = datagen.gen_slab(SSB, seed, shard, slab)
    assert set(s) == set(FIELDS) and all(len(v) == SSB["slab_rides"] for v in s.values())
    for name, (parent, div) in HIERARCHY.items():
        want = s[parent] // div if div else np.asarray(FIELDS[name]["follows"]["map"])[s[parent]]
        assert np.array_equal(s[name], want), name
        assert s[name].dtype == np.uint16 and s[name].max() < FIELDS[name]["rows"]
    # two levels up: a city's region, a brand's manufacturer
    assert np.array_equal(s["c_region"], s["c_city"] // 50) and np.array_equal(s["p_mfgr"], s["p_brand1"] // 200)
    assert np.array_equal(datagen.ancestor_map(FIELDS, "s_city", "s_region"), np.arange(250) // 50)
    assert np.array_equal(datagen.ancestor_map(FIELDS, "orderday", "d_year"), FIELDS["d_year"]["follows"]["map"])
    with pytest.raises(ValueError, match="does not follow"):
        datagen.ancestor_map(FIELDS, "c_city", "s_region")


def test_a_follower_has_the_shares_its_parent_implies():
    for name, (parent, _) in HIERARCHY.items():
        w, pw = datagen.row_weights(FIELDS[name], FIELDS), datagen.row_weights(FIELDS[parent], FIELDS)
        assert abs(w.sum() - 1) < 1e-12 and len(w) == FIELDS[name]["rows"]
        up = datagen.row_map(FIELDS[name], FIELDS)
        for child in (0, int(up.max())):
            assert abs(w[child] - pw[up == child].sum()) < 1e-15
        order = datagen.popularity_order(FIELDS[name], FIELDS)
        assert len(order) == int(FIELDS[name].get("present", FIELDS[name]["rows"])) and (np.diff(w[order]) <= 0).all()
    w = datagen.row_weights(FIELDS["d_year"], FIELDS)
    assert abs(w[6] - 214 / 2406) < 1e-12 and abs(w[0] - 366 / 2406) < 1e-12
    assert np.count_nonzero(datagen.row_weights(FIELDS["d_yearmonthnum"], FIELDS)) == 80
    # over a shard the drawn shares are the implied ones
    col = np.concatenate([datagen.gen_slab(SSB, 5, 0, k)["c_region"] for k in range(4)])
    assert np.abs(np.bincount(col, minlength=5) / len(col) - 0.2).max() < 0.005
    # a follower of a field nobody makes, a map of the wrong length: refused, not guessed
    bad = dict(SSB, fields=[dict(FIELDS["c_nation"], follows={"field": "nowhere", "div": 10})])
    with pytest.raises((ValueError, KeyError)):
        datagen.gen_slab(bad, 1, 0, 0)
    with pytest.raises(ValueError, match="a map of 3 ids"):
        datagen.row_map(dict(FIELDS["d_year"], follows={"field": "orderday", "map": [0, 1, 2]}), FIELDS)


def test_the_measures_are_uniform_products_and_scaled():
    s = {k: v.astype(np.int64) for k, v in datagen.gen_slab(SSB, 2**31 + 11, 3, 2).items()}
    for name, (lo, hi) in {"lo_quantity": (1, 50), "lo_discount": (0, 10), "lo_tax": (0, 8),
                           "p_price": (90000, 209899)}.items():
        assert lo <= s[name].min() <= lo + (hi - lo) // 1000 and hi - (hi - lo) // 1000 <= s[name].max() <= hi, name
        assert FIELDS[name]["uniform"] == [lo, hi] and FIELDS[name]["min"] == 0
    assert np.array_equal(s["lo_extendedprice"], s["lo_quantity"] * s["p_price"])
    assert np.array_equal(s["lo_revenue"], s["lo_extendedprice"] * (100 - s["lo_discount"]) // 100)
    assert np.array_equal(s["lo_supplycost"], s["p_price"] * 60 // 100)
    assert np.array_equal(s["lo_discount_amount"], s["lo_extendedprice"] * s["lo_discount"])
    for f in SSB["fields"]:
        if f["kind"] == "int":
            assert 0 <= s[f["name"]].min() and s[f["name"]].max() <= f["max"], f["name"]
    depth = {f["name"]: datagen.bit_depth(f) for f in SSB["fields"] if f["kind"] == "int"}
    assert (depth["lo_extendedprice"], depth["lo_revenue"], depth["lo_supplycost"], depth["lo_discount_amount"],
            depth["lo_quantity"], depth["lo_discount"]) == (24, 24, 17, 27, 6, 4) and "lo_ordtotalprice" not in depth
    assert FIELDS["lo_extendedprice"]["max"] == 50 * 209899 and FIELDS["lo_supplycost"]["max"] == 209899 * 60 // 100


class FakeHttp:
    def __init__(self):
        self.posts = []

    def json(self, method, path, obj=None):
        self.posts.append(path)


def test_fields_that_are_not_stored_reach_neither_the_schema_the_load_nor_the_reference():
    cfg = rehearsal_cfg()
    stored = [f["name"] for f in datagen.stored_fields(cfg)]
    assert len(stored) == len(cfg["fields"]) - 2 and not UNSTORED & set(stored)
    c = FakeHttp()
    run.make_schema(c, cfg)
    assert c.posts == ["/index/ssb"] + [f"/index/ssb/field/{n}" for n in stored]
    assert [r[0] for r in loadgen.slab_requests(cfg, 3, 0, 1)] == stored
    for kind in (reference.Reference, compare.LossyReference):
        ref = kind(cfg, 3)
        ref.load()
        assert list(ref.one) == stored and set(ref.fields) == set(stored)
        assert all((v != (-1 if v.dtype == np.int32 else datagen.UNSET)).any() for v in ref.one.values())
    with pytest.raises(KeyError):
        ref.evaluate(reference.parse("Sum(field=p_price)"))
    # the grid's other records have no such field
    for name in ("taxi", "taxi-x4", "taxi-ingest"):
        cfg = mf.read_json(f"benchmark/configs/{name}.json")
        assert datagen.stored_fields(cfg) == cfg["fields"]


# ---------------------------------------------------------------------------
# generator: the three picks
# ---------------------------------------------------------------------------


def slots_of(m, cls, seeds, uniform):
    for seed in seeds:
        yield m._slots(np.random.default_rng(seed), m.classes[cls]["slots"], uniform)


@pytest.mark.parametrize("uniform", [True, False])
def test_the_picks_give_what_they_say_under_1000_seeds(uniform):
    m = generator.Mix(SSB, ADHOC)
    seen = {"d": set(), "k": set(), "b": set(), "yy": set(), "same": 0}
    for s in slots_of(m, "q1", range(1000), uniform):
        d, k = s["d"], s["k"]
        assert 0 <= d["lo"] <= d["hi"] <= 10 and d["hi"] - d["lo"] in (1, 2)  # within 1 of a value, clipped at 0 and 10
        assert 0 <= k["lo"] <= k["hi"] <= 50 and k["hi"] - k["lo"] == min(9, 50 - k["lo"])  # q .. q + 9
        seen["d"].add((d["lo"], d["hi"]))
        seen["k"].add(k["lo"])
    assert {(0, 1), (9, 10), (4, 6)} <= seen["d"] and {0, 50} <= seen["k"]
    for s in slots_of(m, "q2", range(1000), uniform):
        assert s["b"] == list(range(s["b"][0], s["b"][0] + 8)) and 0 <= s["b"][0] <= 992
        seen["b"].add(s["b"][0])
    assert len(seen["b"]) > 400
    for s in slots_of(m, "q4", range(1000), uniform):
        assert s["yy"][1] == s["yy"][0] + 1 and 0 <= s["yy"][0] <= 5 and s["f"][1] == s["f"][0] + 1 <= 4
        seen["yy"].add(s["yy"][0])
    assert seen["yy"] == set(range(6))
    for s in slots_of(m, "q3", range(1000), uniform):  # two cities of one nation, each drawn alone
        assert {s["ca"] // 10, s["cb"] // 10} == {s["cn"]} and {s["sa"] // 10, s["sb"] // 10} == {s["sn"]}
        seen["same"] += s["ca"] == s["cb"]
    assert 50 < seen["same"] < 160  # one time in ten
    for s in slots_of(m, "group_sum", range(1000), uniform):
        assert s["b"] // 40 == s["c"] and s["cn"] // 5 == s["cr"] and s["sn"] // 5 == s["sr"]


def test_a_run_names_only_rows_that_occur_and_a_pick_under_no_row_is_refused():
    cfg = dict(SSB, fields=[dict(f, follows={"field": "orderday", "map": [m if m < 40 else m + 3 for m in f["follows"]["map"]]})
                            if f["name"] == "d_yearmonthnum" else f for f in SSB["fields"]])
    m = generator.Mix(cfg, ADHOC)  # months 40-42 never occur
    starts = set(m._run_starts("d_yearmonthnum", 4).tolist())
    assert starts == (set(range(37)) | set(range(43, 80))) and len(m._run_starts("d_year", 7)) == 1
    with pytest.raises(ValueError, match="no 8 consecutive rows"):
        m._run_starts("d_year", 8)
    with pytest.raises(ValueError, match="which is no row pick"):
        m._pick(np.random.default_rng(0), {"pick": "row_under", "field": "c_city", "of": "x"}, True,
                {"x": 1}, {"x": {"pick": "int", "field": "lo_tax"}})
    with pytest.raises(ValueError, match="does not follow"):
        m._pick(np.random.default_rng(0), {"pick": "row_under", "field": "c_city", "of": "x"}, True,
                {"x": 1}, {"x": {"pick": "row", "field": "s_nation"}})


def test_the_mix_is_the_sources_thirteen_queries_and_a_sum_a_flight():
    assert {k: ADHOC[k] for k in ("loop", "connections", "processes", "zipf_theta", "check_one_in", "check_max")} == {
        "loop": "closed", "connections": 32, "processes": 4, "zipf_theta": 0.0, "check_one_in": 40, "check_max": 128}
    classes = ADHOC["classes"]
    assert {k: (c["weight"], len(c["variants"])) for k, c in classes.items()} == {
        "q1": (3, 3), "q2": (3, 3), "q3": (4, 4), "q4": (3, 3), "group_sum": (10, 4)}
    assert all(v.startswith("Sum(Intersect(") and v.endswith("field=lo_discount_amount)") for v in classes["q1"]["variants"])
    assert all(v.count("lo_discount <=") == 1 and "lo_quantity" in v for v in classes["q1"]["variants"])
    assert all(v.startswith("GroupBy(Rows(d_year), Rows(p_brand1), filter=") for v in classes["q2"]["variants"])
    assert [v.count("Rows(") for v in classes["q3"]["variants"] + classes["q4"]["variants"]] == [3, 3, 3, 3, 2, 3, 3]
    assert [re.search(r"field=(\w+)\)$", v).group(1) for v in classes["group_sum"]["variants"]] == [
        "lo_revenue", "lo_revenue", "lo_revenue", "lo_supplycost"]
    # every slot a variant names is a slot of its class, and every slot is named
    for cls, c in [*classes.items(), *SUMS["classes"].items()]:
        named = {n for v in c["variants"] for n in re.findall(r"\{(\w+)", v)}
        assert named == set(c["slots"]), cls
    # groupsum-c32 (PR 44): adhoc-c32's frame key for key, one request a query, the sum beside every group
    frame = ("loop", "connections", "processes", "zipf_theta", "check_one_in", "check_max")
    assert {k: SUMS[k] for k in frame} == {k: ADHOC[k] for k in frame}
    sums = SUMS["classes"]
    assert {k: (c["weight"], len(c["variants"])) for k, c in sums.items()} == {
        "q1": (3, 3), "q2": (3, 3), "q3": (4, 4), "q4": (3, 6)}
    assert sums["q1"] == classes["q1"]  # Q1 has no groups
    for cls in ("q2", "q3", "q4"):
        assert sums[cls]["slots"] == classes[cls]["slots"]
        assert all(v.startswith("GroupBy(") and AGGREGATE.search(v) for v in sums[cls]["variants"]), cls
        plain = [AGGREGATE.sub(")", v) for v in sums[cls]["variants"]]
        assert plain == [v for v in classes[cls]["variants"] for _ in range(2 if cls == "q4" else 1)]
    assert [AGGREGATE.search(v).group(1) for v in sums["q2"]["variants"] + sums["q3"]["variants"]] == ["lo_revenue"] * 7
    assert [AGGREGATE.search(v).group(1) for v in sums["q4"]["variants"]] == ["lo_revenue", "lo_supplycost"] * 3
    assert {"source", "why_classes", "assumed"} <= set(SUMS) and SUMS["name"] == "groupsum-c32"
    lone = mf.read_json("benchmark/traffic/lone-c1.json")
    assert (lone["connections"], lone["processes"], lone["zipf_theta"], lone["check_one_in"]) == (1, 1, 0.0, 10)
    assert [sorted(re.findall(r"Row\((\w+)=", v)) for v in lone["classes"]["pair_count"]["variants"]] == [
        ["duration_minutes", "pickup_time"], ["dist_miles", "pickup_month"]]


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_sweep_twins_and_every_variant_send_distinct_calls_of_the_new_picks(mix):
    m = generator.Mix(SSB, MIXES[mix])
    for calls in ([c for _, cs in m.sweep(7, 32) for c in cs], [cs[0] for _, cs in m.twins(7, 32)],
                  [c for _, cs in m.every_variant(7, 0, 8) for c in cs]):
        # a variant of Q4.1 has 100 distinct requests: the sweep sends them all and leaves the twins none
        assert len(calls) == len(set(calls)) and len(calls) >= sum(len(c["variants"]) for c in m.classes.values()) - 2
        for c in calls:
            reference.parse(c)
    # the sweep's second walk of a variant shares no row between the calls of a request: a run's and a
    # pick-under's rows count as rows
    _, named = m._draw(np.random.default_rng(1), "q2", 1, True)
    assert len(named) == 9 and {f for f, _ in named} == {"p_brand1", "s_region"}
    _, named = m._draw(np.random.default_rng(1), "q3", 3, True)
    assert {f for f, _ in named} == {"c_city", "s_city", "d_yearmonthnum"}
    sizes = [len(cs) for cls, cs in m.sweep(7, 32) if cls == "q3"]
    assert max(sizes) == 32 and sizes.count(32) >= 5


# ---------------------------------------------------------------------------
# the reference against a loop
# ---------------------------------------------------------------------------


def brute(ref, c):
    """The answer column by column, in Python: no numpy in the semantics."""
    cols = [{n: int(v[s, i]) for n, v in ref.one.items()} for s in range(ref.shards) for i in range(ref.hi)]

    def holds(col, b):
        if b.name == "Row" and b.cond is None:
            (name, row), = b.kw.items()
            return col[name] == row
        if b.name == "Row":
            name, conds = b.cond
            return all({"<": col[name] < x, "<=": col[name] <= x, ">": col[name] > x, ">=": col[name] >= x}[op]
                       for op, x in conds)
        parts = [holds(col, p) for p in b.pos]
        return all(parts) if b.name == "Intersect" else any(parts)

    if c.name == "Sum":
        hit = [col[c.kw["field"]] for col in cols if holds(col, c.pos[0])]
        return sum(hit), len(hit)
    names = [r.pos[0] for r in c.pos]
    summed = c.kw["aggregate"].kw["field"] if "aggregate" in c.kw else None
    groups, sums = {}, {}
    for col in cols:
        if holds(col, c.kw["filter"]):
            key = tuple(col[n] for n in names)
            groups[key] = groups.get(key, 0) + 1
            if summed is not None and col[summed] >= 0:  # a column without a value counts and adds nothing
                sums[key] = sums.get(key, 0) + col[summed]
    return (names, groups) if summed is None else (names, groups, sums)


@pytest.fixture(scope="module")
def small_ref():
    cfg = dict(rehearsal_cfg(), columns=8192, slab_rides=4096)  # 2 shards x 8,192 columns for the loop
    ref = reference.Reference(cfg, 17)
    ref.load()
    return cfg, ref


def by_group(a: np.ndarray) -> dict:
    return {tuple(int(i) for i in idx): int(a[tuple(idx)]) for idx in np.argwhere(a)}


@pytest.mark.parametrize("mix,cls,variant", [(mix, cls, v) for mix, data in MIXES.items()
                                             for cls, c in data["classes"].items() for v in range(len(c["variants"]))])
def test_the_reference_is_the_loops(small_ref, mix, cls, variant):
    cfg, ref = small_ref
    m = generator.Mix(cfg, MIXES[mix])
    rng = np.random.default_rng([variant, len(cls)])
    hits = 0
    for _ in range(3):
        pql = m.request(rng, cls, variant)
        c = reference.parse(pql)
        got, want = ref.evaluate(c), brute(ref, c)
        if c.name == "Sum":
            assert got == want, pql
            hits += want[1]
        else:
            names, counts, *sums = got
            assert len(got) == len(want) == (3 if "aggregate" in c.kw else 2), pql
            assert names == want[0] and int(counts.sum()) == sum(want[1].values()), pql
            assert by_group(counts) == want[1], pql
            if sums:  # in this record every column holds every measure: a group that counts has a sum above 0
                assert sums[0].dtype == np.int64 and by_group(sums[0]) == want[2] and set(want[2]) == set(want[1]), pql
            assert compare.check_answer("GroupBy", compare.to_json("GroupBy", got), got) is None
            hits += int(counts.sum())
    assert hits or cls == "q3" or (cls, variant) in {("q1", 2), ("q4", 2), ("group_sum", 0)}, "three empty answers prove little"


# ---------------------------------------------------------------------------
# whole runs: the timed path broken underneath
# ---------------------------------------------------------------------------


@pytest.mark.skipif(STAGED is None, reason="every cell of this PR is in the grid")
def test_the_staged_entries_name_what_is_there_and_one_the_grid_has_is_skipped():
    """Holds while an entry is staged and once the same entry stands in ``BENCHMARK.json``."""
    names = {w["name"] for w in STAGED["workloads"]}
    assert names <= {CELL, LONE, GROUPSUM} and {w["config"] for w in STAGED["workloads"]} <= {c["name"] for c in MANIFEST["configs"]}
    for k in ENTRIES:
        listed = [e["name"] for e in MANIFEST[k]]
        assert len(listed) == len(set(listed)) and {e["name"] for e in STAGED[k]} <= set(listed), k
    for w in STAGED["workloads"]:
        assert mf.cell(MANIFEST, w["name"])["traffic"] == w["traffic"] and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(mf.HERE, "traffic", w["traffic"] + ".json"))
    layers, e2e = {m["layer"] for m in GRID["per_layer"]} | {"host tier"}, {m["name"] for m in GRID["end_to_end"]}
    for m in STAGED["per_layer"]:
        assert set(m["workloads"]) <= names and m["layer"] in layers and m["moves"] in e2e
        assert os.path.exists(os.path.join(mf.HERE, "layer_metrics", m["name"] + ".py"))
    for name, cells in STAGED["per_layer_workloads"].items():
        assert set(cells) <= names and "workloads" in next(m for m in GRID["per_layer"] if m["name"] == name)
    for c in STAGED["configs"]:
        assert c["source"] == mf.read_json(c["file"])["source"] and c["reduced"] == mf.read_json(c["file"])["reduced"]
    # laid over a grid that already holds a staged cell (as the PR that brings its program's side will
    # write it, with readers of its own), the grid's entry stands and nothing comes twice
    cell = next(w for w in STAGED["workloads"] if w["name"] == GROUPSUM)
    own = {"name": "executor.groupsum_test_reader", "unit": "count", "better": "lower", "source": "program_counter",
           "layer": "executor lanes", "moves": "read_qps", "workloads": [GROUPSUM]}
    grid = dict(GRID, workloads=[w for w in GRID["workloads"] if w["name"] != GROUPSUM] + [dict(cell, why="the grid's own")],
                per_layer=GRID["per_layer"] + [own])
    over = laid_over(grid, STAGED)
    assert [w["why"] for w in over["workloads"] if w["name"] == GROUPSUM] == ["the grid's own"]
    assert [w["name"] for w in over["workloads"]].count(LONE) == 1 and over["per_layer"][len(GRID["per_layer"])] == own


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_line_is_the_manifests(capfd, trace):
    """As ``test_rehearsal`` holds every cell of the grid."""
    line, err = rehearsed_line(capfd, MANIFEST, CELL, trace)
    if trace:
        value = {k: v["value"] for k, v in line["metrics"].items()}
        # 0 is the metric's good reading, and the cell's since PR 43: no call of the mix goes per call
        assert 0 <= value["executor.percall_pct_of_flight"] <= 100 and value["executor.lane_declines_per_read"] >= 0
        assert value["rescache.hit_pct"] < 50, "almost no answer is cached"
        # set-up in four: start, schema, load, warm-up; the three that are metrics leave the schema,
        # the workers' start and the window's lead, a second or two
        setup_s = json.loads(re.search(r" e2e (\{.*\})$", err, re.M).group(1))["setup_s"]
        three = value["setup.ready_s"] + value["setup.load_s"] + value["setup.warm_s"]
        assert value["setup.warm_s"] > 0 and three < setup_s < three + 10.0, (setup_s, three)


def test_the_control_is_not_correct(capfd):
    rc, line, err = rehearse(capfd, "--workload", CELL, "--trace", "0", "--control", "lossy", manifest=MANIFEST)
    c = line["compared"]
    assert c["control_mismatches"][0] > 0 and c["read_mismatches"][0] == 0, err[-3000:]
    assert line["correct"] is False


def test_a_sum_or_a_group_one_too_high_is_not_correct(capfd):
    rc, line, err = rehearse(capfd, "--workload", CELL, "--trace", "0", manifest=MANIFEST,
                             child_script=os.path.join(HERE, "broken_sum_child.py"))
    assert line["compared"]["read_mismatches"][0] > 0, err[-3000:]
    assert line["correct"] is False and line["compared"]["failed_requests"][0] == 0


def test_one_connection_of_pair_counts_is_answered_on_the_host(capfd):
    """``taxi.lone-c1``: an untraced rehearsal is sound; a traced one finds no
    operation on the device in its window and so prints no line (exit 1)."""
    line, err = rehearsed_line(capfd, MANIFEST, LONE, 0)
    assert line["attempted"] > 100 and set(line["metrics"]) == {"read_qps", "read_p95_ms", "setup_s"}
    if LONE in {w["name"] for w in GRID["workloads"]}:
        return  # in the grid: test_rehearsal holds its traced line
    rc = run.main(["--seed", "11", "--seconds", "3", "--rehearsal", "--workload", LONE, "--trace", "1"], manifest=MANIFEST)
    out, err = capfd.readouterr()
    assert rc == 1 and out == "" and "the trace holds no device operation" in err


def test_the_new_cells_metrics_are_theirs_alone():
    """What each metric lists at the least: a later PR appends the cells it adds, and none is taken away."""
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    # PR 44: also where the batch lanes leave a TopN per call; the three cells whose readers tests/ pins wait (PERF.md section 7)
    assert by_name["executor.percall_pct_of_flight"]["workloads"][:2] == [CELL, "taxi.dashboard-c32"]
    assert by_name["hosttier.dispatch_share_pct"]["workloads"] == ["taxi.lone-c1"]
    assert CELL in by_name["executor.lane_declines_per_read"]["workloads"]
    warm = by_name["setup.warm_s"]  # PR 44: the warm-up's seconds, beside the two it adds up with
    assert warm == dict(by_name["setup.ready_s"], name="setup.warm_s", workloads=warm["workloads"])
    assert warm["workloads"][:2] == ["taxi.dashboard-c32", CELL]
    assert run.read_layer_metric("setup.warm_s", {"setup": {"warm_s": 86.5}}) == 86.5
    for cell in (w["name"] for w in MANIFEST["workloads"]):
        names = [m["name"] for m in mf.metrics_for(MANIFEST, cell, True)]
        assert "executor.percall_pct_of_flight" in names or cell not in (CELL, "taxi.dashboard-c32")
        assert ("hosttier.dispatch_share_pct" in names) == (cell == "taxi.lone-c1")
    spans = {"batcher": {"flight": {"seconds": 4.0}},
             "executor": {"executeSum": {"seconds": 1.0}, "executeGroupBy": {"seconds": 2.0}, "batchBSI": {"seconds": 0.5}}}
    assert run.read_layer_metric("executor.percall_pct_of_flight", {"vars": {"spans": spans}}) == 75.0
    lanes = {"kernels": {"dispatch_lanes": {"host": 3, "xla": 9}}, "devledger": {"totals": {"launches": 1}}}
    assert run.read_layer_metric("hosttier.dispatch_share_pct", {"vars": lanes}) == 75.0
    lanes["kernels"]["dispatch_lanes"] = {}  # a lane that never dispatched is absent: every answer was a launch
    assert run.read_layer_metric("hosttier.dispatch_share_pct", {"vars": lanes}) == 0.0
    for name, ctx in (("executor.percall_pct_of_flight", {"vars": {"spans": {"batcher": {"flight": {"seconds": 0}}}}}),
                      ("hosttier.dispatch_share_pct", {"vars": {"kernels": {"dispatch_lanes": {}},
                                                                "devledger": {"totals": {"launches": 0}}}})):
        with pytest.raises(ValueError, match="to read"):
            run.read_layer_metric(name, ctx)


# ---------------------------------------------------------------------------
# the cells that were there send what they sent
# ---------------------------------------------------------------------------

with open(os.path.join(HERE, "golden_pr38.json")) as _f:
    GOLDEN = {k: v for k, v in json.load(_f).items() if k != "about"}
MIX_OF = {"dashboard-c32": "taxi", "ingest-serve-c32": "taxi-ingest"}


def _calls_hash(requests) -> str:
    h = hashlib.sha256()
    for cls, calls in requests:
        h.update((cls + "\x00" + "\x01".join(calls) + "\n").encode())
    return h.hexdigest()


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_golden_slabs_and_requests_of_pr38(key):
    name, what, seed, *where = key.split("/")
    if what == "slab":
        cfg = mf.read_json(f"benchmark/configs/{name}.json")
        s = datagen.gen_slab(cfg, int(seed), *map(int, where))
        h = hashlib.sha256()
        for f in cfg["fields"]:
            a = s[f["name"]]
            h.update(f["name"].encode() + str(a.dtype).encode() + a.tobytes())
        assert h.hexdigest() == GOLDEN[key] and list(s) == [f["name"] for f in cfg["fields"]]
        return
    cfg = mf.read_json(f"benchmark/configs/{MIX_OF[name]}.json")
    mix = mf.read_json(f"benchmark/traffic/{name}.json")
    m = generator.Mix(cfg, mix)
    if what == "fingerprint":
        got = generator.fingerprint(cfg, mix, int(seed))
    elif what == "stream":
        h = hashlib.sha256()
        for conn in (0, 13, 31):
            s = m.stream(int(seed), "window", conn)
            for _ in range(2000):
                cls, pql = next(s)
                h.update(f"{conn}\x00{cls}\x00{pql}\n".encode())
        got = h.hexdigest()
    else:
        got = _calls_hash({"sweep": lambda: m.sweep(int(seed), 32), "twins": lambda: m.twins(int(seed), 32),
                           "every_variant": lambda: m.every_variant(int(seed), 1, 16)}[what]())
    assert got == GOLDEN[key]
