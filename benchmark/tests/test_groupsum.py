"""The judge's side of ``GroupBy(Rows(...)..., filter=..., aggregate=Sum(field=<int field>))``
(PR 44): the parser reads it, the reference answers ``(names, counts, sums)`` with
every sum an exact integer, ``to_json`` writes Pilosa 1.4's ``GroupCount``
(``group``, ``count``, ``sum``), ``check_answer`` holds every served group's
sum to the reference's and says which group is wrong, the state key of an
answer takes the summed field in, and the control ``lossy`` differs in sums
as well as in counts.  Numpy only, a few seconds; the reference against the
column-by-column loop for every variant of ``groupsum-c32`` is
``test_ssb.py::test_the_reference_is_the_loops``.

And one run of the program: the staged cell ``ssb-flat.groupsum-c32``
rehearsed on the CPU ends as the program earns it, whichever program this is.
"""

import json
import os

import numpy as np
import pytest

import compare
import generator
import manifest as mf
import reference
import run
from datagen import UNSET

from proctools import children_of
from test_ssb import GROUPSUM, MANIFEST, SUMS, rehearsal_cfg

# ---------------------------------------------------------------------------
# eight columns by hand
# ---------------------------------------------------------------------------

TINY = {"shard_width_exp": 3, "shards": 1, "columns": 8, "slab_rides": 8, "fields": [
    {"name": "g", "kind": "set", "rows": 3}, {"name": "h", "kind": "set", "rows": 2},
    {"name": "v", "kind": "int", "min": 0, "max": 100}]}
ASK = "GroupBy(Rows(g), Rows(h), aggregate=Sum(field=v))"
FILTERED = "GroupBy(Rows(g), Rows(h), filter=Row(h=0), aggregate=Sum(field=v))"


@pytest.fixture
def tiny():
    ref = reference.Reference(TINY, 1)
    ref.one["g"][0] = [0, 0, 1, 1, 2, 2, UNSET, 0]
    ref.one["h"][0] = [0, 1, 0, 1, 0, 1, 0, 0]
    ref.one["v"][0] = [5, -1, 7, 0, -1, -1, 9, 3]  # -1: the column holds no value
    return ref


def test_a_groups_count_is_its_columns_and_its_sum_is_over_those_that_hold_a_value(tiny):
    names, counts, sums = tiny.evaluate(reference.parse(ASK))
    assert names == ["g", "h"] and sums.dtype == np.int64 and sums.shape == counts.shape == (3, 2)
    assert counts.tolist() == [[2, 1], [1, 1], [1, 1]]  # the column without a g is in no group
    assert sums.tolist() == [[8, 0], [7, 0], [0, 0]]    # one without a v counts and adds nothing
    names, counts, sums = tiny.evaluate(reference.parse(FILTERED))
    assert counts.tolist() == [[2, 0], [1, 0], [1, 0]] and sums.tolist() == [[8, 0], [7, 0], [0, 0]]
    # without aggregate the answer is the two-tuple it was, the counts the same
    plain = tiny.evaluate(reference.parse("GroupBy(Rows(g), Rows(h), filter=Row(h=0))"))
    assert len(plain) == 2 and plain[0] == names and np.array_equal(plain[1], counts)
    # a filter that leaves nothing
    names, counts, sums = tiny.evaluate(reference.parse("GroupBy(Rows(g), filter=Row(v > 50), aggregate=Sum(field=v))"))
    assert counts.tolist() == sums.tolist() == [0, 0, 0]


def test_a_group_that_counts_is_listed_also_with_sum_nought(tiny):
    raw = tiny.evaluate(reference.parse(ASK))
    listed = compare.to_json("GroupBy", raw)
    assert [(tuple(x["rowID"] for x in g["group"]), g["count"], g["sum"]) for g in listed] == [
        ((0, 0), 2, 8), ((0, 1), 1, 0), ((1, 0), 1, 7), ((1, 1), 1, 0), ((2, 0), 1, 0), ((2, 1), 1, 0)]
    assert all(type(g["sum"]) is int and type(g["count"]) is int for g in listed)
    assert [x["field"] for x in listed[0]["group"]] == ["g", "h"] and set(listed[0]) == {"group", "count", "sum"}
    assert compare.check_answer("GroupBy", json.loads(json.dumps(listed)), raw) is None
    # a plain GroupBy's JSON has no sum
    assert all(set(g) == {"group", "count"} for g in compare.to_json("GroupBy", raw[:2]))


@pytest.mark.parametrize("pql", [
    "GroupBy(Rows(g), aggregate=Sum(field=h))",            # a set field
    "GroupBy(Rows(g), aggregate=Sum(field=nowhere))",      # no field of the index
    "GroupBy(Rows(g), aggregate=Sum(Row(h=0), field=v))",  # a Sum with a filter of its own
    "GroupBy(Rows(g), aggregate=Sum(field=v, n=3))",
    "GroupBy(Rows(g), aggregate=Sum())",
    "GroupBy(Rows(g), aggregate=Count(Row(h=0)))",
    "GroupBy(Rows(g), aggregate=v)",
    "GroupBy(Rows(g), aggregate=3)",
])
def test_an_aggregate_the_reference_does_not_know_is_refused_not_guessed(tiny, pql):
    with pytest.raises(ValueError, match="no reference for GroupBy"):
        tiny.evaluate(reference.parse(pql))


def test_sums_are_integer_adds_past_2_to_the_53():
    big = 2**53 + 1  # float64 holds 2^53 and 2^53 + 2, not this
    sums = reference._group_sums(np.array([2, 0, 2, 0, 1]), np.array([big, 1, 2, big, big], np.int64), 4)
    assert sums.dtype == np.int64 and sums.tolist() == [big + 1, big, big + 2, 0]
    assert np.bincount([0, 0], weights=[big, 1]).astype(np.int64)[0] != big + 1  # what is not used
    assert reference._group_sums(np.zeros(0, np.int64), np.zeros(0, np.int64), 3).tolist() == [0, 0, 0]


# ---------------------------------------------------------------------------
# one served answer against the reference's: a table of hand-altered answers
# ---------------------------------------------------------------------------


def _group(listed, idx):
    return next(g for g in listed if tuple(x["rowID"] for x in g["group"]) == idx)


def one_too_high(listed):
    _group(listed, (1, 0))["sum"] += 1


def sum_missing(listed):
    del _group(listed, (1, 0))["sum"]


def sum_as_float(listed):
    _group(listed, (1, 0))["sum"] = 7.0


def sum_as_bool(listed):
    _group(listed, (1, 1))["sum"] = False


def sum_as_text(listed):
    _group(listed, (1, 0))["sum"] = "7"


def sum_null(listed):
    _group(listed, (1, 0))["sum"] = None


def sum_moved_to_a_neighbour(listed):
    a, b = _group(listed, (1, 0)), _group(listed, (1, 1))
    a["sum"], b["sum"] = b["sum"], a["sum"]


def count_wrong_sum_right(listed):
    _group(listed, (0, 0))["count"] += 1


def group_left_out(listed):
    listed.remove(_group(listed, (2, 1)))


def group_twice(listed):
    listed.append(dict(_group(listed, (0, 0))))


def empty_group_listed(listed):
    listed.append({"group": [{"field": "g", "rowID": 2}, {"field": "h", "rowID": 1}], "count": 0, "sum": 0})
    listed.remove(_group(listed, (2, 1)))


def sum_past_int64(listed):
    _group(listed, (1, 0))["sum"] = 2**70


@pytest.mark.parametrize("alter,names_group", [
    (one_too_high, "group (1, 0) sum 8, want 7"), (sum_missing, "group (1, 0) has no sum"),
    (sum_as_float, "group (1, 0) sum 7.0 is no integer"), (sum_as_bool, "group (1, 1) sum False is no integer"),
    (sum_as_text, "group (1, 0) sum '7' is no integer"), (sum_null, "group (1, 0) sum None is no integer"),
    (sum_moved_to_a_neighbour, "group (1, 0) sum 0, want 7"), (count_wrong_sum_right, "group (0, 0) count 3, want 2"),
    (group_left_out, "group (2, 1) count 0, want 1"), (group_twice, "group (0, 0) count 2 (empty or repeated)"),
    (empty_group_listed, "group (2, 1) count 0 (empty or repeated)"), (sum_past_int64, "group (1, 0) sum 1180591620717411303424, want 7"),
], ids=lambda a: getattr(a, "__name__", None))
def test_an_altered_answer_is_refused_with_the_group_named(tiny, alter, names_group):
    raw = tiny.evaluate(reference.parse(ASK))
    listed = compare.to_json("GroupBy", raw)
    alter(listed)
    why = compare.check_answer("GroupBy", json.loads(json.dumps(listed)), raw)
    assert why is not None and why.startswith(names_group), why


@pytest.mark.parametrize("unasked", [7, 7.5, None, "x"])
def test_a_plain_groupby_is_judged_to_the_letter_as_it_was(tiny, unasked):
    """No accepted cell is judged differently: a ``sum`` the server adds unasked is not looked at."""
    raw = tiny.evaluate(reference.parse("GroupBy(Rows(g), Rows(h))"))
    listed = compare.to_json("GroupBy", raw)
    assert compare.check_answer("GroupBy", listed, raw) is None
    for g in listed:
        g["sum"] = unasked
    assert compare.check_answer("GroupBy", listed, raw) is None
    _group(listed, (0, 0))["count"] += 1
    assert compare.check_answer("GroupBy", listed, raw) == "group (0, 0) count 3, want 2"
    # and an answer without sums to a question that asked for them is refused
    asked = tiny.evaluate(reference.parse(ASK))
    assert compare.check_answer("GroupBy", compare.to_json("GroupBy", raw), asked).startswith("group (0, 0) has no sum")


# ---------------------------------------------------------------------------
# the state an answer is remembered under, and the controls
# ---------------------------------------------------------------------------


def test_the_answers_state_takes_the_summed_field_in():
    """A stream's import of ``lo_revenue`` changes the key of an aggregated
    ``GroupBy``'s answer, and the answer: before it the slab's columns count
    in their groups and add nothing."""
    cfg = rehearsal_cfg()
    cfg = dict(cfg, columns=cfg["columns"] // 2)  # the other half of every shard is free, as under a stream
    ref = reference.Reference(cfg, 23, extent=1 << cfg["shard_width_exp"])
    ref.load()
    pql = "GroupBy(Rows(d_year), Rows(c_region), filter=Row(s_region=2), aggregate=Sum(field=lo_revenue))"
    call, named = ref.call(pql)
    assert named == ("c_region", "d_year", "lo_revenue", "s_region")
    assert ref.call(pql.replace(", aggregate=Sum(field=lo_revenue)", ""))[1] == ("c_region", "d_year", "s_region")
    _, counts0, sums0 = ref.answer(pql)
    slab = {"shard": 1, "slab": cfg["columns"] // cfg["slab_rides"]}
    for field in ("d_year", "c_region", "s_region", "lo_supplycost"):
        ref.apply_import(dict(slab, field=field))
    _, counts1, sums1 = ref.answer(pql)
    assert counts1.sum() > counts0.sum() and np.array_equal(sums1, sums0)
    kept = len(ref._answers)
    ref.apply_import(dict(slab, field="lo_revenue"))
    _, counts2, sums2 = ref.answer(pql)
    assert len(ref._answers) == kept + 1, "the import of the summed field did not change the key"
    assert np.array_equal(counts2, counts1) and (sums2 >= sums1).all() and sums2.sum() > sums1.sum()
    ref.revert_import(dict(slab, field="lo_revenue"))
    assert np.array_equal(ref.answer(pql)[2], sums0) and len(ref._answers) == kept + 1


@pytest.fixture(scope="module")
def loaded():
    cfg = dict(rehearsal_cfg(), columns=8192, slab_rides=4096)
    refs = []
    for kind in (reference.Reference, compare.LossyReference):
        refs.append(kind(cfg, 29))
        refs[-1].load()
    return cfg, *refs


def test_the_lossy_control_differs_in_sums_not_only_in_counts(loaded):
    """Dropping one column in sixteen leaves a group's count to be judged; the
    sum alone would have failed it too."""
    cfg, ref, lossy = loaded
    m = generator.Mix(cfg, SUMS)
    rng = np.random.default_rng(5)
    reads = [{"cls": cls, "pql": m.request(rng, cls, v)} for cls in ("q2", "q3", "q4")
             for v in range(len(SUMS["classes"][cls]["variants"])) for _ in range(2)]
    by_sum_alone = 0
    for r in reads:
        want, got = ref.answer(r["pql"]), lossy.answer(r["pql"])
        if not want[1].sum():
            continue  # an answer without a column has nothing to lose
        assert (got[1] <= want[1]).all() and (got[2] <= want[2]).all()
        if got[1].sum() < want[1].sum():
            assert compare.check_answer("GroupBy", compare.to_json("GroupBy", got), want).startswith("group (")
        # the lossy store's sums beside the right counts: every group listed, every count right
        doctored = compare.to_json("GroupBy", (want[0], want[1], got[2]))
        why = compare.check_answer("GroupBy", doctored, want)
        if got[2].sum() < want[2].sum():
            assert why is not None and " sum " in why and why.startswith("group ("), why
            by_sum_alone += 1
    assert by_sum_alone >= 10, "too few answers lost a column to prove anything"
    verdict = compare.judge_reads(ref, reads, control=lossy)
    assert verdict["mismatches"] >= by_sum_alone and verdict["compared"] == len(reads)
    assert compare.judge_reads(ref, reads, control=ref)["mismatches"] == 0  # the reference in its own place is sound


def test_the_stale_control_inherits_the_form(loaded):
    cfg, ref, _ = loaded
    stale = compare.StaleReference(cfg, 29)
    stale.load()
    pql = generator.Mix(cfg, SUMS).request(np.random.default_rng(2), "q4", 0)
    a, b = ref.answer(pql), stale.answer(pql)
    assert len(b) == 3 and np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])


# ---------------------------------------------------------------------------
# the staged cell against the program that is here
# ---------------------------------------------------------------------------


def test_the_staged_cell_ends_as_the_program_earns_it(capfd):
    """A CPU rehearsal of ``ssb-flat.groupsum-c32`` over the manifest with the
    staged entry laid in.  A program whose PQL has no ``aggregate=`` is not
    judged correct: the run ends in its failure exit with the refused
    request named, or in a line with ``read_mismatches`` or
    ``failed_requests`` over its limit; never in a line whose only excess is
    ``rehearsal``.  A program that has it earns that line, every class of
    the mix judged.  (PERF.md section 6, PR 44, says which the present
    program earns.)"""
    rc = run.main(["--seed", "11", "--seconds", "3", "--rehearsal", "--workload", GROUPSUM, "--trace", "0",
                   "--limit", "400"], manifest=MANIFEST)
    out, err = capfd.readouterr()
    assert children_of(os.getpid()) == [], "the run left a process"
    if rc == 1:
        assert out == "" and "aggregate" in err and "GroupBy(" in err, err[-3000:]
        return
    assert rc == 3, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False and mf.validate_line(MANIFEST, GROUPSUM, False, line) == []
    over = {k: v for k, (v, limit) in line["compared"].items() if v > limit}
    if over != {"rehearsal": 1}:
        assert set(over) & {"read_mismatches", "failed_requests"}, err[-3000:]
    else:
        assert line["compared"]["classes_unjudged"][0] == 0 and line["attempted"] > 0
