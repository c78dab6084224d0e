"""The write stream, the judge that holds reads to "an acknowledged import
is visible", and the cell ``taxi.ingest-serve`` that needs both (a cell of
``BENCHMARK.json`` since PR 37; ``test_rehearsal.py`` rehearses it with the
others, this file holds it to what a stream adds).

The stream's plan and data are functions of the seed alone; the judge is
driven over hand-made logs (nothing in flight, one, three, four imports in
flight, an answer from before an acknowledged import, an answer ahead of
every import sent); the controls ``stale`` and ``lossy`` come out as not
correct while the program's own answers in the same run stay sound; with
the program itself made stale underneath (``stale_child.py``) the run's own
verdict is not correct; and the mixes that only read send what they sent
before there was a stream (golden fingerprints taken on the code before it).
"""

import hashlib
import json
import os

import numpy as np
import pytest

import compare
import datagen
import generator
import loadgen
import manifest as mf
import reference
import run

from test_rehearsal import MANIFEST, rehearse, rehearsed_line

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "taxi.ingest-serve"


# ---------------------------------------------------------------------------
# the files
# ---------------------------------------------------------------------------


def test_the_configuration_is_taxis_record():
    taxi = mf.read_json("benchmark/configs/taxi.json")
    ingest = mf.read_json("benchmark/configs/taxi-ingest.json")
    for k in ("fields", "published", "added", "guarantees", "index", "chips", "shards",
              "shard_width_exp", "slab_rides", "reduced"):
        assert ingest[k] == taxi[k], k
    differ = {k for k in set(taxi) | set(ingest) if taxi.get(k) != ingest.get(k)}
    assert differ == {"name", "source", "deployment", "columns", "reduced_why", "assumed", "rehearsal"}
    assert ingest["assumed"][:len(taxi["assumed"])] == taxi["assumed"]
    assert ingest["columns"] * 2 == 1 << ingest["shard_width_exp"]  # half of every shard is free


def test_the_readers_are_dashboard_c32s():
    dash = mf.read_json("benchmark/traffic/dashboard-c32.json")
    mix = mf.read_json("benchmark/traffic/ingest-serve-c32.json")
    differ = {k for k in set(dash) | set(mix) if dash.get(k) != mix.get(k)}
    assert differ == {"name", "stream", "rehearsal"}
    assert set(mix["stream"]) == {"every_s", "why"} and mix["stream"]["every_s"] == 4.0


def test_every_row_occurs_in_the_load_under_every_seed():
    """``test_generator``'s rule for a configuration of the grid (100 rides
    a row expected), at this one's own floor: its shards are half filled,
    so the rarest row is expected 84 times in what the load stage imports,
    and a stream's slabs then bring no row that is new to its field."""
    cfg = mf.read_json("benchmark/configs/taxi-ingest.json")
    rides = int(cfg["shards"]) * int(cfg["columns"])
    for f in cfg["fields"]:
        if f["kind"] == "set":
            w = datagen.row_weights(f)
            assert w[w > 0].min() * rides >= 80, f["name"]
            assert len(datagen.popularity_order(f)) == int(f.get("present", f["rows"]))


def test_every_sweep_and_window_slab_has_free_columns():
    _, cfg, mix = run.load_cell(MANIFEST, CELL, rehearsal=False)
    s = run.Stream(generator.Mix(cfg, mix), 7, MANIFEST["run_seconds"])
    assert (len(s.plan), s.sweep, s.in_window) == (64, [1, 2], 12)
    with pytest.raises(run.RunFailure, match="runs out of free columns"):
        run.Stream(generator.Mix(cfg, mix), 7, 400.0)


# ---------------------------------------------------------------------------
# the stream: a function of the seed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_the_streams_plan_and_data_are_the_seeds(seed):
    _, cfg, mix = run.load_cell(MANIFEST, CELL, rehearsal=True)
    m = generator.Mix(cfg, mix)
    plan = m.slabs(seed)
    assert plan == generator.Mix(cfg, mix).slabs(seed)
    shards, loaded = cfg["shards"], datagen.slabs_per_shard(cfg)
    per_shard = (1 << cfg["shard_width_exp"]) // cfg["slab_rides"]
    assert [k for k, _, _ in plan] == list(range(shards * (per_shard - loaded)))
    assert sorted((sh, sl) for _, sh, sl in plan) == [(sh, sl) for sh in range(shards)
                                                      for sl in range(loaded, per_shard)]
    for i in range(0, len(plan) - shards):  # any `shards` in a row go to distinct shards
        assert len({sh for _, sh, _ in plan[i:i + shards]}) == shards
    assert plan[0][1] == seed % shards and plan[0][2] == loaded
    k, shard, slab = plan[5]
    a, b = loadgen.slab_requests(cfg, seed, shard, slab), loadgen.slab_requests(cfg, seed, shard, slab)
    assert a == b and [x[0] for x in a] == [f["name"] for f in cfg["fields"]]
    assert a != loadgen.slab_requests(cfg, seed + 1, shard, slab)
    reads = list(m.every_variant(seed, 2, 16))
    assert reads == list(m.every_variant(seed, 2, 16)) != list(m.every_variant(seed, 3, 16))
    sent = [c for _, calls in reads for c in calls]
    assert len(sent) == len(set(sent))  # an answer cached in a round is not asked again in it
    for cls, c in mix["classes"].items():
        sizes = [len(calls) for k, calls in reads if k == cls]
        assert sizes[:len(c["variants"])] == [1] * len(c["variants"]) and max(sizes) <= 16
    # 21 filtered sums exist: three go singly, and one request holds 16 of the 18 left
    assert 16 in [len(calls) for k, calls in reads if k == "sum_filtered"]


def test_a_streamed_request_lands_on_the_slabs_own_columns():
    from pilosa_tpu.storage import roaring

    _, cfg, _ = run.load_cell(MANIFEST, CELL, rehearsal=True)
    width, rides = 1 << cfg["shard_width_exp"], cfg["slab_rides"]
    reqs = loadgen.slab_requests(cfg, 5, 1, 20)
    field, path, blob, bits = reqs[0]
    assert path == f"/index/taxi/field/{field}/import-roaring/1"
    cols = roaring.deserialize(blob) % np.uint64(width)
    assert bits == rides and cols.min() == 20 * rides and cols.max() == 21 * rides - 1
    assert reqs[-1][1].endswith("?view=bsig_total_amount")


# ---------------------------------------------------------------------------
# the judge, on hand-made logs
# ---------------------------------------------------------------------------

PQL = "Count(Row(cab_type=0))"


@pytest.fixture(scope="module")
def tiny():
    _, cfg, _ = run.load_cell(MANIFEST, CELL, rehearsal=True)
    return cfg


def fresh(cfg, kind=reference.Reference):
    ref = kind(cfg, 21, extent=1 << cfg["shard_width_exp"])
    ref.load()
    return ref


def imp(cfg, k, t_send, t_ack, field="cab_type", status=200):
    loaded = datagen.slabs_per_shard(cfg)
    return {"k": k, "field": field, "shard": k % cfg["shards"], "slab": loaded + k // cfg["shards"],
            "t_send": t_send, "t_ack": t_ack, "status": status, "bits": 1, "due": t_send}


def read(cfg, applied, t_send, t_recv, pql=PQL):
    """A read whose answer is the reference's with ``applied`` visible."""
    ref = fresh(cfg)
    for m in applied:
        ref.apply_import(m)
    call, _ = ref.call(pql)
    body = json.dumps({"results": [compare.to_json(call.name, ref.answer(pql))]}).encode()
    return {"cls": "c", "pql": pql, "body": body, "t_send": t_send, "t_recv": t_recv}


def judge(cfg, reads, imports):
    return compare.judge_reads(fresh(cfg), reads, imports)


def test_judge_nothing_in_flight_is_exact(tiny):
    a, b = imp(tiny, 0, 1.0, 1.5), imp(tiny, 1, 5.0, 5.5)
    v = judge(tiny, [read(tiny, [a], 2.0, 2.2)], [a, b])
    assert (v["mismatches"], v["unjudged"], v["per_class"], v["in_flight"]) == (0, 0, {"c": 1}, {0: 1})


def test_judge_an_answer_from_before_a_certain_import_fails(tiny):
    a = imp(tiny, 0, 1.0, 1.5)
    v = judge(tiny, [read(tiny, [], 2.0, 2.2)], [a])
    assert v["mismatches"] == 1 and v["examples"][0].startswith("c: " + PQL + ": count")


def test_judge_an_answer_ahead_of_every_sent_import_fails(tiny):
    a = imp(tiny, 0, 3.0, 3.5)
    v = judge(tiny, [read(tiny, [a], 2.0, 2.2)], [a])
    assert v["mismatches"] == 1 and v["in_flight"] == {0: 1}


def test_judge_one_in_flight_may_show_or_not(tiny):
    a, b = imp(tiny, 0, 1.0, 1.5), imp(tiny, 1, 1.9, 2.1)
    for shown in ([a], [a, b]):
        v = judge(tiny, [read(tiny, shown, 2.0, 2.2)], [a, b])
        assert (v["mismatches"], v["unjudged"], v["per_class"], v["in_flight"]) == (0, 0, {}, {1: 1})
    assert judge(tiny, [read(tiny, [b], 2.0, 2.2)], [a, b])["mismatches"] == 1  # b without a: no state


def test_judge_an_import_of_another_field_is_not_in_flight(tiny):
    a, b = imp(tiny, 0, 1.0, 1.5), imp(tiny, 1, 1.9, 2.1, field="pickup_year")
    v = judge(tiny, [read(tiny, [a], 2.0, 2.2)], [a, b])
    assert (v["mismatches"], v["per_class"], v["in_flight"]) == (0, {"c": 1}, {0: 1})


def test_judge_three_in_flight_any_state_between(tiny):
    log = [imp(tiny, 0, 1.0, 1.5)] + [imp(tiny, k, 1.6 + k / 10, 2.05 + k / 10) for k in (1, 2, 3)]
    v = judge(tiny, [read(tiny, [log[0], log[1], log[3]], 2.0, 2.5)], log)
    assert (v["mismatches"], v["unjudged"], v["in_flight"]) == (0, 0, {3: 1})
    v = judge(tiny, [read(tiny, [log[1], log[3]], 2.0, 2.5)], log)  # without the certain one
    assert v["mismatches"] == 1 and "nor with any of 3 imports in flight" in v["examples"][0]


def test_judge_four_in_flight_is_not_judged(tiny):
    log = [imp(tiny, k, 1.6 + k / 10, 2.05 + k / 10) for k in range(4)]
    v = judge(tiny, [read(tiny, [], 2.0, 2.5)], log)
    assert (v["compared"], v["mismatches"], v["unjudged"], v["per_class"]) == (0, 0, 1, {})


def test_judge_a_failed_import_is_never_certain(tiny):
    a = imp(tiny, 0, 1.0, 1.5, status=500)
    for shown in ([], [a]):
        v = judge(tiny, [read(tiny, shown, 2.0, 2.2)], [a])
        assert (v["mismatches"], v["per_class"], v["in_flight"]) == (0, {}, {1: 1})


def test_judge_walks_the_reads_in_the_order_they_were_sent(tiny):
    a, b = imp(tiny, 0, 1.0, 1.5), imp(tiny, 1, 3.0, 3.5)
    reads = [read(tiny, [a, b], 4.0, 4.2), read(tiny, [a], 2.0, 2.2), read(tiny, [], 0.5, 0.7)]
    v = judge(tiny, reads, [b, a])
    assert (v["mismatches"], v["per_class"], v["certain"]) == (0, {"c": 3}, 2)


def test_the_readback_is_held_to_every_acknowledged_import(tiny):
    log = [imp(tiny, 0, 1.0, 1.5), imp(tiny, 1, 3.0, 3.5), imp(tiny, 2, 4.0, 4.5, status=500)]
    ok = {"pql": PQL, "body": read(tiny, log[:2], 0, 0)["body"]}
    short = {"pql": PQL, "body": read(tiny, log[:1], 0, 0)["body"]}
    assert compare.judge_readback(fresh(tiny), log, [ok])["mismatches"] == 0
    v = compare.judge_readback(fresh(tiny), log, [ok, short])
    assert (v["compared"], v["mismatches"]) == (2, 1) and v["examples"][0].startswith("read-back: " + PQL)


@pytest.mark.parametrize("control,shown", [("stale", [0]), ("lossy", [0, 1])])
def test_the_controls_on_a_hand_made_log(tiny, control, shown):
    """stale: the newest acknowledged import of a field does not show;
    lossy: it shows with a column in sixteen lost."""
    log = [imp(tiny, 0, 1.0, 1.5), imp(tiny, 1, 1.6, 1.9)]
    v = compare.judge_reads(fresh(tiny), [read(tiny, log, 2.0, 2.2)], log,
                            control=fresh(tiny, compare.CONTROLS[control]))
    assert v["mismatches"] == 1
    c = fresh(tiny, compare.CONTROLS[control])
    for m in log:
        c.apply_import(m)
    assert c.applied["cab_type"] == frozenset((m["shard"], m["slab"]) for m in log if m["k"] in shown)


def test_the_reference_remembers_an_answer_per_state(tiny):
    ref, a = fresh(tiny), imp(tiny, 0, 0, 0)
    before = ref.answer(PQL)
    ref.apply_import(a)
    after = ref.answer(PQL)
    ref.revert_import(a)
    assert after > before == ref.answer(PQL) and len(ref._answers) == 2
    ref.apply_import(imp(tiny, 1, 0, 0, field="pickup_year"))  # not a field of the text: the same state
    assert ref.answer(PQL) == before and len(ref._answers) == 2


# ---------------------------------------------------------------------------
# whole runs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_line_holds_what_a_stream_adds(capfd, trace):
    """As ``test_rehearsal`` holds every cell of the grid; and every import
    is acknowledged, every slab due in the window is acknowledged in it, and
    the read-back after the last acknowledgement is sound."""
    line, err = rehearsed_line(capfd, MANIFEST, CELL, trace)
    assert {"imports_failed", "readback_mismatches", "stream_slabs_short"} < set(line["compared"])
    assert "reads_unjudged" not in line["compared"] and "of them not judged" in err
    assert "slabs due acknowledged in the window" in err
    if trace:
        assert line["metrics"]["ingest.import_ack_p95_ms"]["value"] > 0
        assert line["metrics"]["stacks.refreshes_per_import"]["value"] > 0


@pytest.mark.parametrize("control", ["stale", "lossy"])
def test_a_control_is_not_correct(capfd, control):
    rc, line, err = rehearse(capfd, "--workload", CELL, "--trace", "0", "--control", control,
                             manifest=MANIFEST)
    c = line["compared"]
    assert c["control_mismatches"][0] > 0, err[-3000:]
    assert c["read_mismatches"][0] == 0 and c["readback_mismatches"][0] == 0, err[-3000:]
    assert line["correct"] is False


def test_stale_control_needs_a_stream(capfd):
    rc = run.main(["--workload", "taxi.dashboard-c32", "--rehearsal", "--control", "stale"])
    out, err = capfd.readouterr()
    assert rc == 1 and out == "" and "streams nothing" in err


def test_a_program_that_shows_imports_late_is_not_correct(capfd):
    rc, line, err = rehearse(capfd, "--workload", CELL, "--trace", "0", manifest=MANIFEST,
                             child_script=os.path.join(HERE, "stale_child.py"))
    c = line["compared"]
    assert c["read_mismatches"][0] > 0 and c["readback_mismatches"][0] > 0, err[-3000:]
    assert c["imports_failed"][0] == 0 and line["correct"] is False


# ---------------------------------------------------------------------------
# the mixes that only read did not move
# ---------------------------------------------------------------------------

with open(os.path.join(HERE, "golden_dashboard_c32.json")) as _f:
    GOLDEN = {k: v for k, v in json.load(_f).items() if k != "about"}


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_golden_fingerprints_of_dashboard_c32(key):
    workload, what, seed = key.split("/")
    _, cfg, mix = run.load_cell(MANIFEST, workload, rehearsal=False)
    m = generator.Mix(cfg, mix)
    h = hashlib.sha256()
    if what == "stream":
        for conn in (0, 13, 31):
            s = m.stream(int(seed), "window", conn)
            for _ in range(2000):
                cls, pql = next(s)
                h.update(f"{conn}\x00{cls}\x00{pql}\n".encode())
    else:
        for cls, calls in m.sweep(int(seed), 32):
            h.update((cls + "\x00" + "\x01".join(calls) + "\n").encode())
    assert h.hexdigest() == GOLDEN[key]
    assert m.streams is None and m.slabs(int(seed)) == []
