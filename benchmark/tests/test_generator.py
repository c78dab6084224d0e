"""The generator is a function of the seed; the encoder speaks the
program's roaring; the reference's parser reads every class of every mix."""

import numpy as np
import pytest

import datagen
import generator
import reference
import run

from test_rehearsal import MANIFEST

CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_same_seed_same_requests(workload):
    _, cfg, mix = run.load_cell(MANIFEST, workload, rehearsal=False)
    a = generator.fingerprint(cfg, mix, 2**31 + 7)
    assert a == generator.fingerprint(cfg, mix, 2**31 + 7)
    assert a != generator.fingerprint(cfg, mix, 2**31 + 8)


@pytest.mark.parametrize("workload", CELLS)
def test_every_seed_sends_the_same_shares(workload):
    _, cfg, mix = run.load_cell(MANIFEST, workload, rehearsal=False)
    m = generator.Mix(cfg, mix)
    for seed in (1, 2):
        s = m.stream(seed, "window", 0)
        first = [next(s)[0] for _ in range(len(m.deck))]
        assert sorted(first) == sorted(m.deck)


@pytest.mark.parametrize("workload", CELLS)
def test_reference_reads_every_class(workload):
    _, cfg, mix = run.load_cell(MANIFEST, workload, rehearsal=True)
    m = generator.Mix(cfg, mix)
    ref = reference.Reference(cfg, 3)
    ref.load()
    rng = np.random.default_rng(0)
    for cls, c in mix["classes"].items():
        for _ in range(4 * len(c["variants"])):
            ref.evaluate(reference.parse(m.request(rng, cls)))


@pytest.mark.parametrize("workload", CELLS)
def test_sweep_meets_every_variant_once_per_size(workload):
    """The warm-up's sweep: a function of the seed; every variant of every
    class, up to 32 calls where it has as many; no call sent twice."""
    _, cfg, mix = run.load_cell(MANIFEST, workload, rehearsal=False)
    m = generator.Mix(cfg, mix)
    a = list(m.sweep(2**31 + 7, 32))
    assert a == list(m.sweep(2**31 + 7, 32)) and a != list(m.sweep(2**31 + 8, 32))
    sent = [c for _, calls in a for c in calls]
    assert len(sent) == len(set(sent))
    for cls, c in mix["classes"].items():
        sizes = [len(calls) for k, calls in a if k == cls]
        assert len(sizes) >= len(c["variants"]) and max(sizes) <= 32
        for v in c["variants"]:
            assert any(x.startswith(v.split("{")[0]) for k, calls in a if k == cls for x in calls), v
    if "cab_type" in str(mix["classes"].get("pair_count")):
        pairs = [len(calls) for k, calls in a if k == "pair_count" and "cab_type" in calls[0]
                 and "passenger_count" in calls[0]]
        assert {1, 2, 4, 8} <= set(pairs)  # 30 such calls exist: sizes past the 3 rows of cab_type


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_every_row_occurs_under_every_seed(config):
    """The program keeps one slot per row that occurs, so a row that comes
    and goes with the seed would change the compiled shapes: every row with
    a share is expected at least 80 times in what the load imports (84 is
    ``taxi-ingest``'s rarest, its shards half filled)."""
    cfg = run.mf.read_json(run.mf.config_entry(MANIFEST, config)["file"])
    rides = int(cfg["shards"]) * int(cfg["columns"])
    fields = datagen.fields_by_name(cfg)
    for f in cfg["fields"]:
        if f["kind"] == "set":
            w = datagen.row_weights(f, fields)
            assert w[w > 0].min() * rides >= 80, f["name"]
            assert len(datagen.popularity_order(f, fields)) == int(f.get("present", f["rows"]))


def test_slab_data_is_the_seeds():
    _, cfg, _ = run.load_cell(MANIFEST, "taxi.dashboard-c32", rehearsal=True)
    a, b = datagen.gen_slab(cfg, 2**31 + 5, 1, 3), datagen.gen_slab(cfg, 2**31 + 5, 1, 3)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["pickup_time"], datagen.gen_slab(cfg, 2**31 + 5, 1, 4)["pickup_time"])


def test_encoder_speaks_the_programs_roaring():
    from pilosa_tpu.storage import roaring

    rng = np.random.default_rng(4)
    sparse = np.unique(rng.integers(0, 1 << 22, 3000)).astype(np.uint64)
    dense = np.unique(rng.integers(1 << 22, (1 << 22) + (1 << 17), 90000)).astype(np.uint64)
    pos = np.concatenate([sparse, dense])
    assert np.array_equal(roaring.deserialize(datagen.encode_roaring(pos)), pos)


@pytest.mark.parametrize("workload", CELLS)
def test_twins_hold_one_unsent_call_twice(workload):
    """After the sweep, every variant once more: one request of one call
    twice, a call the sweep has not sent; a function of the seed."""
    _, cfg, mix = run.load_cell(MANIFEST, workload, rehearsal=False)
    m = generator.Mix(cfg, mix)
    a = list(m.twins(2**31 + 7, 32))
    assert a == list(m.twins(2**31 + 7, 32)) and a != list(m.twins(2**31 + 8, 32))
    swept = {c for _, calls in m.sweep(2**31 + 7, 32) for c in calls}
    firsts = [calls[0] for _, calls in a]
    assert all(len(calls) == 2 and calls[0] == calls[1] for _, calls in a)
    assert len(set(firsts)) == len(firsts) and not swept & set(firsts)
    rng = np.random.default_rng(9)
    for cls, c in mix["classes"].items():
        for i, v in enumerate(c["variants"]):
            if "Intersect" in v:  # what the planner shares; a variant of few rows is all sent by then
                left = any(m._draw(rng, cls, i, uniform=True)[0] not in swept for _ in range(40))
                assert not left or any(k == cls and calls[0].startswith(v.split("{")[0]) for k, calls in a), v


def test_after_the_twins_one_question_twice_in_a_flight_compiles_nothing():
    """What ``Mix.twins`` is for, on the program itself: two requests with
    the same filtered range count in one flight, neither cached, go through
    the planner's shared-subtree pass and ``Executor._bsi_rows`` over the
    live stack.  Once the warm-up's requests of the class have been sent,
    such a pair compiles nothing, for ``<`` and for ``>`` (without the twins
    it compiles 3 + 1 programs on the CPU; 7 inside a window on the chip)."""
    from pilosa_tpu.core.field import FieldOptions
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.exec.executor import Executor
    from pilosa_tpu.obs import devledger

    _, cfg, mix = run.load_cell(MANIFEST, "taxi.dashboard-c32", rehearsal=True)
    m = generator.Mix(cfg, mix)
    fields = datagen.fields_by_name(cfg)
    holder = Holder()
    idx = holder.create_index(cfg["index"])
    amount = fields["total_amount"]
    for name in ("cab_type", "pickup_year"):
        idx.create_field(name, FieldOptions())
    idx.create_field("total_amount", FieldOptions(field_type="int", min_=amount["min"], max_=amount["max"]))
    ex = Executor(holder)
    rng = np.random.default_rng(5)
    cabs, years = datagen.popularity_order(fields["cab_type"]), datagen.popularity_order(fields["pickup_year"])
    for lo in range(0, 2000, 100):
        ex.execute(cfg["index"], " ".join(
            f"Set({i}, cab_type={rng.choice(cabs)}) Set({i}, pickup_year={rng.choice(years)}) "
            f"Set({i}, total_amount={rng.integers(amount['min'], amount['max'] + 1)})" for i in range(lo, lo + 100)))

    def flight(*requests):
        before = devledger.counters()["compiles"]
        out = ex.execute_batch(cfg["index"], [(r, None) for r in requests])
        assert not any(isinstance(x, Exception) for x in out), out
        return devledger.counters()["compiles"] - before

    # the classes before this one have built the filter fields' stacks, as in a run
    flight(" ".join(f"Count(Intersect(Row(cab_type={a}), Row(pickup_year={b})))" for a in cabs[:2] for b in years[:2]))
    twins = [calls for cls, calls in m.twins(3, 4) if cls == "range_count"]
    assert len(twins) == 4
    for calls in [calls for cls, calls in m.sweep(3, 4) if cls == "range_count"] + twins:
        flight(" ".join(calls))
    lt = "Count(Intersect(Row(cab_type={c}), Row(total_amount < {v})))"
    gt = "Count(Intersect(Row(pickup_year={y}), Row(total_amount > {v})))"
    assert lt in mix["classes"]["range_count"]["variants"] and gt in mix["classes"]["range_count"]["variants"]
    for text in (lt.format(c=cabs[0], v=4321), gt.format(y=years[1], v=8765)):
        assert flight(text, text) == 0, text
