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
    pairs = [len(calls) for k, calls in a if k == "pair_count" and "cab_type" in calls[0]
             and "passenger_count" in calls[0]]
    assert {1, 2, 4, 8} <= set(pairs)  # 30 such calls exist: sizes past the 3 rows of cab_type


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_every_row_occurs_under_every_seed(config):
    """The program keeps one slot per row that occurs, so a row that comes
    and goes with the seed would change the compiled shapes: every row with
    a share is expected at least 100 times in the index."""
    cfg = run.mf.read_json(run.mf.config_entry(MANIFEST, config)["file"])
    rides = int(cfg["shards"]) * int(cfg["columns"])
    for f in cfg["fields"]:
        if f["kind"] == "set":
            w = datagen.row_weights(f)
            assert w[w > 0].min() * rides >= 100, f["name"]
            assert len(datagen.popularity_order(f)) == int(f.get("present", f["rows"]))


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
