"""Sharded execution on the virtual 8-device CPU mesh (the multi-chip
test technique mirroring the reference's in-process clusters,
test/pilosa.go:344-400)."""

import numpy as np
import pytest

import jax

from pilosa_tpu.core.field import Field, FieldOptions
from pilosa_tpu.parallel import ShardedField, default_mesh, mesh_shape_for
from pilosa_tpu.shardwidth import SHARD_WIDTH


def test_eight_devices_present():
    assert len(jax.devices()) == 8


def test_mesh_shape():
    # all devices ride the shards axis (the rows factor was collapsed
    # in r05 — see parallel/mesh.py module docstring)
    assert mesh_shape_for(8) == (8, 1)
    assert mesh_shape_for(2) == (2, 1)
    assert mesh_shape_for(1) == (1, 1)


@pytest.fixture(scope="module")
def sharded():
    field = Field("i", "f")
    rng = np.random.default_rng(5)
    n = 20000
    rows = rng.integers(0, 10, size=n)
    cols = rng.integers(0, SHARD_WIDTH * 6, size=n)  # 6 shards -> pads to 8
    field.import_bits(rows, cols)
    mesh = default_mesh(8)
    sf = ShardedField.from_field(field, mesh)
    truth = {}
    for r in range(10):
        truth[r] = set(
            (np.uint64(s) * np.uint64(SHARD_WIDTH) + c)
            for s in sf.shard_ids
            for c in field.view("standard").fragments[s].row_columns(r).tolist()
            if field.view("standard").fragments[s].has_row(r)
        )
    return sf, truth


def test_sharded_layout(sharded):
    sf, _ = sharded
    assert sf.bits.shape[0] % 4 == 0  # padded to shards axis
    assert sf.bits.shape[1] % 2 == 0  # padded to rows axis
    # verify the array is actually laid out across devices
    assert len(sf.bits.sharding.device_set) == 8


@pytest.mark.parametrize("op,setop", [
    ("intersect", lambda a, b: a & b),
    ("union", lambda a, b: a | b),
    ("difference", lambda a, b: a - b),
    ("xor", lambda a, b: a ^ b),
])
def test_count_pair_ops(sharded, op, setop):
    sf, truth = sharded
    got = sf.count_pair(3, 7, op=op)
    assert got == len(setop(truth[3], truth[7]))


def test_topn(sharded):
    sf, truth = sharded
    want = sorted(((r, len(c)) for r, c in truth.items()), key=lambda t: (-t[1], t[0]))
    got = sf.topn(3)
    assert [c for _, c in got] == [c for _, c in want[:3]]
    assert {r for r, _ in got} <= {r for r, c in want if c == want[2][1] or c > want[2][1]} | {r for r, _ in want[:3]}


def test_apply_updates(sharded):
    sf, truth = sharded
    S, R, W = sf.bits.shape
    set_mask = np.zeros((S, R, W), dtype=np.uint32)
    set_mask[0, 0, 0] = 1  # set bit col 0 of first row, first shard
    clear_mask = np.zeros_like(set_mask)
    before = sf.count_pair(sf.row_ids[0], sf.row_ids[0], op="union")
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(sf.mesh, P("shards", "rows", None))
    had_bit = bool(np.asarray(sf.bits[0, 0, 0]) & 1)
    sf.apply_updates(
        jax.device_put(set_mask, sharding), jax.device_put(clear_mask, sharding)
    )
    after = sf.count_pair(sf.row_ids[0], sf.row_ids[0], op="union")
    assert after == before + (0 if had_bit else 1)


def test_graft_entry_single_and_multi():
    import importlib.util
    import pathlib

    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", pathlib.Path(__file__).parent.parent / "__graft_entry__.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    mod.dryrun_multichip(8)
    mod.dryrun_multichip(4)
