"""Serving-path multi-device execution.

The conftest boots an 8-virtual-device CPU backend; these tests assert the
REAL serving stack — Holder → Executor → PQL — lays field stacks over the
8-device mesh (NamedSharding over the "shards" axis) and that batched
Count / TopN / GroupBy answer correctly through the sharded kernels, the
role the reference's mapReduce fan-out plays (executor.go:2454-2611).
"""

import numpy as np
import pytest

import jax

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.ops import kernels
from pilosa_tpu.parallel.mesh import serving_mesh


pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs a multi-device backend"
)


@pytest.fixture()
def setup():
    h = Holder()
    idx = h.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    ex = Executor(h)
    rng = np.random.default_rng(11)
    width = h.n_words * 32
    writes = []
    # spread bits over 12 shards so the stack pads to 16 over 8 devices
    for row in range(5):
        for col in rng.integers(0, 12 * width, size=80):
            writes.append(f"Set({int(col)}, f={row})")
    for row in range(3):
        for col in rng.integers(0, 12 * width, size=40):
            writes.append(f"Set({int(col)}, g={row})")
    ex.execute("i", " ".join(writes))
    return h, ex


def test_serving_mesh_exists():
    mesh = serving_mesh()
    assert mesh is not None
    assert mesh.devices.size == len(jax.devices())
    assert mesh.axis_names == ("shards",)


def test_field_stack_is_mesh_sharded(setup):
    h, ex = setup
    field = h.index("i").field("f")
    shards = sorted(h.index("i").available_shards())
    stack = ex.stacks.get(field, shards)
    assert stack is not None
    bits = stack.bits
    assert len(bits.sharding.device_set) == len(jax.devices())
    assert kernels.shards_axis_of(bits) is not None
    # the shard axis padded to a mesh multiple
    assert bits.shape[0] % len(jax.devices()) == 0


def test_batched_counts_match_single_device(setup):
    h, ex = setup
    pairs = [(0, 1), (2, 3), (1, 4), (0, 0)]
    q = " ".join(
        f"Count(Intersect(Row(f={a}), Row(f={b})))" for a, b in pairs
    )
    got = ex.execute("i", q)
    # ground truth from the host mirrors, no device involvement
    f = h.index("i").field("f").view("standard")
    want = []
    for a, b in pairs:
        total = 0
        for frag in f.fragments.values():
            total += int(
                np.bitwise_count(
                    frag.row_words_host(a) & frag.row_words_host(b)
                ).sum()
            )
        want.append(total)
    assert got == want


def test_topn_through_sharded_stack(setup):
    h, ex = setup
    got = ex.execute("i", "TopN(f, n=3)")[0]
    f = h.index("i").field("f").view("standard")
    counts = {}
    for frag in f.fragments.values():
        for r in frag.row_ids():
            c = int(np.bitwise_count(frag.row_words_host(r)).sum())
            if c:
                counts[r] = counts.get(r, 0) + c
    want = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:3]
    assert [(p.id, p.count) for p in got] == want


def test_groupby_through_sharded_stacks(setup):
    h, ex = setup
    got = ex.execute("i", "GroupBy(Rows(f), Rows(g))")[0]
    # ground truth combination counts from host mirrors
    idx = h.index("i")
    fv = idx.field("f").view("standard")
    gv = idx.field("g").view("standard")
    want = []
    f_rows = sorted({r for fr in fv.fragments.values() for r in fr.row_ids()})
    g_rows = sorted({r for fr in gv.fragments.values() for r in fr.row_ids()})
    shards = sorted(set(fv.fragments) | set(gv.fragments))
    for r1 in f_rows:
        for r2 in g_rows:
            total = 0
            for s in shards:
                fa = fv.fragment(s)
                fb = gv.fragment(s)
                if fa is None or fb is None:
                    continue
                total += int(
                    np.bitwise_count(
                        fa.row_words_host(r1) & fb.row_words_host(r2)
                    ).sum()
                )
            if total:
                want.append(((r1, r2), total))
    got_norm = [
        ((gc.group[0].row_id, gc.group[1].row_id), gc.count) for gc in got
    ]
    assert got_norm == want


def test_writes_invalidate_sharded_stack(setup):
    h, ex = setup
    q = "Count(Intersect(Row(f=0), Row(f=1))) Count(Intersect(Row(f=2), Row(f=3)))"
    before = ex.execute("i", q)
    # pick a column not currently intersecting
    width = h.n_words * 32
    col = 5 * width + 17
    ex.execute("i", f"Set({col}, f=0) Set({col}, f=1)")
    after = ex.execute("i", q)
    assert after[0] == before[0] + 1
    assert after[1] == before[1]
