"""exec/stacks.py: the one rule for values derived from a stack snapshot.

A value (gram, row counts, cross gram, BSI aggregate) is served only for
exactly the snapshot it was computed from: an incremental refresh drops
every one of them at once, an install against a snapshot that has moved
on is refused, and a bounded family drops its coldest key first."""

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec.executor import Executor

PAIRS = " ".join(
    f"Count(Intersect(Row(f={a}), Row(f={b})))"
    for a in range(4) for b in range(4)
)

# name -> (the query that installs it, the key it is installed under,
#          a write that changes its answer without adding a row or a
#          shard ({c}: a column of shard 0 no row holds yet),
#          the stack it is kept with)
CASES = {
    "gram": (PAIRS, None, "Set({c}, f=0) Set({c}, f=1)", "f"),
    "rowcounts": (
        "TopN(f, Row(g=0), n=4, tanimotoThreshold=1)", None,
        "Set({c}, f=0) Set({c}, g=0)", "f",
    ),
    "crossgram": (
        "GroupBy(Rows(f), Rows(g))", "g",
        "Set({c}, f=0) Set({c}, g=0)", "f",
    ),
    "bsi_agg": ("Sum(field=v)", "sum", "Set(7, v=-333)", "v"),
}


@pytest.fixture()
def served():
    """An executor over two set fields and an int field, a second one over
    the same holder that may use no stack (the reference), and a column
    of shard 0 that no row holds."""
    h = Holder()
    idx = h.create_index("i")
    idx.create_field("f")
    idx.create_field("g")
    idx.create_field("v", FieldOptions(field_type="int", min_=-1000, max_=1000))
    ex = Executor(h, rescache_entries=0)
    ex._BSI_SINGLE_WARM = 0  # a lone BSI call takes the stack at once
    rng = np.random.default_rng(11)
    pool = rng.integers(0, 3 * h.n_words * 32, size=150)
    writes = [
        f"Set({int(c)}, {name}={row})"
        for name, rows in (("f", 4), ("g", 3))
        for row in range(rows)
        for c in rng.choice(pool, size=40, replace=False)
    ]
    writes += [
        f"Set({int(c)}, v={int(rng.integers(-1000, 1000))})"
        for c in pool[:60]
    ]
    writes.append("Set(7, v=5)")
    ex.execute("i", " ".join(writes))
    plain = Executor(h, rescache_entries=0)
    plain.stacks.get = lambda *a, **k: None
    free = next(c for c in range(h.n_words * 32) if c not in set(pool))
    return ex, plain, free


def _stack(ex, fname):
    idx = ex.holder.index("i")
    field, shards = idx.field(fname), ex._shards_for(idx, None)
    if field.is_bsi():
        return ex.stacks.bsi(field, shards)
    return ex.stacks.get(field, shards)


def _installed(ex, name):
    """Run the name's query until its value is kept with the stack."""
    query, key, _, fname = CASES[name]
    for _ in range(6):
        ex.execute("i", query)
    stack = _stack(ex, fname)
    bits = stack.bits
    assert stack.get(name, bits, key) is not None, name
    return stack, bits


@pytest.mark.parametrize("name", list(CASES))
def test_refresh_drops_every_derived_value_and_the_next_read_is_right(
    served, name
):
    ex, plain, free = served
    query, key, write, fname = CASES[name]
    stack, old = _installed(ex, name)
    stack.put("witness", old, 1)  # a value no lane knows to pop
    before = ex.execute("i", query)
    refreshes, rebuilds = ex.stacks.incremental, ex.stacks.rebuilds
    ex.execute("i", write.format(c=free))
    assert _stack(ex, fname) is stack  # refreshed in place ...
    assert ex.stacks.incremental == refreshes + 1
    assert ex.stacks.rebuilds == rebuilds
    new = stack.bits
    assert new is not old  # ... to a new snapshot, with nothing derived
    for bits in (old, new):
        assert stack.get(name, bits, key) is None
        assert stack.get("witness", bits) is None
    after = ex.execute("i", query)
    assert after == plain.execute("i", query) and after != before
    assert stack.bits is new


@pytest.mark.parametrize("name", list(CASES))
def test_put_against_a_snapshot_that_moved_on_installs_nothing(served, name):
    ex, _, free = served
    _, key, write, fname = CASES[name]
    stack, old = _installed(ex, name)
    value = stack.get(name, old, key)
    ex.execute("i", write.format(c=free))
    assert _stack(ex, fname) is stack and stack.bits is not old
    assert stack.put(name, old, value, key) is False
    assert stack.get(name, old, key) is None
    assert stack.get(name, stack.bits, key) is None
    assert stack.put(name, stack.bits, value, key) is True
    assert stack.get(name, stack.bits, key) is value


@pytest.mark.parametrize("name", list(CASES))
def test_a_bounded_family_drops_its_coldest_key_first(served, name):
    ex = served[0]
    stack = _stack(ex, CASES[name][3])
    bits = stack.bits
    for key in ("a", "b"):
        stack.put(name, bits, key.upper(), key, cap=2)
    assert stack.get(name, bits, "a") == "A"  # a hit: "b" is now coldest
    stack.put(name, bits, "C", "c", cap=2)
    assert stack.get(name, bits, "b") is None
    assert stack.get(name, bits, "a") == "A"
    assert stack.get(name, bits, "c") == "C"
    stack.put(name, bits, None, "a")  # None removes
    assert stack.get(name, bits, "a") is None


def test_reuse_is_counted_per_snapshot(served, monkeypatch):
    from pilosa_tpu.exec import stacks

    ex, _, free = served
    monkeypatch.setattr(stacks, "GRAM_CACHE_MIN_REUSE", 2)
    stack = _stack(ex, "f")
    bits = stack.bits
    assert [stack.reused("gram", bits) for _ in range(3)] == [
        False, False, True
    ]
    assert stack.reused("crossgram", bits, "g") is False  # its own count
    ex.execute("i", f"Set({free}, f=0)")
    assert _stack(ex, "f") is stack
    assert stack.reused("gram", stack.bits) is False  # starts over


def test_drop_retires_the_stacks_and_gives_their_bytes_back(served):
    from pilosa_tpu.core import membudget
    from pilosa_tpu.exec import stacks

    ex = served[0]
    idx = ex.holder.index("i")
    field, shards = idx.field("g"), ex._shards_for(idx, None)
    budget = membudget.default_budget()
    used = budget.used()
    assert not ex.stacks.cached(field, shards)
    assert ex.stacks.get(field, shards) is not None
    assert ex.stacks.cached(field, shards) and budget.used() > used
    stacks.drop(field)
    assert not ex.stacks.cached(field, shards) and budget.used() == used
