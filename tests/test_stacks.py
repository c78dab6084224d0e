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


# --------------------------------------------------------------------------
# The refresh after a write: in place, from the fragments' device copies
# where they are at hand, and the snapshot rule that makes donation safe.
# --------------------------------------------------------------------------

import json
import threading
import urllib.request
import weakref

import jax

from pilosa_tpu.exec import stacks
from pilosa_tpu.obs import tracing
from pilosa_tpu.parallel import mesh

SHARDS = 4
F_ROWS, DEPTH_MAX = 9, 4000


@pytest.fixture(params=[1, 4], ids=["one-device", "mesh-of-4"])
def devices(request):
    mesh.configure_serving(request.param)
    yield request.param
    mesh.configure_serving(None)


@pytest.fixture()
def routes():
    """The ``route`` tag of every ``stacks.refresh`` span, as it ends."""
    rec, old = tracing.RecordingTracer(), tracing.get_tracer()
    tracing.set_tracer(rec)
    yield lambda: [s.tags["route"] for s in rec.finished("stacks.refresh")]
    tracing.set_tracer(old)


def _imported(seed=3, shard_list=range(SHARDS)):
    """(executor, index): a set field ``f`` (row 8 in the first shard
    alone) and an int field ``v`` over four shards, or over those of
    ``shard_list``; stacks not built yet."""
    h = Holder()
    idx = h.create_index("i")
    width = idx.n_words * 32
    rng = np.random.default_rng(seed)
    shard_list = np.array(shard_list, np.int64)
    cols = rng.choice(
        shard_list.size * width, size=300 * shard_list.size, replace=False
    )
    cols = (shard_list[cols // width] * width + cols % width).astype(np.uint64)
    rows = rng.integers(0, F_ROWS - 1, size=cols.size).astype(np.uint64)
    f = idx.create_field("f")
    f.import_bits(rows, cols)
    f.import_bits(
        np.full(5, F_ROWS - 1, np.uint64),
        int(shard_list[0]) * width + np.arange(5, dtype=np.uint64),
    )
    v = idx.create_field(
        "v", FieldOptions(field_type="int", min_=0, max_=DEPTH_MAX)
    )
    v.import_values(cols, rng.integers(0, DEPTH_MAX, size=cols.size))
    return Executor(h, rescache_entries=0), idx


def _get(ex, idx, name, shard_list=range(SHARDS)):
    field = idx.field(name)
    shards = list(shard_list)
    return ex.stacks.bsi(field, shards) if field.is_bsi() else ex.stacks.get(
        field, shards
    )


def _frags(idx, name, shard_list=range(SHARDS)):
    field = idx.field(name)
    view = field.view(field.bsi_view_name() if field.is_bsi() else "standard")
    return [view.fragments[s] for s in shard_list]


def _as_built(idx, name, stack, shard_list=range(SHARDS)) -> np.ndarray:
    """What a build gathers, from the host mirrors: the stack's shape, its
    shards where the stack's own order has them."""
    frags = dict(zip(shard_list, _frags(idx, name, shard_list)))
    out = np.zeros(
        (stack.bits.shape[0], len(stack.slot_of), idx.n_words), np.uint32
    )
    for si, s in stacks.positions(list(shard_list), stack.bits):
        ids, matrix = frags[s].rows_matrix_host()
        for k, r in enumerate(ids):
            out[si, stack.slot_of[r]] = matrix[k]
    return out


def _spent(old) -> bool:
    """Whether a refresh wrote into ``old``'s memory: the array it was
    donated whole is deleted; over a mesh the buffer of the chip that
    was written is, and a read of the array raises."""
    return old.is_deleted() or any(
        sh.data.is_deleted() for sh in old.addressable_shards
    )


def _buffers(bits) -> dict:
    """device -> where its buffer of the array lies."""
    return {
        sh.device: sh.data.unsafe_buffer_pointer()
        for sh in bits.addressable_shards
    }


def _write(idx, name, rng, shards):
    """A seeded import into rows the field has, in ``shards`` only."""
    width = idx.n_words * 32
    for s in shards:
        cols = (s * width + rng.choice(width, size=30, replace=False)).astype(
            np.uint64
        )
        if idx.field(name).is_bsi():
            idx.field(name).import_values(
                cols, rng.integers(0, DEPTH_MAX, size=cols.size)
            )
        else:
            idx.field(name).import_bits(
                rng.integers(0, F_ROWS - 1, size=cols.size).astype(np.uint64),
                cols,
            )


@pytest.mark.parametrize("name", ["f", "v"])
@pytest.mark.parametrize("route", ["device", "host"])
def test_a_refresh_writes_the_changed_blocks_into_the_array_that_is_there(
    devices, route, name, routes
):
    """Seeded imports, set and BSI field, one device and a mesh of four,
    both routes: the old snapshot is spent, the counters hold the
    changed blocks' bytes and not the stack's, and the stack is bit for
    bit what a fresh build makes.  The mesh is held to the device route
    as one device is: nothing through the host, nothing from chip to
    chip, and the chips that hold no changed shard keep their buffers."""
    ex, idx = _imported()
    stack = _get(ex, idx, name)
    block = len(stack.slot_of) * idx.n_words * 4
    rng = np.random.default_rng([5, devices, name == "v"])
    for round_ in range(6):
        if route == "device":
            for frag in _frags(idx, name):
                frag.device_bits()  # the ingest uploader keeps them current
        old = stack.bits
        changed = sorted(
            rng.choice(SHARDS, size=1 + round_ % 2, replace=False).tolist()
        )
        before = (
            ex.stacks.refresh_bytes, ex.stacks.refresh_host_bytes,
            ex.stacks.incremental, ex.stacks.rebuilds,
        )
        was = _buffers(old)
        _write(idx, name, rng, changed)
        assert _get(ex, idx, name) is stack
        assert _spent(old) and stack.bits is not old
        on_host = route == "host"
        assert routes()[-1] == route and ex.stacks.refresh_peer_bytes == 0
        if devices > 1 and route == "device":
            # shard s on chip s: the changed chips' buffers were donated
            # (where the program writes is the runtime's to say); the
            # others are the old array's own, where they lay
            now = _buffers(stack.bits)
            assert all(
                now[d] == was[d]
                for s, d in enumerate(jax.devices()[:devices])
                if s not in changed
            )
            assert [
                sh.data.is_deleted() for sh in old.addressable_shards
            ] == [s in changed for s in range(SHARDS)]
        assert (
            ex.stacks.refresh_bytes, ex.stacks.refresh_host_bytes,
            ex.stacks.incremental, ex.stacks.rebuilds,
        ) == (
            before[0] + len(changed) * block,
            before[1] + on_host * len(changed) * block,
            before[2] + 1, before[3],
        )
        assert ex.stacks.refresh_out_of_place == 0
        assert np.array_equal(
            np.asarray(stack.bits), _as_built(idx, name, stack)
        )
    refreshed = np.asarray(stack.bits)
    stacks.drop(idx.field(name))
    fresh = _get(ex, idx, name)
    assert fresh is not stack and fresh.slot_of == stack.slot_of
    assert np.array_equal(np.asarray(fresh.bits), refreshed)


@pytest.mark.parametrize("route", ["device", "host"])
def test_a_row_the_fragment_lacks_reads_zeros_and_a_new_row_rebuilds(
    devices, route
):
    ex, idx = _imported()
    stack = _get(ex, idx, "f")
    lone = stack.slot_of[F_ROWS - 1]  # shard 0 alone holds the row
    if route == "device":
        for frag in _frags(idx, "f"):
            frag.device_bits()
    _write(idx, "f", np.random.default_rng(1), [2])
    assert _get(ex, idx, "f") is stack and ex.stacks.incremental == 1
    bits = np.asarray(stack.bits)
    assert bits[0, lone].any() and not bits[1:, lone].any()
    assert np.array_equal(bits, _as_built(idx, "f", stack))
    width = idx.n_words * 32
    idx.field("f").import_bits(
        np.array([99], np.uint64), np.array([2 * width + 1], np.uint64)
    )
    rebuilt = _get(ex, idx, "f")
    assert rebuilt is not stack and 99 in rebuilt.slot_of
    assert (ex.stacks.incremental, ex.stacks.rebuilds) == (1, 2)
    assert np.array_equal(
        np.asarray(rebuilt.bits), _as_built(idx, "f", rebuilt)
    )


def test_a_leased_snapshot_is_left_alone(devices):
    """The snapshot rule: the scope that holds a stack is handed it as it
    holds it; another thread's refresh copies, by no program of its own;
    once the lease is back the next refresh is in place again."""
    ex, idx = _imported()
    stack = _get(ex, idx, "f")
    rng = np.random.default_rng(2)
    _write(idx, "f", rng, [0])
    assert _get(ex, idx, "f") is stack  # the refresh's program is compiled
    programs = stacks.DL_STACK.snapshot()["compiles"]
    with stacks.reading():
        held = stack.bits
        was = np.asarray(held).copy()
        _write(idx, "f", rng, [1])
        assert _get(ex, idx, "f") is stack and stack.bits is held
        assert ex.stacks.incremental == 1  # not refreshed under its reader
        other = threading.Thread(target=_get, args=(ex, idx, "f"))
        other.start()
        other.join()
        assert ex.stacks.incremental == 2
        assert ex.stacks.refresh_out_of_place == 1
        assert not _spent(held) and np.array_equal(np.asarray(held), was)
        copy = stack.bits
        assert copy is not held
        assert np.array_equal(np.asarray(copy), _as_built(idx, "f", stack))
    assert stack._leased == 0
    _write(idx, "f", rng, [3])
    assert _get(ex, idx, "f") is stack
    assert _spent(copy) and not _spent(held)
    assert ex.stacks.refresh_out_of_place == 1
    assert stacks.DL_STACK.snapshot()["compiles"] == programs
    assert np.array_equal(
        np.asarray(stack.bits), _as_built(idx, "f", stack)
    )


def test_an_import_inside_the_groupby_lane_copies_and_deletes_no_operand(
    devices, monkeypatch
):
    """The GroupBy lane launches a later level on the snapshot it read at
    its start, so its lease spans start, BSI lane and finish: an import
    into a level's field from another thread in between, and that
    thread's refresh, go out of place; no operand is deleted under the
    lane and every answer is exactly the state's before the import (or
    after it), as a stackless executor counts it."""
    ex, idx = _imported()
    plain = Executor(ex.holder, rescache_entries=0)
    plain.stacks.get = lambda *a, **k: None
    qs = [
        f"GroupBy(Rows(f), Rows(f), Rows(f), filter=Row(f={r}))" for r in range(4)
    ] + ["GroupBy(Rows(f), Rows(f), filter=Union(Row(f=0), Row(f=5)))"]
    flight = [(q, None) for q in qs]
    rng = np.random.default_rng(6)
    ex.execute_batch("i", flight)  # stacks built, programs compiled
    stack = _get(ex, idx, "f")
    _write(idx, "f", rng, [0])
    assert _get(ex, idx, "f") is stack and ex.stacks.incremental == 1
    before = plain.execute_batch("i", flight)
    held, inner = [], ex._batch_bsi

    def between(*args):
        held.append(stack._snap[0])  # what the lane's levels will read

        def other():
            _write(idx, "f", rng, [1, 2])
            assert _get(ex, idx, "f") is stack

        t = threading.Thread(target=other)
        t.start()
        t.join()
        return inner(*args)

    monkeypatch.setattr(ex, "_batch_bsi", between)
    got = ex.execute_batch("i", flight)
    after = plain.execute_batch("i", flight)
    assert (ex.stacks.incremental, ex.stacks.refresh_out_of_place) == (2, 1)
    assert stack._leased == 0 and stack._snap[0] is not held[0]
    assert not _spent(held[0])
    assert ex.lane_declines["groupby"]["error"] == 0
    assert before != after
    for g, b, a in zip(got, before, after):
        assert not isinstance(g, Exception) and g in (b, a)
    assert got == before  # every level read the snapshot of the start
    monkeypatch.setattr(ex, "_batch_bsi", inner)
    assert ex.execute_batch("i", flight) == after


def test_a_rebuild_lets_go_of_the_retired_array_first(devices, monkeypatch):
    """More than half the shards changed between two reads: the stack is
    rebuilt, and its old array is gone before the new one is uploaded."""
    ex, idx = _imported()
    old = weakref.ref(_get(ex, idx, "f").bits)
    _write(idx, "f", np.random.default_rng(4), [0, 1, 3])
    alive = []
    build = stacks.Stacks._build

    def spy(self, *args, **kwargs):
        alive.append(old() is not None)
        return build(self, *args, **kwargs)

    monkeypatch.setattr(stacks.Stacks, "_build", spy)
    assert _get(ex, idx, "f") is not None
    assert alive == [False] and ex.stacks.rebuilds == 2


@pytest.mark.parametrize("n_shards", [4, 12, 32])
@pytest.mark.parametrize("devices", [4], indirect=True)
def test_a_shards_fragment_copies_and_stack_slice_lie_on_one_device(
    devices, n_shards
):
    """The mesh's one rule, read by both sides: every shard's fragment
    copy is on the device that holds the shard's position of every stack,
    set field and BSI field alike, and both stacks share their order."""
    shard_list = list(range(n_shards))
    ex, idx = _imported(shard_list=shard_list)
    for name in ("f", "v"):
        bits = _get(ex, idx, name, shard_list).bits
        assert bits.shape[0] == n_shards
        home = {}
        for sh in bits.addressable_shards:
            lo, hi, _ = sh.index[0].indices(bits.shape[0])
            home.update(dict.fromkeys(range(lo, hi), sh.device))
        placed = stacks.positions(shard_list, bits)
        assert sorted(s for _, s in placed) == shard_list
        assert placed == [
            (p, s) for p, s in enumerate(
                mesh.stack_order(tuple(shard_list), devices)
            )
        ]
        for (p, s), frag in zip(
            sorted(placed, key=lambda ps: ps[1]), _frags(idx, name, shard_list)
        ):
            assert frag.device_bits().devices() == {home[p]}, (name, s)
            assert home[p] == jax.devices()[mesh.chip_of_shard(s, devices)]


@pytest.mark.parametrize(
    "shard_list, crossing",
    [
        # two shards a chip and six of chip 0's: four lie in other
        # chips' shares
        ([0, 4, 8, 12, 16, 20], [8, 12, 16, 20]),
        # a list with a gap, 6 shards over 4 devices: chip 0 has one
        # more than its share
        ([0, 1, 2, 4, 8, 11], [8]),
    ],
    ids=["one-chips-shards", "a-gap"],
)
@pytest.mark.parametrize("name", ["f", "v"])
@pytest.mark.parametrize("devices", [4], indirect=True)
def test_a_shard_the_rule_could_not_align_goes_from_chip_to_chip(
    devices, name, shard_list, crossing, routes
):
    """Its block is gathered where the fragment's copy lies and sent to
    the one chip that keeps it: the peer route, its bytes counted, none
    through the host, the stack bit for bit a fresh build and the reads
    right.  A shard of the same list that does align stays on its chip."""
    ex, idx = _imported(shard_list=shard_list)
    plain = Executor(ex.holder, rescache_entries=0)
    plain.stacks.get = lambda *a, **k: None
    stack = _get(ex, idx, name, shard_list)
    block = len(stack.slot_of) * idx.n_words * 4
    home = {
        s: p // (stack.bits.shape[0] // devices)
        for p, s in stacks.positions(shard_list, stack.bits)
    }
    assert [
        s for s in shard_list if home[s] != mesh.chip_of_shard(s, devices)
    ] == crossing
    rng = np.random.default_rng([8, name == "v"])
    aligned = next(s for s in shard_list if s not in crossing)
    for changed, route, peers in (
        ([crossing[0]], "peer", 1), ([aligned], "device", 0),
        ([aligned, crossing[-1]], "peer", 1),
    ):
        for frag in _frags(idx, name, shard_list):
            frag.device_bits()
        before = ex.stacks.refresh_peer_bytes
        _write(idx, name, rng, changed)
        assert _get(ex, idx, name, shard_list) is stack
        assert routes()[-1] == route
        assert ex.stacks.refresh_peer_bytes == before + peers * block
        assert ex.stacks.refresh_host_bytes == 0
        assert np.array_equal(
            np.asarray(stack.bits), _as_built(idx, name, stack, shard_list)
        )
    assert (ex.stacks.incremental, ex.stacks.rebuilds) == (3, 1)
    queries = (
        ["Sum(field=v)", "Count(Row(v > 2000))", "Sum(Row(f=1), field=v)"]
        if name == "v" else
        [PAIRS, "TopN(f, Row(f=2), n=3)", "Count(Union(Row(f=0), Row(f=8)))"]
    )
    for q in queries:
        assert ex.execute("i", q) == plain.execute("i", q), q


@pytest.mark.parametrize("devices", [4], indirect=True)
def test_a_fragment_with_no_device_copy_takes_the_host_route(devices, routes):
    """Over a mesh as on one device: the block comes from the host mirror
    (``route`` ``host``, its bytes counted) only where the fragment has
    no copy on a device; beside it a shard that has one stays there."""
    ex, idx = _imported()
    stack = _get(ex, idx, "f")
    block = len(stack.slot_of) * idx.n_words * 4
    frags = _frags(idx, "f")
    assert all(f._device is None for f in frags)
    _write(idx, "f", np.random.default_rng(6), [2])
    assert _get(ex, idx, "f") is stack and routes() == ["host"]
    assert ex.stacks.refresh_host_bytes == block
    frags[1].device_bits()
    _write(idx, "f", np.random.default_rng(7), [1])
    assert _get(ex, idx, "f") is stack and routes() == ["host", "device"]
    assert (ex.stacks.refresh_host_bytes, ex.stacks.refresh_peer_bytes) == (
        block, 0
    )
    assert np.array_equal(np.asarray(stack.bits), _as_built(idx, "f", stack))


@pytest.mark.parametrize("devices", [1], indirect=True)  # launches are quickest
def test_three_kinds_of_reader_beside_a_writer(devices, served):
    """The dispatcher's flights, the per-call path and the prefetcher's
    thread against a writer that makes every read refresh a stack: no
    exception, and every answer the plain executor's.  The writer sets
    bits of one row a field; the readers ask about the other rows."""
    ex, plain, _ = served
    idx = ex.holder.index("i")
    shards = ex._shards_for(idx, None)
    width = idx.n_words * 32
    batch = [
        ("Count(Intersect(Row(f=0), Row(f=1))) Count(Union(Row(f=1), Row(f=2)))", None),
        ("Count(Intersect(Row(g=0), Row(f=2)))", None),
        ("Count(Intersect(Row(f=1), Row(v > 100)))", None),
    ]
    single = [
        "TopN(f, Row(g=0), ids=[0, 1, 2])",
        "GroupBy(Rows(f, limit=3), Rows(g, limit=2))",
        "Count(Row(v > 100))",
    ]
    # v: the writer rewrites column 7 with values under 100
    ex.execute("i", "Set(7, v=5)")
    want_batch = [plain.execute("i", q) for q, _ in batch]
    want_single = [plain.execute("i", q) for q in single]
    assert ex.execute_batch("i", batch) == want_batch  # stacks built
    assert [ex.execute("i", q) for q in single] == want_single
    errors, rounds, stop = [], 200, threading.Event()
    turns = {}

    def guarded(fn):
        def run():
            try:
                while not stop.is_set():
                    fn()
                    turns[fn] = turns.get(fn, 0) + 1
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
                stop.set()
        return threading.Thread(target=run)

    def flights():
        got = ex.execute_batch("i", batch)
        assert got == want_batch, got

    def calls():
        for q, want in zip(single, want_single):
            got = ex.execute("i", q)
            assert got == want, (q, got)

    def prefetcher():
        for name in ("f", "g"):
            ex.stacks.prefetch(idx.field(name), shards)

    readers = [guarded(flights), guarded(calls), guarded(prefetcher)]
    for t in readers:
        t.start()
    free = iter(range(3 * width - 1, 0, -1))
    for n in range(rounds):
        if stop.is_set():
            break
        c = next(free)
        ex.execute("i", f"Set({c}, f=3) Set({c}, g=2) Set(7, v={n % 90})")
        seen = dict(turns)  # a write a turn of the slowest reader
        while not stop.is_set() and n < rounds - 1 and any(
            turns.get(fn, 0) == seen.get(fn, 0) for fn in (flights, calls)
        ):
            stop.wait(0.001)
    stop.set()
    for t in readers:
        t.join(60)
    assert not errors, errors
    assert ex.stacks.incremental > rounds
    for q in [b[0] for b in batch] + single + [PAIRS, "Sum(field=v)"]:
        assert ex.execute("i", q) == plain.execute("i", q)


def test_debug_vars_shows_the_refresh(tmp_path):
    """One import and one read on a CPU server: the span and the three
    counters are in /debug/vars."""
    from pilosa_tpu.server.api import API
    from pilosa_tpu.server.http import Server
    from pilosa_tpu.storage import roaring
    from pilosa_tpu.storage.disk import HolderStore

    holder = Holder()
    store = HolderStore(holder, str(tmp_path / "data"))
    store.open()
    server = Server(API(holder, store), port=0)
    server.serve_background()

    def call(method, path, body=None):
        req = urllib.request.Request(
            f"http://localhost:{server.port}{path}", data=body, method=method
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read() or b"{}")

    try:
        call("POST", "/index/i")
        call("POST", "/index/i/field/f")
        width = holder.n_words * 32
        read = (
            b"Count(Intersect(Row(f=0), Row(f=1)))"
            b" Count(Union(Row(f=0), Row(f=1)))"
        )
        for cols in ([1, 2, 3], [2, 3, 4]):
            blob = roaring.serialize(np.array(
                [r * width + c for r in (0, 1) for c in cols], dtype=np.uint64
            ))
            # the span table is the process's: two readings subtract
            before = call("GET", "/debug/vars")["spans"]["stacks"]["refresh"]
            call("POST", "/index/i/field/f/import-roaring/0", blob)
            answer = call("POST", "/index/i/query", read)["results"]
        assert answer == [4, 4]
        seen = call("GET", "/debug/vars")
        span = seen["spans"]["stacks"]["refresh"]
        cache = seen["serving_cache"]
        assert span["count"] == before["count"] + 1
        assert span["seconds"] > before["seconds"]
        assert cache["stack_incremental"] == 1
        assert cache["stack_refresh_bytes"] == 2 * holder.n_words * 4
        assert cache["stack_refresh_host_bytes"] in (0, 2 * holder.n_words * 4)
        assert cache["stack_refresh_peer_bytes"] == 0
        assert cache["stack_refresh_out_of_place"] == 0
    finally:
        server.close()
