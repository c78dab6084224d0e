"""Full-width (SHARD_WIDTH=2^20) correctness check, run as a SUBPROCESS
by tests/test_fullwidth.py — the package reads PILOSA_TPU_SHARD_WIDTH at
import time, so the regular suite's 2^14 conftest pin can't be changed
in-process.  Covers the paths whose shape thresholds the small-width
suite never crosses: real-width import/WAL replay, capacity growth,
host-tier pair counts, gram int32-overflow chunking, and the psum
carry-save mesh reduce.  Exits non-zero on any mismatch."""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

assert os.environ.get("PILOSA_TPU_SHARD_WIDTH") == "20", "run via test_fullwidth"

import numpy as np
import jax

from pilosa_tpu.shardwidth import SHARD_WIDTH, SHARD_WORDS

assert SHARD_WIDTH == 1 << 20 and SHARD_WORDS == 32768


def check_import_and_wal():
    """Vectorized import + WAL replay at real width (positions use the
    full 2^20 column space; the sort-unique key math must not wrap)."""
    from pilosa_tpu.core.fragment import Fragment
    from pilosa_tpu.storage.fragmentfile import FragmentFile

    rng = np.random.default_rng(1)
    n = 200_000
    rows = rng.integers(0, 48, size=n).astype(np.uint64)
    cols = rng.integers(0, SHARD_WIDTH, size=n)
    with tempfile.TemporaryDirectory() as d:
        frag = Fragment(n_words=SHARD_WORDS)
        store = FragmentFile(frag, os.path.join(d, "frag"))
        store.open()
        frag.store = store
        changed = frag.import_bits(rows, cols)
        want_positions = {
            (int(r), int(c)) for r, c in zip(rows, cols)
        }
        assert changed == len(want_positions), (changed, len(want_positions))
        assert frag.total_count() == len(want_positions)
        # maintained counts must equal a recount at this width
        _, counts = frag.row_counts()
        carried = counts.copy()
        frag._counts = None
        _, recounted = frag.row_counts()
        assert np.array_equal(carried, recounted)
        # clear half, then reopen from snapshot+WAL
        frag.import_bits(rows[: n // 2], cols[: n // 2], clear=True)
        total = frag.total_count()
        store.close()
        frag2 = Fragment(n_words=SHARD_WORDS)
        store2 = FragmentFile(frag2, os.path.join(d, "frag"))
        store2.open()
        assert frag2.total_count() == total, (frag2.total_count(), total)
        store2.close()
    print("ok import+wal")


def check_capacity_growth():
    """Row-capacity doubling at real width (each grow reallocates
    [cap, 32768] words and re-uploads on next device query)."""
    from pilosa_tpu.core.fragment import Fragment

    frag = Fragment(n_words=SHARD_WORDS)
    caps = set()
    for r in range(70):  # crosses several power-of-two capacities
        frag.set_bit(r, (r * 131071) % SHARD_WIDTH)
        caps.add(frag.capacity)
    assert frag.capacity >= 70 and len(caps) >= 3, (frag.capacity, caps)
    for r in range(70):
        assert frag.get_bit(r, (r * 131071) % SHARD_WIDTH)
    print("ok capacity growth")


def check_host_tier_and_executor():
    """Executor host-tier pair counts + TopN at real width vs ground
    truth (native kernels walk 32768-word rows)."""
    from pilosa_tpu.core.holder import Holder
    from pilosa_tpu.exec.executor import Executor

    h = Holder()
    h.create_index("i")
    h.index("i").create_field("f")
    ex = Executor(h)
    ex._PAIR_SINGLE_WARM = 10**9  # stay on the host tier
    rng = np.random.default_rng(2)
    sets = {}
    for row in (1, 2):
        cols = rng.choice(2 * SHARD_WIDTH, size=400, replace=False)
        sets[row] = set(int(c) for c in cols)
        q = " ".join(f"Set({int(c)}, f={row})" for c in sorted(sets[row]))
        ex.execute("i", q)
    for name, want in [
        ("Intersect", len(sets[1] & sets[2])),
        ("Union", len(sets[1] | sets[2])),
        ("Difference", len(sets[1] - sets[2])),
        ("Xor", len(sets[1] ^ sets[2])),
    ]:
        got = ex.execute("i", f"Count({name}(Row(f=1), Row(f=2)))")[0]
        assert got == want, (name, got, want)
    top = ex.execute("i", "TopN(f, n=2)")[0]
    want_top = sorted(
        ((r, len(s)) for r, s in sets.items()),
        key=lambda kv: (-kv[1], kv[0]),
    )
    assert [(p.id, p.count) for p in top] == want_top
    print("ok host tier + executor")


def check_gram_chunking():
    """The int32-overflow chunked gram at REAL width.  Crossing the true
    limit needs >2048 full-width shards (2^31 bits per row pair), so the
    limit is lowered to force the chunked path over genuine 32768-word
    rows — the chunk math itself then runs with production word counts."""
    from pilosa_tpu.ops import kernels

    rng = np.random.default_rng(3)
    S, R = 6, 5
    bits = rng.integers(0, 2**32, size=(S, R, SHARD_WORDS), dtype=np.uint32)
    want = np.zeros((R, R), dtype=np.int64)
    for a in range(R):
        for b in range(R):
            want[a, b] = int(
                np.bitwise_count(bits[:, a] & bits[:, b]).sum()
            )
    old = kernels._GRAM_ACC_LIMIT
    try:
        # 2 shards per chunk at W=32768
        kernels._GRAM_ACC_LIMIT = 2 * SHARD_WORDS * 32
        assert not kernels._gram_int32_safe(S, SHARD_WORDS)
        g = kernels.pair_gram(jax.numpy.asarray(bits), list(range(R)))
        assert g is not None
        assert np.array_equal(np.asarray(g).astype(np.int64), want)
    finally:
        kernels._GRAM_ACC_LIMIT = old
    print("ok gram chunking")


def check_psum_mesh_reduce():
    """In-program psum gram reduce over an 8-device mesh at real width
    (the multi-host reduce mode, SURVEY §2.4) vs host ground truth."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pilosa_tpu.ops import kernels

    devs = jax.devices()
    assert len(devs) >= 8, f"need 8 virtual devices, have {len(devs)}"
    mesh = Mesh(np.array(devs[:8]), ("shards",))
    rng = np.random.default_rng(4)
    S, R = 8, 4
    bits = rng.integers(0, 2**32, size=(S, R, SHARD_WORDS), dtype=np.uint32)
    dev = jax.device_put(bits, NamedSharding(mesh, P("shards", None, None)))
    fn = kernels._gram_mesh_fn(mesh, "shards", False, True)
    g = np.asarray(jax.block_until_ready(fn(dev))).astype(np.int64)
    want = np.zeros((R, R), dtype=np.int64)
    for a in range(R):
        for b in range(R):
            want[a, b] = int(np.bitwise_count(bits[:, a] & bits[:, b]).sum())
    assert np.array_equal(g, want), "psum mesh gram mismatch"
    # carry-save chunked psum (the past-int32 multi-host reduce): lower
    # the accumulator limit so chunk == 1 shard/device at real width,
    # then check the hi/lo recombination against the same ground truth
    old = kernels._GRAM_ACC_LIMIT
    try:
        kernels._GRAM_ACC_LIMIT = 8 * SHARD_WORDS * 32
        chunk = kernels._psum_chunk_size(mesh, SHARD_WORDS)
        assert chunk == 1, chunk
        cfn = kernels._psum_chunked_fn(mesh, "shards", "gram", chunk)
        hi, lo = jax.block_until_ready(cfn(dev))
        got = kernels._hi_lo_total(hi, lo)
        assert np.array_equal(got, want), "carry-save psum gram mismatch"
    finally:
        kernels._GRAM_ACC_LIMIT = old
    print("ok psum mesh reduce + carry-save chunks")


if __name__ == "__main__":
    check_import_and_wal()
    check_capacity_growth()
    check_host_tier_and_executor()
    check_gram_chunking()
    check_psum_mesh_reduce()
    print("FULLWIDTH ALL OK")
    sys.exit(0)
