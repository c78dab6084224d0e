"""Two-process jax.distributed test of the REAL serving stack.

Each process boots the framework end to end — Holder -> Executor -> PQL
— owning the shard slice cluster placement would give it (shard % 2 ==
process id, the partition-hash analogue), executes the same queries
through Executor.execute (gram batch pair counts, a general AST tree,
and a BSI Sum), and the per-process partials combine across the
distributed runtime via multihost allgather — the mapReduce reduce step
riding the JAX distributed backend instead of the reference's
HTTP+protobuf (SURVEY §2.4 mapping note; reference executor.go:2454
mapReduce)."""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
import numpy as np

os.environ["JAX_PLATFORMS"] = "cpu"  # two processes, one machine
import jax
jax.config.update("jax_num_cpu_devices", 2)

sys.path.insert(0, os.environ["REPO"])
os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH", "13")
from pilosa_tpu.parallel.mesh import init_multihost

pid = int(sys.argv[1])
mesh = init_multihost(
    coordinator_address=os.environ["COORD"],
    num_processes=2,
    process_id=pid,
)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, len(jax.devices())

from jax.experimental import multihost_utils

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.exec.executor import Executor

# ---- the real serving stack, per process ------------------------------
holder = Holder()
idx = holder.create_index("i")
f = idx.create_field("f")
v = idx.create_field("v", FieldOptions(field_type="int", min_=0, max_=500))

N_SHARDS = 4
width = holder.n_words * 32
rng = np.random.default_rng(42)  # same data on every process
rows = rng.integers(0, 5, size=4000)
cols = rng.integers(0, N_SHARDS * width, size=4000)
vcols = rng.choice(N_SHARDS * width, size=600, replace=False)
vvals = rng.integers(0, 500, size=600)

# ownership: shard % 2 == pid (the placement-hash analogue); each
# process imports and serves ONLY its slice
own = lambda c: (c // width) % 2 == pid
m = own(cols)
f.import_bits(rows[m].astype(np.uint64), cols[m])
mv = own(vcols)
v.import_values(vcols[mv], vvals[mv])

ex = Executor(holder)
my_shards = [s for s in range(N_SHARDS) if s % 2 == pid]

# gram-batched pair counts + a general AST tree + BSI Sum, all through
# Executor.execute on the local shard slice
res = ex.execute(
    "i",
    "Count(Intersect(Row(f=0), Row(f=1)))"
    "Count(Union(Row(f=2), Row(f=3)))"
    "Count(Intersect(Row(f=0), Row(f=1), Row(f=4)))"
    "Sum(field=v)",
    shards=my_shards,
)
partial = np.array(
    [res[0], res[1], res[2], res[3].value, res[3].count], np.int64
)

# reduce across processes over the distributed runtime
all_partials = multihost_utils.process_allgather(partial)
total = all_partials.sum(axis=0)

# ground truth from the full data (both processes know it)
byrow = {}
for r, c in zip(rows.tolist(), cols.tolist()):
    byrow.setdefault(r, set()).add(c)
want = [
    len(byrow[0] & byrow[1]),
    len(byrow[2] | byrow[3]),
    len(byrow[0] & byrow[1] & byrow[4]),
    int(vvals.sum()),
    len(vcols),
]
assert total.tolist() == want, (total.tolist(), want)

# ---- global-mesh device data plane ------------------------------------
# ONE stack sharded across BOTH processes' devices; the gram's reduce is
# an in-program psum riding the distributed backend (DCN across hosts,
# the SURVEY §2.4 mapping of mapReduce's reduce step) — no host-side
# combine at all, every process reads the replicated result.
from jax.sharding import Mesh, PartitionSpec as P
from pilosa_tpu.ops import kernels

R = 5
W = holder.n_words
# each process contributes ONLY its own shards' blocks (order along the
# shard axis is irrelevant to a sum over shards)
mine = sorted(my_shards)
local_block = np.zeros((len(mine), R, W), np.uint32)
for r, c in zip(rows.tolist(), cols.tolist()):
    s, off = divmod(int(c), width)
    if s in mine:
        local_block[mine.index(s), r, off // 32] |= np.uint32(1) << np.uint32(
            off % 32
        )
mesh_g = Mesh(np.array(jax.devices()), ("shards",))
gbits = multihost_utils.host_local_array_to_global_array(
    local_block, mesh_g, P("shards", None, None)
)
assert kernels.mesh_spans_processes(mesh_g)
g = kernels.pair_gram(gbits, list(range(R)))
want_gram = np.array(
    [
        [len(byrow.get(a, set()) & byrow.get(b, set())) for b in range(R)]
        for a in range(R)
    ],
    np.int64,
)
assert np.array_equal(g, want_gram), (g.tolist(), want_gram.tolist())

# gather (row-subset) psum branch
sub = [0, 2, 4]
g_sub = kernels.pair_gram(gbits, sub)
assert np.array_equal(g_sub, want_gram[np.ix_(sub, sub)])

# row counts via in-program psum (replicated result)
rc = kernels.row_counts(gbits)
want_rc = [len(byrow.get(r, set())) for r in range(R)]
assert rc.tolist() == want_rc, (rc.tolist(), want_rc)

# cross gram across two global stacks (reuse the same stack: the
# cross kernel path differs from pair_gram's even when a == b)
xg = kernels.cross_pair_gram(gbits, gbits, sub, [1, 3])
assert np.array_equal(xg, want_gram[np.ix_(sub, [1, 3])])

# ---- r05: the former spanning-mesh declines, now in-program psum ------
import jax.numpy as jnp

# batched pair counts: replicated int64[B] totals (no [B, S] partials)
ras = np.array([0, 2, 1, 3], np.int32)
rbs = np.array([1, 3, 4, 0], np.int32)
pc = kernels.pair_count_batched(gbits, jnp.asarray(ras), jnp.asarray(rbs))
assert pc.ndim == 1 and pc.dtype == np.int64, (pc.shape, pc.dtype)
assert pc.tolist() == [int(want_gram[a, b]) for a, b in zip(ras, rbs)]

# union op exercises the op-parameterized psum kind
pu = kernels.pair_count_batched(
    gbits, jnp.asarray(ras), jnp.asarray(rbs), op="union"
)
want_u = [
    want_rc[a] + want_rc[b] - int(want_gram[a, b]) for a, b in zip(ras, rbs)
]
assert pu.tolist() == want_u, (pu.tolist(), want_u)

# a batch WIDER than the gram lane's row bound (the shape that used to
# raise NotImplementedError) stays on the fast lane across processes.
# GRAM_MAX_ROWS is lowered in-process so the >bound case compiles in
# seconds on the 1-core CI host (a 4096+-step scan program would not);
# the kernel is bound-oblivious, only the batch width matters.
old_gmr = kernels.GRAM_MAX_ROWS
kernels.GRAM_MAX_ROWS = 16
try:
    Bw = kernels.GRAM_MAX_ROWS + 24
    wa_ = np.arange(Bw, dtype=np.int32) % R
    wb_ = (np.arange(Bw, dtype=np.int32) * 3 + 1) % R
    pw = kernels.pair_count_batched(
        gbits, jnp.asarray(wa_), jnp.asarray(wb_)
    )
finally:
    kernels.GRAM_MAX_ROWS = old_gmr
assert pw.shape == (Bw,)
assert pw.tolist() == [int(want_gram[a, b]) for a, b in zip(wa_, wb_)]

# cross-tensor variant (GroupBy's wide lane)
p2 = kernels.pair_count_two_batched(
    gbits, gbits, jnp.asarray(ras), jnp.asarray(rbs)
)
assert p2.ndim == 1
assert p2.tolist() == [int(want_gram[a, b]) for a, b in zip(ras, rbs)]

# filtered TopN: masked row counts psum + host top-k on the replicated
# result — the executor's fast lane for TopN(f, filter=...) across hosts.
# gbits' global shard axis is PROCESS-ordered (proc0's shards then
# proc1's: [0, 2, 1, 3]); the filter must ride the same permutation.
filt = np.zeros((N_SHARDS, W), np.uint32)
for c in sorted(byrow.get(1, set())):
    s, off = divmod(int(c), width)
    filt[s, off // 32] |= np.uint32(1) << np.uint32(off % 32)
shard_perm = [s for p in (0, 1) for s in range(N_SHARDS) if s % 2 == p]
mc = kernels.masked_row_counts(gbits, filt[shard_perm])
want_m = [len(byrow.get(r, set()) & byrow.get(1, set())) for r in range(R)]
assert mc.tolist() == want_m, (mc.tolist(), want_m)
top = sorted(range(R), key=lambda r: (-mc[r], r))[:3]
want_top = sorted(range(R), key=lambda r: (-want_m[r], r))[:3]
assert top == want_top

# compiled-AST count programs on the spanning stack (astbatch r05):
# replicated int64 totals via the in-program chunked psum
from pilosa_tpu.exec import astbatch

sig = ("intersect", ("row", 0), ("row", 0))
slots = np.array([[0, 1], [2, 3], [1, 4], [-1, 2]], np.int32)
tot = astbatch.run_count_batch(sig, (gbits,), slots)
want_t = [int(want_gram[0, 1]), int(want_gram[2, 3]), int(want_gram[1, 4]), 0]
assert tot.tolist() == want_t, (tot.tolist(), want_t)

sig3 = ("union", ("row", 0), ("row", 0), ("row", 0))
tot3 = astbatch.run_count_batch(sig3, (gbits,), np.array([[0, 1, 2]], np.int32))
want_u3 = len(byrow[0] | byrow[1] | byrow[2])
assert tot3.tolist() == [want_u3], (tot3.tolist(), want_u3)

# chunked carry-save path: a larger synthetic stack whose totals are
# declared int32-UNSAFE by shrinking the accumulator limit, forcing
# per-chunk psums combined as uint32 (hi, lo) pairs
S2, R2, W2 = 8, 3, 32
rng2 = np.random.default_rng(7)
full2 = rng2.integers(0, 2**32, size=(S2, R2, W2), dtype=np.uint64).astype(
    np.uint32
)
my_rows = [s for s in range(S2) if s % 2 == pid]
local2 = full2[my_rows]
gbits2 = multihost_utils.host_local_array_to_global_array(
    local2, mesh_g, P("shards", None, None)
)
n_dev = mesh_g.devices.size
old_limit = kernels._GRAM_ACC_LIMIT
# one slice of `chunk` shards/device is safe; the full S2 extent is not
kernels._GRAM_ACC_LIMIT = n_dev * W2 * 32 + 1
try:
    # the shrunk limit must actually make the full extent unsafe, or the
    # four assertions below silently test the plain psum branch
    assert not kernels._gram_int32_safe(S2, W2)
    g2 = kernels.pair_gram(gbits2, list(range(R2)))
    rc2 = kernels.row_counts(gbits2)
    g2_sub = kernels.pair_gram(gbits2, [0, 2])  # chunked gather kind
    x2 = kernels.cross_pair_gram(  # chunked cross kind
        gbits2, gbits2, [0, 2], [1]
    )
    pc_c = kernels.pair_count_batched(  # chunked pair kind (r05)
        gbits2, jnp.asarray([0, 1], np.int32), jnp.asarray([2, 0], np.int32)
    )
    p2_c = kernels.pair_count_two_batched(  # chunked pair2 kind (r05)
        gbits2, gbits2,
        jnp.asarray([0, 1], np.int32), jnp.asarray([2, 0], np.int32),
    )
    filt2 = np.full((S2, W2), 0xFFFFFFFF, np.uint32)
    mc_c = kernels.masked_row_counts(gbits2, filt2)  # chunked masked kind
finally:
    kernels._GRAM_ACC_LIMIT = old_limit
# ground truth from the full array (order along the shard axis differs
# between global layout and full2, but sums are order-invariant)
bits_of = lambda w: np.unpackbits(
    np.ascontiguousarray(w).view(np.uint8), bitorder="little"
)
rows2 = [bits_of(full2[:, r]) for r in range(R2)]
want_g2 = np.array(
    [[int((a & b).sum()) for b in rows2] for a in rows2], np.int64
)
assert np.array_equal(g2, want_g2), (g2.tolist(), want_g2.tolist())
assert rc2.tolist() == [int(a.sum()) for a in rows2]
assert np.array_equal(g2_sub, want_g2[np.ix_([0, 2], [0, 2])])
assert np.array_equal(x2, want_g2[np.ix_([0, 2], [1])])
assert pc_c.tolist() == [int(want_g2[0, 2]), int(want_g2[1, 0])]
assert p2_c.tolist() == [int(want_g2[0, 2]), int(want_g2[1, 0])]
assert mc_c.tolist() == [int(a.sum()) for a in rows2]  # full-filter = rc
print(f"proc{pid} OK {total.tolist()} psum-gram OK", flush=True)
"""


def test_two_process_distributed_executor(tmp_path):
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coord = f"127.0.0.1:{s.getsockname()[1]}"
    s.close()
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = dict(
        os.environ,
        REPO=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        COORD=coord,
        JAX_PLATFORMS="cpu",
    )
    env.pop("XLA_FLAGS", None)  # worker sets its own device count
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=150)
            outs.append(out.decode(errors="replace"))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail("distributed workers hung: " + " | ".join(outs))
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"proc{i} failed:\n{outs[i]}"
    assert "proc0 OK" in outs[0]
    assert "proc1 OK" in outs[1]
