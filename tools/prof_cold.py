"""Profile the cold sequential Count(Intersect) path exactly as bench.py
measures it (full TPU-size index, host latency tier), on the CPU
platform — the host tier never touches the device, so the numbers
transfer to the driver's bench run."""
import os
import sys
import time

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.exec.executor import Executor

S, R, W = 160, 64, 32768
rng = np.random.default_rng(3)
B = 64
ras = rng.integers(0, R, size=B).astype(np.int64)
rbs = rng.integers(0, R, size=B).astype(np.int64)

h = Holder(n_words=W)
idx = h.create_index("seq")
f = idx.create_field("f")
v = f.create_view_if_not_exists(VIEW_STANDARD)
seq_rng = np.random.default_rng(13)
t0 = time.perf_counter()
for s in range(S):
    words = seq_rng.integers(0, 2**32, size=(R, W), dtype=np.uint32) & \
        seq_rng.integers(0, 2**32, size=(R, W), dtype=np.uint32)
    frag = v.create_fragment_if_not_exists(s)
    for r in range(R):
        frag.set_row_words(r, words[r])
print(f"setup: {time.perf_counter()-t0:.1f}s")

ex = Executor(h)
ex._PAIR_SINGLE_WARM = 10**9
q0 = f"Count(Intersect(Row(f={int(ras[0])}), Row(f={int(rbs[0])})))"
ex.execute("seq", q0)

from pilosa_tpu.ops import _hostops
print("native hostops:", _hostops.load() is not None)

n_seq = 30
t0 = time.perf_counter()
for i in range(n_seq):
    ex.execute(
        "seq",
        f"Count(Intersect(Row(f={int(ras[i % B])}), Row(f={int(rbs[i % B])})))",
    )
dt = time.perf_counter() - t0
print(f"cold execute: {dt/n_seq*1e3:.2f} ms/q  ({n_seq/dt:.1f} qps)")

# phase breakdown -------------------------------------------------------
from pilosa_tpu.pql.parser import parse

t0 = time.perf_counter()
for i in range(n_seq):
    parse(f"Count(Intersect(Row(f={int(ras[i % B])}), Row(f={int(rbs[i % B])})))")
print(f"parse only:   {(time.perf_counter()-t0)/n_seq*1e3:.2f} ms/q")

shard_list = list(range(S))
view = idx.field("f").view(VIEW_STANDARD)
t0 = time.perf_counter()
for i in range(n_seq):
    ex._host_pair_count(view, int(ras[i % B]), int(rbs[i % B]), "intersect", shard_list)
print(f"host_pair_count only: {(time.perf_counter()-t0)/n_seq*1e3:.2f} ms/q")

# raw native call, addresses precomputed once
frags = [view.fragment(s) for s in shard_list]
n_words = frags[0].n_words
t0 = time.perf_counter()
for i in range(n_seq):
    ra, rb = int(ras[i % B]), int(rbs[i % B])
    bases = np.array([f_._host.__array_interface__["data"][0] for f_ in frags], dtype=np.uint64)
    sa = np.array([f_._slot_of[ra] for f_ in frags], dtype=np.uint64)
    sb = np.array([f_._slot_of[rb] for f_ in frags], dtype=np.uint64)
    stride = np.uint64(n_words * 4)
    _hostops.pair_count_addrs(bases + sa * stride, bases + sb * stride, n_words, "intersect")
print(f"raw native:   {(time.perf_counter()-t0)/n_seq*1e3:.2f} ms/q")

# numpy baseline as bench.py does it (cache-hot, scaled from 10 shards)
sub = np.stack([frags[s]._host[frags[s]._slot_of[0]] for s in range(10)])
suba = np.empty((10, n_words), dtype=np.uint32)
subb = np.empty((10, n_words), dtype=np.uint32)
qa, qb = int(ras[0]), int(rbs[0])
for s in range(10):
    suba[s] = frags[s]._host[frags[s]._slot_of[qa]]
    subb[s] = frags[s]._host[frags[s]._slot_of[qb]]
times = []
for _ in range(5):
    t0 = time.perf_counter()
    int(np.bitwise_count(suba & subb).sum())
    times.append(time.perf_counter() - t0)
print(f"numpy baseline (scaled x16, best of 5): {min(times)*16*1e3:.2f} ms/q")

if "--cprofile" in sys.argv:
    import cProfile
    import pstats

    pr = cProfile.Profile()
    pr.enable()
    for i in range(n_seq):
        ex.execute(
            "seq",
            f"Count(Intersect(Row(f={int(ras[i % B])}), Row(f={int(rbs[i % B])})))",
        )
    pr.disable()
    pstats.Stats(pr).sort_stats("cumulative").print_stats(25)
