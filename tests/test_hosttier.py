"""Host latency-tier serving: lone cold reads answered from fragment
host mirrors via the fused native kernels (native/hostops.cpp), while
the batched/warm paths keep the device throughput tier.  Reference
behavior being matched: a single Count(op(Row,Row)) through
executor.go:1792 + roaring.go:568."""

import numpy as np
import pytest

from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.ops import _hostops, bitops
from pilosa_tpu.shardwidth import SHARD_WIDTH

OPS = ["intersect", "union", "difference", "xor"]


def _np_op(a, b, op):
    return {
        "intersect": a & b,
        "union": a | b,
        "difference": a & ~b,
        "xor": a ^ b,
    }[op]


class TestHostOps:
    def test_pair_count_matches_numpy(self):
        rng = np.random.default_rng(1)
        for n in (1, 7, 64, 513):  # odd sizes exercise the uint32 tail
            a = rng.integers(0, 2**32, size=n, dtype=np.uint32)
            b = rng.integers(0, 2**32, size=n, dtype=np.uint32)
            for op in OPS:
                want = int(np.bitwise_count(_np_op(a, b, op)).sum())
                assert _hostops.pair_count(a, b, op) == want
                assert np.array_equal(
                    _hostops.pair_op(a, b, op), _np_op(a, b, op)
                )

    def test_popcount_matches_numpy(self):
        rng = np.random.default_rng(2)
        for n in (1, 33, 1024, 4097):
            a = rng.integers(0, 2**32, size=n, dtype=np.uint32)
            assert _hostops.popcount(a) == int(np.bitwise_count(a).sum())

    def test_numpy_fallback_parity(self, monkeypatch):
        """The PILOSA_TPU_NO_NATIVE path must answer identically."""
        rng = np.random.default_rng(3)
        a = rng.integers(0, 2**32, size=100, dtype=np.uint32)
        b = rng.integers(0, 2**32, size=100, dtype=np.uint32)
        native = [_hostops.pair_count(a, b, op) for op in OPS]
        monkeypatch.setattr(_hostops, "load", lambda: None)
        fallback = [_hostops.pair_count(a, b, op) for op in OPS]
        assert native == fallback
        assert _hostops.popcount(a) == int(np.bitwise_count(a).sum())

    def test_shift_row_host_matches_device(self):
        rng = np.random.default_rng(4)
        words = rng.integers(0, 2**32, size=64, dtype=np.uint32)
        for n in (0, 1, 5, 31, 32, 33, 64 * 32 + 5):
            host = bitops.shift_row_host(words, n)
            dev = np.asarray(bitops.shift_row(words, n))
            assert np.array_equal(host, dev), n


class TestFragmentPairCount:
    def test_ops_and_missing_rows(self):
        frag = Fragment(n_words=8)
        rng = np.random.default_rng(5)
        a = rng.integers(0, 2**32, size=8, dtype=np.uint32)
        b = rng.integers(0, 2**32, size=8, dtype=np.uint32)
        frag.set_row_words(1, a)
        frag.set_row_words(2, b)
        for op in OPS:
            want = int(np.bitwise_count(_np_op(a, b, op)).sum())
            assert frag.row_pair_count(1, 2, op) == want
        ca = int(np.bitwise_count(a).sum())
        # absent second operand == zero row
        assert frag.row_pair_count(1, 9, "intersect") == 0
        assert frag.row_pair_count(1, 9, "union") == ca
        assert frag.row_pair_count(1, 9, "difference") == ca
        assert frag.row_pair_count(1, 9, "xor") == ca
        # absent first operand
        assert frag.row_pair_count(9, 1, "intersect") == 0
        assert frag.row_pair_count(9, 1, "union") == ca
        assert frag.row_pair_count(9, 1, "difference") == 0
        assert frag.row_pair_count(9, 1, "xor") == ca
        # both absent
        assert frag.row_pair_count(8, 9, "union") == 0


class TestExecutorHostTier:
    @pytest.fixture()
    def ex(self):
        h = Holder()
        h.create_index("i")
        return Executor(h)

    def _seed(self, ex, n_shards=3):
        """Two rows spread over n_shards shards; returns their column
        sets."""
        idx = ex.holder.index("i")
        idx.create_field("f")
        rng = np.random.default_rng(7)
        sets = {}
        for row in (1, 2):
            cols = rng.choice(
                n_shards * SHARD_WIDTH, size=200, replace=False
            )
            sets[row] = set(int(c) for c in cols)
            q = " ".join(f"Set({int(c)}, f={row})" for c in sorted(sets[row]))
            ex.execute("i", q)
        return sets

    def test_cold_pair_counts_exact(self, ex):
        sets = self._seed(ex)
        want = {
            "Intersect": len(sets[1] & sets[2]),
            "Union": len(sets[1] | sets[2]),
            "Difference": len(sets[1] - sets[2]),
            "Xor": len(sets[1] ^ sets[2]),
        }
        for name, n in want.items():
            got = ex.execute("i", f"Count({name}(Row(f=1), Row(f=2)))")[0]
            assert got == n, name

    def test_cold_single_row_count(self, ex):
        sets = self._seed(ex)
        assert ex.execute("i", "Count(Row(f=1))")[0] == len(sets[1])
        assert ex.execute("i", "Count(Row(f=99))")[0] == 0

    def test_host_tier_matches_warm_gram_path(self, ex):
        """The same query answered cold (host tier) and warm (device
        gram) must agree — serve repeatedly to cross the warm
        threshold."""
        sets = self._seed(ex)
        q = "Count(Intersect(Row(f=1), Row(f=2)))"
        cold = ex.execute("i", q)[0]
        for _ in range(ex._PAIR_SINGLE_WARM + 2):
            warm = ex.execute("i", q)[0]
        assert warm == cold == len(sets[1] & sets[2])

    def test_row_segments_are_host_arrays(self, ex):
        self._seed(ex)
        row = ex.execute("i", "Row(f=1)")[0]
        assert row.segments
        assert all(
            isinstance(seg, np.ndarray) for seg in row.segments.values()
        )

    def test_threaded_fanout_matches_serial(self, ex, monkeypatch):
        """Force the thread-pool fan-out (multi-core policy) and check
        it sums identically to the serial path."""
        sets = self._seed(ex, n_shards=5)
        import pilosa_tpu.exec.executor as exmod

        monkeypatch.setattr(exmod.os, "cpu_count", lambda: 4)
        monkeypatch.setattr(Executor, "_HOST_FANOUT_CHUNK", 1)
        got = ex.execute("i", "Count(Union(Row(f=1), Row(f=2)))")[0]
        assert got == len(sets[1] | sets[2])

    def test_mixed_host_device_segments(self, ex):
        """Intersect of a host-tier Row with a BSI condition row (device
        tier) still counts correctly."""
        from pilosa_tpu.core.field import FieldOptions

        idx = ex.holder.index("i")
        idx.create_field("f")
        idx.create_field(
            "v", FieldOptions(field_type="int", min_=0, max_=1000)
        )
        for c, val in [(1, 10), (2, 500), (3, 900)]:
            ex.execute("i", f"Set({c}, f=1) Set({c}, v={val})")
        got = ex.execute("i", "Count(Intersect(Row(f=1), Row(v < 600)))")[0]
        assert got == 2


class TestBSIHostTier:
    """Lone cold BSI predicates run the SAME ops/bsi kernels on the
    in-process CPU backend over the fragment host mirrors (no device
    stack upload); repeat demand crosses _BSI_SINGLE_WARM and promotes
    to the stacked device path with identical answers."""

    @pytest.fixture()
    def exv(self):
        from pilosa_tpu.core.field import FieldOptions

        h = Holder()
        idx = h.create_index("i")
        idx.create_field(
            "v", FieldOptions(field_type="int", min_=-500, max_=500)
        )
        # rescache off: warm-promotion counts repeat demand per query,
        # and a result-cache hit would never reach the warm counter
        ex = Executor(h, rescache_entries=0)
        rng = np.random.default_rng(23)
        vals = {}
        width = h.n_words * 32
        writes = []
        for col in rng.choice(3 * width, size=180, replace=False):
            v = int(rng.integers(-500, 500))
            vals[int(col)] = v
            writes.append(f"Set({int(col)}, v={v})")
        ex.execute("i", " ".join(writes))
        return ex, vals

    def test_cold_predicates_exact_without_stack(self, exv):
        ex, vals = exv
        field = ex.holder.index("i").field("v")
        checks = [
            ("Row(v < 100)", {c for c, v in vals.items() if v < 100}),
            ("Row(v >= -50)", {c for c, v in vals.items() if v >= -50}),
            ("Row(v == 7)", {c for c, v in vals.items() if v == 7}),
            ("Row(v != 7)", {c for c, v in vals.items() if v != 7}),
            ("Row(-10 < v < 60)", {c for c, v in vals.items() if -10 < v < 60}),
        ]
        # the Nth lone query crosses the warm threshold, so only the
        # first N-1 are guaranteed cold
        for q, want in checks[: ex._BSI_SINGLE_WARM - 1]:
            got = set(ex.execute("i", q)[0].columns().tolist())
            assert got == want, q
        # the cold queries above must NOT have built the device stack
        assert not ex.stacks.bsi_cached(
            field, ex._shards_for(ex.holder.index("i"), None)
        )

    def test_warm_promotion_matches_host_answers(self, exv):
        ex, vals = exv
        q = "Count(Row(v < 0))"
        want = sum(1 for v in vals.values() if v < 0)
        # cold host-tier answers, then past the threshold the stacked
        # device path takes over — same result throughout
        for _ in range(ex._BSI_SINGLE_WARM + 3):
            assert ex.execute("i", q)[0] == want
        field = ex.holder.index("i").field("v")
        assert ex.stacks.bsi_cached(
            field, ex._shards_for(ex.holder.index("i"), None)
        )

    def test_write_between_cold_predicates_is_visible(self, exv):
        ex, vals = exv
        q = "Count(Row(v > 400))"
        before = ex.execute("i", q)[0]
        free = max(vals) + 17
        ex.execute("i", f"Set({free}, v=450)")
        assert ex.execute("i", q)[0] == before + 1
