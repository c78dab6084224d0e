"""Pallas kernel tests (interpret mode on the CPU test mesh).

Validates the fused streaming kernels against numpy bit math, the way the
reference validates its per-container-type op matrix against simple maps
(reference roaring/roaring_internal_test.go).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from pilosa_tpu.ops import kernels


def _rand_bits(rng, s, r, w):
    return rng.integers(0, 2**32, size=(s, r, w), dtype=np.uint64).astype(np.uint32)


OPS_NP = {
    "intersect": lambda a, b: a & b,
    "union": lambda a, b: a | b,
    "difference": lambda a, b: a & ~b,
    "xor": lambda a, b: a ^ b,
}


@pytest.mark.parametrize("op", ["intersect", "union", "difference", "xor"])
def test_pair_count_batched_matches_numpy(op):
    rng = np.random.default_rng(11)
    S, R, W = 3, 7, 256
    bits = _rand_bits(rng, S, R, W)
    B = 9
    ras = rng.integers(0, R, size=B).astype(np.int32)
    rbs = rng.integers(0, R, size=B).astype(np.int32)

    got = np.asarray(
        kernels.pair_count_batched_xla(
            jnp.asarray(bits), jnp.asarray(ras), jnp.asarray(rbs), op=op
        )
    ).astype(np.int64).sum(axis=1)
    want = np.array(
        [
            np.bitwise_count(OPS_NP[op](bits[:, ra], bits[:, rb])).sum()
            for ra, rb in zip(ras, rbs)
        ],
        dtype=np.int64,
    )
    assert got.tolist() == want.tolist()


def test_pair_count_word_blocking():
    # W larger than one gram word-block forces block accumulation.
    rng = np.random.default_rng(3)
    S, R, W = 2, 4, 2 * kernels._GRAM_WB
    bits = _rand_bits(rng, S, R, W)
    ras = np.asarray([1, 3], np.int32)
    rbs = np.asarray([2, 0], np.int32)
    g = kernels.pair_gram(jnp.asarray(bits), sorted({1, 3, 2, 0}))
    got = [int(g[ra, rb]) for ra, rb in zip(ras, rbs)]
    want = [
        int(np.bitwise_count(bits[:, ra] & bits[:, rb]).sum())
        for ra, rb in zip(ras, rbs)
    ]
    assert got == want


@pytest.mark.parametrize("r", [1, 5, 8, 13])
def test_row_counts_matches_numpy(r):
    rng = np.random.default_rng(r)
    S, W = 3, 128
    bits = _rand_bits(rng, S, r, W)
    got = np.asarray(kernels.row_counts_xla(jnp.asarray(bits)))
    want = np.bitwise_count(bits).sum(axis=(0, 2))
    assert got.tolist() == want.tolist()


def test_dispatch_wrappers_run():
    rng = np.random.default_rng(2)
    bits = jnp.asarray(_rand_bits(rng, 2, 3, 128))
    ras = jnp.asarray([0, 2], jnp.int32)
    rbs = jnp.asarray([1, 1], jnp.int32)
    assert kernels.pair_count_batched(bits, ras, rbs).shape == (2, 2)
    assert kernels.row_counts(bits).shape == (3,)


def test_row_counts_per_shard_matches_numpy():
    rng = np.random.default_rng(21)
    bits = _rand_bits(rng, 3, 9, 256)
    want = np.bitwise_count(bits).sum(axis=2)
    got_x = np.asarray(kernels.row_counts_per_shard_xla(jnp.asarray(bits)))
    assert got_x.tolist() == want.tolist()


def test_overflow_safe_paths(monkeypatch):
    """When totals could pass int32, dispatchers switch to per-shard
    partials + host int64 math and still return correct values."""
    rng = np.random.default_rng(22)
    bits = _rand_bits(rng, 2, 5, 128)
    want = np.bitwise_count(bits).sum(axis=(0, 2))
    monkeypatch.setattr(kernels, "_int32_safe", lambda b: False)
    rc = kernels.row_counts(jnp.asarray(bits))
    assert rc.dtype == np.int64
    assert rc.tolist() == want.tolist()
    counts, slots = kernels.topn_counts(jnp.asarray(bits), 3)
    order = np.argsort(-want, kind="stable")[:3]
    assert list(slots) == list(order)
    assert list(counts) == [int(want[s]) for s in order]

# ---------------------------------------------------------------------------
# MXU gram path
# ---------------------------------------------------------------------------


def test_gram_matrix_all_pairs():
    rng = np.random.default_rng(21)
    S, R, W = 3, 6, 128
    bits = _rand_bits(rng, S, R, W)
    g = np.asarray(kernels.gram_matrix_xla(jnp.asarray(bits)))
    for i in range(R):
        for j in range(R):
            want = int(np.bitwise_count(bits[:, i] & bits[:, j]).sum())
            assert g[i, j] == want


def test_gram_gather_subset():
    rng = np.random.default_rng(22)
    S, R, W = 2, 9, 256
    bits = _rand_bits(rng, S, R, W)
    idx = np.array([7, 1, 4], np.int32)
    g = np.asarray(kernels.gram_gather_xla(jnp.asarray(bits), jnp.asarray(idx)))
    for a, ia in enumerate(idx):
        for b, ib in enumerate(idx):
            want = int(np.bitwise_count(bits[:, ia] & bits[:, ib]).sum())
            assert g[a, b] == want


def test_pair_gram_full_and_subset_and_decline():
    rng = np.random.default_rng(23)
    S, R, W = 2, 8, 128
    bits = jnp.asarray(_rand_bits(rng, S, R, W))
    # full-row gram
    g = kernels.pair_gram(bits, list(range(R)))
    assert g is not None and g.shape == (R, R) and g.dtype == np.int64
    # subset
    gs = kernels.pair_gram(bits, [3, 5])
    assert gs is not None and gs.shape == (2, 2)
    assert gs[0, 1] == g[3, 5] and gs[0, 0] == g[3, 3]
    # declines on very wide row sets
    assert kernels.pair_gram(bits, list(range(kernels.GRAM_MAX_ROWS + 1))) is None
    assert kernels.pair_gram(bits, []) is None


@pytest.mark.parametrize("op", ["intersect", "union", "difference", "xor"])
def test_pair_counts_from_gram_formulas(op):
    rng = np.random.default_rng(24)
    S, R, W = 2, 6, 64
    bits = _rand_bits(rng, S, R, W)
    g = kernels.pair_gram(jnp.asarray(bits), list(range(R)))
    B = 12
    pa = rng.integers(0, R, size=B)
    pb = rng.integers(0, R, size=B)
    got = kernels.pair_counts_from_gram(g, pa, pb, op)
    want = np.array(
        [
            np.bitwise_count(OPS_NP[op](bits[:, a], bits[:, b])).sum()
            for a, b in zip(pa, pb)
        ],
        dtype=np.int64,
    )
    assert got.tolist() == want.tolist()


def test_pair_gram_sharded_matches_single():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs multi-device mesh")
    rng = np.random.default_rng(25)
    n = len(devs)
    S, R, W = 2 * n, 5, 128
    bits = _rand_bits(rng, S, R, W)
    mesh = Mesh(np.array(devs), ("shards",))
    dev = jax.device_put(bits, NamedSharding(mesh, P("shards", None, None)))
    g_sharded = kernels.pair_gram(dev, list(range(R)))
    g_single = kernels.pair_gram(jnp.asarray(bits), list(range(R)))
    assert g_sharded.tolist() == g_single.tolist()
    gs2 = kernels.pair_gram(dev, [1, 3])
    assert gs2[0, 1] == g_single[1, 3]


def test_pair_gram_chunked_when_int32_unsafe(monkeypatch):
    """Giant single-device indexes take the shard-chunked host-int64 path
    (device int64 is unavailable without jax_enable_x64)."""
    rng = np.random.default_rng(26)
    S, R, W = 6, 4, 64
    bits = _rand_bits(rng, S, R, W)
    want = kernels.pair_gram(jnp.asarray(bits), list(range(R)))
    # shrink the accumulator limit so this small shape is "unsafe" and
    # must chunk (2 shards per chunk here)
    monkeypatch.setattr(kernels, "_GRAM_ACC_LIMIT", 2 * W * 32)
    got = kernels.pair_gram(jnp.asarray(bits), list(range(R)))
    assert got.tolist() == want.tolist()
    got_sub = kernels.pair_gram(jnp.asarray(bits), [2, 0])
    assert got_sub[0, 1] == want[2, 0]


def test_cross_gram_matches_pairwise():
    rng = np.random.default_rng(31)
    S, Ra, Rb, W = 3, 4, 5, 128
    a = _rand_bits(rng, S, Ra, W)
    b = _rand_bits(rng, S, Rb, W)
    g = np.asarray(kernels.cross_gram_xla(jnp.asarray(a), jnp.asarray(b)))
    for i in range(Ra):
        for j in range(Rb):
            want = int(np.bitwise_count(a[:, i] & b[:, j]).sum())
            assert g[i, j] == want


def test_cross_pair_gram_subsets_and_chunking(monkeypatch):
    rng = np.random.default_rng(32)
    S, Ra, Rb, W = 5, 6, 4, 64
    a = jnp.asarray(_rand_bits(rng, S, Ra, W))
    b = jnp.asarray(_rand_bits(rng, S, Rb, W))
    full = np.asarray(kernels.cross_gram_xla(a, b))
    got = kernels.cross_pair_gram(a, b, [5, 0], [3, 1, 2])
    assert got.shape == (2, 3)
    assert got[0, 0] == full[5, 3] and got[1, 2] == full[0, 2]
    # int32-unsafe shapes chunk the shard axis with host int64 recombine
    monkeypatch.setattr(kernels, "_GRAM_ACC_LIMIT", 2 * W * 32)
    got2 = kernels.cross_pair_gram(a, b, [5, 0], [3, 1, 2])
    assert got2.tolist() == got.tolist()
    # declines on over-wide subsets
    assert kernels.cross_pair_gram(
        a, b, list(range(kernels.GRAM_MAX_ROWS + 1)), [0]
    ) is None


def test_cross_pair_gram_sharded():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs multi-device mesh")
    rng = np.random.default_rng(33)
    n = len(devs)
    S, Ra, Rb, W = 2 * n, 3, 4, 128
    a = _rand_bits(rng, S, Ra, W)
    b = _rand_bits(rng, S, Rb, W)
    mesh = Mesh(np.array(devs), ("shards",))
    spec = NamedSharding(mesh, P("shards", None, None))
    ad = jax.device_put(a, spec)
    bd = jax.device_put(b, spec)
    got = kernels.cross_pair_gram(ad, bd, [0, 2], [1, 3])
    full = np.asarray(kernels.cross_gram_xla(jnp.asarray(a), jnp.asarray(b)))
    assert got[0, 0] == full[0, 1] and got[1, 1] == full[2, 3]


def test_combo_counts_gram_matches_scan():
    rng = np.random.default_rng(34)
    C, S, Rl, R, W = 8, 3, 5, 6, 64
    prefix = jnp.asarray(_rand_bits(rng, C, S, W))
    bits = jnp.asarray(_rand_bits(rng, S, R, W))
    idx = jnp.asarray(np.array([0, 2, 4, 5, 1], np.int32))
    got = kernels.combo_counts_gram(prefix, bits, idx)
    assert got is not None
    want = (
        np.asarray(kernels.combo_counts(prefix, bits, idx))
        .astype(np.int64)
        .sum(axis=2)
    )
    assert got.tolist() == want.tolist()
    # declines on tiny levels (unpack would not pay off)
    assert kernels.combo_counts_gram(prefix[:2], bits, idx[:2]) is None


def test_combo_counts_gram_declines_oversized_prefix():
    rng = np.random.default_rng(35)
    S, R, W = 2, 4, 64
    bits = jnp.asarray(_rand_bits(rng, S, R, W))
    big_c = kernels.GRAM_MAX_ROWS + 1
    # shape-only check: a too-wide prefix must decline before any device
    # work, so a zeros placeholder suffices
    prefix = jnp.zeros((big_c, S, W), jnp.uint32)
    assert kernels.combo_counts_gram(prefix, bits, jnp.arange(4)) is None


class TestFusedGramPallas:
    """The fused unpack+matmul Pallas gram must be bit-identical to the
    XLA scan (it replaces it by default on TPU; interpret mode covers
    the kernel body on CPU)."""

    def test_pallas_gram_matches_xla(self):
        from pilosa_tpu.ops import kernels
        import jax.numpy as jnp
        import jax

        rng = np.random.default_rng(13)
        S, R, W = 9, 16, 256  # S=9 -> sb divisor 3 (no pad path exists)
        bits = jnp.asarray(
            rng.integers(0, 2**32, size=(S, R, W), dtype=np.uint64).astype(
                np.uint32
            )
        )
        want = np.asarray(kernels.gram_matrix_xla(bits))
        got = np.asarray(
            kernels._gram_matrix_pallas(
                bits, sb=kernels._gram_pallas_sb(bits.shape[0]), wb=128
            )
        )
        assert np.array_equal(got, want)

    def test_dispatcher_falls_back_off_tpu(self):
        from pilosa_tpu.ops import kernels
        import jax.numpy as jnp

        rng = np.random.default_rng(13)
        bits = jnp.asarray(
            rng.integers(0, 2**32, size=(4, 8, 128), dtype=np.uint64).astype(
                np.uint32
            )
        )
        want = np.asarray(kernels.gram_matrix_xla(bits))
        assert np.array_equal(np.asarray(kernels.gram_matrix(bits)), want)
        assert np.array_equal(
            np.asarray(kernels.gram_matrix_traced(bits)), want
        )
        idx = jnp.asarray(np.array([1, 3, 4, 1], np.int32))
        assert np.array_equal(
            np.asarray(kernels.gram_gather(bits, idx)),
            np.asarray(kernels.gram_gather_xla(bits, idx)),
        )

    def test_wb_survives_non_power_of_two_rows(self):
        """Regression: a non-power-of-two row count collapsed the word
        block to 1-2 and silently disabled the fused kernel."""
        from pilosa_tpu.ops import kernels

        for R in (48, 96, 160, 1000):
            assert kernels._gram_pallas_wb(R, 32768) >= 128, R
        # and the block actually respects the VMEM budget
        for R in (8, 48, 1024):
            wb = kernels._gram_pallas_wb(R, 32768)
            assert R * wb * 32 <= kernels._GRAM_PALLAS_UNPACK_BYTES

    def test_pallas_gram_non_power_of_two_rows_matches(self):
        from pilosa_tpu.ops import kernels
        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        S, R, W = 3, 12, 256
        bits = jnp.asarray(
            rng.integers(0, 2**32, size=(S, R, W), dtype=np.uint64).astype(
                np.uint32
            )
        )
        want = np.asarray(kernels.gram_matrix_xla(bits))
        got = np.asarray(
            kernels._gram_matrix_pallas(
                bits, sb=kernels._gram_pallas_sb(bits.shape[0]), wb=128
            )
        )
        assert np.array_equal(got, want)

    def test_pallas_cross_gram_matches_xla(self):
        """The fused cross gram (2-level GroupBy path, default ON on
        TPU) must be bit-identical to the XLA scan — asymmetric row
        counts and a non-divisible shard axis included."""
        from pilosa_tpu.ops import kernels
        import jax.numpy as jnp

        rng = np.random.default_rng(21)
        S, Ra, Rb, W = 5, 12, 24, 256
        a = jnp.asarray(
            rng.integers(0, 2**32, size=(S, Ra, W), dtype=np.uint64).astype(
                np.uint32
            )
        )
        b = jnp.asarray(
            rng.integers(0, 2**32, size=(S, Rb, W), dtype=np.uint64).astype(
                np.uint32
            )
        )
        want = np.asarray(kernels.cross_gram_xla(a, b))
        got = np.asarray(
            kernels._cross_gram_pallas(
                a, b, sb=kernels._gram_pallas_sb(a.shape[0]), wb=128
            )
        )
        assert np.array_equal(got, want)

    def test_combo_gate_requires_both_sides_wide(self):
        """combo_counts_gram must not route through the 'fused' variant
        when either side is below cross_gram_traced's floor — a pure-XLA
        trace would falsely prove the Pallas gate."""
        from pilosa_tpu.ops import kernels
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        S, C, Rl, W = 2, 4, 16, 256  # C < 8: must take the plain path
        prefix = jnp.asarray(
            rng.integers(0, 2**32, size=(C, S, W), dtype=np.uint64).astype(
                np.uint32
            )
        )
        bits = jnp.asarray(
            rng.integers(0, 2**32, size=(S, Rl, W), dtype=np.uint64).astype(
                np.uint32
            )
        )
        # force eligibility so the routing itself is what the test
        # enforces (off-TPU the eligibility gate is always False and the
        # guard would be vacuous)
        from unittest import mock

        with mock.patch.object(
            kernels, "_gram_pallas_eligible", lambda *a: True
        ), mock.patch.object(
            kernels,
            "_with_gram_fallback",
            side_effect=AssertionError(
                "C < 8 must not take the fused cross-gram path"
            ),
        ):
            out = kernels.combo_counts_gram(prefix, bits, list(range(Rl)))
        want = (
            np.asarray(kernels.combo_counts(prefix, bits, jnp.arange(Rl)))
            .astype(np.int64)
            .sum(axis=2)
        )
        assert np.array_equal(out, want)


class TestGramGatePolicy:
    """_with_gram_fallback's probe/demote contract: a failed probe
    demotes immediately (with a log); past the probe, transients
    survive and MAX_FAILS lifetime failures demote."""

    def _gate(self):
        return kernels._PallasGate()

    def test_probe_failure_tolerated_then_demotes(self):
        """A transient failure on the first-ever call must NOT demote
        permanently (it gets the same MAX_FAILS tolerance as a proven
        kernel); a persistently failing probe demotes after the bounded
        re-probes."""
        gate = self._gate()

        def boom():
            raise RuntimeError("mosaic says no")

        for i in range(gate.MAX_FAILS - 1):
            out = kernels._with_gram_fallback(boom, lambda: "xla", gate=gate)
            assert out == "xla"
            assert gate.ok is None  # still unproven, not demoted
        out = kernels._with_gram_fallback(boom, lambda: "xla", gate=gate)
        assert out == "xla"
        assert gate.ok is False  # bounded re-probes exhausted

    def test_probe_transient_then_success_proves_gate(self):
        gate = self._gate()

        def boom():
            raise RuntimeError("transient OOM at startup")

        assert kernels._with_gram_fallback(boom, lambda: "x", gate=gate) == "x"
        assert gate.ok is None
        out = kernels._with_gram_fallback(
            lambda: jnp.zeros(()), lambda: "x", gate=gate
        )
        assert out is not None and gate.ok is True

    def test_established_gate_survives_transients_then_demotes(self):
        gate = self._gate()
        ok = lambda: jnp.zeros(())
        assert kernels._with_gram_fallback(ok, lambda: "x", gate=gate) is not None
        assert gate.ok is True

        def boom():
            raise RuntimeError("transient OOM")

        for i in range(gate.MAX_FAILS - 1):
            assert (
                kernels._with_gram_fallback(boom, lambda: "x", gate=gate)
                == "x"
            )
            assert gate.ok is True  # transients survive
        assert kernels._with_gram_fallback(boom, lambda: "x", gate=gate) == "x"
        assert gate.ok is False  # lifetime cap reached

    def test_deferred_leaves_only_a_proven_kernel_unawaited(self, monkeypatch):
        """The GroupBy lane's launches: an unproven gate is probed and
        awaited as ever, so a failing probe still meets the fallback; a
        proven one is enqueued and left to its caller's pull."""
        gate, waited = self._gate(), []
        inner = kernels.wait
        monkeypatch.setattr(
            kernels, "wait", lambda out, k="": waited.append(k) or inner(out, k)
        )
        ok = lambda: jnp.zeros(())
        assert kernels._with_gram_fallback(
            ok, lambda: "x", gate=gate, kernel="probe", deferred=True
        ) is not None
        assert gate.ok is True and waited == ["probe"]
        kernels._with_gram_fallback(
            ok, lambda: "x", gate=gate, kernel="proven", deferred=True
        )
        assert waited == ["probe"]
        kernels._with_gram_fallback(ok, lambda: "x", gate=gate, kernel="proven")
        assert waited == ["probe", "proven"]

    def test_gates_are_independent(self):
        g1, g2 = self._gate(), self._gate()

        def boom():
            raise RuntimeError("no")

        for _ in range(g1.MAX_FAILS):
            kernels._with_gram_fallback(boom, lambda: "x", gate=g1)
        assert g1.ok is False
        assert g2.ok is None and g2.fails == 0  # one kernel's probe
        # never condemns another
